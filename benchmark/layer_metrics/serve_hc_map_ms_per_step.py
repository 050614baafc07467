"""Layer: models/hyper_connections.py. Device time on the first device, per
`mtpu/serve/step` span of the traced window, of every operation whose text
holds the maps' weight `[hc_mult x hidden, hc_mult^2 + 2 hc_mult]` or a
float32 plane of the maps with the tokens minor (`benchmark/hc_kinds.py`,
"map"): the product with phi, the two sigmoids, the exponential, the Sinkhorn
rounds. `None` where the configuration has no residual of streams, the trace
is not a TPU's, or no operation holds such an array (a parent commit)."""
from benchmark.hc_kinds import ms_per_step


def read(run):
    return ms_per_step(run, "map")
