"""Layer: models/hyper_connections.py. Device time on the first device, per
`mtpu/serve/step` span of the traced window, of every operation whose text
holds the residual of `hc_mult` streams (an array whose minor extent is
hc_mult x hidden) and none of the maps' arrays (`benchmark/hc_kinds.py`,
"mix"): H_pre X, H_res X + H_post^T out, the expand, the collapse, the norm's
pass over the streams, and any copy or transposition of the streams that
creeps in. `None` where the configuration has no residual of streams, the
trace is not a TPU's, or no operation holds such an array (a parent
commit)."""
from benchmark.hc_kinds import ms_per_step


def read(run):
    return ms_per_step(run, "mix")
