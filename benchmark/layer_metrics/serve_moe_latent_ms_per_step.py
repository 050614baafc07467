"""Layer: models/moe.py. Device time on the first device, per
`mtpu/serve/step` span of the traced window, of every operation whose text
holds the stack of one of the latent's two projections, [expert layers,
hidden, latent] or [expert layers, latent, hidden] (`benchmark/
ssd_kinds.py`, "latent"): the products the program makes under
`mtpu/moe/latent_in` and `mtpu/moe/latent_out`, round the routed experts.
`None` where the configuration has no Mamba-2 layers or no latent, the
trace is not a TPU's, or no operation holds such an array (a parent
commit)."""
from benchmark.ssd_kinds import ms_per_step


def read(run):
    return ms_per_step(run, "latent")
