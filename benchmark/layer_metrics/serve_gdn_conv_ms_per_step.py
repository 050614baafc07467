"""Layer: serving/kv_pool.py. Device time on the first device, per
`mtpu/serve/step` span of the traced window, of every operation whose text
holds the linear-attention layers' depthwise kernel's last inputs as the
pool holds them, a layer or a slot of them (`benchmark/gdn_kinds.py`,
"conv"): the read ahead of a call's rows and the write behind its last real
row. `None` where the configuration has no linear-attention layers, the
trace is not a TPU's, or no operation holds such an array (a parent
commit)."""
from benchmark.gdn_kinds import ms_per_step


def read(run):
    return ms_per_step(run, "conv")
