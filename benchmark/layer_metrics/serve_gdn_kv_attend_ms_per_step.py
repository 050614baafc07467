"""Layer: models/attention.py. Device time on the first device, per
`mtpu/serve/step` span of the traced window, of every operation whose text
holds the keys and values of a pool that keeps them BESIDE a Gated
DeltaNet state (`benchmark/gdn_kinds.py`, "kv": the attention layers'
folded rows, a layer, a slot or a one-sequence cache of them) and of the
flash kernel that reads a slot's folded rows at a chunk's offset
(`_flash_attention_offset`): a chunk's attention over offset + 4,096
positions of 2 kv heads, a decode step's scores and weighted sums over the
folded rows, and the in-place writes of the new rows. `None` where the
configuration has no linear-attention layers, the trace is not a TPU's, or
no operation holds such an array (a parent commit)."""
from benchmark.gdn_kinds import ms_per_step


def read(run):
    return ms_per_step(run, "kv")
