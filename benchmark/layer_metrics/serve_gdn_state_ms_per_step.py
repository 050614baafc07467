"""Layer: serving/kv_pool.py. Device time on the first device, per
`mtpu/serve/step` span of the traced window, of every operation whose text
holds an array of the pool's Gated DeltaNet state, a layer of it or a slot
of it (`benchmark/gdn_kinds.py`, "state"; the chunk kernel's own calls
excepted): a decode step's read and in-place write of a matrix a value head
a slot, a chunk's landing; a copy of the whole state that creeps in shows
here. `None` where the configuration has no linear-attention layers, the
trace is not a TPU's, or no operation holds such an array (a parent
commit)."""
from benchmark.gdn_kinds import ms_per_step


def read(run):
    return ms_per_step(run, "state")
