"""Layer: models/attention.py. Device time on the first device, per
`mtpu/serve/step` span of the traced window, of every operation whose text
holds an array of the full layers' WHOLE REGIONS (`benchmark/kv_kinds.py`
says which shapes those are): a decode step's in-place write of each slot's
new row, its scores and weighted sum over a layer of regions read whole, and
a prefill's or a chunk's write of its rows, its flash kernel over the region
up to the chunk's end and the copy of the finished sequence into its slot.
Decode and prefill programs together. `None` where the configuration has one
kind of layer, the trace is not a TPU's, or no operation holds such an array
(a parent commit)."""
from benchmark.kv_kinds import ms_per_step


def read(run):
    return ms_per_step(run, 1)
