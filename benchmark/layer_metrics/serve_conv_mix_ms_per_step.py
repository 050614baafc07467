"""Layer: models/short_conv.py. Device time on the first device, per
`mtpu/serve/step` span of the traced window, of every operation whose text
holds an array whose minor extent is 3 x hidden: the convolution layers'
first product, what is fused with it and the weight's own slices or copies
(`benchmark/conv_kinds.py`, "mix"). `None` where the configuration has no
convolution layers, the trace is not a TPU's, or no operation holds such an
array."""
from benchmark.conv_kinds import ms_per_step


def read(run):
    return ms_per_step(run, "mix")
