"""Layer: serving/kv_pool.py. `serve_conv_state_ms_per_step` for a pool
beside Mamba layers (that reader's `conv_kinds.patterns` answers only a
configuration with convolution layers): device time on the first device, per
`mtpu/serve/step` span of the traced window, of every operation whose text
holds an array of the depthwise kernels' last inputs as the pool holds them,
a layer or a slot of them (`benchmark/ssm_kinds.py`, "conv"). `None` where
the configuration has no Mamba layers, the trace is not a TPU's, or no
operation holds such an array (a parent commit)."""
from benchmark.ssm_kinds import ms_per_step


def read(run):
    return ms_per_step(run, "conv")
