"""Layer: serving/kv_pool.py. `serve_ssm_conv_state_ms_per_step` for a pool
beside Mamba-2 layers (that reader's `ssm_kinds.patterns` answers only a
configuration with `attn_layer_period`): device time on the first device,
per `mtpu/serve/step` span of the traced window, of every operation whose
text holds an array of the depthwise kernel's last inputs over x, B and C as
the pool holds them, a layer or a slot of them (`benchmark/ssd_kinds.py`,
"conv"). `None` where the configuration has no Mamba-2 layers, the trace is
not a TPU's, or no operation holds such an array (a parent commit)."""
from benchmark.ssd_kinds import ms_per_step


def read(run):
    return ms_per_step(run, "conv")
