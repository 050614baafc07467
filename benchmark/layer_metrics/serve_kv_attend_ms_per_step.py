"""Layer: models/attention.py. `serve_pool_attend_ms_per_step` for a pool
whose keys and values are the attention layers' alone, beside a convolution
state (`ConvKVCache`, which that reader's `isinstance(pool, KVCache)` turns
away): device time on the first device, per `mtpu/serve/step` span of the
traced window, of every operation whose text holds an array of the pool's
keys or values, [attention layers, slots, max_len, kv heads x head dim], or a
layer of them (`benchmark/conv_kinds.py`, "kv"). `None` where the
configuration has no convolution layers, the trace is not a TPU's, or no
operation holds such an array."""
from benchmark.conv_kinds import ms_per_step


def read(run):
    return ms_per_step(run, "kv")
