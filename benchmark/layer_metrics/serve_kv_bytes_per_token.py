"""Layer: serving/kv_pool.py. Bytes one cached token costs across the layers,
by the pool's own count (`SlotKVPool.bytes_per_token()`, which the engine
puts in its metrics' snapshot as `kv_bytes_per_token` and the driver copies
into its samples): row width x itemsize x layers. 576 x 2 x 5 = 5,760 for the
latent pool of `joyai-llm-flash-5l`, where 32 heads of 192 + 128 would cost
102,400. `None` where the program has no such counter (a parent commit)."""


def read(run):
    return run.samples.get("kv_bytes_per_token") or None
