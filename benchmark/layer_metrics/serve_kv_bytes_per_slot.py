"""Layer: serving/kv_pool.py. Bytes one slot of the KV pool reserves, by the
pool's own count (`SlotKVPool.bytes_per_slot()`, which the engine puts in its
metrics' snapshot as `kv_bytes_per_slot` beside `kv_ring_bytes` and
`kv_full_bytes`, and the driver copies into its samples). In
`command-a-plus.serve-longdoc-32k` a slot is three rings of 4,096 rows and one
whole region of 32,768, 4,096 B a row: 184,549,376; held as one kind, four
regions: 536,870,912. `None` where the program has no such counter (a parent
commit)."""


def read(run):
    return run.samples.get("kv_bytes_per_slot") or None
