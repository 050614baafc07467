"""Layer: serving/kv_pool.py. The bytes of Gated DeltaNet state one slot
holds whatever its sequence's length, as the pool itself counts them
(`SlotKVPool.gdn_state_nbytes()`, which the engine puts in its metrics'
snapshot as `gdn_state_bytes`; the driver divides by the slots): 6 layers x
32 value heads x 128 x 128 float32 = 12,582,912 in
`qwen3-next-80b-a3b.serve-longdoc-32k`. `None` from a driver that does not
copy it, or a pool without such a state."""


def read(run):
    return run.samples.get("gdn_state_bytes_per_slot") or None
