"""Layer: serving/kv_pool.py. The bytes of Mamba-2 state one slot holds
whatever its sequence's length, as the pool itself counts them
(`SlotKVPool.ssd_state_nbytes()`, which the engine puts in its metrics'
snapshot as `ssd_state_bytes`; the driver divides by the slots): 5 layers x
128 heads x 64 x 128 float32 = 20,971,520 in
`nemotron-3-super.serve-agent-8k`. `None` from a driver that does not copy
it, or a pool without such a state."""


def read(run):
    return run.samples.get("ssd_state_bytes_per_slot") or None
