"""Layer: serving/kv_pool.py. The bytes of convolution state one slot holds
whatever its sequence's length, as the pool itself counts them
(`SlotKVPool.conv_state_nbytes()`, which the engine puts in its metrics'
snapshot as `conv_state_bytes`; the driver divides by the slots): 10 layers
x 2 x 2,048 bf16 = 81,920 in `lfm2-8b-a1b.serve-chat-2k`. `None` from a
driver that does not copy it, or a pool without a state."""


def read(run):
    return run.samples.get("state_bytes_per_slot") or None
