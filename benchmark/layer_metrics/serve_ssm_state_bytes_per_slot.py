"""Layer: serving/kv_pool.py. The bytes of selective-scan state one slot
holds whatever its sequence's length, as the pool itself counts them
(`SlotKVPool.ssm_state_nbytes()`, which the engine puts in its metrics'
snapshot as `ssm_state_bytes`; the driver divides by the slots): 26 layers
x 16 x 5,120 float32 = 8,519,680 in `jamba2-3b.serve-longdoc-32k`. `None`
from a driver that does not copy it, or a pool without such a state."""


def read(run):
    return run.samples.get("ssm_state_bytes_per_slot") or None
