"""Layer: models/attention.py. Device time on the first device, per
`mtpu/serve/step` span of the traced window, of every operation whose text
holds an array of the contiguous KV pool's shape (`KVCache.k`): the stack as
the engine holds it, [layers, slots, max_len, kv heads, head dim], a layer of
it, or the view of blocks the decode kernel is handed, [layers * slots *
blocks, rows * kv heads, head dim] (`ops/block_attention_pallas.py`). Those
are a decode step's in-place write of each slot's new row, its attention over
the pool (the dot path's scores and weighted sum over a layer read whole, or
the kernel's one call), and a prefill's copy of its finished sequences into
their slots; decode and prefill programs together. An axis of 1 (Falcon's
one kv head) may be missing from the text.

The shapes come from the configuration (the pool the program's own
`init_kv_caches` would build from its `cli`) and the mix (`num_slots`,
`max_len`), the block's rows from the program's own rule; no operation's name
is written down. `None` where the pool is of another kind (latent rows, rings
and regions), the trace is not a TPU's, or no operation holds such an array;
a program without the kernel (a parent commit) has no rule to ask and is read
by the first two shapes."""
import re

from benchmark.program_spans import count_in, on_tpu


def pool_shapes(config, serving) -> list:
    """The shapes to look for, or [] where the pool is not a `KVCache`."""
    import jax
    from megatron_tpu.arguments import parse_cli
    from megatron_tpu.inference.generation import init_kv_caches
    from megatron_tpu.models.attention import KVCache

    cfg, _ = parse_cli([*config["cli"], "--bf16"], n_devices=1)
    pool = jax.eval_shape(lambda: init_kv_caches(
        cfg.model, serving["num_slots"], serving["max_len"],
        per_slot_offsets=True))
    if not isinstance(pool, KVCache):
        return []
    layers, slots, positions, nkv, hd = pool.k.shape
    shapes = [pool.k.shape, pool.k.shape[1:]]
    try:
        from megatron_tpu.ops.block_attention_pallas import pool_block_rows
    except ImportError:
        return shapes
    rows = pool_block_rows(pool.k.shape, pool.k.dtype, per_slot=True,
                           queries=(slots, 1, cfg.model.num_attention_heads),
                           window=cfg.model.sliding_window is not None,
                           mesh=False, backend="tpu")
    if rows:
        shapes.append((layers * slots * (positions // rows), rows * nkv, hd))
    return shapes


def read(run):
    serving = run.ctx.traffic.get("serving")
    if not on_tpu(run.trace) or not serving:
        return None
    shapes = pool_shapes(run.ctx.config, serving)
    if not shapes:
        return None
    # an axis of 1 may be gone from the text (the compiler carries Falcon's
    # pool of one kv head as [layers, slots, max_len, head dim]) or stand
    # before a layer cut out with its axis kept
    holds = re.compile(r"\[(1,)?(" + "|".join(
        "".join("(1,)?" if d == 1 else f"{d}," for d in s[:-1]) + str(s[-1])
        for s in shapes) + r")\]")
    seconds = run.trace.seconds_where(lambda text: bool(holds.search(text)))
    steps = count_in(run.trace, "mtpu/serve/step")
    if not seconds or not steps:
        return None
    return 1e3 * seconds / steps
