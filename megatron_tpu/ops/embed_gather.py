"""The token gather of a served program, reading the word embedding table
where it lies.

A `[vocabulary, hidden]` table whose hidden extent is not a multiple of 128
lies on a TPU vocabulary-minor (`{0,1:T(8,128)}`): the chip tiles an array
along the extent that fills its 128 lanes without padding, and Falcon-7B's
4,544 is 35.5 lane tiles where its 65,024 words are 508. XLA's gather wants
a token's row contiguous, so `emb[tokens]` first copies the WHOLE table
into row-major order (1.77 GB moved, 2.99 ms of every decode step and every
prefill, to read 64 rows of it; every form that asks XLA for rows does the
same: a `dynamic_slice` a row, `jnp.take` on the transpose, a barrier
between gather and cast; a bf16 table too. Compiled for v5e, ISSUE 53).

`lane_block_gather` asks for no rows. `emb.T` of such a table is a bitcast
(`[hidden, vocabulary]{1,0:T(8,128)}`), token t's row is column t of it,
and the lane-aligned block `[hidden, 128]` that holds the column is a
strided DMA of whole tiles. The kernel's grid is the tokens, the block a
step fetches is a prefetched scalar (`ids[i] // 128`, as
`ops/block_attention_pallas.py` chooses its KV blocks), and the step picks
lane `ids[i] % 128` with a one-hot product on the matrix unit, which also
turns the column into a row. Consecutive tokens in one lane block (a
bucket's padding, a grid's idle rows) fetch it once: the pipeline skips a
block it already holds.

`reads_lane_blocks` says which gathers take it: one rule of what the call
can see (a cache, a mesh, the backend, shapes and dtypes), asked by
`embed_tokens` as `model_forward` traces; no option sets it. Everything
else keeps `emb[tokens]` and the program it had.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# the one-hot operand's rows: the matrix unit's product wants a whole
# sublane tile, and row 0 is the one kept
PICK_ROWS = 8


def _gather_kernel(ids_ref, blk_ref, o_ref):
    lane = ids_ref[pl.program_id(0)] % LANES
    # rounded FIRST (round to nearest even, what `astype` does), picked
    # after: a product of the rounded block with a one-hot row adds one
    # value to zeros in a float32 accumulator, which is exact. Picking from
    # the float32 block would round the product's operand on the way in
    blk = blk_ref[...].astype(o_ref.dtype)                   # [hidden, 128]
    pick = (jax.lax.broadcasted_iota(jnp.int32, (PICK_ROWS, LANES), 1)
            == lane).astype(o_ref.dtype)
    # DEFAULT said here, whatever the caller has set as JAX's default:
    # Mosaic refuses "highest" on bf16 operands, and one bf16 pass is exact
    rows = jax.lax.dot_general(
        pick, blk, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32)                  # [8, hidden]
    o_ref[...] = rows[:1].astype(o_ref.dtype)


def lane_block_gather(table, ids, *, interpret: bool | None = None):
    """`table` [vocabulary, hidden] (float32 or bfloat16), `ids` [n] int ->
    [n, hidden] bfloat16, bit for bit `table[ids].astype(bfloat16)` for
    every id in range and every finite table (the product multiplies the
    block's other 127 lanes by zero). An id out of range reads the nearest
    row, as a clamped gather does.

    The operand is `table.T`, which moves nothing where the table lies
    vocabulary-minor (`reads_lane_blocks` (c)); anywhere else the compiler
    would transpose the table to make it, which is why the rule is asked
    first."""
    vocab, hidden = table.shape
    assert vocab % LANES == 0, (vocab, LANES)
    n = ids.shape[0]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    ids = jnp.clip(ids.astype(jnp.int32), 0, vocab - 1)
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=[pl.BlockSpec((hidden, LANES),
                                   lambda i, ids: (0, ids[i] // LANES))],
            # [n, 1, hidden]: a block of one row is whole in its last two
            # extents, which a block of [n, hidden]'s rows is not
            out_specs=pl.BlockSpec((None, 1, hidden),
                                   lambda i, ids: (i, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((n, 1, hidden), jnp.bfloat16),
        interpret=interpret,
        name="embed_gather",
    )(ids, table.T)
    return out.reshape(n, hidden)


# (d)'s edge: the blocks' bytes over the copy's bytes where the two times
# cross. Timed alone at Falcon-7B's widths (my chip run, PR 53, float32
# table): a block of 2.33 MB in 3.13 us at every row count from 256 up, 0.91
# of the memory's 819 GB/s (64 rows 0.24 ms, 512 rows 1.60, 768 rows 2.41,
# 1,024 rows 3.20); the copy's 1.77 GB in 2.67-2.73 ms, 0.81 of it (2.99 ms
# inside a served program). 0.91 / 0.81: 850 rows, so a prefill of 768 rows
# reads blocks and two prompts of 512 copy
COPY_EDGE = 1.12


def reads_lane_blocks(shape, table_dtype, dtype, *, rows: int, cached: bool,
                      mesh: bool, backend: str | None = None) -> bool:
    """Whether a gather of `rows` rows (batch x padded length) in `dtype`
    from a `[vocabulary, hidden]` table of `shape` and `table_dtype` reads
    lane blocks (`lane_block_gather`) or stays `emb[tokens]`."""
    vocab, hidden = shape
    if backend is None:
        backend = jax.default_backend()
    # the layout below is a TPU's, and the kernel is timed there and
    # interpreted anywhere else: off the chip only a test takes it
    # (`backend="tpu"`), as `pool_block_rows`
    if backend != "tpu":
        return False
    # (a) a served program (it carries a cache: decode, prefill, chunk,
    # verify), as `wcast(read_once=True)`. A training step differentiates
    # the gather, and its rows are over (d) anyway
    if not cached:
        return False
    # (b) the table whole on one device (`active_kernel_mesh`, what the
    # block attention kernel asks): XLA cannot partition the custom call,
    # and a table sharded over 'vocab' has its own gather (mask the ids
    # outside the shard, all-reduce)
    if mesh:
        return False
    # (c) the table lies vocabulary-minor, so `emb.T` is a bitcast and
    # `emb[tokens]` copies the table whole: the chip tiles the extent that
    # fills its 128 lanes without padding, which is the vocabulary where
    # hidden is not a multiple of 128 and the vocabulary is
    # (tests/test_tpu_compile.py holds this proxy to the compiler). A
    # hidden of whole lane tiles (every other configuration's) lies
    # row-major, and its gather reads rows in place
    if hidden % LANES == 0 or vocab % LANES:
        return False
    # the lane's pick is exact as a bf16 product with a float32
    # accumulator, and that is the form compiled and timed
    if jnp.dtype(dtype) != jnp.bfloat16 \
            or jnp.dtype(table_dtype) not in (jnp.float32, jnp.bfloat16):
        return False
    # (d) the bytes: a block of 128 rows a token against the copy's own
    # traffic (the table read, and written in `dtype`)
    itemsize = jnp.dtype(table_dtype).itemsize
    blocks = rows * hidden * LANES * itemsize
    copy = vocab * hidden * (itemsize + jnp.dtype(dtype).itemsize)
    return blocks < COPY_EDGE * copy


def embed_tokens(table, tokens, dtype, *, cached: bool):
    """`table[tokens].astype(dtype)`, [*tokens.shape, hidden]: through the
    lane blocks where `reads_lane_blocks` says so, bit for bit the same."""
    from megatron_tpu.parallel.sharding import active_kernel_mesh
    if not reads_lane_blocks(table.shape, table.dtype, dtype,
                             rows=tokens.size, cached=cached,
                             mesh=active_kernel_mesh() is not None):
        return table[tokens].astype(dtype)
    with jax.named_scope("mtpu/embed/gather"):
        rows = lane_block_gather(table, tokens.reshape(-1))
    return rows.reshape(*tokens.shape, table.shape[1])
