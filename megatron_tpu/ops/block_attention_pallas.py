"""Pallas TPU decode attention over a KV pool read where it lies, block by
block: each slot's blocks up to its live length, and nothing past them.

Two callers, one kernel (`block_native_attention`):

- the block-granular ARENA (`--kv_block_size` + `--block_native_attn`,
  serving/kv_pool.py): `[L, total_blocks, B, nkv, hd]` and a per-slot map of
  physical blocks. vLLM's PagedAttention showed that the attention kernel
  can consume the block map directly; here each grid step's physical
  block (`map[slot, j]`) is a prefetched scalar that the k/v BlockSpec's
  index map reads, so block indices are data and one compile serves every
  assignment;
- the CONTIGUOUS slot pool (`KVCache.k`, `[L, slots, max_len, nkv, hd]`),
  which is the same thing under the identity chain
  (`contiguous_pool_attention`): rows before heads, so a slot's region is
  `max_len / B` blocks of B rows by a reshape that moves nothing, and slot
  s's j-th block is `s * nb + j`. `pool_block_rows` says which pools take
  it and at what B; every other one stays on `_dot_attention`.

The operand is the pool STACKED over layers with the layer's index as one
more prefetched scalar, as `ops/grouped_matmul.py` takes the expert banks:
a Pallas call cannot read a dynamic slice in place, so it is never given
one. The stack goes in as `[L * T, B * nkv, hd]`: only LEADING axes are
merged (the layers with the blocks, a block's rows with the kv heads), which
is free in the chip's tiled layout wherever `nkv` fills the tile's rows.
Folding the heads into the lanes, `[.., B, nkv * hd]`, is free in row-major
order and not in the tiled one (the compiler copies the pool to make it).

- the grid is the LIVE blocks and nothing else, one after the other: a
  slot's blocks up to the one its last query sees, a parked row's first.
  Which slot and which block a step is, and how many steps there are, are
  data (prefetched scalars and a dynamic grid, as megablox's group
  metadata), so a 3-block slot in a 32-block region pays 3 block reads and
  3 grid steps, the next slot's first block is fetched under this slot's
  last, and TPU's sequential grid lets VMEM scratch carry the
  FlashAttention-2 online-softmax state (m, l, acc) across a slot's chain.
  A grid of (slot, block) with dead steps skipped (`pl.when`, and the index
  map re-addressing the last live block) paid a third of a microsecond for
  each of the 384 steps a layer (256 rows a block) and waited for every
  slot's first block: 0.84 ms a decode step of OLMoE's cell against 0.55
  (my chip run, PR 36).
- a block's `B * nkv` (row, kv head) pairs are the KEYS of one product: all
  `nkv * g * w` query rows against all of them, `[G, hd] x [hd, B * nkv]`,
  and a query row keeps the pairs of its own kv head, at positions it may
  see. One product for the scores and one for the weighted sum a block,
  whatever the head count, with the block as the matrix unit's stationary
  operand both times; the products of a row with the other heads' pairs are
  masked away (nkv times the operations, of a unit a decode step leaves
  idle), and nothing is sliced, transposed or assembled by head. Operands
  in q's dtype (bf16 in a served model: the dot path's `astype(dtype)`),
  accumulation and softmax in float32.
- queries per slot w >= 1: w == 1 is plain decode; w == k+1 is the
  speculative-decode verify window (causal within the window, each query
  masked from its own position `length + j`).
- int8 pools dequantize IN KERNEL: the per-(token, head) float32 scales
  multiply the scores (k's) and the probabilities (v's) along the same
  (row, kv head) axis, so the payload reaches the products as it is held.
- idle rows (length 0) read their first block and attend position 0: finite
  garbage, discarded by the engine like every idle-row compute.

The kernel body uses only ops the interpret path supports, so the SAME
kernel runs under `interpret=True` on CPU (tier-1 tests, the arena engine's
CPU fallback); tests/test_tpu_compile.py compiles it for the chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# exp clamp for rows fully masked within one live block (a verify
# window's earliest query sees nothing in a block the window's LAST
# query made live) — same trick as flash_attention_pallas.MASK_CLAMP
MASK_CLAMP = -1e20
# per-row online-softmax stats carry a small trailing lanes dim so the
# VMEM scratch tiles on TPU (same trick, same constant rationale, as
# flash_attention_pallas.STAT_LANES)
STAT_LANES = 8


def _bn_kernel(slot_ref, blk_ref, phys_ref, first_ref, len_ref, q_ref, k_ref,
               v_ref, *refs, scale, rows, nb, nkv, gw, w, quant):
    # refs: [ks_ref, vs_ref]? o_ref, m_ref, l_ref, acc_ref: the int8 scale
    # blocks are inputs only when the pool is quantized, so the bf16 path
    # pays no DMA for them
    del phys_ref, first_ref      # the index maps' (which block to fetch)
    refs = list(refs)
    ks_ref = vs_ref = None
    if quant:
        ks_ref, vs_ref = refs[0], refs[1]
        refs = refs[2:]
    o_ref, m_ref, l_ref, acc_ref = refs
    step = pl.program_id(0)
    si, j = slot_ref[step], blk_ref[step]     # this step: block j of slot si
    length = len_ref[si]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]                                   # [G, hd]
    G, N = q.shape[0], rows * nkv
    # key c of the block is (row c // nkv, kv head c % nkv), as the
    # pool holds them; query row r is (kv head r // gw, then group and
    # query: the query's index in the window is r % w), so it sits at
    # position length + r % w. Decode (w == 1): every row at `length`
    key = jax.lax.broadcasted_iota(jnp.int32, (G, N), 1)
    qrow = jax.lax.broadcasted_iota(jnp.int32, (G, N), 0)
    q_pos = length + jax.lax.rem(qrow, w)
    keep = q_pos >= j * rows + jax.lax.div(key, nkv)  # causal, and the
    if nkv > 1:                                       # partial tail
        keep &= jax.lax.rem(key, nkv) == jax.lax.div(qrow, gw)

    def operand(ref):
        x = ref[...]                                  # [N, hd]
        if x.dtype == jnp.int8:    # exact in any float; no int8->bf16
            x = x.astype(jnp.float32)                 # convert on v5e
        return x.astype(q.dtype)

    # said here, whatever the caller has set as JAX's default: Mosaic
    # refuses "highest" on bf16 operands, and float32 ones (tests, a
    # float32 model) are multiplied as float32
    precision = (jax.lax.Precision.HIGHEST if q.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    s = jax.lax.dot_general(
        q, operand(k_ref), (((1,), (1,)), ((), ())), precision=precision,
        preferred_element_type=jnp.float32) * scale   # [G, N]
    if quant:
        s = s * ks_ref[...]                           # [1, N]
    s = jnp.where(keep, s, NEG_INF)

    m_prev = m_ref[:, :1]                             # [G, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    # MASK_CLAMP: a verify window's earliest query can be fully
    # masked in a block only its later queries made live:
    # exp(NEG_INF - NEG_INF) == 1 would attend those masked keys
    p = jnp.exp(s - jnp.maximum(m_new, MASK_CLAMP))
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    if quant:
        p = p * vs_ref[...]
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(q.dtype), operand(v_ref), (((1,), (0,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32)           # [G, hd]
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(j == jnp.minimum((length + w - 1) // rows, nb - 1))
    def _finalize():
        l = l_ref[:, :1]
        o_ref[...] = (acc_ref[...] / jnp.where(l > 0.0, l, 1.0)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def block_native_attention(q, k, v, block_map, lengths, *, scale: float,
                           layer=None, k_scale=None, v_scale=None,
                           interpret: bool | None = None):
    """Per-slot q against block-chained K/V, straight out of the pool.

    q:          [S, w, nq, hd]  (post-rope queries; w == 1 for decode,
                                 w == k+1 for the speculative verify
                                 window, causal within the window)
    k/v:        [L, T, B, nkv, hd] with `layer` (a traced scalar), the
                                 pool stacked over layers: T blocks of B
                                 rows a layer (int8 for quantized pools);
                                 or one layer's [T, B, nkv, hd]
    block_map:  [S, nb] int32    logical -> physical block per slot
    lengths:    [S] int32        first query's position per slot (the
                                 slot's pre-append token count); the
                                 slot's own k/v for the window must
                                 already be WRITTEN into the pool
                                 (write-before-read, like the dot path)
    k_scale/v_scale: [T, B, nkv, 1] fp32, int8 pools only: the LAYER's
                                 (a thirty-second of its payload at 128
                                 channels a head, and no order in which
                                 the stack could be read in place)

    Returns [S, w, nq, hd] in q's dtype. Rolling (ring) layouts are NOT
    supported: their slot->position map breaks the contiguous position
    arithmetic."""
    S, w, nq, hd = q.shape
    if layer is None:            # one layer's pool: a stack of one
        k, v, layer = k[None], v[None], 0
    L, T, B, nkv, _ = k.shape
    nb = block_map.shape[1]
    assert nq % nkv == 0, (nq, nkv)
    gw = nq // nkv * w
    quant = k_scale is not None
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    G, N = nq * w, B * nkv

    # query rows (kv head, group, query)-major, [S, G, hd]: the same
    # h -> h // g mapping as _dot_attention's reshape
    qg = q.reshape(S, w, nkv, nq // nkv, hd).transpose(0, 2, 3, 1, 4) \
        .reshape(S, G, hd)

    # the grid is the LIVE blocks, one after the other: a slot's blocks are
    # those that ANY of its queries can see (its last sits at position
    # length + w - 1; what starts past it is other requests' KV, a request
    # long gone, or free-list garbage), and a parked row's is its first.
    # Which slot and which of its blocks a step is, and where that block
    # lies in the stack, are data (prefetched scalars), and so is the
    # number of steps: nothing is fetched or computed for a dead block
    lengths = lengths.astype(jnp.int32)
    live = jnp.minimum((lengths + w - 1) // B, nb - 1) + 1           # [S]
    ends = jnp.cumsum(live)
    step = jnp.arange(S * nb, dtype=jnp.int32)
    slot = jnp.minimum(jnp.searchsorted(ends, step, side="right",
                                        method="compare_all"),
                       S - 1).astype(jnp.int32)
    blk = jnp.clip(step - (ends - live)[slot], 0, nb - 1)
    phys = block_map.astype(jnp.int32)[slot, blk]    # within the layer
    first = (jnp.asarray(layer, jnp.int32) * T).reshape(1)

    def kv_at(t, slot_ref, blk_ref, phys_ref, first_ref, len_ref):
        return first_ref[0] + phys_ref[t], 0, 0

    def scale_at(t, slot_ref, blk_ref, phys_ref, first_ref, len_ref):
        return phys_ref[t], 0, 0

    def q_at(t, slot_ref, *_):
        return slot_ref[t], 0, 0

    kv_spec = pl.BlockSpec((None, N, hd), kv_at)
    in_specs = [pl.BlockSpec((None, G, hd), q_at), kv_spec, kv_spec]
    # layers with blocks, rows with kv heads: leading axes only, nothing
    # moves (the module's docstring)
    inputs = [qg, k.reshape(L * T, N, hd), v.reshape(L * T, N, hd)]
    if quant:
        sc_spec = pl.BlockSpec((None, 1, N), scale_at)
        in_specs += [sc_spec, sc_spec]
        inputs += [k_scale.reshape(T, 1, N), v_scale.reshape(T, 1, N)]

    out = pl.pallas_call(
        functools.partial(_bn_kernel, scale=scale, rows=B, nb=nb, nkv=nkv,
                          gw=gw, w=w, quant=quant),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(ends[-1],),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, G, hd), q_at),
            scratch_shapes=[pltpu.VMEM((G, STAT_LANES), jnp.float32),  # m
                            pltpu.VMEM((G, STAT_LANES), jnp.float32),  # l
                            pltpu.VMEM((G, hd), jnp.float32)]),        # acc
        out_shape=jax.ShapeDtypeStruct((S, G, hd), q.dtype),
        interpret=interpret,
    )(slot, blk, phys, first, lengths, *inputs)
    # [S, (kv head, group, query), hd] -> [S, w, nq, hd]
    return out.reshape(S, nkv, nq // nkv, w, hd).transpose(0, 3, 1, 2, 4) \
        .reshape(S, w, nq, hd)


# a block of k (and one of v) the kernel fetches at a time. At OLMoE's 4,096
# B a row, five rows live and nineteen parked (my chip run, PR 36, ms a
# decode step of four layers): 32 rows 0.57, 64 rows 0.43, 128 rows 0.43,
# 256 rows 0.55; every row full: 7.42, 4.99, 4.36, 4.35 (743 GB/s). A parked
# row and a slot's last block are read whole, so the smallest block that
# still reads at the memory's rate: 512 KiB
BLOCK_BYTES = 1 << 19
# ... and the most the scores of one slot's query rows against a block may
# take of the kernel's 16 MiB (OLMoE's block of 2,048 keys: 128 query rows)
SCORE_BYTES = 1 << 20


def pool_block_rows(shape, dtype, *, queries: int, per_slot: bool,
                    window: bool, mesh: bool, backend: str | None = None):
    """The rows B of a block, if a step over the contiguous pool `shape`
    (`KVCache.k`: [layers, slots, max_len, nkv, hd]) of `dtype`, with
    `queries` [slots, tokens a row, query heads], reads it through the
    kernel; None where it stays on `_dot_attention`. One rule of
    shapes, dtype, mesh and backend, asked by `attention_apply` as it traces
    and by the engine for its counters; no option sets it.

    OLMoE-1B-7B's pool (16 kv heads of 128 in bf16: 4,096 B a row) reads
    4,096-position regions in 32 blocks of 128 rows. Falcon-7B's (1 kv head
    of 64: 128 B a row) stays on the dot path twice over: see below."""
    _, _, max_len, nkv, hd = shape
    itemsize = jnp.dtype(dtype).itemsize
    if backend is None:
        backend = jax.default_backend()
    # the kernel is timed on the TPU and interpreted anywhere else: off the
    # chip only a test takes it (`backend="tpu"`), as `grouped_matmul`'s
    # `use_kernel`
    if backend != "tpu":
        return None
    # each slot at its own length is what there is to skip: a step at a
    # scalar offset (a prefill, a chunk) has its rows in one place and the
    # flash path or one batched product for them
    if not per_slot:
        return None
    # the kernel's mask is causal and its positions are the rows' indices:
    # a sliding window's band is not in it, and a rolling pool's rows are
    # not in time order (`kv_positions`)
    if window:
        return None
    # XLA cannot partition the custom call: under a mesh that shards the
    # heads (tp) or the rows (dp) the dot path is partitioned as it always
    # has been
    if mesh:
        return None
    # the operand is the pool with (row, kv head) merged, which moves
    # nothing only where the kv heads fill the rows of the chip's tile (8
    # sublanes of 32 bits: 8 float32, 16 bf16 or 32 int8 rows) and a head's
    # channels its 128 lanes. Falcon's 1 kv head of 64 fails both; so does
    # a GQA pool of 8 kv heads in bf16, which would be copied whole
    if hd % 128 or nkv % (8 * 4 // itemsize):
        return None
    # B from the row's bytes: the largest power of two of rows within
    # BLOCK_BYTES that divides the region
    rows = 1 << (BLOCK_BYTES // (nkv * hd * itemsize)).bit_length() - 1
    while rows > 1 and max_len % rows:
        rows //= 2
    # the float32 scores of every query row of a slot against a block's
    # keys, and the few arrays of their shape, stand in fast memory beside
    # the blocks: a decode step's rows (OLMoE: 16) and a verify window's
    # (16 x 5) do, a grid of prompts at per-slot offsets does not
    if queries[1] * queries[2] * rows * nkv * 4 > SCORE_BYTES:
        return None
    # a region that one block covers has nothing inside a slot to skip, and
    # a grid step a slot a layer costs more than reading it (Falcon's 256
    # KiB a slot a layer: 64 slots x 11 layers of steps against 0.47 ms)
    if max_len // rows < 2:
        return None
    return rows


def contiguous_pool_attention(q, k, v, lengths, *, layer, rows: int,
                              scale: float, k_scale=None, v_scale=None):
    """`block_native_attention` over the contiguous pool k/v [L, slots,
    max_len, nkv, hd] under the identity chain: slot s's j-th block of
    `rows` rows is block s * nb + j of the layer. Scales as the pool holds
    them, [L, slots, max_len, nkv, 1]."""
    L, S, max_len, nkv, hd = k.shape
    nb = max_len // rows

    def blocks(a):
        return a.reshape(L, S * nb, rows, nkv, a.shape[-1])

    def layer_blocks(a):
        return None if a is None else jax.lax.dynamic_index_in_dim(
            blocks(a), layer, 0, keepdims=False)
    chain = jnp.arange(S * nb, dtype=jnp.int32).reshape(S, nb)
    return block_native_attention(
        q, blocks(k), blocks(v), chain, lengths, scale=scale, layer=layer,
        k_scale=layer_blocks(k_scale), v_scale=layer_blocks(v_scale))
