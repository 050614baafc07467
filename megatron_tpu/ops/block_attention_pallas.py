"""Pallas TPU block-native decode attention: read the KV arena through
the block map, never materialize the contiguous view.

With the block-granular pool (`--kv_block_size`, serving/kv_pool.py)
every decode / verify dispatch used to bracket its body with
`resolve_view`/`scatter_view` — a full [L, S, cap, nkv, hd] gather of
every slot's blocks into a contiguous view and a scatter back, PER
STEP: O(pool bytes) of HBM traffic spent relocating KV the attention
dot then streams *again*. vLLM's PagedAttention showed the fix — the
attention kernel consumes the block map directly. We rejected paging
on TPU when it meant XLA-level gather indirection; this hand-written
kernel indexes the flat arena by physical block id instead, which
sidesteps exactly that objection:

- grid (slot, kv_block): the kv axis is innermost, so TPU's sequential
  grid execution lets VMEM scratch carry the FlashAttention-2
  online-softmax state (m, l, acc) across a slot's block CHAIN — the
  same (m, l, acc) pattern as ops/flash_attention_pallas.py, walking a
  block map instead of a contiguous sequence.
- the per-slot block map and lengths ride as SCALAR PREFETCH
  (pltpu.PrefetchScalarGridSpec): the k/v BlockSpec index_map reads
  map[slot, j] to pick which physical arena block to DMA — block
  indices are data, so one compile serves every block assignment, and
  each block is DMA'd HBM->VMEM exactly once per slot regardless of
  head count (all kv heads ride in one block fetch; the head loop is
  static).
- blocks past a slot's live length are SKIPPED: compute via `pl.when`,
  and the DMA via the index-revisit trick (a dead step's index_map
  returns the previous live block, and Pallas skips re-fetching an
  unchanged block) — a 3-block slot in a 64-block region pays 3 block
  reads, not 64.
- queries per slot w >= 1: w == 1 is plain decode; w == k+1 is the
  speculative-decode verify window (causal within the window, each
  query masked from its own position `length + j`) — ONE kernel serves
  both, so decode and verify keep one trace each.
- GQA: a static loop over kv heads computes that head's g query rows
  against the block's k/v slice — MQA/GQA never materialize the
  broadcast (the kv-head slice is a static lane offset into the
  nkv*hd-folded block).
- int8 pools dequantize IN KERNEL: per-(token, head) fp32 scales are
  fetched alongside k/v (same index_map) and multiply the int8 payload
  after the cast — HBM streams the int8 bytes, exactly like the
  XLA-fused dot path.
- the partial tail block is masked by lane iota against the slot's
  length (causal: query at position len+j attends kv positions <=
  len+j), and idle rows (length 0, map parked on the TRASH block) read
  one garbage position — finite garbage in, garbage out, discarded by
  the engine like every idle-row compute.

Like flash_attention_pallas.py, the kernel body uses only ops the
interpret path supports (no pltpu-only primitives), so the SAME kernel
runs under `interpret=True` on CPU — that is the tier-1 test path and
the serving engine's CPU fallback; tests/test_tpu_compile.py compiles it
for the chip, where it has not been timed.

Layout: q [S, w, nq, hd] at the API boundary; arena k/v
[total_blocks, B, nkv, hd] (the serving pool's per-layer arena slice),
scales [total_blocks, B, nkv, 1]; map [S, nb] int32; lengths [S] int32
(each slot's first query position). The kernel runs group-major
[S, nkv*g*w, hd] internally.
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


def _bn_kernel(map_ref, len_ref, q_ref, k_ref, v_ref, *refs, scale,
               block_size, nb, nkv, g, w, hd, quant):
    # refs: [ks_ref, vs_ref]? o_ref, m_ref, l_ref, acc_ref — the int8
    # scale blocks are inputs only when the pool is quantized, so the
    # bf16 path pays zero extra DMA
    refs = list(refs)
    ks_ref = vs_ref = None
    if quant:
        ks_ref, vs_ref = refs[0], refs[1]
        refs = refs[2:]
    o_ref, m_ref, l_ref, acc_ref = refs
    si = pl.program_id(0)
    j = pl.program_id(1)
    B = block_size
    length = len_ref[si]

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # a block is live when ANY query can see it: the slot's last query
    # sits at position length + w - 1, so blocks starting past it hold
    # nothing this dispatch may read (their content is other slots' KV
    # or free-list garbage)
    live = j * B <= length + w - 1

    @pl.when(live)
    def _body():
        # every (kv_head, group, query) row of one kv head is a
        # contiguous row range [h*g*w, (h+1)*g*w) — a TRACE CONSTANT —
        # so each head runs its own online-softmax update straight on
        # static slices of the q block and the (m, l, acc) scratch.
        # Nothing is assembled across heads: Mosaic has no
        # dynamic_update_slice / dynamic_slice on values, and static
        # ref slices at sublane offsets that are not multiples of 8
        # (g*w == 1 for MHA decode) lower to masked loads/stores.
        gw = g * w
        # query index of row r within a head is r % w (rows are
        # (group, query)-major), so it sits at position length + r % w
        # — decode (w == 1) degenerates to every row at `length`
        row_q = jax.lax.broadcasted_iota(jnp.int32, (gw, B), 0)
        q_pos = length + jax.lax.rem(row_q, w)
        kv_pos = j * B + jax.lax.broadcasted_iota(jnp.int32, (gw, B), 1)
        keep = q_pos >= kv_pos  # causal incl. the partial tail block
        for h in range(nkv):  # static GQA loop: nkv is a trace constant
            rows = slice(h * gw, (h + 1) * gw)
            qh = q_ref[0, rows, :].astype(jnp.float32) * scale  # [gw, hd]
            kh = k_ref[0, :, h * hd:(h + 1) * hd] \
                .astype(jnp.float32)                          # [B, hd]
            vh = v_ref[0, :, h * hd:(h + 1) * hd] \
                .astype(jnp.float32)                          # [B, hd]
            if quant:
                kh = kh * ks_ref[0, :, h:h + 1].astype(jnp.float32)
                vh = vh * vs_ref[0, :, h:h + 1].astype(jnp.float32)
            sh = jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)           # [gw, B]
            sh = jnp.where(keep, sh, NEG_INF)

            m_prev = m_ref[rows, :1]                          # [gw, 1]
            m_cur = jnp.max(sh, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            # MASK_CLAMP: a verify window's earliest query can be fully
            # masked in a block only its later queries made live —
            # exp(NEG_INF - NEG_INF) == 1 would attend those masked keys
            p = jnp.exp(sh - jnp.maximum(m_new, MASK_CLAMP))
            alpha = jnp.exp(m_prev - m_new)
            l_ref[rows, :] = l_ref[rows, :] * alpha + jnp.sum(
                p, axis=-1, keepdims=True)
            oh = jax.lax.dot_general(
                p, vh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # [gw, hd]
            acc_ref[rows, :] = acc_ref[rows, :] * alpha + oh
            m_ref[rows, :] = jnp.broadcast_to(m_new,
                                              (gw, m_ref.shape[1]))

    @pl.when(j == nb - 1)
    def _finalize():
        l = l_ref[:, :1]
        l_safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_size", "interpret"))
def block_native_attention(q, k_arena, v_arena, block_map, lengths, *,
                           scale: float, block_size: int,
                           k_scale=None, v_scale=None,
                           interpret: bool | None = None):
    """Per-slot q against block-chained K/V, straight out of the arena.

    q:          [S, w, nq, hd]  (post-rope queries; w == 1 for decode,
                                 w == k+1 for the speculative verify
                                 window — causal within the window)
    k_arena/v_arena: [total_blocks, B, nkv, hd]  flat arena (one
                                 layer's slice of the serving pool;
                                 int8 for quantized pools)
    block_map:  [S, nb] int32    logical -> physical block per slot
    lengths:    [S] int32        first query's position per slot (the
                                 slot's pre-append token count); the
                                 slot's own k/v for the window must
                                 already be WRITTEN into the arena
                                 (write-before-read, like the dot path)
    k_scale/v_scale: [total_blocks, B, nkv, 1] fp32 — int8 pools only;
                                 dequant happens in kernel.

    Returns [S, w, nq, hd] in q's dtype. Rolling (ring) layouts are
    NOT supported — their slot->position map breaks the contiguous
    position arithmetic; the engine keeps the resolve/scatter bracket
    for those (serving/engine.py)."""
    S, w, nq, hd = q.shape
    T, B, nkv, _ = k_arena.shape
    nb = block_map.shape[1]
    assert B == block_size, (B, block_size)
    assert nq % nkv == 0, (nq, nkv)
    g = nq // nkv
    quant = k_scale is not None
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    G = nq * w

    # group-major query rows [S, nkv*g*w, hd]: row r = (kv_head, group,
    # query)-major, so the kernel's static head loop slices contiguous
    # row ranges (same h -> h // g mapping as _dot_attention's reshape)
    qg = q.reshape(S, w, nkv, g, hd).transpose(0, 2, 3, 1, 4) \
        .reshape(S, G, hd)
    # fold (nkv, hd) into lanes: free reshape (row-major contiguous),
    # and it keeps the block's trailing dims TPU-tileable
    # ([B, nkv*hd] instead of [B, nkv, hd] with a sub-8 middle dim)
    kf = k_arena.reshape(T, B, nkv * hd)
    vf = v_arena.reshape(T, B, nkv * hd)
    flat_map = block_map.reshape(-1).astype(jnp.int32)
    lengths = lengths.astype(jnp.int32)

    def _phys(si, j, map_ref, len_ref):
        # index-revisit DMA skip: steps past the slot's last live block
        # re-address that same live block, so Pallas skips the fetch
        # (pl.when skips the compute) — dead blocks cost nothing
        last = jnp.maximum(len_ref[si] + w - 1, 0) // B
        j_eff = jnp.minimum(j, jnp.minimum(last, nb - 1))
        return (map_ref[si * nb + j_eff], 0, 0)

    kv_spec = pl.BlockSpec((1, B, nkv * hd), _phys)
    in_specs = [
        pl.BlockSpec((1, G, hd), lambda si, j, m, ln: (si, 0, 0)),
        kv_spec, kv_spec,
    ]
    inputs = [qg, kf, vf]
    if quant:
        ksf = k_scale.reshape(T, B, nkv)
        vsf = v_scale.reshape(T, B, nkv)
        sc_spec = pl.BlockSpec((1, B, nkv), _phys)
        in_specs += [sc_spec, sc_spec]
        inputs += [ksf, vsf]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, G, hd),
                               lambda si, j, m, ln: (si, 0, 0)),
        scratch_shapes=[pltpu.VMEM((G, STAT_LANES), jnp.float32),  # m
                        pltpu.VMEM((G, STAT_LANES), jnp.float32),  # l
                        pltpu.VMEM((G, hd), jnp.float32)],         # acc
    )
    out = pl.pallas_call(
        functools.partial(_bn_kernel, scale=scale, block_size=B,
                          nb=nb, nkv=nkv, g=g, w=w, hd=hd,
                          quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, G, hd), q.dtype),
        interpret=interpret,
    )(flat_map, lengths, *inputs)
    # [S, nkv*g*w, hd] group-major -> [S, w, nq, hd]
    return out.reshape(S, nkv, g, w, hd).transpose(0, 3, 1, 2, 4) \
        .reshape(S, w, nq, hd)
