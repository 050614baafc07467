"""Pallas TPU flash attention: causal + GQA + segment masks, fwd and bwd.

TPU-native replacement for the reference's CUDA attention kernels — the
external FlashAttention-2 package (ref: megatron/model/transformer.py:514-522
`flash_attn_func`) and the fused scaled-masked-softmax kernels it superseded
(ref: megatron/fused_kernels/scaled_*_softmax*.cu — K1-K3 in SURVEY.md §2.2).

Kernel shape (FlashAttention-2 algorithm on the TPU memory hierarchy):
- grid (batch, q_heads, q_blocks, kv_blocks); the kv axis is innermost, so
  TPU's sequential grid execution lets a VMEM scratch accumulator carry the
  online-softmax state (m, l, acc) across kv steps — the analogue of the
  CUDA kernel's per-CTA registers.
- Q/K/V blocks are DMA'd HBM->VMEM by BlockSpec; the MXU does the two GEMMs
  per tile; softmax renormalization runs on the VPU in fp32.
- Every product (2 in the forward, 3 in dQ, 4 in dK/dV: `_dot`) takes its
  operands in the dtype the rows arrived in and sums in float32: q, k, v and
  dO are multiplied as they are read, the scale goes onto the float32 scores
  and onto dQ / dK as they leave their accumulators, and p and dS, computed
  in float32, are rounded to the rows' dtype where they enter a product
  (`models/attention.py::_dot_attention` rounds its probabilities the same
  way). Max, sum, exp, lse, delta, masks and accumulators are float32.
  Float32 rows are multiplied as float32, under the caller's precision.
- Causality skips whole kv blocks past the diagonal (`pl.when`; the forward
  does not fetch them either, `_kv_block_index`), the partial diagonal block
  is masked by lane iota.
- GQA: the kv-head BlockSpec index maps q-head h -> kv-head h // group, so
  MQA/GQA never materialize broadcast K/V (the reference materializes the
  broadcast at transformer.py:448-455 in the unfused path).
- Backward is a custom VJP with the standard flash recomputation: saved
  per-row logsumexp + delta = rowsum(dO*O), one kernel for dQ (grid over q
  blocks) and one for dK/dV (grid over kv blocks).

Layout: [b, s, n, d] at the API boundary (matching models/attention.py);
kernels run head-major [b, n, s, d].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The forward kernels' blocks. A grid step pays for every query row of its
# block whatever the keys' block holds: the running max and sum, the rescale
# of the accumulator, all on [bq, 1] columns that take a vreg for 8 values.
# At 512 x 512 that, and not the products, is most of a step (PERF.md
# section 6, PR 42: masks and scale taken out move nothing), so fewer and
# larger steps are faster up to what VMEM holds.
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_KV = 1024
# The two backward kernels and a forward with dropout hold four and more
# [bq, bkv] float32 arrays at once; 1024 x 1024 of them overrun the 16 MiB
# a kernel may take of VMEM (the compiler: "Ran out of memory in memory
# space vmem"), so these keep blocks of 512.
MANY_TEMPS_BLOCK = 512
NEG_INF = -1e30
# exp clamp for rows whose every score in a block is masked (possible with
# segment masking: a document's rows see zero keys in a foreign-document
# block). exp(s - max(m, CLAMP)) = exp(NEG_INF + 1e20) == 0 for masked
# entries even when the running max itself is still NEG_INF; real scores
# always exceed the clamp so normal rows are untouched.
MASK_CLAMP = -1e20
# Per-row stats (lse, delta) carry a trailing lanes dim: TPU lowering requires
# the last two block dims be (8k, 128k) or equal to the array dims, so a
# rank-3 [b, n, s] stat with block (1, 1, bq) cannot lower. Stats are stored
# [b, n, s, STAT_LANES] with the row value broadcast across lanes (the
# official jax TPU flash kernel does the same with 128 lanes; 8 == one f32
# sublane keeps the HBM footprint 16x smaller, which matters at 32k seq).
STAT_LANES = 8

# murmur3 fmix32 constants as wrapping int32 (0x85ebca6b, 0xc2b2ae35) —
# the in-kernel counter-based dropout RNG below uses plain int32 ops
# (wrapping multiply + LOGICAL shifts), so it runs identically under
# interpret mode on CPU and compiled on TPU; pltpu.prng_random_bits has
# no CPU lowering, which would leave the dropout path untestable here
_FMIX_M1 = -2048144789
_FMIX_M2 = -1028477387


def _fmix32(x):
    """murmur3 finalizer: full avalanche on int32 (wrapping arithmetic).

    Constants stay PYTHON ints (signed-int32 values): a jnp constant
    would be captured as a pallas_call closure array, which the
    interpret path refuses ('Cannot lower a pallas_call with
    constants'); python scalars promote weakly onto the traced int32."""
    srl = jax.lax.shift_right_logical
    x = x ^ srl(x, 16)
    x = x * _FMIX_M1
    x = x ^ srl(x, 13)
    x = x * _FMIX_M2
    x = x ^ srl(x, 16)
    return x


def _dropout_keep(seed_i32, bh, qi, ki, block_q, block_kv, rate):
    """Deterministic [block_q, block_kv] keep mask for (batch*head, q
    block, kv block): two fmix rounds over (seed ^ head-row, kv column).
    The SAME function runs in the forward and BOTH backward kernels, so
    the mask regenerates bit-exactly without ever being stored."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0)
    kv_pos = ki * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)
    # golden-ratio constants as wrapping int32 (0x9E3779B1 == -1640531535
    # signed); python ints, not jnp constants — see _fmix32
    row = _fmix32(seed_i32 ^ (bh * (-1640531535))
                  ^ (q_pos * 0x61C88647))
    u = _fmix32(row ^ kv_pos)
    # 31 uniform bits vs a compile-time threshold
    u31 = jax.lax.shift_right_logical(u, 1)
    thresh = int(rate * float(2 ** 31))
    return u31 >= thresh


def _dot(a, b, a_dim, b_dim):
    """a · b contracted over (a_dim, b_dim): the operands as they are, the
    sum in float32. Operands narrower than float32 say their precision
    themselves, whatever the caller has set as JAX's default: their products
    are exact in float32 already, and Mosaic refuses "highest" on bf16
    operands. Float32 operands leave it to that default."""
    precision = (None if a.dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    return jax.lax.dot_general(a, b, (((a_dim,), (b_dim,)), ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def _fwd_kernel(q_ref, k_ref, v_ref, *refs, scale, causal, block_q,
                block_kv, num_kv, has_segs=False, window=None,
                dropout_rate=0.0, q_off=None, kv_start=None,
                kv_folded=False):
    # q_off / kv_start (traced scalars, `_fwd_kernel_offset` alone): query
    # row i stands at position q_off + i of the keys' own numbering, and
    # keys before kv_start hold nothing
    # refs: [qs_ref, ks_ref]? [seed_ref]? o_ref, lse_ref, acc_ref, m_ref,
    # l_ref — segment-id blocks / the dropout seed are inputs only when
    # the feature is on, so the plain path pays zero extra DMA
    refs = list(refs)
    qs_ref = ks_ref = seed_ref = None
    if has_segs:
        qs_ref, ks_ref = refs[0], refs[1]
        refs = refs[2:]
    if dropout_rate > 0.0:
        seed_ref = refs[0]
        refs = refs[1:]
    o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    drop_z = None
    if dropout_rate > 0.0:
        # computed at kernel top level: program_id inside a pl.when body
        # would be captured as a cond-closure constant, which the
        # interpret path refuses
        bh = pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)
        dkeep = _dropout_keep(seed_ref[0, 0].astype(jnp.int32), bh, qi,
                              ki, block_q, block_kv, dropout_rate)
        drop_z = dkeep.astype(jnp.float32) / (1.0 - dropout_rate)

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # whole block beyond the diagonal -> skip (causal); with a sliding
    # window also skip blocks entirely BEHIND the band
    run = True
    q_first = qi * block_q                # the block's first query position
    if q_off is not None:
        q_first = q_first + q_off
    if causal:
        run = ki * block_kv <= q_first + block_q - 1
        if window is not None:
            run = run & (ki * block_kv + block_kv - 1
                         > q_first - window)
        if kv_start is not None:
            run = run & (ki * block_kv + block_kv - 1 >= kv_start)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]                                  # [bq, d]
        if kv_folded:   # blocks [1, bkv, d] of rows [b, sk, nkv * d]
            k, v = k_ref[0], v_ref[0]
        else:
            k = k_ref[0, 0]                              # [bkv, d]
            v = v_ref[0, 0]
        s = _dot(q, k, 1, 1) * scale
        if causal:
            q_pos = q_first + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            kv_pos = ki * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            keep = q_pos >= kv_pos
            if window is not None:
                keep = keep & (q_pos - kv_pos < window)
            if kv_start is not None:
                keep = keep & (kv_pos >= kv_start)
            s = jnp.where(keep, s, NEG_INF)
        if has_segs:
            # block-diagonal across documents (ref: --reset_attention_mask,
            # megatron/utils.py:137-194); ids ride as f32 lanes, equality
            # on small ints is exact
            q_seg = qs_ref[0][:, :1]                     # [bq, 1]
            k_seg = ks_ref[0][:, 0][None, :]             # [1, bkv]
            s = jnp.where(q_seg == k_seg, s, NEG_INF)

        m_prev = m_ref[:, :1]                            # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # MASK_CLAMP: a row can be fully masked in this block (foreign
        # document) — without the clamp exp(NEG_INF - NEG_INF) == 1 would
        # attend uniformly to the masked keys
        p = jnp.exp(s - jnp.maximum(m_new, MASK_CLAMP))
        alpha = jnp.exp(m_prev - m_new)                  # [bq, 1]
        # softmax-then-dropout: l keeps the UNdropped sum (dropout scales
        # the normalized probs, it does not renormalize them); only the
        # value accumulation sees the inverted-dropout mask
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pz = p if drop_z is None else p * drop_z
        acc_ref[:] = acc_ref[:] * alpha + _dot(pz.astype(v.dtype), v, 1, 0)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(ki == num_kv - 1)
    def _finalize():
        l = l_ref[:, :1]
        l_safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = jnp.broadcast_to(
            m_ref[:, :1] + jnp.log(l_safe), lse_ref.shape[2:])


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   *refs, scale, causal, block_q, block_kv, num_kv,
                   has_dlse=False, has_segs=False, window=None,
                   dropout_rate=0.0):
    # refs: [qs_ref, ks_ref]? [dlse_ref]? [seed_ref]? dq_ref, dq_acc —
    # segment blocks / dlse / the dropout seed are inputs only when the
    # respective feature is on (the plain path skips the DMAs)
    refs = list(refs)
    qs_ref = ks_ref = dlse_ref = seed_ref = None
    if has_segs:
        qs_ref, ks_ref = refs[0], refs[1]
        refs = refs[2:]
    if has_dlse:
        dlse_ref = refs[0]
        refs = refs[1:]
    if dropout_rate > 0.0:
        seed_ref = refs[0]
        refs = refs[1:]
    dq_ref, dq_acc = refs
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    drop_z = None
    if dropout_rate > 0.0:
        bh = pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)
        dkeep = _dropout_keep(seed_ref[0, 0].astype(jnp.int32), bh, qi,
                              ki, block_q, block_kv, dropout_rate)
        drop_z = dkeep.astype(jnp.float32) / (1.0 - dropout_rate)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = True
    if causal:
        run = ki * block_kv <= qi * block_q + block_q - 1
        if window is not None:
            run = run & (ki * block_kv + block_kv - 1
                         > qi * block_q - window)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        # clamp like the forward: a fully-masked row's lse is NEG_INF and
        # exp(NEG_INF - NEG_INF) would resurrect its masked entries
        lse = jnp.maximum(lse_ref[0, 0][:, :1], MASK_CLAMP)  # [bq, 1]
        delta = delta_ref[0, 0][:, :1]
        s = _dot(q, k, 1, 1) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            kv_pos = ki * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            keep = q_pos >= kv_pos
            if window is not None:
                keep = keep & (q_pos - kv_pos < window)
            s = jnp.where(keep, s, NEG_INF)
        if has_segs:
            q_seg = qs_ref[0][:, :1]
            k_seg = ks_ref[0][:, 0][None, :]
            s = jnp.where(q_seg == k_seg, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = _dot(do, v, 1, 1)
        if drop_z is not None:
            # the forward's regenerated mask; with O = (P∘Z)V/l the
            # chain rule gives dS = P ∘ (Z∘dP_raw - delta): delta =
            # rowsum(dO∘O) already absorbs the dropped entries
            dp = dp * drop_z
        # dlse term: d(lse)/d(s) = p, so an lse cotangent adds p*dlse
        # (used by ring attention's online merge weights)
        rest = dp - delta
        if has_dlse:
            rest = rest + dlse_ref[0, 0][:, :1]
        ds = p * rest
        dq_acc[:] += _dot(ds.astype(k.dtype), k, 1, 0)

    @pl.when(ki == num_kv - 1)
    def _finalize():
        dq_ref[0, 0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    *refs, scale, causal, block_q, block_kv, num_q,
                    has_dlse=False, has_segs=False, window=None,
                    dropout_rate=0.0):
    refs = list(refs)
    qs_ref = ks_ref = dlse_ref = seed_ref = None
    if has_segs:
        qs_ref, ks_ref = refs[0], refs[1]
        refs = refs[2:]
    if has_dlse:
        dlse_ref = refs[0]
        refs = refs[1:]
    if dropout_rate > 0.0:
        seed_ref = refs[0]
        refs = refs[1:]
    dk_ref, dv_ref, dk_acc, dv_acc = refs
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    drop_z = None
    if dropout_rate > 0.0:
        # same (bh, qi, ki) stream as the forward — this kernel's grid
        # swaps the block axes, but the mask is indexed by the block
        # COORDINATES, not the grid order
        bh = pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)
        dkeep = _dropout_keep(seed_ref[0, 0].astype(jnp.int32), bh, qi,
                              ki, block_q, block_kv, dropout_rate)
        drop_z = dkeep.astype(jnp.float32) / (1.0 - dropout_rate)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = True
    if causal:
        # q block entirely above the diagonal contributes nothing; with a
        # sliding window, neither does one entirely past the band
        run = qi * block_q + block_q - 1 >= ki * block_kv
        if window is not None:
            run = run & (qi * block_q
                         < ki * block_kv + block_kv - 1 + window)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = jnp.maximum(lse_ref[0, 0][:, :1], MASK_CLAMP)  # [bq, 1]
        delta = delta_ref[0, 0][:, :1]
        s = _dot(q, k, 1, 1) * scale
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            kv_pos = ki * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            keep = q_pos >= kv_pos
            if window is not None:
                keep = keep & (q_pos - kv_pos < window)
            s = jnp.where(keep, s, NEG_INF)
        if has_segs:
            q_seg = qs_ref[0][:, :1]
            k_seg = ks_ref[0][:, 0][None, :]
            s = jnp.where(q_seg == k_seg, s, NEG_INF)
        p = jnp.exp(s - lse)                             # [bq, bkv]
        pz = p
        dp = _dot(do, v, 1, 1)
        if drop_z is not None:
            pz = p * drop_z  # dV sees the dropped weights: dV = (P∘Z)ᵀdO
            dp = dp * drop_z  # dS = P ∘ (Z∘dP_raw - delta)
        dv_acc[:] += _dot(pz.astype(do.dtype), do, 0, 0)
        rest = dp - delta
        if has_dlse:
            rest = rest + dlse_ref[0, 0][:, :1]
        ds = p * rest
        dk_acc[:] += _dot(ds.astype(q.dtype), q, 0, 0)

    @pl.when(qi == num_q - 1)
    def _finalize():
        # q entered its products unscaled: the scale meets dk here, once
        dk_ref[0, 0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[:].astype(dv_ref.dtype)


def _pick_block(s: int, bmax: int) -> int:
    """Largest block <= bmax that tiles s: the requested block if it divides
    s exactly, else the largest 128-multiple divisor of s. Handles
    128-divisible-but-not-512-divisible lengths like 640/768/1280 by
    shrinking instead of asserting."""
    bmax = min(bmax, s)
    if s % bmax == 0:
        return bmax
    for b in range(bmax - bmax % 128, 0, -128):
        if s % b == 0:
            return b
    raise ValueError(
        f"sequence length {s} has no 128-multiple block divisor <= {bmax}; "
        "pad the sequence to a multiple of 128 or use the XLA fallback path")


def _pick_blocks(sq, sk, block_q, block_kv, many_temps=False):
    if many_temps:
        block_q = min(block_q, MANY_TEMPS_BLOCK)
        block_kv = min(block_kv, MANY_TEMPS_BLOCK)
    return _pick_block(sq, block_q), _pick_block(sk, block_kv)


def _kv_block_index(ki, q_first, bq, bkv, num_kv, window, kv_start=0):
    """The keys' block a forward grid step fetches: `ki` where the step
    runs, else the nearest block that does. Blocks wholly past the query
    block's diagonal, behind its window or before `kv_start` are skipped by
    the kernel, and their DMA too: a block index that does not change is
    not fetched again."""
    last = (q_first + bq - 1) // bkv
    first = kv_start
    if window is not None:
        first = jnp.maximum(first, q_first - window + 1)
    first = jnp.maximum(first, 0) // bkv
    return jnp.clip(ki, first, jnp.minimum(last, num_kv - 1))


def _seg_lanes(seg, lanes=STAT_LANES):
    """[b, s] f32 segment ids -> [b, s, lanes] broadcast (same trick as
    the lse/delta stats: the trailing lanes dim satisfies TPU tiling)."""
    return jnp.broadcast_to(seg.astype(jnp.float32)[..., None],
                            seg.shape + (lanes,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 10, 11))
def pallas_flash_attention(q, k, v, causal=True, scale=None,
                           block_q=DEFAULT_BLOCK_Q, block_kv=DEFAULT_BLOCK_KV,
                           interpret=False, q_seg=None, k_seg=None,
                           sliding_window=None, dropout_rate=0.0,
                           dropout_seed=None):
    """q [b, sq, nq, d], k/v [b, sk, nkv, d] -> [b, sq, nq, d].

    `q_seg`/`k_seg` [b, s] FLOAT segment ids (cast outside so the vjp's
    cotangent plumbing stays all-float): scores are masked where ids
    differ — block-diagonal attention across EOD-separated documents
    (ref: --reset_attention_mask, megatron/utils.py:137-194).

    `dropout_rate` (static) + `dropout_seed` ([1, STAT_LANES] f32 array
    holding one integer <= 2^24, a zero-cotangent diff arg like the seg
    ids): attention dropout INSIDE the kernel — the reference's FA2
    `dropout_p` (ref: transformer.py:514-522). Masks are regenerated
    from (seed, head, block coords) by a counter-based hash in forward
    AND both backward kernels; nothing is stored."""
    out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_kv, interpret,
                        q_seg, k_seg, sliding_window, dropout_rate,
                        dropout_seed)
    return out


def _flash_fwd(q, k, v, causal, scale, block_q, block_kv, interpret,
               q_seg=None, k_seg=None, sliding_window=None,
               dropout_rate=0.0, dropout_seed=None):
    b, sq, nq, d = q.shape
    sk, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    if scale is None:
        scale = d ** -0.5
    has_drop = dropout_rate > 0.0
    bq, bkv = _pick_blocks(sq, sk, block_q, block_kv, many_temps=has_drop)
    num_q, num_kv = sq // bq, sk // bkv
    has_segs = q_seg is not None
    assert has_segs == (k_seg is not None), "q_seg/k_seg must come together"
    assert not has_drop or dropout_seed is not None, (
        "dropout_rate > 0 needs dropout_seed")

    qT = q.transpose(0, 2, 1, 3)  # [b, nq, sq, d]
    kT = k.transpose(0, 2, 1, 3)  # [b, nkv, sk, d]
    vT = v.transpose(0, 2, 1, 3)

    grid = (b, nq, num_q, num_kv)
    q_spec = pl.BlockSpec((1, 1, bq, d), lambda bi, h, qi, ki: (bi, h, qi, 0))

    def kv_block(qi, ki):
        if not causal:
            return ki
        return _kv_block_index(ki, qi * bq, bq, bkv, num_kv, sliding_window)

    kv_spec = pl.BlockSpec(
        (1, 1, bkv, d), lambda bi, h, qi, ki: (bi, h // g, kv_block(qi, ki), 0))
    o_spec = pl.BlockSpec((1, 1, bq, d), lambda bi, h, qi, ki: (bi, h, qi, 0))
    lse_spec = pl.BlockSpec((1, 1, bq, STAT_LANES),
                            lambda bi, h, qi, ki: (bi, h, qi, 0))
    seg_inputs, seg_specs = [], []
    if has_segs:
        seg_inputs = [_seg_lanes(q_seg), _seg_lanes(k_seg)]
        seg_specs = [
            pl.BlockSpec((1, bq, STAT_LANES),
                         lambda bi, h, qi, ki: (bi, qi, 0)),
            pl.BlockSpec((1, bkv, STAT_LANES),
                         lambda bi, h, qi, ki: (bi, kv_block(qi, ki), 0)),
        ]
    drop_inputs, drop_specs = [], []
    if has_drop:
        drop_inputs = [jnp.broadcast_to(
            jnp.asarray(dropout_seed, jnp.float32).reshape(1, -1)[:, :1],
            (1, STAT_LANES))]
        drop_specs = [pl.BlockSpec((1, STAT_LANES),
                                   lambda bi, h, qi, ki: (0, 0))]

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=bq, block_kv=bkv, num_kv=num_kv,
                          has_segs=has_segs, window=sliding_window,
                          dropout_rate=dropout_rate),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec] + seg_specs + drop_specs,
        out_specs=[o_spec, lse_spec],
        out_shape=[jax.ShapeDtypeStruct((b, nq, sq, d), q.dtype),
                   jax.ShapeDtypeStruct((b, nq, sq, STAT_LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                        pltpu.VMEM((bq, STAT_LANES), jnp.float32),
                        pltpu.VMEM((bq, STAT_LANES), jnp.float32)],
        interpret=interpret,
    )(qT, kT, vT, *seg_inputs, *drop_inputs)
    out = out.transpose(0, 2, 1, 3)
    return out, (q, k, v, out, lse, q_seg, k_seg, dropout_seed)


def _fwd_kernel_offset(off_ref, q_ref, k_ref, v_ref, *refs, **static):
    """`_fwd_kernel` with the two prefetched scalars of
    `pallas_flash_attention_offset`."""
    _fwd_kernel(q_ref, k_ref, v_ref, *refs, q_off=off_ref[0],
                kv_start=off_ref[1], **static)


def pallas_flash_attention_offset(q, k, v, q_offset, kv_start=0, *,
                                  scale=None, sliding_window=None,
                                  block_q=DEFAULT_BLOCK_Q,
                                  block_kv=DEFAULT_BLOCK_KV,
                                  interpret=False,
                                  kv_heads_major: bool = False,
                                  kv_folded: int = 0):
    """Causal attention of a CHUNK of queries against keys that begin before
    it: q [b, sq, nq, d] (row i at position `q_offset` + i of the keys'
    numbering), k/v [b, sk, nkv, d] (or, `kv_heads_major`, [b, nkv, sk, d]:
    the order the kernel reads, so a cache held that way is not transposed;
    or, `kv_folded` = nkv, [b, sk, nkv * d]: a position's row holds the kv
    heads' channels side by side, d whole lane tiles, and kv head g's
    channels are the g-th block of d along the row, read where they lie)
    with sk >= q_offset + sq -> [b, sq, nq, d]. `q_offset` and `kv_start` are traced scalars (one compiled program
    serves every offset); keys before `kv_start` hold nothing and are masked.
    What a serving prefill that continues a cache needs (models/attention.py:
    a chunk against its ring's earlier rows and itself, or against the
    region it has just been written into). Forward only.

    The kernel is `_fwd_kernel` with its positions shifted. Key blocks
    wholly past a query block's diagonal, behind its window or before
    `kv_start` are skipped as the aligned kernel skips them, and their DMA
    too (`_kv_block_index`)."""
    b, sq, nq, d = q.shape
    if kv_folded:
        assert d % 128 == 0 and k.shape[2] == kv_folded * d, (k.shape, d)
        nkv, sk = kv_folded, k.shape[1]
    else:
        if not kv_heads_major:
            k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
        nkv, sk = k.shape[1], k.shape[2]
    g = nq // nkv
    if scale is None:
        scale = d ** -0.5
    bq, bkv = _pick_blocks(sq, sk, block_q, block_kv)
    num_q, num_kv = sq // bq, sk // bkv
    offs = jnp.stack([jnp.asarray(q_offset, jnp.int32),
                      jnp.asarray(kv_start, jnp.int32)])

    def kv_block(qi, ki, off):
        return _kv_block_index(ki, off[0] + qi * bq, bq, bkv, num_kv,
                               sliding_window, kv_start=off[1])

    q_spec = pl.BlockSpec((1, 1, bq, d),
                          lambda bi, h, qi, ki, off: (bi, h, qi, 0))
    kv_spec = pl.BlockSpec(
        (1, 1, bkv, d),
        lambda bi, h, qi, ki, off: (bi, h // g, kv_block(qi, ki, off), 0))
    if kv_folded:
        kv_spec = pl.BlockSpec(
            (1, bkv, d),
            lambda bi, h, qi, ki, off: (bi, kv_block(qi, ki, off), h // g))
    lse_spec = pl.BlockSpec((1, 1, bq, STAT_LANES),
                            lambda bi, h, qi, ki, off: (bi, h, qi, 0))
    out, _ = pl.pallas_call(
        functools.partial(_fwd_kernel_offset, scale=scale, causal=True,
                          block_q=bq, block_kv=bkv, num_kv=num_kv,
                          window=sliding_window,
                          kv_folded=bool(kv_folded)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, nq, num_q, num_kv),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=[q_spec, lse_spec],
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                            pltpu.VMEM((bq, STAT_LANES), jnp.float32),
                            pltpu.VMEM((bq, STAT_LANES), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, nq, sq, d), q.dtype),
                   jax.ShapeDtypeStruct((b, nq, sq, STAT_LANES),
                                        jnp.float32)],
        interpret=interpret,
    )(offs, q.transpose(0, 2, 1, 3), k, v)
    return out.transpose(0, 2, 1, 3)


def _flash_bwd_core(causal, scale, block_q, block_kv, interpret, res, dout,
                    dlse=None, sliding_window=None, dropout_rate=0.0):
    """Shared backward. `dlse` [b, sq, nq] is the cotangent of the exposed
    logsumexp (ring attention's merge weights use it); None means zero."""
    q, k, v, out, lse, q_seg, k_seg, dropout_seed = res
    b, sq, nq, d = q.shape
    sk, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    if scale is None:
        scale = d ** -0.5
    bq, bkv = _pick_blocks(sq, sk, block_q, block_kv, many_temps=True)
    num_q, num_kv = sq // bq, sk // bkv
    has_segs = q_seg is not None
    has_drop = dropout_rate > 0.0

    qT = q.transpose(0, 2, 1, 3)
    kT = k.transpose(0, 2, 1, 3)
    vT = v.transpose(0, 2, 1, 3)
    doT = dout.transpose(0, 2, 1, 3)
    # delta = rowsum(dO * O) [b, nq, sq] (flash-2 backward precomputation),
    # broadcast to STAT_LANES like the lse residual
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1)
    delta = jnp.broadcast_to(delta[..., None], (b, nq, sq, STAT_LANES))
    has_dlse = dlse is not None
    seg_inputs = ([_seg_lanes(q_seg), _seg_lanes(k_seg)] if has_segs else [])
    extra = []
    if has_dlse:
        extra = [jnp.broadcast_to(
            dlse.astype(jnp.float32).transpose(0, 2, 1)[..., None],
            (b, nq, sq, STAT_LANES))]
    drop_inputs = []
    if has_drop:
        drop_inputs = [jnp.broadcast_to(
            jnp.asarray(dropout_seed, jnp.float32).reshape(1, -1)[:, :1],
            (1, STAT_LANES))]

    q_spec = pl.BlockSpec((1, 1, bq, d), lambda bi, h, qi, ki: (bi, h, qi, 0))
    kv_spec = pl.BlockSpec((1, 1, bkv, d),
                           lambda bi, h, qi, ki: (bi, h // g, ki, 0))
    row_spec = pl.BlockSpec((1, 1, bq, STAT_LANES),
                            lambda bi, h, qi, ki: (bi, h, qi, 0))
    seg_specs = ([
        pl.BlockSpec((1, bq, STAT_LANES), lambda bi, h, qi, ki: (bi, qi, 0)),
        pl.BlockSpec((1, bkv, STAT_LANES), lambda bi, h, qi, ki: (bi, ki, 0)),
    ] if has_segs else [])

    seed_spec = [pl.BlockSpec((1, STAT_LANES),
                              lambda bi, h, qi, ki: (0, 0))] * has_drop

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_kv=bkv, num_kv=num_kv,
                          has_dlse=has_dlse, has_segs=has_segs,
                          window=sliding_window,
                          dropout_rate=dropout_rate),
        grid=(b, nq, num_q, num_kv),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec]
        + seg_specs + [row_spec] * has_dlse + seed_spec,
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bi, h, qi, ki: (bi, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nq, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(qT, kT, vT, doT, lse, delta, *seg_inputs, *extra, *drop_inputs)

    # dk/dv: grid swaps the roles — kv blocks outer, q blocks inner; every
    # q-head contributes to its kv-head, so run per Q-HEAD and sum groups
    # after (keeps the kernel free of cross-head reductions)
    q_spec2 = pl.BlockSpec((1, 1, bq, d),
                           lambda bi, h, ki, qi: (bi, h, qi, 0))
    kv_spec2 = pl.BlockSpec((1, 1, bkv, d),
                            lambda bi, h, ki, qi: (bi, h // g, ki, 0))
    row_spec2 = pl.BlockSpec((1, 1, bq, STAT_LANES),
                             lambda bi, h, ki, qi: (bi, h, qi, 0))
    dk_spec = pl.BlockSpec((1, 1, bkv, d),
                           lambda bi, h, ki, qi: (bi, h, ki, 0))
    seg_specs2 = ([
        pl.BlockSpec((1, bq, STAT_LANES), lambda bi, h, ki, qi: (bi, qi, 0)),
        pl.BlockSpec((1, bkv, STAT_LANES), lambda bi, h, ki, qi: (bi, ki, 0)),
    ] if has_segs else [])

    seed_spec2 = [pl.BlockSpec((1, STAT_LANES),
                               lambda bi, h, ki, qi: (0, 0))] * has_drop

    dk_per_head, dv_per_head = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_kv=bkv, num_q=num_q,
                          has_dlse=has_dlse, has_segs=has_segs,
                          window=sliding_window,
                          dropout_rate=dropout_rate),
        grid=(b, nq, num_kv, num_q),
        in_specs=[q_spec2, kv_spec2, kv_spec2, q_spec2, row_spec2, row_spec2]
        + seg_specs2 + [row_spec2] * has_dlse + seed_spec2,
        out_specs=[dk_spec, dk_spec],
        out_shape=[jax.ShapeDtypeStruct((b, nq, sk, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, nq, sk, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bkv, d), jnp.float32),
                        pltpu.VMEM((bkv, d), jnp.float32)],
        interpret=interpret,
    )(qT, kT, vT, doT, lse, delta, *seg_inputs, *extra, *drop_inputs)

    # GQA: sum the per-q-head dk/dv into kv heads
    dk = dk_per_head.reshape(b, nkv, g, sk, d).sum(axis=2)
    dv = dv_per_head.reshape(b, nkv, g, sk, d).sum(axis=2)

    grads = (dq.transpose(0, 2, 1, 3),
             dk.transpose(0, 2, 1, 3).astype(k.dtype),
             dv.transpose(0, 2, 1, 3).astype(v.dtype))
    # float segment ids / the dropout seed are diff args purely for
    # plumbing: zero cotangent
    seg_grads = (jnp.zeros_like(q_seg) if has_segs else None,
                 jnp.zeros_like(k_seg) if has_segs else None,
                 jnp.zeros_like(dropout_seed) if has_drop else None)
    return grads, seg_grads


def _flash_bwd(causal, scale, block_q, block_kv, interpret,
               sliding_window, dropout_rate, res, dout):
    # sliding_window/dropout_rate arrive as NONDIFF args (static Python
    # values), never via the residuals — a traced scalar could not close
    # over the kernels
    (dq, dk, dv), (dqs, dks, dseed) = _flash_bwd_core(
        causal, scale, block_q, block_kv, interpret, res, dout,
        sliding_window=sliding_window, dropout_rate=dropout_rate)
    return dq, dk, dv, dqs, dks, dseed


def _flash_fwd_rule(q, k, v, causal, scale, block_q, block_kv, interpret,
                    q_seg=None, k_seg=None, sliding_window=None,
                    dropout_rate=0.0, dropout_seed=None):
    out, res = _flash_fwd(q, k, v, causal, scale, block_q, block_kv,
                          interpret, q_seg, k_seg, sliding_window,
                          dropout_rate, dropout_seed)
    return out, res


pallas_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def pallas_flash_attention_with_lse(q, k, v, causal=True, scale=None,
                                    block_q=DEFAULT_BLOCK_Q,
                                    block_kv=DEFAULT_BLOCK_KV,
                                    interpret=False):
    """Like pallas_flash_attention but also returns the per-row logsumexp
    [b, sq, nq] — differentiable, for online merging across blocks that
    live on different devices (ring attention hops)."""
    (out, lse), _ = _with_lse_fwd(q, k, v, causal, scale, block_q, block_kv,
                                  interpret)
    return out, lse


def _with_lse_fwd(q, k, v, causal, scale, block_q, block_kv, interpret):
    out, res = _flash_fwd(q, k, v, causal, scale, block_q, block_kv,
                          interpret)
    lse4 = res[4]  # [b, nq, sq, STAT_LANES]
    return (out, lse4[..., 0].transpose(0, 2, 1)), res


def _with_lse_bwd(causal, scale, block_q, block_kv, interpret, res, cot):
    dout, dlse = cot
    grads, _ = _flash_bwd_core(causal, scale, block_q, block_kv, interpret,
                               res, dout, dlse)
    return grads


pallas_flash_attention_with_lse.defvjp(_with_lse_fwd, _with_lse_bwd)
