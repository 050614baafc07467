"""Flash attention: blockwise online-softmax attention, O(seq) memory.

TPU-native replacement for the reference's FlashAttention-2 integration
(ref: megatron/model/transformer.py:514-522 `flash_attn_func` from the
external CUDA `flash_attn` package) and, transitively, for the fused
scaled-masked-softmax CUDA kernels it superseded (ref: megatron/fused_kernels/
scaled_*_softmax*.cu, K1-K3 in SURVEY.md §2.2).

This module provides the flash *algorithm* (tiled K/V loop with online
softmax renormalization) expressed in XLA ops via `lax.scan` — it runs on any
backend and is the numerics reference. The hand-tuned Pallas TPU kernel
(`megatron_tpu.ops.flash_attention_pallas`) overrides it on TPU when
available; both share this module's interface:

    flash_attention(q, k, v, *, causal, scale, segment_ids) -> out
      q: [b, sq, nq, d], k/v: [b, skv, nkv, d], GQA by nq % nkv == 0;
      segment_ids [b, s] masks attention block-diagonally across
      EOD-separated documents (ref: --reset_attention_mask).
"""
from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp

DEFAULT_BLOCK_KV = 512
logger = logging.getLogger(__name__)
_warned_shapes = set()


def flash_attention(q, k, v, *, causal: bool = True, scale: float | None = None,
                    block_kv: int = DEFAULT_BLOCK_KV, use_pallas: bool | None = None,
                    segment_ids=None, sliding_window: int | None = None,
                    dropout_rate: float = 0.0, dropout_rng=None,
                    q_offset=None, kv_start=None,
                    kv_heads_major: bool = False, kv_folded: int = 0):
    """Blockwise attention with online softmax. Returns [b, sq, nq, d].

    `q_offset` (a traced scalar; None: 0) puts query row i at position
    q_offset + i of the keys' numbering, and keys before `kv_start` hold
    nothing: a chunk of a serving prefill against the cache it continues
    (models/attention.py::HybridKVCache). Causal, forward only, no
    segments, no dropout, no mesh. `kv_heads_major`: k and v come [b, nkv,
    skv, d], as that cache holds them and as the kernel reads them.
    `kv_folded` = nkv > 0: k and v come [b, skv, nkv * d], a position's row
    the kv heads' channels side by side (`ConvKVCache`), d whole lane tiles.

    `segment_ids` [b, s] (shared q/k length) masks attention across
    EOD-separated documents (ref: --reset_attention_mask) — the flash
    formulation of the reference's block-diagonal mask, O(s) memory
    instead of the dot path's O(s^2) scores.

    `dropout_rate > 0` applies attention dropout INSIDE the tiled loop
    (the reference's FlashAttention-2 `dropout_p`,
    ref: megatron/model/transformer.py:514-522): the inverted-dropout
    mask multiplies each block's post-softmax weights in the value
    accumulation while the softmax normalizer keeps the undropped sum —
    exactly softmax-then-dropout like the dot path, O(block) mask
    memory, unbiased (E[out] == no-dropout out). Mask bits are drawn
    per kv-block from `dropout_rng` folded with the block index, so
    the backward (jax AD through the scan) sees identical masks.

    Under a dp/tp mesh (the activation-sharding context the sharded
    train step and the serving mesh trace in) the Pallas kernel runs
    inside an explicit shard_map — batch over 'dp', heads over 'tp':
    XLA cannot partition a Mosaic custom call, and attention is
    independent per (batch row, head), so no collective is needed."""
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    assert not (kv_heads_major or kv_folded) or q_offset is not None
    assert not (kv_heads_major and kv_folded)
    if use_pallas and (q.shape[1] % 128 != 0
                       or k.shape[2 if kv_heads_major else 1] % 128 != 0):
        # kernel blocks need 128-divisible sequence lengths; odd shapes take
        # the XLA blockwise path. Warn once per shape — this is a perf cliff,
        # not a correctness issue.
        key = (q.shape, k.shape)
        if key not in _warned_shapes:
            _warned_shapes.add(key)
            logger.warning(
                "flash_attention: seq lengths %s/%s not 128-divisible; "
                "falling back to the (slower) XLA blockwise path",
                q.shape[1], k.shape[1])
        use_pallas = False
    if dropout_rate > 0.0:
        assert dropout_rng is not None, (
            "flash_attention: dropout_rate > 0 needs dropout_rng")
    if q_offset is not None:
        assert causal and segment_ids is None and dropout_rate == 0.0
        return _flash_attention_offset(
            q, k, v, jnp.asarray(q_offset, jnp.int32),
            jnp.asarray(0 if kv_start is None else kv_start, jnp.int32),
            scale=scale, block_kv=block_kv, use_pallas=use_pallas,
            sliding_window=sliding_window, kv_heads_major=kv_heads_major,
            kv_folded=kv_folded)
    static = dict(causal=causal, scale=scale, block_kv=block_kv,
                  use_pallas=use_pallas, sliding_window=sliding_window,
                  dropout_rate=dropout_rate)
    mesh = None
    if use_pallas:
        from megatron_tpu.parallel.sharding import active_kernel_mesh
        mesh = active_kernel_mesh()
    if mesh is None:
        return _flash_attention(q, k, v, segment_ids, dropout_rng, **static)
    return _mesh_flash_attention(mesh, q, k, v, segment_ids, dropout_rng,
                                 static)


def _mesh_flash_attention(mesh, q, k, v, segment_ids, dropout_rng, static):
    """The kernel under shard_map on `mesh`: each device runs it on its
    own batch rows and heads. K/V heads shard over 'tp' when tp divides
    them; a single kv head (MQA) is replicated, every shard's query
    heads read it."""
    from jax.sharding import PartitionSpec as P
    from megatron_tpu.parallel.mesh import DATA_AXIS, TENSOR_AXIS
    dp = mesh.shape.get(DATA_AXIS, 1)  # the serving mesh has no 'dp'
    tp = mesh.shape.get(TENSOR_AXIS, 1)
    b, nq, nkv = q.shape[0], q.shape[2], k.shape[2]
    assert nq % tp == 0, f"tp={tp} must divide the query heads ({nq})"
    if nkv % tp == 0:
        kv_heads = TENSOR_AXIS
    elif nkv == 1:
        kv_heads = None
    else:
        raise NotImplementedError(
            f"flash attention under tp={tp}: {nkv} kv heads neither "
            "divide by tp nor are a single shared head")
    rows = DATA_AXIS if dp > 1 and b % dp == 0 else None
    heads = TENSOR_AXIS if tp > 1 else None
    q_spec = P(rows, None, heads, None)
    kv_spec = P(rows, None, kv_heads if tp > 1 else None, None)
    args, specs = [q, k, v], [q_spec, kv_spec, kv_spec]
    if segment_ids is not None:
        args.append(segment_ids)
        specs.append(P(rows, None))
    if dropout_rng is not None:
        args.append(dropout_rng)
        specs.append(P())

    def local(q_, k_, v_, *rest):
        rest = list(rest)
        seg = rest.pop(0) if segment_ids is not None else None
        rng = rest.pop(0) if dropout_rng is not None else None
        if rng is not None:  # every shard its own dropout stream
            for axis in (rows, heads):
                if axis is not None:
                    rng = jax.random.fold_in(rng, jax.lax.axis_index(axis))
        return _flash_attention(q_, k_, v_, seg, rng, **static)

    return jax.shard_map(local, mesh=mesh, in_specs=tuple(specs),
                         out_specs=q_spec, check_vma=False)(*args)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_kv",
                                             "use_pallas", "sliding_window",
                                             "dropout_rate"))
def _flash_attention(q, k, v, segment_ids, dropout_rng, *, causal, scale,
                     block_kv, use_pallas, sliding_window, dropout_rate):
    if use_pallas:
        # no fallback here: a kernel that cannot be imported or that the
        # compiler refuses raises, it does not drop to the XLA path
        from megatron_tpu.ops.flash_attention_pallas import (
            DEFAULT_BLOCK_KV as PBKV, DEFAULT_BLOCK_Q as PBQ, STAT_LANES,
            pallas_flash_attention)
        # positional: custom_vjp functions reject keyword arguments;
        # ids go in as floats so every diff arg is float
        seg = (segment_ids.astype(jnp.float32)
               if segment_ids is not None else None)
        seed = None
        if dropout_rate > 0.0:
            # the kernel's counter-based hash takes one integer seed
            # (<= 2^24 so the f32 plumbing is exact); per-block
            # streams come from hashing it with the block coords
            seed = jax.random.randint(
                dropout_rng, (1, STAT_LANES), 0,
                1 << 23).astype(jnp.float32)
        return pallas_flash_attention(
            q, k, v, causal, scale, PBQ, PBKV, False, seg, seg,
            sliding_window, dropout_rate, seed)
    return _blockwise_attention(q, k, v, causal=causal, scale=scale,
                                block_kv=block_kv, segment_ids=segment_ids,
                                sliding_window=sliding_window,
                                dropout_rate=dropout_rate,
                                dropout_rng=dropout_rng)


@functools.partial(jax.jit, static_argnames=(
    "scale", "block_kv", "use_pallas", "sliding_window", "kv_heads_major",
    "kv_folded"))
def _flash_attention_offset(q, k, v, q_offset, kv_start, *, scale, block_kv,
                            use_pallas, sliding_window, kv_heads_major,
                            kv_folded=0):
    """The chunk form (`flash_attention(q_offset=...)`); jitted under its
    own name so that the device trace names the kernel after it."""
    if use_pallas:
        from megatron_tpu.ops.flash_attention_pallas import \
            pallas_flash_attention_offset
        return pallas_flash_attention_offset(
            q, k, v, q_offset, kv_start, scale=scale,
            sliding_window=sliding_window, kv_heads_major=kv_heads_major,
            kv_folded=kv_folded)
    if kv_folded:
        k, v = (t.reshape(*t.shape[:2], kv_folded, -1) for t in (k, v))
    if kv_heads_major:
        k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    return _blockwise_attention(q, k, v, causal=True, scale=scale,
                                block_kv=block_kv,
                                sliding_window=sliding_window,
                                q_offset=q_offset, kv_start=kv_start)


def _blockwise_attention(q, k, v, *, causal, scale, block_kv,
                         segment_ids=None, sliding_window=None,
                         dropout_rate=0.0, dropout_rng=None,
                         q_offset=None, kv_start=None):
    b, sq, nq, d = q.shape
    skv, nkv = k.shape[1], k.shape[2]
    if scale is None:
        scale = d ** -0.5
    g = nq // nkv
    block_kv = min(block_kv, skv)
    # pad kv to a multiple of block_kv
    n_blocks = -(-skv // block_kv)
    pad = n_blocks * block_kv - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    k_seg_blocks = None
    if segment_ids is not None:
        k_seg = segment_ids
        if pad:  # pad with -1: matches no real document id
            k_seg = jnp.pad(k_seg, ((0, 0), (0, pad)), constant_values=-1)
        k_seg_blocks = k_seg.reshape(b, n_blocks, block_kv)

    qg = (q.astype(jnp.float32) * scale).reshape(b, sq, nkv, g, d)
    kb = k.astype(jnp.float32).reshape(b, n_blocks, block_kv, nkv, d)
    vb = v.astype(jnp.float32).reshape(b, n_blocks, block_kv, nkv, d)
    q_pos = jnp.arange(sq)
    if q_offset is not None:
        q_pos = q_pos + q_offset

    def body(carry, blk):
        acc, m, l = carry  # acc [b,sq,nkv,g,d], m/l [b,sq,nkv,g]
        kj, vj, j = blk    # kj/vj [b,block_kv,nkv,d]
        s = jnp.einsum("bsngd,btnd->bsngt", qg, kj)  # [b,sq,nkv,g,block_kv]
        kv_pos = j * block_kv + jnp.arange(block_kv)
        valid = kv_pos < skv
        if kv_start is not None:
            valid = valid & (kv_pos >= kv_start)
        if causal:
            win = q_pos[:, None] >= kv_pos[None, :]
            if sliding_window is not None:
                win = win & (q_pos[:, None] - kv_pos[None, :]
                             < sliding_window)
            valid = valid[None, :] & win
            valid = jnp.broadcast_to(valid[None], (b, sq, block_kv))
        else:
            valid = jnp.broadcast_to(valid[None, None], (b, sq, block_kv))
        if segment_ids is not None:
            # block-diagonal across documents (--reset_attention_mask)
            ksj = jax.lax.dynamic_index_in_dim(k_seg_blocks, j, axis=1,
                                               keepdims=False)
            valid = valid & (segment_ids[:, :, None] == ksj[:, None, :])
        s = jnp.where(valid[:, :, None, None, :], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # guard fully-masked rows (m_new = -inf): exp(-inf - -inf) -> use 0
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        # l accumulates the UNdropped sum (dropout scales softmax output,
        # it does not renormalize it — same as the dot path's
        # softmax-then-dropout); only the value accumulation sees the
        # inverted-dropout mask
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pz = p
        if dropout_rate > 0.0:
            keep = jax.random.bernoulli(
                jax.random.fold_in(dropout_rng, j), 1.0 - dropout_rate,
                p.shape)
            pz = p * keep.astype(p.dtype) / (1.0 - dropout_rate)
        acc_new = acc * alpha[..., None] + jnp.einsum("bsngt,btnd->bsngd",
                                                      pz, vj)
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((b, sq, nkv, g, d), jnp.float32)
    m0 = jnp.full((b, sq, nkv, g), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, sq, nkv, g), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(
        body, (acc0, m0, l0),
        (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), jnp.arange(n_blocks)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, sq, nq, d).astype(q.dtype)
