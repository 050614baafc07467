"""Multi-head latent attention (MLA) over a latent cache.

The second attention of this package (the first: models/attention.py),
chosen in `transformer.layer_init / layer_apply` where `cfg.kv_lora_rank` is
set. Per token x (DeepSeek-V2's equations, the ones JoyAI-LLM-Flash's
config.json names):

    c_q = RMSNorm(x W_dq)                       [q_lora_rank]
    q_h = c_q W_uq,h = [q_nope_h ; q_rope_h]    [qk_nope + qk_rope] a head
    [c_kv ; k_r] = x W_dkv ;  c_kv = RMSNorm(c_kv)   (the norm over c_kv alone)
    q_rope_h, k_r = rotary(q_rope_h), rotary(k_r)     (adjacent pairs;
                                  ONE rotary key a token, shared by the heads)

**The cache row is `[c_kv ; k_r]`**, kv_lora_rank + qk_rope values a token a
layer, and nothing else is cached (`LatentKVCache`: one array
`[layers, batch, row, positions]` and the offsets, carried through the layer
loop and written in place at (layer, row, position), as models/attention.py
writes `KVCache`).

Two forms of the same mathematics, chosen from what the code sees:

- *expanded* (no cache, or a cached forward of many positions at offset 0,
  which reads nothing the cache held): `[k_nope_h ; v_h] = c_kv W_ukv,h`,
  `k_h = [k_nope_h ; k_r]`, causal softmax of `q_h . k_h / sqrt(qk_nope +
  qk_rope)`, `o_h = sum p v_h`. With `attention_impl` "flash" through the
  flash kernel, whose one head width is the next multiple of 128 over the
  key's (256 at 192 / 128): q, k and v are padded with ZEROS, which adds
  nothing to a score and gives output columns of zero that are cut off, so
  the result is the unpadded mathematics at 1.6 times its products
  ((256 + 256) / (192 + 128); PERF.md section 6, PR 31).
- *absorbed* (whatever reads the cache: a decode step, a verify window, a
  continuation chunk): `q~_h = q_nope_h W_uk,h^T` [kv_lora_rank], scores
  `(q~_h . c_kv + q_rope_h . k_r) / sqrt(...)`, `o~_h = sum p c_kv`,
  `o_h = o~_h W_uv,h`: multi-query attention of every head over ONE shared
  key, the row, whose first kv_lora_rank values are also the value. No key or
  value of a head is ever made for a cached position.

A cached forward of many positions at a scalar offset holds both under a
`lax.cond` on `offset == 0`, as models/attention.py's `prefill_flash` does.

What the absorbed form reads. A decode step, a verify window and a short
suffix (at most `ABSORBED_Q_BLOCK` queries) take their queries at once over
the WHOLE region of `max_seq` positions, whatever the offsets. A longer
chunk (PR 59) runs a block of `ABSORBED_Q_BLOCK` queries at a time, and a
block reads the cached positions in blocks of `ABSORBED_KEY_BLOCK` keys up
to the last block any of its queries can see and no further
(`absorbed_key_blocks`, the one rule: the program's trip count, which is
data, and the engine's `latent_chunk_blocks_read`), under the running
maximum, sum and weighted sum in float32 that the flash kernels keep: a
chunk at offset 4,096 of a region of 32,768 reads a fifth of it, and the
scores are `[batch, query block, heads, key block]`, never a chunk's
against a region's.

Sharding: none. The latent row has no head axis; `config.validate` refuses
a tensor-parallel mesh and ROADMAP R5 says what a sharded MLA would need.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from megatron_tpu.config import ModelConfig
from megatron_tpu.models.norms import rmsnorm, rmsnorm_init
from megatron_tpu.models.rope import apply_rotary, yarn_softmax_mscale

# queries and cached positions a block of the absorbed form of many
# positions: its scores are [batch, query block, heads, key block] float32
# (PERF.md section 6, PR 59: the blocks tried on the chip and their times)
ABSORBED_Q_BLOCK = 256
ABSORBED_KEY_BLOCK = 1024


def absorbed_query_block(s: int) -> int:
    """The queries a block of the absorbed form of `s` positions, or 0
    where it takes them at once over the whole region (a decode step, a
    verify window, a short suffix)."""
    blk = ABSORBED_Q_BLOCK
    return blk if s > blk and s % blk == 0 else 0


def _key_block(t: int) -> int:
    """The keys a block of `t` cached positions: a shorter region is one."""
    return min(ABSORBED_KEY_BLOCK, t)


def absorbed_key_blocks(last_pos, t: int):
    """The key blocks that a block of queries reads of `t` cached
    positions, `last_pos` the largest position of its queries: those up to
    the last one any of them can see. THE rule: the program's trip count
    (a traced scalar) and the engine's `latent_chunk_blocks_read` (numpy)
    both come from here."""
    xp = jnp if isinstance(last_pos, jax.Array) else np
    kb = _key_block(t)
    return (xp.minimum(last_pos + 1, t) + kb - 1) // kb


class LatentKVCache(NamedTuple):
    """The latent cache STACKED over layers, beside `attention.KVCache`:
    `mla_apply` takes the stack and the layer's index, writes the layer's
    new rows where they live and returns the stack."""
    # [layers, batch, kv_lora_rank + qk_rope, max_seq]: POSITIONS MINOR. The
    # chip tiles the two minor dimensions (8, 128): a 576-wide minor one is
    # padded to 640 there, so XLA keeps an array [.., max_seq, 576] with the
    # positions minor anyway and the decode program copies the pool into the
    # order it was written in and back, every step, and a layer of it
    # transposed for each product (compile for the chip, PR 31: 17.0 GiB
    # where 15.75 are allowed). Held this way the products read a layer
    # where it lies; a new token's 576 values land in 72 tiles of 2 KiB,
    # and a chunk's key block (PR 59) is a cut of whole lane tiles, a
    # multiple of 128 positions of every row.
    c: jax.Array
    # tokens already in the cache: [layers], or per row [layers, batch]
    # (the serving engine's slot grid), as KVCache.offset
    offset: jax.Array

    @staticmethod
    def create(layers: int, batch: int, max_seq: int, row: int,
               dtype=jnp.bfloat16, per_slot_offsets: bool = False):
        return LatentKVCache(
            c=jnp.zeros((layers, batch, row, max_seq), dtype),
            offset=jnp.zeros((layers, batch) if per_slot_offsets
                             else (layers,), jnp.int32))


def mla_init(rng, cfg: ModelConfig, dtype=jnp.float32):
    """wq_a [h, q_lora], wq_b [q_lora, n (nope + rope)] (or, with
    `q_lora_rank` None, ONE wq [h, n (nope + rope)] and no norm), wkv_a [h,
    kv_lora + rope], wkv_b [kv_lora, n (nope + v)] (a head's k_nope columns,
    then its v columns), wo [n v, h], and the norms' scales."""
    h, n = cfg.hidden_size, cfg.num_attention_heads
    rq, rkv = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    keys = jax.random.split(rng, 5)
    std = cfg.init_method_std
    out_std = (std / math.sqrt(2.0 * cfg.num_layers)
               if cfg.use_scaled_init else std)

    def draw(k, shape, s=std):
        return jax.random.normal(k, shape, dtype) * s
    if rq is None:      # ONE query matrix, no norm (a published q_lora_rank null)
        query = {"wq": draw(keys[0], (h, n * (dn + dr)))}
    else:
        query = {"wq_a": draw(keys[0], (h, rq)),
                 "q_norm": rmsnorm_init(rq, dtype),
                 "wq_b": draw(keys[1], (rq, n * (dn + dr)))}
    return {
        **query,
        "wkv_a": draw(keys[2], (h, rkv + dr)),
        "kv_norm": rmsnorm_init(rkv, dtype),
        "wkv_b": draw(keys[3], (rkv, n * (dn + dv))),
        "wo": draw(keys[4], (n * dv, h), out_std),
    }


def mla_axes(cfg: ModelConfig):
    """Every matrix whole on its device (validate refuses a mesh that
    would split them)."""
    query = ({"wq": ("embed", None)} if cfg.q_lora_rank is None else
             {"wq_a": ("embed", None), "q_norm": {"scale": (None,)},
              "wq_b": (None, None)})
    return {
        **query, "wkv_a": ("embed", None),
        "kv_norm": {"scale": (None,)}, "wkv_b": (None, None),
        "wo": (None, "embed"),
    }


def _masked_softmax(scores, q_pos, kv_pos, segment_ids=None):
    """Causal softmax in float32. scores [b, n, s, t]; q_pos [b|1, s];
    kv_pos [t]."""
    mask = q_pos[:, :, None] >= kv_pos[None, None, :]          # [b|1, s, t]
    if segment_ids is not None:
        mask = mask & (segment_ids[:, :, None] == segment_ids[:, None, :])
    scores = jnp.where(mask[:, None], scores.astype(jnp.float32),
                       jnp.finfo(jnp.float32).min)
    return jax.nn.softmax(scores, axis=-1)


def _attend_expanded(q, c, k_r, wkv_b, cfg: ModelConfig, scale, *,
                     flash: bool, segment_ids=None):
    """q [b, s, n, nope + rope] (rotated), c [b, s, kv_lora] (normed), k_r
    [b, s, rope] (rotated): the positions attend one another causally from
    position 0. Returns [b, s, n, v]."""
    b, s, n, _ = q.shape
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    kv = (c @ wkv_b).reshape(b, s, n, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r[:, :, None], (b, s, n, k_r.shape[-1]))],
        axis=-1)
    v = kv[..., dn:]
    if flash:
        from megatron_tpu.ops.flash_attention import flash_attention
        d = -(-q.shape[-1] // 128) * 128       # the kernel's one head width

        def pad(a):
            return jnp.pad(a, ((0, 0),) * 3 + ((0, d - a.shape[-1]),))
        out = flash_attention(pad(q), pad(k), pad(v), causal=True,
                              scale=scale, segment_ids=segment_ids)
        return out[..., :dv]
    scores = jnp.einsum("bsnd,btnd->bnst", q, k) * scale
    pos = jnp.arange(s)
    probs = _masked_softmax(scores, pos[None], pos, segment_ids)
    return jnp.einsum("bnst,btnd->bsnd", probs.astype(v.dtype), v)


def _attend_absorbed(q, stack, layer, wkv_b, cfg: ModelConfig, scale, q_pos):
    """q [b, s, n, nope + rope] (rotated) against layer `layer` of the
    cache `stack` [layers, b, kv_lora + rope, t], each query at its own
    position q_pos [b|1, s] and seeing the cached positions up to it.
    Returns [b, s, n, v].

    The two products cut their operand out of the stack EACH FOR ITSELF, the
    scores the whole rows and the weighted sum their first kv_lora values:
    one cut that fed both was made in memory, a copy of a layer of the pool
    in every pass, where a cut with one reader is part of the product's own
    operand load (compile for the chip, PR 31). A long chunk's key block
    (`attend_key_blocks`) is one cut for both: a megabyte, not a layer."""
    b, s, n, _ = q.shape
    dn, dv, r = cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    w = wkv_b.reshape(r, n, dn + dv)
    w_uk, w_uv = w[..., :dn], w[..., dn:]
    # q~ = q_nope W_uk^T, beside q_rope: one query of the row's width a head
    qt = jnp.concatenate(
        [jnp.einsum("bsnd,rnd->bsnr", q[..., :dn], w_uk), q[..., dn:]],
        axis=-1)
    dtype, t = q.dtype, stack.shape[3]
    kv_pos = jnp.arange(t)

    def layer_rows(width):
        return jax.lax.dynamic_slice(
            stack, (layer, 0, 0, 0), (1, b, width, t))[0].astype(dtype)
    q_pos = jnp.broadcast_to(q_pos, (b, s))

    def attend(qt_blk, pos_blk):
        scores = jnp.einsum("bsnr,brt->bnst", qt_blk,
                            layer_rows(stack.shape[2])) * scale
        probs = _masked_softmax(scores, pos_blk, kv_pos)
        return jnp.einsum("bnst,brt->bsnr", probs.astype(dtype),
                          layer_rows(r))
    kb = _key_block(t)
    lowest = jnp.finfo(jnp.float32).min

    def attend_key_blocks(qt_blk, pos_blk):
        """A block of queries over the key blocks its queries can see, the
        flash kernels' running softmax in plain XLA: the trip count is
        data. Block 0 holds position 0, which every query sees, so the
        running maximum is finite from the first iteration on and a block
        that a row cannot see at all adds exact zeros."""
        def body(j, carry):
            m, l, acc = carry
            # the last block of a `t` that `kb` does not divide starts where
            # it still fits, and leaves the positions before j kb to block
            # j - 1, which counted them
            start = jnp.minimum(j * kb, t - kb)
            rows = jax.lax.dynamic_slice(
                stack, (layer, 0, 0, start),
                (1, b, stack.shape[2], kb))[0].astype(dtype)
            pos = start + jnp.arange(kb)
            scores = jnp.einsum("bsnr,brt->bsnt", qt_blk, rows) * scale
            mask = pos_blk[:, :, None] >= pos
            if t % kb:
                mask &= pos >= j * kb
            scores = jnp.where(mask[:, :, None], scores.astype(jnp.float32),
                               lowest)
            m_new = jnp.maximum(m, scores.max(axis=-1))
            p = jnp.exp(scores - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bsnt,brt->bsnr", p.astype(dtype), rows[:, :r],
                preferred_element_type=jnp.float32)
            return m_new, l * alpha + p.sum(axis=-1), acc
        rows_q = qt_blk.shape[:3]
        _, l, acc = jax.lax.fori_loop(
            0, absorbed_key_blocks(pos_blk.max(), t), body,
            (jnp.full(rows_q, lowest), jnp.zeros(rows_q, jnp.float32),
             jnp.zeros((*rows_q, r), jnp.float32)))
        return (acc / l[..., None]).astype(dtype)
    blk = absorbed_query_block(s)
    if blk:
        o = jax.lax.map(
            lambda xs: attend_key_blocks(*xs),
            (qt.reshape(b, s // blk, blk, n, -1).swapaxes(0, 1),
             q_pos.reshape(b, s // blk, blk).swapaxes(0, 1)))
        o = o.swapaxes(0, 1).reshape(b, s, n, r)
    else:
        o = attend(qt, q_pos)
    return jnp.einsum("bsnr,rnd->bsnd", o, w_uv)


def mla_apply(params, x, cfg: ModelConfig, *, rope_cos, rope_sin,
              position_ids=None, kv_cache: LatentKVCache | None = None,
              cache_layer=None, segment_ids=None):
    """x [b, s, h] -> (out [b, s, h], the cache). `kv_cache` is the latent
    cache stacked over layers (`LatentKVCache`, or
    `attention.LatentStateCache` in a pattern of mixers) and `cache_layer`
    this layer's index in it."""
    b, s, _ = x.shape
    n, dtype = cfg.num_attention_heads, x.dtype
    dn, dr, r = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    eps = cfg.norm_epsilon
    # under YaRN with `rope_mscale_all_dim`, m(factor, mscale_all_dim)^2: 1.0
    # for every model that has none
    scale = yarn_softmax_mscale(cfg) / math.sqrt(dn + dr)

    q_offset, per_slot = None, False
    if kv_cache is not None:
        q_offset = jax.lax.dynamic_index_in_dim(
            kv_cache.offset, cache_layer, 0, keepdims=False)
        per_slot = jnp.ndim(q_offset) == 1     # the serving slot grid
        if position_ids is None:
            position_ids = jnp.broadcast_to(
                (q_offset[:, None] if per_slot else q_offset)
                + jnp.arange(s)[None, :], (b, s))

    def rotary(t):
        # `mla_nope`: NEITHER the queries' rope channels NOR the shared key
        # channels are rotated; the row and the scale stay what they are
        return t if cfg.mla_nope else apply_rotary(t, rope_cos, rope_sin,
                                                   position_ids)

    with jax.named_scope("mtpu/mla/q"):
        if cfg.q_lora_rank is None:
            q = x @ params["wq"].astype(dtype)
        else:
            c_q = rmsnorm(params["q_norm"], x @ params["wq_a"].astype(dtype),
                          eps)
            q = c_q @ params["wq_b"].astype(dtype)
        q = q.reshape(b, s, n, dn + dr)
        if not cfg.mla_nope:
            q = jnp.concatenate([q[..., :dn], rotary(q[..., dn:])], axis=-1)
    with jax.named_scope("mtpu/mla/latent"):
        down = x @ params["wkv_a"].astype(dtype)            # [b, s, r + dr]
        c = rmsnorm(params["kv_norm"], down[..., :r], eps)
        k_r = rotary(down[:, :, None, r:])[:, :, 0]
        if kv_cache is not None:
            # written at (layer, row, position) of the STACKED buffer, the
            # layer loop's carry: in place, only the new rows move
            new = jnp.concatenate([c, k_r], axis=-1).astype(
                kv_cache.c.dtype).swapaxes(1, 2)[None]       # [1, b, row, s]
            if per_slot:
                # row i writes its s tokens at offset[i]..offset[i]+s-1, one
                # `dynamic_update_slice` a row: a scatter wants the dimension
                # it writes whole (the row's values) minor and the products
                # want the positions minor, and XLA then carries the pool
                # transposed and copies a layer of it back for the products
                # in every pass (compile for the chip, PR 31). A row parked
                # at the capacity clamp with s > 1 has its window's start
                # clamped into the region: garbage over the garbage of a row
                # that is finished.
                def write(i, buf):
                    return jax.lax.dynamic_update_slice(
                        buf, jax.lax.dynamic_slice_in_dim(new, i, 1, axis=1),
                        (cache_layer, i, 0, q_offset[i]))
                stack = jax.lax.fori_loop(0, b, write, kv_cache.c)
            else:
                stack = jax.lax.dynamic_update_slice(
                    kv_cache.c, new, (cache_layer, 0, 0, q_offset))
            # `_replace`: the cache may be `attention.LatentStateCache`, the
            # latent rows beside a state of fixed size
            kv_cache = kv_cache._replace(
                c=stack, offset=jax.lax.dynamic_update_index_in_dim(
                    kv_cache.offset, q_offset + s, cache_layer, 0))

    wkv_b = params["wkv_b"].astype(dtype)
    flash = cfg.attention_impl == "flash"

    def expanded():
        with jax.named_scope("mtpu/mla/attend_expanded"):
            return _attend_expanded(q, c, k_r, wkv_b, cfg, scale,
                                    flash=flash, segment_ids=segment_ids)

    def absorbed():
        # this layer of the buffer AFTER the write: a step's own rows are
        # attended, and the order is a data dependence
        with jax.named_scope("mtpu/mla/attend_absorbed"):
            return _attend_absorbed(
                q, kv_cache.c, cache_layer, wkv_b, cfg, scale,
                (q_offset[:, None] if per_slot else q_offset)
                + jnp.arange(s)[None, :])

    if kv_cache is None:
        out = expanded()
    elif s == 1 or per_slot:
        assert segment_ids is None, "no segments on the cached path"
        out = absorbed()
    else:
        # many positions at a scalar offset: a prefill at offset 0 reads
        # nothing the cache held; a continuation chunk reads it all
        out = jax.lax.cond(q_offset == 0,
                           lambda: expanded().astype(jnp.float32),
                           lambda: absorbed().astype(jnp.float32)
                           ).astype(dtype)
    out = out.reshape(b, s, n * cfg.v_head_dim) @ params["wo"].astype(dtype)
    return out, kv_cache
