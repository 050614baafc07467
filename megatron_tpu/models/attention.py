"""Multi-head / grouped-query / multi-query attention.

TPU-native equivalent of the reference's ParallelAttention + CoreAttention
(ref: megatron/model/transformer.py:280-529 and :144-277). Differences by
design, not omission:

- The reference fuses Q,K,V into one column-parallel matmul with a grouped
  [s,b,groups,q_per_group+2,hd] layout (ref: transformer.py:313-333,440-455)
  because NCCL-sharded checkpoints need contiguous per-rank slices. Under
  GSPMD the parameter layout is decoupled from device layout, so we keep a
  Q projection and a fused KV projection: Q shards over 'heads'→tp and KV over
  'kv_heads'→tp (replicated when kv_heads < tp, the MQA case), which is the
  clean mesh formulation of the reference's GQA broadcast
  (ref: transformer.py:448-455).
- The unfused CoreAttention path (baddbmm into a global memory buffer + fused
  scale-mask-softmax CUDA kernel, ref: transformer.py:191-277 and
  fused_kernels K1-K3) is a single einsum chain here — XLA fuses
  scale+mask+softmax on TPU without a custom kernel. The flash path
  (ref: transformer.py:514-522 flash_attn_func) maps to our Pallas flash
  kernel in megatron_tpu/ops/flash_attention.py.
- KV-cache (`InferenceParams`, ref: megatron/text_generation/forward_step.py:
  17-42, used at transformer.py:402-409,482-495) becomes an explicit
  functional cache pytree, STACKED over layers: the layer loop carries the
  whole stack and each layer appends its tokens at (layer, row, position)
  of that buffer in place, then reads its own layer of it. No layer of the
  cache is ever cut out, updated and written back. Where every row sits at
  its own length (a decode step of the serving grid) and the pool's shape
  allows, the read is the block kernel's, which stops at each row's length
  (ops/block_attention_pallas.py::pool_block_rows says where).
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from megatron_tpu.config import ModelConfig
from megatron_tpu.models.norms import (apply_norm, norm_init, rmsnorm,
                                       rmsnorm_init)
from megatron_tpu.models.rope import apply_rotary
from megatron_tpu.ops.dropout import dropout
from megatron_tpu.ops.quantized import W8, qdense, wcast


class KVCache(NamedTuple):
    """Functional KV cache (ref: InferenceParams, forward_step.py:17-42),
    STACKED over layers: `attention_apply` takes the whole stack and the
    layer's index, writes that layer's new tokens where they live and
    returns the stack (stack_apply carries it through the layer loop).

    dtype=jnp.int8 stores k/v int8 with per-(batch, token, head) fp32
    scales (k_scale/v_scale, amax over head_dim) — decode streams the
    whole cache every step, so int8 halves the bandwidth-bound cache
    read AND the residency: a 7B 32k-context cache (~17 GB bf16) does
    not fit a 16 GB v5e at all until quantized. Entries are quantized at
    write time and dequantized at read — including the current decode
    token's own k/v (one round-trip, same ~0.4% error as the rest of
    the cache); only the offset-0 flash-prefill branch bypasses the
    cache entirely (it reads the raw projections)."""
    k: jax.Array  # [layers, batch, max_seq, n_kv_heads, head_dim]
    v: jax.Array
    # tokens already in cache: [layers] int32 (one position for the whole
    # batch), or PER-ROW [layers, batch] int32 for the serving engine's
    # slot grid (each row decodes at its own length)
    offset: jax.Array
    k_scale: Optional[jax.Array] = None  # [layers, batch, max_seq, n_kv, 1]
    v_scale: Optional[jax.Array] = None  # fp32

    @staticmethod
    def create(layers: int, batch: int, max_seq: int, n_kv: int,
               head_dim: int, dtype=jnp.bfloat16,
               per_slot_offsets: bool = False):
        shape = (layers, batch, max_seq, n_kv, head_dim)
        # normalize: accept "int8" the way cfg dtypes are spelled — the
        # raw `dtype == jnp.int8` would be False for the string while
        # jnp.zeros still allocated int8, leaving scales None (crash at
        # the first cache write)
        quant = jnp.dtype(dtype) == jnp.dtype(jnp.int8)
        return KVCache(
            k=jnp.zeros(shape, dtype=dtype),
            v=jnp.zeros(shape, dtype=dtype),
            offset=jnp.zeros((layers, batch) if per_slot_offsets
                             else (layers,), dtype=jnp.int32),
            k_scale=jnp.ones(shape[:4] + (1,), jnp.float32) if quant else None,
            v_scale=jnp.ones(shape[:4] + (1,), jnp.float32) if quant else None,
        )


class LoraAdapter(NamedTuple):
    """Batched low-rank (LoRA) adapter factors for the q/k/v/o
    projections — the model-facing half of multi-tenant adapter serving
    (serving/adapters.py AdapterBank; S-LoRA / Punica, PAPERS.md).

    Two shapes flow through the same type:
      - STACKED (what the bank holds and stack_apply scans): every leaf
        carries a leading 'layers' dim — [L, n, h, r] for the A factors,
        [L, n, r, out] for the B factors — so the stack scan slices one
        layer's [n, ...] bank per step with the layer's params;
      - PER-LAYER (what attention_apply consumes inside the scan):
        [n, h, r] / [n, r, out].

    `n` is the bank capacity (adapter slots + 1); ROW 0 IS THE IDENTITY
    adapter (all-zero factors), so base-model requests ride the same
    batched gather + matmul trace with a zero delta — adapter indices
    are DATA, like the KV block map, and the decode/verify/prefill
    programs keep one compile each. Scaling (alpha / rank) is folded
    into the B factors at load time, so apply-time math is just
    x @ A[idx] @ B[idx] added to the base projection."""
    aq: jax.Array  # [.., n, h, r]
    bq: jax.Array  # [.., n, r, nq*hd]
    ak: jax.Array  # [.., n, h, r]
    bk: jax.Array  # [.., n, r, nkv*hd]
    av: jax.Array  # [.., n, h, r]
    bv: jax.Array  # [.., n, r, nkv*hd]
    ao: jax.Array  # [.., n, nq*hd, r]
    bo: jax.Array  # [.., n, r, h]


class BlockKVCache(NamedTuple):
    """Block-NATIVE serving cache: the flat block arena plus the
    per-slot block map, consumed directly by the Pallas block-native
    decode-attention kernel (ops/block_attention_pallas.py) — no
    contiguous [S, cap, ...] view is ever materialized (the
    resolve_view/scatter_view bracket in serving/kv_pool.py is exactly
    what this type exists to delete from the decode hot path).

    STACKED over layers like KVCache (the map is broadcast over layers
    by serving/kv_pool.block_native_cache); attention_apply takes the
    stack and the layer's index:

      k/v:     [L, total_blocks, B, nkv, hd]  flat arena (int8 for
                                              quantized pools)
      offset:  [L, num_slots] int32           per-slot live lengths
      map:     [L, num_slots, cap/B] int32    logical -> physical block
      k_scale/v_scale: [L, total_blocks, B, nkv, 1] fp32 (int8 pools)

    attention_apply recognizes this type and takes the block-native
    path: the step's k/v scatter ONLY into the touched arena blocks
    (O(slots * tokens) bytes, not O(pool)), and the attention read
    walks each slot's block chain through the map inside the kernel.
    Causal self-attention with per-slot vector offsets only; ROLLING
    (ring) layouts are excluded — the engine keeps the view bracket
    for those."""
    k: jax.Array
    v: jax.Array
    offset: jax.Array
    map: jax.Array
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None


class HybridKVCache(NamedTuple):
    """The cache of a stack that mixes window and full attention
    (`cfg.window_layer_period`): two stacks side by side, each carried
    through the layer loop and written in place as `KVCache` is.

    - RINGS for the window layers, [window layers, batch, n_kv, ring, hd]
      with ring = min(sliding_window, max_seq): position p of a sequence
      lives in row p % ring, so a ring holds the last `ring` positions and
      nothing else, whatever the sequence's length.
    - WHOLE REGIONS for the full layers, [full layers, batch, n_kv, max_seq,
      hd]: position p in row p.

    HEADS before rows, where `KVCache` has rows before heads: the products
    contract the head's channels and batch over (sequence, kv head), so this
    is the order they read, and the flash kernel's too. Held the other way,
    with 8 kv heads, the chip's compiler copied each layer of the pool into
    this order in every decode step (1 GiB a full layer of 16 slots x
    32,768, twice; compile, PR 33), as it copied the latent pool in PR 31.

    Nothing is read from the order rows are stored in: every mask is made
    from positions, and those from `offset`. A multi-token step at a scalar
    offset (a prefill, a chunk of one) reads a window layer's ring as it
    stood BEFORE its own rows (turned into time order, `jnp.roll`) beside
    its own fresh k and v, and only then writes its rows over the oldest;
    it writes a full layer's region first and reads it back, as `KVCache`
    does. A decode step (one token a row, at each row's own offset) writes
    and then reads, both kinds.

    `live_end` (scalar or [batch]): positions at and past it are a bucket's
    padding. A padded row may lie in a REGION, beyond the offset, until it
    is overwritten; written into a RING it would land on a row the next
    real token still reads, so ring writes stop at `live_end`
    (generation.prefill_chunk and the engine's prefill set it; it is
    `NO_PADDING` wherever all the rows given are real)."""
    ring_k: jax.Array
    ring_v: jax.Array
    full_k: jax.Array
    full_v: jax.Array
    # tokens already in the cache, one entry a layer of the MODEL (window
    # and full layers in their own order): [layers] or [layers, batch], as
    # KVCache.offset
    offset: jax.Array
    live_end: jax.Array

    NO_PADDING = 2 ** 30

    @staticmethod
    def create(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16, per_slot_offsets: bool = False):
        periods = cfg.num_layers // cfg.window_layer_period
        n_win = periods * cfg.window_layers_per_period
        ring = min(cfg.sliding_window, max_seq)
        nkv, hd = cfg.num_kv_heads, cfg.kv_channels
        return HybridKVCache(
            ring_k=jnp.zeros((n_win, batch, nkv, ring, hd), dtype),
            ring_v=jnp.zeros((n_win, batch, nkv, ring, hd), dtype),
            full_k=jnp.zeros((periods, batch, nkv, max_seq, hd), dtype),
            full_v=jnp.zeros((periods, batch, nkv, max_seq, hd), dtype),
            offset=jnp.zeros((cfg.num_layers, batch) if per_slot_offsets
                             else (cfg.num_layers,), jnp.int32),
            live_end=jnp.int32(HybridKVCache.NO_PADDING))


class ConvKVCache(NamedTuple):
    """The cache of a model whose layers are convolutions or state-space
    mixers and attention (`cfg.layer_types`): two or three kinds of state
    side by side, each carried through the layer loop and written in place
    at its own kind's index.

    - KEYS AND VALUES for the attention layers alone, [attention layers,
      batch, max_seq, n_kv * hd]: a position's row holds every kv head's
      channels side by side (`_folded_update_attend` says why the heads'
      axis is folded away: with heads of 64 channels the device keeps
      neither `KVCache`'s order nor `HybridKVCache`'s in place).
    - THE CONVOLUTIONS' STATE, [conv layers, batch, conv_L_cache - 1,
      hidden]: the last inputs of the depthwise kernel (`a = B * z`,
      models/short_conv.py), the older first. It costs the same whatever
      the sequence's length, and no mask hides it: whoever takes a slot
      writes the whole of its state. A "mamba" layer's kernel has
      `mamba_d_conv` - 1 rows over d_inner channels (models/mamba.py).
    - THE SCANS' STATE (`ssm`; None where the model has no "mamba" layer),
      [mamba layers, batch, d_state, d_inner] float32 whatever the cache's
      dtype: the recurrence's matrix a layer a sequence, the channels minor
      (ops/selective_scan.py says why). A "mamba2" layer's is a matrix a
      HEAD, [mamba2 layers, batch, heads, head_dim, d_state]
      (models/mamba2.py, ops/ssd_scan.py), over a depthwise state of
      d_inner + 2 groups x d_state channels; the layers that are a
      feed-forward alone hold nothing. Fixed in size and hidden by no mask,
      as the convolutions' is.

    `live_rows` (scalar or [batch]): how many of the rows given to THIS call
    are real; those behind them are a bucket's padding. Keys and values of
    padding rows lie beyond the offset until they are overwritten; a state
    taken at the end of the rows would be the state after the padding, so a
    convolution layer leaves the state as it stood after row `live_rows` -
    1 (the engine's prefill and generation.prefill_chunk set it; it is
    `NO_PADDING` wherever all the rows given are real)."""
    k: jax.Array       # [attention layers, batch, max_seq, n_kv * head_dim]
    v: jax.Array
    conv: jax.Array    # [state layers, batch, taps - 1, channels]
    # tokens already in the cache, one entry an ATTENTION layer: [layers]
    # or [layers, batch], as KVCache.offset
    offset: jax.Array
    live_rows: jax.Array
    # [mamba layers, batch, d_state, d_inner], or
    # [mamba2 layers, batch, heads, head_dim, d_state]
    ssm: Optional[jax.Array] = None

    NO_PADDING = 2 ** 30

    @staticmethod
    def create(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16, per_slot_offsets: bool = False):
        n_attn = cfg.layers_of("full_attention")
        kv = (n_attn, batch, max_seq, cfg.num_kv_heads * cfg.kv_channels)
        return ConvKVCache(
            k=jnp.zeros(kv, dtype), v=jnp.zeros(kv, dtype),
            conv=jnp.zeros((cfg.state_layers, batch,
                            *cfg.conv_state_shape), dtype),
            offset=jnp.zeros((n_attn, batch) if per_slot_offsets
                             else (n_attn,), jnp.int32),
            live_rows=jnp.int32(ConvKVCache.NO_PADDING),
            ssm=(jnp.zeros((cfg.state_layers, batch, *cfg.ssm_state_shape),
                           jnp.float32)
                 if cfg.ssm_state_shape else None))


class LatentStateCache(NamedTuple):
    """The cache of a model whose layers are Kimi Delta Attention mixers and
    MLA attention (`cfg.layer_types` "kda" | "full_attention" with
    `cfg.mla`): `ConvKVCache`'s sibling with LATENT ROWS in the place of keys
    and values, three parts side by side, each written in place at its own
    kind's index.

    - THE LATENT ROWS of the attention layers alone, `c` [attention layers,
      batch, kv_lora_rank + qk_rope, max_seq], the positions minor:
      `mla.LatentKVCache.c`'s layout and readers (models/mla.py reads and
      writes `c` and `offset` of either cache).
    - THE DEPTHWISE KERNELS' STATE, `conv` [kda layers, batch, taps - 1, 3 x
      heads x head_dim]: the last inputs of the three kernels over q, k and
      v, the older first, in the cache's dtype.
    - THE RULE'S STATE, `ssm` [kda layers, batch, heads, head_dim, head_dim]
      float32 whatever the cache's dtype: a matrix a head (models/kda.py,
      ops/kda_chunk.py).

    `offset` (one entry an ATTENTION layer) and `live_rows` are
    `ConvKVCache`'s."""
    c: jax.Array
    conv: jax.Array
    ssm: jax.Array
    offset: jax.Array
    live_rows: jax.Array

    @staticmethod
    def create(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16, per_slot_offsets: bool = False):
        n_attn = cfg.layers_of("full_attention")
        return LatentStateCache(
            c=jnp.zeros((n_attn, batch, cfg.kv_row_width, max_seq), dtype),
            conv=jnp.zeros((cfg.state_layers, batch,
                            *cfg.conv_state_shape), dtype),
            ssm=jnp.zeros((cfg.state_layers, batch, *cfg.ssm_state_shape),
                          jnp.float32),
            offset=jnp.zeros((n_attn, batch) if per_slot_offsets
                             else (n_attn,), jnp.int32),
            live_rows=jnp.int32(ConvKVCache.NO_PADDING))


LANES = 128     # the channels of one lane tile


def _layer_of(a, layer):
    """Layer `layer` (a traced scalar) of an array stacked over layers."""
    return jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)


def _block_native_update_attend(q, k, v, stacked: BlockKVCache, layer, *,
                                scale: float, dtype):
    """Block-native KV append + kernel attention for layer `layer` of the
    stacked arena; returns (out, the stacked cache).

    Append: row i's s tokens land at positions offset[i]..offset[i]+s-1
    — physical block map[i, pos // B], in-block slot pos % B — as ONE
    scatter into the stacked arena itself, touching only the written
    blocks of this layer (`mode="drop"` vanishes writes past the region
    for rows parked at the capacity clamp, the same contract as the
    contiguous per-slot scatter). Idle rows (map parked on the shared
    TRASH block) write their garbage there, exactly where scatter_view
    used to land it.

    Read: the Pallas kernel walks the map: q attends each slot's
    block-chained K/V causally from its own offset, dequantizing int8 in
    kernel. It takes the arena STACKED over layers and the layer's index,
    AFTER the append (write-before-read by data dependence): no layer of
    the arena is cut out of the stack (the scales, a thirty-second of the
    payload, are: ops/block_attention_pallas.py says why), and nothing is
    written back."""
    from megatron_tpu.ops.block_attention_pallas import \
        block_native_attention
    S, s, nq, hd = q.shape
    _, T, B, nkv, _ = stacked.k.shape
    bmap = _layer_of(stacked.map, layer)
    nb = bmap.shape[1]
    cap = nb * B
    offset = _layer_of(stacked.offset, layer)
    pos = offset[:, None] + jnp.arange(s)[None, :]          # [S, s]
    blk_log = jnp.minimum(pos // B, nb - 1)
    phys = jnp.take_along_axis(bmap, blk_log, axis=1)       # [S, s]
    # out-of-region writes (idle rows at the clamp with s > 1) index
    # past the arena and are DROPPED — never wrap, never collide
    phys = jnp.where(pos >= cap, jnp.int32(T), phys)
    inblk = pos % B

    def wr(arena, val):
        return arena.at[layer, phys, inblk].set(val.astype(arena.dtype),
                                                mode="drop")

    quant = stacked.k.dtype == jnp.int8
    ks = vs = None
    if quant:
        from megatron_tpu.ops.quantized import quantize_rows
        k, ks = quantize_rows(k)  # per (slot, token, head) scales
        v, vs = quantize_rows(v)
    stacked = stacked._replace(
        k=wr(stacked.k, k), v=wr(stacked.v, v),
        k_scale=wr(stacked.k_scale, ks) if quant else None,
        v_scale=wr(stacked.v_scale, vs) if quant else None,
        offset=jax.lax.dynamic_update_index_in_dim(
            stacked.offset, offset + s, layer, 0))
    args = [q, stacked.k, stacked.v, bmap, offset,
            jnp.asarray(layer, jnp.int32)]
    if quant:
        args += [_layer_of(stacked.k_scale, layer),
                 _layer_of(stacked.v_scale, layer)]

    def _kern(q_, k_, v_, m_, off_, layer_, ks_=None, vs_=None):
        return block_native_attention(
            q_, k_, v_, m_, off_, scale=scale, layer=layer_,
            k_scale=ks_, v_scale=vs_)
    # TP-sharded serving (serving/topology.py): XLA cannot partition a
    # custom call, so with a tp mesh active the kernel runs under an
    # explicit shard_map on the head-sharded arena — each tp shard
    # walks its OWN nkv/tp kv heads' block chains (the head loop
    # shrinks per shard; attention is per-head independent, so no
    # collective inside). Single-device traces (mesh None) lower the
    # bare call.
    from megatron_tpu.parallel.sharding import active_tp_mesh
    mesh = active_tp_mesh()
    if mesh is None:
        out = _kern(*args)
    else:
        from jax.sharding import PartitionSpec as P
        from megatron_tpu.parallel.mesh import TENSOR_AXIS
        tp = mesh.shape[TENSOR_AXIS]
        assert nq % tp == 0 and nkv % tp == 0, (
            f"block_native_attn under serving_tp={tp} needs query "
            f"({nq}) and kv ({nkv}) head counts divisible by tp — "
            "serve with the resolve/scatter bracket instead "
            "(ServingConfig.validate rejects this combination)")
        h_spec = P(None, None, TENSOR_AXIS, None)
        stack_spec = P(None, *h_spec)
        out = jax.shard_map(
            _kern, mesh=mesh,
            in_specs=(h_spec, stack_spec, stack_spec, P(), P(), P())
            + (h_spec, h_spec) * quant,
            out_specs=h_spec, check_vma=False)(*args)
    return out.astype(dtype), stacked


def _attend_heads_major(q, k, v, q_pos, kv_pos, *, scale, window,
                        softmax_fp32: bool):
    """Unfused causal attention over keys held HEADS-MAJOR, as
    `HybridKVCache` holds them: q [b, s, nq, hd], k/v [b, nkv, t, hd], q_pos
    [b|1, s], kv_pos [b|1, t] (a row that holds nothing: a position no
    query reaches) -> [b, s, nq, hd]. `_dot_attention` with the keys' two
    middle axes the other way round, so that a layer of the cache is read
    where it lies."""
    b, s, nq, hd = q.shape
    nkv = k.shape[1]
    qg = q.reshape(b, s, nkv, nq // nkv, hd)
    scores = jnp.einsum("bsngd,bntd->bngst", qg, k) * scale
    if softmax_fp32:
        scores = scores.astype(jnp.float32)
    mask = q_pos[:, :, None] >= kv_pos[:, None, :]          # [b|1, s, t]
    if window is not None:
        mask = mask & (q_pos[:, :, None] - kv_pos[:, None, :] < window)
    scores = jnp.where(mask[:, None, None], scores,
                       jnp.finfo(scores.dtype).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bngst,bntd->bsngd", probs, v)
    return out.reshape(b, s, nq, hd)


def _hybrid_update_attend(q, k, v, cache: HybridKVCache, layer, kind_layer,
                          cfg: ModelConfig, *, scale: float):
    """Append this layer's k and v to its stack of `cache` and attend; `cfg`
    is the layer's own kind (`ModelConfig.window_layers()` with its
    `sliding_window`, or `.full_layers()` with none), `layer` the layer's
    index in the model (its offset) and `kind_layer` in its kind's stack.
    q [b, s, nq, hd], k/v [b, s, nkv, hd] -> (out [b, s, nq, hd], cache).
    The three cases are the class docstring's."""
    b, s = q.shape[:2]
    dtype = q.dtype
    window = cfg.sliding_window
    is_window = window is not None
    buf_k, buf_v = ((cache.ring_k, cache.ring_v) if is_window
                    else (cache.full_k, cache.full_v))
    cap = buf_k.shape[3]
    offset = _layer_of(cache.offset, layer)
    per_slot = jnp.ndim(offset) == 1
    # what the cache will hold of the new rows: the step attends the values
    # a later step will read back
    k = k.astype(buf_k.dtype)
    v = v.astype(buf_v.dtype)
    rows = jnp.arange(b)[:, None]
    flash = cfg.attention_impl == "flash"
    fp32 = cfg.attention_softmax_in_fp32
    NOWHERE = jnp.int32(2 ** 30)       # a position no query reaches

    def scatter(at):
        # at [b|1, s]: the row of the buffer each new token goes to; `cap`
        # and beyond is nowhere (mode="drop"). The heads' axis lies between
        # the indexed ones, so the value is [b, s, nkv, hd], as k and v are
        def wr(buf, val):
            return buf.at[kind_layer, rows, :, at].set(val, mode="drop")
        return wr(buf_k, k), wr(buf_v, v)

    def heads_major(x):                 # [b, t, nkv, hd] -> [b, nkv, t, hd]
        return x.transpose(0, 2, 1, 3)

    if per_slot:
        assert s == 1, (
            "a pool of rings and regions takes one token a row a step: a "
            "verify window's rejected rows would have overwritten ring "
            "rows that the rewind needs (ServingConfig.validate refuses "
            "speculative_k)")
        pos = offset[:, None]                                   # [b, 1]
        at = offset % cap if is_window else offset

        # one `dynamic_update_slice` a row, as models/mla.py writes its
        # pool: a scatter wants the rows' axis major and the products want
        # the heads' axis major, and the chip's compiler then copies the
        # whole pool into the scatter's order and back in every step (2.7
        # GiB of temporaries; compile, PR 33). The engine keeps a parked
        # row's length inside the region, so no start is clamped onto a
        # live row.
        def write(buf, val):
            val = val.transpose(0, 2, 1, 3)[None]        # [1, b, nkv, 1, hd]
            for i in range(b):      # unrolled: b is the grid's static size
                buf = jax.lax.dynamic_update_slice(
                    buf, val[:, i:i + 1], (kind_layer, i, 0, at[i], 0))
            return buf
        new_k, new_v = write(buf_k, k), write(buf_v, v)
        if is_window:
            # row j holds the latest position p <= offset with p % cap == j
            p = pos - ((pos - jnp.arange(cap)[None, :]) % cap)  # [b, cap]
            kv_pos = jnp.where(p >= 0, p, NOWHERE)
        else:
            kv_pos = jnp.arange(cap)[None, :]
        out = _attend_heads_major(
            q, _layer_of(new_k, kind_layer).astype(dtype),
            _layer_of(new_v, kind_layer).astype(dtype), pos, kv_pos,
            scale=scale, window=window, softmax_fp32=fp32)
    elif is_window:
        # the ring BEFORE this step's rows, in time order: index i holds
        # position offset - cap + i (nothing where that is negative)
        shift = offset % cap
        keys = jnp.concatenate(
            [jnp.roll(_layer_of(buf_k, kind_layer), -shift, axis=2),
             heads_major(k)], axis=2).astype(dtype)
        vals = jnp.concatenate(
            [jnp.roll(_layer_of(buf_v, kind_layer), -shift, axis=2),
             heads_major(v)], axis=2).astype(dtype)
        pos = offset + jnp.arange(s)[None, :]                   # [1, s]
        if flash:
            from megatron_tpu.ops.flash_attention import flash_attention
            out = flash_attention(q, keys, vals, causal=True, scale=scale,
                                  sliding_window=window, q_offset=cap,
                                  kv_start=jnp.maximum(cap - offset, 0),
                                  kv_heads_major=True)
        else:
            kv_pos = jnp.arange(cap + s) + (offset - cap)
            out = _attend_heads_major(
                q, keys, vals, pos,
                jnp.where(kv_pos >= 0, kv_pos, NOWHERE)[None, :],
                scale=scale, window=window, softmax_fp32=fp32)
        # then the new rows go over the oldest: the last `cap` real ones
        end = jnp.minimum(jnp.reshape(cache.live_end, (-1, 1)), offset + s)
        new_k, new_v = scatter(jnp.where((pos < end) & (pos >= end - cap),
                                         pos % cap, cap))
    else:
        def wr(buf, val):
            return jax.lax.dynamic_update_slice(
                buf, heads_major(val)[None], (kind_layer, 0, 0, offset, 0))
        new_k, new_v = wr(buf_k, k), wr(buf_v, v)
        keys = _layer_of(new_k, kind_layer).astype(dtype)
        vals = _layer_of(new_v, kind_layer).astype(dtype)
        if flash:
            from megatron_tpu.ops.flash_attention import flash_attention
            out = flash_attention(q, keys, vals, causal=True, scale=scale,
                                  q_offset=offset, kv_heads_major=True)
        else:
            out = _attend_heads_major(
                q, keys, vals, offset + jnp.arange(s)[None, :],
                jnp.arange(cap)[None, :], scale=scale, window=None,
                softmax_fp32=fp32)
    cache = cache._replace(
        offset=jax.lax.dynamic_update_index_in_dim(
            cache.offset, offset + s, layer, 0),
        **({"ring_k": new_k, "ring_v": new_v} if is_window
           else {"full_k": new_k, "full_v": new_v}))
    return out.astype(dtype), cache


def _folded_update_attend(q, k, v, cache: ConvKVCache, layer,
                          cfg: ModelConfig, *, scale: float):
    """Append this layer's k and v to the keys and values of a `ConvKVCache`
    and attend. q [b, s, nq, hd], k/v [b, s, nkv, hd] -> (out [b, s, nq, hd],
    cache); `layer` is the layer's index among the attention layers.

    The pool holds a position's row as [n_kv * hd] values, the kv heads'
    channels side by side, and the products are taken over that whole row:
    head h's query is laid into its own kv head's channels of a row of
    zeros, the scores are one product of [heads, n_kv * hd] with the rows
    (every other head's channels meet zeros), the weighted sum one product
    of [heads, positions] with the rows, of which each head keeps its own
    kv head's channels. That is n_kv times the operations (8 here, tens of
    GFLOP a decode step of 128 slots: a fraction of a millisecond on the
    matrix unit) for rows the device holds in the order they are written and
    read. Why not a heads' axis: with heads of 64 channels the device lays
    [.., max_seq, hd] out with max_seq minor (64 values would pad a tile of
    128 lanes), in `KVCache`'s order and `HybridKVCache`'s alike; the chip's
    compiler then kept the pool in a third order through the layer loop
    (slots minor, for the writes) and copied all of it in and out of that
    order in every decode step, and a layer of it into the products' order
    in every layer: 13.8 GiB counted for the decode program, 3.7 of it
    temporaries, where this form counts none (compile, PR 37).

    A step writes first and reads the layer back, as `KVCache` does: every
    slot at its own offset through one scatter, or the rows of a prefill or a
    chunk at the scalar offset. A prefill at offset 0 under
    `attention_impl="flash"` attends its own fresh k and v through the flash
    kernel, as `KVCache`'s does (`lax.cond` on the offset: a continuation
    chunk takes the products over the region). ONE kv head, or several of
    whole lane tiles each, are read by the flash kernel out of the folded
    rows at whatever offset the chunk has."""
    b, s, nq, hd = q.shape
    nkv = k.shape[2]
    width, group = nkv * hd, nq // nkv
    dtype = q.dtype
    offset = _layer_of(cache.offset, layer)
    per_slot = jnp.ndim(offset) == 1
    # what the cache will hold of the new rows: the step attends the values
    # a later step will read back
    k_new = k.reshape(b, s, width).astype(cache.k.dtype)
    v_new = v.reshape(b, s, width).astype(cache.v.dtype)
    if per_slot:
        pos = offset[:, None] + jnp.arange(s)[None, :]            # [b, s]
        rows = jnp.arange(b)[:, None]

        def wr(buf, val):       # past the region: nowhere (a parked row)
            return buf.at[layer, rows, pos].set(val, mode="drop")
    else:
        pos = (offset + jnp.arange(s))[None, :]                   # [1, s]

        def wr(buf, val):
            return jax.lax.dynamic_update_slice(buf, val[None],
                                                (layer, 0, offset, 0))
    new_k, new_v = wr(cache.k, k_new), wr(cache.v, v_new)
    cache = cache._replace(
        k=new_k, v=new_v, offset=jax.lax.dynamic_update_index_in_dim(
            cache.offset, offset + s, layer, 0))

    def over_the_region():
        keys = _layer_of(new_k, layer).astype(dtype)          # [b, t, width]
        vals = _layer_of(new_v, layer).astype(dtype)
        own = (jnp.arange(nq)[:, None] // group
               == jnp.arange(nkv)[None, :])                   # [nq, nkv]
        q_wide = (q[..., None, :] * own[:, :, None].astype(dtype)).reshape(
            b, s, nq, width)
        scores = jnp.einsum("bshc,btc->bhst", q_wide, keys) * scale
        if cfg.attention_softmax_in_fp32:
            scores = scores.astype(jnp.float32)
        mask = pos[:, :, None] >= jnp.arange(keys.shape[1])[None, None, :]
        scores = jnp.where(mask[:, None], scores,
                           jnp.finfo(scores.dtype).min)
        probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
        wide = jnp.einsum("bhst,btc->bshc", probs, vals).reshape(
            b, s, nq, nkv, hd)
        return jnp.sum(jnp.where(own[:, :, None], wide, 0), axis=3)

    if cfg.attention_impl == "flash" and s > 1 and not per_slot:
        from megatron_tpu.ops.flash_attention import flash_attention
        if nkv == 1:
            # ONE kv head: the folded row IS a head's row, [b, 1, t, hd]
            # is the pool as the kernel reads it, and a prefill or a chunk
            # attends the region it has just been written into through the
            # kernel at its offset, as `HybridKVCache`'s full layers do.
            # The products over the region would hold [heads, s, max_seq]
            # scores, in both arms of a `cond`: 5.4 GB in float32 for a
            # 2,048-row chunk of 20 heads over 32,768 positions
            out = flash_attention(
                q, _layer_of(new_k, layer)[:, None].astype(dtype),
                _layer_of(new_v, layer)[:, None].astype(dtype),
                causal=True, scale=scale, q_offset=offset,
                kv_heads_major=True)
        elif hd % LANES == 0:
            # SEVERAL kv heads of whole lane tiles: kv head g's channels of
            # the folded row are a block the kernel can index, so the pool
            # is read where it lies, at the chunk's offset, and no [heads,
            # s, max_seq] scores are made (16 x 4,096 x 32,768 float32 =
            # 8.6 GB at Qwen3-Next's widths)
            out = flash_attention(
                q, _layer_of(new_k, layer).astype(dtype),
                _layer_of(new_v, layer).astype(dtype),
                causal=True, scale=scale, q_offset=offset, kv_folded=nkv)
        else:
            out = jax.lax.cond(
                offset == 0,
                lambda: flash_attention(q, k, v, causal=True, scale=scale),
                over_the_region)
    else:
        out = over_the_region()
    return out.astype(dtype), cache


def attention_init(rng, cfg: ModelConfig, dtype=jnp.float32):
    """Params: wq [h, nq*hd] (`cfg.attn_output_gate`: [h, nq*2*hd], a
    head's query and its gate side by side), wkv [h, 2*nkv*hd], wo [nq*hd,
    h]."""
    h = cfg.hidden_size
    hd = cfg.kv_channels
    nq = cfg.num_attention_heads
    nkv = cfg.num_kv_heads
    k1, k2, k3 = jax.random.split(rng, 3)
    std = cfg.init_method_std
    out_std = std / math.sqrt(2.0 * cfg.num_layers) if cfg.use_scaled_init else std
    q_cols = nq * hd * (2 if cfg.attn_output_gate else 1)
    params = {
        "wq": jax.random.normal(k1, (h, q_cols), dtype) * std,
        "wkv": jax.random.normal(k2, (h, 2 * nkv * hd), dtype) * std,
        "wo": jax.random.normal(k3, (nq * hd, h), dtype) * out_std,
    }
    if cfg.use_bias:
        params["bq"] = jnp.zeros((nq * hd,), dtype)
        params["bkv"] = jnp.zeros((2 * nkv * hd,), dtype)
        params["bo"] = jnp.zeros((h,), dtype)
    if cfg.qk_norm:
        params["q_norm"] = rmsnorm_init(nq * hd, dtype)
        params["k_norm"] = rmsnorm_init(nkv * hd, dtype)
    if cfg.qk_head_norm:
        # a zero-centred scale where the model's norms are ("rmsnorm_1p")
        params["q_norm"] = norm_init(_head_norm_type(cfg), hd, dtype)
        params["k_norm"] = norm_init(_head_norm_type(cfg), hd, dtype)
    return params


def attention_axes(cfg: ModelConfig):
    axes = {
        "wq": ("embed", "heads"),
        "wkv": ("embed", "kv_heads"),
        "wo": ("heads", "embed"),
    }
    if cfg.use_bias:
        axes.update({"bq": ("heads",), "bkv": ("kv_heads",), "bo": ("embed",)})
    if cfg.qk_norm:
        axes.update({"q_norm": {"scale": ("heads",)},
                     "k_norm": {"scale": ("kv_heads",)}})
    if cfg.qk_head_norm:
        axes.update({"q_norm": {"scale": (None,)},
                     "k_norm": {"scale": (None,)}})
    return axes


def qk_norm(params, q, k, eps: float):
    """OLMoE's q_norm / k_norm: RMSNorm over ALL channels of the q
    projection and of the k projection (every head together), before the
    rotary. q: [b, s, nq, hd], k: [b, t, nkv, hd], split into heads
    already (the fused kv projection and the LoRA deltas are); the
    statistic runs over the last two axes flattened. Training, prefill,
    chunked prefill and decode all come through `attention_apply`, so
    this is the one place."""
    with jax.named_scope("mtpu/attn/qk_norm"):
        def whole(p, x):
            flat = x.reshape(*x.shape[:-2], x.shape[-2] * x.shape[-1])
            return rmsnorm(p, flat, eps).reshape(x.shape)
        return whole(params["q_norm"], q), whole(params["k_norm"], k)


def _head_norm_type(cfg: ModelConfig) -> str:
    return "rmsnorm_1p" if cfg.norm_type == "rmsnorm_1p" else "rmsnorm"


def qk_head_norm(params, q, k, eps: float, norm_type: str = "rmsnorm"):
    """LFM2's q_layernorm / k_layernorm: RMSNorm over EACH head's channels,
    one scale [head_dim] shared by the heads, before the rotary. q: [b, s,
    nq, hd], k: [b, t, nkv, hd]. `norm_type` "rmsnorm_1p": the scale is
    zero-centred, 1 + w (Qwen3-Next's q_norm / k_norm)."""
    with jax.named_scope("mtpu/attn/head_norm"):
        return (apply_norm(norm_type, params["q_norm"], q, eps),
                apply_norm(norm_type, params["k_norm"], k, eps))


def _gated(out, gate):
    """The attention's output gate (`cfg.attn_output_gate`): out [b, s, nq *
    hd] times sigmoid of the gate the query's projection carried."""
    if gate is None:
        return out
    with jax.named_scope("mtpu/attn/gate"):
        return (out.astype(jnp.float32)
                * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(out.dtype)


def _dot_attention(q, k, v, *, causal: bool, softmax_fp32: bool,
                   scale: float, q_offset=None, dropout_rate: float = 0.0,
                   dropout_rng=None, segment_ids=None,
                   sliding_window=None, kv_positions=None):
    """Unfused attention: einsum QK^T -> mask -> softmax -> einsum AV.

    q: [b, s, nq, hd]; k, v: [b, t, nkv, hd]. GQA handled by reshaping q into
    [b, s, nkv, q_per_kv, hd] (equivalent of the reference's kv broadcast at
    transformer.py:448-455, but without materializing the broadcast).
    `q_offset` (scalar) shifts the causal mask for incremental decoding.
    `segment_ids` [b, s] makes the mask block-diagonal across EOD-separated
    documents (ref: --reset_attention_mask, megatron/utils.py:137-194)."""
    b, s, nq, hd = q.shape
    t, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    qg = q.reshape(b, s, nkv, g, hd)
    scores = jnp.einsum("bsngd,btnd->bngst", qg, k) * scale
    if softmax_fp32:
        scores = scores.astype(jnp.float32)
    # sliding_window is a refinement OF the causal mask; non-causal
    # callers must not set it (attention_apply asserts), so the gate
    # stays causal-or-segments
    if causal or segment_ids is not None:
        if causal:
            q_pos = jnp.arange(s)[None, :]  # [1, s]
            if q_offset is not None:
                # scalar offset (one sequence position for the whole
                # batch) or PER-ROW [b] offsets (the serving engine's
                # slot grid, where every row decodes at its own length)
                off = (q_offset[:, None] if jnp.ndim(q_offset) == 1
                       else q_offset)
                q_pos = q_pos + off  # [b|1, s]
            # kv_positions: the ROLLING cache's slot->position map (slot
            # order is not time order), [t] shared or [b, t] per-row;
            # default is the contiguous layout
            if kv_positions is not None:
                kv_pos = (kv_positions if kv_positions.ndim == 2
                          else kv_positions[None, :])
            else:
                kv_pos = jnp.arange(t)[None, :]  # [1, t]
            win = (q_pos[:, :, None] >= kv_pos[:, None, :])  # [b|1, s, t]
            if sliding_window is not None:
                # banded causal: attend at most the previous W positions
                win = win & (q_pos[:, :, None] - kv_pos[:, None, :]
                             < sliding_window)
            mask = jnp.broadcast_to(win, (b, s, t))
        else:
            mask = jnp.ones((b, s, t), bool)
        if segment_ids is not None:
            assert s == t, "segment masking requires full (non-cached) attn"
            mask = mask & (segment_ids[:, :, None] == segment_ids[:, None, :])
        scores = jnp.where(mask[:, None, None], scores, jnp.finfo(scores.dtype).min)
        # fully-masked rows (e.g. pad queries in their own segment... none
        # here since a pad attends itself) would softmax to NaN; segments
        # always include self so every row keeps >=1 valid entry
    probs = jax.nn.softmax(scores, axis=-1)
    probs = probs.astype(v.dtype)
    if dropout_rate > 0.0 and dropout_rng is not None:
        probs = dropout(dropout_rng, probs, dropout_rate)
    out = jnp.einsum("bngst,btnd->bsngd", probs, v)
    return out.reshape(b, s, nq, hd)


def _project(x, w, cfg: ModelConfig, *, read_once: bool):
    """x [b, t, d] through a projection's weight, cast as `wcast` casts it.

    Where the program reads the weight once (`read_once`: it carries a KV
    cache) and the weight is held wider than x, the product is made a
    product of its own: rows flattened to [b * t, d], a barrier after it.
    `wcast` then rounds the layer's slice on the way into the product, and
    that holds only while the compiler sees a plain matrix product. Left
    free it carries what comes after back into the weight: the rotary's
    view of adjacent pairs (a decode step copied wq into [heads, hd / 2, 2,
    h] order, three passes over it) and, for a prefill of two prompts, the
    residual's rows-minor layout of [b, t, h], which a float32 matrix of
    4,544 columns cannot be read in without a transposed copy of the slice
    (20 bytes a weight where the lifted cast cost 8 and this form 4;
    compile for v5e, PR 34: benchmark/fit.py). A weight already in x's
    dtype, or int8, takes the plain product: its program is unchanged."""
    wn = wcast(w, x.dtype, read_once=read_once)
    if not read_once or isinstance(w, W8) or w.dtype == x.dtype:
        return qdense(x, wn, cfg.quantized_gemm)
    b, t, d = x.shape
    y = jax.lax.optimization_barrier(
        qdense(x.reshape(b * t, d), wn, cfg.quantized_gemm))
    return y.reshape(b, t, *y.shape[1:])


def attention_apply(
    params,
    x,
    cfg: ModelConfig,
    *,
    rope_cos=None,
    rope_sin=None,
    position_ids=None,
    kv_cache: Optional[KVCache] = None,
    cache_layer=None,
    layer_number: int = 1,
    dropout_rng=None,
    deterministic: bool = True,
    segment_ids=None,
    causal: bool = True,
    kv_input=None,
    cp_pre_zigzag: bool = False,
    adapters=None,
    kind_layer=None,
):
    """Forward pass. x: [b, s, h]. Returns (out [b, s, h], new_kv_cache).

    `kind_layer`: with a `HybridKVCache` (a stack of window and full
    layers; `cfg` is then the layer's own kind) or a `ConvKVCache`
    (convolution and attention layers), the layer's index in its kind's
    stack, beside `cache_layer`, its index in the model.

    `kv_cache` is the cache STACKED over layers (KVCache or BlockKVCache)
    and `cache_layer` this layer's index in it (a traced scalar inside
    stack_apply's loop). The layer's new k/v are written into the stack
    where they live and the stack is returned: the loop carries one
    buffer, updated in place, and no layer of it is copied out or back.

    `causal=False` gives a bidirectional encoder (BERT/T5-encoder,
    ref: megatron/model/transformer.py AttnMaskType.padding).
    `kv_input` switches to CROSS-attention: keys/values projected from the
    encoder output, no rotary on k (ref: transformer.py:664-683 decoder
    cross-attention).

    `adapters`: optional (LoraAdapter per-layer bank, adapter_idx [b])
    pair — the multi-tenant LoRA path (serving/adapters.py). Each batch
    row gathers its own adapter's A/B factors from the bank (one take
    per factor) and adds the low-rank delta x @ A[idx] @ B[idx] to the
    q/k/v/o projections — the Punica batched-gather-grouped-matmul
    shape, with row 0 the identity (zero) adapter so base rows ride the
    same trace. Indices are data: adapters on keeps one compile per
    program; adapters=None compiles to exactly today's graph."""
    if isinstance(kv_cache, ConvKVCache):
        # keys, values and offsets lie at the layer's index among the
        # attention layers
        cache_layer = kind_layer
    b, s, h = x.shape
    hd = cfg.kv_channels
    nq = cfg.num_attention_heads
    nkv = cfg.num_kv_heads
    dtype = x.dtype
    cross = kv_input is not None

    lw = aidx = None
    if adapters is not None:
        lw, aidx = adapters
        assert not cross, (
            "LoRA adapters apply to causal self-attention projections "
            "only (the serving slot grid); cross-attention has no "
            "adapter path")

    def _lora(inp, a, bmat):
        """Per-row low-rank delta: inp [b, s, d_in] -> [b, s, d_out]
        through each row's gathered [d_in, r] / [r, d_out] factors.
        Scaling (alpha/r) is pre-folded into bmat at bank-load time."""
        at = jnp.take(a, aidx, axis=0).astype(dtype)      # [b, d_in, r]
        bt = jnp.take(bmat, aidx, axis=0).astype(dtype)   # [b, r, d_out]
        t = jnp.einsum("bsd,bdr->bsr", inp.astype(dtype), at)
        return jnp.einsum("bsr,brd->bsd", t, bt)

    # a program with a cache multiplies by each weight once a call
    read_once = kv_cache is not None
    q = _project(x, params["wq"], cfg, read_once=read_once)
    kv = _project(kv_input if cross else x, params["wkv"], cfg,
                  read_once=read_once)
    if cfg.use_bias:
        q = q + params["bq"].astype(dtype)
        kv = kv + params["bkv"].astype(dtype)
    if lw is not None:
        # deltas join BEFORE the head reshape (and therefore before
        # rope): (W + A·B) @ x semantics, the merged-weights oracle the
        # exactness tests pin against
        q = q + _lora(x, lw.aq, lw.bq)
    gate = None
    if cfg.attn_output_gate:
        assert lw is None and not cross and not isinstance(
            kv_cache, (HybridKVCache, BlockKVCache)), (
            "attn_output_gate serves causal self-attention over whole "
            "regions, without adapters (config.validate)")
        q = q.reshape(b, s, nq, 2 * hd)
        q, gate = q[..., :hd], q[..., hd:].reshape(b, s, nq * hd)
    q = q.reshape(b, s, nq, hd)
    kv = kv.reshape(b, kv.shape[1], 2, nkv, hd)
    k, v = kv[:, :, 0], kv[:, :, 1]
    if lw is not None:
        t = kv.shape[1]
        k = k + _lora(x, lw.ak, lw.bk).reshape(b, t, nkv, hd)
        v = v + _lora(x, lw.av, lw.bv).reshape(b, t, nkv, hd)

    q_offset = None
    per_slot = False
    if kv_cache is not None:
        q_offset = _layer_of(kv_cache.offset, cache_layer)
        # PER-SLOT offsets (vector [b]): every batch row sits at its own
        # sequence position — the continuous-batching engine's slot grid
        # (serving/engine.py). s == 1 is the classic decode step; s > 1
        # is the GRID-BATCHED multi-token append (the speculative-decode
        # verify window, serving engine `--speculative_k`): row i writes
        # its s tokens at positions offset[i]..offset[i]+s-1 and the
        # causal mask starts at each row's own offset — prefill_chunk's
        # continuation form generalized from batch-1/scalar-offset to
        # the whole grid with vector offsets.
        per_slot = jnp.ndim(q_offset) == 1
        if per_slot:
            assert not cross, (
                "per-slot (vector) KV-cache offsets support only "
                "self-attention")
        if position_ids is None:
            if per_slot:
                position_ids = q_offset[:, None] + jnp.arange(s)[None, :]
            else:
                position_ids = q_offset + jnp.arange(s)[None, :]
                position_ids = jnp.broadcast_to(position_ids, (b, s))

    if cfg.qk_norm:
        assert not cross, "qk_norm is self-attention's (OLMoE)"
        q, k = qk_norm(params, q, k, cfg.norm_epsilon)
    if cfg.qk_head_norm:
        assert not cross, "qk_head_norm is self-attention's (LFM2)"
        q, k = qk_head_norm(params, q, k, cfg.norm_epsilon,
                            _head_norm_type(cfg))

    if isinstance(kv_cache, HybridKVCache) and s == 1:
        # a decode step's q and k stay the projections' outputs: left free,
        # the chip's compiler carries the rotary's view of adjacent pairs
        # ([.., hd / 2, 2]) back through the product into the weight and
        # copies wq (128 MiB at 128 heads of 128) into that order in every
        # step, 1.1 ms a window layer (my chip run, PR 33)
        q, k = jax.lax.optimization_barrier((q, k))
    if cfg.use_rotary_emb and not cross:
        assert rope_cos is not None and rope_sin is not None, (
            "cfg.use_rotary_emb=True requires rope_cos/rope_sin tables "
            "(build them with models.language_model.make_rope)")
        q = apply_rotary(q, rope_cos, rope_sin, position_ids)
        k = apply_rotary(k, rope_cos, rope_sin, position_ids)

    # Active attention dropout runs on the dot path AND the flash
    # blockwise path (per-block inverted-dropout masks); the cp rings
    # and the cached prefill exclude it (see the dispatch below).
    # sliding_window refines the CAUSAL mask; a bidirectional caller
    # (BERT/T5-encoder, cross-attention) setting it would be silently
    # ignored by every implementation — fail at trace time instead
    assert cfg.sliding_window is None or (causal and not cross), (
        "sliding_window requires causal self-attention")
    dropout_active = not deterministic and cfg.attention_dropout > 0.0
    if isinstance(kv_cache, HybridKVCache):
        assert causal and not cross and segment_ids is None \
            and lw is None and not cfg.use_bias and not dropout_active, (
            "a stack of window and full layers serves causal "
            "self-attention without bias, adapters or segments")
        with jax.named_scope("mtpu/attn/window" if cfg.sliding_window
                             else "mtpu/attn/full"):
            out, kv_cache = _hybrid_update_attend(
                q, k, v, kv_cache, cache_layer, kind_layer, cfg,
                scale=1.0 / math.sqrt(hd))
        out = out.reshape(b, s, nq * hd)
        return _project(out, params["wo"], cfg,
                        read_once=read_once), kv_cache
    if isinstance(kv_cache, ConvKVCache):
        assert causal and not cross and segment_ids is None \
            and lw is None and not cfg.use_bias and not dropout_active \
            and cfg.sliding_window is None, (
            "a pattern of convolution and attention layers serves causal "
            "self-attention over whole regions, without bias, adapters or "
            "segments")
        with jax.named_scope("mtpu/attn/folded"):
            out, kv_cache = _folded_update_attend(
                q, k, v, kv_cache, cache_layer, cfg,
                scale=1.0 / math.sqrt(hd))
        out = _gated(out.reshape(b, s, nq * hd), gate)
        return _project(out, params["wo"], cfg,
                        read_once=read_once), kv_cache
    if isinstance(kv_cache, BlockKVCache):
        # block-NATIVE serving path (--block_native_attn): append this
        # step's k/v into the touched arena blocks only and read the
        # chain through the map inside the Pallas kernel — the
        # contiguous view (and its resolve/scatter bracket) never
        # exists. Decode (s == 1) and the speculative verify window
        # (s > 1, causal within the window from each row's offset)
        # share this one path.
        assert causal and not cross and segment_ids is None, (
            "block-native attention serves causal self-attention only")
        assert cfg.sliding_window is None, (
            "block-native attention excludes ROLLING (sliding-window) "
            "layouts — the ring's slot->position map breaks the "
            "kernel's contiguous position arithmetic; the engine keeps "
            "the resolve/scatter bracket there (ServingConfig.validate)")
        assert not dropout_active, "no dropout on the serving path"
        out, kv_cache = _block_native_update_attend(
            q, k, v, kv_cache, cache_layer, scale=1.0 / math.sqrt(hd),
            dtype=dtype)
        out = out.reshape(b, s, nq * hd)
        proj = _project(out, params["wo"], cfg, read_once=read_once)
        if lw is not None:
            proj = proj + _lora(out, lw.ao, lw.bo)
        out = proj
        if cfg.use_bias:
            out = out + params["bo"].astype(dtype)
        return out, kv_cache
    # A cached forward with s > 1 is either an offset-0 prefill
    # (generation.py's whole-prompt pass) or a CONTINUATION chunk at
    # offset > 0 (generation.py prefill_chunk — the serving engine's
    # prefix-cache suffix / chunked prefill): the decode masking
    # generalized to q-len > 1, queries at positions offset..offset+s
    # attending the cache's live region. At offset 0 causal attention
    # over the cache equals plain causal attention over the fresh k/v,
    # so that case can take the flash path on the raw (un-cache-rounded)
    # tensors instead of paying O(s^2) score materialization on the dot
    # path — the reference's prefill pays full unfused attention. The
    # offset-0 condition is ENFORCED below with a lax.cond: an
    # offset > 0 chunk gets the correct cached dot path, not silently
    # wrong flash over the fresh chunk only.
    # per_slot excluded: a grid-batched s > 1 append (speculative
    # verify) has VECTOR offsets — never all-zero (active rows sit at
    # len >= 1), so the offset-0 flash shortcut can't apply and the
    # lax.cond predicate below wouldn't even be a scalar; it takes the
    # cached dot path, the same path the s == 1 grid decode uses.
    # QUANTIZED caches also skip the shortcut (except rolling buffers,
    # which need it for prompts longer than the window): flash-over-raw
    # reads different values than the dequantized int8 cache an
    # offset>0 continuation (prefix suffix, chunk, preemption replay,
    # speculative verify) reads, which is exactly the token-exactness
    # hole the old flash-int8 serving exclusions papered over. Routing
    # the int8 prefill through the cached dot path makes EVERY cached
    # forward read the same dequantized values through the same
    # algorithm — the exclusions are erased structurally, at the cost
    # of O(s^2) score materialization for int8-flash prefills.
    cache_rolling = (kv_cache is not None and cfg.sliding_window is not None
                     and kv_cache.k.shape[2] == cfg.sliding_window)
    cache_quant = kv_cache is not None and kv_cache.k.dtype == jnp.int8
    prefill_flash = (cfg.attention_impl == "flash" and kv_cache is not None
                     and s > 1 and segment_ids is None and causal
                     and not cross and not dropout_active and not per_slot
                     and (not cache_quant or cache_rolling))
    k_raw, v_raw = k, v

    kv_positions = pool_rows = None
    if kv_cache is not None:
        cap = kv_cache.k.shape[2]
        # ROLLING mode: the cache holds only the last `sliding_window`
        # positions (capacity == window). Writes land at position % W and
        # reads mask by the slot->position map below — O(W) serving
        # memory for unbounded streams. Created by init_kv_caches when
        # cfg.sliding_window < max_len.
        if cache_quant:
            from megatron_tpu.ops.quantized import quantize_rows
            k_new, ks = quantize_rows(k)  # per (b, token, head) over head_dim
            v_new, vs = quantize_rows(v)
            if prefill_flash:
                # ROLLING int8 prefill keeps the flash shortcut (a
                # prompt longer than W cannot take the cached dot
                # path), but reads the quantize->dequantize ROUND-TRIP
                # of the fresh k/v, i.e. exactly the values the cache
                # now holds — so continuation steps (which read the
                # dequantized ring) see the same numbers the prefill
                # attended, and a retained rolling prefix clone stays
                # token-consistent with the cache-off path.
                k_raw = k_new.astype(dtype) * ks.astype(dtype)
                v_raw = v_new.astype(dtype) * vs.astype(dtype)
        else:
            k_new, v_new, ks, vs = k, v, None, None
        # Every write below goes to (cache_layer, row, position) of the
        # STACKED buffer itself: its operand is the layer loop's carry,
        # so XLA updates it in place and moves only the new tokens.
        if per_slot or cache_rolling:
            if per_slot:
                # serving slot grid: row i writes its s tokens' k/v at
                # its own offset[i]..offset[i]+s-1 (one scatter, [b, s]
                # index grids) — through the ring (position % W) when
                # the buffer is rolling. s > 1 is the speculative-verify
                # window; its rewind invariant (rejected-position KV
                # overwritten write-before-read) cannot hold on a
                # rolling ring, so the engine excludes that combination
                # (ServingConfig.validate).
                assert s == 1 or not cache_rolling, (
                    "per-slot multi-token appends (speculative verify) are "
                    "undefined on ROLLING caches: a rejected draft's ring "
                    "write already evicted history — see "
                    "ServingConfig.validate")
                n_keep = s
                slots = q_offset[:, None] + jnp.arange(s)[None, :]
            else:
                # tokens beyond the window never survive a chunked
                # write: keep only the last min(s, W) and scatter to
                # their slots (unique by construction). Multi-token
                # chunks are CORRECT when (a) routed through the
                # offset-0 flash prefill (outputs come from the raw k/v;
                # the cache just ends in the right state) or (b) s <= W
                # at offset 0 on the dot path (nothing is overwritten).
                # Mid-stream s > 1 chunks would need history this buffer
                # already dropped — generation.py only prefills at
                # offset 0, which is the caller contract here.
                assert s == 1 or prefill_flash or s <= cap, (
                    "rolling KV cache: multi-token steps need the flash "
                    "prefill or s <= sliding_window (decode steps are "
                    "s == 1)")
                n_keep = min(s, cap)  # static: plain slices, no gather
                slots = (q_offset + (s - n_keep)
                         + jnp.arange(n_keep))[None, :]
            if cache_rolling:
                slots = slots % cap
            rows = jnp.arange(b)[:, None]

            # mode="drop": a row parked at the capacity clamp
            # (serving/engine.py keeps device lengths <= max_len-1)
            # would index past the region with s > 1 — those writes are
            # garbage for garbage rows and must vanish, not wrap or
            # collide nondeterministically at cap-1
            def wr(buf, val):
                return buf.at[cache_layer, rows, slots].set(
                    val[:, s - n_keep:].astype(buf.dtype), mode="drop")
        else:
            def wr(buf, val):
                return jax.lax.dynamic_update_slice(
                    buf, val[None].astype(buf.dtype),
                    (cache_layer, 0, q_offset, 0, 0))

        kv_cache = KVCache(
            wr(kv_cache.k, k_new), wr(kv_cache.v, v_new),
            jax.lax.dynamic_update_index_in_dim(
                kv_cache.offset, q_offset + s, cache_layer, 0),
            wr(kv_cache.k_scale, ks) if cache_quant else None,
            wr(kv_cache.v_scale, vs) if cache_quant else None)
        if cache_rolling:
            # slot j holds the largest position p <= t_last (per row on
            # the slot grid) with p % W == j; never-written slots (p < 0)
            # map to a sentinel the causal mask rejects
            t_last = q_offset + s - 1
            if per_slot:
                t_last = t_last[:, None]  # [b, 1]
            p = t_last - ((t_last - jnp.arange(cap)) % cap)
            kv_positions = jnp.where(p >= 0, p, jnp.int32(2 ** 30))
        # The read is this layer of the buffer AFTER the write (a step's
        # own tokens are attended; data dependence keeps the order).
        from megatron_tpu.ops.block_attention_pallas import pool_block_rows
        from megatron_tpu.parallel.sharding import active_kernel_mesh
        pool_rows = pool_block_rows(
            kv_cache.k.shape, kv_cache.k.dtype, queries=q.shape[:3],
            per_slot=per_slot,
            window=cfg.sliding_window is not None,
            mesh=active_kernel_mesh() is not None)
        if pool_rows is None:
            # The whole layer feeds the products directly, so XLA can fuse
            # the slice (and the int8 dequant: convert*scale) into the
            # dot's operand load and stream the pool from HBM once.
            k = _layer_of(kv_cache.k, cache_layer).astype(dtype)
            v = _layer_of(kv_cache.v, cache_layer).astype(dtype)
            if cache_quant:
                k = k * _layer_of(kv_cache.k_scale,
                                  cache_layer).astype(dtype)
                v = v * _layer_of(kv_cache.v_scale,
                                  cache_layer).astype(dtype)

    scale = 1.0 / math.sqrt(hd)
    # Note on apply_query_key_layer_scaling: in the reference it divides QK^T
    # by layer_number and the fused softmax multiplies it straight back
    # (ref: transformer.py:172-184, fused_softmax.py:193-196) — a net-no-op
    # fp16 overflow trick. Our softmax always runs in fp32
    # (attention_softmax_in_fp32), so the trick is unnecessary and the flag
    # intentionally has no numerical effect.

    # dropout_active (defined above, with the prefill gate): the cp
    # rings have no dropout plumbing, so a training trace with
    # attention_dropout > 0 routes them to the dot path (validate warns);
    # the flash branch below carries dropout natively. Eval traces
    # (deterministic=True) keep every fused path.
    ring_branch = (cfg.attention_impl in ("ring", "ulysses")
                   and kv_cache is None and segment_ids is None and causal
                   and cfg.sliding_window is None and not dropout_active)
    # a pre-permuted batch MUST reach the ring path: any gating drift
    # between data_zigzag_cp (which told the loss to permute) and this
    # dispatch would apply causal masks to the wrong rows and silently
    # diverge — fail at trace time instead
    assert not cp_pre_zigzag or (ring_branch
                                 and cfg.attention_impl == "ring"), (
        "cp_pre_zigzag=True but the ring-attention path is not taken "
        "(data_zigzag_cp and attention_apply gating drifted)")
    if ring_branch:
        # context-parallel attention over the 'cp' mesh axis (absent in
        # the reference — SURVEY.md §2.8): K/V-rotation ring
        # (parallel/ring_attention.py) or all-to-all head-parallel Ulysses
        # (parallel/ulysses.py)
        mesh = jax.sharding.get_abstract_mesh()  # jit-safe ambient mesh
        if "cp" in mesh.axis_names and not mesh.empty:
            if cfg.attention_impl == "ulysses":
                from megatron_tpu.parallel.ulysses import ulysses_attention
                out = ulysses_attention(q, k, v, mesh, causal=True,
                                        scale=scale)
            else:
                from megatron_tpu.parallel.ring_attention import \
                    ring_attention
                # cp_pre_zigzag: the loss pre-permuted the batch into
                # zigzag order (data_zigzag_cp), so the ring skips its
                # runtime permute-gathers
                out = ring_attention(
                    q, k, v, mesh, causal=True, scale=scale,
                    layout="pre_zigzag" if cp_pre_zigzag else "auto")
        else:
            assert not cp_pre_zigzag, (
                "cp_pre_zigzag=True but no 'cp' mesh is ambient — the "
                "batch was permuted for a ring that will not run")
            from megatron_tpu.ops.flash_attention import flash_attention
            out = flash_attention(q, k, v, causal=True, scale=scale)
    elif cfg.attention_impl == "flash" and kv_cache is None:
        from megatron_tpu.ops.flash_attention import flash_attention
        # segment_ids ride into the kernel (EOD-reset block-diagonal
        # masking, ref: --reset_attention_mask) — O(s) memory where the
        # dot path would materialize the [s, s] scores; sliding_window
        # additionally skips whole blocks outside the band. Active
        # attention dropout stays on this path too (the reference's
        # FA2 dropout_p, ref: transformer.py:514-522): the blockwise
        # impl draws per-block inverted-dropout masks — no O(s^2)
        # demotion when training GPT/Falcon presets with dropout
        out = flash_attention(
            q, k, v, causal=causal, scale=scale, segment_ids=segment_ids,
            sliding_window=cfg.sliding_window,
            dropout_rate=(cfg.attention_dropout
                          if dropout_active and dropout_rng is not None
                          else 0.0),
            dropout_rng=dropout_rng if dropout_active else None)
    elif pool_rows is not None:
        # each slot at its own length (a decode step, a verify window):
        # the kernel reads every slot's blocks up to its length out of the
        # stacked pool where they lie, and no layer of it is cut out
        # (ops/block_attention_pallas.py::pool_block_rows says which pools)
        assert causal and segment_ids is None and not dropout_active, (
            "per-slot offsets serve causal self-attention, no dropout")
        from megatron_tpu.ops.block_attention_pallas import \
            contiguous_pool_attention
        out = contiguous_pool_attention(
            q, kv_cache.k, kv_cache.v, q_offset, layer=cache_layer,
            rows=pool_rows, scale=scale, k_scale=kv_cache.k_scale,
            v_scale=kv_cache.v_scale).astype(dtype)
    elif prefill_flash:
        from megatron_tpu.ops.flash_attention import flash_attention

        if kv_positions is not None:
            # ROLLING cache: the dot fallback below would be silently
            # wrong for an offset>0 chunk (the chunk's own writes already
            # evicted history its early queries need), so a multi-token
            # step is defined ONLY at offset 0 — take flash directly on
            # the raw k/v, and poison the output with NaN for any
            # offset>0 chunked prefill so a contract violation fails
            # loudly at the first logit instead of decoding garbage
            out = flash_attention(
                q, k_raw, v_raw, causal=True, scale=scale,
                sliding_window=cfg.sliding_window)
            out = jnp.where(q_offset == 0, out, jnp.nan)
        else:
            # both branches trace (compile-time cost only); runtime
            # executes one, and only offset 0 gets the flash shortcut
            out = jax.lax.cond(
                q_offset == 0,
                lambda: flash_attention(
                    q, k_raw, v_raw, causal=True, scale=scale,
                    sliding_window=cfg.sliding_window).astype(jnp.float32),
                lambda: _dot_attention(
                    q, k, v, causal=causal,
                    softmax_fp32=cfg.attention_softmax_in_fp32,
                    scale=scale, q_offset=q_offset,
                    segment_ids=segment_ids,
                    sliding_window=cfg.sliding_window,
                    kv_positions=kv_positions).astype(jnp.float32),
            ).astype(dtype)
    else:
        rate = 0.0 if deterministic else cfg.attention_dropout
        out = _dot_attention(
            q, k, v, causal=causal,
            softmax_fp32=cfg.attention_softmax_in_fp32,
            scale=scale, q_offset=q_offset, dropout_rate=rate,
            dropout_rng=dropout_rng, segment_ids=segment_ids,
            sliding_window=cfg.sliding_window,
            kv_positions=kv_positions)

    out = _gated(out.reshape(b, s, nq * hd), gate)
    proj = _project(out, params["wo"], cfg, read_once=read_once)
    if lw is not None:
        proj = proj + _lora(out, lw.ao, lw.bo)
    out = proj
    if cfg.use_bias:
        out = out + params["bo"].astype(dtype)
    return out, kv_cache
