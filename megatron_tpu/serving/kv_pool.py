"""Slot-based KV-cache pool for continuous batching.

vLLM pools KV memory as fixed-size blocks chained per request
(PagedAttention); the TPU-native formulation here is a fixed GRID of
batch slots over one pre-allocated cache — [layers, num_slots, cap,
kv_heads, head_dim] from `init_kv_caches` (inference/generation.py), so
the int8-quantized and sliding-window ROLLING layouts come for free.
A slot owns a contiguous `cap`-token region; admission binds a request
to a free slot, prefill writes the prompt's KV into the region via
`lax.dynamic_update_slice`, and eviction returns the slot to the free
list with no copying — the next request simply overwrites it (stale
entries past a row's offset are invisible to the causal mask and are
overwritten write-before-read during decode).

Prefix-cache support (SGLang's RadixAttention, slot-grid native): a
finished slot can be RETAINED instead of freed — its KV stays resident
and is reclaimed lazily, only when admission needs the memory
(`retain`/`touch`/`alloc`). A request whose prompt shares a prefix
with a retained (or still-running) slot reuses the prefix KV through
ONE on-device region copy — `clone_prefix` / `slice_slot` — instead of
re-running L forward layers over the shared tokens.

Block-granular mode (`block_size=B`, vLLM's PagedAttention trade made
static-shape): the pool's storage becomes a flat ARENA of
`cap/B`-token physical blocks ([L, total_blocks, B, nkv, hd]) plus a
device-resident per-slot BLOCK MAP ([num_slots, cap/B] int32, logical
block -> physical block). The map is resolved at dispatch time —
`resolve_view` gathers each slot's blocks into the SAME contiguous
[L, S, cap, ...] layout the grid's compiled programs already consume,
and `scatter_view` writes the result back — so shapes stay static and
the one-compile decode trace survives (unlike true paging, only block
INDICES are data). What changes is the ACCOUNTING: physical blocks are
refcounted, a retained prefix pins only the blocks it actually covers
(a 3-block prefix costs 3 blocks, not a whole cap region — and holds
NO grid row, so retained capacity is bounded by blocks, not slots), a
prefix hit ALIASES the shared blocks into the new slot's map instead
of copying them, and idle grid rows point every map entry at a shared
TRASH block so their garbage writes can never clobber retained KV.
The rolling W-slot ring rides the same machinery (ring positions live
at block (p // B) % (W/B)), which is what makes ROLLING pools
retainable/cloneable/preemptible for the first time: a released ring
row's garbage writes land in trash, not in the retained ring.
"""
from __future__ import annotations

import collections
import itertools
from typing import Callable, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from megatron_tpu.config import ModelConfig
from megatron_tpu.inference.generation import (KV_CACHE_AXES, init_kv_caches,
                                               kv_region_cap)
from megatron_tpu.models.attention import (BlockKVCache, ConvKVCache,
                                            HybridKVCache, KVCache,
                                            LatentStateCache)
from megatron_tpu.models.mla import LatentKVCache
from megatron_tpu.serving import capabilities
from megatron_tpu.utils.logging import print_rank_0


def insert_prefill(pool: KVCache, prefill: KVCache, slot, plen) -> KVCache:
    """Write a batch-1 prefill cache into `slot`'s pool region.

    Pure/jittable (slot and plen are traced scalars, so one compile
    serves every slot). The prefill cache must share the pool's layout —
    both come from `init_kv_caches(cfg, ..., max_len, dtype)`, so caps
    (full-length or rolling W), dtypes, and scale tensors line up.
    Only the row's offset is set to `plen`, the TRUE prompt length: a
    bucket-padded prefill leaves pad garbage at [plen, padded), which
    decode overwrites write-before-read: attention_apply writes position
    `offset` of its layer into the stacked pool and attends that layer
    of the buffer it has just written, so the order is a data dependence
    and not a convention."""
    dus = jax.lax.dynamic_update_slice
    zero = jnp.int32(0)
    slot = jnp.asarray(slot, jnp.int32)
    if isinstance(pool, HybridKVCache):
        # both stacks of the slot whole: a ring's rows are where the
        # prefill's own ring put them (position % ring), so the copy keeps
        # them valid; the pool's `live_end` stays "no padding"
        start5 = (zero, slot, zero, zero, zero)
        return pool._replace(
            offset=dus(pool.offset,
                       jnp.full((pool.offset.shape[0], 1), plen, jnp.int32),
                       (zero, slot)),
            **{f: dus(getattr(pool, f),
                      getattr(prefill, f).astype(getattr(pool, f).dtype),
                      start5)
               for f in ("ring_k", "ring_v", "full_k", "full_v")})
    if isinstance(pool, ConvKVCache):
        # the slot's convolution state WHOLE, beside its keys and values: no
        # mask hides a state, so what the slot's last tenant left goes here
        start4 = (zero, slot, zero, zero)
        return pool._replace(
            k=dus(pool.k, prefill.k.astype(pool.k.dtype), start4),
            v=dus(pool.v, prefill.v.astype(pool.v.dtype), start4),
            conv=dus(pool.conv, prefill.conv.astype(pool.conv.dtype),
                     start4),
            ssm=(None if pool.ssm is None
                 else dus(pool.ssm, prefill.ssm,
                          (zero, slot) + (zero,) * (pool.ssm.ndim - 2))),
            offset=dus(pool.offset,
                       jnp.full((pool.offset.shape[0], 1), plen, jnp.int32),
                       (zero, slot)))
    if isinstance(pool, LatentStateCache):
        # the slot's three parts WHOLE: its latent rows, the depthwise
        # kernels' inputs and the rule's matrices (no mask hides a state)
        def land(name):
            into, part = getattr(pool, name), getattr(prefill, name)
            return dus(into, part.astype(into.dtype),
                       (zero, slot) + (zero,) * (into.ndim - 2))
        return pool._replace(
            c=land("c"), conv=land("conv"), ssm=land("ssm"),
            offset=dus(pool.offset,
                       jnp.full((pool.offset.shape[0], 1), plen, jnp.int32),
                       (zero, slot)))
    if isinstance(pool, LatentKVCache):
        return LatentKVCache(
            c=dus(pool.c, prefill.c.astype(pool.c.dtype),
                  (zero, slot, zero, zero)),
            offset=dus(pool.offset,
                       jnp.full((pool.offset.shape[0], 1), plen, jnp.int32),
                       (zero, slot)))
    start5 = (zero, slot, zero, zero, zero)
    new = KVCache(
        k=dus(pool.k, prefill.k.astype(pool.k.dtype), start5),
        v=dus(pool.v, prefill.v.astype(pool.v.dtype), start5),
        offset=dus(pool.offset,
                   jnp.full((pool.offset.shape[0], 1), plen, jnp.int32),
                   (zero, slot)),
        k_scale=(None if pool.k_scale is None
                 else dus(pool.k_scale, prefill.k_scale, start5)),
        v_scale=(None if pool.v_scale is None
                 else dus(pool.v_scale, prefill.v_scale, start5)),
    )
    return new


def slice_slot(pool: KVCache, slot, offset) -> KVCache:
    """Extract `slot`'s whole cap-region as a batch-1 cache positioned
    at `offset` (both traced scalars — one compile serves every slot).

    The inverse of `insert_prefill`: the copy spans the full region, so
    tokens past `offset` (the source's own continuation, or stale
    garbage) ride along — they sit beyond the returned cache's offset,
    where the causal mask never reads them and appends overwrite them
    write-before-read, the same invariant bucket-padded prefill relies
    on. int8 pools copy quantized blocks + scales verbatim."""
    ds = jax.lax.dynamic_slice
    zero = jnp.int32(0)
    slot = jnp.asarray(slot, jnp.int32)
    assert not isinstance(pool, HybridKVCache), (
        "a slot of rings cannot be cut out at a shorter length: the rows "
        "of its earlier positions are gone (ServingConfig.validate refuses "
        "prefix cache and preemption on window_layer_period)")
    assert not isinstance(pool, (ConvKVCache, LatentStateCache)), (
        "a slot with a convolution state cannot be cut out at a shorter "
        "length: the state is the one at the slot's current length "
        "(ServingConfig.validate refuses prefix cache, retained slots and "
        "preemption on layer_types with conv layers)")
    if isinstance(pool, LatentKVCache):
        L, _, row, cap = pool.c.shape
        return LatentKVCache(
            c=ds(pool.c, (zero, slot, zero, zero), (L, 1, row, cap)),
            offset=jnp.full((L,), offset, jnp.int32))
    L, _, cap, nkv, hd = pool.k.shape
    start5 = (zero, slot, zero, zero, zero)
    return KVCache(
        k=ds(pool.k, start5, (L, 1, cap, nkv, hd)),
        v=ds(pool.v, start5, (L, 1, cap, nkv, hd)),
        offset=jnp.full((L,), offset, jnp.int32),
        k_scale=(None if pool.k_scale is None
                 else ds(pool.k_scale, start5, (L, 1, cap, nkv, 1))),
        v_scale=(None if pool.v_scale is None
                 else ds(pool.v_scale, start5, (L, 1, cap, nkv, 1))),
    )


def batch_row(caches, i: int):
    """Row `i` of a batch-B prefill cache as a batch-1 cache (every array
    but the offsets cut on the batch axis), for `insert_prefill`."""
    def row(x):
        return None if x is None else jax.lax.dynamic_slice_in_dim(
            x, i, 1, axis=1)
    return caches._replace(**{f: row(getattr(caches, f))
                              for f in caches._fields
                              if f not in ("offset", "live_end",
                                           "live_rows")})


def clone_prefix(pool: KVCache, src_slot, dst_slot, plen) -> KVCache:
    """Copy `src_slot`'s region into `dst_slot` and mark the first
    `plen` tokens live — the prefix-cache hit primitive: one on-device
    region copy replaces L forward layers over the shared prefix.

    Pure/jittable; all three scalars are traced, so one compile serves
    every (src, dst, plen) triple. Copies k/v (and int8 scales)
    VERBATIM — a cloned prefix is bit-identical to the source's, which
    is what the token-exact cache-on-vs-off contract requires. Only
    defined for contiguous (non-ROLLING) pools: a rolling region holds
    the last W positions ring-ordered by the SOURCE's length, so the
    prefix [0, plen) may already be evicted —
    `ServingConfig.validate` / the engine exclude rolling pools
    (block-granular pools lift this: see SlotKVPool block mode).

    The engine's admission path runs this decomposed around the suffix
    forward (`slice_slot` → append suffix KV → `insert_prefill`), which
    is the same two region copies fused with the prefill."""
    return insert_prefill(pool, slice_slot(pool, src_slot, plen),
                          dst_slot, plen)


# ---------------------------------------------------------------------
# block-granular arena: static per-slot block map, resolved at dispatch
# ---------------------------------------------------------------------
class BlockKV(NamedTuple):
    """Device state of a block-granular pool.

    `arena` holds k/v as [L, total_blocks, B, nkv, hd] (int8 scales as
    [L, total_blocks, B, nkv, 1]) and the PER-SLOT offsets [L, S] —
    offsets are per-row state, not per-block. `map` is the static
    per-slot block table [S, cap/B] int32: map[s, i] is the physical
    block holding slot s's positions [i*B, (i+1)*B). The LAST physical
    block is the shared TRASH block: every map entry of an idle row
    points at it, so the grid's garbage writes for inactive rows land
    somewhere nothing ever reads. Block indices are DATA — remapping a
    slot never retraces anything."""
    arena: KVCache
    map: jax.Array  # [S, cap/B] int32


def resolve_view(bkv: BlockKV) -> KVCache:
    """Gather the arena through the block map into the contiguous
    [L, S, cap, nkv, hd] slot-grid layout every compiled program
    already consumes. Pure/jittable; the map is a traced operand, so
    ONE compile serves every block assignment."""
    S, nb = bkv.map.shape
    flat = bkv.map.reshape(-1)

    def g(x):
        y = jnp.take(x, flat, axis=1)  # [L, S*nb, B, ...]
        return y.reshape(x.shape[0], S, nb * x.shape[2], *x.shape[3:])

    a = bkv.arena
    return KVCache(
        k=g(a.k), v=g(a.v), offset=a.offset,
        k_scale=None if a.k_scale is None else g(a.k_scale),
        v_scale=None if a.v_scale is None else g(a.v_scale))


def scatter_view(bkv: BlockKV, view: KVCache) -> BlockKV:
    """Write an updated contiguous view back through the block map —
    the inverse of `resolve_view`, closing a dispatch. Duplicate map
    entries (the shared TRASH block, or a prefix block aliased into
    several slots) receive identical values by construction: nobody
    writes below its own offset, and aliased prefix blocks sit below
    every alias-holder's offset, so the unordered scatter is
    deterministic where it matters."""
    S, nb = bkv.map.shape
    flat = bkv.map.reshape(-1)

    def s(ax, vx):
        B = ax.shape[2]
        blocks = vx.reshape(vx.shape[0], S * nb, B, *vx.shape[3:])
        return ax.at[:, flat].set(blocks.astype(ax.dtype))

    a = bkv.arena
    arena = a._replace(
        k=s(a.k, view.k), v=s(a.v, view.v), offset=view.offset,
        k_scale=None if a.k_scale is None else s(a.k_scale, view.k_scale),
        v_scale=None if a.v_scale is None else s(a.v_scale, view.v_scale))
    return bkv._replace(arena=arena)


def block_native_cache(bkv: BlockKV) -> BlockKVCache:
    """View a BlockKV as the model-facing BlockKVCache WITHOUT moving
    any data: arena leaves pass through, the per-slot map broadcasts
    over layers so attention can index it by layer (a few KiB of
    int32 — the whole point is that block INDICES, not block contents,
    are what dispatch resolves). The engine's block-native decode /
    verify programs (`--block_native_attn`) hand this to
    lm.model_forward in place of the resolve_view gather; the Pallas
    kernel (ops/block_attention_pallas.py) then reads the arena
    through the map directly."""
    a = bkv.arena
    L = a.k.shape[0]
    return BlockKVCache(
        k=a.k, v=a.v, offset=a.offset,
        map=jnp.broadcast_to(bkv.map[None], (L,) + bkv.map.shape),
        k_scale=a.k_scale, v_scale=a.v_scale)


def pack_block_native(cache: BlockKVCache, map2d) -> BlockKV:
    """Inverse of `block_native_cache`: rewrap the forward pass's
    updated arena (appends landed block-natively) as the pool's
    BlockKV. `map2d` is the pool's own [S, nb] map — the forward never
    remaps anything, so the original rides through."""
    return BlockKV(
        arena=KVCache(k=cache.k, v=cache.v, offset=cache.offset,
                      k_scale=cache.k_scale, v_scale=cache.v_scale),
        map=map2d)


def slice_blocks(bkv: BlockKV, blocks, offset) -> KVCache:
    """Gather an explicit physical-block list ([cap/B] int32, traced)
    into a batch-1 cache positioned at `offset` — the block-mode read
    half of `clone_prefix` (and the preemption park). Works for rows
    AND row-less retained prefixes: the caller owns the block list."""
    a = bkv.arena

    def g(x):
        y = jnp.take(x, blocks, axis=1)  # [L, nb, B, ...]
        return y.reshape(x.shape[0], 1, -1, *x.shape[3:])

    return KVCache(
        k=g(a.k), v=g(a.v),
        offset=jnp.full((a.k.shape[0],), offset, jnp.int32),
        k_scale=None if a.k_scale is None else g(a.k_scale),
        v_scale=None if a.v_scale is None else g(a.v_scale))


def insert_blocks(bkv: BlockKV, sub: KVCache, slot, plen,
                  pfx_blocks) -> BlockKV:
    """Land a batch-1 cache in `slot`'s mapped blocks with the first
    `plen` tokens live — the block-mode write half of `clone_prefix`.

    `pfx_blocks` (traced) is the copy-on-write boundary: blocks below
    it are ALIASED shared-prefix blocks whose content the sub carries
    verbatim (it was sliced through the same map) — rewriting them
    would race identical bytes against other alias holders for no
    benefit, so their writes are redirected to the TRASH block instead.
    Only the fresh blocks at/after the boundary are written. Pass 0 to
    write the whole region (a miss, a preemption resume)."""
    S, nb = bkv.map.shape
    a = bkv.arena
    trash = a.k.shape[1] - 1  # static: last physical block
    slot = jnp.asarray(slot, jnp.int32)
    row = jax.lax.dynamic_slice(bkv.map, (slot, jnp.int32(0)), (1, nb))[0]
    idx = jnp.where(jnp.arange(nb) >= pfx_blocks, row, jnp.int32(trash))

    def s(ax, sx):
        B = ax.shape[2]
        blocks = sx.reshape(sx.shape[0], nb, B, *sx.shape[3:])
        return ax.at[:, idx].set(blocks.astype(ax.dtype))

    offset = jax.lax.dynamic_update_slice(
        a.offset, jnp.full((a.offset.shape[0], 1), plen, jnp.int32),
        (jnp.int32(0), slot))
    arena = a._replace(
        k=s(a.k, sub.k), v=s(a.v, sub.v), offset=offset,
        k_scale=None if a.k_scale is None else s(a.k_scale, sub.k_scale),
        v_scale=None if a.v_scale is None else s(a.v_scale, sub.v_scale))
    return bkv._replace(arena=arena)


class RetainedPrefix:
    """A finished sequence's KV pinned at BLOCK granularity: the
    physical blocks covering its first `length` tokens (ALL ring
    blocks for a rolling pool — the whole window is live), plus the
    token sequence for index/continuation checks. Holds NO grid row:
    retained capacity is bounded by free blocks, not by slots.
    `namespace` is the adapter id the KV was computed under (None =
    base model) — it rides into the prefix index and the host tier so
    a cross-adapter clone is structurally impossible."""

    __slots__ = ("key", "blocks", "length", "tokens", "namespace")

    def __init__(self, key, blocks: List[int], length: int,
                 tokens: List[int], namespace=None):
        self.key = key
        self.blocks = blocks
        self.length = length
        self.tokens = tokens
        self.namespace = namespace


class SlotKVPool:
    """Pre-allocated slot-grid cache + host-side free bookkeeping.

    `caches` is the live device pytree; the engine replaces it
    functionally every step. Slot/block accounting runs only on the
    engine thread.

    Whole-region mode (block_size=None, the bit-compatible default):
    `caches` is the [L, S, cap, nkv, hd] KVCache, each slot owns its
    contiguous region, and lazy eviction works per-REGION: `retain`
    parks a finished slot's KV on an LRU instead of the free list, and
    `alloc` reclaims free-first-then-LRU (`exclude=` protects a
    same-cycle clone source). `retained_limit` caps the list;
    `on_reclaim(slot)` fires when a retained slot's KV is about to be
    overwritten so the engine can drop its prefix-index entries.

    Block mode (block_size=B dividing cap): `caches` is a `BlockKV`
    (flat arena + per-slot block map) and the second resource besides
    grid rows is the refcounted physical-block pool. Rows allocate
    their cap/B blocks up front (`alloc_row`, optionally ALIASING
    shared prefix blocks), release them on eviction (`release_row`),
    and retention (`retain_row`) converts a finished row into a
    row-less `RetainedPrefix` pinning only the blocks its tokens
    cover — the tail blocks (and the grid row) free immediately, which
    is where the slots-per-HBM-byte win comes from. `retained_limit`
    caps retained ENTRIES; `on_reclaim(key)` fires with the entry key
    when block pressure (or the limit) evicts one."""

    def __init__(self, cfg: ModelConfig, num_slots: int, max_len: int,
                 dtype=jnp.bfloat16, retained_limit: Optional[int] = None,
                 block_size: Optional[int] = None):
        assert num_slots >= 1, num_slots
        self.cfg = cfg
        self.num_slots = num_slots
        self.max_len = max_len
        self.dtype = jnp.dtype(dtype)
        # what a slot holds, the capacity in POSITIONS and the block size
        # are serving/capabilities.py's, as ServingConfig.validate read them.
        # Window and full layers in one stack (rings and whole regions side
        # by side, attention.HybridKVCache) have the regions' capacity, and
        # `rolling`, which means "the whole slot forgets", stays False:
        # chunks and buckets are taken
        kind = capabilities.pool_kind(cfg, max_len)
        self.cap = capabilities.slot_cap(cfg, max_len)
        self.rolling = kind == "rolling"
        # a pool can be built without an engine, so it keeps this guard
        assert block_size is None \
            or "kv_block_size" not in capabilities.REFUSED[kind], \
            capabilities.refusal(kind, "kv_block_size", cfg)
        block_size = capabilities.resolved_block_size(cfg, max_len,
                                                      block_size)
        self.block_size = block_size
        self._free: collections.deque = collections.deque(range(num_slots))
        # retained state, oldest first (OrderedDict as an LRU: touch
        # moves to the end, reclaim pops from the front). Whole-region
        # mode keys by SLOT; block mode keys by RetainedPrefix key.
        self._retained: "collections.OrderedDict" = \
            collections.OrderedDict()
        self.retained_limit = retained_limit
        self.on_reclaim: Optional[Callable] = None
        # block mode only: fires with the dying RetainedPrefix BEFORE
        # its blocks are unreffed — the host-RAM tier's demotion hook
        # (serving/host_tier.py); the entry's device content is still
        # intact at call time (retained blocks receive no idle writes)
        self.on_evict_entry: Optional[Callable] = None
        if block_size is None:
            self.caches = init_kv_caches(cfg, num_slots, max_len,
                                         dtype=dtype,
                                         per_slot_offsets=True)
            # positions: axis 2 of k and v, the minor axis of a latent pool
            assert self.cap == (self.caches.c.shape[3] if cfg.mla
                                else self.caches.full_k.shape[3]
                                if self.hybrid
                                else self.caches.k.shape[2]), (
                "kv_region_cap drifted from init_kv_caches")
            return
        # ---- block mode ----------------------------------------------
        self.blocks_per_slot = self.cap // block_size
        # one block set per slot plus the shared TRASH block (last
        # physical index): same usable token capacity as the
        # whole-region pool, one block of overhead
        self.total_blocks = num_slots * self.blocks_per_slot + 1
        self.TRASH = self.total_blocks - 1
        from megatron_tpu.parallel.sharding import constrain
        L, nkv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.kv_channels
        quant = self.dtype == jnp.dtype(jnp.int8)
        shape = (L, self.total_blocks, block_size, nkv, hd)
        sshape = shape[:4] + (1,)
        arena = KVCache(
            k=constrain(jnp.zeros(shape, dtype), KV_CACHE_AXES),
            v=constrain(jnp.zeros(shape, dtype), KV_CACHE_AXES),
            offset=jnp.zeros((L, num_slots), jnp.int32),
            k_scale=(constrain(jnp.ones(sshape, jnp.float32),
                               KV_CACHE_AXES) if quant else None),
            v_scale=(constrain(jnp.ones(sshape, jnp.float32),
                               KV_CACHE_AXES) if quant else None),
        )
        self._map = np.full((num_slots, self.blocks_per_slot),
                            self.TRASH, np.int32)
        # TP-sharded serving (serving/topology.py place_pool) pins the
        # map's replicated NamedSharding here so every _sync_map
        # re-upload lands identically placed; None (default) keeps the
        # uncommitted single-device upload
        self._map_sharding = None
        # jnp.array, not asarray: the device map must never alias the
        # host buffer (see _sync_map)
        self.caches = BlockKV(arena=arena, map=jnp.array(self._map))
        self._rc = np.zeros(self.total_blocks, np.int64)
        self._rc[self.TRASH] = 1 << 60  # never freed
        self._free_blocks: collections.deque = collections.deque(
            range(self.total_blocks - 1))
        self._ret_ids = itertools.count()
        # free_count memo: the reclaimable-block walk is O(retained
        # blocks) and the engine calls it every loop iteration — cache
        # it and invalidate on any accounting mutation (_acct_dirty)
        self._acct_dirty = True
        self._free_count_cache = 0

    @property
    def blocks_enabled(self) -> bool:
        return self.block_size is not None

    @property
    def hybrid(self) -> bool:
        """Rings and whole regions side by side (`cfg.window_layer_period`)."""
        return bool(self.cfg.window_layer_period)

    @property
    def conv_layers(self) -> int:
        """Layers that keep a state of fixed size a slot and no keys or
        values: a convolution's last inputs and, in a "mamba", "mamba2",
        "kda" or "linear_attention" layer, the scan's state beside them (`cfg.layer_types`;
        models/attention.py::ConvKVCache, LatentStateCache)."""
        return self.cfg.state_layers

    @property
    def kv_layers(self) -> int:
        """Layers that hold a row a token for as long as its sequence lives
        (`ModelConfig.kv_layers`): every byte count a token below is over
        these."""
        return self.cfg.kv_layers

    def make_prefill_caches(self, batch: int = 1) -> KVCache:
        """A fresh request-local cache in the POOL's layout (same cap /
        dtype / rolling decision), for the prefill pass that precedes
        `insert_prefill` / `insert_blocks`."""
        return init_kv_caches(self.cfg, batch, self.max_len,
                              dtype=self.dtype)

    # ---- whole-region slot bookkeeping (engine thread only) ----------
    def alloc(self, exclude=()) -> Optional[int]:
        """Allocate a slot: free list first, then reclaim the
        least-recently-used retained slot (its KV is about to be
        overwritten — `on_reclaim` fires so the index can forget it).
        `exclude` protects slots that must survive this allocation
        (the source of a prefix clone in the same admission cycle);
        returns None when nothing outside `exclude` is allocatable.
        Alloc order is pinned (tested): free slots come back FIFO in
        release order, then retained slots oldest-first."""
        assert not self.blocks_enabled, "block pools use alloc_row"
        if self._free:
            return self._free.popleft()
        victim = None
        for slot in self._retained:  # oldest first; no copy
            if slot not in exclude:
                victim = slot
                break
        if victim is None:
            return None
        del self._retained[victim]
        self._reclaim(victim)
        return victim

    def retain(self, slot: int):
        """Finished request: keep the slot's KV for prefix reuse. The
        slot moves to the retained LRU (most-recent end); if that
        overflows `retained_limit`, the OLDEST retained slot is
        demoted to the free list (and reclaimed for the index)."""
        assert not self.blocks_enabled, "block pools use retain_row"
        slot = int(slot)
        assert slot not in self._free and slot not in self._retained, (
            f"retain of non-busy slot {slot}")
        self._retained[slot] = None
        if (self.retained_limit is not None
                and len(self._retained) > max(self.retained_limit, 0)):
            old, _ = self._retained.popitem(last=False)
            self._reclaim(old)
            self._free.append(old)

    def touch(self, slot: int):
        """A prefix hit read `slot`'s KV — refresh its LRU position
        (no-op for running slots, which are not on the retained list)."""
        if slot in self._retained:
            self._retained.move_to_end(slot)

    def _reclaim(self, key):
        if self.on_reclaim is not None:
            self.on_reclaim(key)

    def release(self, slot: int):
        """Hard free (error/cancel eviction): the KV is NOT indexed for
        reuse — the engine drops any index entries itself. In block
        mode this is `release_row`."""
        if self.blocks_enabled:
            self.release_row(slot)
            return
        slot = int(slot)
        assert slot not in self._free, f"double free of slot {slot}"
        self._retained.pop(slot, None)
        self._free.append(slot)

    # ---- block-mode accounting (engine thread only) ------------------
    def _sync_map(self):
        # jnp.array COPIES (unlike jnp.asarray, which on the CPU
        # backend can alias the numpy buffer zero-copy). The copy is
        # load-bearing twice over: the map rides inside the DONATED
        # pool pytree, so an aliased buffer would be recycled by XLA
        # as scratch and corrupt the host-side map mid-flight; and
        # host-side map surgery must never mutate the map an already
        # dispatched program is still consuming.
        if isinstance(self.caches, list):
            # pipeline-sharded serving (serving/topology.py place_pool
            # under serving_pp>1): one BlockKV per layer stage, each
            # carrying its OWN replicated copy of the map on its stage
            # sub-mesh — block indices are dispatch data identical
            # across stages, so every stage re-uploads the same host
            # map (the per-stage invariant serving/invariants.py pins)
            sh = (self._map_sharding
                  if isinstance(self._map_sharding, list)
                  else [self._map_sharding] * len(self.caches))
            staged = []
            for bkv, s in zip(self.caches, sh):
                m = jnp.array(self._map)
                if s is not None:
                    m = jax.device_put(m, s)
                staged.append(bkv._replace(map=m))
            self.caches = staged
            return
        m = jnp.array(self._map)
        if self._map_sharding is not None:
            m = jax.device_put(m, self._map_sharding)
        self.caches = self.caches._replace(map=m)

    def _unref(self, block: int):
        self._acct_dirty = True
        self._rc[block] -= 1
        assert self._rc[block] >= 0, f"refcount underflow on {block}"
        if self._rc[block] == 0:
            self._free_blocks.append(block)

    def _evict_retained(self):
        key, ent = self._retained.popitem(last=False)
        if self.on_evict_entry is not None:
            # demotion BEFORE unref: the tier must gather the blocks'
            # device content while the entry still pins them. A failed
            # demotion only loses the host copy — eviction proceeds.
            try:
                self.on_evict_entry(ent)
            except Exception as e:  # noqa: BLE001 — tier is best-effort
                print_rank_0(
                    f"kv_pool: on_evict_entry failed for {key}: {e!r}")
        for b in ent.blocks:
            self._unref(b)
        self._reclaim(key)

    def _ensure_free_blocks(self, n: int) -> bool:
        while len(self._free_blocks) < n and self._retained:
            self._evict_retained()
        return len(self._free_blocks) >= n

    def map_row(self, slot: int) -> List[int]:
        return [int(b) for b in self._map[slot]]

    def alloc_row(self, alias: Sequence[int] = (), install: bool = True,
                  sync: bool = True) -> Optional[Tuple[int, List[int]]]:
        """Allocate a grid row plus its cap/B physical blocks.

        `alias` (a prefix of shared blocks, from a running row's map or
        a RetainedPrefix) is referenced IN PLACE — the hit's zero-copy
        half; only the remaining blocks come fresh from the free pool,
        evicting retained entries LRU-first under pressure (aliased
        entries may evict too: the refs taken here keep their blocks
        alive). Returns (slot, block_list) or None; with
        `install=False` the map row stays on TRASH — the caller must
        `install_row` at activation time, so that the grid's idle
        writes for the still-inactive row can never touch the blocks
        (aliased ones especially) before the prefill lands."""
        assert self.blocks_enabled
        if not self._free:
            return None
        alias = list(alias)
        assert len(alias) <= self.blocks_per_slot
        self._acct_dirty = True
        for b in alias:
            self._rc[b] += 1  # take refs FIRST: eviction-safe
        need = self.blocks_per_slot - len(alias)
        if not self._ensure_free_blocks(need):
            for b in alias:
                self._unref(b)
            return None
        fresh = [self._free_blocks.popleft() for _ in range(need)]
        for b in fresh:
            assert self._rc[b] == 0, b
            self._rc[b] = 1
        slot = self._free.popleft()
        blocks = alias + fresh
        if install:
            self.install_row(slot, blocks, sync=sync)
        return slot, blocks

    def install_row(self, slot: int, blocks: Sequence[int],
                    sync: bool = True):
        """Point `slot`'s map at its blocks (refs already held by
        alloc_row) — called at activation, right before the insert.
        `sync=False` defers the device-map upload so a batched caller
        (the engine's group prefill) can install several rows and pay
        ONE `_sync_map` instead of one per row."""
        assert self.blocks_enabled
        self._map[slot] = blocks
        if sync:
            self._sync_map()

    def drop_blocks(self, blocks: Sequence[int]):
        """Unref blocks held OUTSIDE a map row (an aborted pending
        prefill whose row was never installed)."""
        for b in blocks:
            self._unref(b)

    def release_row(self, slot: int):
        """Free a grid row: unref its mapped blocks, park the map on
        TRASH (idle garbage writes land there), return the row."""
        assert self.blocks_enabled
        slot = int(slot)  # np.int64 from np.nonzero must not leak into
        #                   the row deque and become index keys later
        self._acct_dirty = True
        assert slot not in self._free, f"double free of slot {slot}"
        for b in self._map[slot]:
            if b != self.TRASH:
                self._unref(int(b))
        self._map[slot] = self.TRASH
        self._sync_map()
        self._free.append(slot)

    def retain_row(self, slot: int, length: int, tokens: List[int],
                   namespace=None):
        """Finished request, block mode: convert the row into a
        row-less RetainedPrefix pinning only the blocks covering
        `length` tokens (ALL ring blocks for rolling pools — the
        window is wholly live); the tail blocks and the grid row free
        immediately. Returns the retained key (for the prefix index),
        or None when `retained_limit` is 0. Overflowing the limit
        evicts the OLDEST entry (on_reclaim fires with its key)."""
        assert self.blocks_enabled
        if self.retained_limit is not None and self.retained_limit <= 0:
            self.release_row(slot)
            return None
        if self.rolling:
            live = self.blocks_per_slot
        else:
            live = min(-(-int(length) // self.block_size),
                       self.blocks_per_slot)
        blocks = [int(b) for b in self._map[slot][:live]]
        assert all(b != self.TRASH for b in blocks), (slot, blocks)
        key = ("ret", next(self._ret_ids))
        self._acct_dirty = True
        for b in blocks:
            self._rc[b] += 1  # the entry's refs, before the row drops its own
        self.release_row(slot)
        self._retained[key] = RetainedPrefix(key, blocks, int(length),
                                             list(tokens),
                                             namespace=namespace)
        if (self.retained_limit is not None
                and len(self._retained) > self.retained_limit):
            self._evict_retained()
        return key

    def gather_blocks_host(self, blocks: Sequence[int]):
        """Fetch an explicit physical-block list's arena content to
        HOST numpy arrays — the host-RAM tier's demotion read (engine
        thread, during retained-entry eviction: the blocks are still
        pinned, so the gather reads stable content). Returns
        {"k", "v"[, "k_scale", "v_scale"]} shaped [L, nb, B, nkv, *]."""
        assert self.blocks_enabled
        a = self.caches.arena
        idx = jnp.asarray(list(blocks), jnp.int32)
        # np.array (copy): device_get may hand back a read-only view
        # of the transfer buffer — the tier owns mutable host memory
        out = {"k": np.array(jax.device_get(jnp.take(a.k, idx, axis=1))),
               "v": np.array(jax.device_get(jnp.take(a.v, idx, axis=1)))}
        if a.k_scale is not None:
            out["k_scale"] = np.array(
                jax.device_get(jnp.take(a.k_scale, idx, axis=1)))
            out["v_scale"] = np.array(
                jax.device_get(jnp.take(a.v_scale, idx, axis=1)))
        return out

    def host_blocks_to_sub(self, arrays, plen: int,
                           pad_to_cap: bool = True) -> KVCache:
        """Assemble host-gathered block arrays into a batch-1 cache in
        the pool's layout, positioned at `plen` — the host-RAM tier's
        restore write (`device_put` half): the engine hands this sub to
        the normal suffix-prefill + insert path, so a restore needs no
        pool-accounting surgery and lands through already-compiled
        programs. Positions past the restored blocks are zeros — they
        sit at/after the sub's offset, where appends overwrite them
        write-before-read (the bucketed-prefill invariant).

        `pad_to_cap=False` returns the TRUNCATED [L, 1, nb*B, ...]
        layout instead — only the live blocks' bytes are uploaded; the
        disaggregated engine widens it on the prefill mesh so the
        cap-sized zero tail never rides a transfer (the same
        block-granular discipline as the prefill→decode handoff)."""
        assert self.blocks_enabled
        L, nb, B = arrays["k"].shape[:3]
        cap = self.cap if pad_to_cap else nb * B

        def fill(name, tail_shape, fill_value, dtype):
            a = arrays[name]
            if not pad_to_cap:
                return jnp.asarray(
                    a.reshape((L, 1, nb * B) + a.shape[3:]))
            full = np.full((L, 1, cap) + tail_shape, fill_value,
                           dtype=dtype)
            full[:, 0, :nb * B] = a.reshape((L, nb * B) + a.shape[3:])
            return jnp.asarray(full)

        quant = "k_scale" in arrays
        nkv, hd = arrays["k"].shape[3], arrays["k"].shape[4]
        return KVCache(
            k=fill("k", (nkv, hd), 0, arrays["k"].dtype),
            v=fill("v", (nkv, hd), 0, arrays["v"].dtype),
            offset=jnp.full((L,), plen, jnp.int32),
            k_scale=(fill("k_scale", (nkv, 1), 1.0, np.float32)
                     if quant else None),
            v_scale=(fill("v_scale", (nkv, 1), 1.0, np.float32)
                     if quant else None),
        )

    def entry(self, key) -> Optional[RetainedPrefix]:
        return self._retained.get(key)

    def touch_key(self, key):
        if key in self._retained:
            self._retained.move_to_end(key)

    def drop_retained(self) -> int:
        """Reclaim EVERY retained entry/slot in one pass — the weight
        hot-swap's version-hygiene sweep (serving/engine.py
        `_apply_swap`): KV decoded under the old weights must not stay
        cloneable once the new weights serve, so retained prefixes die
        here rather than lingering unreachable until block pressure.
        `on_evict_entry` (host-tier demotion) deliberately does NOT
        fire — the caller is invalidating the old version everywhere,
        host tier included — while `on_reclaim` fires per entry so the
        (already rebuilt) index stays consistent. Returns the count."""
        n = len(self._retained)
        if self.blocks_enabled:
            hook, self.on_evict_entry = self.on_evict_entry, None
            try:
                while self._retained:
                    self._evict_retained()
            finally:
                self.on_evict_entry = hook
        else:
            while self._retained:
                slot, _ = self._retained.popitem(last=False)
                self._reclaim(slot)
                self._free.append(slot)
        return n

    # ---- capacity / introspection ------------------------------------
    def accounting(self) -> dict:
        """Read-only accounting snapshot for the system-wide invariant
        checker (serving/invariants.py): the raw refcounts, block map,
        free lists, and retained entries the KV-block conservation laws
        (refcounts == row refs + retained refs + pending refs;
        free + used == total; no cross-namespace block sharing) are
        recomputed against. Copies everything — the checker can never
        mutate pool state through it. Engine-thread state: call with
        the engine quiesced (idle/drained/closed), like
        `ServingEngine.invariant_state`."""
        out = {
            "blocks_enabled": self.blocks_enabled,
            "num_slots": self.num_slots,
            "free_rows": [int(s) for s in self._free],
            "retained": {
                key: {
                    "blocks": (list(ent.blocks)
                               if self.blocks_enabled else None),
                    "length": (ent.length if self.blocks_enabled
                               else None),
                    "namespace": (getattr(ent, "namespace", None)
                                  if self.blocks_enabled else None),
                }
                for key, ent in self._retained.items()
            },
            "rolling": self.rolling,
        }
        if self.blocks_enabled:
            out.update({
                "rc": self._rc.copy(),
                "map": self._map.copy(),
                "free_blocks": [int(b) for b in self._free_blocks],
                "total_blocks": self.total_blocks,
                "trash": self.TRASH,
                "blocks_per_slot": self.blocks_per_slot,
            })
        return out

    def free_count(self) -> int:
        """Allocatable slots. Whole-region mode: truly free + lazily
        evictable retained. Block mode: the CONSERVATIVE bound
        min(free rows, worst-case-fresh admissions the free +
        reclaimable blocks can back) — prefix aliasing only ever needs
        fewer fresh blocks than this assumes. A block is RECLAIMABLE
        when every one of its refs comes from retained entries
        (evicting them frees it) — counting only rc==1 blocks here
        would be a LIVENESS bug: multi-turn chains retain entries that
        alias each other's blocks (rc >= 2 with no row holding them),
        and since pop_ready(free_count()) gates the only path that
        evicts retained entries, undercounting them would starve
        admission permanently."""
        if not self.blocks_enabled:
            return len(self._free) + len(self._retained)
        if not self._acct_dirty:
            return self._free_count_cache
        retained_refs: collections.Counter = collections.Counter()
        for ent in self._retained.values():
            for b in ent.blocks:
                retained_refs[b] += 1
        avail = len(self._free_blocks) + sum(
            1 for b, n in retained_refs.items() if self._rc[b] == n)
        self._free_count_cache = min(len(self._free),
                                     avail // self.blocks_per_slot)
        self._acct_dirty = False
        return self._free_count_cache

    def free_rows(self) -> int:
        """Race-free free grid-row count. `health()` snapshots read
        this from HTTP threads; `free_count()`'s memoized
        reclaimable-block walk is ENGINE-THREAD-ONLY (a cross-thread
        call could mark a dirty memo clean mid-mutation and feed
        admission a stale gate)."""
        return len(self._free)

    def retained_count(self) -> int:
        return len(self._retained)

    def shared_block_count(self) -> int:
        """Physical blocks held by MORE than one owner (row maps,
        retained entries, pending-prefill aliases) — the COW-alias
        gauge. An n-best fan-out aliasing the leader's prompt blocks
        raises this by (children sharing) × (prompt blocks); when the
        fan-out finishes and every child releases, it must return to
        its pre-fan-out value — the refcount no-leak pin
        (tests/test_structured.py, measured with retained_slots=0:
        a retained prefix LEGITIMATELY keeps the prompt blocks pinned
        across requests, which is reuse, not a leak). 0 for
        whole-region pools (they never alias)."""
        if not self.blocks_enabled:
            return 0
        return int(np.sum(self._rc[:self.TRASH] > 1))

    def block_refcount(self, block: int) -> int:
        """One block's live reference count (engine-thread accounting
        truth) — test introspection for the COW-alias lifecycle."""
        assert self.blocks_enabled
        return int(self._rc[int(block)])

    def used_count(self) -> int:
        if self.blocks_enabled:
            return self.num_slots - len(self._free)
        return self.num_slots - self.free_count()

    def nbytes(self) -> int:
        # pipeline-sharded pools hold a per-stage list of layer-sliced
        # arenas — the stages partition the layer axis, so their sum is
        # the same total the single arena would report
        if isinstance(self.caches, list):
            def _one(c):
                n = c.k.nbytes + c.v.nbytes
                if c.k_scale is not None:
                    n += c.k_scale.nbytes + c.v_scale.nbytes
                return n
            return sum(_one(b.arena) for b in self.caches)
        c = self.caches.arena if self.blocks_enabled else self.caches
        if isinstance(c, HybridKVCache):
            return self.ring_nbytes() + self.full_nbytes()
        if isinstance(c, LatentKVCache):
            return c.c.nbytes
        if isinstance(c, ConvKVCache):
            return (c.k.nbytes + c.v.nbytes + c.conv.nbytes
                    + (0 if c.ssm is None else c.ssm.nbytes))
        if isinstance(c, LatentStateCache):
            return c.c.nbytes + c.conv.nbytes + c.ssm.nbytes
        n = c.k.nbytes + c.v.nbytes
        if c.k_scale is not None:
            n += c.k_scale.nbytes + c.v_scale.nbytes
        return n

    def conv_state_nbytes(self) -> int:
        """Bytes of the convolutions' state (0 where the pool has none)."""
        return self.caches.conv.nbytes if self.conv_layers else 0

    def _scan_state_nbytes(self, kind: str) -> int:
        ssm = getattr(self.caches, "ssm", None)
        held = ssm is not None and self.cfg.state_kind == kind
        return ssm.nbytes if held else 0

    def ssm_state_nbytes(self) -> int:
        """Bytes of the Mamba-1 scans' state, [d_state, d_inner] float32 a
        layer a slot (0 where the pool has none)."""
        return self._scan_state_nbytes("mamba")

    def ssd_state_nbytes(self) -> int:
        """Bytes of the Mamba-2 scans' state, [heads, head_dim, d_state]
        float32 a layer a slot (0 where the pool has none)."""
        return self._scan_state_nbytes("mamba2")

    def kda_state_nbytes(self) -> int:
        """Bytes of the delta rule's state, [heads, head_dim, head_dim]
        float32 a layer a slot (0 where the pool has none)."""
        return self._scan_state_nbytes("kda")

    def gdn_state_nbytes(self) -> int:
        """Bytes of a Gated DeltaNet rule's state, [value heads,
        key_head_dim, value_head_dim] float32 a layer a slot (0 where the
        pool has none)."""
        return self._scan_state_nbytes("linear_attention")

    def ring_nbytes(self) -> int:
        """Bytes of the window layers' rings (0 where the pool has none)."""
        if not self.hybrid:
            return 0
        return self.caches.ring_k.nbytes + self.caches.ring_v.nbytes

    def full_nbytes(self) -> int:
        """Bytes of the whole regions: the full layers' of a pool of two
        kinds, else the whole pool."""
        if not self.hybrid:
            return (self.nbytes() - self.conv_state_nbytes()
                    - self.ssm_state_nbytes() - self.ssd_state_nbytes()
                    - self.kda_state_nbytes() - self.gdn_state_nbytes())
        return self.caches.full_k.nbytes + self.caches.full_v.nbytes

    def bytes_per_slot(self) -> int:
        """What one slot reserves, whatever it holds: its regions and,
        where the pool has them, its rings or its convolution state."""
        return self.nbytes() // self.num_slots

    def view_nbytes(self) -> int:
        """Bytes of ONE materialized contiguous [L, S, cap, ...] view
        (k + v + int8 scales) — the traffic unit of a single
        `resolve_view` gather or `scatter_view` write-back, feeding
        the engine's kv_gather_bytes_per_step gauge. Defined for every
        layout (whole-region pools never bracket, but the unit is
        still what a bracket WOULD move)."""
        if self.hybrid:
            return self.nbytes()
        n = (self.kv_layers * self.num_slots * self.cap
             * self.cfg.kv_row_width * self.dtype.itemsize)
        if self.dtype == jnp.dtype(jnp.int8):
            n += 2 * (self.kv_layers * self.num_slots * self.cap
                      * self.cfg.num_kv_heads) * 4  # fp32 scales
        return n

    def bytes_per_token(self) -> int:
        """k+v (and int8 scale) bytes one cached token costs across
        layers — the unit behind kv_bytes_wasted. From the cache's own row
        width (`ModelConfig.kv_row_width`: 2 x kv heads x head dim, or a
        latent row). In a pool of rings and regions a token costs a row in
        every FULL layer's region for as long as its sequence lives, and
        that is what this counts: its rows in the rings are reserved with
        the slot (`bytes_per_slot`, `ring_nbytes`) and cost the same
        whatever the sequence's length, so they are no part of what a
        shorter sequence leaves unused (`kv_bytes_wasted`). A convolution
        layer holds no row at all: its state, too, is the slot's."""
        n = self.kv_layers * self.cfg.kv_row_width * self.dtype.itemsize
        if self.dtype == jnp.dtype(jnp.int8):
            n += 2 * self.kv_layers * self.cfg.num_kv_heads * 4
        return n

    def kv_gauges(self, lengths) -> Tuple[int, int, int]:
        """(kv_blocks_used, kv_blocks_retained, kv_bytes_wasted) for
        the serving metrics. `lengths` is the engine's per-slot length
        array (live token counts for rows; block mode adds retained
        entries' own lengths — they hold no row). kv_bytes_wasted is
        reserved-minus-live: the internal-fragmentation gauge the
        block refactor exists to shrink. Whole-region pools report in
        region units (1 region == 1 "block")."""
        lengths = np.minimum(np.asarray(lengths), self.cap)
        if self.blocks_enabled:
            used = int(self.total_blocks - 1 - len(self._free_blocks))
            # per-PHYSICAL-block live-token coverage: aliased blocks
            # (one physical block in several maps/entries) count once,
            # at their maximum coverage — so reserved-minus-live is
            # the true fragmentation, not inflated by sharing
            B = self.block_size
            cover = np.zeros(self.total_blocks, np.int64)

            def _cover(blocks, ntok):
                for i, b in enumerate(blocks):
                    c = min(max(ntok - i * B, 0), B)
                    if c > cover[b]:
                        cover[b] = c

            for slot in range(self.num_slots):
                if lengths[slot] > 0:
                    _cover(self._map[slot], int(lengths[slot]))
            pinned = set()
            for e in self._retained.values():
                _cover(e.blocks, min(e.length, self.cap))
                pinned.update(e.blocks)
            retained = len(pinned)
            cover[self.TRASH] = 0
            live = int(cover.sum())
            reserved = used * B
        else:
            used = self.num_slots - len(self._free)
            retained = len(self._retained)
            live = int(lengths.sum())
            reserved = used * self.cap
        wasted = max(reserved - live, 0) * self.bytes_per_token()
        return used, retained, wasted


def slot_nbytes(cfg: ModelConfig, max_len: int,
                dtype=jnp.bfloat16, block_size: Optional[int] = None) -> int:
    """Bytes ONE slot's cache region will occupy (k+v, plus int8
    scales), without allocating — for sizing num_slots against free
    device memory before building the pool. The capacity comes from
    `generation.kv_region_cap`, the SAME helper `init_kv_caches`
    allocates from, so this can never disagree with the pool the
    engine actually builds. `block_size` rounds the region up to
    whole blocks (a no-op when it divides the cap, which
    ServingConfig.validate enforces)."""
    if cfg.window_layer_period:
        # rings for the window layers, whole regions for the full ones
        periods = cfg.num_layers // cfg.window_layer_period
        rows = periods * (cfg.window_layers_per_period
                          * min(cfg.sliding_window, max_len) + max_len)
        return rows * cfg.kv_row_width * jnp.dtype(dtype).itemsize
    cap = kv_region_cap(cfg, max_len)
    if block_size is not None and block_size < cap:
        cap = -(-cap // block_size) * block_size
    n = cfg.kv_layers * cap * cfg.kv_row_width * jnp.dtype(dtype).itemsize
    if jnp.dtype(dtype) == jnp.dtype(jnp.int8):
        n += 2 * (cfg.kv_layers * cap * cfg.num_kv_heads) * 4  # fp32 scales
    # a state layer's fixed size, whatever the length: the depthwise
    # kernel's inputs in the pool's dtype, the scan's matrix in float32
    n += cfg.state_layers * cfg.conv_state_width * jnp.dtype(dtype).itemsize
    n += cfg.state_layers * cfg.ssm_state_width * 4
    return n


def fit_num_slots(cfg: ModelConfig, max_len: int, dtype=jnp.bfloat16,
                  requested: int = 8, headroom: float = 0.8,
                  block_size: Optional[int] = None,
                  pending_bytes: int = 0, shards: int = 1) -> int:
    """Clamp `requested` slots to what one device's free memory can
    hold. The pool's budget is bytes_limit - bytes_in_use -
    `pending_bytes`: what is resident now, less what the caller will
    still place on the device after sizing (weights staged on the host
    are not in bytes_in_use yet — the server CLI passes their byte
    count). `shards` is the tensor-parallel width the weights and the
    KV arena are split over: each device holds 1/shards of both. The
    CPU backend has no memory stats and returns `requested` unchanged;
    on any other platform missing stats are an error."""
    import jax
    dev = jax.local_devices()[0]
    stats = dev.memory_stats()
    if not stats or not stats.get("bytes_limit"):
        if dev.platform == "cpu":
            return requested
        raise RuntimeError(
            f"fit_num_slots: {dev.platform} device {dev.device_kind!r} "
            "reports no memory stats (bytes_limit); pass --num_slots")
    free = (stats["bytes_limit"] - stats.get("bytes_in_use", 0)
            - pending_bytes // shards)
    fit = int(free * headroom) // max(
        slot_nbytes(cfg, max_len, dtype, block_size) // shards, 1)
    return max(1, min(requested, fit))
