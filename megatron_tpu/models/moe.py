"""Mixture-of-Experts MLP with expert parallelism.

ABSENT in the reference (SURVEY.md §2.8: "Expert parallelism (MoE) —
absent") — provided here the TPU-native way, like the ring/Ulysses
context parallelism: experts are one more sharded parameter dimension,
not a process group. The GShard/Switch dense-dispatch formulation keeps
every shape static for XLA:

- router: logits = x @ wr, softmax in fp32, top-k gates renormalized;
- capacity C = ceil(top_k * s * capacity_factor / E) per expert; each
  token takes the next free slot of its chosen experts (cumsum position,
  k=0 round gets priority, overflow tokens drop — the standard Switch
  semantics);
- dispatch/combine are einsums against a [b, s, E, C] one-hot tensor, so
  expert parallelism is purely the 'experts'-axis sharding on the expert
  weight bank [E, ...] — GSPMD inserts the all-to-alls;
- load-balancing aux loss (Switch eq. 4): E * sum_e f_e * P_e, where f_e
  is the top-1 dispatch fraction and P_e the mean router probability.
  loss_fn adds cfg.moe_aux_loss_coeff * aux.

Two dispatch implementations share the same routing semantics (capacity
fills k=0 choices first, then k=1, ...; within a round, earlier sequence
positions win; overflow drops):

- "sort" (default): the (token, k) choices are sorted by expert id
  (stable sort keeps the priority order), the slot index inside each
  expert is rank-minus-segment-start, and tokens move through ONE
  scatter-add into the [E, C, h] expert blocks and one gather back.
  Memory is O(s * top_k * h) — linear in sequence length — so MoE
  composes with long context. The sort itself is O(sK log sK) int32 work
  per layer, noise beside the expert GEMMs.
- "dense": the original GShard einsum against a [b, s, E, C] one-hot
  dispatch tensor — O(s^2 * top_k * capacity_factor) elements. Kept as
  the semantic oracle (sort-vs-dense equality is tested) and for
  explicit A/B on chip.

Expert parallelism is the 'experts'-axis sharding on the weight bank and
the [b, E, C, h] blocks in both paths; GSPMD partitions the dense
einsums directly and the sort path's scatter/gather by resharding the
(small, [b, sK]) index vectors.

A third dispatch has no capacity at all:

- "dropless" (OLMoE's default): the router's product and softmax run in
  float32; the (token, k) choices of the WHOLE [b*s] batch are sorted by
  expert (stable), the sorted rows are multiplied group by group by
  ops/grouped_matmul.py (rows [b*s*K, h] x bank [E, h, 2f], activation,
  x bank [E, f, h]), and each token sums its K rows with their weights.
  No [b, E, C, h] block exists, no token is dropped, and a token's output
  depends on no other token: what a served model needs, since a prompt
  prefilled in a padded bucket and decoded beside strangers must give the
  model's own full forward. Every row given is multiplied, a bucket's
  padding included: `model_forward` has no mask of real tokens to hand
  down (PERF.md section 7, PR 27). One device only (config.validate
  refuses a mesh).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from megatron_tpu.config import ModelConfig
from megatron_tpu.models.mlp import activation_fn


def moe_capacity(cfg: ModelConfig, seq: int) -> int:
    return int(math.ceil(cfg.moe_top_k * seq * cfg.moe_capacity_factor
                         / cfg.num_experts))


def moe_init(rng, cfg: ModelConfig, dtype=jnp.float32):
    E = cfg.num_experts
    h = cfg.hidden_size
    ffn = cfg.ffn_hidden_size
    kr, k1, k2 = jax.random.split(rng, 3)
    std = cfg.init_method_std
    out_std = (std / math.sqrt(2.0 * cfg.num_layers)
               if cfg.use_scaled_init else std)
    # A GLU bank is [E, h, 2f], gate columns then value columns, whatever
    # the dispatch: the matrix the grouped product reads as it is. (The
    # dense MLP's [h, 2, f] with an expert axis in front has a dimension of
    # 2 next to the minor one, which the device tiles as (2, 128): viewing
    # that as [E, h, 2f] is a copy of the whole bank, 2.6 ms a layer in
    # every step at OLMoE's widths; PERF.md section 6, PR 27.)
    w1 = jax.random.normal(
        k1, (E, h, 2 * ffn if cfg.is_glu else ffn), dtype) * std
    params = {
        "router": jax.random.normal(kr, (h, E), dtype) * std,
        "w1": w1,
        "w2": jax.random.normal(k2, (E, ffn, h), dtype) * out_std,
    }
    if cfg.use_bias:
        b1_shape = (E, 2, ffn) if cfg.is_glu else (E, ffn)
        params["b1"] = jnp.zeros(b1_shape, dtype)
        params["b2"] = jnp.zeros((E, h), dtype)
    return params


def moe_axes(cfg: ModelConfig):
    # experts shard over 'tp' (expert parallelism); the ffn dim stays
    # unsharded — one expert's GEMM runs whole on its device
    axes = {
        "router": ("embed", None),
        "w1": ("experts", "embed", None),
        "w2": ("experts", None, "embed"),
    }
    if cfg.use_bias:
        axes["b1"] = (("experts", None, None) if cfg.is_glu
                      else ("experts", None))
        axes["b2"] = ("experts", None)
    return axes


def moe_dispatch(idx, gates, E: int, C: int):
    """Build the dispatch/combine tensors [b, s, E, C] from top-k routing.

    Capacity slots fill k=0 choices first, then k=1, ... (Switch
    priority); each (token, k) choice takes the next free slot of its
    expert via a sequence cumsum offset by the earlier rounds' running
    per-expert counts. Tokens past capacity drop (dispatch row all-zero).
    Invariants (tested in tests/test_moe.py): each filled slot holds
    exactly one token; with ample capacity every token occupies exactly
    its top-k slots and its combine weights sum to 1."""
    dispatch = 0.0
    combine = 0.0
    count = 0.0
    for k in range(idx.shape[-1]):
        onek = jax.nn.one_hot(idx[..., k], E, dtype=jnp.float32)
        pos = (jnp.cumsum(onek, axis=1) - onek) + count
        keep = (pos < C) * onek                              # [b, s, E]
        slot = jax.nn.one_hot(pos.astype(jnp.int32), C,
                              dtype=jnp.float32) * keep[..., None]
        dispatch = dispatch + slot
        combine = combine + slot * gates[..., k][:, :, None, None]
        count = count + jnp.sum(onek, axis=1)[:, None, :]
    return dispatch, combine


def _sort_route(idx, gates, E: int, C: int):
    """Per-batch-row routing by stable sort (vmapped over b).

    idx/gates: [s, K] -> entry arrays [K*s] in k-major order (all k=0
    choices first — the Switch priority; within a k, sequence order):
    (expert, token, gate, slot, keep). Slot = the entry's rank among
    same-expert entries; computed as sorted-rank minus the expert's
    segment start, then scattered back to entry order. Exactly the
    bookkeeping moe_dispatch materializes as [s, E, C] one-hots, in
    O(sK) memory."""
    s, K = idx.shape
    e = idx.T.reshape(-1)                        # [K*s], k-major
    g = gates.T.reshape(-1)
    tok = jnp.tile(jnp.arange(s), K)
    order = jnp.argsort(e)                       # stable in jax
    e_sorted = e[order]
    counts = jnp.zeros((E,), jnp.int32).at[e_sorted].add(1)
    seg_start = jnp.cumsum(counts) - counts      # exclusive cumsum [E]
    pos_sorted = jnp.arange(K * s) - seg_start[e_sorted]
    n = K * s
    pos = jnp.zeros((n,), jnp.int32).at[order].set(pos_sorted)
    keep = pos < C
    return e, tok, g, pos, keep


def _dropless_experts(params, x, idx, gates, cfg: ModelConfig):
    """The dropless expert products and their combination. x [b, s, h],
    idx / gates [b, s, K] -> y [b, s, h]."""
    from megatron_tpu.ops.grouped_matmul import grouped_matmul
    b, s, h = x.shape
    E, K, f = cfg.num_experts, cfg.moe_top_k, cfg.ffn_hidden_size
    n, dtype = b * s, x.dtype
    with jax.named_scope("mtpu/moe/route"):
        e = idx.reshape(n * K)               # row r: token r // K, choice r % K
        e_sorted, order = jax.lax.sort_key_val(
            e, jnp.arange(n * K, dtype=jnp.int32))        # stable
        starts = jnp.searchsorted(e_sorted, jnp.arange(E + 1, dtype=e.dtype))
        group_sizes = jnp.diff(starts).astype(jnp.int32)
        rows = x.reshape(n, h)[order // K]   # [n*K, h], sorted by expert
    with jax.named_scope("mtpu/moe/experts"):
        y1 = grouped_matmul(rows, params["w1"].astype(dtype), group_sizes)
        if cfg.is_glu:                       # bank [E, h, 2f]: gate, value
            act = activation_fn(cfg.activation, y1[:, :f], y1[:, f:])
        else:
            act = activation_fn(cfg.activation, y1)
        y2 = grouped_matmul(act, params["w2"].astype(dtype), group_sizes)
    with jax.named_scope("mtpu/moe/combine"):
        # back to (token, choice) order
        inv = jnp.zeros((n * K,), jnp.int32).at[order].set(
            jnp.arange(n * K, dtype=jnp.int32), unique_indices=True)
        y2 = y2[inv].reshape(n, K, h)
        y = jnp.einsum("nkh,nk->nh", y2.astype(jnp.float32),
                       gates.reshape(n, K))
    return y.astype(dtype).reshape(b, s, h)


def moe_apply(params, x, cfg: ModelConfig):
    """x: [b, s, h] -> (y [b, s, h], aux_loss scalar f32)."""
    b, s, h = x.shape
    E = cfg.num_experts
    K = cfg.moe_top_k
    C = moe_capacity(cfg, s)
    dtype = x.dtype
    dropless = cfg.moe_dispatch == "dropless"

    with jax.named_scope("mtpu/moe/route"):
        if dropless:
            # float32 throughout: a top-k choice that flips at a near-tie
            # between this router and the float32 reference's swaps an
            # expert, which no tolerance on the logits forgives
            logits = jnp.dot(x.astype(jnp.float32),
                             params["router"].astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
        else:
            logits = x @ params["router"].astype(dtype)     # [b, s, E]
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        gates, idx = jax.lax.top_k(probs, K)                # [b, s, K]
        if cfg.moe_norm_topk_prob:
            gates = gates / jnp.maximum(
                jnp.sum(gates, axis=-1, keepdims=True), 1e-9)

        # Switch aux loss on the top-1 assignment (before capacity drops)
        top1 = jax.nn.one_hot(idx[..., 0], E, dtype=jnp.float32)
        f_e = jnp.mean(top1, axis=(0, 1))                   # [E]
        p_e = jnp.mean(probs, axis=(0, 1))
        aux = E * jnp.sum(f_e * p_e)

    if dropless:
        assert not cfg.use_bias and cfg.quantized_gemm == "none", (
            "the dropless path has no expert bias and no int8 product")
        return _dropless_experts(params, x, idx, gates, cfg), aux

    if cfg.moe_dispatch == "dense":
        dispatch, combine = moe_dispatch(idx, gates, E, C)
        # dispatch -> per-expert token blocks [b, E, C, h]
        xin = jnp.einsum("bsec,bsh->bech", dispatch.astype(dtype), x)
    else:
        e, tok, g, pos, keep = jax.vmap(
            lambda i, ga: _sort_route(i, ga, E, C))(idx, gates)
        pos_c = jnp.minimum(pos, C - 1)      # dropped entries write 0s
        brow = jnp.arange(b)[:, None]
        contrib = x[brow, tok] * keep[..., None].astype(dtype)  # [b,KS,h]
        xin = jnp.zeros((b, E, C, h), dtype).at[brow, e, pos_c].add(contrib)
    w1 = params["w1"].astype(dtype)
    w2 = params["w2"].astype(dtype)

    def bank_gemm(xb, wb):
        # expert GEMMs honor --quantized_gemm like the dense MLP does
        if cfg.quantized_gemm == "int8":
            from megatron_tpu.ops.quantized import int8_expert_matmul
            return int8_expert_matmul(xb, wb)
        return jnp.einsum("beck,ekn->becn", xb, wb)

    # the weight banks are multiplied UNRESHAPED: under the 1F1B
    # store-activations stash, reshaped banks would stop being identity-
    # passthrough vjp leaves and a full bank copy would ride every stash
    # slot (the _assert_dedup_passthrough guard fires)
    y1 = bank_gemm(xin, w1)
    if cfg.is_glu:                           # [.., 2f] -> gate, value
        y1 = y1.reshape(*y1.shape[:-1], 2, cfg.ffn_hidden_size)
    if cfg.use_bias:
        y1 = y1 + params["b1"].astype(dtype)[None, :, None]
    if cfg.is_glu:
        act = activation_fn(cfg.activation, y1[..., 0, :], y1[..., 1, :])
    else:
        act = activation_fn(cfg.activation, y1)
    y2 = bank_gemm(act, w2)
    if cfg.use_bias:
        # per-expert output bias; dropped (not duplicated) tokens simply
        # never see it, matching the dispatch semantics
        y2 = y2 + params["b2"].astype(dtype)[None, :, None]
    if cfg.moe_dispatch == "dense":
        y = jnp.einsum("bech,bsec->bsh", y2, combine.astype(dtype))
    else:
        out = y2[brow, e, pos_c]                         # [b, KS, h]
        w = (g * keep).astype(dtype)
        y = (out * w[..., None]).reshape(b, K, s, h).sum(axis=1)
    return y, aux
