"""Plain reference of the LFM2-8B-A1B decoder (`lfm2_moe`): float32
`jax.numpy`, no kernel, no cache, no state, no batching, no sorting. Written
from the equations the published config.json names
(LiquidAI/LFM2-8B-A1B; ISSUE 37 writes them out):

    x = E[tokens];  n(x) = x / sqrt(mean(x^2) + 1e-5) * g
    layer l, mixer layer_types[l]:
        u = n_op(x)
        conv:  (B, C, z) = split3(u W_in)           W_in [h, 3h], no bias
               a_t = B_t * z_t
               c_t = w_0 a_{t-2} + w_1 a_{t-1} + w_2 a_t    w [3, h] depthwise,
                     causal: the whole sequence left-padded with two zeros
               m_t = (C_t * c_t) W_out              no activation anywhere
        full_attention:  q = u Wq (32 heads of 64), k, v = u Wkv (8 of 64)
               q, k <- RMSNorm over EACH head's 64 channels, one scale [64]
                       shared by the heads; then rotated (theta 1e6)
               m = softmax(q k^T / 8 + causal) v Wo   kv head g serves q
                                                      heads 4g .. 4g + 3
        x = x + m;  v = n_ffn(x)
        l < num_dense_layers:  x = x + (silu(v W1) * (v W3)) W2   width 7168
        else:  s = sigmoid(v W_r) over the 32 experts, float32
               S = the 4 largest of s + b          (b chooses, is not valued)
               g = s[S] / max(sum s[S], 1e-9)      (x routed_scaling_factor 1)
               x = x + sum_{e in S} g_e Expert_e(v)          no shared expert
    logits = n_f(x) E^T                                            (tied)

Every expert is computed for every token and weighted by `g` where the expert
is among the token's 4 and by 0 elsewhere, a `fori_loop` over the experts with
each expert's matrices cut out of the stacked banks and upcast where they are
used; attention is a `lax.map` over the heads (loops for the compiler's sake:
unrolled they cost the chip's compiler minutes, PERF.md section 6, PR 31), so
that the reference fits on the chip beside the engine's bf16 tree.

It reads the program's own parameter tree (`lm.model_init`), so these follow
the program's layout and not the Hugging Face file's, and are noted as
departures: `transformer` is {"dense", "moe"} (the `num_dense_layers`
leading layers and the rest), each {"conv", "full_attention"}: a kind's
layers stacked in the model's order; rotary pairs are the adjacent channels
(2i, 2i + 1) where the published weights pair channel i with i + 32 (the same
function under a fixed permutation of Wq's and Wk's columns and the head
norms' scale, which random weights cannot tell apart); `wkv` [h, 2 x 8 x 64]
holds k's columns then v's; the three thirds of `in_proj` are (B, C, z) in
that order; a dense MLP's `w1` is [h, 2, f] (gate, up); an expert's gate and
up are the first and second f columns of `w1[e]` [h, 2f]. `assumed`, not in
config.json: the tied head; the divisor's floor 1e-9 (`models/moe.py`'s own:
a sum of four sigmoids never comes near it).

Every matrix product runs under `jax.default_matmul_precision("highest")`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rmsnorm(p, x, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * p["scale"].astype(F32)


def _rotary(x, theta):
    """x: [s, heads, d]; position p rotates the pair (2i, 2i+1) by
    p * theta^(-2i/d)."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                     axis=-1).reshape(x.shape)


def short_conv(p, u, cfg):
    """`p`: one layer's `conv` parameters as held; u [s, h] -> [s, h]. The
    convolution over the whole sequence, left-padded with L - 1 zeros."""
    s, h = u.shape
    L = cfg.conv_L_cache
    bcz = u @ p["in_proj"].astype(F32)
    gate_b, gate_c, z = bcz[:, :h], bcz[:, h:2 * h], bcz[:, 2 * h:]
    a = jnp.pad(gate_b * z, ((L - 1, 0), (0, 0)))
    w = p["conv"].astype(F32)
    c = sum(w[j] * a[j:j + s] for j in range(L))
    return (gate_c * c) @ p["out_proj"].astype(F32)


def attention(p, u, cfg):
    """`p`: one layer's `attention` parameters as held; u [s, h] -> [s, h].
    A head at a time: [s, s] scores, never [heads, s, s]."""
    s = u.shape[0]
    nq, nkv, hd = cfg.num_attention_heads, cfg.num_kv_heads, cfg.kv_channels
    eps = cfg.norm_epsilon
    q = (u @ p["wq"].astype(F32)).reshape(s, nq, hd)
    kv = (u @ p["wkv"].astype(F32)).reshape(s, 2, nkv, hd)
    k, v = kv[:, 0], kv[:, 1]
    q = _rotary(_rmsnorm(p["q_norm"], q, eps), cfg.rope_theta)
    k = _rotary(_rmsnorm(p["k_norm"], k, eps), cfg.rope_theta)
    causal = jnp.tril(jnp.ones((s, s), bool))
    group = nq // nkv

    def head(n):
        q_h = jax.lax.dynamic_index_in_dim(q, n, 1, False)
        k_h = jax.lax.dynamic_index_in_dim(k, n // group, 1, False)
        v_h = jax.lax.dynamic_index_in_dim(v, n // group, 1, False)
        scores = q_h @ k_h.T / math.sqrt(hd)
        return jax.nn.softmax(jnp.where(causal, scores, -jnp.inf),
                              axis=-1) @ v_h
    out = jax.lax.map(head, jnp.arange(nq))                   # [nq, s, hd]
    return out.swapaxes(0, 1).reshape(s, nq * hd) @ p["wo"].astype(F32)


def gate_weights(router, bias, v, cfg):
    """[s, experts] float32: g where the expert is among the token's top k
    of s + b (ties to the lower index, as `jax.lax.top_k` breaks them), 0
    elsewhere."""
    scores = jax.nn.sigmoid(v @ router)
    _, idx = jax.lax.top_k(scores + bias, cfg.moe_top_k)
    g = jnp.take_along_axis(scores, idx, axis=-1)
    g = g / jnp.maximum(jnp.sum(g, axis=-1, keepdims=True), 1e-9)
    g = g * cfg.moe_routed_scaling_factor
    rows = jnp.arange(v.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, idx].set(g)


def _glu(v, gate, up, down):
    return (jax.nn.silu(v @ gate) * (v @ up)) @ down


def experts(mlp, v, cfg, at: int):
    """(the routed sum [s, h], the gate weights [s, experts]) of layer `at`
    of a kind's stacked `mlp`: each expert's matrices are cut straight out
    of the stacked banks and upcast where they are used."""
    w = gate_weights(mlp["router"][at].astype(F32),
                     mlp["e_score_correction_bias"][at].astype(F32), v, cfg)
    f = mlp["w2"].shape[-2]

    def pick(bank, e):
        cut = jax.lax.dynamic_slice(
            bank, (at, e, 0, 0), (1, 1) + bank.shape[-2:])
        return cut.reshape(bank.shape[-2:]).astype(F32)

    def add_expert(e, out):
        w1 = pick(mlp["w1"], e)
        y = _glu(v, w1[:, :f], w1[:, f:], pick(mlp["w2"], e))
        return out + jax.lax.dynamic_slice_in_dim(w, e, 1, axis=1) * y
    return jax.lax.fori_loop(0, cfg.num_experts, add_expert,
                             jnp.zeros_like(v)), w


def block(stack, x, cfg, kind: str, at: int, dense: bool):
    """Layer `at` of the stacked parameters of one kind of one group: (x',
    the gate weights or None)."""
    eps = cfg.norm_epsilon
    rest = jax.tree.map(lambda a: a[at],
                        {k: v for k, v in stack.items() if k != "mlp"})
    u = _rmsnorm(rest["input_norm"], x, eps)
    x = x + (short_conv(rest["conv"], u, cfg) if kind == "conv"
             else attention(rest["attention"], u, cfg))
    v = _rmsnorm(rest["post_attn_norm"], x, eps)
    if dense:
        w1 = stack["mlp"]["w1"][at].astype(F32)
        return x + _glu(v, w1[:, 0], w1[:, 1],
                        stack["mlp"]["w2"][at].astype(F32)), None
    y, w = experts(stack["mlp"], v, cfg, at)
    return x + y, w


def _trunk(params, tokens, cfg):
    """(the last layer's output [s, h] before the final norm, the gate
    weights of every expert layer)."""
    assert (cfg.layer_types is not None and cfg.first_k_dense_replace
            and cfg.qk_head_norm and cfg.num_experts > 1
            and cfg.activation == "swiglu" and cfg.norm_type == "rmsnorm"
            and cfg.moe_scoring_func == "sigmoid"
            and cfg.moe_score_correction_bias and cfg.moe_norm_topk_prob
            and not cfg.n_shared_experts and not cfg.use_bias
            and cfg.tie_embed_logits), \
        "this reference is the LFM2-8B-A1B block only"
    x = params["embedding"]["word_embeddings"][tokens].astype(F32)
    k, types = cfg.first_k_dense_replace, cfg.layer_types
    weights = []
    for l, kind in enumerate(types):
        dense = l < k
        group = types[:k] if dense else types[k:]
        at = group[:l if dense else l - k].count(kind)
        x, w = block(params["transformer"]["dense" if dense else "moe"][kind],
                     x, cfg, kind, at, dense)
        if w is not None:
            weights.append(w)
    return x, weights


def _head(params, x, cfg, columns: int = 16384):
    """The tied head's matrix is upcast a block of rows of the embedding at
    a time."""
    x = _rmsnorm(params["final_norm"], x, cfg.norm_epsilon)
    emb = params["embedding"]["word_embeddings"]
    return jnp.concatenate(
        [x @ emb[i:i + columns].astype(F32).T
         for i in range(0, cfg.vocab_size, columns)],
        axis=-1)[:, :cfg.vocab_size]


def logits(params, tokens, cfg):
    """tokens [s] int -> logits [s, vocab] float32."""
    with jax.default_matmul_precision("highest"):
        x, _ = _trunk(params, tokens, cfg)
        return _head(params, x, cfg)


def token_logprobs(params, tokens, cfg, with_choices: bool = False,
                   tail: int | None = None):
    """log p(tokens[i+1] | tokens[:i+1]) for every i: [s-1] float32, or with
    `tail` for the last `tail` of them alone. With `with_choices` also
    [expert layers, s-1, experts] bool: which experts each of the s-1 input
    tokens chose, by this reference's own router."""
    tail = tokens.shape[0] - 1 if tail is None else tail
    with jax.default_matmul_precision("highest"):
        x, weights = _trunk(params, tokens[:-1], cfg)
        out = _head(params, x[-tail:], cfg)
    lp = jnp.take_along_axis(jax.nn.log_softmax(out, axis=-1),
                             tokens[-tail:, None], axis=-1)[:, 0]
    if with_choices:
        return lp, jnp.stack([w > 0 for w in weights])
    return lp
