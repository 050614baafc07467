"""Plain reference of the OLMoE decoder: float32 `jax.numpy`, no kernel, no
cache, no batching, no scan, no sorting, no capacity. It follows the
published block (allenai/OLMoE-1B-7B-0125-Instruct, `modeling_olmoe.py`):

    h = E[tokens]
    per layer:
        a  = RMSNorm(h)
        q  = RMSNorm_q(a Wq)  ;  k = RMSNorm_k(a Wk)  ;  v = a Wv
                     (norms over all 2048 channels, then split into 16 heads of 128)
        q, k = rotary(q), rotary(k)                                   (theta 10000)
        h  = h + softmax(q k^T / sqrt(128), causal) v . Wo
        m  = RMSNorm(h)
        p  = softmax(m Wr) in float32 over the 64 experts
        S  = the 8 largest p, weights p[S] as they are (not renormalised)
        h  = h + sum_{e in S} p_e . (silu(m Wgate_e) * (m Wup_e)) Wdown_e
                     (no capacity, no token dropped)
    logits = RMSNorm(h) . W_head^T                                    (untied)

Every expert is computed for every token and weighted by `p` where the
expert is among the token's 8 and by 0 elsewhere: a Python loop over layers
and experts, 64 dense products a layer.

It reads the program's own parameter tree (`lm.model_init`), so these follow
the program's layout and not the Hugging Face file's, and are noted as
departures: rotary pairs are the interleaved channels (2i, 2i+1) of a head,
where the HF weights pair channel i with i + 64 (the same function under a
fixed permutation of each head's q/k columns, which a checkpoint converter
applies, to the q_norm/k_norm scales too); k and v come from one fused
matrix `wkv` [h, 2, heads, 128] (k first); an expert's gate and up matrices
are the first and the second 1024 columns of `w1[e]` [h, 2048], its down
matrix `w2[e]`; the
head `lm_head` is [h, vocab] (HF: [vocab, h]). With `cfg.moe_norm_topk_prob`
the chosen weights are divided by their sum (`norm_topk_prob` true; OLMoE's
own config says false), and with `cfg.qk_norm` off the two norms are left
out: the tests use both to show the program's fields do what they say.

Every matrix product runs under `jax.default_matmul_precision("highest")`:
on a TPU a float32 product is otherwise computed in bf16 passes.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _rmsnorm(p, x, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * p["scale"].astype(jnp.float32)


def _rotary(x, theta):
    """x: [s, heads, d]; position p rotates the pair (2i, 2i+1) by
    p * theta^(-2i/d)."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                     axis=-1).reshape(x.shape)


def router_probs(p, m):
    """softmax(m Wr): [s, experts] float32."""
    return jax.nn.softmax(m @ p["router"], axis=-1)


def expert_weights(probs, cfg):
    """[s, experts]: p where the expert is among the token's top k (ties
    to the lower index, as `jax.lax.top_k` breaks them), 0 elsewhere."""
    top, idx = jax.lax.top_k(probs, cfg.moe_top_k)
    if cfg.moe_norm_topk_prob:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    rows = jnp.arange(probs.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, idx].set(top)


def _experts(p, i, m, cfg):
    """(sum of the chosen experts' outputs [s, h], the weights [s, experts]).
    `p` is the stacked tree of every layer's experts and `i` the layer: each
    expert's matrices are cut straight out of the stacked banks where they
    are used, so that no copy of a whole layer's bank (1.5 GiB at OLMoE's
    widths) stands beside the weights."""
    w = expert_weights(router_probs({"router": p["router"][i]}, m), cfg)
    out = jnp.zeros_like(m)
    f = p["w2"].shape[2]
    for e in range(cfg.num_experts):
        gate, up = p["w1"][i, e, :, :f], p["w1"][i, e, :, f:]
        y = (jax.nn.silu(m @ gate) * (m @ up)) @ p["w2"][i, e]
        out = out + w[:, e:e + 1] * y
    return out, w


def _attention(p, a, cfg):
    s = a.shape[0]
    nq, nkv, d = cfg.num_attention_heads, cfg.num_kv_heads, cfg.kv_channels
    q = a @ p["wq"]                                    # [s, nq * d]
    kv = (a @ p["wkv"]).reshape(s, 2, nkv * d)
    k, v = kv[:, 0], kv[:, 1]
    if cfg.qk_norm:                                    # over all channels
        q = _rmsnorm(p["q_norm"], q, cfg.norm_epsilon)
        k = _rmsnorm(p["k_norm"], k, cfg.norm_epsilon)
    q = _rotary(q.reshape(s, nq, d), cfg.rope_theta)
    k = _rotary(k.reshape(s, nkv, d), cfg.rope_theta)
    v = v.reshape(s, nkv, d)
    g = nq // nkv                                      # 1 for OLMoE (MHA)
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    scores = jnp.einsum("snd,tnd->nst", q, k) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("nst,tnd->snd", probs, v).reshape(s, nq * d) @ p["wo"]


def _block(stacked, i, h, cfg):
    """Layer `i` of the stacked tree `stacked`."""
    p = jax.tree.map(lambda x: x[i],
                     {k: v for k, v in stacked.items() if k != "mlp"})
    h = h + _attention(p["attention"],
                       _rmsnorm(p["input_norm"], h, cfg.norm_epsilon), cfg)
    m = _rmsnorm(p["post_attn_norm"], h, cfg.norm_epsilon)
    y, w = _experts(stacked["mlp"], i, m, cfg)
    return h + y, w


def _forward(params, tokens, cfg):
    """(logits [s, vocab], the router's weights of every layer)."""
    assert (cfg.num_experts > 1 and cfg.activation == "swiglu"
            and cfg.norm_type == "rmsnorm" and cfg.use_rotary_emb
            and not cfg.parallel_attn and not cfg.use_bias
            and not cfg.use_post_ln and not cfg.tie_embed_logits), \
        "this reference is the OLMoE block only"
    with jax.default_matmul_precision("highest"):
        f32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
        h = f32["embedding"]["word_embeddings"][tokens]
        n_layers = jax.tree.leaves(f32["transformer"])[0].shape[0]
        weights = []
        for i in range(n_layers):
            h, w = _block(f32["transformer"], i, h, cfg)
            weights.append(w)
        h = _rmsnorm(f32["final_norm"], h, cfg.norm_epsilon)
        return (h @ f32["lm_head"])[:, :cfg.vocab_size], weights


def logits(params, tokens, cfg):
    """tokens [s] int -> logits [s, vocab] float32 (padded columns of the
    head, if any, are cut off)."""
    return _forward(params, tokens, cfg)[0]


def token_logprobs(params, tokens, cfg, with_choices: bool = False):
    """log p(tokens[i+1] | tokens[:i+1]) for every i: [s-1] float32. With
    `with_choices` also [layers, s-1, experts] bool: which experts each of
    the s-1 input tokens chose, by this reference's own router."""
    out, weights = _forward(params, tokens[:-1], cfg)
    lp = jnp.take_along_axis(jax.nn.log_softmax(out, axis=-1),
                             tokens[1:, None], axis=-1)[:, 0]
    if with_choices:
        return lp, jnp.stack([w > 0 for w in weights])
    return lp


def loss(params, tokens, loss_mask, cfg):
    """Masked mean next-token cross-entropy of one sequence, without the
    router's balancing term. tokens [s+1], loss_mask [s]."""
    nll = -token_logprobs(params, tokens, cfg)
    mask = loss_mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def batch_loss(params, tokens, loss_mask, cfg):
    """Mean over a stack of sequences ([n, s+1], [n, s]) of `loss`, one
    sequence at a time."""
    total = 0.0
    for i in range(tokens.shape[0]):
        total = total + loss(params, tokens[i], loss_mask[i], cfg)
    return total / tokens.shape[0]
