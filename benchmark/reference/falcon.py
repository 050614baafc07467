"""Plain reference of the Falcon decoder: float32 `jax.numpy`, no kernel, no
cache, no batching tricks, no scan. It follows the published block
(tiiuae/falcon-7b and falcon-40b `modelling_falcon.py`):

    h   = embedding[tokens]
    per block:
        a = LayerNorm_attn(h)                 (7B: the one input LayerNorm)
        m = LayerNorm_mlp(h)                  (40B: a second one; 7B: m = a)
        q, k, v = a @ Wq, a @ Wkv             (71 heads over 1 kv head, or
                                               128 over 8; head size 64)
        q, k = rotary(q), rotary(k)
        attn = softmax(q k^T / sqrt(64), causal) v  @ Wo
        h   = h + attn + gelu(m @ W1) @ W2    (parallel attention, no bias)
    logits = LayerNorm_f(h) @ embedding^T     (tied head)

It reads the program's own parameter tree (`lm.model_init`), so two things
follow the program's layout and not the Hugging Face file's, and are noted
as departures: rotary pairs are the interleaved channels (2i, 2i+1) of a
head, where the HF weights pair channel i with i + 32 (the same function
under a fixed permutation of each head's q/k columns, which a checkpoint
converter applies); and query head j reads kv head j // (heads / kv heads).

Every matrix product runs under `jax.default_matmul_precision("highest")`:
on a TPU a float32 product is otherwise computed in bf16 passes.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _layernorm(p, x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) / jnp.sqrt(var + eps) * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32))


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


def _block(p, h, cfg):
    s = h.shape[0]
    nq, nkv, d = cfg.num_attention_heads, cfg.num_kv_heads, cfg.kv_channels
    a = _layernorm(p["input_norm"], h, cfg.norm_epsilon)
    m = (_layernorm(p["mlp_norm"], h, cfg.norm_epsilon)
         if cfg.parallel_layernorm else a)
    q = (a @ p["attention"]["wq"]).reshape(s, nq, d)
    kv = (a @ p["attention"]["wkv"]).reshape(s, 2, nkv, d)
    q = _rotary(q, cfg.rope_theta)
    k = _rotary(kv[:, 0], cfg.rope_theta)
    v = kv[:, 1]
    g = nq // nkv
    k = jnp.repeat(k, g, axis=1)          # query head j reads kv head j // g
    v = jnp.repeat(v, g, axis=1)
    scores = jnp.einsum("snd,tnd->nst", q, k) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("nst,tnd->snd", probs, v).reshape(s, nq * d)
    attn = attn @ p["attention"]["wo"]
    mlp = jax.nn.gelu(m @ p["mlp"]["w1"], approximate=False) @ p["mlp"]["w2"]
    return h + attn + mlp


def logits(params, tokens, cfg):
    """tokens [s] int -> logits [s, vocab] float32 (padded rows of the
    embedding, if any, are cut off)."""
    assert (cfg.parallel_attn and cfg.tie_embed_logits and cfg.use_rotary_emb
            and not cfg.use_bias and cfg.norm_type == "layernorm"
            and cfg.activation == "gelu" and cfg.num_experts == 1), \
        "this reference is the Falcon block only"
    with jax.default_matmul_precision("highest"):
        f32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
        emb = f32["embedding"]["word_embeddings"]
        h = emb[tokens]
        n_layers = jax.tree.leaves(f32["transformer"])[0].shape[0]
        for i in range(n_layers):
            layer = jax.tree.map(lambda x: x[i], f32["transformer"])
            h = _block(layer, h, cfg)
        h = _layernorm(f32["final_norm"], h, cfg.norm_epsilon)
        return (h @ emb.T)[:, :cfg.vocab_size]


def token_logprobs(params, tokens, cfg):
    """log p(tokens[i+1] | tokens[:i+1]) for every i: [s-1] float32."""
    lp = jax.nn.log_softmax(logits(params, tokens[:-1], cfg), axis=-1)
    return jnp.take_along_axis(lp, tokens[1:, None], axis=-1)[:, 0]


def loss(params, tokens, loss_mask, cfg):
    """Masked mean next-token cross-entropy of one sequence.
    tokens [s+1], loss_mask [s]."""
    nll = -token_logprobs(params, tokens, cfg)
    mask = loss_mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def batch_loss(params, tokens, loss_mask, cfg):
    """Mean over a stack of sequences ([n, s+1], [n, s]) of `loss`, one
    sequence at a time: what a training step's reported loss is for its
    micro-batches of one sequence each."""
    total = 0.0
    for i in range(tokens.shape[0]):
        total = total + loss(params, tokens[i], loss_mask[i], cfg)
    return total / tokens.shape[0]
