"""Plain reference of the AI21-Jamba2-3B decoder (`jamba`): float32
`jax.numpy`, no kernel, no cache, no state carried between calls, no
batching. Written from the equations the published config.json names
(ai21labs/AI21-Jamba2-3B; ISSUE 47 writes them out), with d_inner =
mamba_expand x hidden, N = mamba_d_state, R = mamba_dt_rank, K =
mamba_d_conv:

    x = E[tokens];  n(x) = x / sqrt(mean(x^2) + 1e-6) * g
    layer l:  u = n_in(x)
      l % attn_layer_period != attn_layer_offset (a Mamba-1 mixer):
        [a, z] = u W_in                         W_in [h, 2 d_inner], no bias
        a_t = silu(b + sum_j w_j a_{t-K+1+j})   w [K, d_inner] depthwise,
              causal: the sequence left-padded with K - 1 zeros; bias b
        [dt, B, C] = a W_x                      W_x [d_inner, R + 2 N]
        dt, B, C <- RMSNorm of each, its own scale (eps 1e-6)
        dt = softplus(dt W_dt + b_dt)           W_dt [R, d_inner]
        A = -exp(A_log);  h_0 = 0
        h_t[c, n] = exp(dt_t[c] A[c, n]) h_{t-1}[c, n] + dt_t[c] B_t[n] a_t[c]
        y_t[c] = sum_n C_t[n] h_t[c, n] + D[c] a_t[c]
        m = (y * silu(z)) W_out                 W_out [d_inner, h], no bias
      else (attention): q = u Wq (20 heads of 128), k, v = u Wkv (ONE head
        of 128 each), no bias, NO positional term of any kind
        m = softmax(q k^T / sqrt(128) + causal) v Wo
      x = x + m;  v = n_ffn(x);  x = x + (silu(v W1) * (v W3)) W2
    logits = n_f(x) E^T                                            (tied)

The recurrence is a `lax.scan` over single tokens with the state [N,
d_inner] float32 its carry; attention is a `lax.map` over the heads ([s, s]
scores, never [heads, s, s]); the head is computed for the positions asked
for alone, so that the reference fits on the chip beside the engine's bf16
tree.

It reads the program's own parameter tree (`lm.model_init`), so these follow
the program's layout and not the Hugging Face file's, and are noted as
departures: `transformer["layers"]` is {"mamba", "full_attention"}, each
kind's layers stacked in the model's order; `A_log` is held [N, d_inner],
the published one's transpose (the state's channels are minor on the
device); `x_proj`'s columns are (dt, B, C) in that order; `wkv` [h, 2 x
128] holds k's columns then v's; the MLP's `w1` is [h, 2, f], gate then up.

Every matrix product runs under `jax.default_matmul_precision("highest")`.

`faults` (`benchmark/tests/ssm_fault_at_width.py` alone; empty everywhere
else) plants the nearest precision below the float32 the configuration's
recurrence is stated in: "state_bf16" rounds the carried state to bfloat16
behind every token, "recurrence_bf16" computes the step sizes' products, the
exponential and the update in bfloat16 too.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rmsnorm(p, x, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * p["scale"].astype(F32)


def mamba(p, u, cfg, faults=frozenset()):
    """`p`: one layer's `mamba` parameters as held; u [s, h] -> [s, h]."""
    s = u.shape[0]
    di, n, r, k = (cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank,
                   cfg.mamba_d_conv)
    eps = cfg.norm_epsilon
    az = u @ p["in_proj"].astype(F32)
    a, z = az[:, :di], az[:, di:]
    padded = jnp.pad(a, ((k - 1, 0), (0, 0)))
    w = p["conv"].astype(F32)
    a = sum(w[j] * padded[j:j + s] for j in range(k))
    if "conv_bias" in p:
        a = a + p["conv_bias"].astype(F32)
    a = jax.nn.silu(a)
    dbc = a @ p["x_proj"].astype(F32)
    dt = _rmsnorm(p["dt_norm"], dbc[:, :r], eps)
    b = _rmsnorm(p["b_norm"], dbc[:, r:r + n], eps)
    c = _rmsnorm(p["c_norm"], dbc[:, r + n:], eps)
    dt = jax.nn.softplus(dt @ p["dt_proj"].astype(F32)
                         + p["dt_bias"].astype(F32))
    a_neg = -jnp.exp(p["A_log"].astype(F32))                  # [n, di]

    low = jnp.bfloat16
    carried = low if faults & {"state_bf16", "recurrence_bf16"} else F32
    inside = low if "recurrence_bf16" in faults else F32

    def token(h, row):
        a_t, dt_t, b_t, c_t = (r.astype(inside) for r in row)
        h = jnp.exp(dt_t[None, :] * a_neg.astype(inside)) * h.astype(inside) \
            + (dt_t * a_t)[None, :] * b_t[:, None]
        return h.astype(carried), jnp.sum(c_t[:, None].astype(F32)
                                          * h.astype(F32), axis=0)
    _, y = jax.lax.scan(token, jnp.zeros((n, di), carried), (a, dt, b, c))
    y = y + p["D"].astype(F32) * a
    return (y * jax.nn.silu(z)) @ p["out_proj"].astype(F32)


def attention(p, u, cfg):
    """`p`: one layer's `attention` parameters as held; u [s, h] -> [s, h].
    A head at a time; one kv head serves them all; no positions."""
    s = u.shape[0]
    nq, hd = cfg.num_attention_heads, cfg.kv_channels
    q = (u @ p["wq"].astype(F32)).reshape(s, nq, hd)
    kv = u @ p["wkv"].astype(F32)
    k, v = kv[:, :hd], kv[:, hd:]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def head(q_h):
        scores = q_h @ k.T / math.sqrt(hd)
        return jax.nn.softmax(jnp.where(causal, scores, -jnp.inf),
                              axis=-1) @ v
    out = jax.lax.map(head, q.swapaxes(0, 1))                 # [nq, s, hd]
    return out.swapaxes(0, 1).reshape(s, nq * hd) @ p["wo"].astype(F32)


def block(stack, x, cfg, kind: str, at: int, faults=frozenset()):
    """Layer `at` of the stacked parameters of one kind."""
    eps = cfg.norm_epsilon
    p = jax.tree.map(lambda a: a[at], stack)
    u = _rmsnorm(p["input_norm"], x, eps)
    x = x + (mamba(p["mamba"], u, cfg, faults) if kind == "mamba"
             else attention(p["attention"], u, cfg))
    v = _rmsnorm(p["post_attn_norm"], x, eps)
    w1 = p["mlp"]["w1"].astype(F32)
    return x + (jax.nn.silu(v @ w1[:, 0]) * (v @ w1[:, 1])) \
        @ p["mlp"]["w2"].astype(F32)


def _trunk(params, tokens, cfg, faults=frozenset()):
    """The last layer's output [s, h] before the final norm."""
    assert (cfg.layer_types is not None and cfg.layers_of("mamba")
            and set(cfg.layer_types) <= {"mamba", "full_attention"}
            and cfg.num_kv_heads == 1 and not cfg.use_rotary_emb
            and not cfg.use_position_embedding and cfg.num_experts == 1
            and cfg.activation == "swiglu" and cfg.norm_type == "rmsnorm"
            and not cfg.use_bias and not cfg.mamba_proj_bias
            and not cfg.first_k_dense_replace and cfg.tie_embed_logits), \
        "this reference is the Jamba2 block only"
    x = params["embedding"]["word_embeddings"][tokens].astype(F32)
    types = cfg.layer_types
    for l, kind in enumerate(types):
        x = block(params["transformer"]["layers"][kind], x, cfg, kind,
                  types[:l].count(kind), faults)
    return x


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
        return _head(params, _trunk(params, tokens, cfg), cfg)


def token_logprobs(params, tokens, cfg, tail: int | None = None,
                   faults=frozenset()):
    """log p(tokens[i+1] | tokens[:i+1]) for every i: [s-1] float32, or with
    `tail` for the last `tail` of them alone."""
    tail = tokens.shape[0] - 1 if tail is None else tail
    with jax.default_matmul_precision("highest"):
        x = _trunk(params, tokens[:-1], cfg, faults)
        out = _head(params, x[-tail:], cfg)
    return jnp.take_along_axis(jax.nn.log_softmax(out, axis=-1),
                               tokens[-tail:, None], axis=-1)[:, 0]
