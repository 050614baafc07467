"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, on
hyper-connections, arXiv:2409.19606): the residual path of a model with
`cfg.hc_mult` = n > 1 (Xing4.0's `hc_mult`, `hc_sinkhorn_iters`, `hc_eps`,
`mhc_h_res_clamp_min/max`).

A token's residual is n streams of C = hidden_size values, X [n, C]. Round a
sublayer F (attention or feed-forward WITH its own pre-norm), with the
sublayer's own phi [nC, n^2 + 2n], alpha [3], b [n^2 + 2n]:

    x^     = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)            (no weight)
    H~_pre, H~_post, mat(H~_res) = alpha_k (x^ phi_k) + b_k    (k = pre, post, res)
    H_pre  = sigmoid(H~_pre)   [n]      H_post = 2 sigmoid(H~_post)   [n]
    M      = exp(clip(H~_res, -hc_res_clamp, +hc_res_clamp))   [n, n]
    hc_sinkhorn_iters times: every column of M divided by (its sum + hc_eps),
                             then every row by (its sum + hc_eps)
    H_res  = M                                  (doubly stochastic, nearly)
    X'     = H_res X + H_post^T F(H_pre X)

`expand` makes the model's input (the embedding, n times) and `collapse` its
output (the streams summed) ahead of the final norm.

**How the program holds it.** The streams lie side by side in the last
axis, `[b, s, n * C]`, stream i the columns i C to (i + 1) C: vec(X) is the
array itself, so the maps' product reads it where it lies, and a stream is a
slice on a lane boundary (C a multiple of 128 at the published widths); an
axis of n = 4 in front of C would be padded to a tile's 8 or 16 rows on the
chip. The maps live with the TOKENS minor, `[n, b, s]` and `[n, n, b, s]`: a
Sinkhorn round is then 2 n^2 divisions and 2 n (n - 1) additions of whole
[b, s] planes, never a reduction inside a vector.

**Precision.** The streams are held in the compute dtype. The maps'
product takes them and phi in the compute dtype and ACCUMULATES IN FLOAT32
(the products of two bf16 values are exact in float32), and is divided by
the root mean square afterwards, in float32: x^ phi = (vec(X) phi) / rms,
the same number without a float32 copy of the streams. The sigmoids, the
exponential, the Sinkhorn rounds and the two mixes' sums are float32; the
mixes' results are rounded to the compute dtype once.
"""
from __future__ import annotations

import math
from functools import reduce
from operator import add

import jax
import jax.numpy as jnp

from megatron_tpu.config import ModelConfig


def hc_init(rng, cfg: ModelConfig, dtype=jnp.float32):
    """One sublayer's maps. Drawn so that a fresh model starts as the
    one-stream residual it widens: H_pre 1/n on every stream (the input of F
    is the streams' mean), H_post 1, H_res the identity within e^-8, and
    alpha small, so that phi (drawn at the initialiser's std) bends them a
    little and has a gradient."""
    n, h = cfg.hc_mult, cfg.hidden_size
    b_res = jnp.where(jnp.eye(n, dtype=bool), 0.0, -8.0).reshape(n * n)
    b = jnp.concatenate([
        jnp.full((n,), math.log(1.0 / (n - 1.0))),      # sigmoid -> 1 / n
        jnp.zeros((n,)), b_res])
    return {
        "phi": jax.random.normal(rng, (n * h, n * n + 2 * n), dtype)
        * cfg.init_method_std,
        "alpha": jnp.full((3,), 0.01, dtype),
        "b": b.astype(dtype),
    }


def hc_axes(cfg: ModelConfig):
    """Whole on its device (`config.validate` refuses a mesh)."""
    return {"phi": (None, None), "alpha": (None,), "b": (None,)}


def sinkhorn(m, iters: int, eps: float):
    """m [n, n, ...] positive float32, rows the first axis: `iters` rounds of
    columns then rows."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
    return m


def hc_maps(params, x, cfg: ModelConfig):
    """x [b, s, n C] -> (H_pre [n, b, s], H_post [n, b, s], H_res [n, n, b,
    s]), float32."""
    n = cfg.hc_mult
    with jax.named_scope("mtpu/hc/map"):
        # [b, s, m] turned tokens-minor behind the product (XLA:CPU has no
        # bf16 x bf16 -> f32 product that writes [m, b, s] itself)
        raw = jnp.moveaxis(
            jnp.dot(x, params["phi"].astype(x.dtype),
                    preferred_element_type=jnp.float32), -1, 0)
        xf = x.astype(jnp.float32)
        inv_rms = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1) + cfg.hc_eps)
        alpha = params["alpha"].astype(jnp.float32)
        b = params["b"].astype(jnp.float32)[:, None, None]
        raw = raw * inv_rms[None]
        pre = jax.nn.sigmoid(alpha[0] * raw[:n] + b[:n])
        post = 2.0 * jax.nn.sigmoid(alpha[1] * raw[n:2 * n] + b[n:2 * n])
        res = (alpha[2] * raw[2 * n:] + b[2 * n:]).reshape(
            n, n, *raw.shape[1:])
        res = jnp.exp(jnp.clip(res, -cfg.hc_res_clamp, cfg.hc_res_clamp))
        return pre, post, sinkhorn(res, cfg.hc_sinkhorn_iters, cfg.hc_eps)


def _streams(x, n: int):
    c = x.shape[-1] // n
    return [x[..., i * c:(i + 1) * c] for i in range(n)]


def hc_pre(h_pre, x, cfg: ModelConfig):
    """H_pre X: [b, s, n C] -> the sublayer's input [b, s, C]."""
    with jax.named_scope("mtpu/hc/pre"):
        out = reduce(add, (h_pre[i][..., None] * xi.astype(jnp.float32)
                           for i, xi in enumerate(_streams(x, cfg.hc_mult))))
        return out.astype(x.dtype)


def hc_post(h_post, h_res, x, out, cfg: ModelConfig):
    """H_res X + H_post^T out: the streams [b, s, n C] after the sublayer,
    whose output is `out` [b, s, C]."""
    n = cfg.hc_mult
    with jax.named_scope("mtpu/hc/post"):
        xs = [xi.astype(jnp.float32) for xi in _streams(x, n)]
        of = out.astype(jnp.float32)
        new = [reduce(add, (h_res[i, j][..., None] * xs[j] for j in range(n)),
                      h_post[i][..., None] * of)
               for i in range(n)]
        return jnp.concatenate(new, axis=-1).astype(x.dtype)


def expand(x, cfg: ModelConfig):
    """The model's input: the embedding [b, s, C] in every stream."""
    with jax.named_scope("mtpu/hc/expand"):
        return jnp.tile(x, (1, 1, cfg.hc_mult))


def collapse(x, cfg: ModelConfig):
    """The model's output ahead of the final norm: the streams summed."""
    with jax.named_scope("mtpu/hc/collapse"):
        return reduce(add, (xi.astype(jnp.float32) for xi in _streams(
            x, cfg.hc_mult))).astype(x.dtype)


def hc_sublayer(params, x, cfg: ModelConfig, sublayer):
    """X' = H_res X + H_post^T F(H_pre X). `sublayer`: its input [b, s, C]
    -> (its output [b, s, C], whatever else it returns)."""
    h_pre, h_post, h_res = hc_maps(params, x, cfg)
    out, rest = sublayer(hc_pre(h_pre, x, cfg))
    return hc_post(h_post, h_res, x, out, cfg), rest
