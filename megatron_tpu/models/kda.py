"""Kimi Delta Attention mixer: a `kimi_linear` model's "kda" layers
(`cfg.layer_types`; Kimi Linear, arXiv:2510.26692).

With H = kda_num_heads heads of D = kda_head_dim key and value channels (d =
H D), K = kda_conv_kernel, r = kda_gate_rank, on the layer's normed input x
[s, hidden]:

    [q~, k~, v~] = x W_in            W_in [h, 3 d], no bias
    [q^, k^, v^] = SiLU(conv(.))     three depthwise causal kernels of K taps
                                     a channel, held side by side [K, 3 d], no
                                     bias; the K - 1 inputs before the rows
                                     are the carried state
    q = L2norm_head(q^) / sqrt(D),  k = L2norm_head(k^),  v = v^
    [f, z, b] = x W_low              W_low [h, r + r + H]
    g = -exp(A_log[h]) softplus(f W_fb + dt_bias)   [s, H, D] <= 0 float32, a
                                     log-decay a CHANNEL
    beta = sigmoid(b)                [s, H]
    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                  (ops/kda_chunk.py: S [D, D] float32 a head)
    y = RMSNorm_head(o; w[D]) * sigmoid(z W_gb + b_g)
    out = y W_out                    W_out [d, h]

No positions. What a sequence carries from one call to the next is the
depthwise kernels' last K - 1 inputs (`LatentStateCache.conv`, in the
cache's dtype, the older first, over all 3 d channels) and the rule's state
(`LatentStateCache.ssm`, [H, D, D] float32 a layer). Both are left as they
stood after the call's last REAL row (`live_rows`): the depthwise state by
where it is cut (`short_conv.state_after`), the rule's by beta = 0 and g = 0
on the padding rows, which make the rule's step the identity: no masked copy
of the state is made. A prefill or a chunk runs the chunked kernel where its
shape rule holds, a decode step the one-row update over the pool's layer,
and a call with no cache (training, scoring) the recurrence that `jax.grad`
differentiates.

The initialiser is the public one's for the decays, so that drawn weights
have a memory that is neither none nor endless: A uniform in [1, 16] a
head, dt_bias such that softplus(dt_bias) is log-uniform in [0.001, 0.1]
(floored at 1e-4), the norm's scale 1, the taps N(0, 1 / K).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from megatron_tpu.config import ModelConfig
from megatron_tpu.models.attention import _layer_of, _project
from megatron_tpu.models.mamba2 import A_MAX, A_MIN, DT_FLOOR, DT_MAX, DT_MIN
from megatron_tpu.models.norms import rmsnorm
from megatron_tpu.models.short_conv import depthwise_causal, state_after
from megatron_tpu.ops.kda_chunk import kda_chunk, kda_recurrent, kda_step

L2_EPS = 1e-6


def kda_init(rng, cfg: ModelConfig, dtype=jnp.float32):
    h, heads, di = cfg.hidden_size, cfg.kda_num_heads, cfg.kda_d_inner
    rank, k = cfg.kda_gate_rank, cfg.kda_conv_kernel
    keys = jax.random.split(rng, 8)
    std = cfg.init_method_std
    out_std = (std / math.sqrt(2.0 * cfg.num_layers)
               if cfg.use_scaled_init else std)
    dt = jnp.maximum(jnp.exp(
        jax.random.uniform(keys[5], (di,), jnp.float32)
        * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN)), DT_FLOOR)

    def draw(key, shape, s=std):
        return jax.random.normal(key, shape, dtype) * s
    return {
        "in_proj": draw(keys[0], (h, 3 * di)),
        "conv": jax.random.normal(keys[1], (k, 3 * di), dtype) / math.sqrt(k),
        "low_proj": draw(keys[2], (h, 2 * rank + heads)),
        "f_b": draw(keys[3], (rank, di)),
        "g_b": draw(keys[4], (rank, di)),
        "g_bias": jnp.zeros((di,), dtype),
        # the inverse of softplus at dt
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "A_log": jnp.log(jax.random.uniform(
            keys[6], (heads,), jnp.float32, A_MIN, A_MAX)).astype(dtype),
        "norm": {"scale": jnp.ones((cfg.kda_head_dim,), dtype)},
        "out_proj": draw(keys[7], (di, h), out_std),
    }


def kda_axes(cfg: ModelConfig):
    # no head shard has been written (config.validate refuses a mesh)
    return {"in_proj": ("embed", None), "conv": (None, None),
            "low_proj": ("embed", None), "f_b": (None, None),
            "g_b": (None, None), "g_bias": (None,), "dt_bias": (None,),
            "A_log": (None,), "norm": {"scale": (None,)},
            "out_proj": (None, "embed")}


def _l2norm(x):
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(
        jnp.sum(jnp.square(xf), axis=-1, keepdims=True) + L2_EPS)


def kda_apply(params, x, cfg: ModelConfig, *, kv_cache=None, kind_layer=None):
    """x [b, s, h] -> (out [b, s, h], kv_cache). `kv_cache`: None, or the
    `LatentStateCache` stacked over layers with `kind_layer` this layer's
    index among the KDA layers."""
    b, s, _ = x.shape
    heads, hd, di = cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_d_inner
    rank, taps = cfg.kda_gate_rank, cfg.kda_conv_kernel
    dtype, f32 = x.dtype, jnp.float32
    cached = kv_cache is not None
    with jax.named_scope("mtpu/kda/proj"):
        qkv = _project(x, params["in_proj"], cfg, read_once=cached)
        low = _project(x, params["low_proj"], cfg, read_once=cached)
        f_a, z_a, b_l = jnp.split(low, [rank, 2 * rank], axis=-1)
    h0, live = None, None
    with jax.named_scope("mtpu/kda/conv"):
        if cached:
            qkv = qkv.astype(kv_cache.conv.dtype)
            prev = _layer_of(kv_cache.conv, kind_layer)
            h0 = _layer_of(kv_cache.ssm, kind_layer)
            if s > 1:
                live = jnp.broadcast_to(
                    jnp.clip(kv_cache.live_rows, 0, s), (b,))
        else:
            prev = jnp.zeros((b, taps - 1, 3 * di), dtype)
        full = jnp.concatenate([prev, qkv], axis=1).astype(dtype)
        q, k, v = jnp.split(
            jax.nn.silu(depthwise_causal(full, params["conv"])), 3, axis=-1)
        by_head = lambda t: t.reshape(b, s, heads, hd)       # noqa: E731
        q = (_l2norm(by_head(q)) / math.sqrt(hd)).astype(dtype)
        k = _l2norm(by_head(k)).astype(dtype)
        v = by_head(v).astype(dtype)
    with jax.named_scope("mtpu/kda/gate"):
        g = jax.nn.softplus(
            _project(f_a, params["f_b"], cfg, read_once=cached).astype(f32)
            + params["dt_bias"].astype(f32)).reshape(b, s, heads, hd)
        g = -jnp.exp(params["A_log"].astype(f32))[:, None] * g
        beta = jax.nn.sigmoid(b_l.astype(f32))               # [b, s, H]
        if live is not None:
            # a padding row's step is the identity: (I - 0) Diag(1) S
            real = (jnp.arange(s)[None, :] < live[:, None])[..., None]
            g = jnp.where(real[..., None], g, 0.0)
            beta = jnp.where(real, beta, 0.0)
    with jax.named_scope("mtpu/kda/scan"):
        if cached and s == 1:
            o, state = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                beta[:, 0], h0)
            o = o[:, None]
        elif cached:
            o, state = kda_chunk(q, k, v, g, beta, h0)
        else:
            # the kernel has no backward pass: a call with no cache may be
            # under `jax.grad`, and takes the rule as written
            o, state = kda_recurrent(q, k, v, g, beta)
        if cached:
            kv_cache = kv_cache._replace(
                conv=jax.lax.dynamic_update_index_in_dim(
                    kv_cache.conv,
                    state_after(full, live, taps - 1).astype(
                        kv_cache.conv.dtype), kind_layer, 0),
                ssm=jax.lax.dynamic_update_index_in_dim(
                    kv_cache.ssm, state, kind_layer, 0))
    with jax.named_scope("mtpu/kda/out"):
        y = rmsnorm(params["norm"], o, cfg.norm_epsilon)     # a head
        gate = jax.nn.sigmoid(
            _project(z_a, params["g_b"], cfg, read_once=cached).astype(f32)
            + params["g_bias"].astype(f32))
        y = (y.reshape(b, s, di).astype(f32) * gate).astype(dtype)
        out = _project(y, params["out_proj"], cfg, read_once=cached)
    return out, kv_cache
