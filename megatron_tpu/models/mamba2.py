"""Mamba-2 mixer: a `nemotron_h` model's "mamba2" layers (`cfg.layer_types`).

With H = mamba_num_heads heads of P = mamba_head_dim channels (d_inner = H
P), G = mamba_n_groups groups of B and C, N = mamba_d_state, K =
mamba_d_conv, on the layer's normed input u [s, hidden]:

    [z, xBC, dt] = u W_in            W_in [h, d_inner + (d_inner + 2 G N) + H]
    xBC = SiLU(conv(xBC) + b)        ONE depthwise causal kernel, K taps a
                                     channel over x, B and C together; the
                                     K - 1 inputs before the rows are the
                                     carried state
    [x, B, C] = xBC                  x [s, H, P]; B, C [s, G, N]: head h
                                     reads group h // (H / G)
    dt = softplus(dt + dt_bias)      [s, H] float32
    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g]
    y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]       A = -exp(A_log) [H], a scalar
                                               a head (ops/ssd_scan.py)
    y = RMSNorm_by_group(y * SiLU(z)) * w      the gate first, then the norm
                                               over each group's d_inner / G
                                               channels
    out = y W_out                    W_out [d_inner, h]

No keys, no values, no positions. What a sequence carries from one call to
the next is the depthwise kernel's last K - 1 inputs (`ConvKVCache.conv`, in
the cache's dtype, the older first, over all d_inner + 2 G N channels) and
the scan's state (`ConvKVCache.ssm`, [H, P, N] float32 a layer: a matrix a
head). Both are left as they stood after the call's last REAL row
(`live_rows`), as models/mamba.py leaves its own: the depthwise state by
where it is cut, the scan's by a step size of 0 on the padding rows. A
prefill or a chunk runs the chunked scan's kernel where its shape rule
holds, a decode step the one-step update over the pool's layer, and a call
with no cache (training, scoring) the `einsum` form that `jax.grad`
differentiates.

The initialiser is Mamba-2's published one, so that drawn weights have a
memory: A uniform in [1, 16] a head, D = 1, dt_bias such that
softplus(dt_bias) is log-uniform in [0.001, 0.1] (floored at 1e-4), the
norm's scale 1, the taps N(0, 1 / K) and their bias N(0, std^2).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from megatron_tpu.config import ModelConfig
from megatron_tpu.models.attention import ConvKVCache, _layer_of, _project
from megatron_tpu.models.short_conv import depthwise_causal, state_after
from megatron_tpu.ops.ssd_scan import ssd_scan, ssd_step

DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4
A_MIN, A_MAX = 1.0, 16.0


def mamba2_init(rng, cfg: ModelConfig, dtype=jnp.float32):
    h, di, heads = cfg.hidden_size, cfg.mamba_d_inner, cfg.mamba_num_heads
    channels, k = cfg.mamba2_conv_channels, cfg.mamba_d_conv
    keys = jax.random.split(rng, 6)
    std = cfg.init_method_std
    out_std = (std / math.sqrt(2.0 * cfg.num_layers)
               if cfg.use_scaled_init else std)
    dt = jnp.maximum(jnp.exp(
        jax.random.uniform(keys[3], (heads,), jnp.float32)
        * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN)), DT_FLOOR)
    params = {
        "in_proj": jax.random.normal(
            keys[0], (h, di + channels + heads), dtype) * std,
        "conv": jax.random.normal(keys[1], (k, channels), dtype)
        / math.sqrt(k),
        # the inverse of softplus at dt
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "A_log": jnp.log(jax.random.uniform(
            keys[4], (heads,), jnp.float32, A_MIN, A_MAX)).astype(dtype),
        "D": jnp.ones((heads,), dtype),
        "norm": {"scale": jnp.ones((di,), dtype)},
        "out_proj": jax.random.normal(keys[2], (di, h), dtype) * out_std,
    }
    if cfg.mamba_conv_bias:
        params["conv_bias"] = jax.random.normal(
            keys[5], (channels,), dtype) * std
    return params


def mamba2_axes(cfg: ModelConfig):
    # no head shard has been written (config.validate refuses a mesh)
    axes = {"in_proj": ("embed", None), "conv": (None, None),
            "dt_bias": (None,), "A_log": (None,), "D": (None,),
            "norm": {"scale": (None,)}, "out_proj": (None, "embed")}
    if cfg.mamba_conv_bias:
        axes["conv_bias"] = (None,)
    return axes


def gated_group_norm(scale, y, z, groups: int, eps: float):
    """RMSNorm of y * SiLU(z) over each of `groups` groups of the channels,
    float32 statistics, one learned scale a channel: [..., d_inner]."""
    dtype, f32 = y.dtype, jnp.float32
    g = y.astype(f32) * jax.nn.silu(z.astype(f32))
    by_group = g.reshape(*g.shape[:-1], groups, g.shape[-1] // groups)
    var = jnp.mean(jnp.square(by_group), axis=-1, keepdims=True)
    g = (by_group * jax.lax.rsqrt(var + eps)).reshape(g.shape)
    return g.astype(dtype) * scale.astype(dtype)


def mamba2_apply(params, u, cfg: ModelConfig, *, kv_cache=None,
                 kind_layer=None):
    """u [b, s, h] -> (out [b, s, h], kv_cache). `kv_cache`: None, or the
    `ConvKVCache` stacked over layers with `kind_layer` this layer's index
    among the Mamba-2 layers."""
    b, s, _ = u.shape
    di, heads, hd = cfg.mamba_d_inner, cfg.mamba_num_heads, cfg.mamba_head_dim
    groups, n = cfg.mamba_n_groups, cfg.mamba_d_state
    channels = cfg.mamba2_conv_channels
    dtype, f32 = u.dtype, jnp.float32
    cached = kv_cache is not None
    with jax.named_scope("mtpu/ssd/in_proj"):
        zxd = _project(u, params["in_proj"], cfg, read_once=cached)
        z, xbc, dt = jnp.split(zxd, [di, di + channels], axis=-1)
    h0 = None
    live = None
    with jax.named_scope("mtpu/ssd/state"):
        if cached:
            assert isinstance(kv_cache, ConvKVCache), type(kv_cache)
            xbc = xbc.astype(kv_cache.conv.dtype)
            prev = _layer_of(kv_cache.conv, kind_layer)
            h0 = _layer_of(kv_cache.ssm, kind_layer)
            if s > 1:
                live = jnp.broadcast_to(
                    jnp.clip(kv_cache.live_rows, 0, s), (b,))
        else:
            prev = jnp.zeros((b, cfg.mamba_d_conv - 1, channels), dtype)
        full = jnp.concatenate([prev, xbc], axis=1).astype(dtype)
    with jax.named_scope("mtpu/ssd/conv"):
        xbc = jax.nn.silu(depthwise_causal(
            full, params["conv"], params.get("conv_bias"))).astype(dtype)
        x, bmat, cmat = jnp.split(xbc, [di, di + groups * n], axis=-1)
        x = x.reshape(b, s, heads, hd)
        bmat = bmat.reshape(b, s, groups, n)
        cmat = cmat.reshape(b, s, groups, n)
        dt = jax.nn.softplus(dt.astype(f32) + params["dt_bias"].astype(f32))
        if live is not None:
            # a padding row moves no state
            dt = jnp.where((jnp.arange(s)[None, :] < live[:, None])[..., None],
                           dt, 0.0)
        a = -jnp.exp(params["A_log"].astype(f32))
    with jax.named_scope("mtpu/ssd/scan"):
        if cached and s == 1:
            y, h = ssd_step(x[:, 0], dt[:, 0], a, bmat[:, 0], cmat[:, 0],
                            params["D"], h0)
            y = y[:, None]
        else:
            # the kernel has no backward pass: a call with no cache may be
            # under `jax.grad`, and takes the `einsum` form
            y, h = ssd_scan(x, dt, a, bmat, cmat, params["D"], h0,
                            chunk=cfg.mamba_chunk_size,
                            use_kernel=None if cached else False)
    if cached:
        with jax.named_scope("mtpu/ssd/state"):
            kv_cache = kv_cache._replace(
                conv=jax.lax.dynamic_update_index_in_dim(
                    kv_cache.conv,
                    state_after(full, live, cfg.mamba_d_conv - 1).astype(
                        kv_cache.conv.dtype), kind_layer, 0),
                ssm=jax.lax.dynamic_update_index_in_dim(
                    kv_cache.ssm, h, kind_layer, 0))
    with jax.named_scope("mtpu/ssd/norm"):
        y = gated_group_norm(params["norm"]["scale"], y.reshape(b, s, di), z,
                             groups, cfg.norm_epsilon)
    with jax.named_scope("mtpu/ssd/out_proj"):
        out = _project(y, params["out_proj"], cfg, read_once=cached)
    return out, kv_cache
