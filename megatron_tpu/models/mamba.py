"""Mamba-1 mixer: Jamba's "mamba" layers (`cfg.layer_types`).

With d_inner = mamba_expand x hidden, N = mamba_d_state, R = mamba_dt_rank,
K = mamba_d_conv, on the layer's normed input u [s, hidden]:

    [x, z] = u W_in                      W_in [h, 2 d_inner]
    x = SiLU(conv(x))                    depthwise causal, K taps a channel
                                         and a bias; the K - 1 inputs before
                                         the rows are the carried state
    [dt, B, C] = x W_x                   W_x [d_inner, R + 2 N]; each of the
                                         three RMS-normalised, its own scale
    dt = softplus(dt W_dt + b_dt)        W_dt [R, d_inner], float32
    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t        A = -exp(A_log)
    y_t = C_t . h_t + D x_t                         (ops/selective_scan.py)
    out = (y * SiLU(z)) W_out            W_out [d_inner, h]

No keys, no values, no positions. What a sequence carries from one call to
the next is the depthwise kernel's last K - 1 inputs (`ConvKVCache.conv`,
in the cache's dtype, the older first) and the scan's state
(`ConvKVCache.ssm`, [d_state, d_inner] float32 a layer: the channels minor,
`A_log` is held [d_state, d_inner] for the same reason; a published
checkpoint's is its transpose). Both are left as they stood after the call's
last REAL row (`live_rows`): the depthwise state by where it is cut
(models/short_conv.py::state_after), the scan's by a step size of 0 on the
padding rows, which moves no state. A prefill or a chunk runs the scan's
kernel where its shape rule holds, a decode step (one row a sequence) the
one-step update over the pool's layer, and a call with no cache (training,
scoring) the `lax.scan` that `jax.grad` differentiates.

The initialiser is Mamba's published one, so that drawn weights have a
memory: A_log = log(1..N) a channel, D = 1, b_dt such that softplus(b_dt) is
log-uniform in [0.001, 0.1], W_dt uniform in +- R^-1/2, the three norms'
scales 1, the taps N(0, 1 / K) and their bias N(0, std^2).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from megatron_tpu.config import ModelConfig
from megatron_tpu.models.attention import ConvKVCache, _layer_of, _project
from megatron_tpu.models.norms import rmsnorm, rmsnorm_init
from megatron_tpu.models.short_conv import depthwise_causal, state_after
from megatron_tpu.ops.selective_scan import (selective_scan,
                                             selective_scan_step)

DT_MIN, DT_MAX = 1e-3, 1e-1


def mamba_init(rng, cfg: ModelConfig, dtype=jnp.float32):
    h, di = cfg.hidden_size, cfg.mamba_d_inner
    n, r, k = cfg.mamba_d_state, cfg.mamba_dt_rank, cfg.mamba_d_conv
    keys = jax.random.split(rng, 7)
    std = cfg.init_method_std
    out_std = (std / math.sqrt(2.0 * cfg.num_layers)
               if cfg.use_scaled_init else std)
    dt = jnp.exp(jax.random.uniform(keys[5], (di,), jnp.float32)
                 * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN))
    params = {
        "in_proj": jax.random.normal(keys[0], (h, 2 * di), dtype) * std,
        "conv": jax.random.normal(keys[1], (k, di), dtype) / math.sqrt(k),
        "x_proj": jax.random.normal(keys[2], (di, r + 2 * n), dtype) * std,
        "dt_norm": rmsnorm_init(r, dtype),
        "b_norm": rmsnorm_init(n, dtype),
        "c_norm": rmsnorm_init(n, dtype),
        "dt_proj": jax.random.uniform(keys[3], (r, di), dtype,
                                      -r ** -0.5, r ** -0.5),
        # the inverse of softplus at dt
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "A_log": jnp.broadcast_to(jnp.log(jnp.arange(
            1, n + 1, dtype=jnp.float32))[:, None], (n, di)).astype(dtype),
        "D": jnp.ones((di,), dtype),
        "out_proj": jax.random.normal(keys[4], (di, h), dtype) * out_std,
    }
    if cfg.mamba_conv_bias:
        params["conv_bias"] = jax.random.normal(keys[6], (di,), dtype) * std
    if cfg.mamba_proj_bias:
        params["in_bias"] = jnp.zeros((2 * di,), dtype)
        params["out_bias"] = jnp.zeros((h,), dtype)
    return params


def mamba_axes(cfg: ModelConfig):
    # no channel shard has been written (config.validate refuses a mesh)
    axes = {"in_proj": ("embed", None), "conv": (None, None),
            "x_proj": (None, None), "dt_norm": {"scale": (None,)},
            "b_norm": {"scale": (None,)}, "c_norm": {"scale": (None,)},
            "dt_proj": (None, None), "dt_bias": (None,),
            "A_log": (None, None), "D": (None,),
            "out_proj": (None, "embed")}
    if cfg.mamba_conv_bias:
        axes["conv_bias"] = (None,)
    if cfg.mamba_proj_bias:
        axes.update(in_bias=(None,), out_bias=("embed",))
    return axes


def mamba_apply(params, u, cfg: ModelConfig, *, kv_cache=None,
                kind_layer=None):
    """u [b, s, h] -> (out [b, s, h], kv_cache). `kv_cache`: None, or the
    `ConvKVCache` stacked over layers with `kind_layer` this layer's index
    among the Mamba layers."""
    b, s, _ = u.shape
    di, n, r = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    dtype, f32 = u.dtype, jnp.float32
    eps = cfg.norm_epsilon
    cached = kv_cache is not None
    with jax.named_scope("mtpu/ssm/in_proj"):
        xz = _project(u, params["in_proj"], cfg, read_once=cached)
        if cfg.mamba_proj_bias:
            xz = xz + params["in_bias"].astype(dtype)
        x, z = jnp.split(xz, 2, axis=-1)
    h0 = None
    live = None
    with jax.named_scope("mtpu/ssm/state"):
        if cached:
            assert isinstance(kv_cache, ConvKVCache), type(kv_cache)
            x = x.astype(kv_cache.conv.dtype)
            prev = _layer_of(kv_cache.conv, kind_layer)
            h0 = _layer_of(kv_cache.ssm, kind_layer)
            if s > 1:
                live = jnp.broadcast_to(
                    jnp.clip(kv_cache.live_rows, 0, s), (b,))
        else:
            prev = jnp.zeros((b, cfg.mamba_d_conv - 1, di), dtype)
        full = jnp.concatenate([prev, x], axis=1).astype(dtype)
    with jax.named_scope("mtpu/ssm/conv"):
        x = jax.nn.silu(depthwise_causal(
            full, params["conv"], params.get("conv_bias"))).astype(dtype)
    with jax.named_scope("mtpu/ssm/params"):
        dbc = _project(x, params["x_proj"], cfg, read_once=cached)
        dt, bmat, cmat = jnp.split(dbc, [r, r + n], axis=-1)
        dt = rmsnorm(params["dt_norm"], dt, eps)
        bmat = rmsnorm(params["b_norm"], bmat, eps).astype(f32)
        cmat = rmsnorm(params["c_norm"], cmat, eps).astype(f32)
        dt = jax.nn.softplus(
            jnp.dot(dt, params["dt_proj"].astype(dtype),
                    preferred_element_type=f32)
            + params["dt_bias"].astype(f32))
        if live is not None:
            # a padding row moves no state
            dt = jnp.where((jnp.arange(s)[None, :] < live[:, None])[..., None],
                           dt, 0.0)
        a_t = -jnp.exp(params["A_log"].astype(f32))
    with jax.named_scope("mtpu/ssm/scan"):
        if cached and s == 1:
            y, h = selective_scan_step(x[:, 0], dt[:, 0], a_t, bmat[:, 0],
                                       cmat[:, 0], params["D"], z[:, 0], h0)
            y = y[:, None]
        else:
            # the kernel has no backward pass: a call with no cache may be
            # under `jax.grad`, and takes the `lax.scan`
            y, h = selective_scan(x, dt, a_t, bmat, cmat, params["D"], z, h0,
                                  use_kernel=None if cached else False)
    if cached:
        with jax.named_scope("mtpu/ssm/state"):
            kv_cache = kv_cache._replace(
                conv=jax.lax.dynamic_update_index_in_dim(
                    kv_cache.conv,
                    state_after(full, live, cfg.mamba_d_conv - 1).astype(
                        kv_cache.conv.dtype), kind_layer, 0),
                ssm=jax.lax.dynamic_update_index_in_dim(
                    kv_cache.ssm, h, kind_layer, 0))
    with jax.named_scope("mtpu/ssm/out_proj"):
        out = _project(y, params["out_proj"], cfg, read_once=cached)
        if cfg.mamba_proj_bias:
            out = out + params["out_bias"].astype(dtype)
    return out, kv_cache
