"""Gated DeltaNet mixer: a `qwen3_next` model's "linear_attention" layers
(`cfg.layer_types`; Gated Delta Networks, arXiv:2412.06464), `models/kda.py`'s
sibling with ONE decay a head and key heads under value heads.

With H_k = gdn_key_heads key heads of D_k = gdn_key_head_dim channels, H =
gdn_value_heads value heads of D_v = gdn_value_head_dim channels (value head j
reads key head j // (H / H_k)), K = gdn_conv_kernel, on the layer's normed
input x [s, hidden]:

    [q~, k~, v~, z] = x W_in         W_in [h, 2 H_k D_k + 2 H D_v], no bias:
                                     q | k | v | z side by side (the public
                                     checkpoint groups them a key head: a
                                     permutation a converter owns)
    [b, a] = x W_ba                  W_ba [h, 2 H]
    [q^, k^, v^] = SiLU(conv(.))     ONE depthwise causal kernel of K taps
                                     over the 2 H_k D_k + H D_v channels of
                                     q~, k~ and v~, no bias; the K - 1 inputs
                                     before the rows are the carried state
    q = L2norm_head(q^) / sqrt(D_k),  k = L2norm_head(k^),  v = v^
    beta = sigmoid(b)                [s, H]
    g = -exp(A_log) softplus(a + dt_bias)    [s, H] <= 0 float32: ONE
                                     log-decay a HEAD a row
    S_t = (I - beta_t k_t k_t^T) exp(g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t                  (ops/kda_chunk.py, forms (a), (b), (d):
                                     S [D_k, D_v] float32 a value head)
    y = RMSNorm_head(o; w [D_v], the scale w ITSELF) * SiLU(z)
    out = y W_out                    W_out [H D_v, h]

No positions. What a sequence carries from one call to the next is the
depthwise kernel's last K - 1 inputs (`ConvKVCache.conv`, in the cache's
dtype, the older first) and the rule's state (`ConvKVCache.ssm`, [H, D_k,
D_v] float32 a layer), beside the attention layers' keys and values in the
same cache. Both are left as they stood after the call's last REAL row
(`live_rows`): the depthwise state by where it is cut
(`short_conv.state_after`), the rule's by beta = 0 and g = 0 on the padding
rows, which make the rule's step the identity. A prefill or a chunk runs the
scalar-decay chunk kernel where its shape rule holds, a decode step the
one-row update over the pool's layer, and a call with no cache (training,
scoring) the recurrence that `jax.grad` differentiates.

The initialiser is `models/kda.py`'s for the decays (A uniform in [1, 16] a
head, dt_bias such that softplus(dt_bias) is log-uniform in [0.001, 0.1]),
the norm's scale 1, the taps N(0, 1 / K).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from megatron_tpu.config import ModelConfig
from megatron_tpu.models.attention import _layer_of, _project
from megatron_tpu.models.kda import _l2norm
from megatron_tpu.models.mamba2 import A_MAX, A_MIN, DT_FLOOR, DT_MAX, DT_MIN
from megatron_tpu.models.norms import rmsnorm
from megatron_tpu.models.short_conv import depthwise_causal, state_after
from megatron_tpu.ops.kda_chunk import gdn_chunk, gdn_recurrent, gdn_step


def _widths(cfg: ModelConfig):
    """(key channels, value channels): H_k D_k and H D_v."""
    return (cfg.gdn_key_heads * cfg.gdn_key_head_dim,
            cfg.gdn_value_heads * cfg.gdn_value_head_dim)


def gdn_init(rng, cfg: ModelConfig, dtype=jnp.float32):
    h, heads, k = cfg.hidden_size, cfg.gdn_value_heads, cfg.gdn_conv_kernel
    dk, dv = _widths(cfg)
    keys = jax.random.split(rng, 6)
    std = cfg.init_method_std
    out_std = (std / math.sqrt(2.0 * cfg.num_layers)
               if cfg.use_scaled_init else std)
    dt = jnp.maximum(jnp.exp(
        jax.random.uniform(keys[3], (heads,), jnp.float32)
        * (math.log(DT_MAX) - math.log(DT_MIN)) + math.log(DT_MIN)), DT_FLOOR)
    return {
        "in_proj": jax.random.normal(keys[0], (h, 2 * dk + 2 * dv),
                                     dtype) * std,
        "ba_proj": jax.random.normal(keys[1], (h, 2 * heads), dtype) * std,
        "conv": jax.random.normal(keys[2], (k, cfg.gdn_conv_channels), dtype)
        / math.sqrt(k),
        # the inverse of softplus at dt
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "A_log": jnp.log(jax.random.uniform(
            keys[4], (heads,), jnp.float32, A_MIN, A_MAX)).astype(dtype),
        "norm": {"scale": jnp.ones((cfg.gdn_value_head_dim,), dtype)},
        "out_proj": jax.random.normal(keys[5], (dv, h), dtype) * out_std,
    }


def gdn_axes(cfg: ModelConfig):
    # no head shard has been written (config.validate refuses a mesh)
    return {"in_proj": ("embed", None), "ba_proj": ("embed", None),
            "conv": (None, None), "dt_bias": (None,), "A_log": (None,),
            "norm": {"scale": (None,)}, "out_proj": (None, "embed")}


def gdn_apply(params, x, cfg: ModelConfig, *, kv_cache=None, kind_layer=None):
    """x [b, s, h] -> (out [b, s, h], kv_cache). `kv_cache`: None, or the
    `ConvKVCache` stacked over layers with `kind_layer` this layer's index
    among the linear-attention layers."""
    b, s, _ = x.shape
    hk, hv = cfg.gdn_key_heads, cfg.gdn_value_heads
    d_k, d_v, taps = (cfg.gdn_key_head_dim, cfg.gdn_value_head_dim,
                      cfg.gdn_conv_kernel)
    dk, dv = _widths(cfg)
    dtype, f32 = x.dtype, jnp.float32
    cached = kv_cache is not None
    with jax.named_scope("mtpu/gdn/proj"):
        qkvz = _project(x, params["in_proj"], cfg, read_once=cached)
        qkv, z = qkvz[..., :2 * dk + dv], qkvz[..., 2 * dk + dv:]
        b_l, a_l = jnp.split(
            _project(x, params["ba_proj"], cfg, read_once=cached), 2, axis=-1)
    h0, live = None, None
    with jax.named_scope("mtpu/gdn/conv"):
        if cached:
            qkv = qkv.astype(kv_cache.conv.dtype)
            prev = _layer_of(kv_cache.conv, kind_layer)
            h0 = _layer_of(kv_cache.ssm, kind_layer)
            if s > 1:
                live = jnp.broadcast_to(
                    jnp.clip(kv_cache.live_rows, 0, s), (b,))
        else:
            prev = jnp.zeros((b, taps - 1, 2 * dk + dv), dtype)
        full = jnp.concatenate([prev, qkv], axis=1).astype(dtype)
        q, k, v = jnp.split(
            jax.nn.silu(depthwise_causal(full, params["conv"])),
            [dk, 2 * dk], axis=-1)
        q = (_l2norm(q.reshape(b, s, hk, d_k)) / math.sqrt(d_k)).astype(dtype)
        k = _l2norm(k.reshape(b, s, hk, d_k)).astype(dtype)
        v = v.reshape(b, s, hv, d_v).astype(dtype)
    with jax.named_scope("mtpu/gdn/gate"):
        g = -jnp.exp(params["A_log"].astype(f32)) * jax.nn.softplus(
            a_l.astype(f32) + params["dt_bias"].astype(f32))  # [b, s, H]
        beta = jax.nn.sigmoid(b_l.astype(f32))
        if live is not None:
            # a padding row's step is the identity: (I - 0) exp(0) S
            real = jnp.arange(s)[None, :, None] < live[:, None, None]
            g = jnp.where(real, g, 0.0)
            beta = jnp.where(real, beta, 0.0)
    with jax.named_scope("mtpu/gdn/scan"):
        if cached and s == 1:
            o, state = gdn_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                beta[:, 0], h0)
            o = o[:, None]
        elif cached:
            o, state = gdn_chunk(q, k, v, g, beta, h0)
        else:
            # the kernel has no backward pass: a call with no cache may be
            # under `jax.grad`, and takes the rule as written
            o, state = gdn_recurrent(q, k, v, g, beta)
        if cached:
            kv_cache = kv_cache._replace(
                conv=jax.lax.dynamic_update_index_in_dim(
                    kv_cache.conv,
                    state_after(full, live, taps - 1).astype(
                        kv_cache.conv.dtype), kind_layer, 0),
                ssm=jax.lax.dynamic_update_index_in_dim(
                    kv_cache.ssm, state, kind_layer, 0))
    with jax.named_scope("mtpu/gdn/out"):
        y = rmsnorm(params["norm"], o, cfg.norm_epsilon)     # a head, w itself
        y = (y.reshape(b, s, dv).astype(f32)
             * jax.nn.silu(z.astype(f32))).astype(dtype)
        out = _project(y, params["out_proj"], cfg, read_once=cached)
    return out, kv_cache
