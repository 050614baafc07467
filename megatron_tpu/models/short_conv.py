"""Gated short convolution: LFM2's "conv" mixer.

    (B, C, z) = split3(u W_in)                 W_in [h, 3h], no bias
    a_t = B_t * z_t
    c_t = sum_j w_j * a_{t - (L - 1) + j}      w [L, h] depthwise, causal,
                                               a_{<0} = 0, no bias; L =
                                               cfg.conv_L_cache (3)
    m_t = (C_t * c_t) W_out                    W_out [h, h]

No activation anywhere, no keys, no values. What a sequence carries from
one call to the next is the kernel's last L - 1 inputs, (a_{t-L+2} .. a_t):
`ConvKVCache.conv` (models/attention.py), [conv layers, batch, L - 1, h],
the older first.

Without a cache the convolution is the shifted sum over the sequence, which
XLA fuses (L - 1 shifts, L multiply-adds; there is no kernel here). With one,
every call is the same sum over [the state ; the call's own a]: a prefill
starts from the zero state a fresh cache holds, a chunk from the state the
chunk before it left, a decode step (one row a sequence) from the slot's own.
The state a call leaves is the one after its last REAL row (`live_rows`): a
bucket's padding rows are computed, as every row of a padded bucket is, and
reach no state. Each call writes its layer of the state whole, one update in
place; a row of the batch writes its own state and no other's.

Rows and weights in the compute dtype; the taps are accumulated in float32.
`depthwise_causal` and `state_after` are what this mixer shares with a Mamba
layer's depthwise kernel (models/mamba.py: four taps, a bias, SiLU).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from megatron_tpu.config import ModelConfig
from megatron_tpu.models.attention import ConvKVCache, _layer_of, _project


def short_conv_init(rng, cfg: ModelConfig, dtype=jnp.float32):
    """Params: in_proj [h, 3h] (B, C, z), conv [L, h], out_proj [h, h]. The
    taps are drawn at unit gain (variance 1 / L, a depthwise kernel's usual
    scale), not at `init_method_std`: at 0.02 the mixer would add a
    fiftieth of what it reads and no test of its state would see it."""
    h, L = cfg.hidden_size, cfg.conv_L_cache
    k1, k2, k3 = jax.random.split(rng, 3)
    std = cfg.init_method_std
    out_std = (std / math.sqrt(2.0 * cfg.num_layers)
               if cfg.use_scaled_init else std)
    return {
        "in_proj": jax.random.normal(k1, (h, 3 * h), dtype) * std,
        "conv": jax.random.normal(k2, (L, h), dtype) / math.sqrt(L),
        "out_proj": jax.random.normal(k3, (h, h), dtype) * out_std,
    }


def short_conv_axes(cfg: ModelConfig):
    # no channel shard has been written (config.validate refuses a mesh)
    return {"in_proj": ("embed", None), "conv": (None, None),
            "out_proj": (None, "embed")}


def depthwise_causal(full, w, bias=None):
    """The depthwise causal kernel over `full` = [the state ; the call's
    rows], [b, taps - 1 + s, channels] (the state: the taps - 1 inputs before
    the rows, the older first), w [taps, channels] -> c [b, s, channels]
    float32: c_t = bias + sum_j w_j full_{t + j}, accumulated in float32.
    Shared, with `state_after`, by this file's mixer and models/mamba.py."""
    taps = w.shape[0]
    s = full.shape[1] - (taps - 1)
    w = w.astype(jnp.float32)
    c = sum(w[j] * full[:, j:j + s].astype(jnp.float32)
            for j in range(taps))
    return c if bias is None else c + bias.astype(jnp.float32)


def state_after(full, live, keep):
    """The `keep` inputs up to the last real row: rows n .. n + keep - 1 of
    `full` = [state ; rows], n [b] the call's count of real rows (`live`;
    None: a call of one row has one real row, a static cut)."""
    if live is None:
        return full[:, -keep:]
    return jax.vmap(lambda f, i: jax.lax.dynamic_slice_in_dim(
        f, i, keep, axis=0))(full, live)


def short_conv_apply(params, x, cfg: ModelConfig, *, kv_cache=None,
                     kind_layer=None):
    """x [b, s, h] -> (out [b, s, h], kv_cache). `kv_cache`: None, or the
    `ConvKVCache` stacked over layers with `kind_layer` this layer's index
    among the convolution layers."""
    b, s, h = x.shape
    L = cfg.conv_L_cache
    dtype = x.dtype
    read_once = kv_cache is not None
    with jax.named_scope("mtpu/conv/in_proj"):
        bcz = _project(x, params["in_proj"], cfg, read_once=read_once)
    gate_b, gate_c, z = jnp.split(bcz, 3, axis=-1)
    a = gate_b * z
    with jax.named_scope("mtpu/conv/state"):
        if kv_cache is None:
            prev = jnp.zeros((b, L - 1, h), dtype)
        else:
            assert isinstance(kv_cache, ConvKVCache), type(kv_cache)
            # what the state will hold of these rows: the step mixes the
            # values a later step will read back
            a = a.astype(kv_cache.conv.dtype)
            prev = _layer_of(kv_cache.conv, kind_layer)
        full = jnp.concatenate([prev, a], axis=1).astype(dtype)
    with jax.named_scope("mtpu/conv/mix"):
        c = depthwise_causal(full, params["conv"])
        y = (gate_c.astype(jnp.float32) * c).astype(dtype)
    if kv_cache is not None:
        with jax.named_scope("mtpu/conv/state"):
            # the L - 1 inputs up to the last real row
            live = None if s == 1 else jnp.broadcast_to(
                jnp.clip(kv_cache.live_rows, 0, s), (b,))
            kv_cache = kv_cache._replace(
                conv=jax.lax.dynamic_update_index_in_dim(
                    kv_cache.conv,
                    state_after(full, live, L - 1).astype(
                        kv_cache.conv.dtype), kind_layer, 0))
    with jax.named_scope("mtpu/conv/out_proj"):
        out = _project(y, params["out_proj"], cfg, read_once=read_once)
    return out, kv_cache
