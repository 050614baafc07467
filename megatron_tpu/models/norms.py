"""RMSNorm / LayerNorm.

TPU-native replacement for the reference's fused CUDA mixed-precision
LayerNorm (ref: megatron/fused_kernels/layer_norm_cuda_kernel.cu, wrapped by
megatron/model/fused_layer_norm.py:64-122) and its plain-torch RMSNorm
(ref: fused_layer_norm.py:125-139). On TPU, XLA fuses the normalization
chain into neighboring ops, so the "fused kernel" is simply the jnp
expression; stats are computed in fp32 regardless of input dtype, matching
the reference's mixed-precision contract (fp16/bf16 in, fp32 stats).

A Pallas implementation lives in megatron_tpu/ops/fused_norms.py for cases
where we want explicit control; this module is the canonical reference
implementation.
"""
from __future__ import annotations

import jax.lax as lax
import jax.numpy as jnp


def rmsnorm_init(hidden_size: int, dtype=jnp.float32):
    return {"scale": jnp.ones((hidden_size,), dtype=dtype)}


def rmsnorm_axes():
    return {"scale": ("embed",)}


def rmsnorm(params, x, eps: float = 1e-5):
    """RMSNorm with fp32 statistics (ref: fused_layer_norm.py:132-139 computes
    in fp32 then casts back)."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    xf = xf * lax.rsqrt(var + eps)
    return xf.astype(dtype) * params["scale"].astype(dtype)


def rmsnorm_1p(params, x, eps: float = 1e-6):
    """RMSNorm whose learned scale is ZERO-CENTRED (Qwen3-Next's, Gemma's):
    x / rms(x) * (1 + w), w held around 0. The statistics AND the scale in
    float32, then the cast, as the public modelling code has it."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    xf = xf * lax.rsqrt(var + eps)
    return (xf * (1.0 + params["scale"].astype(jnp.float32))).astype(x.dtype)


def layernorm_init(hidden_size: int, dtype=jnp.float32):
    return {
        "scale": jnp.ones((hidden_size,), dtype=dtype),
        "bias": jnp.zeros((hidden_size,), dtype=dtype),
    }


def layernorm_axes():
    return {"scale": ("embed",), "bias": ("embed",)}


def layernorm(params, x, eps: float = 1e-5):
    """Affine LayerNorm, fp32 stats (ref: layer_norm_cuda.cpp forward_affine)."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    xf = (xf - mean) * lax.rsqrt(var + eps)
    return xf.astype(dtype) * params["scale"].astype(dtype) + params["bias"].astype(dtype)


def layernorm_nobias(params, x, eps: float = 1e-5):
    """LayerNorm with a scale and no bias (Cohere's): fp32 stats."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    xf = (xf - mean) * lax.rsqrt(var + eps)
    return xf.astype(dtype) * params["scale"].astype(dtype)


def norm_init(norm_type: str, hidden_size: int, dtype=jnp.float32):
    if norm_type in ("rmsnorm", "layernorm_nobias"):
        return rmsnorm_init(hidden_size, dtype)  # a scale alone
    elif norm_type == "rmsnorm_1p":
        return {"scale": jnp.zeros((hidden_size,), dtype=dtype)}
    elif norm_type == "layernorm":
        return layernorm_init(hidden_size, dtype)
    raise ValueError(norm_type)


def norm_axes(norm_type: str):
    return (layernorm_axes() if norm_type == "layernorm"
            else rmsnorm_axes())


def apply_norm(norm_type: str, params, x, eps: float = 1e-5):
    if norm_type == "rmsnorm":
        return rmsnorm(params, x, eps)
    elif norm_type == "layernorm":
        return layernorm(params, x, eps)
    elif norm_type == "layernorm_nobias":
        return layernorm_nobias(params, x, eps)
    elif norm_type == "rmsnorm_1p":
        return rmsnorm_1p(params, x, eps)
    raise ValueError(norm_type)
