"""Feed-forward / GLU-family MLP.

TPU-native equivalent of the reference's ParallelMLP
(ref: megatron/model/transformer.py:77-141) and its GLU activation family
liglu/geglu/reglu/swiglu (ref: megatron/model/glu_activations.py:13-55).
The reference's column-parallel h→4h (doubled for GLU) + row-parallel 4h→h
pair becomes two matmuls whose parameters carry 'mlp'-axis sharding; XLA
inserts the row-parallel all-reduce. The jit-fused bias-gelu kernel
(ref: megatron/model/fused_bias_gelu.py, warmed up at initialize.py:208-275)
is unnecessary — XLA fuses bias+activation into the GEMM epilogue.

Sharding note for GLU: the reference doubles one column-parallel projection
so every TP rank holds matching gate/value slices (ref: transformer.py:86-95).
We get the same alignment by shaping w1 as [h, 2, ffn] with the 'mlp' axis on
the ffn dim — the gate/value split is then a leading-index, never crossing a
shard boundary.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from megatron_tpu.config import ModelConfig
from megatron_tpu.ops.quantized import qdense, wcast


def activation_fn(name: str, a, b=None):
    """Activation dispatch (ref: transformer.py:99-124, glu_activations.py:13-55).

    GLU variants take the (gate, value) pair: act(a) * b."""
    if name == "gelu":
        return jax.nn.gelu(a, approximate=False)
    if name == "relu":
        return jax.nn.relu(a)
    if name == "squared_relu":
        r = jax.nn.relu(a)
        return r * r
    if name == "swiglu":
        return jax.nn.silu(a) * b
    if name == "geglu":
        return jax.nn.gelu(a, approximate=False) * b
    if name == "reglu":
        return jax.nn.relu(a) * b
    if name == "liglu":
        return a * b
    raise ValueError(f"unknown activation {name}")


def mlp_init(rng, cfg: ModelConfig, dtype=jnp.float32):
    h = cfg.hidden_size
    ffn = cfg.ffn_hidden_size
    k1, k2 = jax.random.split(rng)
    std = cfg.init_method_std
    out_std = std / math.sqrt(2.0 * cfg.num_layers) if cfg.use_scaled_init else std
    if cfg.is_glu:
        w1 = jax.random.normal(k1, (h, 2, ffn), dtype) * std
        b1_shape = (2, ffn)
    else:
        w1 = jax.random.normal(k1, (h, ffn), dtype) * std
        b1_shape = (ffn,)
    params = {
        "w1": w1,
        "w2": jax.random.normal(k2, (ffn, h), dtype) * out_std,
    }
    if cfg.use_bias:
        params["b1"] = jnp.zeros(b1_shape, dtype)
        params["b2"] = jnp.zeros((h,), dtype)
    return params


def mlp_axes(cfg: ModelConfig):
    if cfg.is_glu:
        axes = {"w1": ("embed", None, "mlp"), "w2": ("mlp", "embed")}
        b1_axes = (None, "mlp")
    else:
        axes = {"w1": ("embed", "mlp"), "w2": ("mlp", "embed")}
        b1_axes = ("mlp",)
    if cfg.use_bias:
        axes.update({"b1": b1_axes, "b2": ("embed",)})
    return axes


def mlp_apply(params, x, cfg: ModelConfig, *, read_once: bool = False):
    """x: [b, s, h] -> [b, s, h]. `read_once`: the program multiplies by
    each weight once a call (it carries a KV cache), which chooses the
    form of the weights' cast (`ops/quantized.py::wcast`)."""
    dtype = x.dtype
    # GLU: single h -> 2*ffn GEMM, gate/value as leading index of the output
    y = qdense(x, wcast(params["w1"], dtype, read_once=read_once),
               cfg.quantized_gemm)
    if cfg.use_bias:
        y = y + params["b1"].astype(dtype)
    if cfg.is_glu:
        y = activation_fn(cfg.activation, y[:, :, 0], y[:, :, 1])
    else:
        y = activation_fn(cfg.activation, y)
    y = qdense(y, wcast(params["w2"], dtype, read_once=read_once),
               cfg.quantized_gemm)
    if cfg.use_bias:
        y = y + params["b2"].astype(dtype)
    return y
