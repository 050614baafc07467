"""Rotary position embeddings (RoPE) with linear position-interpolation
scaling, or YaRN's blended frequencies (`yarn_freqs`).

TPU-native equivalent of the reference's complex-multiplication RoPE
(ref: megatron/model/positional_embeddings.py:7-51 `precompute_freqs_cis` /
`apply_rotary_emb`, applied at megatron/model/transformer.py:373-379,500-501).

Convention: the *interleaved-pair* (Meta/Llama) layout — head-dim elements
(2i, 2i+1) form the complex pair. The reference keeps the same convention and
permutes HF checkpoints into it during conversion
(ref: weights2megatron/permute_qkv.py:12-81); our converter does the same, so
numerics line up with the reference end-to-end.

Instead of complex arithmetic (poorly supported on the TPU vector unit) we use
the equivalent real-valued rotation on the de-interleaved halves, which XLA
fuses into the surrounding attention ops.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def precompute_freqs(
    head_dim: int,
    max_seq_len: int,
    theta: float = 10000.0,
    scaling_factor: float = 1.0,
    dtype=jnp.float32,
):
    """cos/sin tables of shape [max_seq_len, head_dim // 2].

    `scaling_factor` implements linear position interpolation: positions are
    divided by the factor so a model trained at 4k attends coherently at
    4k * factor (ref: positional_embeddings.py:10-12, --rope_scaling_factor
    arguments.py:460-461)."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_seq_len, dtype=jnp.float32) / scaling_factor
    freqs = jnp.outer(t, inv_freq)  # [s, hd/2]
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def yarn_mscale(factor: float, a: float) -> float:
    """m(s, a) = 0.1 a ln s + 1, and 1 where nothing is stretched."""
    return 1.0 if factor <= 1.0 else 0.1 * a * math.log(factor) + 1.0


def yarn_softmax_mscale(cfg) -> float:
    """What YaRN puts on an attention's softmax scale: m(factor,
    mscale_all_dim)^2 where `rope_mscale_all_dim` is set, else 1 (MLA's
    scale, models/mla.py, as DeepSeek-V2/V3's modelling code has it)."""
    if cfg.rope_scaling_type != "yarn" or not cfg.rope_mscale_all_dim:
        return 1.0
    return yarn_mscale(cfg.rope_scaling_factor, cfg.rope_mscale_all_dim) ** 2


def yarn_freqs(
    head_dim: int,
    max_seq_len: int,
    theta: float,
    factor: float,
    original_max_position: int,
    beta_fast: float = 32.0,
    beta_slow: float = 1.0,
    mscale: float = 1.0,
    mscale_all_dim: float = 0.0,
    dtype=jnp.float32,
):
    """cos/sin tables [max_seq_len, head_dim // 2] under YaRN (Peng et al.
    2023, as DeepSeek-V2/V3's modelling code computes it). Pair i of
    head_dim / 2 turns theta^(-2i/d) a position. dim(beta) is the pair that
    turns beta times over the original context; the pairs below
    floor(dim(beta_fast)) keep their frequency (they turn often enough to
    have been seen whole in training), those above ceil(dim(beta_slow)) are
    interpolated by 1 / factor, and the ones between are blended linearly.
    Both tables carry m(factor, mscale) / m(factor, mscale_all_dim)."""
    with jax.named_scope("mtpu/rope/yarn"):
        d = head_dim

        def dim_of(beta):
            return d * math.log(original_max_position / (2 * math.pi * beta)) \
                / (2 * math.log(theta))
        low = max(math.floor(dim_of(beta_fast)), 0)
        high = min(math.ceil(dim_of(beta_slow)), d - 1)
        if low == high:
            high += 0.001                       # no division by zero
        i = jnp.arange(d // 2, dtype=jnp.float32)
        ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
        plain = 1.0 / (theta ** (2.0 * i / d))
        inv_freq = (1.0 - ramp) * plain + ramp * plain / factor
        t = jnp.arange(max_seq_len, dtype=jnp.float32)
        freqs = jnp.outer(t, inv_freq)
        m = yarn_mscale(factor, mscale) / yarn_mscale(factor, mscale_all_dim)
        return ((jnp.cos(freqs) * m).astype(dtype),
                (jnp.sin(freqs) * m).astype(dtype))


def apply_rotary(x, cos, sin, position_ids=None):
    """Rotate [batch, seq, heads, head_dim] by position.

    Supports non-monotonic `position_ids` [batch, seq] the same way the
    reference indexes freqs_cis by position_ids
    (ref: positional_embeddings.py:34-43).

    Tables narrower than head_dim / 2 (`cfg.partial_rotary_factor` < 1:
    `make_rope` builds them for `cfg.rotary_dim`) turn the FIRST 2 x their
    width of a head's channels and leave the others as they are."""
    turned = 2 * cos.shape[-1]
    if turned < x.shape[-1]:
        with jax.named_scope("mtpu/rope/partial"):
            return jnp.concatenate(
                [apply_rotary(x[..., :turned], cos, sin, position_ids),
                 x[..., turned:]], axis=-1)
    b, s, n, d = x.shape
    if position_ids is None:
        c = cos[:s][None, :, None, :]  # [1, s, 1, d/2]
        sn = sin[:s][None, :, None, :]
    else:
        c = cos[position_ids][:, :, None, :]  # [b, s, 1, d/2]
        sn = sin[position_ids][:, :, None, :]
    # interleaved pairs: (x0, x1), (x2, x3), ...
    xr = x.astype(jnp.float32).reshape(b, s, n, d // 2, 2)
    x0, x1 = xr[..., 0], xr[..., 1]
    out0 = x0 * c - x1 * sn
    out1 = x1 * c + x0 * sn
    out = jnp.stack([out0, out1], axis=-1).reshape(b, s, n, d)
    return out.astype(x.dtype)
