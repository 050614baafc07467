"""Pallas fused RMSNorm / LayerNorm (fwd + bwd).

TPU-native equivalent of the reference's fused mixed-precision LayerNorm
CUDA extension (ref: megatron/fused_kernels/layer_norm_cuda_kernel.cu:1-818,
layer_norm_cuda.cpp forward_affine/backward_affine) and the RMSNorm it
pairs with (ref: megatron/model/fused_layer_norm.py:125-139). Stats are
fp32 regardless of input dtype — the reference kernel's mixed-precision
contract.

One kernel invocation normalizes a [block_rows, h] tile resident in VMEM:
the load, the fp32 moment reduction, the rsqrt, and the affine output are
fused with zero HBM round-trips. The backward recomputes row statistics
from x (cheaper than an HBM round-trip for saved stats at transformer
widths) and emits per-grid-step partial weight grads that are summed
outside — the Pallas formulation of the CUDA kernel's two-stage
gamma/beta reduction (ref: layer_norm_cuda_kernel.cu cuComputePartGradGammaBeta).

`megatron_tpu/models/norms.py` is the canonical jnp implementation; these
kernels exist for explicit fusion control. Not timed on the chip (ROADMAP
D6): the model default stays jnp unless a profile says otherwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _pick_rows(rows: int, h: int, vmem_budget: int = 1 << 21) -> int:
    """Row block: a multiple of 8 (TPU sublane) whose fp32 tile stays under
    ~2 MB of VMEM. Divisibility of `rows` is NOT required — callers zero-pad
    the row dim up to a block multiple (padded rows contribute nothing to
    the weight-grad partials since dy is zero there), so a prime row count
    no longer collapses to a 1-row grid."""
    cap = max(vmem_budget // (4 * h), 1)
    if cap < 8:
        return cap
    return min(cap // 8 * 8, max(-(-rows // 8) * 8, 8))


def _pad_rows(xr, br: int):
    """Zero-pad [rows, h] up to a multiple of the row block."""
    pad = (-xr.shape[0]) % br
    if pad:
        xr = jnp.pad(xr, ((0, pad), (0, 0)))
    return xr


# Each grid step's weight-grad partial is written as a block of 8 rows
# (row 0 real, the rest zero): the TPU lowering wants an output block's
# second-last dimension divisible by the 8-row sublane tile, and a
# (1, h) block over a (grid, h) array is refused. The zero rows vanish
# in the sum over partials outside the kernel.
PART_ROWS = 8


def _partial_rows(part):
    """[1, h] per-block partial -> [PART_ROWS, h], row 0 real."""
    h = part.shape[-1]
    row = jax.lax.broadcasted_iota(jnp.int32, (PART_ROWS, h), 0)
    return jnp.where(row == 0, jnp.broadcast_to(part, (PART_ROWS, h)), 0.0)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def _rms_fwd_kernel(x_ref, s_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    o_ref[...] = (x * r * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _rms_bwd_kernel(x_ref, s_ref, dy_ref, dx_ref, ds_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    s = s_ref[...].astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    xh = x * r
    g = dy * s
    c = jnp.mean(g * xh, axis=-1, keepdims=True)
    dx_ref[...] = (r * (g - xh * c)).astype(dx_ref.dtype)
    ds_ref[...] = _partial_rows(jnp.sum(dy * xh, axis=0, keepdims=True))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def pallas_rmsnorm(x, scale, eps: float = 1e-5, interpret: bool = False):
    """x [..., h] * rsqrt(mean(x², -1) + eps) * scale, fused."""
    out, _ = _rms_fwd(x, scale, eps, interpret)
    return out


def _rms_fwd(x, scale, eps, interpret):
    orig_shape = x.shape
    h = orig_shape[-1]
    xr = x.reshape(-1, h)
    rows = xr.shape[0]
    br = _pick_rows(rows, h)
    xr = _pad_rows(xr, br)
    rows_p = xr.shape[0]
    s2 = scale.reshape(1, h)
    out = pl.pallas_call(
        functools.partial(_rms_fwd_kernel, eps=eps),
        grid=(rows_p // br,),
        in_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)),
                  pl.BlockSpec((1, h), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((br, h), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, h), x.dtype),
        interpret=interpret,
    )(xr, s2)
    return out[:rows].reshape(orig_shape), (x, scale)


def _rms_bwd(eps, interpret, res, dy):
    x, scale = res
    orig_shape = x.shape
    h = orig_shape[-1]
    xr = x.reshape(-1, h)
    dyr = dy.reshape(-1, h)
    rows = xr.shape[0]
    br = _pick_rows(rows, h)
    xr = _pad_rows(xr, br)
    dyr = _pad_rows(dyr, br)
    rows_p = xr.shape[0]
    grid = rows_p // br
    dx, ds_part = pl.pallas_call(
        functools.partial(_rms_bwd_kernel, eps=eps),
        grid=(grid,),
        in_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)),
                  pl.BlockSpec((1, h), lambda i: (0, 0)),
                  pl.BlockSpec((br, h), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)),
                   pl.BlockSpec((PART_ROWS, h), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows_p, h), x.dtype),
                   jax.ShapeDtypeStruct((grid * PART_ROWS, h), jnp.float32)],
        interpret=interpret,
    )(xr, scale.reshape(1, h), dyr)
    ds = jnp.sum(ds_part, axis=0).astype(scale.dtype)
    return dx[:rows].reshape(orig_shape), ds


pallas_rmsnorm.defvjp(_rms_fwd, _rms_bwd)


# ---------------------------------------------------------------------------
# LayerNorm
# ---------------------------------------------------------------------------

def _ln_fwd_kernel(x_ref, s_ref, b_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    r = jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)
    o_ref[...] = (xc * r * s_ref[...].astype(jnp.float32)
                  + b_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _ln_bwd_kernel(x_ref, s_ref, dy_ref, dx_ref, ds_ref, db_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    s = s_ref[...].astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    r = jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)
    xh = xc * r
    g = dy * s
    gm = jnp.mean(g, axis=-1, keepdims=True)
    c = jnp.mean(g * xh, axis=-1, keepdims=True)
    dx_ref[...] = (r * (g - gm - xh * c)).astype(dx_ref.dtype)
    ds_ref[...] = _partial_rows(jnp.sum(dy * xh, axis=0, keepdims=True))
    db_ref[...] = _partial_rows(jnp.sum(dy, axis=0, keepdims=True))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def pallas_layernorm(x, scale, bias, eps: float = 1e-5,
                     interpret: bool = False):
    """Affine LayerNorm (fp32 stats), fused
    (ref: layer_norm_cuda.cpp forward_affine)."""
    out, _ = _ln_fwd(x, scale, bias, eps, interpret)
    return out


def _ln_fwd(x, scale, bias, eps, interpret):
    orig_shape = x.shape
    h = orig_shape[-1]
    xr = x.reshape(-1, h)
    rows = xr.shape[0]
    br = _pick_rows(rows, h)
    xr = _pad_rows(xr, br)
    rows_p = xr.shape[0]
    out = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps),
        grid=(rows_p // br,),
        in_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)),
                  pl.BlockSpec((1, h), lambda i: (0, 0)),
                  pl.BlockSpec((1, h), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((br, h), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, h), x.dtype),
        interpret=interpret,
    )(xr, scale.reshape(1, h), bias.reshape(1, h))
    return out[:rows].reshape(orig_shape), (x, scale)


def _ln_bwd(eps, interpret, res, dy):
    x, scale = res
    orig_shape = x.shape
    h = orig_shape[-1]
    xr = x.reshape(-1, h)
    dyr = dy.reshape(-1, h)
    rows = xr.shape[0]
    br = _pick_rows(rows, h)
    xr = _pad_rows(xr, br)
    dyr = _pad_rows(dyr, br)
    rows_p = xr.shape[0]
    grid = rows_p // br
    dx, ds_part, db_part = pl.pallas_call(
        functools.partial(_ln_bwd_kernel, eps=eps),
        grid=(grid,),
        in_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)),
                  pl.BlockSpec((1, h), lambda i: (0, 0)),
                  pl.BlockSpec((br, h), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((br, h), lambda i: (i, 0)),
                   pl.BlockSpec((PART_ROWS, h), lambda i: (i, 0)),
                   pl.BlockSpec((PART_ROWS, h), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows_p, h), x.dtype),
                   jax.ShapeDtypeStruct((grid * PART_ROWS, h), jnp.float32),
                   jax.ShapeDtypeStruct((grid * PART_ROWS, h), jnp.float32)],
        interpret=interpret,
    )(xr, scale.reshape(1, h), dyr)
    ds = jnp.sum(ds_part, axis=0).astype(scale.dtype)
    db = jnp.sum(db_part, axis=0).astype(scale.dtype)
    return dx[:rows].reshape(orig_shape), ds, db


pallas_layernorm.defvjp(_ln_fwd, _ln_bwd)
