"""Grouped matrix product over ragged groups: the expert GEMM of a dropless
mixture of experts (models/moe.py, `moe_dispatch="dropless"`).

    grouped_matmul(lhs [m, k], rhs [E, k, n], group_sizes [E]) -> [m, n]

The rows of `lhs` are sorted by group: the first `group_sizes[0]` rows are
multiplied by `rhs[0]`, the next `group_sizes[1]` by `rhs[1]`, and so on.
`sum(group_sizes)` is `m` wherever the program calls this. Rows past it (the
few that pad `m` to the kernel's row tile) buy no work on the TPU and hold
nothing defined.

On the TPU this is the megablox kernel that ships with JAX
(`jax.experimental.pallas.ops.tpu.megablox.gmm`, with its `tgmm` transpose
for the weight gradient), each inside a jitted function of this module so
that the device trace names it (`%_moe_grouped_matmul.N` the forward
product, `..._dlhs.N` and `..._drhs.N` the backward pass's two, as
`%_flash_attention.N`).
Which product was kept, and what `jax.lax.ragged_dot` measured at the same
shapes: PERF.md section 6, PR 27. Anywhere else the same rows take a plain
`jax.numpy` product (each row against its own group's matrix), so that CPU
tests cover the routing round the kernel and everything but the kernel
itself. Both are differentiable in `lhs` and `rhs`.

Nothing here is imported by a model without experts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def grouped_matmul(lhs, rhs, group_sizes, *, use_kernel: bool | None = None):
    m, k = lhs.shape
    assert rhs.ndim == 3 and rhs.shape[1] == k, (lhs.shape, rhs.shape)
    assert group_sizes.shape == (rhs.shape[0],), group_sizes.shape
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if not use_kernel:
        return _plain_grouped_matmul(lhs, rhs, group_sizes)
    # the kernel's row tile must divide m: pad with rows of no group, which
    # the kernel skips and the slice below drops
    pad = -m % _tiling(m, k, rhs.shape[2])[0]
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = _kernel_grouped_matmul(lhs, rhs, group_sizes.astype(jnp.int32))
    return out[:m] if pad else out


def _tiling(m: int, k: int, n: int):
    """(rows, contraction, columns) tile of the kernel, from the sweep on the
    chip at OLMoE's shapes (PERF.md section 6, PR 27). The whole contraction
    in one tile (k is 2048 or 1024 there) was the largest single gain: no
    partial sums go through the accumulator. A decode step's 256 rows over 64
    groups want the smallest row tile; thousands of prefill rows want 256.
    Columns fill what is left of the kernel's 16 MiB with two buffers of the
    bank's tile."""
    tk = min(k, 2048)
    return (128 if m <= 4096 else 256, tk, min(n, (2 << 20) // tk))


def _backward_tiling(m: int, k: int, n: int):
    """The backward pass's two kernels keep a tile the chip's compiler was
    seen to accept; they have not been timed on the chip (no cell trains
    this model yet)."""
    return (128 if m <= 4096 else 256, min(k, 1024), min(n, 1024))


def _megablox():
    """megablox's `gmm` and `tgmm` without their own `jax.jit`: inside OUR
    jitted functions, so that the trace names each kernel after them. The
    module is experimental and `__wrapped__` is how `jax.jit` (functools)
    keeps the function it wraps in the installed JAX 0.9: where either has
    moved, say so here and do not fall back to a kernel the trace cannot
    name."""
    import importlib
    mod = importlib.import_module(      # the package re-exports the function
        "jax.experimental.pallas.ops.tpu.megablox.gmm")      # under this name
    try:
        return mod.gmm.__wrapped__, mod.tgmm.__wrapped__
    except AttributeError as e:
        raise ImportError(
            f"jax {jax.__version__}: megablox's gmm/tgmm are no longer "
            "jitted functions with a __wrapped__ (written against jax "
            "0.9.0): point ops/grouped_matmul.py::_megablox at the "
            "un-jitted kernels") from e


# no fallback in these: a kernel that cannot be imported or that the
# compiler refuses raises, it does not drop to the plain path. The kernel's
# products are bf16 in, float32 accumulated, whatever precision the caller
# has set as JAX's default (Mosaic refuses "highest" on bf16 operands).
_KERNEL_PRECISION = "bfloat16"


@jax.jit
def _moe_grouped_matmul(lhs, rhs, group_sizes):
    gmm, _ = _megablox()
    tiling = _tiling(lhs.shape[0], rhs.shape[1], rhs.shape[2])
    with jax.default_matmul_precision(_KERNEL_PRECISION):
        return gmm(lhs, rhs, group_sizes, lhs.dtype, tiling)


@jax.jit
def _moe_grouped_matmul_dlhs(grad, rhs, group_sizes):
    gmm, _ = _megablox()
    tiling = _backward_tiling(grad.shape[0], rhs.shape[2], rhs.shape[1])
    with jax.default_matmul_precision(_KERNEL_PRECISION):
        return gmm(grad, rhs, group_sizes, grad.dtype, tiling,
                   transpose_rhs=True)


@jax.jit
def _moe_grouped_matmul_drhs(lhs, grad, group_sizes):
    _, tgmm = _megablox()
    tiling = _backward_tiling(lhs.shape[0], lhs.shape[1], grad.shape[1])
    with jax.default_matmul_precision(_KERNEL_PRECISION):
        return tgmm(lhs.swapaxes(0, 1), grad, group_sizes, lhs.dtype, tiling)


@jax.custom_vjp
def _kernel_grouped_matmul(lhs, rhs, group_sizes):
    return _moe_grouped_matmul(lhs, rhs, group_sizes)


def _kernel_fwd(lhs, rhs, group_sizes):
    return (_moe_grouped_matmul(lhs, rhs, group_sizes),
            (lhs, rhs, group_sizes))


def _kernel_bwd(res, grad):
    lhs, rhs, group_sizes = res
    grad = grad.astype(lhs.dtype)
    dlhs = _moe_grouped_matmul_dlhs(grad, rhs, group_sizes)
    drhs = _moe_grouped_matmul_drhs(lhs, grad, group_sizes)
    return dlhs, drhs.astype(rhs.dtype), None


_kernel_grouped_matmul.defvjp(_kernel_fwd, _kernel_bwd)


def _plain_grouped_matmul(lhs, rhs, group_sizes):
    """Each row against its own group's matrix, gathered: [m, k, n] of
    weights, fine at test sizes and nowhere else. Rows of no group come out
    zero."""
    E = rhs.shape[0]
    gid = jnp.searchsorted(jnp.cumsum(group_sizes), jnp.arange(lhs.shape[0]),
                           side="right")         # E for rows of no group
    out = jnp.einsum("mk,mkn->mn", lhs, rhs[jnp.minimum(gid, E - 1)])
    return jnp.where((gid < E)[:, None], out, 0).astype(lhs.dtype)
