"""Grouped matrix product over ragged groups: the expert GEMM of a dropless
mixture of experts (models/moe.py, `moe_dispatch="dropless"`).

    grouped_matmul(lhs [m, k], rhs [E, k, n], group_sizes [E]) -> [m, n]
    grouped_matmul(lhs [m, k], rhs [L, E, k, n], group_sizes [E], layer=i)
    round_bank(rhs [L, E, k, n], layer, dtype) -> [E, k, n]

The rows of `lhs` are sorted by group: the first `group_sizes[0]` rows are
multiplied by `rhs[0]`, the next `group_sizes[1]` by `rhs[1]`, and so on.
`sum(group_sizes)` is `m` wherever the program calls this. Rows past it (the
few that pad `m` to the kernel's row tile) buy no work on the TPU and hold
nothing defined.

`rhs` may be the bank STACKED over layers with the layer's index beside it,
in whatever precision the weights are held: a cached program's layer loop
hands the whole bank down (models/transformer.py::stack_apply). The product
is the one of `rhs[layer].astype(lhs.dtype)`; on the TPU neither that slice
nor that cast is ever made in memory. That is a decode step's call. A
prefill takes `round_bank` first, one Pallas pass that writes the layer's
bank rounded from the stack where it lies, and multiplies that (why it does
not read in place too: models/moe.py::_dropless_experts).

On the TPU the forward product is this module's own Pallas kernel
(`_grouped_product`): megablox's grid and group metadata (one grid step per
row tile and group that meet, groups with no row skipped), with two things
megablox's `gmm` has not. The kernel is given every matrix of the stack,
`[L * E, k, n]` (the stack with its two leading axes merged, which moves
nothing), and the index of the layer's first, one more prefetched scalar:
group g's block is matrix `layer * E + g`, so a Pallas call that cannot read
a dynamic slice in place is never given one. And a bank held in float32 is
read as float32 and rounded to the rows' dtype in fast memory (round to
nearest even, as XLA's `convert`) before a product of the rows' dtype
accumulated in float32: megablox would multiply in float32, and a cast
outside the kernel writes a narrow copy of every bank and reads it back (12
bytes moved per weight and step where the kernel needs 4; PERF.md section 6,
PR 30). A bank `[E, k, n]` is the one-layer case. The backward pass keeps
megablox (`gmm` against the bank transposed, `tgmm` for the bank's gradient)
on the layer's bank.
Each kernel sits inside a jitted function of this module so that the device
trace names it (`%_moe_grouped_matmul.N` the forward product, `..._dlhs.N`
and `..._drhs.N` the backward pass's two, as `%_flash_attention.N`).
Which product was kept, and what `jax.lax.ragged_dot` measured at the same
shapes: PERF.md section 6, PR 27. Anywhere else the same rows take a plain
`jax.numpy` product (each row against its own group's matrix), so that CPU
tests cover the routing round the kernel and everything but the kernel
itself. Both are differentiable in `lhs` and `rhs`.

Nothing here is imported by a model without experts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def grouped_matmul(lhs, rhs, group_sizes, *, layer=None,
                   use_kernel: bool | None = None):
    m, k = lhs.shape
    assert rhs.ndim == (3 if layer is None else 4), (rhs.shape, layer)
    assert rhs.shape[-2] == k, (lhs.shape, rhs.shape)
    assert group_sizes.shape == (rhs.shape[-3],), group_sizes.shape
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if not use_kernel:
        if layer is not None:
            rhs = jax.lax.dynamic_index_in_dim(rhs, layer, 0, keepdims=False)
        return _plain_grouped_matmul(lhs, rhs.astype(lhs.dtype), group_sizes)
    if layer is None:            # one layer's bank: a stack of one
        rhs, layer = rhs[None], 0
    # the kernel's row tile must divide m: pad with rows of no group, which
    # the kernel skips and the slice below drops
    pad = -m % _tiling(m, k, rhs.shape[3], rhs.dtype.itemsize)[0]
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = _kernel_grouped_matmul(lhs, rhs, jnp.asarray(layer, jnp.int32),
                                 group_sizes.astype(jnp.int32))
    return out[:m] if pad else out


def round_bank(bank, layer, dtype, *, use_kernel: bool | None = None):
    """`bank[layer].astype(dtype)` of a stacked bank `[L, E, k, n]`, for the
    products of a cached program that do not read the stack in place (a
    prefill's: models/moe.py::_dropless_experts). On the TPU one Pallas pass
    reads the layer's float32 blocks where they lie and writes them rounded
    (6 bytes a weight). Left to XLA, the cast goes ahead of the layer loop,
    over every layer's banks, and a layer is copied out of the cast in every
    pass (10 bytes a weight, and a narrow copy of the whole stack held).
    Not differentiable on the TPU: only a cached program calls it."""
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if not use_kernel or bank.dtype == dtype:
        return jax.lax.dynamic_index_in_dim(
            bank, layer, 0, keepdims=False).astype(dtype)
    return _moe_round_bank(bank, jnp.asarray(layer, jnp.int32),
                           jnp.dtype(dtype))


def _tiling(m: int, k: int, n: int, itemsize: int = 2):
    """(rows, contraction, columns) tile of the kernel, from the sweep on the
    chip at OLMoE's shapes (PERF.md section 6, PR 27). The whole contraction
    in one tile (k is 2048 or 1024 there) was the largest single gain: no
    partial sums go through the accumulator. A decode step's 256 rows over 64
    groups want the smallest row tile; thousands of prefill rows want 256.
    Columns fill what is left of the kernel's 16 MiB with two buffers of the
    bank's tile AS THE BANK IS HELD (`itemsize`): 4 MiB each, 1024 columns
    of a bf16 bank at k = 2048 and 512 of a float32 one, whose rounded copy
    takes 2 MiB more. Fewer columns than the bank has are a whole number of
    lanes (128), which the chip's compiler asks of a block: at k = 1792
    (LFM2's second product) 4 MiB are 1,170 columns, and the tile is 1,152."""
    tk = min(k, 2048)
    return (128 if m <= 4096 else 256, tk,
            min(n, (4 << 20) // (tk * itemsize) // 128 * 128))


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


def _grouped_product(lhs, bank, first, group_sizes, tiling, *,
                     interpret: bool = False):
    """lhs [m, k] x bank[first : first + E] [E, k, n] by groups -> [m, n]
    of lhs's dtype; bank [G, k, n] with G >= first + E, m a multiple of the
    row tile. The grid is megablox's: (column tiles, row-tile-and-group pairs
    that hold a row, contraction tiles), the middle extent known only on the
    device."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from jax.experimental.pallas.ops.tpu.megablox.gmm import \
        make_group_metadata
    (m, k), (_, _, n), (tm, tk, tn) = lhs.shape, bank.shape, tiling
    groups = group_sizes.shape[0]
    dtype = lhs.dtype
    tiles_k, k_rem = pl.cdiv(k, tk), k % tk
    (offsets, group_ids, m_tile_ids), num_tiles = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=groups, visit_empty_groups=False)

    def kernel(offsets, group_ids, m_tile_ids, first, lhs_ref, bank_ref,
               out_ref, acc):
        del first
        step, k_i = pl.program_id(1), pl.program_id(2)

        def past_k(x, axis):        # the last contraction tile's overhang
            if not k_rem:
                return x
            inside = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis) < k_rem
            return jnp.where(jnp.logical_or(k_i < tiles_k - 1, inside),
                             x.astype(jnp.float32), 0).astype(x.dtype)

        @pl.when(k_i == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)

        # a float32 block is rounded here, at every step that meets it: kept
        # in a scratch and rounded once a fetch it measured 3 % slower at
        # 49,152 rows (PERF.md section 6, PR 30)
        weights = bank_ref[...].astype(dtype)
        acc[...] += jax.lax.dot_general(
            past_k(lhs_ref[...], 1), past_k(weights, 0),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

        @pl.when(k_i == tiles_k - 1)
        def _():
            # a row tile that two groups share is visited once for each:
            # only the visiting group's rows are written
            group = group_ids[step]
            row = m_tile_ids[step] * tm + jax.lax.broadcasted_iota(
                jnp.int32, (tm, tn), 0)
            mine = jnp.logical_and(row >= offsets[group],
                                   row < offsets[group + 1])
            out_ref[...] = jnp.where(
                mine, acc[...], out_ref[...].astype(jnp.float32)
            ).astype(out_ref.dtype)

    def rows_at(n_i, step, k_i, offsets, group_ids, m_tile_ids, first):
        return m_tile_ids[step], k_i

    def bank_at(n_i, step, k_i, offsets, group_ids, m_tile_ids, first):
        return first[0] + group_ids[step], k_i, n_i

    def out_at(n_i, step, k_i, offsets, group_ids, m_tile_ids, first):
        return m_tile_ids[step], n_i

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[pl.BlockSpec((tm, tk), rows_at),
                      pl.BlockSpec((None, tk, tn), bank_at)],
            out_specs=pl.BlockSpec((tm, tn), out_at),
            grid=(pl.cdiv(n, tn), num_tiles, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k * pl.cdiv(n, tn) + m * n) * dtype.itemsize
            + groups * k * n * bank.dtype.itemsize),
        interpret=interpret,
    )(offsets, group_ids, m_tile_ids, first.reshape(1), lhs, bank)


def _rounded_layer(bank, layer, dtype, *, interpret: bool = False):
    """bank [L, E, k, n] -> bank[layer] [E, k, n] as `dtype`, a block at a
    time: 2 MiB of float32 in, half that out, two buffers of each."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    _, experts, k, n = bank.shape
    tn = min(n, 2048)
    tk = min(k, (2 << 20) // (tn * bank.dtype.itemsize))

    def kernel(layer, bank_ref, out_ref):
        del layer
        out_ref[...] = bank_ref[...].astype(out_ref.dtype)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((experts, k, n), dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec(
                (None, None, tk, tn),
                lambda e, k_i, n_i, layer: (layer[0], e, k_i, n_i))],
            out_specs=pl.BlockSpec(
                (None, tk, tn), lambda e, k_i, n_i, layer: (e, k_i, n_i)),
            grid=(experts, pl.cdiv(k, tk), pl.cdiv(n, tn))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=0, transcendentals=0,
            bytes_accessed=experts * k * n
            * (bank.dtype.itemsize + jnp.dtype(dtype).itemsize)),
        interpret=interpret,
    )(layer.reshape(1), bank)


# no fallback in these: a kernel that cannot be imported or that the
# compiler refuses raises, it does not drop to the plain path. The kernel's
# products are bf16 in, float32 accumulated, whatever precision the caller
# has set as JAX's default (Mosaic refuses "highest" on bf16 operands).
_KERNEL_PRECISION = "bfloat16"


@jax.jit
def _moe_grouped_matmul(lhs, bank, layer, group_sizes):
    _, experts, k, n = bank.shape
    tiling = _tiling(lhs.shape[0], k, n, bank.dtype.itemsize)
    with jax.default_matmul_precision(_KERNEL_PRECISION):
        return _grouped_product(lhs, bank.reshape(-1, k, n), layer * experts,
                                group_sizes, tiling)


@functools.partial(jax.jit, static_argnames="dtype")
def _moe_round_bank(bank, layer, dtype):
    return _rounded_layer(bank, layer, dtype)


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
def _kernel_grouped_matmul(lhs, bank, layer, group_sizes):
    return _moe_grouped_matmul(lhs, bank, layer, group_sizes)


def _kernel_fwd(lhs, bank, layer, group_sizes):
    return (_moe_grouped_matmul(lhs, bank, layer, group_sizes),
            (lhs, bank, layer, group_sizes))


def _kernel_bwd(res, grad):
    lhs, bank, layer, group_sizes = res
    grad = grad.astype(lhs.dtype)
    rhs = jax.lax.dynamic_index_in_dim(bank, layer, 0, keepdims=False)
    rhs = rhs.astype(lhs.dtype)
    dlhs = _moe_grouped_matmul_dlhs(grad, rhs, group_sizes)
    drhs = _moe_grouped_matmul_drhs(lhs, grad, group_sizes)
    dbank = jax.lax.dynamic_update_index_in_dim(
        jnp.zeros_like(bank), drhs.astype(bank.dtype), layer, 0)
    return dlhs, dbank, None, None


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
