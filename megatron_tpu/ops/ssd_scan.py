"""Chunked scan of a Mamba-2 mixer (models/mamba2.py): the state-space
duality form of its recurrence.

For every sequence, head h of H (P channels each, in group g = h // (H / G)
of B and C) and state n of N:

    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t[g]   [P, N]
    y_t[h] = S_t[h] C_t[g] + D[h] x_t[h]                               [P]

The decay is a SCALAR a head, so a chunk of Q rows is four matrix products a
head. With a_t = dt_t A, L_t its running sum inside the chunk (inclusive):

    Y_intra[t] = sum_{s<=t} exp(L_t - L_s) (C_t . B_s) dt_s x_s
    Y_inter[t] = exp(L_t) C_t . S_prev
    S_next     = exp(L_end) S_prev + sum_s exp(L_end - L_s) dt_s x_s (x) B_s

`h0` is the state the sequence carries in (zeros for a new one) and the state
after the last row is handed back, float32 [batch, H, P, N] whatever the
rows' dtype. A row whose dt is 0 leaves the state where it stood (its decay
is 1, its input term 0): that is how the caller keeps a bucket's padding
rows out of the state, and how rows are padded to whole chunks here. The
decays, the running sums and the state are float32; the products take their
operands in the rows' dtype (bf16 on the chip) and accumulate in float32,
but the one that reads the float32 state, which stays float32. y comes back
in x's dtype and is NOT gated: the mixer's norm by group follows the gate.

Three forms, one function each:

(a) `_ssd_chunk_scan`: a Pallas kernel for a prefill or a chunk on the TPU,
    jitted under that name so that the device trace names its calls after
    it. Grid (sequence, blocks of heads, chunks), the chunks innermost: a
    block is one GROUP's heads (they share B and C, so C B^T is made once a
    step), whose states [heads, P, N] float32 stay in fast memory from a
    sequence's first chunk to its last and meet HBM twice. The running sums
    are made outside (a cumulative sum over [rows, H] float32, a thousandth
    of the rows' bytes) and handed in both orientations, a column a head
    and a row a head, so that the kernel transposes nothing.
(b) `_ssd_einsum`: the same in plain `einsum`s with a `lax.scan` over the
    chunks' states. The path off the chip, the kernel's oracle, and what
    `jax.grad` differentiates (there is no backward kernel).
(c) `ssd_step`: one row a sequence, the decode step's: elementwise over the
    pool's layer of state, which the caller updates in place.

No option chooses between (a) and (b): `ssd_block_heads` is the kernel's
shape rule, and (a) runs where it holds on a TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LANES = 128
F32 = jnp.float32


def ssd_block_heads(rows: int, heads: int, head_dim: int, groups: int,
                    d_state: int, chunk: int, *, aligned: bool = True):
    """The kernel's shape rule: the heads a grid step takes (one group's),
    or None where the kernel does not take the shape (form (b) then). Whole
    chunks of rows; on the chip (`aligned`) a chunk of whole lane tiles, a
    group's channels a whole number of lane tiles (or all of them) and a
    state of whole lane tiles."""
    if rows % chunk or heads % groups:
        return None
    hb = heads // groups
    if aligned and (chunk % LANES or d_state % LANES
                    or (hb * head_dim % LANES and groups > 1)
                    or (hb % 8 and groups > 1)):
        return None
    return hb


def ssd_scan(x, dt, a, b, c, d, h0=None, *, chunk: int = 128,
             use_kernel=None, interpret: bool = False):
    """x [batch, rows, H, P]; dt [batch, rows, H] float32, 0 on rows that
    must not reach the state; a [H] float32 (negative); b, c [batch, rows,
    G, N]; d [H]; h0 [batch, H, P, N] float32 or None (zeros) -> (y [batch,
    rows, H, P] in x's dtype, the state after the last row, float32)."""
    batch, rows, heads, head_dim = x.shape
    groups, d_state = b.shape[2:]
    if h0 is None:
        h0 = jnp.zeros((batch, heads, head_dim, d_state), F32)
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if (use_kernel or interpret) and ssd_block_heads(
            rows, heads, head_dim, groups, d_state, chunk,
            aligned=not interpret) is not None:
        return _ssd_chunk_scan(x, dt, a, b, c, d, h0, chunk=chunk,
                               interpret=interpret)
    return _ssd_einsum(x, dt, a, b, c, d, h0, chunk=chunk)


def ssd_step(x, dt, a, b, c, d, h):
    """Form (c): x [batch, H, P], dt [batch, H], b, c [batch, G, N], h
    [batch, H, P, N] float32 -> (y [batch, H, P] in x's dtype, the new
    state)."""
    batch, heads, head_dim, d_state = h.shape
    groups = b.shape[1]
    by_group = (batch, groups, heads // groups)
    xf, dt = x.astype(F32), dt.astype(F32)
    decay = jnp.exp(dt * a.astype(F32))                       # [batch, H]
    h = h.reshape(*by_group, head_dim, d_state)
    h = (decay.reshape(*by_group, 1, 1) * h
         + (dt[..., None] * xf).reshape(*by_group, head_dim, 1)
         * b.astype(F32)[:, :, None, None, :])
    y = jnp.sum(h * c.astype(F32)[:, :, None, None, :], axis=-1)
    y = y.reshape(batch, heads, head_dim) + d.astype(F32)[:, None] * xf
    return y.astype(x.dtype), h.reshape(batch, heads, head_dim, d_state)


def _running_sums(dt, a, chunk: int):
    """dt [batch, chunks * chunk, H] float32, a [H] -> (L, w, decay_end), by
    chunk [batch, chunks, chunk, H]: the inclusive running sum of dt A
    inside each chunk, every row's weight into the chunk's last state
    exp(L_end - L_s) dt_s, and [batch, chunks, H] the chunk's whole decay
    exp(L_end)."""
    batch, rows, heads = dt.shape
    dt = dt.reshape(batch, rows // chunk, chunk, heads)
    run = jnp.cumsum(dt * a.astype(F32), axis=2)
    end = run[:, :, -1:]
    return run, jnp.exp(end - run) * dt, jnp.exp(end[:, :, 0])


def _ssd_einsum(x, dt, a, b, c, d, h0, *, chunk: int):
    """Form (b)."""
    batch, rows, heads, head_dim = x.shape
    groups, d_state = b.shape[2:]
    per, dtype = heads // groups, x.dtype
    pad = -rows % chunk
    if pad:     # rows of no step: they move no state, and their y is cut off
        x, dt, b, c = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                       for t in (x, dt, b, c))
    n = (rows + pad) // chunk
    dt = dt.astype(F32)
    run, w, end = _running_sums(dt, a, chunk)
    grouped = lambda t: t.reshape(*t.shape[:-1], groups, per)  # noqa: E731
    run, w, end = grouped(run), grouped(w), grouped(end)
    dtc = grouped(dt.reshape(batch, n, chunk, heads))
    xc = x.reshape(batch, n, chunk, groups, per, head_dim)
    bc = b.reshape(batch, n, chunk, groups, d_state).astype(dtype)
    cc = c.reshape(batch, n, chunk, groups, d_state).astype(dtype)
    # inside a chunk: row t reads row s <= t through exp(L_t - L_s)
    cb = jnp.einsum("bitgn,bisgn->bitsg", cc, bc, preferred_element_type=F32)
    seg = run[:, :, :, None] - run[:, :, None, :]     # [b, i, t, s, g, r]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))[:, :, None, None]
    mix = cb[..., None] * jnp.exp(jnp.where(causal, seg, -jnp.inf)) \
        * dtc[:, :, None]
    y = jnp.einsum("bitsgr,bisgrp->bitgrp", mix.astype(dtype), xc,
                   preferred_element_type=F32)
    # each chunk's own contribution to the state behind it
    fresh = jnp.einsum("bisgrp,bisgn->bigrpn",
                       (xc.astype(F32) * w[..., None]).astype(dtype), bc,
                       preferred_element_type=F32)

    def carry(state, chunk_i):
        decay, new = chunk_i
        return decay[..., None, None] * state + new, state
    by_chunk = lambda t: jnp.swapaxes(t, 0, 1)        # noqa: E731
    last, before = jax.lax.scan(
        carry, h0.astype(F32).reshape(batch, groups, per, head_dim, d_state),
        (by_chunk(end), by_chunk(fresh)))
    y = y + jnp.einsum("bitgn,bigrpn->bitgrp", cc.astype(F32),
                       by_chunk(before),
                       precision=jax.lax.Precision.HIGHEST) \
        * jnp.exp(run)[..., None]
    y = y.reshape(batch, rows + pad, heads, head_dim)[:, :rows] \
        + d.astype(F32)[:, None] * x[:, :rows].astype(F32)
    return y.astype(dtype), last.reshape(batch, heads, head_dim, d_state)


def _chunk_kernel(x_ref, b_ref, c_ref, run_col, w_col, run_row, dt_row,
                  end_ref, d_ref, h0_ref, y_ref, state_ref, *, heads: int,
                  head_dim: int, chunk: int):
    from jax.experimental import pallas as pl
    dtype = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = h0_ref[...]

    bm, cm = b_ref[0], c_ref[0]                              # [chunk, N]
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=F32)     # [t, s]
    t_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = t_i >= s_i
    cf = cm.astype(F32)
    for j in range(heads):
        at = slice(j * head_dim, (j + 1) * head_dim)
        xj = x_ref[0, :, at]                                 # [chunk, P]
        lc = run_col[0, 0, :, j:j + 1]                       # [chunk, 1]
        lr = run_row[0, j:j + 1, :]                          # [1, chunk]
        mix = cb * jnp.exp(jnp.where(causal, lc - lr, -jnp.inf)) \
            * dt_row[0, j:j + 1, :]
        y = jnp.dot(mix.astype(dtype), xj, preferred_element_type=F32)
        state = state_ref[0, j]                              # [P, N]
        y = y + jnp.exp(lc) * jax.lax.dot_general(
            cf, state, (((1,), (1,)), ((), ())),
            preferred_element_type=F32,
            precision=jax.lax.Precision.HIGHEST)
        y_ref[0, :, at] = (y + d_ref[:, at] * xj.astype(F32)).astype(dtype)
        weighted = (xj.astype(F32) * w_col[0, 0, :, j:j + 1]).astype(dtype)
        state_ref[0, j] = end_ref[0, 0, j:j + 1, :] * state \
            + jax.lax.dot_general(weighted, bm, (((0,), (0,)), ((), ())),
                                  preferred_element_type=F32)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_chunk_scan(x, dt, a, b, c, d, h0, *, chunk=128, interpret=False):
    """Form (a), under the name the device trace reads."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    batch, rows, heads, head_dim = x.shape
    groups, d_state = b.shape[2:]
    hb = ssd_block_heads(rows, heads, head_dim, groups, d_state, chunk,
                         aligned=not interpret)
    n = rows // chunk
    run, w, end = _running_sums(dt.astype(F32), a, chunk)
    flat = lambda t: t.reshape(batch, rows, heads)           # noqa: E731
    # a column a head, [batch, groups, rows, heads a group], and a row a
    # head, [batch, H, rows]
    cols = lambda t: flat(t).reshape(                        # noqa: E731
        batch, rows, groups, hb).swapaxes(1, 2)
    rows_of = lambda t: flat(t).swapaxes(1, 2)               # noqa: E731
    width = hb * head_dim
    by_rows = pl.BlockSpec((1, chunk, width), lambda bi, gi, ci: (bi, ci, gi))
    group_rows = pl.BlockSpec((1, chunk, d_state),
                              lambda bi, gi, ci: (bi, ci, gi))
    col_spec = pl.BlockSpec((1, 1, chunk, hb),
                            lambda bi, gi, ci: (bi, gi, ci, 0))
    row_spec = pl.BlockSpec((1, hb, chunk), lambda bi, gi, ci: (bi, gi, ci))
    # a group's states come in with the sequence's first chunk and go out
    # behind its last: the block's index stands still over the chunks
    state_spec = pl.BlockSpec((1, hb, head_dim, d_state),
                              lambda bi, gi, ci: (bi, gi, 0, 0))
    products = 2 * batch * rows * heads * (
        chunk * d_state // hb + chunk * head_dim + 2 * head_dim * d_state)
    call = pl.pallas_call(
        functools.partial(_chunk_kernel, heads=hb, head_dim=head_dim,
                          chunk=chunk),
        grid=(batch, groups, n),
        in_specs=[by_rows, group_rows, group_rows, col_spec, col_spec,
                  row_spec, row_spec,
                  pl.BlockSpec((1, 1, hb, d_state),
                               lambda bi, gi, ci: (bi, ci, gi, 0)),
                  pl.BlockSpec((1, width), lambda bi, gi, ci: (0, gi)),
                  state_spec],
        out_specs=[by_rows, state_spec],
        out_shape=[jax.ShapeDtypeStruct((batch, rows, heads * head_dim),
                                        x.dtype),
                   jax.ShapeDtypeStruct(h0.shape, F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=products, transcendentals=batch * rows * heads * chunk,
            bytes_accessed=2 * x.size * x.dtype.itemsize
            + (b.size + c.size) * b.dtype.itemsize + 4 * dt.size * 4
            + 2 * h0.size * 4),
        interpret=interpret)
    # the kernel's products take bf16 operands and accumulate in float32
    # whatever precision the caller has set as JAX's default (Mosaic refuses
    # "highest" on bf16 operands); the one that reads the float32 state
    # names its own
    with jax.default_matmul_precision("bfloat16"):
        y, last = call(
            x.reshape(batch, rows, heads * head_dim),
            b.reshape(batch, rows, groups * d_state).astype(x.dtype),
            c.reshape(batch, rows, groups * d_state).astype(x.dtype),
            cols(run), cols(w), rows_of(run), rows_of(dt.astype(F32)),
            # a chunk's whole decay a head, along the state's lanes: the
            # kernel spreads it over the sublanes alone
            jnp.broadcast_to(end[..., None], (batch, n, heads, d_state)),
            jnp.repeat(d.astype(F32), head_dim).reshape(1, heads * head_dim),
            h0.astype(F32))
    return y.reshape(x.shape), last
