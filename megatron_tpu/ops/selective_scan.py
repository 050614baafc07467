"""Selective scan: the recurrence of a Mamba-1 mixer (models/mamba.py).

For every sequence, channel c of d_inner and state n of d_state:

    h_t[n, c] = exp(dt_t[c] * A[n, c]) * h_{t-1}[n, c] + dt_t[c] * B_t[n] * x_t[c]
    y_t[c]    = (sum_n C_t[n] * h_t[n, c] + D[c] * x_t[c]) * silu(z_t[c])

`h_0` is the state the sequence carries in (zeros for a new one) and the
state after the last row is handed back. A row whose dt is 0 leaves the state
where it stood (exp(0) = 1, the input term 0): that is how the caller keeps a
bucket's padding rows out of the state, and the scan itself knows of no
padding. The state is held [d_state, d_inner], the CHANNELS minor: sixteen
values would fill an eighth of the device's 128 lanes, 5,120 fill them, and
the pool holds it in the order the kernel reads it. The recurrence and its
state are float32 whatever the rows' dtype; y comes back in x's dtype, GATED
(the kernel reads z anyway, and a pass over y is saved).

Three forms, one function each:

(a) `_scan_xla`: a `lax.scan` over time, plain XLA. The CPU, the training
    path (it is what `jax.grad` differentiates: there is no backward
    kernel) and every shape the kernel does not take.
(b) `_ssm_selective_scan`: a Pallas kernel for prefill on the TPU, jitted
    under that name so that the device trace names its calls after it.
    Grid (sequence, blocks of rows, blocks of channels), the channels
    innermost: the state of ALL channels, [d_inner / block, d_state, block]
    float32 (320 KB at d_inner 5,120), lives in VMEM from a sequence's first
    block of rows to its last and meets HBM twice, coming in and going out.
    A step of the recurrence is elementwise over [d_state, 128 channels]
    tiles (two registers): no matrix product anywhere, the vector and
    exponential units bound it. B_t[n] and C_t[n] are the same for every
    channel, so a tile needs them spread along the lanes; the caller's XLA
    does that once a row ([rows, d_state, 128], 16 KB a row beside the 51 KB
    of x, dt, z and y) and the block stays put while the channel blocks go
    by.
(c) `selective_scan_step`: one row a sequence, the decode step's, in XLA:
    elementwise over the pool's layer of state, which the caller updates in
    place.

No option chooses between (a) and (b): `scan_block_rows` is the kernel's
shape rule, and (b) runs where it holds on a TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LANES = 128
GROUP = 16          # rows a loop iteration: a bf16 tile's sublanes
BLOCK_ROWS = 128    # rows a grid step
BLOCK_CHANNELS = 512


def scan_block_rows(rows: int, d_inner: int, d_state: int):
    """The kernel's shape rule: the rows a grid step takes, or None where
    the kernel does not take the shape (form (a) then). Whole blocks of
    rows, whole blocks of channels, a state of whole float32 sublane
    tiles."""
    if rows % BLOCK_ROWS or d_inner % BLOCK_CHANNELS or d_state % 8:
        return None
    return BLOCK_ROWS


def selective_scan(x, dt, a_t, b, c, d, z, h0=None, *, use_kernel=None,
                   interpret: bool = False):
    """x, z [batch, rows, d_inner]; dt [batch, rows, d_inner] float32, 0 on
    rows that must not reach the state; a_t [d_state, d_inner] float32 (A
    transposed, negative); b, c [batch, rows, d_state]; d [d_inner]; h0
    [batch, d_state, d_inner] float32 or None (zeros) -> (y [batch, rows,
    d_inner] in x's dtype, gated; the state after the last row, float32)."""
    batch, rows, d_inner = x.shape
    d_state = a_t.shape[0]
    if h0 is None:
        h0 = jnp.zeros((batch, d_state, d_inner), jnp.float32)
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if (use_kernel or interpret) \
            and scan_block_rows(rows, d_inner, d_state) is not None:
        return _ssm_selective_scan(x, dt, a_t, b, c, d, z, h0,
                                   interpret=interpret)
    return _scan_xla(x, dt, a_t, b, c, d, z, h0)


def selective_scan_step(x, dt, a_t, b, c, d, z, h):
    """Form (c): x, dt, z [batch, d_inner], b, c [batch, d_state], h
    [batch, d_state, d_inner] float32 -> (y [batch, d_inner] in x's dtype,
    gated; the new state)."""
    f32 = jnp.float32
    xf, dt = x.astype(f32), dt.astype(f32)
    h = (jnp.exp(dt[:, None, :] * a_t[None]) * h
         + (dt * xf)[:, None, :] * b.astype(f32)[:, :, None])
    y = jnp.sum(h * c.astype(f32)[:, :, None], axis=1) + d.astype(f32) * xf
    return (y * jax.nn.silu(z.astype(f32))).astype(x.dtype), h


def _scan_xla(x, dt, a_t, b, c, d, z, h0):
    """Form (a): `selective_scan_step` under a `lax.scan` over the rows."""
    def step(h, row):
        y, h = selective_scan_step(*row[:2], a_t, *row[2:4], d, row[4], h)
        return h, y

    by_row = lambda a: jnp.swapaxes(a, 0, 1)        # noqa: E731
    h, y = jax.lax.scan(step, h0.astype(jnp.float32),
                        tuple(map(by_row, (x, dt, b, c, z))))
    return by_row(y), h


def _scan_kernel(x_ref, dt_ref, z_ref, b_ref, c_ref, a_ref, d_ref, h0_ref,
                 y_ref, ht_ref, h_all, y_rows, *, block_rows, block_ch,
                 row_blocks):
    from jax.experimental import pallas as pl
    f32 = jnp.float32
    ti, ci = pl.program_id(1), pl.program_id(2)
    tiles = block_ch // LANES

    @pl.when(ti == 0)
    def _():
        h_all[ci] = h0_ref[0]

    a = a_ref[...]                                   # [d_state, block_ch]

    def group(g, hs):
        r0 = pl.multiple_of(g * GROUP, GROUP)
        xg = x_ref[0, pl.ds(r0, GROUP), :].astype(f32)       # [GROUP, ch]
        dtg = dt_ref[0, pl.ds(r0, GROUP), :]
        dxg = dtg * xg
        hs = list(hs)
        for i in range(GROUP):
            b_t = b_ref[0, r0 + i]                   # [d_state, LANES]
            c_t = c_ref[0, r0 + i]
            for j in range(tiles):
                at = slice(j * LANES, (j + 1) * LANES)
                h = (jnp.exp(dtg[i:i + 1, at] * a[:, at]) * hs[j]
                     + dxg[i:i + 1, at] * b_t)
                hs[j] = h
                y_rows[i:i + 1, at] = jnp.sum(h * c_t, axis=0,
                                              keepdims=True)
        zg = z_ref[0, pl.ds(r0, GROUP), :].astype(f32)
        y = (y_rows[...] + d_ref[...] * xg) * (zg * jax.nn.sigmoid(zg))
        y_ref[0, pl.ds(r0, GROUP), :] = y.astype(y_ref.dtype)
        return tuple(hs)

    h = h_all[ci]
    hs = jax.lax.fori_loop(
        0, block_rows // GROUP, group,
        tuple(h[:, j * LANES:(j + 1) * LANES] for j in range(tiles)))
    h = jnp.concatenate(hs, axis=1)
    h_all[ci] = h

    @pl.when(ti == row_blocks - 1)
    def _():
        ht_ref[0] = h


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssm_selective_scan(x, dt, a_t, b, c, d, z, h0, *, interpret=False):
    """Form (b), under the name the device trace reads."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    f32 = jnp.float32
    batch, rows, d_inner = x.shape
    d_state = a_t.shape[0]
    tb = scan_block_rows(rows, d_inner, d_state)
    cb = BLOCK_CHANNELS
    nt, nc = rows // tb, d_inner // cb
    # B_t[n] and C_t[n] along the lanes, once a row for every channel block
    spread = lambda a: jnp.broadcast_to(              # noqa: E731
        a.astype(f32)[..., None], (batch, rows, d_state, LANES))
    by_rows = pl.BlockSpec((1, tb, cb), lambda bi, ti, ci: (bi, ti, ci))
    spread_spec = pl.BlockSpec((1, tb, d_state, LANES),
                               lambda bi, ti, ci: (bi, ti, 0, 0))
    # the state's block comes in with a sequence's first block of rows and
    # goes out with its last: anywhere else the index stands still, and a
    # block whose index stands still is neither fetched nor written back
    h0_spec = pl.BlockSpec(
        (1, d_state, cb),
        lambda bi, ti, ci: (bi, 0, jnp.where(ti == 0, ci, 0)))
    ht_spec = pl.BlockSpec(
        (1, d_state, cb),
        lambda bi, ti, ci: (bi, 0, jnp.where(ti == nt - 1, ci, 0)))
    y, ht = pl.pallas_call(
        functools.partial(_scan_kernel, block_rows=tb, block_ch=cb,
                          row_blocks=nt),
        grid=(batch, nt, nc),
        in_specs=[by_rows, by_rows, by_rows, spread_spec, spread_spec,
                  pl.BlockSpec((d_state, cb), lambda bi, ti, ci: (0, ci)),
                  pl.BlockSpec((1, cb), lambda bi, ti, ci: (0, ci)),
                  h0_spec],
        out_specs=[by_rows, ht_spec],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(h0.shape, f32)],
        scratch_shapes=[pltpu.VMEM((nc, d_state, cb), f32),
                        pltpu.VMEM((GROUP, cb), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(x, dt.astype(f32), z, spread(b), spread(c), a_t.astype(f32),
      d.astype(f32).reshape(1, d_inner), h0.astype(f32))
    return y, ht
