"""The selective scan (ops/selective_scan.py, PR 47): the `lax.scan` form
against a token-by-token loop written out in numpy, the Pallas kernel in
interpret mode against the `lax.scan` form, the state carried from one call
to the next, padding rows (a step size of 0) that move no state, the
one-step update, and the gradient of the form the training path
differentiates. Float32; 1e-5 on values of magnitude ~1 to ~20. Every planted
fault fails by orders of magnitude."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.ops import selective_scan as ss

TOL = 1e-5


def _draw(seed, batch, rows, d_inner, d_state=16, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (batch, rows, d_inner)).astype(dtype)
    z = jax.random.normal(ks[1], (batch, rows, d_inner)).astype(dtype)
    # step sizes as the initialiser leaves them, 0.001 to 0.1 and a tail
    dt = jax.nn.softplus(jax.random.normal(ks[2], (batch, rows, d_inner)) - 3)
    a_t = -jnp.broadcast_to(jnp.arange(1, d_state + 1, dtype=jnp.float32)[
        :, None], (d_state, d_inner))
    b = jax.random.normal(ks[3], (batch, rows, d_state))
    c = jax.random.normal(ks[4], (batch, rows, d_state))
    d = jnp.linspace(0.5, 1.5, d_inner)
    h0 = jax.random.normal(ks[5], (batch, d_state, d_inner))
    return x, dt, a_t, b, c, d, z, h0


def _token_loop(x, dt, a_t, b, c, d, z, h0):
    """The equations a token at a time, numpy float64."""
    x, dt, a_t, b, c, d, z, h = (np.asarray(a, np.float64)
                                 for a in (x, dt, a_t, b, c, d, z, h0))
    y = np.zeros_like(x)
    for t in range(x.shape[1]):
        h = np.exp(dt[:, t, None, :] * a_t[None]) * h \
            + (dt[:, t] * x[:, t])[:, None, :] * b[:, t, :, None]
        y[:, t] = (np.einsum("bnd,bn->bd", h, c[:, t]) + d * x[:, t]) \
            * (z[:, t] / (1.0 + np.exp(-z[:, t])))
    return y, h


def test_the_scan_matches_a_token_by_token_loop():
    args = _draw(0, 2, 37, 24)
    y, h = ss.selective_scan(*args)
    want_y, want_h = _token_loop(*args)
    assert np.abs(want_y).max() > 1.0 and np.abs(want_h).max() > 0.5
    assert np.abs(np.asarray(y) - want_y).max() < TOL
    assert np.abs(np.asarray(h) - want_h).max() < TOL


@pytest.mark.parametrize("batch,rows,d_inner", [(1, 128, 512), (2, 256, 1024)])
def test_the_kernel_in_interpret_mode_matches_the_scan(batch, rows, d_inner):
    """One block of rows and of channels, then two of each and two
    sequences: the state crosses from a block of rows to the next in VMEM,
    and each channel block's comes in and goes out once."""
    args = _draw(1, batch, rows, d_inner)
    assert ss.scan_block_rows(rows, d_inner, 16) == 128
    y, h = ss.selective_scan(*args, interpret=True)
    want_y, want_h = ss._scan_xla(*args)
    assert np.abs(np.asarray(y - want_y)).max() < TOL
    assert np.abs(np.asarray(h - want_h)).max() < TOL
    loop_y, loop_h = _token_loop(*args)
    assert np.abs(np.asarray(y) - loop_y).max() < 1e-4
    assert np.abs(np.asarray(h) - loop_h).max() < 1e-4


def test_the_kernel_takes_bfloat16_rows_and_keeps_a_float32_state():
    args = _draw(2, 1, 128, 512, dtype=jnp.bfloat16)
    y, h = ss.selective_scan(*args, interpret=True)
    want_y, want_h = ss._scan_xla(*args)
    assert y.dtype == jnp.bfloat16 and h.dtype == jnp.float32
    assert np.abs(np.asarray(h - want_h)).max() < TOL
    assert np.abs(np.asarray(y, np.float32)
                  - np.asarray(want_y, np.float32)).max() < 0.13  # one ulp of 16


@pytest.mark.parametrize("rows,d_inner,d_state,takes", [
    (2048, 5120, 16, True), (512, 5120, 16, True), (128, 512, 8, True),
    (100, 5120, 16, False), (128, 640, 16, False), (128, 512, 4, False),
    (1, 5120, 16, False)])
def test_the_kernels_shape_rule(rows, d_inner, d_state, takes):
    assert (ss.scan_block_rows(rows, d_inner, d_state) is not None) == takes


def test_a_shape_the_kernel_does_not_take_runs_the_scan():
    args = _draw(3, 1, 100, 24)
    y, h = ss.selective_scan(*args, use_kernel=True, interpret=True)
    want_y, _ = _token_loop(*args)
    assert np.abs(np.asarray(y) - want_y).max() < TOL


@pytest.mark.parametrize("form", ["scan", "kernel"])
def test_one_scan_is_two_scans_with_the_state_carried(form):
    kw = dict(interpret=True) if form == "kernel" else {}
    rows, d_inner = (256, 512) if form == "kernel" else (30, 24)
    x, dt, a_t, b, c, d, z, h0 = _draw(4, 2, rows, d_inner)
    y, h = ss.selective_scan(x, dt, a_t, b, c, d, z, h0, **kw)
    cut = rows // 2
    first = lambda a: a[:, :cut]                    # noqa: E731
    second = lambda a: a[:, cut:]                   # noqa: E731
    y1, h1 = ss.selective_scan(first(x), first(dt), a_t, first(b), first(c),
                               d, first(z), h0, **kw)
    y2, h2 = ss.selective_scan(second(x), second(dt), a_t, second(b),
                               second(c), d, second(z), h1, **kw)
    assert np.abs(np.asarray(jnp.concatenate([y1, y2], 1) - y)).max() < TOL
    assert np.abs(np.asarray(h2 - h)).max() < TOL
    # planted: the second half started from zeros (its first rows show it;
    # a state fades over a hundred rows of these step sizes)
    y_zero, _ = ss.selective_scan(second(x), second(dt), a_t, second(b),
                                  second(c), d, second(z), None, **kw)
    assert np.abs(np.asarray(y_zero - y2))[:, :4].max() > 1e-2


@pytest.mark.parametrize("form", ["scan", "kernel"])
def test_rows_of_step_size_zero_leave_the_state_untouched(form):
    """What models/mamba.py does to a bucket's padding rows: the state
    after 100 real rows and 28 rows of dt = 0 is the state after the 100."""
    kw = dict(interpret=True) if form == "kernel" else {}
    x, dt, a_t, b, c, d, z, h0 = _draw(5, 1, 128, 512)
    live = 100
    masked = jnp.where(jnp.arange(128)[None, :, None] < live, dt, 0.0)
    _, h = ss.selective_scan(x, masked, a_t, b, c, d, z, h0, **kw)
    _, want = ss._scan_xla(x[:, :live], dt[:, :live], a_t, b[:, :live],
                           c[:, :live], d, z[:, :live], h0)
    assert np.abs(np.asarray(h - want)).max() < TOL
    # planted: the padding rows keep their step size
    _, behind = ss.selective_scan(x, dt, a_t, b, c, d, z, h0, **kw)
    assert np.abs(np.asarray(behind - want)).max() > 1e-2


def test_the_one_step_update_is_a_scan_of_one_row():
    x, dt, a_t, b, c, d, z, h0 = _draw(6, 3, 1, 24)
    y, h = ss.selective_scan_step(x[:, 0], dt[:, 0], a_t, b[:, 0], c[:, 0],
                                  d, z[:, 0], h0)
    want_y, want_h = _token_loop(x, dt, a_t, b, c, d, z, h0)
    assert np.abs(np.asarray(y) - want_y[:, 0]).max() < TOL
    assert np.abs(np.asarray(h) - want_h).max() < TOL


def test_the_gradient_of_the_scan_matches_the_reference_recurrence():
    """`jax.grad` through form (a), which the training path differentiates,
    against `jax.grad` through the reference's own token scan
    (benchmark/reference/jamba.py writes the recurrence [state, channel] a
    token, no batch)."""
    x, dt, a_t, b, c, d, z, _ = _draw(7, 1, 19, 24)

    def ours(x, dt, b, c):
        y, h = ss.selective_scan(x, dt, a_t, b, c, d, z, None)
        return jnp.sum(y ** 2) + jnp.sum(h)

    def reference(x, dt, b, c):
        def token(h, row):
            x_t, dt_t, b_t, c_t = row
            h = jnp.exp(dt_t[None, :] * a_t) * h \
                + (dt_t * x_t)[None, :] * b_t[:, None]
            return h, jnp.sum(c_t[:, None] * h, axis=0)
        h, y = jax.lax.scan(token, jnp.zeros_like(a_t),
                            (x[0], dt[0], b[0], c[0]))
        y = (y + d * x[0]) * jax.nn.silu(z[0])
        return jnp.sum(y ** 2) + jnp.sum(h)
    got = jax.grad(ours, argnums=(0, 1, 2, 3))(x, dt, b, c)
    want = jax.grad(reference, argnums=(0, 1, 2, 3))(x, dt, b, c)
    for g, w in zip(got, want):
        assert np.abs(np.asarray(w)).max() > 0.1
        assert np.abs(np.asarray(g - w)).max() < 1e-4 * np.abs(
            np.asarray(w)).max()


@pytest.mark.parametrize("fault", ["bf16_state", "no_decay", "no_skip"])
def test_a_planted_fault_fails_by_orders_of_magnitude(fault):
    x, dt, a_t, b, c, d, z, h0 = _draw(8, 1, 64, 24)
    want_y, want_h = _token_loop(x, dt, a_t, b, c, d, z, h0)
    if fault == "bf16_state":
        def step(h, row):
            y, h = ss.selective_scan_step(*row[:2], a_t, *row[2:4], d,
                                          row[4], h.astype(jnp.float32))
            return h.astype(jnp.bfloat16), y
        by_row = lambda a: jnp.swapaxes(a, 0, 1)    # noqa: E731
        h, y = jax.lax.scan(step, h0.astype(jnp.bfloat16),
                            tuple(map(by_row, (x, dt, b, c, z))))
        y = by_row(y)
    elif fault == "no_decay":
        y, h = ss.selective_scan(x, dt, jnp.zeros_like(a_t), b, c, d, z, h0)
    else:
        y, h = ss.selective_scan(x, dt, a_t, b, c, jnp.zeros_like(d), z, h0)
    assert np.abs(np.asarray(y, np.float64) - want_y).max() > 100 * TOL
