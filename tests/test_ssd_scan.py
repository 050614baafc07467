"""The chunked scan of a Mamba-2 mixer (`ops/ssd_scan.py`, PR 52): the
`einsum` form, the Pallas kernel in interpret mode and the one-step update
against the sequential recurrence written as a loop over tokens. Float32,
small sizes, heads != groups."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.ops.ssd_scan import (_ssd_chunk_scan, _ssd_einsum,
                                       ssd_block_heads, ssd_scan, ssd_step)

TOL = 2e-5
B, H, P, G, N, Q = 2, 8, 8, 2, 16, 16


def _inputs(rows, seed=0, batch=B):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        x=jax.random.normal(k[0], (batch, rows, H, P)),
        dt=0.5 * jax.nn.softplus(jax.random.normal(k[1], (batch, rows, H))),
        a=-jnp.exp(jax.random.normal(k[2], (H,))),
        b=jax.random.normal(k[3], (batch, rows, G, N)),
        c=jax.random.normal(k[4], (batch, rows, G, N)),
        d=jax.random.normal(k[5], (H,)),
        h0=jax.random.normal(k[6], (batch, H, P, N)))


def _token_loop(x, dt, a, b, c, d, h0):
    """The recurrence as written: a token at a time, head h reads group
    h // (H / G)."""
    per = H // G
    ys, h = [], h0
    for t in range(x.shape[1]):
        bt = jnp.repeat(b[:, t], per, axis=1)               # [B, H, N]
        ct = jnp.repeat(c[:, t], per, axis=1)
        h = jnp.exp(dt[:, t] * a)[..., None, None] * h \
            + (dt[:, t, :, None] * x[:, t])[..., None] * bt[:, :, None, :]
        ys.append(jnp.einsum("bhpn,bhn->bhp", h, ct)
                  + d[None, :, None] * x[:, t])
    return jnp.stack(ys, axis=1), h


def _close(got, want):
    for g, w in zip(got, want):
        assert np.abs(np.asarray(w)).max() > 0.1
        assert np.abs(np.asarray(g) - np.asarray(w)).max() < TOL


@pytest.mark.parametrize("rows", [16, 48, 37, 5])
def test_chunked_einsum_is_the_sequential_recurrence(rows):
    """Whole chunks, and rows that are not: the form pads them with rows of
    no step and cuts their y off."""
    args = _inputs(rows, seed=rows)
    _close(ssd_scan(**args, chunk=Q), _token_loop(**args))
    assert ssd_block_heads(rows, H, P, G, N, Q, aligned=False) == \
        (None if rows % Q else H // G)


def test_one_step_is_one_token_of_the_loop():
    args = _inputs(1, seed=3)
    y, h = ssd_step(args["x"][:, 0], args["dt"][:, 0], args["a"],
                    args["b"][:, 0], args["c"][:, 0], args["d"], args["h0"])
    want_y, want_h = _token_loop(**args)
    _close((y, h), (want_y[:, 0], want_h))


@pytest.mark.parametrize("interpret", [False, True], ids=["einsum", "kernel"])
def test_state_carried_over_two_calls_is_one_call(interpret):
    args = _inputs(64, seed=5)
    whole = ssd_scan(**args, chunk=Q, interpret=interpret)
    cut = lambda t, at: {k: (v[:, at] if k in ("x", "dt", "b", "c") else v)
                         for k, v in t.items()}
    y1, h1 = ssd_scan(**cut(args, slice(0, 32)), chunk=Q, interpret=interpret)
    y2, h2 = ssd_scan(**{**cut(args, slice(32, 64)), "h0": h1}, chunk=Q,
                      interpret=interpret)
    _close((jnp.concatenate([y1, y2], axis=1), h2), whole)


@pytest.mark.parametrize("interpret", [False, True], ids=["einsum", "kernel"])
def test_padding_rows_move_no_state(interpret):
    """Rows whose step size is 0 (a bucket's padding, as the mixer marks
    it) leave the state where the last real row left it, whatever they
    hold; each sequence at its own length."""
    args = _inputs(48, seed=7)
    live = jnp.asarray([21, 40])
    real = jnp.arange(48)[None, :] < live[:, None]
    args["dt"] = jnp.where(real[..., None], args["dt"], 0.0)
    _, h = ssd_scan(**args, chunk=Q, interpret=interpret)
    for i, n in enumerate(np.asarray(live)):
        one = {k: (v[i:i + 1, :n] if k in ("x", "dt", "b", "c")
                   else v[i:i + 1] if k == "h0" else v)
               for k, v in args.items()}
        _, want = _token_loop(**one)
        assert np.abs(np.asarray(h[i]) - np.asarray(want[0])).max() < TOL


@pytest.mark.parametrize("rows", [16, 64])
def test_kernel_in_interpret_mode_is_the_einsum_form(rows):
    args = _inputs(rows, seed=11)
    got = _ssd_chunk_scan(*args.values(), chunk=Q, interpret=True)
    _close(got, _ssd_einsum(*args.values(), chunk=Q))
    _close(got, _token_loop(**args))


def test_heads_of_one_group_differ_and_groups_differ():
    """A head reads its OWN group's B and C: swapping the groups' rows
    changes every head's output, and two heads of one group differ by their
    decay and their x alone."""
    args = _inputs(32, seed=13, batch=1)
    y, _ = ssd_scan(**args, chunk=Q)
    swapped = {**args, "b": args["b"][:, :, ::-1], "c": args["c"][:, :, ::-1]}
    y2, _ = ssd_scan(**swapped, chunk=Q)
    assert np.abs(np.asarray(y - y2)).max() > 0.1
    same = {**args, "x": jnp.broadcast_to(args["x"][:, :, :1], args["x"].shape),
            "a": jnp.full((H,), -0.5), "d": jnp.ones((H,)),
            "dt": jnp.broadcast_to(args["dt"][:, :, :1], args["dt"].shape),
            "h0": jnp.zeros_like(args["h0"])}
    y3 = np.asarray(ssd_scan(**same, chunk=Q)[0])
    per = H // G
    assert np.abs(y3[:, :, 0] - y3[:, :, per - 1]).max() < TOL
    assert np.abs(y3[:, :, 0] - y3[:, :, per]).max() > 0.1


def test_the_einsum_form_is_differentiable():
    """What training and scoring take: finite gradients in every input, and
    the loop's own."""
    args = _inputs(24, seed=17, batch=1)
    keys = ("x", "dt", "a", "b", "c", "d", "h0")

    def loss(fn, *vals):
        y, h = fn(**dict(zip(keys, vals)))
        return jnp.sum(y * y) + jnp.sum(h)
    vals = tuple(args[k] for k in keys)
    got = jax.grad(lambda *v: loss(
        lambda **kw: ssd_scan(**kw, chunk=Q, use_kernel=False), *v),
        argnums=tuple(range(7)))(*vals)
    want = jax.grad(lambda *v: loss(_token_loop, *v),
                    argnums=tuple(range(7)))(*vals)
    for g, w in zip(got, want):
        scale = max(np.abs(np.asarray(w)).max(), 1.0)
        assert np.all(np.isfinite(np.asarray(g)))
        assert np.abs(np.asarray(g - w)).max() < 1e-4 * scale


def test_the_shape_rule_on_the_chip():
    """Whole chunks of whole lane tiles, a group's channels whole lane
    tiles: Nemotron-3's widths pass, a bucket of 100 rows or a state of 16
    does not (the `einsum` form takes those)."""
    assert ssd_block_heads(2048, 128, 64, 8, 128, 128) == 16
    assert ssd_block_heads(512, 128, 64, 8, 128, 128) == 16
    assert ssd_block_heads(100, 128, 64, 8, 128, 128) is None
    assert ssd_block_heads(2048, 128, 64, 8, 16, 128) is None
    assert ssd_block_heads(2048, 128, 64, 8, 128, 64) is None
    assert ssd_block_heads(2048, 12, 8, 4, 128, 128) is None
