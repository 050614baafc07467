"""The gated delta rule of a Kimi Delta Attention mixer (PR 58):
`ops/kda_chunk.py`'s three forms against one another and `models/kda.py`'s
mixer against the reference's layer (`benchmark/reference/kimi_linear.py`).
The per-token recurrence `kda_recurrent` is the arbiter: the chunk kernel
(interpret mode here; `tests/test_tpu_compile.py` compiles it for the chip),
the one-row step and a chunked run with a carried state agree with it at
1e-4 in float32, at decays near none (g near 0) and at g = -8 a row, where
e^-G would pass float32 after eleven rows."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import kimi_linear as reference
from megatron_tpu.config import MODEL_PRESETS
from megatron_tpu.inference.generation import init_kv_caches
from megatron_tpu.models.kda import kda_apply, kda_init
from megatron_tpu.ops.kda_chunk import (CHUNK, HEADS_A_STEP, SUB,
                                        _running_sums, kda_block_heads,
                                        kda_chunk, kda_recurrent, kda_step)

TOL = 1e-4
DECAYS = {"typical": {}, "near_0": dict(scale=1e-3), "minus_8": dict(const=-8.0),
          "mixed_to_minus_40": dict(scale=20.0)}


def _rows(seed, batch=2, rows=96, heads=4, d=16, scale=1.0, const=None):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(keys[0], (batch, rows, heads, d))) / d ** 0.5
    k = unit(jax.random.normal(keys[1], (batch, rows, heads, d)))
    v = jax.random.normal(keys[2], (batch, rows, heads, d))
    g = -scale * jax.nn.softplus(
        jax.random.normal(keys[3], (batch, rows, heads, d)))
    if const is not None:
        g = jnp.full_like(g, const)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (batch, rows, heads)))
    h0 = jax.random.normal(keys[5], (batch, heads, d, d))
    return q, k, v, g, beta, h0


def _close(a, b, tol=TOL):
    return float(jnp.abs(a - b).max()) < tol


@pytest.mark.parametrize("chunk", [16, 32, 64, 128])
@pytest.mark.parametrize("decay", sorted(DECAYS))
def test_chunk_kernel_is_the_recurrence(decay, chunk):
    """96 rows: whole chunks of 16 and 32, a padded tail under 64, not one
    whole chunk of 128."""
    args = _rows(1, **DECAYS[decay])
    want_o, want_s = kda_recurrent(*args)
    got_o, got_s = kda_chunk(*args, chunk=chunk, interpret=True)
    assert bool(jnp.isfinite(got_o).all())
    assert float(jnp.abs(want_o).max()) > 0.1
    assert _close(got_o, want_o) and _close(got_s, want_s)


@pytest.mark.parametrize("decay", sorted(DECAYS))
def test_chunked_run_carries_its_state(decay):
    """Two calls of 64 and 32 rows, the second entered with the state the
    first left, are one call of 96: through the kernel and through the
    recurrence."""
    q, k, v, g, beta, h0 = _rows(2, **DECAYS[decay])
    want_o, want_s = kda_recurrent(q, k, v, g, beta, h0)
    for form in (lambda *a: kda_chunk(*a, chunk=32, interpret=True),
                 kda_recurrent):
        cut = lambda t, a, b: t[:, a:b]                          # noqa: E731
        o1, s1 = form(*(cut(t, 0, 64) for t in (q, k, v, g, beta)), h0)
        o2, s2 = form(*(cut(t, 64, 96) for t in (q, k, v, g, beta)), s1)
        assert _close(jnp.concatenate([o1, o2], axis=1), want_o)
        assert _close(s2, want_s)


@pytest.mark.parametrize("chunk", [16, 64, 128])
def test_running_sums_in_the_kernel_are_cumsum(chunk):
    """The kernel makes a chunk's running sums itself (PR 61): over two
    chunks of a grid they are `jnp.cumsum` inside each chunk, starting
    again at the boundary, at sums of a few thousand as at sums near 0."""
    from jax.experimental import pallas as pl

    def kernel(g_ref, out_ref):
        out_ref[...] = _running_sums(g_ref[...])
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    g = -jax.nn.softplus(jax.random.normal(keys[0], (2 * chunk, 256))) \
        * jnp.exp(4 * jax.random.normal(keys[1], (1, 256)))
    block = pl.BlockSpec((chunk, 256), lambda i: (i, 0))
    got = pl.pallas_call(kernel, grid=(2,), in_specs=[block],
                         out_specs=block, out_shape=g, interpret=True)(g)
    want = jnp.cumsum(g.reshape(2, chunk, 256), axis=1).reshape(g.shape)
    assert float(jnp.abs(want).max()) > 1e3
    assert bool((jnp.abs(got - want) <= 2e-6 * jnp.abs(want)).all())
    # the row behind the boundary holds its own g alone
    assert bool((got[chunk] == g[chunk]).all())


@pytest.mark.parametrize("decay", ["minus_8", "mixed_to_minus_40"])
def test_every_row_of_a_128_row_chunk(decay):
    """One whole chunk of 128 rows (eight sub-chunks, three levels of
    halves between them) under decays that pass float32 as e^-G within a
    dozen rows: every row finite and the recurrence's, none of the levels'
    exponents ever above 0."""
    args = _rows(6, rows=128, **DECAYS[decay])
    want_o, want_s = kda_recurrent(*args)
    got_o, got_s = kda_chunk(*args, chunk=128, interpret=True)
    assert bool(jnp.isfinite(got_o).all()) and bool(jnp.isfinite(got_s).all())
    assert float(jnp.abs(got_o - want_o).max(axis=(0, 2, 3)).max()) < TOL
    assert float(jnp.abs(want_o).min(axis=(0, 2, 3)).max()) > 0
    assert _close(got_s, want_s)


@pytest.mark.parametrize("heads,a_step", [(3, 1), (6, 2), (8, 4), (12, 4)])
def test_heads_a_grid_step(heads, a_step):
    """Four heads a grid step where four divide the heads, two where two
    do, one under an odd count: the same rows' results whatever the step
    takes."""
    assert kda_block_heads(heads, 128, 128) == a_step
    assert kda_block_heads(heads, 16, 16, aligned=False) == a_step
    args = _rows(8, batch=1, rows=80, heads=heads)
    want_o, want_s = kda_recurrent(*args)
    got_o, got_s = kda_chunk(*args, chunk=32, interpret=True)
    assert _close(got_o, want_o) and _close(got_s, want_s)


def test_one_row_step_is_the_recurrence():
    q, k, v, g, beta, h0 = _rows(3, rows=5)
    want_o, want_s = kda_recurrent(q, k, v, g, beta, h0)
    state, outs = h0, []
    for t in range(5):
        o, state = kda_step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t],
                            state)
        outs.append(o)
    assert _close(jnp.stack(outs, axis=1), want_o, 1e-6)
    assert _close(state, want_s, 1e-6)


@pytest.mark.parametrize("form", ["kernel", "kernel_128", "recurrence",
                                  "step"])
def test_padding_rows_leave_the_state_bit_for_bit(form):
    """beta = 0 and g = 0: the rule's step is (I - 0) Diag(1) S, and the
    state behind 40 real rows and 24 such rows is the state behind the 40,
    to the bit (no masked copy of the state is needed)."""
    q, k, v, g, beta, h0 = _rows(4, rows=64)
    real = (jnp.arange(64) < 40)[None, :, None]
    g = jnp.where(real[..., None], g, 0.0)
    beta = jnp.where(real, beta, 0.0)
    cut = lambda t: t[:, :40]                                    # noqa: E731
    if form.startswith("kernel"):
        # 40 rows is two sub-chunks and a half: the kernel pads them itself
        # (in chunks of 32: the padding rows fill a chunk's tail; of 128:
        # real and padding rows share ONE chunk, four heads a grid step)
        chunk = 128 if form == "kernel_128" else 32
        _, want = kda_chunk(*(cut(t) for t in (q, k, v, g, beta)), h0,
                            chunk=chunk, interpret=True)
        _, got = kda_chunk(q, k, v, g, beta, h0, chunk=chunk, interpret=True)
    elif form == "recurrence":
        _, want = kda_recurrent(*(cut(t) for t in (q, k, v, g, beta)), h0)
        _, got = kda_recurrent(q, k, v, g, beta, h0)
    else:
        _, want = kda_recurrent(*(cut(t) for t in (q, k, v, g, beta)), h0)
        _, got = kda_step(q[:, 50], k[:, 50], v[:, 50], g[:, 50],
                          beta[:, 50], want)
    assert bool((got == want).all())


def test_the_kernels_shape_rule():
    assert kda_block_heads(32, 128, 128) == HEADS_A_STEP == 4
    assert kda_block_heads(3, 128, 128) == 1
    assert kda_block_heads(4, 16, 16) is None            # narrow heads
    assert kda_block_heads(4, 16, 16, aligned=False) == 4
    assert kda_block_heads(2, 16, 16, aligned=False) == 2
    assert SUB == 16 and CHUNK == 64
    # where the rule does not hold the recurrence runs
    args = _rows(5, rows=48)
    o, s = kda_chunk(*args, use_kernel=True)
    want_o, want_s = kda_recurrent(*args)
    assert bool((o == want_o).all()) and bool((s == want_s).all())
    # a chunk the kernel cannot take is refused, not sent to the recurrence
    for chunk in (48, 24):      # 3 sub-chunks; no whole sub-chunk
        with pytest.raises(AssertionError, match="whole sub-chunks"):
            kda_chunk(*args, chunk=chunk, interpret=True)


def _mixer(**over):
    cfg = dataclasses.replace(MODEL_PRESETS["kimi-linear-tiny"](),
                              compute_dtype="float32", init_method_std=0.11,
                              **over)
    params = kda_init(jax.random.PRNGKey(0), cfg)
    # a trained model's output gate has a bias; the initialiser's is zero
    params["g_bias"] = 0.3 * jax.random.normal(
        jax.random.PRNGKey(1), params["g_bias"].shape)
    return cfg, params


def test_mixer_is_the_references_layer():
    cfg, params = _mixer()
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 37, cfg.hidden_size))
    got, _ = kda_apply(params, x, cfg)
    for row, out in zip(x, got):
        want, _, _ = reference.kda(params, row, cfg)
        assert float(jnp.abs(want).max()) > 0.05
        assert _close(out, want)


@pytest.mark.parametrize("fault,least", [("decay_after", 100), ("decay", 100),
                                         ("sums_bf16", 10),
                                         ("state_bf16", 3)])
def test_a_planted_fault_fails_by_orders(fault, least):
    """The decay applied after the update and not before (or left out)
    moves the layer's output by a hundred tolerances; sums or a state in
    bfloat16 by several."""
    cfg, params = _mixer()
    x = jax.random.normal(jax.random.PRNGKey(2), (37, cfg.hidden_size))
    want, _, _ = reference.kda(params, x, cfg)
    off, _, _ = reference.kda(params, x, cfg, faults=frozenset({fault}))
    got, _ = kda_apply(params, x[None], cfg)
    assert _close(got[0], want)
    assert float(jnp.abs(got[0] - off).max()) > least * TOL


def test_mixer_through_a_cache_in_padded_chunks_then_steps():
    """A prefill of 21 rows in a bucket of 32, a chunk of 9 in a bucket of
    16, then 7 single rows, each from the depthwise inputs and the state the
    call before left at its last REAL row: the mixer with no cache over the
    37 rows. And what the cache holds is the reference's."""
    cfg, params = _mixer()
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 37, cfg.hidden_size))
    want, _ = kda_apply(params, x, cfg)
    cache = init_kv_caches(cfg, 1, 64, dtype=jnp.float32)
    outs = []
    for a, b, bucket in ((0, 21, 32), (21, 30, 16)):
        rows = jnp.pad(x[:, a:b], ((0, 0), (0, bucket - (b - a)), (0, 0)))
        out, cache = kda_apply(params, rows, cfg,
                               kv_cache=cache._replace(
                                   live_rows=jnp.int32(b - a)),
                               kind_layer=2)
        outs.append(out[:, :b - a])
    for t in range(30, 37):
        out, cache = kda_apply(params, x[:, t:t + 1], cfg, kv_cache=cache,
                               kind_layer=2)
        outs.append(out)
    assert _close(jnp.concatenate(outs, axis=1), want)
    _, states, inputs = reference.kda(params, x[0], cfg)
    assert _close(cache.ssm[2, 0], states[0])
    assert _close(cache.conv[2, 0], inputs[0])
    # the other layers' parts were not touched
    assert float(jnp.abs(cache.ssm[jnp.array([0, 1, 3, 4, 5])]).max()) == 0.0
