"""The gated delta rule with ONE decay a head of a Gated DeltaNet mixer (PR
60): `ops/kda_chunk.py`'s scalar-decay forms against the definition and the
channel form, and `models/gated_delta.py`'s mixer against the reference's
layer (`benchmark/reference/qwen3_next.py`). `kda_recurrent` with g
broadcast over the channels and q, k repeated to the value heads is the
arbiter: the scalar-decay chunk kernel (interpret mode here;
`tests/test_tpu_compile.py` compiles it for the chip), the channel-form
kernel fed the broadcast, the one-row step and a chunked run with a carried
state agree with it at 1e-4 in float32, at decays near none and at g = -8 a
row."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import qwen3_next as reference
from megatron_tpu.config import MODEL_PRESETS
from megatron_tpu.inference.generation import init_kv_caches
from megatron_tpu.models.gated_delta import gdn_apply, gdn_init
from megatron_tpu.ops.kda_chunk import (gdn_chunk, gdn_recurrent, gdn_step,
                                        kda_chunk, kda_recurrent)

TOL = 1e-4
DECAYS = {"typical": {}, "near_0": dict(scale=1e-3),
          "minus_8": dict(const=-8.0), "mixed_to_minus_40": dict(scale=20.0)}
HEADS = {"2_under_4": (2, 4), "4_under_4": (4, 4), "1_under_4": (1, 4),
         "1_under_3": (1, 3),
         # four value heads a grid step over two, four and ONE key head;
         # a ratio of three under two heads a step: one head a step
         "4_under_8": (4, 8), "8_under_8": (8, 8), "2_under_8": (2, 8),
         "2_under_6": (2, 6)}


def _rows(seed, batch=2, rows=96, hk=2, hv=4, d=16, scale=1.0, const=None):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(keys[0], (batch, rows, hk, d))) / d ** 0.5
    k = unit(jax.random.normal(keys[1], (batch, rows, hk, d)))
    v = jax.random.normal(keys[2], (batch, rows, hv, d))
    g = -scale * jax.nn.softplus(
        jax.random.normal(keys[3], (batch, rows, hv)))
    if const is not None:
        g = jnp.full_like(g, const)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (batch, rows, hv)))
    h0 = jax.random.normal(keys[5], (batch, hv, d, d))
    return q, k, v, g, beta, h0


def _definition(q, k, v, g, beta, h0):
    """`kda_recurrent` with the decay the same in all channels of a head and
    key head j // ratio's q and k under value head j."""
    ratio = v.shape[2] // q.shape[2]
    wide = jnp.broadcast_to(g[..., None], g.shape + (q.shape[-1],))
    return (jnp.repeat(q, ratio, axis=2), jnp.repeat(k, ratio, axis=2), v,
            wide, beta, h0)


def _close(a, b, tol=TOL):
    return float(jnp.abs(a - b).max()) < tol


@pytest.mark.parametrize("heads", sorted(HEADS))
@pytest.mark.parametrize("decay", sorted(DECAYS))
def test_scalar_form_is_the_definition_is_the_channel_form(decay, heads):
    """96 rows under chunks of 64: a whole chunk and a padded tail. The
    channel form is given g broadcast and q, k repeated: what the scalar
    form saves."""
    hk, hv = HEADS[heads]
    args = _rows(1, hk=hk, hv=hv, **DECAYS[decay])
    want_o, want_s = kda_recurrent(*_definition(*args))
    got_o, got_s = gdn_chunk(*args, interpret=True)
    wide_o, wide_s = kda_chunk(*_definition(*args), interpret=True)
    assert bool(jnp.isfinite(got_o).all())
    assert float(jnp.abs(want_o).max()) > 0.1
    assert _close(got_o, want_o) and _close(got_s, want_s)
    assert _close(wide_o, want_o) and _close(wide_s, want_s)
    # off the chip `gdn_chunk` IS the definition
    o, s = gdn_chunk(*args, use_kernel=True)
    assert bool((o == want_o).all()) and bool((s == want_s).all())


@pytest.mark.parametrize("chunk", [16, 32, 128])
def test_smaller_chunks_and_a_carried_state(chunk):
    """Two calls of 64 and 32 rows, the second entered with the state the
    first left, are one call of 96 (under a chunk of 128 neither call is a
    whole chunk)."""
    q, k, v, g, beta, h0 = _rows(2)
    want_o, want_s = gdn_recurrent(q, k, v, g, beta, h0)
    cut = lambda t, a, b: t[:, a:b]                              # noqa: E731
    form = lambda *a: gdn_chunk(*a, chunk=chunk, interpret=True)  # noqa
    o1, s1 = form(*(cut(t, 0, 64) for t in (q, k, v, g, beta)), h0)
    o2, s2 = form(*(cut(t, 64, 96) for t in (q, k, v, g, beta)), s1)
    assert _close(jnp.concatenate([o1, o2], axis=1), want_o)
    assert _close(s2, want_s)
    for bad in (48, 24):
        with pytest.raises(AssertionError, match="whole sub-chunks"):
            gdn_chunk(q, k, v, g, beta, h0, chunk=bad, interpret=True)


@pytest.mark.parametrize("decay", ["minus_8", "mixed_to_minus_40"])
def test_every_row_of_a_128_row_chunk(decay):
    """One whole chunk of 128 rows under decays whose running sums reach
    the thousands: every row finite and the definition's (the sums are made
    in the kernel, a few roundings from the sequential ones: PR 61)."""
    args = _rows(6, rows=128, hk=2, hv=8, **DECAYS[decay])
    want_o, want_s = kda_recurrent(*_definition(*args))
    got_o, got_s = gdn_chunk(*args, chunk=128, interpret=True)
    assert bool(jnp.isfinite(got_o).all()) and bool(jnp.isfinite(got_s).all())
    assert float(jnp.abs(got_o - want_o).max(axis=(0, 2, 3)).max()) < TOL
    assert _close(got_s, want_s)


def test_one_row_step_is_the_recurrence():
    q, k, v, g, beta, h0 = _rows(3, rows=5)
    want_o, want_s = gdn_recurrent(q, k, v, g, beta, h0)
    state, outs = h0, []
    for t in range(5):
        o, state = gdn_step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t],
                            state)
        outs.append(o)
    assert _close(jnp.stack(outs, axis=1), want_o, 1e-6)
    assert _close(state, want_s, 1e-6)


@pytest.mark.parametrize("form", ["kernel", "kernel_128", "recurrence",
                                  "step"])
def test_padding_rows_leave_the_state_bit_for_bit(form):
    """beta = 0 and g = 0: the state behind 40 real rows and 24 such rows
    is the state behind the 40, to the bit."""
    q, k, v, g, beta, h0 = _rows(4, rows=64)
    real = (jnp.arange(64) < 40)[None, :, None]
    g, beta = jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0)
    cut = lambda t: t[:, :40]                                    # noqa: E731
    if form.startswith("kernel"):
        chunk = 128 if form == "kernel_128" else 32
        _, want = gdn_chunk(*(cut(t) for t in (q, k, v, g, beta)), h0,
                            chunk=chunk, interpret=True)
        _, got = gdn_chunk(q, k, v, g, beta, h0, chunk=chunk, interpret=True)
    elif form == "recurrence":
        _, want = gdn_recurrent(*(cut(t) for t in (q, k, v, g, beta)), h0)
        _, got = gdn_recurrent(q, k, v, g, beta, h0)
    else:
        _, want = gdn_recurrent(*(cut(t) for t in (q, k, v, g, beta)), h0)
        _, got = gdn_step(q[:, 50], k[:, 50], v[:, 50], g[:, 50],
                          beta[:, 50], want)
    assert bool((got == want).all())


def _mixer(**over):
    cfg = dataclasses.replace(MODEL_PRESETS["qwen3-next-tiny"](),
                              compute_dtype="float32", init_method_std=0.11,
                              **over)
    params = gdn_init(jax.random.PRNGKey(0), cfg)
    # a trained norm's scale is not 1
    params["norm"]["scale"] = 1.0 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(1), params["norm"]["scale"].shape)
    return cfg, params


def test_mixer_is_the_references_layer():
    cfg, params = _mixer()
    assert params["in_proj"].shape == (64, 2 * 32 + 2 * 64)
    assert params["conv"].shape == (4, 128)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 37, cfg.hidden_size))
    got, _ = gdn_apply(params, x, cfg)
    for row, out in zip(x, got):
        want, _, _ = reference.gated_delta(params, row, cfg)
        assert float(jnp.abs(want).max()) > 0.05
        assert _close(out, want)


@pytest.mark.parametrize("fault,least", [
    ("decay_after", 100), ("decay_mean", 100), ("key_head", 100),
    ("state_bf16", 3)])
def test_a_planted_fault_fails_by_orders(fault, least):
    cfg, params = _mixer()
    x = jax.random.normal(jax.random.PRNGKey(2), (37, cfg.hidden_size))
    want, _, _ = reference.gated_delta(params, x, cfg)
    off, _, _ = reference.gated_delta(params, x, cfg,
                                      faults=frozenset({fault}))
    got, _ = gdn_apply(params, x[None], cfg)
    assert _close(got[0], want)
    assert float(jnp.abs(got[0] - off).max()) > least * TOL


def test_mixer_through_a_cache_in_padded_chunks_then_steps():
    """A prefill of 21 rows in a bucket of 32, a chunk of 9 in a bucket of
    16, then 7 single rows, each from the depthwise inputs and the state the
    call before left at its last REAL row: the mixer with no cache over the
    37 rows. And what the cache holds is the reference's."""
    cfg, params = _mixer()
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 37, cfg.hidden_size))
    want, _ = gdn_apply(params, x, cfg)
    cache = init_kv_caches(cfg, 1, 64, dtype=jnp.float32)
    outs = []
    for a, b, bucket in ((0, 21, 32), (21, 30, 16)):
        rows = jnp.pad(x[:, a:b], ((0, 0), (0, bucket - (b - a)), (0, 0)))
        out, cache = gdn_apply(params, rows, cfg,
                               kv_cache=cache._replace(
                                   live_rows=jnp.int32(b - a)),
                               kind_layer=2)
        outs.append(out[:, :b - a])
    for t in range(30, 37):
        out, cache = gdn_apply(params, x[:, t:t + 1], cfg, kv_cache=cache,
                               kind_layer=2)
        outs.append(out)
    assert _close(jnp.concatenate(outs, axis=1), want)
    _, states, inputs = reference.gated_delta(params, x[0], cfg)
    assert _close(cache.ssm[2, 0], states[0])
    assert _close(cache.conv[2, 0], inputs[0])
    # the other layers' parts were not touched
    assert float(jnp.abs(cache.ssm[jnp.array([0, 1, 3, 4, 5])]).max()) == 0.0


def test_gradient_of_the_no_cache_path_against_finite_differences():
    """`jax.grad` through the mixer with no cache (the recurrence) against
    central differences along three random directions of each parameter."""
    cfg, params = _mixer()
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 11, cfg.hidden_size))
    probe = jax.random.normal(jax.random.PRNGKey(5), (1, 11, cfg.hidden_size))

    def value(p):
        with jax.default_matmul_precision("highest"):
            return jnp.sum(gdn_apply(p, x, cfg)[0] * probe)
    grads = jax.grad(value)(params)
    flat, tree = jax.tree.flatten(params)
    for i, leaf in enumerate(flat):
        for seed in range(3):
            way = jax.random.normal(jax.random.PRNGKey(10 * i + seed),
                                    leaf.shape)
            way = way / jnp.linalg.norm(way)
            eps = 3e-2
            moved = [jax.tree.unflatten(
                tree, flat[:i] + [leaf + s * eps * way] + flat[i + 1:])
                for s in (1, -1)]
            want = float(value(moved[0]) - value(moved[1])) / (2 * eps)
            got = float(jnp.sum(jax.tree.leaves(grads)[i] * way))
            assert abs(got - want) < 2e-2 * max(abs(want), 1.0), (i, got, want)
    assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(grads))
