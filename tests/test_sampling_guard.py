"""The sampler sorts the vocabulary only in a step where a live request
filters (`inference/sampling.py::_filter_rows`, one `lax.cond` over both
filters; PR 32).

- row for row `sample_batched` stays bit-identical to the serial `sample()`
  and to the filters called with no guard round them, whatever mix of rows
  the grid holds;
- a grid in which no row needs a filter takes the branch without sorts, and
  the program holds no sort anywhere else;
- in an engine the branch with the sorts is live only while a request that
  filters is: `sample_filter_steps` counts those steps, and a freed slot's
  knobs are parked at the disabled values.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.config import ModelConfig, ServingConfig
from megatron_tpu.inference import Generator, SamplingParams
from megatron_tpu.inference import sampling
from megatron_tpu.models import language_model as lm
from megatron_tpu.serving import SamplingOptions, ServingEngine

V, PADDED = 83, 96

# (temperature, top_k, top_p): every kind of row a grid can hold
ROWS = {
    "plain": (1.0, 0, 0.0),
    "warm": (0.7, 0, 1.0),
    "top_k": (0.9, 5, 0.0),
    "top_p": (1.1, 0, 0.8),
    "both": (0.8, 7, 0.6),
    "greedy_t0": (0.0, 0, 0.0),
    "greedy_k1": (1.0, 1, 0.0),
    "greedy_t0_with_top_p": (0.0, 0, 0.9),
    "k_over_vocab": (1.0, PADDED + 5, 0.0),
}
FILTERING = {"top_k", "top_p", "both", "k_over_vocab"}
GRIDS = {
    "mixed": list(ROWS),
    "all_unfiltered": ["plain", "warm", "plain", "warm"],
    "all_greedy": ["greedy_t0", "greedy_k1", "greedy_t0_with_top_p"],
    "greedy_and_unfiltered": ["plain", "greedy_t0_with_top_p", "warm",
                              "greedy_k1"],
    "one_filters_among_defaults": ["plain", "plain", "top_p", "plain"],
}


def knobs(names):
    t, k, p = zip(*(ROWS[n] for n in names))
    return (jnp.asarray(t, jnp.float32), jnp.asarray(k, jnp.int32),
            jnp.asarray(p, jnp.float32))


def inputs(b, seed=0):
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(seed), (b, PADDED))
    rngs = jax.vmap(jax.random.PRNGKey)(jnp.arange(100, 100 + b))
    return rngs, logits


def unguarded(monkeypatch):
    """The parent's sampler: both filters on every step."""
    monkeypatch.setattr(
        sampling, "_filter_rows",
        lambda x, temperature, top_k, top_p: sampling._top_p_filter_rows(
            sampling._top_k_filter_rows(x, top_k), top_p))


def extras(kind, b, seed=3):
    """`banned` / `mask` arguments of the grid, by name."""
    out = {}
    if kind in ("banned", "banned_and_mask"):
        out["banned"] = jnp.where(jnp.arange(b) % 2 == 0,
                                  jnp.arange(b) + 2, -1).astype(jnp.int32)
    if kind in ("mask", "banned_and_mask"):
        m = jax.random.bernoulli(jax.random.PRNGKey(seed), 0.6, (b, PADDED))
        out["mask"] = m.at[0].set(True).at[b - 1].set(False)
    return out


@pytest.mark.parametrize("grid", list(GRIDS))
def test_needs_filter_only_for_rows_a_filter_changes(grid):
    t, k, p = knobs(GRIDS[grid])
    want = [n in FILTERING for n in GRIDS[grid]]
    assert sampling.rows_need_filter(t, k, p).tolist() == want
    # the engine asks its numpy mirrors the same question
    assert sampling.rows_need_filter(
        np.asarray(t), np.asarray(k), np.asarray(p)).tolist() == want


@pytest.mark.parametrize("grid", list(GRIDS))
def test_rows_equal_the_serial_sampler(grid):
    names = GRIDS[grid]
    t, k, p = knobs(names)
    rngs, logits = inputs(len(names))
    got = jax.jit(lambda *a: sampling.sample_batched(
        a[0], a[1], temperature=a[2], top_k=a[3], top_p=a[4],
        vocab_size=V))(rngs, logits, t, k, p)
    for i, name in enumerate(names):
        temp, top_k, top_p = ROWS[name]
        want = sampling.sample(rngs[i], logits[i:i + 1], top_k=top_k,
                               top_p=top_p, temperature=temp, vocab_size=V)
        assert int(got[i]) == int(want[0]), (name, i)


@pytest.mark.parametrize("extra", ["none", "banned", "mask",
                                   "banned_and_mask"])
@pytest.mark.parametrize("grid", list(GRIDS))
def test_sample_batched_equals_the_unguarded_filters(grid, extra,
                                                     monkeypatch):
    names = GRIDS[grid]
    t, k, p = knobs(names)
    rngs, logits = inputs(len(names), seed=1)
    kw = dict(temperature=t, top_k=k, top_p=p, vocab_size=V,
              **extras(extra, len(names)))
    got = sampling.sample_batched(rngs, logits, **kw)
    unguarded(monkeypatch)
    want = sampling.sample_batched(rngs, logits, **kw)
    assert got.tolist() == want.tolist()
    if "mask" in kw:
        assert int(got[-1]) == -1           # the dead row's sentinel


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("grid", list(GRIDS))
def test_verify_draft_probs_equals_the_unguarded_filters(grid, masked,
                                                         monkeypatch):
    names = GRIDS[grid]
    b, w = len(names), 3
    t, k, p = knobs(names)
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(2), (b, w, PADDED))
    drafts = jax.random.randint(jax.random.PRNGKey(4), (b, w), 0, V)
    mask = (jax.random.bernoulli(jax.random.PRNGKey(5), 0.7, (b, w, PADDED))
            if masked else None)
    kw = dict(temperature=t, top_k=k, top_p=p, vocab_size=V, mask=mask)
    probs, targets = sampling.verify_draft_probs(logits, drafts, **kw)
    unguarded(monkeypatch)
    want_probs, want_targets = sampling.verify_draft_probs(logits, drafts,
                                                           **kw)
    assert np.array_equal(targets, want_targets)
    # a greedy row accepts on its target alone: its probs are read by
    # nobody, and are the only ones the guard may leave unfiltered
    read = ~np.asarray((t == 0.0) | (k == 1))
    # (NaN where the mask leaves none of a row's top-k: equal on both sides)
    np.testing.assert_array_equal(np.asarray(probs)[read],
                                  np.asarray(want_probs)[read])


def _sorts(jaxpr):
    """(sorts outside every cond, [sorts in each cond branch])."""
    outside, branches = 0, []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            outside += 1
        subs = [j for v in eqn.params.values()
                for j in (v if isinstance(v, (list, tuple)) else [v])
                if hasattr(j, "jaxpr") or hasattr(j, "eqns")]
        for sub in subs:
            inner = getattr(sub, "jaxpr", sub)
            o, b = _sorts(inner)
            if eqn.primitive.name == "cond":
                branches.append(o + sum(b))
            else:
                outside += o
                branches += b
    return outside, branches


@pytest.mark.parametrize("fn", ["sample_batched", "verify_draft_probs"])
def test_no_sort_outside_the_one_guarded_branch(fn):
    t, k, p = knobs(GRIDS["mixed"])
    b = len(GRIDS["mixed"])
    rngs, logits = inputs(b)
    if fn == "sample_batched":
        jaxpr = jax.make_jaxpr(lambda *a: sampling.sample_batched(
            a[0], a[1], temperature=a[2], top_k=a[3], top_p=a[4],
            vocab_size=V, banned=jnp.full((b,), -1, jnp.int32),
            mask=jnp.ones((b, PADDED), bool)))(rngs, logits, t, k, p)
    else:
        jaxpr = jax.make_jaxpr(lambda *a: sampling.verify_draft_probs(
            a[0], a[1], temperature=a[2], top_k=a[3], top_p=a[4],
            vocab_size=V))(jnp.stack([logits, logits], 1),
                           jnp.zeros((b, 2), jnp.int32), t, k, p)
    outside, branches = _sorts(jaxpr.jaxpr)
    assert outside == 0
    assert sorted(branches) == [0, 2]      # top-k's sort and top-p's


def test_the_sort_counter_sees_an_unguarded_program(monkeypatch):
    """`_sorts` is not blind: the parent's sampler shows its two sorts."""
    unguarded(monkeypatch)
    t, k, p = knobs(GRIDS["mixed"])
    rngs, logits = inputs(len(GRIDS["mixed"]))
    jaxpr = jax.make_jaxpr(lambda *a: sampling.sample_batched(
        a[0], a[1], temperature=a[2], top_k=a[3], top_p=a[4]))(
            rngs, logits, t, k, p)
    assert _sorts(jaxpr.jaxpr) == (2, [])


# -- in an engine ----------------------------------------------------------

@pytest.fixture(scope="module")
def gen():
    cfg = ModelConfig(num_layers=2, hidden_size=64, num_attention_heads=4,
                      num_kv_heads=2, vocab_size=96, seq_length=64,
                      make_vocab_size_divisible_by=32,
                      compute_dtype="float32").derived()
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    return Generator(params, cfg, eos_id=0, pad_id=0)


def oracle(gen, prompt, n, opts, seed):
    sp = SamplingParams(temperature=opts.temperature, top_k=opts.top_k,
                        top_p=opts.top_p)
    toks, lens, _ = gen.generate([prompt], n, sampling=sp, seed=seed)
    return toks[0, :lens[0]].tolist()


@pytest.mark.parametrize("interval", [1, 3])
def test_default_traffic_never_takes_the_filter_branch(gen, interval):
    plain = SamplingOptions(temperature=1.0)
    greedy = SamplingOptions(temperature=0.0, top_p=0.9)
    with ServingEngine(gen, ServingConfig(
            num_slots=3, max_queue=16, max_len=64,
            decode_sync_interval=interval)) as eng:
        reqs = [eng.submit([5 + i, 17, 3], 6, plain if i % 2 else greedy,
                           seed=i) for i in range(5)]
        for i, r in enumerate(reqs):
            assert r.result(timeout=300)[0] == oracle(
                gen, [5 + i, 17, 3], 6, plain if i % 2 else greedy, i)
        snap = eng.metrics.snapshot()
        assert snap["decode_steps"] > 0
        assert snap["sample_filter_steps"] == 0
        assert eng._decode_traces == 1


@pytest.mark.parametrize("opts", [SamplingOptions(temperature=1.0,
                                                  top_p=0.9),
                                  SamplingOptions(temperature=0.8,
                                                  top_k=4)],
                         ids=["top_p", "top_k"])
def test_the_branch_is_live_only_while_a_filtering_request_is(gen, opts):
    plain = SamplingOptions(temperature=1.0)
    with ServingEngine(gen, ServingConfig(num_slots=2, max_queue=16,
                                          max_len=64)) as eng:
        # alone in the grid, so every step of its life filters ...
        short = eng.submit([9, 10, 11], 5, opts, seed=7)
        assert short.result(timeout=300)[0] == oracle(
            gen, [9, 10, 11], 5, opts, 7)
        lived = eng.metrics.snapshot()
        assert 1 <= lived["sample_filter_steps"] <= lived["decode_steps"]
        # ... and once it is evicted its slot's knobs read disabled
        assert not eng._active.any()
        assert eng._top_ks.tolist() == [0, 0]
        assert eng._top_ps.tolist() == [0.0, 0.0]
        # defaults that follow, in that slot and the other, never sort
        later = [eng.submit([21 + i, 22], 12, plain, seed=40 + i)
                 for i in range(3)]
        for i, r in enumerate(later):
            assert r.result(timeout=300)[0] == oracle(
                gen, [21 + i, 22], 12, plain, 40 + i)
        snap = eng.metrics.snapshot()
        assert snap["decode_steps"] >= lived["decode_steps"] + 12
        assert snap["sample_filter_steps"] == lived["sample_filter_steps"]
        assert not eng._filter_live
        assert eng._decode_traces == 1


def test_one_filtering_request_among_defaults(gen):
    """A `top_p=0.9` request decoding beside defaults: everyone's tokens
    are the serial oracle's, and the steps that sort are at most the steps
    it lived through."""
    plain = SamplingOptions(temperature=1.0)
    nucleus = SamplingOptions(temperature=1.0, top_p=0.9)
    with ServingEngine(gen, ServingConfig(num_slots=3, max_queue=16,
                                          max_len=64)) as eng:
        long_a = eng.submit([5, 6, 7], 30, plain, seed=1)
        short = eng.submit([9, 10], 4, nucleus, seed=2)
        long_b = eng.submit([11, 12, 13, 14], 30, plain, seed=3)
        assert short.result(timeout=300)[0] == oracle(
            gen, [9, 10], 4, nucleus, 2)
        assert long_a.result(timeout=300)[0] == oracle(
            gen, [5, 6, 7], 30, plain, 1)
        assert long_b.result(timeout=300)[0] == oracle(
            gen, [11, 12, 13, 14], 30, plain, 3)
        snap = eng.metrics.snapshot()
        assert len(long_a.generated) == 30        # premise: no early EOS
        assert 1 <= snap["sample_filter_steps"] <= 4
        assert snap["sample_filter_steps"] < snap["decode_steps"]
        assert eng._decode_traces == 1


def test_parking_disables_a_slots_filters(gen):
    """What `_evict`, `_preempt` and the supervisor's restart call for a
    freed slot; the restart parks the whole grid."""
    with ServingEngine(gen, ServingConfig(num_slots=2, max_queue=8,
                                          max_len=64), start=False) as eng:
        eng._top_ks[:] = 5
        eng._top_ps[:] = 0.9
        eng._park_knobs(1)
        assert eng._top_ks.tolist() == [5, 0]
        assert eng._top_ps.tolist() == [pytest.approx(0.9), 0.0]
        assert sampling.rows_need_filter(
            eng._temps, eng._top_ks, eng._top_ps).tolist() == [True, False]
        eng._restart_session("test")
        assert not sampling.rows_need_filter(
            eng._temps, eng._top_ks, eng._top_ps).any()
        assert eng._sampling_dirty
