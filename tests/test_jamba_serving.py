"""AI21-Jamba2-3B through `ServingEngine` (PR 47): the pool holds, a slot,
the attention layers' keys and values, the depthwise kernels' last three
inputs and the scans' [d_state, d_inner] float32 matrix a Mamba layer
(`attention.ConvKVCache`). Prefill then decode through pool and state
against the float32 reference's full forward
(`benchmark/reference/jamba.py`: no cache, no state carried):
log-probabilities, never tokens, 1e-4 in float32."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import jamba as reference
from megatron_tpu.config import MODEL_PRESETS, ServingConfig
from megatron_tpu.inference import Generator
from megatron_tpu.inference.generation import (SamplingParams, init_kv_caches,
                                               prefill_chunk)
from megatron_tpu.models import language_model as lm
from megatron_tpu.models.attention import ConvKVCache
from megatron_tpu.serving import SamplingOptions, ServingEngine, capabilities
from megatron_tpu.serving.kv_pool import (SlotKVPool, insert_prefill,
                                          slot_nbytes)

TOL = 1e-4
STD = 0.11          # tests/test_jamba.py says why


def _model(impl="dot"):
    cfg = dataclasses.replace(
        MODEL_PRESETS["jamba2-3b-tiny"](), compute_dtype="float32",
        attention_impl=impl, init_method_std=STD)
    return cfg, lm.model_init(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def model():
    return _model()


def _engine(cfg, params, start=True, **serving):
    gen = Generator(params, cfg, eos_id=-1, pad_id=0,
                    kv_cache_dtype=jnp.float32)
    base = dict(num_slots=3, max_queue=16, max_len=96, prefill_bucket=8,
                prefill_max_batch=2)
    return ServingEngine(gen, ServingConfig(**{**base, **serving})
                         .validate(cfg), start=start)


def _diff(req, params, cfg, new):
    tokens, _ = req.result(timeout=600)
    got = np.asarray(req.gen_logprobs, np.float64)
    want = np.asarray(reference.token_logprobs(
        params, jnp.asarray(tokens), cfg, tail=new), np.float64)
    assert got.shape == (new,)
    return np.abs(got - want)


def _check(req, params, cfg, new):
    diff = _diff(req, params, cfg, new)
    assert diff.max() < TOL, diff


@pytest.mark.parametrize("plen", [1, 3, 21])
def test_one_shot_prefill_with_padding_then_decode(model, plen):
    """A bucketed prefill (bucket 8: a prompt of 1 or 3 leaves a depthwise
    state that is part zeros; 21 has three padding rows behind it) and then
    16 tokens decoded through pool and state, beside an unrelated request."""
    cfg, params = model
    rng = np.random.default_rng(plen)
    with _engine(cfg, params) as eng:
        other = eng.submit(rng.integers(1, cfg.vocab_size, 9).tolist(), 20,
                           SamplingOptions(temperature=1.0), seed=3)
        req = eng.submit(rng.integers(1, cfg.vocab_size, plen).tolist(), 16,
                         SamplingOptions(temperature=0.0), seed=1)
        _check(req, params, cfg, 16)
        other.result(timeout=600)
        snap = eng.metrics.snapshot()
        assert eng._rope is None                      # no positions at all
    # 2 attention layers of k and v of one head of 16 a token; 26 Mamba
    # layers of 3 x 128 depthwise inputs and 16 x 128 float32 a slot
    assert snap["kv_bytes_per_token"] == 2 * 2 * 16 * 4
    assert snap["conv_state_bytes"] == 3 * 26 * 3 * 128 * 4
    assert snap["ssm_state_bytes"] == 3 * 26 * 16 * 128 * 4


def test_prefill_through_the_flash_kernels_offset_form():
    """The cell's own attention_impl: one kv head, so a prefill and every
    chunk attend the region through `flash_attention(q_offset=...)`."""
    cfg, params = _model(impl="flash")
    rng = np.random.default_rng(29)
    with _engine(cfg, params, prefill_chunk=16) as eng:
        req = eng.submit(rng.integers(1, cfg.vocab_size, 37).tolist(), 5,
                         SamplingOptions(temperature=0.0), seed=1)
        _check(req, params, cfg, 5)
        assert req.prefill_chunks == 3


def test_batched_prefill_of_unequal_lengths(model):
    """Prompts of 10 and 15 share one padded bucket of 16 (one `_prefill_fn`
    call of two rows): each row leaves both states at its OWN length."""
    cfg, params = model
    rng = np.random.default_rng(7)
    eng = _engine(cfg, params, start=False)
    reqs = [eng.submit(rng.integers(1, cfg.vocab_size, n).tolist(), 5,
                       SamplingOptions(temperature=0.0), seed=n)
            for n in (10, 15)]
    eng._thread.start()
    try:
        for r in reqs:
            _check(r, params, cfg, 5)
        assert eng._prefill_traces == 1
    finally:
        eng.close()


@pytest.mark.parametrize("chunk,chunks", [(8, 3), (16, 2)])
def test_a_prompt_over_several_chunks_carries_both_states(model, chunk,
                                                          chunks):
    """21 tokens in chunks of 8 (8 + 8 + 5 padded to 8) or 16 (16 + 5
    padded to 8): a continuation chunk starts from the depthwise inputs and
    the matrix the chunk before it left, attends the rows already written,
    and leaves the states at its own last real row."""
    cfg, params = model
    rng = np.random.default_rng(17)
    with _engine(cfg, params, prefill_chunk=chunk) as eng:
        req = eng.submit(rng.integers(1, cfg.vocab_size, 21).tolist(), 6,
                         SamplingOptions(temperature=0.0), seed=1)
        _check(req, params, cfg, 6)
        assert req.prefill_chunks == chunks


def test_a_slot_reused_after_a_longer_request(model):
    """One slot: a long request, then short ones in the same slot. Their
    states are their own: nothing of the slot's last tenant."""
    cfg, params = model
    rng = np.random.default_rng(11)
    with _engine(cfg, params, num_slots=1) as eng:
        first = eng.submit(rng.integers(1, cfg.vocab_size, 30).tolist(), 12,
                           SamplingOptions(temperature=1.0), seed=2)
        first.result(timeout=600)
        for n in (1, 5):
            req = eng.submit(rng.integers(1, cfg.vocab_size, n).tolist(), 4,
                             SamplingOptions(temperature=0.0), seed=n)
            _check(req, params, cfg, 4)


def test_prefill_chunk_leaves_the_states_at_the_last_real_row(model):
    """`generation.prefill_chunk` on a padded chunk: both states are the
    ones a chunk of the real rows alone leaves."""
    cfg, params = model
    tokens = np.random.default_rng(19).integers(1, cfg.vocab_size, 11)

    def run(padded):
        caches = init_kv_caches(cfg, 1, 32, dtype=jnp.float32)
        toks = np.zeros((1, padded), np.int32)
        toks[0, :5] = tokens[:5]
        caches, _ = prefill_chunk(params, jnp.asarray(toks), caches, cfg,
                                  rope=None, last_idx=4, next_offset=5)
        toks = np.full((1, padded), 7, np.int32)
        toks[0, :6] = tokens[5:]
        return prefill_chunk(params, jnp.asarray(toks), caches, cfg,
                             rope=None, last_idx=5, next_offset=11)
    (exact, last_a), (padded, last_b) = run(6), run(8)
    assert isinstance(exact, ConvKVCache) and exact.ssm.dtype == jnp.float32
    assert exact.ssm.shape == (26, 1, 16, 128)
    assert exact.conv.shape == (26, 1, 3, 128)
    for a, b in ((exact.conv, padded.conv), (exact.ssm, padded.ssm)):
        assert np.abs(np.asarray(a - b)).max() < 1e-6
        assert np.abs(np.asarray(a)).max() > 1e-3
    want = np.asarray(reference.logits(params, jnp.asarray(tokens), cfg))[-1]
    assert np.abs(np.asarray(last_a)[:cfg.vocab_size] - want).max() < TOL
    assert np.abs(np.asarray(last_b)[:cfg.vocab_size] - want).max() < TOL


def test_serial_generate_matches_reference(model):
    cfg, params = model
    gen = Generator(params, cfg, eos_id=-1, pad_id=0,
                    kv_cache_dtype=jnp.float32)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist() for n in (37, 21)]
    tokens, lengths, logprobs = gen.generate(
        prompts, 6, SamplingParams(temperature=0.0), seed=0)
    for i, p in enumerate(prompts):
        seq = tokens[i, :lengths[i]]
        want = np.asarray(reference.token_logprobs(
            params, jnp.asarray(seq), cfg, tail=6))
        assert np.abs(logprobs[i, len(p):lengths[i]] - want).max() < TOL


def test_insert_prefill_overwrites_both_states(model):
    cfg, _ = model
    pool = init_kv_caches(cfg, 3, 16, dtype=jnp.float32,
                          per_slot_offsets=True)
    pool = pool._replace(conv=jnp.ones_like(pool.conv),
                         ssm=jnp.ones_like(pool.ssm))
    sub = init_kv_caches(cfg, 1, 16, dtype=jnp.float32)
    sub = sub._replace(conv=jnp.full_like(sub.conv, 2.0),
                       ssm=jnp.full_like(sub.ssm, 3.0))
    out = insert_prefill(pool, sub, 1, 5)
    assert np.all(np.asarray(out.conv[:, 1]) == 2.0)
    assert np.all(np.asarray(out.ssm[:, 1]) == 3.0)
    assert np.all(np.asarray(out.conv[:, (0, 2)]) == 1.0)
    assert np.all(np.asarray(out.ssm[:, (0, 2)]) == 1.0)
    assert np.asarray(out.offset).tolist() == [[0, 5, 0]] * 2


def test_pool_byte_counts():
    """At the published widths: what `slot_nbytes` / `fit_num_slots` size a
    slot at is what the pool allocates, and the cell's numbers."""
    cfg = MODEL_PRESETS["jamba2-3b"]()
    shapes = jax.eval_shape(lambda: init_kv_caches(
        cfg, 32, 32768, dtype=jnp.bfloat16, per_slot_offsets=True))
    assert shapes.ssm.shape == (26, 32, 16, 5120)
    assert shapes.ssm.dtype == jnp.float32
    assert shapes.conv.shape == (26, 32, 3, 5120)
    assert shapes.k.shape == (2, 32, 32768, 128)
    nbytes = lambda a: int(np.prod(a.shape)) * a.dtype.itemsize  # noqa: E731
    assert nbytes(shapes.ssm) // 32 == 8_519_680
    assert (nbytes(shapes.ssm) + nbytes(shapes.conv)) // 32 == 9_318_400
    per_slot = sum(nbytes(getattr(shapes, f))
                   for f in ("k", "v", "conv", "ssm")) // 32
    assert slot_nbytes(cfg, 32768, jnp.bfloat16) == per_slot \
        == 32768 * 1024 + 9_318_400
    tiny = MODEL_PRESETS["jamba2-3b-tiny"]()
    pool = SlotKVPool(tiny, 4, 64, dtype=jnp.bfloat16)
    assert pool.conv_layers == 26
    assert pool.ssm_state_nbytes() == 4 * 26 * 16 * 128 * 4
    assert pool.conv_state_nbytes() == 4 * 26 * 3 * 128 * 2
    assert pool.bytes_per_slot() == slot_nbytes(tiny, 64, jnp.bfloat16)
    assert pool.bytes_per_token() == 2 * 2 * 16 * 2
    assert pool.full_nbytes() == 4 * 64 * pool.bytes_per_token()
    # a pool with no scan reports none (LFM2's)
    lfm2 = SlotKVPool(MODEL_PRESETS["lfm2-8b-a1b-tiny"](), 2, 32)
    assert lfm2.ssm_state_nbytes() == 0 and lfm2.caches.ssm is None


@pytest.mark.parametrize("name", sorted(capabilities.REFUSED["conv-state"]))
def test_serving_refusals_by_name(name):
    """The state row's fourteen refusals hold for a matrix state, and none
    is lifted; chunked prefill is served."""
    cfg = MODEL_PRESETS["jamba2-3b-tiny"]()
    assert capabilities.pool_kind(cfg, 64) == "conv-state"
    given = {
        "enable_prefix_cache": dict(enable_prefix_cache=True),
        "retained_slots": dict(retained_slots=1),
        "preemption": dict(preemption=True),
        "speculative_k": dict(speculative_k=2),
        "kv_block_size": dict(kv_block_size=16),
        "block_native_attn": dict(kv_block_size=16, block_native_attn=True),
        "serving_tp": dict(serving_tp=2), "prefill_tp": dict(prefill_tp=2),
        "decode_tp": dict(decode_tp=2), "serving_pp": dict(serving_pp=2),
        "disaggregate_prefill": dict(disaggregate_prefill=True),
        "host_kv_bytes": dict(host_kv_bytes=1 << 20),
        "adapter_slots": dict(adapter_slots=2),
        "kv_dtype int8": dict(kv_dtype="int8"),
    }[name]
    with pytest.raises(AssertionError, match="refused.*ROADMAP R6"):
        ServingConfig(num_slots=2, max_len=64, **given).validate(cfg)
    ServingConfig(num_slots=2, max_len=64, prefill_bucket=8,
                  prefill_max_batch=1, prefill_chunk=16).validate(cfg)


@pytest.mark.parametrize("fault", [
    "state_behind_the_padding", "chunk_starts_from_zeros", "bf16_state"])
def test_a_planted_fault_fails_the_comparison(model, monkeypatch, fault):
    """What the comparisons above can see: the same engine over a Mamba
    layer that forgets `live_rows` (a prefill of 21 in a bucket of 24 leaves
    the states behind the padding), that starts every continuation chunk
    from an empty state, or that keeps the matrix in bfloat16. The first two
    are off by a hundred times the tolerance or more, a rounding of the state
    to 8 bits of mantissa by twenty."""
    from megatron_tpu.models import mamba
    sound = mamba.mamba_apply

    def faulty(params, u, cfg, *, kv_cache=None, kind_layer=None):
        if kv_cache is None or u.shape[1] == 1 and fault != "bf16_state":
            return sound(params, u, cfg, kv_cache=kv_cache,
                         kind_layer=kind_layer)
        given = kv_cache
        if fault == "state_behind_the_padding":
            given = kv_cache._replace(live_rows=jnp.int32(
                ConvKVCache.NO_PADDING))
        elif fault == "chunk_starts_from_zeros":
            given = kv_cache._replace(ssm=jnp.zeros_like(kv_cache.ssm),
                                      conv=jnp.zeros_like(kv_cache.conv))
        out, new = sound(params, u, cfg, kv_cache=given,
                         kind_layer=kind_layer)
        if fault == "chunk_starts_from_zeros":
            # the other layers' states as they were
            layer = lambda a, b: jax.lax.dynamic_update_index_in_dim(  # noqa: E731
                a, jax.lax.dynamic_index_in_dim(b, kind_layer, 0, False),
                kind_layer, 0)
            new = new._replace(ssm=layer(kv_cache.ssm, new.ssm),
                               conv=layer(kv_cache.conv, new.conv))
        if fault == "bf16_state":
            new = new._replace(ssm=new.ssm.astype(jnp.bfloat16)
                               .astype(jnp.float32))
        return out, new._replace(live_rows=kv_cache.live_rows)
    monkeypatch.setattr(mamba, "mamba_apply", faulty)
    cfg, params = model
    rng = np.random.default_rng(21)
    chunked = dict(prefill_chunk=8) if fault == "chunk_starts_from_zeros" \
        else {}
    with _engine(cfg, params, **chunked) as eng:
        req = eng.submit(rng.integers(1, cfg.vocab_size, 21).tolist(), 6,
                         SamplingOptions(temperature=0.0), seed=1)
        diff = _diff(req, params, cfg, 6)
    assert diff.max() > (10 if fault == "bf16_state" else 100) * TOL, diff
