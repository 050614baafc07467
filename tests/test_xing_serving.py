"""Xing4.0-29B-A4B through `ServingEngine` (PR 41): `test_joyai_serving.py`'s
cases on the tiny preset, whose residual is four streams and whose positions
are YaRN's. The pool, the scheduler and the engine are JoyAI's: a
bucket-padded prefill in the expanded form, continuation chunks and a verify
window in the absorbed form, decode steps through the latent pool beside
other live slots, each against the float32 reference's full forward
(`benchmark/reference/xing4.py`). Log-probabilities, never tokens.

The tolerance is JoyAI's 5e-4 made 1e-4: float32 on the CPU through five
layers, where the engine's head, sampler and cache differ from the
reference's one forward by the order of float32 sums alone (read: 1e-5 to
4e-5)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import xing4 as reference
from megatron_tpu.config import ServingConfig
from megatron_tpu.inference import Generator
from megatron_tpu.models import mla
from megatron_tpu.models.mla import LatentKVCache
from megatron_tpu.serving import SamplingOptions, ServingEngine
from tests.test_xing import seeded, tiny


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    params = seeded(cfg)
    params.pop("mtp")            # a server does not load the module
    return cfg, params


def _logprobs(eng, prompt, n_new):
    req = eng.submit(prompt, n_new, SamplingOptions(temperature=0.0), seed=11)
    tokens, _ = req.result(timeout=600)
    return req, tokens, np.asarray(req.gen_logprobs, np.float64)


@pytest.mark.parametrize("how", ["plain", "chunked_prefill", "prefix_hit",
                                 "speculative", "to_the_last_position",
                                 "chunked_in_key_blocks",
                                 "key_blocks_to_the_last_position"])
def test_engine_prefill_and_decode_match_reference(model, how, monkeypatch):
    """A prompt prefilled in a padded bucket (37 tokens in 48: by chunks of
    16, the second and third continuing the sequence's own latent rows in the
    absorbed form), then decoded through the latent cache one token at a time
    beside an unrelated request. `to_the_last_position`: prompt + output =
    `max_len`, which the cell's mix can reach (15,872 + 512 = 16,384).
    `key_blocks`: the blocks cut small, so that a chunk of 16 rows runs four
    blocks of queries, each over the key blocks of 8 positions it can see
    (PR 59); to the last position, over a region of 49 that 8 does not
    divide."""
    cfg, params = model
    if "key_blocks" in how:
        monkeypatch.setattr(mla, "ABSORBED_Q_BLOCK", 4)
        monkeypatch.setattr(mla, "ABSORBED_KEY_BLOCK", 8)
    gen = Generator(params, cfg, eos_id=-1, pad_id=0,
                    kv_cache_dtype=jnp.float32)
    serving = dict(num_slots=3, max_queue=8, max_len=96, prefill_bucket=16)
    serving.update({"chunked_prefill": dict(prefill_chunk=16),
                    "chunked_in_key_blocks": dict(prefill_chunk=16),
                    "to_the_last_position": dict(prefill_chunk=16,
                                                 max_len=49),
                    "key_blocks_to_the_last_position": dict(
                        prefill_chunk=16, max_len=49),
                    "prefix_hit": dict(enable_prefix_cache=True),
                    "speculative": dict(speculative_k=2)}.get(how, {}))
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, cfg.vocab_size, size=37).tolist()
    if how == "speculative":     # a prompt the n-gram drafter can draft from
        prompt = (prompt[:6] * 7)[:37]
    other = rng.integers(1, cfg.vocab_size, size=21).tolist()
    with ServingEngine(gen, ServingConfig(**serving).validate(cfg)) as eng:
        assert isinstance(eng.pool.caches, LatentKVCache)
        # YaRN's tables, cut to the engine's positions: a program closes
        # over `max_len` rows of them, not the published (stretched) context
        assert eng._rope.cos.shape[0] == eng.max_len < len(gen.rope.cos)
        noise = eng.submit(other, 20, SamplingOptions(temperature=1.0),
                           seed=3)
        if how == "prefix_hit":
            first = prompt[:32] + rng.integers(1, 512, size=4).tolist()
            _logprobs(eng, first, 2)
        req, tokens, got = _logprobs(eng, prompt, 12)
        noise.result(timeout=600)
        snap = eng.metrics.snapshot()
    if how == "prefix_hit":
        assert snap["prefix_hits"] >= 1 and req.prefix_len >= 16
    if "chunked" in how or "to_the_last_position" in how:
        assert snap["prefill_chunks"] >= 3 and req.prefill_chunks == 3
        read, held = (snap["latent_chunk_blocks_read"],
                      snap["latent_chunk_blocks_held"])
        assert 0 < read < held if "key_blocks" in how else 0 < read == held
    if how == "speculative":
        assert snap["spec_rounds"] > 0 and snap["draft_tokens"] > 0
    assert len(got) == 12 and tokens[:37] == prompt
    want = np.asarray(reference.token_logprobs(
        params, jnp.asarray(tokens, jnp.int32), cfg), np.float64)[36:]
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-4)
    # the pool's own count, in the metrics' snapshot: 5 layers x (32 + 16)
    assert snap["kv_bytes_per_token"] == 5 * 48 * 4


def test_a_planted_fault_fails_the_served_comparison(model):
    """The same comparison, a chunked prefill and decode steps, against a
    reference with ten Sinkhorn rounds for twenty: over ten times the
    tolerance (one round for twenty: a thousand times, tests/test_xing.py)."""
    cfg, params = model
    gen = Generator(params, cfg, eos_id=-1, pad_id=0,
                    kv_cache_dtype=jnp.float32)
    serving = ServingConfig(num_slots=2, max_queue=4, max_len=64,
                            prefill_bucket=16, prefill_chunk=16).validate(cfg)
    prompt = np.random.default_rng(5).integers(1, 512, size=37).tolist()
    with ServingEngine(gen, serving) as eng:
        _, tokens, got = _logprobs(eng, prompt, 8)
    faulty = np.asarray(reference.token_logprobs(
        params, jnp.asarray(tokens, jnp.int32), cfg,
        faults={"sinkhorn_10"}), np.float64)[36:]
    assert np.abs(got - faulty).max() > 10 * 1e-4
