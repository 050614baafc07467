"""JoyAI-LLM-Flash through `ServingEngine` (PR 31): the latent pool, a
bucket-padded prefill in the expanded form, decode steps in the absorbed form
beside other live slots, and prefix cache, chunked prefill and speculation
over the latent cache, each against the float32 reference's full forward
(`benchmark/reference/joyai.py`). Log-probabilities, never tokens."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import joyai as reference
from megatron_tpu.config import MODEL_PRESETS, ModelConfig, ServingConfig
from megatron_tpu.inference import Generator
from megatron_tpu.models import language_model as lm
from megatron_tpu.models import mla
from megatron_tpu.models.mla import LatentKVCache
from megatron_tpu.serving import SamplingOptions, ServingEngine
from megatron_tpu.serving.kv_pool import SlotKVPool, slot_nbytes


@pytest.fixture(scope="module")
def model():
    cfg = dataclasses.replace(MODEL_PRESETS["joyai-llm-flash-tiny"](),
                              compute_dtype="float32")
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    mlp = params["transformer"]["moe"]["mlp"]
    mlp["e_score_correction_bias"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(5), mlp["e_score_correction_bias"].shape)
    params["embedding"]["word_embeddings"] *= 50.0
    params.pop("mtp")            # a server does not load the module
    return cfg, params


def _logprobs(eng, prompt, n_new):
    req = eng.submit(prompt, n_new, SamplingOptions(temperature=0.0), seed=11)
    tokens, _ = req.result(timeout=600)
    return req, tokens, np.asarray(req.gen_logprobs, np.float64)


@pytest.mark.parametrize("how", ["plain", "chunked_prefill", "prefix_hit",
                                 "speculative"])
def test_engine_prefill_and_decode_match_reference(model, how):
    """A prompt prefilled in a padded bucket (37 tokens in 48), then decoded
    through the latent cache one token at a time beside an unrelated
    request. Every prefill's head runs on each row's last real position
    alone (tests/test_prefill_head_rows.py)."""
    cfg, params = model
    gen = Generator(params, cfg, eos_id=-1, pad_id=0,
                    kv_cache_dtype=jnp.float32)
    serving = dict(num_slots=3, max_queue=8, max_len=96, prefill_bucket=16)
    serving.update({"chunked_prefill": dict(prefill_chunk=16),
                    "prefix_hit": dict(enable_prefix_cache=True),
                    "speculative": dict(speculative_k=2)}.get(how, {}))
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, cfg.vocab_size, size=37).tolist()
    if how == "speculative":     # a prompt the n-gram drafter can draft from
        prompt = (prompt[:6] * 7)[:37]
    other = rng.integers(1, cfg.vocab_size, size=21).tolist()
    with ServingEngine(gen, ServingConfig(**serving).validate(cfg)) as eng:
        assert isinstance(eng.pool.caches, LatentKVCache)
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
    if "chunked" in how:
        assert snap["prefill_chunks"] >= 3
    if how == "speculative":
        assert snap["spec_rounds"] > 0 and snap["draft_tokens"] > 0
    assert len(got) == 12 and tokens[:37] == prompt
    want = np.asarray(reference.token_logprobs(
        params, jnp.asarray(tokens, jnp.int32), cfg), np.float64)[36:]
    np.testing.assert_allclose(got, want, rtol=0, atol=5e-4)
    # the pool's own count, in the metrics' snapshot
    assert snap["kv_bytes_per_token"] == 4 * 40 * 4
    assert snap["kv_pool_bytes"] == 4 * 3 * 96 * 40 * 4


@pytest.mark.parametrize("how, want", [
    # 37 tokens by chunks of 16 over 96 positions, 4 MLA layers, blocks of 4
    # queries and 8 keys: the chunk at 16 has query blocks ending at 19, 23,
    # 27, 31 (3, 3, 4, 4 key blocks), the chunk at 32 (5 rows padded to 16)
    # at 35, 39, 43, 47 (5, 5, 6, 6), of 12 a region
    ("key_blocks", (4 * (14 + 22), 4 * 2 * 4 * 12)),
    # the blocks as they are: a chunk of 16 rows is taken at once over the
    # whole region, one key block (96 < 1,024), twice
    ("taken_at_once", (4 * 2, 4 * 2)),
    ("unchunked", (0, 0)),
    ("no_latent_rows", (0, 0)),
])
def test_latent_chunk_blocks_counted_by_the_programs_rule(model, monkeypatch,
                                                          how, want):
    """`latent_chunk_blocks_read` / `_held`: what a continuation chunk's
    absorbed attention reads of its sequence's region against the whole of
    it, from `mla.absorbed_key_blocks`, the rule the program loops by."""
    cfg, params = model
    if how == "no_latent_rows":
        cfg = ModelConfig(num_layers=2, hidden_size=64,
                          num_attention_heads=4, vocab_size=96,
                          seq_length=96, make_vocab_size_divisible_by=32,
                          compute_dtype="float32").derived()
        params = lm.model_init(jax.random.PRNGKey(0), cfg)
    if how == "key_blocks":
        monkeypatch.setattr(mla, "ABSORBED_Q_BLOCK", 4)
        monkeypatch.setattr(mla, "ABSORBED_KEY_BLOCK", 8)
    gen = Generator(params, cfg, eos_id=-1, pad_id=0,
                    kv_cache_dtype=jnp.float32)
    serving = dict(num_slots=2, max_queue=8, max_len=96, prefill_bucket=16,
                   prefill_chunk=None if how == "unchunked" else 16)
    prompt = np.random.default_rng(5).integers(1, 90, size=37).tolist()
    with ServingEngine(gen, ServingConfig(**serving).validate(cfg)) as eng:
        req, tokens, got = _logprobs(eng, prompt, 3)
        snap = eng.metrics.snapshot()
    assert req.prefill_chunks == (1 if how == "unchunked" else 3)
    assert (snap["latent_chunk_blocks_read"],
            snap["latent_chunk_blocks_held"]) == want
    if cfg.mla:
        ref = np.asarray(reference.token_logprobs(
            params, jnp.asarray(tokens, jnp.int32), cfg), np.float64)[36:]
        np.testing.assert_allclose(got, ref, rtol=0, atol=5e-4)


@pytest.mark.parametrize("name, want", [
    ("joyai-llm-flash-tiny", 4 * (32 + 8) * 2),       # layers x row x bf16
    ("joyai-llm-flash", 40 * 576 * 2),
    ("falcon-tiny", 2 * 2 * 1 * 64 * 2),              # layers x k,v x nkv x hd
    ("falcon-7b", 32 * 2 * 1 * 64 * 2),
    ("olmoe-tiny", 2 * 2 * 4 * 16 * 2),
    ("olmoe-1b-7b", 16 * 2 * 16 * 128 * 2),
])
def test_bytes_per_token_reads_the_caches_own_row(name, want):
    """576 values a token a layer for the latent pool; 2 x kv heads x head
    dim for the others, as before."""
    cfg = MODEL_PRESETS[name]()
    pool = SlotKVPool.__new__(SlotKVPool)
    pool.cfg, pool.dtype = cfg, jnp.dtype(jnp.bfloat16)
    pool.num_slots, pool.cap = 4, 64
    assert pool.bytes_per_token() == want
    assert slot_nbytes(cfg, 64) == 64 * want
    assert pool.view_nbytes() == 4 * 64 * want
    pool.dtype = jnp.dtype(jnp.int8)
    if not cfg.mla:
        scales = 2 * cfg.num_layers * cfg.num_kv_heads * 4
        assert pool.bytes_per_token() == want // 2 + scales
        assert slot_nbytes(cfg, 64, jnp.int8) == 64 * (want // 2 + scales)


def test_latent_pool_is_one_array_written_in_place(model):
    """The decode and prefill programs carry the latent pool through the
    layer loops of both stacks and write it where it lies: no operation
    makes, cuts out or writes back a whole layer of it."""
    cfg, params = model
    gen = Generator(params, cfg, eos_id=-1, pad_id=0)
    serving = ServingConfig(num_slots=5, max_len=48, prefill_bucket=16,
                            prefill_max_batch=2).validate(cfg)
    eng = ServingEngine(gen, serving, start=False)
    try:
        pool = eng.pool.caches
        assert pool.c.shape == (4, 5, 40, 48) and pool.c.dtype == jnp.bfloat16
        assert eng.pool.nbytes() == pool.c.nbytes
        state = (eng._p_dec, pool, eng._last_logits, eng._rngs)
        grid = (eng._d_lengths, eng._d_temps, eng._d_top_ks, eng._d_top_ps)
        programs = {
            "decode": (eng._decode_fn, (*state, *grid, eng._d_reject,
                                        eng._d_masks, None, None)),
            "prefill": (eng._prefill_fn, (
                *state, jnp.zeros((2, 16), jnp.int32),
                jnp.full((2,), 7, jnp.int32), jnp.arange(2),
                jnp.zeros((2, 2), jnp.uint32), None, None))}
        from tests.test_kv_inplace import check_in_place
        for name, (fn, args) in programs.items():
            batches = (5,) if name == "decode" else (5, 2)
            seen = check_in_place(
                jax.make_jaxpr(fn)(*args),
                {(4, b, 40, 48) for b in batches})
            # both stacks' loops carry it, and every layer writes once
            assert seen["carried"] >= 2 and seen["writes"] >= 2, (name, seen)
    finally:
        eng.close()
