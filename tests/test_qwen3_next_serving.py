"""Qwen3-Next-80B-A3B-Instruct through `ServingEngine` (PR 60): the pool
holds, a slot, the attention layers' keys and values beside the Gated
DeltaNet layers' depthwise inputs and [value heads, head_dim, head_dim]
float32 matrices (`attention.ConvKVCache`, the pool kind `conv-state`).
Prefill, chunks and decode through pool and state against the float32
reference's full forward (`benchmark/reference/qwen3_next.py`: no cache, no
state carried, the rule token by token): log-probabilities, never tokens,
1e-4 in float32."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import qwen3_next as reference
from megatron_tpu.config import MODEL_PRESETS, ServingConfig
from megatron_tpu.inference import Generator
from megatron_tpu.inference.generation import (SamplingParams, init_kv_caches,
                                               prefill_chunk)
from megatron_tpu.models import language_model as lm
from megatron_tpu.models.attention import ConvKVCache
from megatron_tpu.serving import SamplingOptions, ServingEngine, capabilities
from megatron_tpu.serving.kv_pool import (SlotKVPool, insert_prefill,
                                          slice_slot, slot_nbytes)
from tests.test_qwen3_next import STD, drawn

TOL = 1e-4


def _model(impl="dot", **over):
    cfg = dataclasses.replace(
        MODEL_PRESETS["qwen3-next-tiny"](), compute_dtype="float32",
        attention_impl=impl, init_method_std=STD, **over)
    return cfg, drawn(lm.model_init(jax.random.PRNGKey(0), cfg))


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


def _check(req, params, cfg, new):
    tokens, _ = req.result(timeout=600)
    got = np.asarray(req.gen_logprobs, np.float64)
    want = np.asarray(reference.token_logprobs(
        params, jnp.asarray(tokens), cfg, tail=new), np.float64)
    assert got.shape == (new,)
    assert np.abs(got - want).max() < TOL, np.abs(got - want)
    return tokens


@pytest.mark.parametrize("plen", [1, 3, 21])
def test_one_shot_prefill_with_padding_then_decode(model, plen):
    """A bucketed prefill (bucket 8: a prompt of 1 or 3 leaves depthwise
    inputs that are part zeros; 21 has three padding rows behind it) and
    then 12 tokens decoded through pool and state, beside an unrelated
    request: two slots of different lengths in one grid."""
    cfg, params = model
    rng = np.random.default_rng(plen)
    with _engine(cfg, params) as eng:
        other = eng.submit(rng.integers(1, cfg.vocab_size, 9).tolist(), 16,
                           SamplingOptions(temperature=1.0), seed=3)
        req = eng.submit(rng.integers(1, cfg.vocab_size, plen).tolist(), 12,
                         SamplingOptions(temperature=0.0), seed=1)
        _check(req, params, cfg, 12)
        other.result(timeout=600)
        snap = eng.metrics.snapshot()
        assert eng._rope.cos.shape[-1] == 2       # 4 of 16 channels turned
    # 2 attention layers of 2 kv heads of 16, keys and values; 6 linear
    # layers of 3 x 128 depthwise inputs and 4 x 16 x 16 float32 a slot
    assert snap["kv_bytes_per_token"] == 2 * 2 * 32 * 4
    assert snap["conv_state_bytes"] == 3 * 6 * 3 * 128 * 4
    assert snap["gdn_state_bytes"] == 3 * 6 * 4 * 16 * 16 * 4
    assert snap["ssm_state_bytes"] == snap["ssd_state_bytes"] \
        == snap["kda_state_bytes"] == 0
    assert snap["kv_pool_bytes"] == 3 * 96 * 512 \
        + snap["conv_state_bytes"] + snap["gdn_state_bytes"]


@pytest.mark.parametrize("chunk,chunks", [(8, 3), (16, 2)])
def test_chunked_prefill_is_one_shot_prefill(model, chunk, chunks):
    """21 tokens in chunks of 8 (8 + 8 + 5 padded to 8) or 16 (16 + 5
    padded to 8): a continuation chunk starts from the depthwise inputs and
    the matrices the chunk before it left, attends the keys and values
    already held, and leaves the state at its own last real row: the same
    log-probabilities as one program."""
    cfg, params = model
    prompt = np.random.default_rng(17).integers(1, cfg.vocab_size, 21).tolist()
    seen = []
    for serving in (dict(prefill_chunk=chunk), {}):
        with _engine(cfg, params, **serving) as eng:
            req = eng.submit(prompt, 6, SamplingOptions(temperature=0.0),
                             seed=1)
            _check(req, params, cfg, 6)
            seen.append((req.prefill_chunks, np.asarray(req.gen_logprobs)))
            snap = eng.metrics.snapshot()
    assert [n for n, _ in seen] == [chunks, 1]
    assert np.abs(seen[0][1] - seen[1][1]).max() < TOL
    assert snap["prefill_chunks"] == 0          # the one-shot engine's


def test_prefill_through_the_flash_form():
    """The cell's own attention_impl: a prefill and its chunks through the
    flash form (off the chip its blockwise fallback; heads of 16 channels
    keep the products over the region at an offset)."""
    cfg, params = _model(impl="flash")
    rng = np.random.default_rng(29)
    with _engine(cfg, params, prefill_chunk=16) as eng:
        req = eng.submit(rng.integers(1, cfg.vocab_size, 37).tolist(), 5,
                         SamplingOptions(temperature=0.0), seed=1)
        _check(req, params, cfg, 5)
        assert req.prefill_chunks == 3


def test_batched_prefill_of_unequal_lengths(model):
    """Prompts of 10 and 15 share one padded bucket of 16: each row leaves
    its state at its OWN length."""
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


def test_a_slot_taken_over_starts_from_zeros_in_all_parts(model):
    """One slot: a long request, then short ones in the same slot. Their
    keys, depthwise inputs and matrices are their own: a prompt of 1 reads
    three rows of zeros ahead of it, not the last tenant's inputs."""
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


def test_the_pool_holds_the_references_state_behind_a_request(model):
    """What the benchmark's check reads back: the slot's six matrices, its
    depthwise inputs and its last keys are the reference's behind the same
    tokens (the engine runs a step ahead: either row is its right)."""
    cfg, params = model
    rng = np.random.default_rng(13)
    with _engine(cfg, params, prefill_chunk=16, num_slots=2) as eng:
        req = eng.submit(rng.integers(1, cfg.vocab_size, 29).tolist(), 9,
                         SamplingOptions(temperature=0.0), seed=1)
        slot = None
        while not req.done():
            if slot is None:
                slot = next((i for i, r in enumerate(eng._slot_req)
                             if r is req), None)
        tokens = _check(req, params, cfg, 9)
        time.sleep(0.3)
        pool = jax.tree.map(np.asarray, eng.pool.caches)
    ref = reference.checked(params, jnp.asarray(list(tokens) + [0]),
                            len(tokens), cfg, 9)
    errs = [max(np.abs(pool.ssm[:, slot] - np.asarray(ref["states"][i])).max(),
                np.abs(pool.conv[:, slot]
                       - np.asarray(ref["inputs"][i])).max())
            for i in (0, 1)]
    assert min(errs) < TOL, errs
    n = len(tokens) - 1
    rows = pool.k[:, slot, n - reference.KEY_ROWS:n]
    assert np.abs(rows - np.asarray(ref["keys"])).max() < TOL
    assert np.abs(rows).max() > 0.1


def test_prefill_chunk_leaves_the_state_at_the_last_real_row(model):
    """`generation.prefill_chunk` on a padded chunk: the state and the
    depthwise inputs are the ones a chunk of the real rows alone leaves."""
    cfg, params = model
    tokens = np.random.default_rng(19).integers(1, cfg.vocab_size, 11)
    rope = lm.make_rope(cfg)

    def run(padded):
        caches = init_kv_caches(cfg, 1, 32, dtype=jnp.float32)
        toks = np.zeros((1, padded), np.int32)
        toks[0, :5] = tokens[:5]
        caches, _ = prefill_chunk(params, jnp.asarray(toks), caches, cfg,
                                  rope=rope, last_idx=4, next_offset=5)
        toks = np.full((1, padded), 7, np.int32)
        toks[0, :6] = tokens[5:]
        return prefill_chunk(params, jnp.asarray(toks), caches, cfg,
                             rope=rope, last_idx=5, next_offset=11)
    (exact, last_a), (padded, last_b) = run(6), run(8)
    assert isinstance(exact, ConvKVCache)
    assert exact.ssm.dtype == jnp.float32
    assert exact.ssm.shape == (6, 1, 4, 16, 16)
    assert exact.conv.shape == (6, 1, 3, 128)
    assert exact.k.shape == (2, 1, 32, 32)          # TWO attention layers
    for a, b in ((exact.conv, padded.conv), (exact.ssm, padded.ssm)):
        # two programs of two shapes: float32's rounding, not a padding row
        assert np.abs(np.asarray(a - b)).max() < 1e-5
        assert np.abs(np.asarray(a)).max() > 1e-2
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


def test_insert_prefill_overwrites_all_parts(model):
    cfg, _ = model
    pool = init_kv_caches(cfg, 3, 16, dtype=jnp.float32,
                          per_slot_offsets=True)
    pool = pool._replace(**{f: jnp.ones_like(getattr(pool, f))
                            for f in ("k", "v", "conv", "ssm")})
    sub = init_kv_caches(cfg, 1, 16, dtype=jnp.float32)
    sub = sub._replace(k=jnp.full_like(sub.k, 4.0),
                       v=jnp.full_like(sub.v, 5.0),
                       conv=jnp.full_like(sub.conv, 2.0),
                       ssm=jnp.full_like(sub.ssm, 3.0))
    out = insert_prefill(pool, sub, 1, 5)
    assert out.ssm.shape == (6, 3, 4, 16, 16)
    for part, value in (("conv", 2.0), ("ssm", 3.0)):
        assert np.all(np.asarray(getattr(out, part)[:, 1]) == value)
        assert np.all(np.asarray(getattr(out, part)[:, (0, 2)]) == 1.0)
    assert np.all(np.asarray(out.k[:, 1, :5]) == 4.0)
    assert np.all(np.asarray(out.v[:, 1, :5]) == 5.0)
    assert np.all(np.asarray(out.k[:, (0, 2)]) == 1.0)
    assert np.asarray(out.offset).tolist() == [[0, 5, 0]] * 2
    with pytest.raises(AssertionError, match="cannot be cut out"):
        slice_slot(out, 1, 3)


def test_pool_byte_counts():
    """At the published widths and the cell's cut: what `slot_nbytes` /
    `fit_num_slots` size a slot at is what the pool allocates, and the
    cell's numbers (ISSUE 60: 128 MiB of keys and values, 12 MiB of state,
    288 KiB of depthwise inputs a slot; 4.4 GiB at 32 slots)."""
    full = MODEL_PRESETS["qwen3-next"]()
    cfg = dataclasses.replace(full, num_layers=8,
                              layer_types=full.layer_types[:8],
                              num_experts=128, vocab_size=37984)
    shapes = jax.eval_shape(lambda: init_kv_caches(
        cfg, 32, 32768, dtype=jnp.bfloat16, per_slot_offsets=True))
    assert shapes.ssm.shape == (6, 32, 32, 128, 128)
    assert shapes.ssm.dtype == jnp.float32
    assert shapes.conv.shape == (6, 32, 3, 8192)
    assert shapes.k.shape == shapes.v.shape == (2, 32, 32768, 512)
    assert shapes.offset.shape == (2, 32)
    nbytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                 for a in (shapes.k, shapes.v, shapes.conv, shapes.ssm))
    assert nbytes == 32 * slot_nbytes(cfg, 32768)
    assert slot_nbytes(cfg, 32768) == (128 << 20) + (12 << 20) + (288 << 10)
    assert round(nbytes / 2 ** 30, 2) == 4.38
    assert capabilities.pool_kind(cfg, 32768) == "conv-state"
    tiny = MODEL_PRESETS["qwen3-next-tiny"]()
    pool = SlotKVPool(tiny, 2, 16, dtype=jnp.bfloat16)
    assert pool.nbytes() == 2 * slot_nbytes(tiny, 16)
    assert pool.gdn_state_nbytes() == 2 * 6 * 4 * 16 * 16 * 4
    assert pool.kda_state_nbytes() == pool.ssd_state_nbytes() \
        == pool.ssm_state_nbytes() == 0
    assert pool.conv_state_nbytes() == 2 * 6 * 3 * 128 * 2
    assert pool.bytes_per_token() == 2 * 2 * 32 * 2
    assert pool.full_nbytes() == 2 * 16 * pool.bytes_per_token()


@pytest.mark.parametrize("name",
                         sorted(capabilities.REFUSED["conv-state"]))
def test_serving_refusals_by_name(name):
    """The `conv-state` row's refusals stand as they are for the new kind."""
    cfg = MODEL_PRESETS["qwen3-next-tiny"]()
    on = {"enable_prefix_cache": dict(enable_prefix_cache=True),
          "retained_slots": dict(retained_slots=1),
          "preemption": dict(preemption=True),
          "speculative_k": dict(speculative_k=2),
          "kv_block_size": dict(kv_block_size=8),
          "block_native_attn": dict(kv_block_size=8, block_native_attn=True),
          "serving_tp": dict(serving_tp=2), "prefill_tp": dict(prefill_tp=2),
          "decode_tp": dict(decode_tp=2), "serving_pp": dict(serving_pp=2),
          "disaggregate_prefill": dict(disaggregate_prefill=True),
          "host_kv_bytes": dict(host_kv_bytes=1 << 20),
          "adapter_slots": dict(adapter_slots=2),
          "kv_dtype int8": dict(kv_dtype="int8")}[name]
    serving = ServingConfig(num_slots=2, max_len=32, **on)
    hits = [m for row, f, m in capabilities.refusals(serving, cfg)
            if row == "conv-state" and f == name]
    assert hits and "ROADMAP R6" in hits[0]
    with pytest.raises((AssertionError, ValueError)):
        serving.validate(cfg)
