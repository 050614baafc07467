"""NVIDIA-Nemotron-3-Super-120B-A12B through `ServingEngine` (PR 52): the
pool holds, a slot, ONE attention layer's keys and values, the depthwise
kernel's last three inputs over x, B and C, and the chunked scans' [heads,
head_dim, d_state] float32 matrices a Mamba-2 layer (`attention.
ConvKVCache`); the expert layers hold nothing. Prefill then decode through
pool and state against the float32 reference's full forward
(`benchmark/reference/nemotron_h.py`: no cache, no state carried, the
sequential recurrence): log-probabilities, never tokens, 1e-4 in float32.
And the share tied to the model: four chips' shares add up to the uncut
layer."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import nemotron_h as reference
from megatron_tpu.config import MODEL_PRESETS, ServingConfig
from megatron_tpu.inference import Generator
from megatron_tpu.inference.generation import (SamplingParams, init_kv_caches,
                                               prefill_chunk)
from megatron_tpu.models import language_model as lm
from megatron_tpu.models.attention import ConvKVCache
from megatron_tpu.models.moe import moe_apply
from megatron_tpu.serving import SamplingOptions, ServingEngine, capabilities
from megatron_tpu.serving.kv_pool import (SlotKVPool, insert_prefill,
                                          slot_nbytes)

TOL = 1e-4
STD = 0.11          # tests/test_nemotron_h.py says why


def _model(impl="dot", **over):
    cfg = dataclasses.replace(
        MODEL_PRESETS["nemotron-3-super-tiny"](), compute_dtype="float32",
        attention_impl=impl, init_method_std=STD, **over)
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
    16 tokens decoded through pool and state, beside an unrelated request:
    slots of different lengths in one grid."""
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
    # 1 attention layer of k and v of 2 heads of 16 a token; 5 Mamba-2
    # layers of 3 x 128 depthwise inputs and 8 x 8 x 16 float32 a slot
    assert snap["kv_bytes_per_token"] == 2 * 2 * 16 * 4
    assert snap["conv_state_bytes"] == 3 * 5 * 3 * 128 * 4
    assert snap["ssd_state_bytes"] == 3 * 5 * 8 * 8 * 16 * 4
    assert snap["ssm_state_bytes"] == 0


def test_prefill_through_the_flash_kernels_offset_form():
    """The cell's own attention_impl: a prefill and every chunk attend the
    region through `flash_attention(q_offset=...)`."""
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
def test_chunked_prefill_is_one_shot_prefill(model, chunk, chunks):
    """21 tokens in chunks of 8 (8 + 8 + 5 padded to 8) or 16 (16 + 5
    padded to 8): a continuation chunk starts from the depthwise inputs and
    the matrices the chunk before it left (a chunk of 8 or 16 is half a scan
    chunk or one), attends the rows already written, and leaves the states
    at its own last real row: the same log-probabilities as one program."""
    cfg, params = model
    prompt = np.random.default_rng(17).integers(1, cfg.vocab_size, 21).tolist()
    seen = []
    for serving in (dict(prefill_chunk=chunk), {}):
        with _engine(cfg, params, **serving) as eng:
            req = eng.submit(prompt, 6, SamplingOptions(temperature=0.0),
                             seed=1)
            _check(req, params, cfg, 6)
            seen.append((req.prefill_chunks, np.asarray(req.gen_logprobs)))
    assert [n for n, _ in seen] == [chunks, 1]
    assert np.abs(seen[0][1] - seen[1][1]).max() < TOL


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
    assert exact.ssm.shape == (5, 1, 8, 8, 16)
    assert exact.conv.shape == (5, 1, 3, 128)
    assert exact.k.shape == (1, 1, 32, 32)          # ONE attention layer
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
    assert out.ssm.shape == (5, 3, 8, 8, 16)
    assert np.all(np.asarray(out.conv[:, 1]) == 2.0)
    assert np.all(np.asarray(out.ssm[:, 1]) == 3.0)
    assert np.all(np.asarray(out.conv[:, (0, 2)]) == 1.0)
    assert np.all(np.asarray(out.ssm[:, (0, 2)]) == 1.0)
    assert np.asarray(out.offset).tolist() == [[0, 5, 0]]


def test_pool_byte_counts():
    """At the published widths and the cell's cut: what `slot_nbytes` /
    `fit_num_slots` size a slot at is what the pool allocates, and the
    cell's numbers; every kind counts its own layers."""
    cfg = dataclasses.replace(
        MODEL_PRESETS["nemotron-3-super"](), num_layers=11,
        layer_types=MODEL_PRESETS["nemotron-3-super"]().layer_types[:11],
        num_experts=128, vocab_size=32768)
    shapes = jax.eval_shape(lambda: init_kv_caches(
        cfg, 64, 8192, dtype=jnp.bfloat16, per_slot_offsets=True))
    assert shapes.ssm.shape == (5, 64, 128, 64, 128)
    assert shapes.ssm.dtype == jnp.float32
    assert shapes.conv.shape == (5, 64, 3, 10240)
    assert shapes.k.shape == (1, 64, 8192, 256)
    assert shapes.offset.shape == (1, 64)
    nbytes = lambda a: int(np.prod(a.shape)) * a.dtype.itemsize  # noqa: E731
    assert nbytes(shapes.ssm) // 64 == 20 * 2 ** 20
    assert nbytes(shapes.conv) // 64 == 307_200
    per_slot = sum(nbytes(getattr(shapes, f))
                   for f in ("k", "v", "conv", "ssm")) // 64
    assert slot_nbytes(cfg, 8192, jnp.bfloat16) == per_slot \
        == 8192 * 1024 + 20 * 2 ** 20 + 307_200 == 29_667_328
    tiny = MODEL_PRESETS["nemotron-3-super-tiny"]()
    pool = SlotKVPool(tiny, 4, 64, dtype=jnp.bfloat16)
    assert pool.conv_layers == 5 and pool.kv_layers == 1
    assert pool.ssd_state_nbytes() == 4 * 5 * 8 * 8 * 16 * 4
    assert pool.ssm_state_nbytes() == 0
    assert pool.conv_state_nbytes() == 4 * 5 * 3 * 128 * 2
    assert pool.bytes_per_slot() == slot_nbytes(tiny, 64, jnp.bfloat16)
    assert pool.bytes_per_token() == 2 * 2 * 16 * 2
    assert pool.full_nbytes() == 4 * 64 * pool.bytes_per_token()
    # Jamba's pool counts a Mamba-1 matrix and no Mamba-2 one
    jamba = SlotKVPool(MODEL_PRESETS["jamba2-3b-tiny"](), 2, 32)
    assert jamba.ssd_state_nbytes() == 0
    assert jamba.ssm_state_nbytes() == 2 * 26 * 16 * 128 * 4


@pytest.mark.parametrize("name", sorted(capabilities.REFUSED["conv-state"]))
def test_serving_refusals_by_name(name):
    """The state row's fourteen refusals (`tests/test_capabilities.py`'s
    matrix) hold for a matrix a head, and none is lifted; chunked prefill is
    served."""
    cfg = MODEL_PRESETS["nemotron-3-super-tiny"]()
    assert capabilities.pool_kind(cfg, 64) == "conv-state"
    assert "dropless-experts" in capabilities.rows_of(cfg, 64, None)
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


FAULTS = ["state_behind_the_padding", "chunk_starts_from_zeros",
          "bf16_state"]


def _plant(monkeypatch, fault):
    """The same engine over a Mamba-2 layer that forgets `live_rows` (a
    prefill of 21 in a bucket of 24 leaves the states behind the padding),
    that starts every continuation chunk from an empty state, or that keeps
    the matrices in bfloat16."""
    from megatron_tpu.models import mamba2
    sound = mamba2.mamba2_apply

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
    monkeypatch.setattr(mamba2, "mamba2_apply", faulty)


@pytest.mark.parametrize("fault", FAULTS)
def test_a_planted_fault_fails_the_comparison(model, monkeypatch, fault):
    """What the comparisons above can see: the first two faults are off by
    a hundred tolerances or more, a rounding of the state to 8 bits of
    mantissa over six decoded tokens by four (4.7e-4)."""
    _plant(monkeypatch, fault)
    cfg, params = model
    rng = np.random.default_rng(21)
    chunked = dict(prefill_chunk=8) if fault == "chunk_starts_from_zeros" \
        else {}
    with _engine(cfg, params, **chunked) as eng:
        req = eng.submit(rng.integers(1, cfg.vocab_size, 21).tolist(), 6,
                         SamplingOptions(temperature=0.0), seed=1)
        diff = _diff(req, params, cfg, 6)
    assert diff.max() > (3 if fault == "bf16_state" else 100) * TOL, diff


@pytest.mark.parametrize("fault", ["none", *FAULTS])
def test_the_cells_check_reads_a_finished_requests_state_from_its_slot(
        model, monkeypatch, fault):
    """The benchmark cell's second instrument
    (`benchmark/drivers/serve_open_loop_nemotron.py`: `check_request`,
    `slot_states`, `state_verdict`) at float32: the slot a finished request
    ran in holds the reference's state behind every one of its tokens (the
    engine dispatches a step ahead of the host's reading: one row ahead of
    the last log-probability's) to a part in 1e5; a state taken behind the
    padding or a continuation chunk started from zeros reads a tenth or
    more, a state kept in bfloat16 a part in a thousand."""
    from benchmark.by_name import load_module
    driver = load_module("drivers", "serve_open_loop_nemotron")
    if fault != "none":
        _plant(monkeypatch, fault)
    cfg, params = model
    with _engine(cfg, params, prefill_chunk=8) as eng:
        req, slot, tokens, got = driver.check_request(
            eng, cfg, {"request_timeout_s": 600}, 3,
            {"prompt": 21, "output": 6})
        held = driver.slot_states(eng, slot)
    assert req.prefill_chunks == 3 and slot is not None
    padded = jnp.asarray(list(tokens) + [0] * 4, jnp.int32)
    ref = reference.checked(params, padded, len(tokens), cfg, 6)
    plain = reference.token_logprobs(params, jnp.asarray(tokens), cfg, tail=6)
    assert np.abs(np.asarray(ref["logprobs"]) - np.asarray(plain)).max() < 1e-5
    read = driver.state_verdict(held, ref["states"])
    assert read["state_rows_ahead"] in (0, 1)
    least = {"none": 0, "bf16_state": 3e-4}.get(fault, 0.1)
    assert (read["state_rel_err"] < 1e-5) == (fault == "none"), read
    assert read["state_rel_err"] >= least, read


def test_four_shares_add_up_to_the_uncut_layer_and_head():
    """The share tied to the model: an expert layer of 8 experts under the
    uncut reference against the four shares of 2 experts each through
    `moe_apply` (`moe_first_expert` 0, 2, 4, 6 under `moe_router_experts`
    8). Each share's routed part goes through its own W_up-side sum, which
    is linear; the shared expert, which every chip computes alike, is
    counted once. And the four vocabulary slices' logits concatenate to the
    whole head's."""
    cfg, params = _model()
    mlp = params["transformer"]["layers"]["moe"]["mlp"]
    at = 2
    u = jax.random.normal(jax.random.PRNGKey(5), (1, 19, cfg.hidden_size))
    routed, shared, w = reference.experts(mlp, u[0], cfg, at)
    assert (np.asarray(w) > 0).sum(axis=1).tolist() == [cfg.moe_top_k] * 19
    layer = jax.tree.map(lambda a: a[at], mlp)
    whole, _ = moe_apply(layer, u, cfg)
    assert np.abs(np.asarray(whole[0] - (routed + shared))).max() < TOL
    held, parts = cfg.num_experts // 4, []
    for chip in range(4):
        first = chip * held
        share_cfg = dataclasses.replace(
            cfg, num_experts=held, moe_first_expert=first)
        share = {**layer, "w1": layer["w1"][first:first + held],
                 "w2": layer["w2"][first:first + held]}
        out, _ = moe_apply(share, u, share_cfg)
        mine, alike, _ = reference.experts(
            {**mlp, "w1": mlp["w1"][:, first:first + held],
             "w2": mlp["w2"][:, first:first + held]}, u[0], share_cfg, at)
        # the program's share is the reference's share
        assert np.abs(np.asarray(out[0] - (mine + alike))).max() < TOL
        assert np.abs(np.asarray(alike - shared)).max() < 1e-6
        parts.append(np.asarray(out[0] - alike))      # the routed part
        assert np.abs(parts[-1]).max() > 1e-2
    assert np.abs(sum(parts) + np.asarray(shared)
                  - np.asarray(routed + shared)).max() < TOL
    # the head: four slices of the vocabulary's columns
    tokens = jnp.asarray(np.random.default_rng(9).integers(
        1, cfg.vocab_size, 17))
    want = np.asarray(reference.logits(params, tokens, cfg))
    rows = cfg.vocab_size // 4
    slices = []
    for chip in range(4):
        cut = dataclasses.replace(cfg, vocab_size=rows)
        sliced = {**params, "lm_head": params["lm_head"][
            :, chip * rows:(chip + 1) * rows]}
        got, _ = lm.model_forward(sliced, tokens[None], cut,
                                  logits_dtype=jnp.float32)
        slices.append(np.asarray(got)[0, :, :rows])
    assert np.abs(np.concatenate(slices, axis=-1) - want).max() < TOL
