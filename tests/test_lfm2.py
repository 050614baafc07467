"""LFM2-8B-A1B on the normal path (PR 37): gated short-convolution layers
whose state lives in the pool beside the keys and values of the attention
layers (`attention.ConvKVCache`), a pattern of mixers scanned a period at a
time with the kinds' parameters stacked apart, a leading dense stack, a tail
off the period, per-head QK-norm. Each against the float32 reference
(`benchmark/reference/lfm2_moe.py`: no cache, no state, the convolution over
the whole sequence). Logits and log-probabilities, never tokens. Float32
throughout, so the tolerances are those of sums taken in another order: 1e-4
on logits of magnitude ~5."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2_moe as reference
from megatron_tpu.config import (LFM2_LAYER_TYPES, MODEL_PRESETS,
                                 MegatronConfig, ModelConfig, ParallelConfig,
                                 ServingConfig)
from megatron_tpu.inference import Generator
from megatron_tpu.inference.generation import (SamplingParams, init_kv_caches,
                                               prefill_chunk)
from megatron_tpu.models import language_model as lm
from megatron_tpu.models.attention import (ConvKVCache, qk_head_norm,
                                            qk_norm)
from megatron_tpu.models.transformer import _pattern_period
from megatron_tpu.serving import SamplingOptions, ServingEngine
from megatron_tpu.serving.kv_pool import (SlotKVPool, insert_prefill,
                                          slot_nbytes)

TOL = 1e-4
# Every matrix drawn at sqrt(64) x 0.11 = 0.9 of gain, the published widths'
# sqrt(2048) x 0.02: the mixers then add to the residual stream what they add
# at width, and a wrong state moves a log-probability by tenths (the last
# test below). At the initialiser's 0.02 and hidden 64 the stream is the
# embedding and nothing else; with the embedding alone scaled up, as the
# untied models' tests scale theirs, the tied head gives every position its
# own input with probability 1 and a greedy token's log-probability is 0
# whatever the state.
STD = 0.11
# the benchmark's cut: published layer 0 (dense) and layers 2 to 13
CUT = ("conv",) + ("full_attention", "conv", "conv", "conv") * 3


def _model(pattern="cut", impl="dot"):
    over = (dict(num_layers=13, first_k_dense_replace=1, layer_types=CUT)
            if pattern == "cut" else {})
    cfg = dataclasses.replace(
        MODEL_PRESETS["lfm2-8b-a1b-tiny"](), compute_dtype="float32",
        attention_impl=impl, init_method_std=STD, **over)
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    # a choosing bias that chooses (zeros as initialised)
    for kind, stack in params["transformer"]["moe"].items():
        bias = stack["mlp"]["e_score_correction_bias"]
        stack["mlp"]["e_score_correction_bias"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(len(kind)), bias.shape, bias.dtype)
    return cfg, params


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture(scope="module")
def whole():
    return _model("whole")


def _engine(cfg, params, **serving):
    gen = Generator(params, cfg, eos_id=-1, pad_id=0,
                    kv_cache_dtype=jnp.float32)
    base = dict(num_slots=3, max_queue=16, max_len=96, prefill_bucket=8,
                prefill_max_batch=2)
    return ServingEngine(gen, ServingConfig(**{**base, **serving})
                         .validate(cfg))


def _check(req, params, cfg, new):
    tokens, _ = req.result(timeout=600)
    got = np.asarray(req.gen_logprobs, np.float64)
    want = np.asarray(reference.token_logprobs(
        params, jnp.asarray(tokens), cfg, tail=new), np.float64)
    assert got.shape == (new,)
    # every decoded position, from the first: a wrong state shows in the
    # first two steps and fades
    assert np.abs(got - want).max() < TOL, np.abs(got - want)


@pytest.mark.parametrize("pattern,impl", [
    ("cut", "dot"), ("cut", "flash"), ("whole", "dot")])
def test_forward_without_a_cache_matches_reference(pattern, impl):
    """`model_forward` with no cache (training's path): the cut's pattern,
    one dense layer and three periods; and the whole published pattern, two
    dense layers, four periods and the tail off the period."""
    cfg, params = _model(pattern, impl)
    assert cfg.layer_types == (CUT if pattern == "cut" else LFM2_LAYER_TYPES)
    tokens = np.random.default_rng(0).integers(1, cfg.vocab_size, (2, 37))
    got, _ = lm.model_forward(params, jnp.asarray(tokens), cfg,
                              rope=lm.make_rope(cfg),
                              logits_dtype=jnp.float32)
    for row, toks in zip(np.asarray(got), tokens):
        want = np.asarray(reference.logits(params, jnp.asarray(toks), cfg))
        assert np.abs(want).max() > 1.0
        assert np.abs(row[:, :cfg.vocab_size] - want).max() < TOL


def test_pattern_periods():
    """The scan's period and count, and what is left for the tail."""
    A, C = "full_attention", "conv"
    assert _pattern_period(LFM2_LAYER_TYPES[2:]) == (4, 4)      # 6 in the tail
    assert _pattern_period(CUT[1:]) == (4, 3)
    assert _pattern_period((C, C)) == (1, 2)
    assert _pattern_period((C,)) == (1, 1)
    assert _pattern_period((A, C, C, A, C)) == (5, 1)           # no repeat
    assert _pattern_period((A, C, A, C, A)) == (2, 2)


@pytest.mark.parametrize("plen", [1, 2, 3, 21])
def test_engine_prefill_and_decode_match_reference(model, plen):
    """`ServingEngine`: a bucketed prefill (bucket 8: prompts of 1, 2 and 3
    tokens leave a state that is part zeros, and 21 is no multiple of the
    bucket: three padding rows) and then 6 tokens decoded through pool and
    state, beside an unrelated request at another length."""
    cfg, params = model
    rng = np.random.default_rng(plen)
    with _engine(cfg, params) as eng:
        other = eng.submit(rng.integers(1, cfg.vocab_size, 9).tolist(), 20,
                           SamplingOptions(temperature=1.0), seed=3)
        req = eng.submit(rng.integers(1, cfg.vocab_size, plen).tolist(), 6,
                         SamplingOptions(temperature=0.0), seed=1)
        _check(req, params, cfg, 6)
        other.result(timeout=600)
        snap = eng.metrics.snapshot()
        # the programs close over rotary tables of the engine's own length
        assert eng._rope[0].shape[0] == 96 < cfg.max_position_embeddings
    # the pool's own count: 3 attention layers of k and v of 2 heads x 8
    # channels a token; 10 convolution layers of 2 x 64 values a slot; float32
    assert snap["kv_bytes_per_token"] == 3 * 2 * 2 * 8 * 4
    assert snap["conv_state_bytes"] == 3 * 10 * 2 * 64 * 4
    assert snap["kv_bytes_per_slot"] == 96 * 384 + 10 * 2 * 64 * 4
    assert snap["kv_pool_bytes"] == 3 * snap["kv_bytes_per_slot"]


def test_engine_prefill_through_the_flash_kernel():
    """The cell's own attention_impl: a prefill's attention layers read
    their whole regions heads-major through the flash kernel."""
    cfg, params = _model(impl="flash")
    rng = np.random.default_rng(29)
    with _engine(cfg, params) as eng:
        req = eng.submit(rng.integers(1, cfg.vocab_size, 21).tolist(), 5,
                         SamplingOptions(temperature=0.0), seed=1)
        _check(req, params, cfg, 5)


def test_engine_on_the_whole_published_pattern(whole):
    """Two dense layers, four scanned periods and the tail, through the
    engine: the caches' indices by kind run across the groups."""
    cfg, params = whole
    rng = np.random.default_rng(5)
    with _engine(cfg, params) as eng:
        req = eng.submit(rng.integers(1, cfg.vocab_size, 13).tolist(), 5,
                         SamplingOptions(temperature=0.0), seed=1)
        _check(req, params, cfg, 5)


def test_batched_prefill_of_two_lengths_in_one_bucket(model):
    """Two prompts of 10 and 15 tokens share one padded bucket of 16 (one
    `_prefill_fn` call of two rows): each row leaves the state at its OWN
    length."""
    cfg, params = model
    rng = np.random.default_rng(7)
    eng = _engine(cfg, params)
    eng.close()
    eng = ServingEngine(eng.gen, eng.serving, start=False)
    reqs = [eng.submit(rng.integers(1, cfg.vocab_size, n).tolist(), 5,
                       SamplingOptions(temperature=0.0), seed=n)
            for n in (10, 15)]
    eng._thread.start()
    try:
        for r in reqs:
            _check(r, params, cfg, 5)
        assert eng._prefill_traces == 1         # one program, one bucket
    finally:
        eng.close()


def test_a_reused_slot_starts_from_no_state(model):
    """One slot: a long request, then a prompt of ONE token in the same
    slot. Its state after the prefill is (0, a_0): nothing of the slot's
    last tenant."""
    cfg, params = model
    rng = np.random.default_rng(11)
    with _engine(cfg, params, num_slots=1) as eng:
        first = eng.submit(rng.integers(1, cfg.vocab_size, 30).tolist(), 12,
                           SamplingOptions(temperature=1.0), seed=2)
        first.result(timeout=600)
        for n in (1, 2):
            req = eng.submit(rng.integers(1, cfg.vocab_size, n).tolist(), 4,
                             SamplingOptions(temperature=0.0), seed=n)
            _check(req, params, cfg, 4)


def test_a_live_slot_does_not_depend_on_its_neighbours(model):
    """The same request alone and between two others that come and go (one
    finishes and frees its slot mid-way, parked rows keep computing): the
    same log-probabilities to the last bit of the comparison."""
    cfg, params = model
    rng = np.random.default_rng(13)
    prompt = rng.integers(1, cfg.vocab_size, 12).tolist()
    opts = SamplingOptions(temperature=0.0)
    with _engine(cfg, params) as eng:
        alone = eng.submit(prompt, 10, opts, seed=1)
        alone.result(timeout=600)
    with _engine(cfg, params) as eng:
        a = eng.submit(rng.integers(1, cfg.vocab_size, 5).tolist(), 3,
                       SamplingOptions(temperature=1.0), seed=4)
        beside = eng.submit(prompt, 10, opts, seed=1)
        b = eng.submit(rng.integers(1, cfg.vocab_size, 17).tolist(), 25,
                       SamplingOptions(temperature=1.0), seed=5)
        _check(beside, params, cfg, 10)
        a.result(timeout=600), b.result(timeout=600)
    assert np.abs(np.asarray(alone.gen_logprobs)
                  - np.asarray(beside.gen_logprobs)).max() < 1e-5


@pytest.mark.parametrize("chunk,chunks", [(8, 3), (16, 2)])
def test_chunked_prefill_carries_the_state(model, chunk, chunks):
    """A prompt of 21 in chunks of 8 (8 + 8 + 5 padded to 8) or 16 (16 + 5
    padded to 8): every chunk starts from the state the one before it left
    and leaves the state at its own last real row."""
    cfg, params = model
    rng = np.random.default_rng(17)
    with _engine(cfg, params, prefill_chunk=chunk) as eng:
        req = eng.submit(rng.integers(1, cfg.vocab_size, 21).tolist(), 6,
                         SamplingOptions(temperature=0.0), seed=1)
        _check(req, params, cfg, 6)
        assert req.prefill_chunks == chunks


def test_prefill_chunk_leaves_the_state_at_the_last_real_row(model):
    """`generation.prefill_chunk` on a padded chunk: the state is the one a
    chunk of the real rows alone leaves, and the padding changes nothing."""
    cfg, params = model
    rope = lm.make_rope(cfg)
    tokens = np.random.default_rng(19).integers(1, cfg.vocab_size, 11)

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
    assert np.abs(np.asarray(exact.conv - padded.conv)).max() < 1e-6
    assert np.abs(np.asarray(exact.conv)).max() > 1e-3
    want = np.asarray(reference.logits(params, jnp.asarray(tokens), cfg))[-1]
    assert np.abs(np.asarray(last_a)[:cfg.vocab_size] - want).max() < TOL
    assert np.abs(np.asarray(last_b)[:cfg.vocab_size] - want).max() < TOL


def test_serial_generate_matches_reference(model):
    """`Generator.generate` (the serial route: scalar offsets, a batch of
    two at a common prefix, then one token a step)."""
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


def test_insert_prefill_overwrites_the_whole_state(model):
    cfg, _ = model
    pool = init_kv_caches(cfg, 3, 16, dtype=jnp.float32,
                          per_slot_offsets=True)
    pool = pool._replace(conv=jnp.ones_like(pool.conv))
    sub = init_kv_caches(cfg, 1, 16, dtype=jnp.float32)
    sub = sub._replace(conv=jnp.full_like(sub.conv, 2.0))
    out = insert_prefill(pool, sub, 1, 5)
    assert np.all(np.asarray(out.conv[:, 1]) == 2.0)
    assert np.all(np.asarray(out.conv[:, (0, 2)]) == 1.0)
    assert np.asarray(out.offset).tolist() == [[0, 5, 0]] * 3


def test_per_head_qk_norm_and_olmoes_unchanged():
    """`qk_head_norm`: each head's 64 channels by their own root mean
    square, one scale shared by the heads; `qk_norm` (OLMoE's) still over
    all the heads' channels together."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 5, 4, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 5, 2, 8)), jnp.float32)
    sq = jnp.asarray(rng.normal(size=(8,)), jnp.float32)
    sk = jnp.asarray(rng.normal(size=(8,)), jnp.float32)
    got_q, got_k = qk_head_norm({"q_norm": {"scale": sq},
                                 "k_norm": {"scale": sk}}, q, k, 1e-5)

    def by_hand(x, scale):
        x = np.asarray(x, np.float64)
        return x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5) \
            * np.asarray(scale)
    assert np.abs(np.asarray(got_q) - by_hand(q, sq)).max() < 1e-5
    assert np.abs(np.asarray(got_k) - by_hand(k, sk)).max() < 1e-5
    # OLMoE's: one statistic over heads x channels, a scale a channel of all
    wq = jnp.asarray(rng.normal(size=(32,)), jnp.float32)
    wk = jnp.asarray(rng.normal(size=(16,)), jnp.float32)
    oq, ok = qk_norm({"q_norm": {"scale": wq}, "k_norm": {"scale": wk}},
                     q, k, 1e-5)
    flat = np.asarray(q, np.float64).reshape(2, 5, 32)
    want = flat / np.sqrt((flat ** 2).mean(-1, keepdims=True) + 1e-5) \
        * np.asarray(wq)
    assert np.abs(np.asarray(oq).reshape(2, 5, 32) - want).max() < 1e-5
    assert ok.shape == k.shape
    olmoe = MODEL_PRESETS["olmoe-tiny"]()
    assert olmoe.qk_norm and not olmoe.qk_head_norm


def test_pool_byte_counts():
    cfg = dataclasses.replace(MODEL_PRESETS["lfm2-8b-a1b"](), num_layers=13,
                              first_k_dense_replace=1, layer_types=CUT)
    # the cell's: a token costs 3 attention layers x 2 x 8 x 64 bf16; a
    # slot's state 10 layers x 2 x 2048 bf16 whatever its length
    assert cfg.kv_layers == 3 and cfg.layers_of("conv") == 10
    assert slot_nbytes(cfg, 2048) == 2048 * 6144 + 81920
    whole = MODEL_PRESETS["lfm2-8b-a1b"]()
    assert whole.kv_layers == 6 and whole.layers_of("conv") == 18
    tiny = dataclasses.replace(MODEL_PRESETS["lfm2-8b-a1b-tiny"](),
                               num_layers=13, first_k_dense_replace=1,
                               layer_types=CUT)
    pool = SlotKVPool(tiny, 5, 64, dtype=jnp.bfloat16)
    row = 2 * 2 * 8 * 2
    assert pool.kv_layers == 3 and pool.conv_layers == 10
    assert pool.caches.k.shape == (3, 5, 64, 2 * 8)     # a row: both heads
    assert pool.caches.conv.shape == (10, 5, 2, 64)
    assert pool.bytes_per_token() == 3 * row
    assert pool.conv_state_nbytes() == 5 * 10 * 2 * 64 * 2
    assert pool.bytes_per_slot() == slot_nbytes(tiny, 64) \
        == 64 * 3 * row + 10 * 2 * 64 * 2
    assert pool.nbytes() == 5 * pool.bytes_per_slot()
    assert pool.full_nbytes() == pool.nbytes() - pool.conv_state_nbytes()
    assert pool.view_nbytes() == 5 * 64 * 3 * row
    # a pool of one kind: every layer holds rows, and no state
    falcon = SlotKVPool(MODEL_PRESETS["falcon-tiny"](), 2, 64)
    assert falcon.kv_layers == falcon.cfg.num_layers
    assert falcon.conv_state_nbytes() == 0 and falcon.conv_layers == 0


REFUSED = dict(
    enable_prefix_cache=dict(enable_prefix_cache=True),
    retained_slots=dict(retained_slots=2),
    preemption=dict(preemption=True, priority_levels=2),
    speculative_k=dict(speculative_k=2),
    kv_block_size=dict(kv_block_size=16),
    block_native_attn=dict(block_native_attn=True),
    serving_pp=dict(serving_pp=2),
    serving_tp=dict(serving_tp=2),
    prefill_tp=dict(prefill_tp=2),
    decode_tp=dict(decode_tp=2),
    disaggregate_prefill=dict(disaggregate_prefill=True),
    host_kv_bytes=dict(host_kv_bytes=1 << 20),
    adapter_slots=dict(adapter_slots=2),
    kv_dtype=dict(kv_dtype="int8"),
)


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_serving_refusals_by_name(name):
    cfg = MODEL_PRESETS["lfm2-8b-a1b-tiny"]()
    with pytest.raises(AssertionError, match="conv layers: " + name
                       + ".*refused.*ROADMAP R6"):
        ServingConfig(num_slots=2, max_len=64, **REFUSED[name]).validate(cfg)
    # and what the cell uses, and chunked prefill, are taken
    ServingConfig(num_slots=2, max_len=64, prefill_bucket=8,
                  prefill_max_batch=2, prefill_chunk=16).validate(cfg)


@pytest.mark.parametrize("change,parallel,match", [
    (dict(num_layers=6), {}, "24 entries"),
    (dict(layer_types=("conv", "window") * 12), {}, "'conv' | 'full_att"),
    (dict(conv_L_cache=1), {}, "conv_L_cache=1"),
    (dict(sliding_window=16), {}, "refused with MLA"),
    (dict(window_layer_period=4, sliding_window=16), {}, "layer_types"),
    (dict(qk_norm=True), {}, "different models' norms"),
    ({}, dict(tensor_parallel=2), "one device only"),
    ({}, dict(pipeline_parallel=2), "one device only"),
    (dict(attention_impl="ring"), {}, "context-parallel"),
])
def test_model_refusals_by_name(change, parallel, match):
    cfg = dataclasses.replace(MODEL_PRESETS["lfm2-8b-a1b-tiny"](), **change)
    with pytest.raises(AssertionError, match=match):
        MegatronConfig(model=cfg, parallel=ParallelConfig(**parallel)
                       ).validate(2 if parallel else 1)


def test_presets_hold_the_published_sizes():
    cfg = MODEL_PRESETS["lfm2-8b-a1b"]()
    assert isinstance(cfg, ModelConfig)
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_kv_heads, cfg.kv_channels, cfg.ffn_hidden_size,
            cfg.dense_ffn_hidden_size, cfg.vocab_size) == \
        (24, 2048, 32, 8, 64, 1792, 7168, 65536)
    assert (cfg.num_experts, cfg.router_experts, cfg.moe_top_k,
            cfg.n_shared_experts, cfg.first_k_dense_replace) == \
        (32, 32, 4, 0, 2)
    assert (cfg.conv_L_cache, cfg.rope_theta, cfg.norm_epsilon,
            cfg.max_position_embeddings) == (3, 1e6, 1e-5, 128000)
    assert cfg.layer_types == LFM2_LAYER_TYPES and len(cfg.layer_types) == 24
    assert [l for l, k in enumerate(cfg.layer_types)
            if k == "full_attention"] == [2, 6, 10, 14, 18, 21]
    assert cfg.qk_head_norm and not cfg.qk_norm and cfg.tie_embed_logits \
        and cfg.moe_score_correction_bias and cfg.moe_norm_topk_prob \
        and cfg.moe_scoring_func == "sigmoid"
    MegatronConfig(model=cfg).validate(1)
    MegatronConfig(model=MODEL_PRESETS["lfm2-8b-a1b-tiny"]()).validate(1)
    # the whole model: 8,340 M parameters (the published 8.3 B), the kinds
    # stacked apart in each group
    shapes = jax.eval_shape(lambda: lm.model_init(jax.random.PRNGKey(0), cfg))
    assert round(sum(int(np.prod(x.shape))
                     for x in jax.tree.leaves(shapes)) / 1e6) == 8340
    stacks = shapes["transformer"]
    assert set(stacks) == {"dense", "moe"} and set(stacks["dense"]) == {"conv"}
    assert stacks["moe"]["conv"]["conv"]["in_proj"].shape == (16, 2048, 6144)
    assert stacks["moe"]["conv"]["conv"]["conv"].shape == (16, 3, 2048)
    assert stacks["moe"]["full_attention"]["attention"]["q_norm"][
        "scale"].shape == (6, 64)
    assert stacks["moe"]["full_attention"]["mlp"]["w1"].shape == \
        (6, 32, 2048, 3584)
    assert stacks["dense"]["conv"]["mlp"]["w1"].shape == (2, 2048, 2, 7168)
    assert "lm_head" not in shapes


def test_cli_cuts_the_preset_to_the_cells_depth():
    from megatron_tpu.arguments import parse_cli
    cfg, _ = parse_cli(["--model", "lfm2-8b-a1b", "--num_layers", "13",
                        "--num_dense_layers", "1", "--layer_types",
                        ",".join(CUT), "--bf16"], n_devices=1)
    m = cfg.model
    assert (m.num_layers, m.first_k_dense_replace, m.layer_types) == \
        (13, 1, CUT)
    assert m.params_dtype == m.compute_dtype == "bfloat16"
    assert m.num_experts == 32 and m.vocab_size == 65536


def test_training_loss_runs_through_the_pattern_scan(whole):
    """The training path: `loss_fn` and its gradient through both groups'
    scans and the tail; every kind's parameters get a gradient."""
    cfg, params = whole
    tokens = jnp.asarray(np.random.default_rng(23).integers(
        1, cfg.vocab_size, (2, 17)))
    rope = lm.make_rope(cfg)

    def loss(p):
        return lm.loss_fn(p, tokens, cfg, rope=rope)
    value, grads = jax.value_and_grad(loss)(params)
    assert np.isfinite(float(value))
    for kind in ("conv", "full_attention"):
        g = grads["transformer"]["moe"][kind]
        mixer = g["conv"]["conv"] if kind == "conv" else g["attention"]["wq"]
        assert np.all(np.abs(np.asarray(mixer)).max(
            axis=tuple(range(1, mixer.ndim))) > 0)


def test_a_state_taken_behind_the_padding_fails_the_comparison(
        model, monkeypatch):
    """What the comparisons above can see: the same engine over a program
    whose convolution layers forget `live_rows`, so that a prefill leaves the
    state behind its bucket's padding (21 tokens in a bucket of 24). The
    prefill's own position is right; the first two decoded positions read
    the wrong state and are off by tenths, a thousand times the tolerance."""
    from megatron_tpu.models import short_conv
    sound = short_conv.short_conv_apply

    def state_at_the_buckets_end(params, x, cfg, *, kv_cache=None,
                                 kind_layer=None):
        if kv_cache is None:
            return sound(params, x, cfg)
        out, new = sound(params, x, cfg, kind_layer=kind_layer,
                         kv_cache=kv_cache._replace(live_rows=jnp.int32(
                             ConvKVCache.NO_PADDING)))
        return out, new._replace(live_rows=kv_cache.live_rows)
    monkeypatch.setattr(short_conv, "short_conv_apply",
                        state_at_the_buckets_end)
    cfg, params = model
    rng = np.random.default_rng(21)
    with _engine(cfg, params) as eng:
        req = eng.submit(rng.integers(1, cfg.vocab_size, 21).tolist(), 6,
                         SamplingOptions(temperature=0.0), seed=1)
        tokens, _ = req.result(timeout=600)
    diff = np.abs(np.asarray(req.gen_logprobs) - np.asarray(
        reference.token_logprobs(params, jnp.asarray(tokens), cfg, tail=6)))
    assert diff[0] < TOL
    assert diff[1:3].max() > 0.1, diff
