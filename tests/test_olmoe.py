"""OLMoE on the normal path, against the plain float32 reference
(`benchmark/reference/olmoe.py`), at `olmoe-tiny` on the CPU: dropless top-k
routing through the grouped product's plain path, QK-norm, gates not
renormalised, and the serving engine's prefill + decode through the cache.

Everything is float32 with seeded weights, so the program and the reference
differ only in the order of summation: tolerances are a few 1e-4 on logits of
magnitude ~1, where a dropped token, a renormalised gate or a missing norm
moves them by 1e-2 and more (the `differ` tests below show by how much).
"""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import olmoe as reference
from megatron_tpu.arguments import parse_cli
from megatron_tpu.config import (MODEL_PRESETS, MegatronConfig, ParallelConfig,
                                 ServingConfig, olmoe_config)
from megatron_tpu.inference.generation import Generator
from megatron_tpu.models import language_model as lm
from megatron_tpu.models.moe import moe_apply, moe_init
from megatron_tpu.serving import SamplingOptions, ServingEngine

# float32 on both sides: only the order of summation differs
TOL = dict(rtol=2e-4, atol=2e-4)
PUBLISHED = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "configs", "olmoe-1b-7b-4l.json")


def tiny(**overrides):
    return olmoe_config("tiny", compute_dtype="float32", **overrides)


def seeded_params(cfg, seed=7):
    params = lm.model_init(jax.random.PRNGKey(seed), cfg)
    # non-trivial norm scales (q_norm / k_norm among them) and a router far
    # enough from zero that the top-k choice is not a coin toss
    return jax.tree.map(
        lambda x: x + 0.05 * jax.random.normal(jax.random.PRNGKey(x.size),
                                               x.shape, x.dtype), params)


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    return cfg, seeded_params(cfg)


@pytest.fixture(scope="module")
def batch():
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 49), 1, 512)
    mask = (jax.random.uniform(jax.random.PRNGKey(9), (2, 48)) > 0.2
            ).astype(jnp.float32)
    return tokens, mask


def program_logits(params, tokens, cfg):
    return lm.model_forward(params, tokens, cfg)[0][..., :cfg.vocab_size]


def test_logits_match_reference(model, batch):
    cfg, params = model
    tokens, _ = batch
    want = jnp.stack([reference.logits(params, t[:-1], cfg) for t in tokens])
    np.testing.assert_allclose(program_logits(params, tokens[:, :-1], cfg),
                               want, **TOL)


def test_loss_and_gradients_match_reference(model, batch):
    """`jax.grad` through the dropless path (sort, gather, grouped product,
    scatter back) against the reference's all-experts loop. The router's
    balancing term is the program's alone: coefficient 0 here."""
    cfg, params = model
    cfg = dataclasses.replace(cfg, moe_aux_loss_coeff=0.0)
    tokens, mask = batch

    def program(p):
        return jnp.mean(jnp.stack([
            lm.loss_fn(p, tokens[i:i + 1], cfg, loss_mask=mask[i:i + 1])
            for i in range(2)]))

    lw, gw = jax.value_and_grad(program)(params)
    lg, gg = jax.value_and_grad(
        lambda p: reference.batch_loss(p, tokens, mask, cfg))(params)
    assert abs(float(lw) - float(lg)) < 1e-5
    for a, b in zip(jax.tree.leaves(gg), jax.tree.leaves(gw)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-6)


@pytest.mark.parametrize("change", ["batch_mates", "row_length", "padding"])
def test_a_token_depends_on_no_other_token(model, batch, change):
    """What capacity routing lacks: the first 20 positions of a row give
    the same logits whoever shares the batch, however long the row is, and
    whatever pads its bucket (causal attention hides what comes after)."""
    cfg, params = model
    row = batch[0][0, :20]
    alone = program_logits(params, row[None], cfg)[0]
    if change == "batch_mates":
        others = jax.random.randint(jax.random.PRNGKey(1), (3, 20), 1, 512)
        got = program_logits(
            params, jnp.concatenate([others[:2], row[None], others[2:]]),
            cfg)[2]
    elif change == "row_length":
        got = program_logits(params, batch[0][:1, :48], cfg)[0, :20]
    else:
        padded = jnp.concatenate([row, jnp.zeros((44,), row.dtype)])
        got = program_logits(params, padded[None], cfg)[0, :20]
    np.testing.assert_allclose(got, alone, rtol=1e-5, atol=1e-5)


def test_capacity_routing_does_depend_on_batch_mates(model, batch):
    """The property above is the dropless path's, not a weak test's."""
    cfg, params = model
    cfg = dataclasses.replace(cfg, moe_dispatch="sort",
                              moe_capacity_factor=1.0)
    params = lm.model_init(jax.random.PRNGKey(7), cfg)
    row = batch[0][0, :20]
    alone = program_logits(params, row[None], cfg)[0]
    longer = program_logits(params, batch[0][:1, :48], cfg)[0, :20]
    assert float(jnp.abs(alone - longer).max()) > 1e-3


def test_dropless_equals_sort_path_with_room_for_all(model, batch):
    cfg, params = model
    roomy = dataclasses.replace(
        cfg, moe_dispatch="sort",
        moe_capacity_factor=cfg.num_experts / cfg.moe_top_k)
    tokens = batch[0][:, :-1]
    # one parameter tree serves both: the dispatch is a runtime option and
    # changes no stored layout
    np.testing.assert_allclose(program_logits(params, tokens, cfg),
                               program_logits(params, tokens, roomy),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("norm_topk_prob", [False, True])
def test_gates_follow_norm_topk_prob(norm_topk_prob):
    """One expert bank, by hand: with the field off the K weights are the
    softmax's own values (their sum is under 1), with it on they sum to 1."""
    cfg = tiny(moe_norm_topk_prob=norm_topk_prob)
    p = moe_init(jax.random.PRNGKey(3), cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, cfg.hidden_size))
    y, _ = moe_apply(p, x, cfg)
    probs = jax.nn.softmax(x.reshape(18, -1) @ p["router"], axis=-1)
    top, idx = jax.lax.top_k(probs, cfg.moe_top_k)
    assert float(top.sum(-1).max()) < 0.9          # far from renormalised
    if norm_topk_prob:
        top = top / top.sum(-1, keepdims=True)
    want = jnp.zeros((18, cfg.hidden_size))
    for k in range(cfg.moe_top_k):
        for t in range(18):
            e = int(idx[t, k])
            g, u = (p["w1"][e, :, :cfg.ffn_hidden_size],
                    p["w1"][e, :, cfg.ffn_hidden_size:])
            out = (jax.nn.silu(x.reshape(18, -1)[t] @ g)
                   * (x.reshape(18, -1)[t] @ u)) @ p["w2"][e]
            want = want.at[t].add(top[t, k] * out)
    np.testing.assert_allclose(y.reshape(18, -1), want, rtol=1e-4, atol=1e-6)


def test_norm_topk_prob_on_matches_reference_and_differs_from_off(batch):
    cfg = tiny(moe_norm_topk_prob=True)
    params = seeded_params(cfg)
    tokens = batch[0][0, :-1]
    on = program_logits(params, tokens[None], cfg)[0]
    np.testing.assert_allclose(on, reference.logits(params, tokens, cfg),
                               **TOL)
    off = program_logits(params, tokens[None], tiny())[0]
    assert float(jnp.abs(on - off).max()) > 1e-2


def test_qk_norm_on_matches_reference_and_differs_from_off(model, batch):
    cfg, params = model
    tokens = batch[0][0, :-1]
    on = program_logits(params, tokens[None], cfg)[0]
    np.testing.assert_allclose(on, reference.logits(params, tokens, cfg),
                               **TOL)
    plain = tiny(qk_norm=False)
    assert "q_norm" not in lm.model_init(
        jax.random.PRNGKey(0), plain)["transformer"]["attention"]
    off = program_logits(params, tokens[None], plain)[0]
    np.testing.assert_allclose(off, reference.logits(params, tokens, plain),
                               **TOL)
    assert float(jnp.abs(on - off).max()) > 1e-2


def _engine_logprobs(eng, prompt, n_new):
    req = eng.submit(prompt, n_new, SamplingOptions(temperature=0.0), seed=0)
    tokens, _ = req.result(timeout=300)
    return req, tokens, np.asarray(req.gen_logprobs, np.float64)


def _reference_logprobs(params, cfg, tokens, n_prompt):
    return np.asarray(reference.token_logprobs(
        params, jnp.asarray(tokens, jnp.int32), cfg),
        np.float64)[n_prompt - 1:]


@pytest.mark.parametrize("how", ["plain", "chunked_prefill", "prefix_hit"])
def test_engine_prefill_and_decode_match_reference(model, how):
    """`ServingEngine` over `Generator`: a prompt prefilled in a padded
    bucket (37 tokens in 48), then decoded through the cache one token at a
    time beside an unrelated request, gives the reference's full forward of
    prompt + output. Compared on the log-probabilities of the engine's own
    greedy tokens, never on the tokens."""
    cfg, params = model
    gen = Generator(params, cfg, eos_id=-1, pad_id=0,
                    kv_cache_dtype=jnp.float32)
    serving = dict(num_slots=3, max_queue=8, max_len=96, prefill_bucket=16)
    if how == "chunked_prefill":
        serving.update(prefill_chunk=16)
    if how == "prefix_hit":
        serving.update(enable_prefix_cache=True)
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, cfg.vocab_size, size=37).tolist()
    other = rng.integers(1, cfg.vocab_size, size=21).tolist()
    with ServingEngine(gen, ServingConfig(**serving).validate(cfg)) as eng:
        noise = eng.submit(other, 20, SamplingOptions(temperature=1.0),
                           seed=3)
        if how == "prefix_hit":
            first = prompt[:32] + rng.integers(1, 512, size=4).tolist()
            _engine_logprobs(eng, first, 2)
        req, tokens, got = _engine_logprobs(eng, prompt, 12)
        noise.result(timeout=300)
        snap = eng.metrics.snapshot()
    if how == "prefix_hit":
        assert snap["prefix_hits"] >= 1 and req.prefix_len >= 16
    assert len(got) == 12 and tokens[:37] == prompt
    want = _reference_logprobs(params, cfg, tokens, len(prompt))
    np.testing.assert_allclose(got, want, rtol=0, atol=5e-4)


def test_preset_fields_equal_the_published_config():
    """The preset against the published `config.json` as the repo holds it
    (the benchmark's configuration file, whose only cut is the depth)."""
    with open(PUBLISHED) as f:
        hf = json.load(f)
    hf.update(hf["published"])
    cfg = MODEL_PRESETS["olmoe-1b-7b"]()
    assert (cfg.num_layers, cfg.hidden_size, cfg.ffn_hidden_size) == (
        hf["num_hidden_layers"], hf["hidden_size"], hf["intermediate_size"])
    assert (cfg.num_attention_heads, cfg.num_kv_heads, cfg.kv_channels) == (
        hf["num_attention_heads"], hf["num_key_value_heads"],
        hf["hidden_size"] // hf["num_attention_heads"])
    assert (cfg.num_experts, cfg.moe_top_k, cfg.moe_norm_topk_prob) == (
        hf["num_experts"], hf["num_experts_per_tok"], hf["norm_topk_prob"])
    assert (cfg.vocab_size, cfg.padded_vocab_size,
            cfg.max_position_embeddings) == (
        hf["vocab_size"], hf["vocab_size"], hf["max_position_embeddings"])
    assert (cfg.norm_type, cfg.norm_epsilon, cfg.rope_theta) == (
        "rmsnorm", hf["rms_norm_eps"], hf["rope_theta"])
    assert cfg.activation == "swiglu" and hf["hidden_act"] == "silu"
    assert cfg.tie_embed_logits == hf["tie_word_embeddings"]
    assert cfg.use_bias == hf["attention_bias"]
    assert cfg.qk_norm and cfg.moe_dispatch == "dropless"


def test_preset_through_parse_cli():
    cfg, _ = parse_cli(["--model", "olmoe-1b-7b", "--num_layers", "4",
                        "--bf16"], n_devices=1)
    m = cfg.model
    assert (m.num_layers, m.num_experts, m.moe_dispatch, m.qk_norm) == (
        4, 64, "dropless", True)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(
        jax.eval_shape(lambda: lm.model_init(jax.random.PRNGKey(0), m))))
    assert n == 4 * 419_569_664 + 2 * 50304 * 2048 + 2048


@pytest.mark.parametrize("what", ["dropless_tp", "qk_norm_tp", "serving_tp",
                                  "dropless_dp"])
def test_validate_refuses_what_has_not_been_made_to_work(what):
    m = tiny()
    if what == "serving_tp":
        with pytest.raises(AssertionError, match="width 1"):
            ServingConfig(serving_tp=2).validate(m)
        return
    if what == "qk_norm_tp":
        m = dataclasses.replace(m, moe_dispatch="sort")
    par = (ParallelConfig(data_parallel=2) if what == "dropless_dp"
           else ParallelConfig(tensor_parallel=2))
    with pytest.raises(AssertionError,
                       match="dropless" if "dropless" in what else "qk_norm"):
        MegatronConfig(model=m, parallel=par).validate(n_devices=2)
