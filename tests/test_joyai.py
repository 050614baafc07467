"""JoyAI-LLM-Flash on the normal path (PR 31): latent attention in its two
forms, the sigmoid router with a choosing bias beside a shared expert, a
dense layer ahead of the expert layers, the MTP term of the training loss,
each against the plain float32 reference (`benchmark/reference/joyai.py`) on
the tiny preset with seeded weights and float32 compute. The serving side is
tests/test_joyai_serving.py."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import joyai as reference
from megatron_tpu.arguments import parse_cli
from megatron_tpu.config import (MODEL_PRESETS, MegatronConfig,
                                 ParallelConfig, ServingConfig)
from megatron_tpu.models import language_model as lm
from megatron_tpu.models import mla
from megatron_tpu.models.moe import moe_apply

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED = os.path.join(REPO, "benchmark", "configs",
                         "joyai-llm-flash-5l.json")


def tiny(**overrides):
    return dataclasses.replace(MODEL_PRESETS["joyai-llm-flash-tiny"](),
                               compute_dtype="float32", **overrides)


def seeded(cfg, seed=0):
    """The initialiser's weights with what makes the test tell things apart:
    a non-zero choosing bias, and the embedding at a scale at which tokens
    route apart."""
    params = lm.model_init(jax.random.PRNGKey(seed), cfg)
    for mlp in (params["transformer"]["moe"]["mlp"],
                params["mtp"]["layer"]["mlp"]):
        mlp["e_score_correction_bias"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(seed + 5),
            mlp["e_score_correction_bias"].shape)
    params["embedding"]["word_embeddings"] *= 50.0
    return params


@pytest.fixture(scope="module")
def model():
    cfg = tiny()
    return cfg, seeded(cfg)


# ---------------------------------------------------------------------------
# (a) the two forms of the attention
# ---------------------------------------------------------------------------

def _attention_case(cfg, b=2, s=16):
    params = mla.mla_init(jax.random.PRNGKey(1), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (b, s, cfg.hidden_size))
    rope = lm.make_rope(cfg, 64)
    want = np.stack([np.asarray(reference.attention(params, x[i], cfg))
                     for i in range(b)])
    return params, x, rope, want


@pytest.mark.parametrize("impl", ["dot", "flash"])
def test_expanded_form_is_the_references_attention(impl):
    """No cache: the expanded form; through the flash path the heads are
    padded with zeros to the kernel's one width and the result is the
    unpadded mathematics."""
    cfg = tiny(attention_impl=impl)
    params, x, rope, want = _attention_case(cfg)
    got, _ = mla.mla_apply(params, x, cfg, rope_cos=rope.cos,
                           rope_sin=rope.sin)
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-6)


@pytest.mark.parametrize("offsets", ["scalar", "per_slot"])
def test_absorbed_form_is_the_expanded_form(offsets):
    """Through the latent cache: a prefill at offset 0 (expanded, under the
    `cond`), then a continuation chunk at a non-zero scalar offset, or, on
    the slot grid, a decode step and a verify window at each row's own
    offset (absorbed): every position equals the reference's attention of
    the whole sequence."""
    cfg = tiny()
    params, x, rope, want = _attention_case(cfg)
    kw = dict(rope_cos=rope.cos, rope_sin=rope.sin, cache_layer=1)
    cache = mla.LatentKVCache.create(3, 2, 32, cfg.kv_row_width, jnp.float32)
    got, cache = mla.mla_apply(params, x[:, :9], cfg, kv_cache=cache, **kw)
    np.testing.assert_allclose(got, want[:, :9], rtol=0, atol=2e-6)
    assert cache.c.shape == (3, 2, 40, 32)
    assert not np.asarray(cache.c[0]).any() and not np.asarray(cache.c[2]).any()
    if offsets == "scalar":
        got, cache = mla.mla_apply(params, x[:, 9:], cfg, kv_cache=cache, **kw)
        np.testing.assert_allclose(got, want[:, 9:], rtol=0, atol=2e-6)
        assert cache.offset.tolist() == [0, 16, 0]
        return
    # the slot grid: row 0 stands at 9, row 1 at 5 (its rows past 5 are the
    # garbage a bucket's padding leaves, overwritten before they are read)
    at = jnp.array([9, 5])
    grid = cache._replace(offset=jnp.broadcast_to(at, (3, 2)))
    step = jnp.stack([x[0, 9:10], x[1, 5:6]])
    got, grid = mla.mla_apply(params, step, cfg, kv_cache=grid, **kw)
    np.testing.assert_allclose(got[0, 0], want[0, 9], rtol=0, atol=2e-6)
    np.testing.assert_allclose(got[1, 0], want[1, 5], rtol=0, atol=2e-6)
    assert grid.offset[1].tolist() == [10, 6]
    window = jnp.stack([x[0, 10:13], x[1, 6:9]])
    got, grid = mla.mla_apply(params, window, cfg, kv_cache=grid, **kw)
    np.testing.assert_allclose(got[0], want[0, 10:13], rtol=0, atol=2e-6)
    np.testing.assert_allclose(got[1], want[1, 6:9], rtol=0, atol=2e-6)


def test_absorbed_form_in_query_blocks(monkeypatch):
    """A chunk of more queries than a block runs a block at a time."""
    cfg = tiny()
    params, x, rope, want = _attention_case(cfg)
    monkeypatch.setattr(mla, "ABSORBED_Q_BLOCK", 4)
    kw = dict(rope_cos=rope.cos, rope_sin=rope.sin, cache_layer=0)
    cache = mla.LatentKVCache.create(1, 2, 32, cfg.kv_row_width, jnp.float32)
    _, cache = mla.mla_apply(params, x[:, :4], cfg, kv_cache=cache, **kw)
    got, _ = mla.mla_apply(params, x[:, 4:], cfg, kv_cache=cache, **kw)
    np.testing.assert_allclose(got, want[:, 4:], rtol=0, atol=2e-6)


def _small_blocks(monkeypatch, q_block=4, key_block=8):
    monkeypatch.setattr(mla, "ABSORBED_Q_BLOCK", q_block)
    monkeypatch.setattr(mla, "ABSORBED_KEY_BLOCK", key_block)


@pytest.mark.parametrize("first, rows, t, why", [
    (8, 8, 32, "the offset a multiple of the key block"),
    (5, 8, 32, "an offset inside a key block"),
    (6, 4, 32, "one query block, 6..9, across the edge of key block 0"),
    (4, 8, 12, "a region the key block does not divide: the last block "
               "starts where it fits and counts no position twice"),
    (8, 8, 16, "the chunk ends where the region does"),
    (8, 8, 5 * 8, "the region's last blocks never reached"),
])
def test_absorbed_form_in_key_blocks(monkeypatch, first, rows, t, why):
    """A block of queries reads the cached positions a key block at a time
    and stops at the last block any of its queries can see: the same
    numbers as the forward without a cache."""
    cfg = tiny()
    params, x, rope, want = _attention_case(cfg)
    _small_blocks(monkeypatch)
    kw = dict(rope_cos=rope.cos, rope_sin=rope.sin, cache_layer=1)
    cache = mla.LatentKVCache.create(2, 2, t, cfg.kv_row_width, jnp.float32)
    _, cache = mla.mla_apply(params, x[:, :first], cfg, kv_cache=cache, **kw)
    got, cache = mla.mla_apply(params, x[:, first:first + rows], cfg,
                               kv_cache=cache, **kw)
    np.testing.assert_allclose(got, want[:, first:first + rows], rtol=0,
                               atol=2e-6, err_msg=why)
    assert cache.offset.tolist() == [0, first + rows]


def test_key_blocks_of_a_padded_last_chunk(monkeypatch):
    """A last chunk padded to its bucket: the padding rows stand past the
    real length (their bound reaches further than any real row's) and
    change no real row."""
    cfg = tiny()
    params, x, rope, want = _attention_case(cfg)
    _small_blocks(monkeypatch)
    kw = dict(rope_cos=rope.cos, rope_sin=rope.sin, cache_layer=0)
    cache = mla.LatentKVCache.create(1, 2, 32, cfg.kv_row_width, jnp.float32)
    _, cache = mla.mla_apply(params, x[:, :7], cfg, kv_cache=cache, **kw)
    padded = jnp.concatenate([x[:, 7:10], jnp.full_like(x[:, :5], 7.0)],
                             axis=1)
    got, _ = mla.mla_apply(params, padded, cfg, kv_cache=cache, **kw)
    np.testing.assert_allclose(got[:, :3], want[:, 7:10], rtol=0, atol=2e-6)
    assert np.isfinite(np.asarray(got)).all()


def test_key_blocks_bound_is_clamped_at_the_region(monkeypatch):
    """On the slot grid a row parked at the capacity has queries past the
    region: its bound is the region, and the row beside it is exact."""
    cfg = tiny()
    params, x, rope, want = _attention_case(cfg)
    _small_blocks(monkeypatch)
    assert mla.absorbed_key_blocks(16 + 7, 16) == 2
    assert mla.absorbed_key_blocks(np.array([0, 7, 8, 40]), 20).tolist() \
        == [1, 1, 2, 3]
    kw = dict(rope_cos=rope.cos, rope_sin=rope.sin, cache_layer=0)
    cache = mla.LatentKVCache.create(1, 2, 16, cfg.kv_row_width, jnp.float32)
    _, cache = mla.mla_apply(params, x[:, :4], cfg, kv_cache=cache, **kw)
    grid = cache._replace(offset=jnp.array([[4, 16]]))
    got, _ = mla.mla_apply(params, x[:, 4:12], cfg, kv_cache=grid, **kw)
    np.testing.assert_allclose(got[0], want[0, 4:12], rtol=0, atol=2e-6)
    assert np.isfinite(np.asarray(got[1])).all()


def test_key_blocks_past_the_last_visible_one_are_not_read(monkeypatch):
    """The proof that the read stops: every key block past the last one a
    chunk may see is filled with NaN, and the output is the same finite
    numbers (read and masked, a NaN would come through the weighted sum as
    0 x NaN). And block by block of queries: with block 3 poisoned, the
    queries that see blocks 0..2 alone are untouched."""
    cfg = tiny()
    params, x, rope, want = _attention_case(cfg)
    _small_blocks(monkeypatch, key_block=4)
    kw = dict(rope_cos=rope.cos, rope_sin=rope.sin, cache_layer=0)
    cache = mla.LatentKVCache.create(1, 2, 32, cfg.kv_row_width, jnp.float32)
    _, cache = mla.mla_apply(params, x[:, :8], cfg, kv_cache=cache, **kw)
    poisoned = cache._replace(c=cache.c.at[..., 16:].set(jnp.nan))
    got, after = mla.mla_apply(params, x[:, 8:], cfg, kv_cache=poisoned, **kw)
    np.testing.assert_allclose(got, want[:, 8:], rtol=0, atol=2e-6)

    # queries 8..11 see key blocks 0..2, queries 12..15 block 3 too
    scale = 1.0 / np.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    q = jax.random.normal(jax.random.PRNGKey(4), (
        2, 8, cfg.num_attention_heads,
        cfg.qk_nope_head_dim + cfg.qk_rope_head_dim))
    wkv_b = params["wkv_b"]
    clean = mla._attend_absorbed(q, after.c[..., :16], 0, wkv_b, cfg, scale,
                                 8 + jnp.arange(8)[None])
    assert np.isfinite(np.asarray(clean)).all()
    stack = after.c[..., :16].at[..., 12:].set(jnp.nan)
    got = mla._attend_absorbed(q, stack, 0, wkv_b, cfg, scale,
                               8 + jnp.arange(8)[None])
    np.testing.assert_array_equal(got[:, :4], clean[:, :4])
    assert np.isnan(np.asarray(got[:, 4:])).all()


# ---------------------------------------------------------------------------
# (b) the router and the shared expert
# ---------------------------------------------------------------------------

def test_router_chooses_by_the_bias_and_values_by_the_score(model):
    cfg, params = model
    moe_cfg = dataclasses.replace(cfg, first_k_dense_replace=0)
    mlp = jax.tree.map(lambda x: x[1], params["transformer"]["moe"]["mlp"])
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(3), (1, 64, 64))
    got, _ = moe_apply(mlp, x, moe_cfg)
    want, w = reference.experts(mlp, x[0], cfg)
    np.testing.assert_allclose(got[0], want, rtol=0, atol=2e-5)
    # the bias changed some token's set, and is in no gate's value
    scores = jax.nn.sigmoid(x[0] @ mlp["router"])
    unbiased = jax.lax.top_k(scores, cfg.moe_top_k)[1]
    chosen = np.asarray(w > 0)
    assert (chosen.sum(axis=1) == cfg.moe_top_k).all()
    assert any(set(np.flatnonzero(chosen[i])) != set(unbiased[i].tolist())
               for i in range(64))
    for i in range(64):
        g = np.asarray(scores[i])[chosen[i]]
        np.testing.assert_allclose(np.asarray(w[i])[chosen[i]],
                                   g / g.sum() * 2.5, rtol=1e-6)
    # the shared expert is counted once, beside the routed sum
    bare = dataclasses.replace(moe_cfg, n_shared_experts=0)
    routed, _ = moe_apply({k: v for k, v in mlp.items() if k != "shared"},
                          x, bare)
    np.testing.assert_allclose(
        got[0] - routed[0], reference._dense_mlp(mlp["shared"], x[0]),
        rtol=0, atol=2e-5)


# ---------------------------------------------------------------------------
# (c) the whole model, (e) the training loss
# ---------------------------------------------------------------------------

def test_logits_match_reference(model):
    """One dense layer, three expert layers, through `model_forward`."""
    cfg, params = model
    assert jax.tree.leaves(params["transformer"]["dense"])[0].shape[0] == 1
    assert jax.tree.leaves(params["transformer"]["moe"])[0].shape[0] == 3
    tokens = jax.random.randint(jax.random.PRNGKey(1), (33,), 0, 512)
    logits, _ = lm.model_forward(params, tokens[None, :-1], cfg)
    got = jnp.take_along_axis(jax.nn.log_softmax(logits[0, :, :512], -1),
                              tokens[1:, None], -1)[:, 0]
    want = reference.token_logprobs(params, tokens, cfg)
    np.testing.assert_allclose(got, want, rtol=0, atol=5e-6)
    np.testing.assert_allclose(
        reference.token_logprobs(params, tokens, cfg, tail=7), want[-7:],
        rtol=0, atol=1e-6)


def test_cached_forward_equals_plain_forward(model):
    """A prefill and decode steps through `model_forward`'s latent cache,
    the running layer index through both stacks."""
    from megatron_tpu.inference.generation import init_kv_caches
    cfg, params = model
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 20), 0, 512)
    plain, _ = lm.model_forward(params, tokens, cfg)
    caches = init_kv_caches(cfg, 2, 32, dtype=jnp.float32)
    assert isinstance(caches, mla.LatentKVCache)
    assert caches.c.shape == (4, 2, 40, 32)
    got, caches = lm.model_forward(params, tokens[:, :16], cfg,
                                   kv_caches=caches)
    steps = [got]
    for i in range(16, 20):
        out, caches = lm.model_forward(params, tokens[:, i:i + 1], cfg,
                                       kv_caches=caches)
        steps.append(out)
    np.testing.assert_allclose(jnp.concatenate(steps, axis=1), plain,
                               rtol=0, atol=2e-5)
    assert caches.offset.tolist() == [20] * 4


def test_loss_and_gradients_match_reference(model):
    cfg, params = model
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 33), 0, 512)
    mask = (jax.random.uniform(jax.random.PRNGKey(3), (2, 32)) > 0.2
            ).astype(jnp.float32)
    loss, grads = jax.value_and_grad(
        lambda p: lm.loss_fn(p, tokens, cfg, loss_mask=mask))(params)
    want, want_grads = reference.loss_and_grads(params, tokens, mask, cfg)
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=0, atol=1e-6, err_msg=str(path))
    # the MTP term is there, its module learns, and the bias that only
    # chooses receives nothing
    no_mtp = lm.loss_fn(params, tokens, dataclasses.replace(
        cfg, mtp_loss_coeff=0.0), loss_mask=mask)
    assert float(loss) > float(no_mtp) + 1.0
    assert float(jnp.abs(grads["mtp"]["eh_proj"]).max()) > 0
    assert not np.asarray(
        grads["transformer"]["moe"]["mlp"]["e_score_correction_bias"]).any()


def test_mtp_module_is_not_in_the_models_own_logits(model):
    """What a server loads: the tree without the module gives the same
    logits, and no gradient of them reaches it."""
    cfg, params = model
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 24), 0, 512)
    full, _ = lm.model_forward(params, tokens, cfg)
    served = {k: v for k, v in params.items() if k != "mtp"}
    np.testing.assert_array_equal(full, lm.model_forward(served, tokens,
                                                         cfg)[0])
    g = jax.grad(lambda p: lm.model_forward(p, tokens, cfg)[0].sum())(params)
    assert not any(np.asarray(x).any() for x in jax.tree.leaves(g["mtp"]))


def test_head_held_in_the_compute_dtype_keeps_its_float32_accumulator():
    """A bf16 head's logits come out of the product in float32 (a step of
    bf16 at the top logit is 0.03); a float32 head keeps the program it had
    (tests/test_jaxpr_unchanged.py)."""
    cfg = dataclasses.replace(MODEL_PRESETS["joyai-llm-flash-tiny"](),
                              params_dtype="bfloat16")
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    x = 8.0 * jax.random.normal(jax.random.PRNGKey(1), (1, 16, 64),
                                jnp.bfloat16)
    got = lm.head_logits(params, x, cfg)
    assert got.dtype == jnp.float32
    normed = lm.apply_norm(cfg.norm_type, params["final_norm"], x,
                           cfg.norm_epsilon).astype(jnp.float32)
    exact = normed @ params["lm_head"].astype(jnp.float32)
    np.testing.assert_allclose(got, exact, rtol=0, atol=1e-5)
    rounded = exact.astype(jnp.bfloat16).astype(jnp.float32)
    assert float(jnp.abs(rounded - exact).max()) > 1e-4


def test_bank_in_the_compute_dtype_is_read_in_place():
    """A prefill over banks held in the compute dtype gives the stack and
    the layer's index to the grouped product; over float32 banks it rounds
    the layer's banks first (OLMoE's programs, unchanged)."""
    from megatron_tpu.inference.generation import init_kv_caches
    from megatron_tpu.ops import grouped_matmul as gm
    seen = []
    real = gm.grouped_matmul

    def spy(lhs, rhs, sizes, **kw):
        seen.append((rhs.ndim, str(rhs.dtype)))
        return real(lhs, rhs, sizes, **kw)
    for held in ("bfloat16", "float32"):
        cfg = dataclasses.replace(MODEL_PRESETS["joyai-llm-flash-tiny"](),
                                  params_dtype=held)
        params = jax.eval_shape(
            lambda: lm.model_init(jax.random.PRNGKey(0), cfg))
        seen.clear()
        gm.grouped_matmul = spy
        try:
            jax.eval_shape(
                lambda p, t: lm.model_forward(
                    p, t, cfg, kv_caches=init_kv_caches(cfg, 1, 32)),
                params, jnp.zeros((1, 16), jnp.int32))
        finally:
            gm.grouped_matmul = real
        assert seen == [(4, held)] * 2 if held == "bfloat16" \
            else seen == [(3, "bfloat16")] * 2, (held, seen)


# ---------------------------------------------------------------------------
# the presets
# ---------------------------------------------------------------------------

def test_preset_fields_equal_the_published_config():
    with open(PUBLISHED) as f:
        hf = json.load(f)
    hf.update(hf["published"])
    cfg = MODEL_PRESETS["joyai-llm-flash"]()
    assert (cfg.num_layers, cfg.hidden_size, cfg.dense_ffn_hidden_size,
            cfg.ffn_hidden_size) == (
        hf["num_hidden_layers"], hf["hidden_size"], hf["intermediate_size"],
        hf["moe_intermediate_size"])
    assert (cfg.num_attention_heads, cfg.num_kv_heads, cfg.kv_channels) == (
        hf["num_attention_heads"], hf["num_key_value_heads"], hf["head_dim"])
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (
        hf["q_lora_rank"], hf["kv_lora_rank"], hf["qk_nope_head_dim"],
        hf["qk_rope_head_dim"], hf["v_head_dim"])
    assert cfg.qk_nope_head_dim + cfg.qk_rope_head_dim == hf["qk_head_dim"]
    assert (cfg.num_experts, cfg.moe_top_k, cfg.n_shared_experts,
            cfg.first_k_dense_replace, cfg.moe_norm_topk_prob,
            cfg.moe_routed_scaling_factor, cfg.moe_scoring_func) == (
        hf["n_routed_experts"], hf["num_experts_per_tok"],
        hf["n_shared_experts"], hf["first_k_dense_replace"],
        hf["norm_topk_prob"], hf["routed_scaling_factor"],
        hf["scoring_func"])
    assert hf["num_experts"] == hf["n_routed_experts"]
    assert cfg.moe_score_correction_bias and hf["topk_method"] == "noaux_tc"
    assert (hf["n_group"], hf["topk_group"], hf["moe_layer_freq"]) == (1, 1, 1)
    assert cfg.mtp_num_layers == hf["num_nextn_predict_layers"]
    assert (cfg.vocab_size, cfg.padded_vocab_size,
            cfg.max_position_embeddings) == (
        hf["vocab_size"], hf["vocab_size"], hf["max_position_embeddings"])
    assert (cfg.norm_type, cfg.norm_epsilon, cfg.rope_theta,
            cfg.rope_scaling_factor) == (
        "rmsnorm", hf["rms_norm_eps"], hf["rope_theta"], 1.0)
    assert hf["rope_scaling"] is None and hf["rope_interleave"] is True
    assert cfg.activation == "swiglu" and hf["hidden_act"] == "silu"
    assert cfg.tie_embed_logits == hf["tie_word_embeddings"]
    assert cfg.use_bias == hf["attention_bias"]
    assert cfg.params_dtype == "bfloat16" and cfg.moe_dispatch == "dropless"
    assert cfg.moe_aux_loss_coeff == 0.0


def test_preset_through_parse_cli():
    """The benchmark's configuration as its `cli` builds it: the parameter
    counts of ISSUE 31, and the cache's row."""
    with open(PUBLISHED) as f:
        cli = json.load(f)["cli"]
    m = parse_cli([*cli, "--bf16"], n_devices=1)[0].model
    assert (m.num_layers, m.first_k_dense_replace, m.num_experts) == (
        5, 1, 256)
    shapes = jax.eval_shape(lambda: lm.model_init(jax.random.PRNGKey(0), m))
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))
    per = lambda t, n: count(t) // n
    mla_n, norms = 26_345_472 + 1536 + 512, 2 * 2048
    assert per(shapes["transformer"]["dense"], 1) == (
        mla_n + norms + 3 * 2048 * 7168)
    assert per(shapes["transformer"]["moe"], 4) == (
        mla_n + norms + 256 * 4_718_592 + 4_718_592 + 2048 * 256 + 256)
    assert count(shapes["embedding"]) + count(shapes["lm_head"]) == 529_530_880
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(shapes))
    assert m.kv_row_width == 576


# ---------------------------------------------------------------------------
# (g) what is refused, by name
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("what, match", [
    (dict(parallel=ParallelConfig(tensor_parallel=2)), "one device only"),
    (dict(parallel=ParallelConfig(context_parallel=2)), "one device only"),
    (dict(parallel=ParallelConfig(pipeline_parallel=2)), "one device only"),
    (dict(model=dict(sliding_window=16)), "sliding_window"),
    (dict(model=dict(kv_channels=16)), "rotary"),
    # (None is ONE query matrix since PR 58: tests/test_kimi_linear.py)
    (dict(model=dict(q_lora_rank=0)), "q_lora_rank"),
    (dict(model=dict(mtp_num_layers=2)), "depth 1"),
    (dict(model=dict(moe_dispatch="sort", moe_capacity_factor=4.0)),
     "dropless router"),
    (dict(model=dict(first_k_dense_replace=4)), "first_k_dense_replace"),
])
def test_validate_refuses_by_name(what, match):
    m = tiny(**what.get("model", {}))
    with pytest.raises(AssertionError, match=match):
        MegatronConfig(model=m, parallel=what.get(
            "parallel", ParallelConfig())).validate(
                n_devices=2 if "parallel" in what else 1)


def test_two_stacks_without_mla_refuse_a_pipeline():
    m = dataclasses.replace(
        tiny(), kv_lora_rank=None, q_lora_rank=None, qk_nope_head_dim=None,
        qk_rope_head_dim=None, v_head_dim=None, kv_channels=16,
        moe_dispatch="sort", moe_scoring_func="softmax",
        moe_routed_scaling_factor=1.0, moe_score_correction_bias=False,
        n_shared_experts=0)
    with pytest.raises(AssertionError, match="two stacks"):
        MegatronConfig(model=m, parallel=ParallelConfig(
            pipeline_parallel=2)).validate(n_devices=2)


@pytest.mark.parametrize("serving, match", [
    (dict(serving_tp=2), "no head axis"),
    (dict(decode_tp=2, prefill_tp=1, disaggregate_prefill=True,
          kv_block_size=16), "no head axis"),
    (dict(serving_pp=2, kv_block_size=16), "no head axis"),
    (dict(kv_block_size=16), "kv_block_size"),
    (dict(kv_block_size=16, block_native_attn=True), "block_native_attn"),
    (dict(kv_dtype="int8"), "int8"),
    (dict(disaggregate_prefill=True), "disaggregate_prefill"),
    (dict(host_kv_bytes=1 << 20, enable_prefix_cache=True), "host tier"),
    (dict(adapter_slots=2), "adapter_slots"),
])
def test_serving_validate_refuses_by_name(serving, match):
    with pytest.raises(AssertionError, match=match):
        ServingConfig(max_len=64, **serving).validate(tiny())


def test_finetune_reaches_the_mtp_loss_on_one_device(tmp_path):
    """`finetune.py --model joyai-llm-flash-tiny` on one device: the whole
    argparse -> loop surface, the loss with its MTP term (ln 512 x 1.3 at the
    start), a checkpoint of the tree with two stacks and the module."""
    import subprocess
    import sys
    from megatron_tpu.data.indexed_dataset import IndexedDatasetBuilder
    prefix = str(tmp_path / "tiny_document")
    rng = np.random.default_rng(0)
    b = IndexedDatasetBuilder(prefix, dtype=np.uint16)
    for _ in range(100):
        b.add_item(rng.integers(0, 500, rng.integers(8, 40)).tolist())
        b.end_document()
    b.finalize()
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "finetune.py"),
         "--model", "joyai-llm-flash-tiny", "--seq_length", "32",
         "--micro_batch_size", "2", "--global_batch_size", "2",
         "--lr", "1e-3", "--data_path", prefix, "--split", "90,10,0",
         "--log_interval", "1", "--eval_interval", "1000",
         "--train_iters", "3", "--save", str(tmp_path / "ckpt"),
         "--save_interval", "3"],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    losses = [float(line.split("lm loss:")[1].split("|")[0])
              for line in (p.stdout + p.stderr).splitlines()
              if "lm loss:" in line]
    assert len(losses) >= 3 and 7.5 < losses[0] < 8.7, losses
    assert os.path.exists(tmp_path / "ckpt" / "iter_0000003")
