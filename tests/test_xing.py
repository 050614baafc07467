"""Xing4.0-29B-A4B on the normal path (PR 41): the residual of `hc_mult`
streams mixed by Sinkhorn-constrained hyper-connections round every MLA and
feed-forward sublayer, YaRN's blended frequencies and its factor on MLA's
softmax scale, the MTP term of the training loss, each against the plain
float32 reference (`benchmark/reference/xing4.py`) on the tiny preset with
seeded weights and float32 compute. The serving side is
tests/test_xing_serving.py.

Tolerances, all float32 on the CPU against a float32 reference: 2e-6 where
two sums of a few hundred float32 terms are compared (rope tables, the
maps); 2e-5 on log-probabilities and logits through five layers (the
reference sums experts and heads in another order; JoyAI's tests hold 5e-6
to 2e-5 on four layers without the mixes); 1e-5 on the sums of H_res's rows
and columns (twenty rounds leave hc_eps 1e-6 times the 4 entries in each
sum); gradients 5e-5 absolute + 1e-4 relative (the fixture's scaled-up
weights give gradients up to 0.3, summed over 64 positions and six blocks in
another order than the reference's). A planted fault reads 1e-2 to 3e-1 on
the forward comparison."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import xing4 as reference
from megatron_tpu.arguments import parse_cli
from megatron_tpu.config import (MODEL_PRESETS, MegatronConfig,
                                 ParallelConfig, ServingConfig)
from megatron_tpu.models import hyper_connections as hc
from megatron_tpu.models import language_model as lm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PUBLISHED = os.path.join(REPO, "benchmark", "configs",
                         "xing4.0-29b-a4b-6l.json")


def tiny(**overrides):
    return dataclasses.replace(MODEL_PRESETS["xing4.0-29b-a4b-tiny"](),
                               compute_dtype="float32", **overrides)


def seeded(cfg, seed=0):
    """The initialiser's weights with what makes a test tell things apart:
    the embedding at a scale at which tokens route apart, a non-zero
    choosing bias, the sublayers' outputs as large as the residual they are
    written into (at the initialiser's 0.02 they are a thousandth of it and
    no fault of the residual path would show), and maps that do work: alpha
    1, phi ten times the initialiser's and b drawn N(0, 1), so that H_res
    is neither the identity nor uniform and moves from token to token."""
    params = lm.model_init(jax.random.PRNGKey(seed), cfg)
    params["embedding"]["word_embeddings"] *= 50.0
    key = jax.random.PRNGKey(seed + 9)
    for stack in (params["transformer"]["dense"],
                  params["transformer"]["moe"], params["mtp"]["layer"]):
        attn, mlp = stack["attention"], stack["mlp"]
        attn["wo"] = attn["wo"] * 120.0
        attn["wq_b"], attn["wkv_b"] = attn["wq_b"] * 20.0, attn["wkv_b"] * 20.0
        mlp["w2"] = mlp["w2"] * 30.0
        if "shared" in mlp:
            mlp["shared"]["w2"] = mlp["shared"]["w2"] * 30.0
            key, k = jax.random.split(key)
            mlp["e_score_correction_bias"] = 0.1 * jax.random.normal(
                k, mlp["e_score_correction_bias"].shape)
        for name in ("hc_attn", "hc_mlp"):
            key, k = jax.random.split(key)
            maps = stack[name]
            maps["alpha"] = jnp.ones_like(maps["alpha"])
            maps["phi"] = maps["phi"] * 10.0
            maps["b"] = jax.random.normal(k, maps["b"].shape)
    return params


@pytest.fixture(scope="module", params=[4, 2], ids=["n4", "n2"])
def model(request):
    cfg = tiny(hc_mult=request.param)
    return cfg, seeded(cfg)


@pytest.fixture(scope="module")
def model4():
    cfg = tiny()
    return cfg, seeded(cfg)


def _logprobs(params, tokens, cfg, **kw):
    logits, _ = lm.model_forward(params, tokens[None, :-1], cfg, **kw)
    return jnp.take_along_axis(
        jax.nn.log_softmax(logits[0, :, :cfg.vocab_size], -1),
        tokens[1:, None], -1)[:, 0]


# ---------------------------------------------------------------------------
# (a) the maps alone
# ---------------------------------------------------------------------------

def _maps_with(cfg, b_res, b_pre=0.0, b_post=0.0):
    """The maps of a sublayer whose phi is zero: H~ = b."""
    n = cfg.hc_mult
    params = {"phi": jnp.zeros((n * cfg.hidden_size, n * n + 2 * n)),
              "alpha": jnp.ones((3,)),
              "b": jnp.concatenate([jnp.full((n,), b_pre),
                                    jnp.full((n,), b_post),
                                    jnp.asarray(b_res, jnp.float32).reshape(-1)])}
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 3, n * cfg.hidden_size))
    return hc.hc_maps(params, x, cfg)


@pytest.mark.parametrize("case", ["all_max", "all_min", "diagonal",
                                  "antidiagonal", "past_the_clamp"])
def test_h_res_is_doubly_stochastic_at_the_clamps_ends(case):
    """Rows and columns of H_res sum to 1 within 1e-5 where every entry of
    H~_res stands at an end of the clamp (exp(+/-30): 1e13 and 1e-13)."""
    cfg = tiny()
    n, c = cfg.hc_mult, cfg.hc_res_clamp
    eye = np.eye(n)
    b_res = {"all_max": np.full((n, n), c), "all_min": np.full((n, n), -c),
             "diagonal": np.where(eye, c, -c),
             "antidiagonal": np.where(eye[::-1], c, -c),
             "past_the_clamp": np.where(eye, 100.0, -100.0)}[case]
    pre, post, res = _maps_with(cfg, b_res, b_pre=-40.0, b_post=40.0)
    res = np.asarray(res)[..., 0, 0]                  # one token's [n, n]
    np.testing.assert_allclose(res.sum(axis=1), 1.0, rtol=0, atol=1e-5)
    np.testing.assert_allclose(res.sum(axis=0), 1.0, rtol=0, atol=1e-5)
    assert (res >= 0).all()
    if case == "past_the_clamp":            # what lies past the clamp is AT it
        at = np.asarray(_maps_with(cfg, np.where(eye, c, -c))[2])[..., 0, 0]
        np.testing.assert_array_equal(res, at)
        np.testing.assert_allclose(res, eye, rtol=0, atol=1e-5)
    if case.startswith("all"):
        np.testing.assert_allclose(res, 1.0 / n, rtol=0, atol=1e-5)
    # the sigmoids' ranges, open at both ends in exact arithmetic and never
    # outside them in float32
    assert (np.asarray(pre) >= 0).all() and (np.asarray(pre) <= 1).all()
    assert (np.asarray(post) >= 0).all() and (np.asarray(post) <= 2).all()


def test_maps_match_reference(model):
    """The program's maps (tokens minor, the product divided by the root mean
    square afterwards) are the reference's (x^ first), on drawn streams:
    H_pre in (0, 1), H_post in (0, 2), H_res's rows 1 within 1e-5."""
    cfg, params = model
    n = cfg.hc_mult
    p = jax.tree.map(lambda t: t[1], params["transformer"]["moe"]["hc_mlp"])
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(4),
                                (2, 9, n * cfg.hidden_size))
    pre, post, res = hc.hc_maps(p, x, cfg)
    assert pre.shape == (n, 2, 9) and res.shape == (n, n, 2, 9)
    for b in range(2):
        w_pre, w_post, w_res = reference.hc_maps(
            p, x[b].reshape(9, n, cfg.hidden_size), cfg)
        np.testing.assert_allclose(pre[:, b].T, w_pre, rtol=0, atol=2e-6)
        np.testing.assert_allclose(post[:, b].T, w_post, rtol=0, atol=2e-6)
        np.testing.assert_allclose(jnp.moveaxis(res[:, :, b], -1, 0), w_res,
                                   rtol=0, atol=2e-6)
    pre, post, res = map(np.asarray, (pre, post, res))
    assert 0 < pre.min() and pre.max() < 1 and 0 < post.min() \
        and post.max() < 2
    np.testing.assert_allclose(res.sum(axis=1), 1.0, rtol=0, atol=1e-5)
    # the drawn maps do work: not the identity, not uniform, not one matrix
    assert res.max(axis=1).mean() < 0.98 and res.max(axis=1).mean() > 1.2 / n
    assert res[0, 0].std() > 0.01


def test_mixes_match_their_equations(model):
    """H_pre X and H_res X + H_post^T out over the side-by-side streams."""
    cfg, _ = model
    n, h = cfg.hc_mult, cfg.hidden_size
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(ks[0], (2, 7, n * h))
    out = jax.random.normal(ks[1], (2, 7, h))
    pre = jax.random.uniform(ks[2], (n, 2, 7))
    post = jax.random.uniform(ks[3], (n, 2, 7))
    res = jax.random.uniform(ks[4], (n, n, 2, 7))
    xs = x.reshape(2, 7, n, h)
    np.testing.assert_allclose(
        hc.hc_pre(pre, x, cfg), jnp.einsum("nbs,bsnc->bsc", pre, xs),
        rtol=0, atol=2e-6)
    want = (jnp.einsum("ijbs,bsjc->bsic", res, xs)
            + jnp.einsum("ibs,bsc->bsic", post, out))
    np.testing.assert_allclose(hc.hc_post(post, res, x, out, cfg),
                               want.reshape(2, 7, n * h), rtol=0, atol=5e-6)
    np.testing.assert_array_equal(
        hc.expand(out, cfg).reshape(2, 7, n, h),
        jnp.broadcast_to(out[:, :, None], (2, 7, n, h)))
    np.testing.assert_allclose(hc.collapse(x, cfg), xs.sum(axis=2),
                               rtol=0, atol=2e-6)


# ---------------------------------------------------------------------------
# (b) YaRN
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["xing4.0-29b-a4b-tiny", "xing4.0-29b-a4b"])
def test_rope_tables_match_reference(name):
    """`models/rope.py::yarn_freqs` through `make_rope` against the
    reference's own tables: cos and sin of float32 angles up to 128 x 1 at
    the tiny preset and 4,608 x 1 at the published one, where one float32
    step of the angle is 5e-4 (so 1e-3 there, 2e-6 at the tiny one)."""
    cfg = MODEL_PRESETS[name]()
    s = 128 if "tiny" in name else 4608
    rope = lm.make_rope(cfg, s)
    cos, sin = reference.rope_tables(cfg, s)
    tol = 2e-6 if "tiny" in name else 1e-3
    np.testing.assert_allclose(rope.cos, cos, rtol=0, atol=tol)
    np.testing.assert_allclose(rope.sin, sin, rtol=0, atol=tol)
    assert rope.cos.shape == (s, cfg.qk_rope_head_dim // 2)


def test_yarn_blend_at_the_published_widths():
    """ISSUE 41's numbers: the blend runs from pair 10 to pair 23 of 32, the
    fast pairs keep base^(-2i/64), the slow ones are divided by 64, and
    m(64, 1) = 1.416 enters the softmax scale squared."""
    from megatron_tpu.models.rope import yarn_mscale, yarn_softmax_mscale
    cfg = MODEL_PRESETS["xing4.0-29b-a4b"]()
    w = np.asarray(reference.yarn_inv_freq(cfg), np.float64)
    plain = 10000.0 ** (-2.0 * np.arange(32) / 64)
    np.testing.assert_allclose(w[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(w[23:], plain[23:] / 64, rtol=1e-6)
    assert ((w[11:23] < plain[11:23]) & (w[11:23] > plain[11:23] / 64)).all()
    assert abs(yarn_mscale(64.0, 1.0) - 1.4159) < 1e-4
    assert abs(yarn_softmax_mscale(cfg) - 2.0047) < 1e-4
    assert abs(reference.softmax_scale(cfg) * 192 ** 0.5 - 2.0047) < 1e-4
    # a model without YaRN keeps its scale
    assert yarn_softmax_mscale(MODEL_PRESETS["joyai-llm-flash"]()) == 1.0


# ---------------------------------------------------------------------------
# (c) the whole model, (d) the training loss
# ---------------------------------------------------------------------------

def test_logits_match_reference(model):
    """Two dense layers, three expert layers, through `model_forward`; and
    the reference a block of rows at a time is the reference."""
    cfg, params = model
    assert jax.tree.leaves(params["transformer"]["dense"])[0].shape[0] == 2
    assert jax.tree.leaves(params["transformer"]["moe"])[0].shape[0] == 3
    tokens = jax.random.randint(jax.random.PRNGKey(1), (33,), 0, 512)
    want = reference.token_logprobs(params, tokens, cfg)
    np.testing.assert_allclose(_logprobs(params, tokens, cfg), want,
                               rtol=0, atol=2e-5)
    np.testing.assert_allclose(
        reference.token_logprobs(params, tokens, cfg, tail=7), want[-7:],
        rtol=0, atol=1e-6)
    old = reference.ROW_BLOCK
    reference.ROW_BLOCK = 8          # 32 rows: four blocks; 31 would pad
    try:
        np.testing.assert_allclose(
            reference.token_logprobs(params, tokens, cfg), want,
            rtol=0, atol=2e-6)
        np.testing.assert_allclose(
            reference.token_logprobs(params, tokens[:-1], cfg), want[:-1],
            rtol=0, atol=2e-6)
    finally:
        reference.ROW_BLOCK = old


def test_cached_forward_with_padding_rows_matches_reference(model):
    """A prefill of 21 tokens in a bucket of 32 (11 padding rows behind
    them, mapped, mixed and routed like any row), then decode steps through
    the latent cache from the real length on: every real position equals
    the reference's full forward."""
    from megatron_tpu.inference.generation import init_kv_caches
    cfg, params = model
    tokens = jax.random.randint(jax.random.PRNGKey(4), (26,), 1, 512)
    want = reference.logits(params, tokens, cfg)
    caches = init_kv_caches(cfg, 1, 48, dtype=jnp.float32)
    padded = jnp.concatenate([tokens[:21], jnp.zeros((11,), tokens.dtype)])
    got, caches = lm.model_forward(params, padded[None], cfg,
                                   kv_caches=caches)
    np.testing.assert_allclose(got[0, :21, :512], want[:21], rtol=0,
                               atol=5e-5)
    # the engine sets a sequence's offset to its real length
    caches = caches._replace(offset=jnp.full_like(caches.offset, 21))
    for i in range(21, 26):
        out, caches = lm.model_forward(params, tokens[None, i:i + 1], cfg,
                                       kv_caches=caches)
        np.testing.assert_allclose(out[0, 0, :512], want[i], rtol=0,
                                   atol=5e-5)


def test_loss_and_gradients_match_reference(model):
    cfg, params = model
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 33), 0, 512)
    mask = (jax.random.uniform(jax.random.PRNGKey(3), (2, 32)) > 0.2
            ).astype(jnp.float32)
    loss, grads = jax.value_and_grad(
        lambda p: lm.loss_fn(p, tokens, cfg, loss_mask=mask))(params)
    want, want_grads = reference.loss_and_grads(params, tokens, mask, cfg)
    np.testing.assert_allclose(loss, want, rtol=2e-6)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=5e-5,
                                   err_msg=str(path))
    # the MTP term is there, its module's maps learn, and so do the trunk's
    no_mtp = lm.loss_fn(params, tokens, dataclasses.replace(
        cfg, mtp_loss_coeff=0.0), loss_mask=mask)
    assert float(loss) > float(no_mtp) + 1.0
    for maps in (grads["mtp"]["layer"]["hc_mlp"],
                 grads["transformer"]["dense"]["hc_attn"]):
        for name in ("phi", "alpha", "b"):
            assert float(jnp.abs(maps[name]).max()) > 0, name


def test_mtp_module_is_not_in_the_models_own_logits(model4):
    cfg, params = model4
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 24), 0, 512)
    full, _ = lm.model_forward(params, tokens, cfg)
    served = {k: v for k, v in params.items() if k != "mtp"}
    np.testing.assert_array_equal(full, lm.model_forward(served, tokens,
                                                         cfg)[0])


# ---------------------------------------------------------------------------
# (e) planted faults fail the same comparison
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fault", ["sinkhorn_1", "post_without_2",
                                   "no_mscale"])
def test_a_planted_fault_fails_the_forward_comparison(model4, fault,
                                                      monkeypatch):
    """One Sinkhorn round for twenty, H_post without its 2, the softmax
    scale without mscale^2, each planted IN THE PROGRAM: the comparison that
    holds at 2e-5 reads over a hundred times its tolerance. The reference
    with the same fault planted agrees with the faulty program, so it is the
    fault that is read and nothing else."""
    cfg, params = model4
    tokens = jax.random.randint(jax.random.PRNGKey(1), (33,), 0, 512)
    want = reference.token_logprobs(params, tokens, cfg)
    run_cfg = cfg
    if fault == "sinkhorn_1":
        run_cfg = dataclasses.replace(cfg, hc_sinkhorn_iters=1)
    elif fault == "post_without_2":
        real = jax.nn.sigmoid
        calls = []

        def sigmoid(x):     # hc_maps: the second sigmoid of a call is H_post's
            calls.append(1)
            return real(x) * (0.5 if len(calls) % 2 == 0 else 1.0)
        monkeypatch.setattr(hc.jax.nn, "sigmoid", sigmoid)
    else:
        from megatron_tpu.models import mla
        monkeypatch.setattr(mla, "yarn_softmax_mscale", lambda cfg: 1.0)
    got = _logprobs(params, tokens, run_cfg)
    monkeypatch.undo()
    assert float(jnp.abs(got - want).max()) > 100 * 2e-5, fault
    np.testing.assert_allclose(
        got, reference.token_logprobs(params, tokens, cfg, faults={fault}),
        rtol=0, atol=2e-5)


# ---------------------------------------------------------------------------
# the presets
# ---------------------------------------------------------------------------

def test_preset_fields_equal_the_published_config():
    with open(PUBLISHED) as f:
        hf = json.load(f)
    hf.update(hf["published"])
    cfg = MODEL_PRESETS["xing4.0-29b-a4b"]()
    assert (cfg.num_layers, cfg.hidden_size, cfg.dense_ffn_hidden_size,
            cfg.ffn_hidden_size) == (
        hf["num_hidden_layers"], hf["hidden_size"], hf["intermediate_size"],
        hf["moe_intermediate_size"])
    assert (cfg.num_attention_heads, cfg.num_kv_heads) == (
        hf["num_attention_heads"], hf["num_key_value_heads"])
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_channels) == (
        hf["q_lora_rank"], hf["kv_lora_rank"], hf["qk_nope_head_dim"],
        hf["qk_rope_head_dim"], hf["v_head_dim"], hf["qk_rope_head_dim"])
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
    assert (cfg.norm_type, cfg.norm_epsilon, cfg.rope_theta) == (
        "rmsnorm", hf["rms_norm_eps"], hf["rope_theta"])
    rs = hf["rope_scaling"]
    assert (cfg.rope_scaling_type, cfg.rope_scaling_factor,
            cfg.rope_original_max_position, cfg.rope_beta_fast,
            cfg.rope_beta_slow, cfg.rope_mscale, cfg.rope_mscale_all_dim) == (
        rs["type"], rs["factor"], rs["original_max_position_embeddings"],
        rs["beta_fast"], rs["beta_slow"], rs["mscale"], rs["mscale_all_dim"])
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps,
            -cfg.hc_res_clamp, cfg.hc_res_clamp) == (
        hf["hc_mult"], hf["hc_sinkhorn_iters"], hf["hc_eps"],
        hf["mhc_h_res_clamp_min"], hf["mhc_h_res_clamp_max"])
    assert cfg.activation == "swiglu" and hf["hidden_act"] == "silu"
    assert cfg.tie_embed_logits == hf["tie_word_embeddings"]
    assert cfg.use_bias == hf["attention_bias"]
    assert cfg.params_dtype == "bfloat16" and cfg.moe_dispatch == "dropless"
    assert cfg.moe_aux_loss_coeff == 0.0


def test_configuration_file_keeps_every_published_key():
    """Every number of the catalog row's `config` stands in the file under
    the same key, but the two in `reduced`."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Xing4.0-29B-A4B")
    with open(PUBLISHED) as f:
        held = json.load(f)
    assert held["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if held.get(k) != v}
    assert differ == set(held["reduced"]) == {"num_hidden_layers",
                                              "first_k_dense_replace"}
    assert all(held["published"][k] == row["config"][k] for k in differ)
    for key in ("assumed", "deployment", "notes", "cli"):
        assert held[key]


def test_preset_through_parse_cli():
    """The benchmark's configuration as its `cli` builds it: the parameter
    counts of `reduced`, the cache's row, and the preset through
    `ServingConfig.validate` with the cell's own serving block."""
    with open(PUBLISHED) as f:
        cli = json.load(f)["cli"]
    m = parse_cli([*cli, "--bf16"], n_devices=1)[0].model
    assert (m.num_layers, m.first_k_dense_replace, m.num_experts,
            m.hc_mult, m.rope_scaling_type) == (6, 1, 64, 4, "yarn")
    shapes = jax.eval_shape(lambda: lm.model_init(jax.random.PRNGKey(0), m))
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))
    mla_n, norms = 28_411_136, 2 * 3584
    maps = 2 * (14_336 * 24 + 24 + 3)
    assert count(shapes["transformer"]["dense"]) == (
        mla_n + norms + maps + 3 * 3584 * 9216) == 128_196_918
    assert count(shapes["transformer"]["moe"]) // 5 == (
        mla_n + norms + maps + 65 * 11_010_048 + 3584 * 64 + 64
    ) == 744_989_046
    assert count(shapes["embedding"]) + count(shapes["lm_head"]) == 939_524_096
    served = {k: v for k, v in shapes.items() if k != "mtp"}
    assert count(served) == 4_792_669_828
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(shapes))
    assert m.kv_row_width == 576
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "mixed-16k-chunked-open-loop.json")) as f:
        serving = ServingConfig(**json.load(f)["serving"]).validate(m)
    assert (serving.prefill_chunk, serving.num_slots) == (4096, 24)


# ---------------------------------------------------------------------------
# (f) what is refused, by name; (g) hc_mult 1 is today's code
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("what, match", [
    (dict(model=dict(parallel_attn=True)), "parallel_attn is refused"),
    (dict(model=dict(use_post_ln=True)), "use_post_ln is refused"),
    (dict(model=dict(drop_path_rate=0.1)), "drop_path_rate is refused"),
    (dict(model=dict(hc_sinkhorn_iters=0)), "hc_sinkhorn_iters >= 1"),
    (dict(model=dict(rope_original_max_position=None)),
     "rope_original_max_position"),
    (dict(model=dict(rope_scaling_type="ntk")), "rope_scaling_type"),
    (dict(parallel=ParallelConfig(tensor_parallel=2)), "one device only"),
    (dict(parallel=ParallelConfig(pipeline_parallel=2)), "one device only"),
])
def test_validate_refuses_by_name(what, match):
    m = tiny(**what.get("model", {}))
    with pytest.raises(AssertionError, match=match):
        MegatronConfig(model=m, parallel=what.get(
            "parallel", ParallelConfig())).validate(
                n_devices=2 if "parallel" in what else 1)


def _plain_hc(**overrides):
    """A dense one-kind model with a residual of two streams: what of the
    refusals is hyper-connections' own and not MLA's or the experts'."""
    from megatron_tpu.config import llama2_config
    return dataclasses.replace(llama2_config("tiny"), hc_mult=2, **overrides)


@pytest.mark.parametrize("what, match", [
    (dict(parallel=ParallelConfig(tensor_parallel=2)), "tensor_parallel"),
    (dict(parallel=ParallelConfig(context_parallel=2)), "context_parallel"),
    (dict(parallel=ParallelConfig(data_parallel=2)), "data_parallel"),
    (dict(parallel=ParallelConfig(pipeline_parallel=2)),
     "pipeline_parallel is refused"),
    (dict(model=dict(layer_types=("conv", "full_attention"))),
     "layer_types is refused"),
    (dict(model=dict(window_layer_period=2, sliding_window=8)),
     "window_layer_period is refused"),
    (dict(model=dict(rope_scaling_type="yarn", rope_scaling_factor=4.0,
                     rope_original_max_position=32, rope_mscale_all_dim=1.0)),
     "rope_mscale_all_dim acts on MLA"),
])
def test_validate_refuses_hyper_connections_own(what, match):
    m = _plain_hc(**what.get("model", {}))
    with pytest.raises(AssertionError, match=match):
        MegatronConfig(model=m, parallel=what.get(
            "parallel", ParallelConfig())).validate(n_devices=2)


@pytest.mark.parametrize("serving, match", [
    (dict(adapter_slots=2, adapter_rank=4), "adapter_slots"),
    (dict(serving_tp=2), "serving mesh"),
    (dict(serving_pp=2, kv_block_size=16), "serving mesh"),
])
def test_serving_validate_refuses_by_name(serving, match):
    with pytest.raises(AssertionError, match=match):
        ServingConfig(max_len=64, **serving).validate(_plain_hc().derived())


def test_an_encoder_or_an_adapter_bank_is_refused_where_it_is_given():
    """What no configuration says: a layer under hyper-connections given an
    encoder's output or a LoRA bank refuses it."""
    from megatron_tpu.models import transformer as tfm
    cfg = dataclasses.replace(_plain_hc(), compute_dtype="float32").derived()
    params = tfm.layer_init(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((1, 4, 2 * cfg.hidden_size))
    rope = lm.make_rope(cfg, 8)
    kw = dict(rope_cos=rope.cos, rope_sin=rope.sin)
    out, _, _ = tfm.layer_apply(params, x, cfg, **kw)
    assert out.shape == x.shape
    with pytest.raises(AssertionError, match="hyper-connections wrap"):
        tfm.layer_apply(params, x, cfg, encoder_output=x, **kw)
    with pytest.raises(AssertionError, match="hyper-connections wrap"):
        tfm.layer_apply(params, x, cfg, adapters=(None, None), **kw)


@pytest.mark.parametrize("name", ["falcon-tiny", "joyai-llm-flash-tiny"])
def test_hc_mult_1_reaches_none_of_it(name, monkeypatch):
    """`hc_mult` 1: `model_forward` and the loss trace without one call into
    models/hyper_connections.py, the parameter tree has no maps, and no
    array of the traced program is as wide as two streams of the residual or
    has the maps' [.., n, n] planes (tests/test_jaxpr_unchanged.py holds two
    models' programs to their digests besides)."""
    cfg = dataclasses.replace(MODEL_PRESETS[name](), vocab_size=512)
    assert cfg.hc_mult == 1

    def never(*a, **k):
        raise AssertionError("hyper_connections reached with hc_mult 1")
    for fn in ("hc_init", "hc_maps", "hc_pre", "hc_post", "hc_sublayer",
               "expand", "collapse"):
        monkeypatch.setattr(hc, fn, never)
    params = jax.eval_shape(lambda: lm.model_init(jax.random.PRNGKey(0), cfg))
    assert not any("hc_" in jax.tree_util.keystr(path) for path, _ in
                   jax.tree_util.tree_leaves_with_path(params))
    tokens = jnp.zeros((2, 17), jnp.int32)
    jaxpr = jax.make_jaxpr(
        lambda p, t: lm.model_forward(p, t, cfg)[0])(params, tokens[:, :-1])
    jax.make_jaxpr(lambda p, t: lm.loss_fn(p, t, cfg))(params, tokens)
    # the residual is [b, s, hidden] in every equation that holds one
    text = str(jaxpr)
    assert f"[2,16,{cfg.hidden_size}]" in text
    assert f"[2,16,4,{cfg.hidden_size}]" not in text
    monkeypatch.undo()
    want = tiny()
    with_hc = str(jax.make_jaxpr(
        lambda p, t: lm.model_forward(p, t, want)[0])(
            jax.eval_shape(lambda: lm.model_init(jax.random.PRNGKey(0), want)),
            tokens[:, :-1]))
    assert f"[2,16,{4 * want.hidden_size}]" in with_hc     # the positive


def test_finetune_reaches_the_loss_on_one_device(tmp_path):
    """`finetune.py --model xing4.0-29b-a4b-tiny` on one device: the whole
    argparse -> loop surface, the loss with its MTP term (ln 512 x 1.3 at the
    start), a checkpoint of the tree with two stacks, the maps and the
    module."""
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
         "--model", "xing4.0-29b-a4b-tiny", "--seq_length", "32",
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


def test_named_scopes_are_in_the_program_and_in_the_table():
    """Every `mtpu/hc/...` scope and `mtpu/rope/yarn` is in the op names of
    the lowered forward and has its row in `utils/tracing.py`'s table."""
    from megatron_tpu.utils import tracing
    cfg = tiny()
    params = jax.eval_shape(lambda: lm.model_init(jax.random.PRNGKey(0), cfg))
    text = jax.jit(lambda p, t: lm.model_forward(p, t, cfg)[0]).lower(
        params, jnp.zeros((1, 16), jnp.int32)).as_text(debug_info=True)
    for scope in ("mtpu/hc/expand", "mtpu/hc/map", "mtpu/hc/pre",
                  "mtpu/hc/post", "mtpu/hc/collapse", "mtpu/rope/yarn"):
        assert scope in text, scope
        assert f"| `{scope}` |" in tracing.__doc__, scope
