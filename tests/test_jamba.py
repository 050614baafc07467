"""AI21-Jamba2-3B on the normal path (PR 47): Mamba-1 mixers
(models/mamba.py over ops/selective_scan.py) in `cfg.layer_types` beside two
position-less attention layers of one kv head, scanned a period of 14 at a
time. The model as `finetune.py` builds it (`parse_cli` -> `model_init` ->
`model_forward` / `loss_fn`) against the float32 reference
(`benchmark/reference/jamba.py`: a token at a time, no cache, no state
carried). Float32 throughout: 1e-4 on logits of magnitude ~3, on the loss
and on gradients relative to their largest entry."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import jamba as reference
from megatron_tpu.arguments import parse_cli
from megatron_tpu.config import (MODEL_PRESETS, MegatronConfig, ModelConfig,
                                 ParallelConfig, jamba_layer_types)
from megatron_tpu.models import language_model as lm
from megatron_tpu.models.transformer import _pattern_period

TOL = 1e-4
# as tests/test_lfm2.py: matrices at sqrt(64) x 0.11 = 0.9 of gain, the
# published widths' sqrt(2560) x 0.02, so that a mixer adds to the stream
# what it adds at width
STD = 0.11


def _model(**over):
    cfg = dataclasses.replace(
        MODEL_PRESETS["jamba2-3b-tiny"](), compute_dtype="float32",
        init_method_std=STD, **over)
    return cfg, lm.model_init(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def model():
    return _model()


def test_presets_hold_the_published_sizes():
    cfg = MODEL_PRESETS["jamba2-3b"]()
    assert isinstance(cfg, ModelConfig)
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_kv_heads, cfg.kv_channels, cfg.ffn_hidden_size,
            cfg.vocab_size, cfg.max_position_embeddings) == \
        (28, 2560, 20, 1, 128, 8192, 65536, 262144)
    assert (cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_dt_rank,
            cfg.mamba_expand, cfg.mamba_d_inner, cfg.mamba_conv_bias,
            cfg.mamba_proj_bias, cfg.norm_epsilon) == \
        (16, 4, 160, 2, 5120, True, False, 1e-6)
    assert [l for l, k in enumerate(cfg.layer_types)
            if k == "full_attention"] == [7, 21]
    assert cfg.layer_types.count("mamba") == 26 and len(cfg.layer_types) == 28
    assert cfg.layer_types == jamba_layer_types(28, 14, 7)
    assert not cfg.use_rotary_emb and not cfg.use_position_embedding \
        and cfg.tie_embed_logits and cfg.num_experts == 1
    assert lm.make_rope(cfg) is None
    assert _pattern_period(cfg.layer_types) == (14, 2)
    # a slot's state: 26 x (16 x 5120 float32 + 3 x 5120 bf16)
    assert cfg.state_layers == 26 and cfg.state_kind == "mamba"
    assert cfg.conv_state_shape == (3, 5120)
    assert 26 * (cfg.ssm_state_width * 4 + cfg.conv_state_width * 2) \
        == 9_318_400
    assert cfg.kv_layers == 2 and cfg.kv_row_width * 2 * 2 == 1024
    MegatronConfig(model=cfg).validate(1)
    MegatronConfig(model=MODEL_PRESETS["jamba2-3b-tiny"]()).validate(1)
    # the whole model: 3,029.3 M parameters (ISSUE 47 counts 3,028 from a
    # layer rounded to 104.1 M), the kinds stacked apart
    shapes = jax.eval_shape(lambda: lm.model_init(jax.random.PRNGKey(0), cfg))
    assert round(sum(int(np.prod(x.shape))
                     for x in jax.tree.leaves(shapes)) / 1e6) == 3029
    stack = shapes["transformer"]["layers"]
    assert set(stack) == {"mamba", "full_attention"}
    m = stack["mamba"]["mamba"]
    assert m["in_proj"].shape == (26, 2560, 10240)
    assert m["x_proj"].shape == (26, 5120, 192)
    assert m["dt_proj"].shape == (26, 160, 5120)
    assert m["A_log"].shape == (26, 16, 5120)
    assert m["conv"].shape == (26, 4, 5120)
    assert m["conv_bias"].shape == m["D"].shape == (26, 5120)
    assert "in_bias" not in m and "out_bias" not in m
    assert stack["full_attention"]["attention"]["wkv"].shape == (2, 2560, 256)
    assert stack["mamba"]["mlp"]["w1"].shape == (26, 2560, 2, 8192)
    assert "lm_head" not in shapes


def test_the_cli_builds_the_preset():
    cfg, _ = parse_cli(["--model", "jamba2-3b", "--bf16"], n_devices=1)
    m = cfg.model
    assert m.num_layers == 28 and m.layer_types == jamba_layer_types(28)
    assert m.params_dtype == m.compute_dtype == "bfloat16"
    assert m.mamba_d_inner == 5120 and m.vocab_size == 65536


def test_the_initialiser_is_mambas(model):
    cfg, params = model
    m = params["transformer"]["layers"]["mamba"]["mamba"]
    want = np.log(np.arange(1, 17, dtype=np.float32))
    assert np.allclose(np.asarray(m["A_log"][3, :, 5]), want)
    assert np.all(np.asarray(m["D"]) == 1.0)
    dt = np.asarray(jax.nn.softplus(m["dt_bias"]))
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 0.1 * 1.001
    assert np.abs(np.asarray(m["dt_proj"])).max() <= cfg.mamba_dt_rank ** -0.5
    for norm in ("dt_norm", "b_norm", "c_norm"):
        assert np.all(np.asarray(m[norm]["scale"]) == 1.0)


@pytest.mark.parametrize("impl", ["dot", "flash"])
def test_forward_without_a_cache_matches_reference(impl):
    cfg, params = _model(attention_impl=impl)
    tokens = np.random.default_rng(0).integers(1, cfg.vocab_size, (2, 37))
    got, _ = lm.model_forward(params, jnp.asarray(tokens), cfg,
                              rope=lm.make_rope(cfg),
                              logits_dtype=jnp.float32)
    for row, toks in zip(np.asarray(got), tokens):
        want = np.asarray(reference.logits(params, jnp.asarray(toks), cfg))
        assert np.abs(want).max() > 1.0
        assert np.abs(row[:, :cfg.vocab_size] - want).max() < TOL


def test_a_cut_of_the_depth_with_a_tail_off_the_period():
    """Ten layers, attention at 7: no whole second period, so one period of
    them all; and 17 with its own pattern: one period of 7 twice and a
    tail."""
    for n, types in ((10, None), (17, ("mamba",) * 6 + ("full_attention",)
                                  + ("mamba",) * 6 + ("full_attention",)
                                  + ("mamba",) * 3)):
        cfg, params = _model(num_layers=n, layer_types=(
            types or jamba_layer_types(n)))
        MegatronConfig(model=cfg).validate(1)
        tokens = np.random.default_rng(n).integers(1, cfg.vocab_size, 23)
        got, _ = lm.model_forward(params, jnp.asarray(tokens)[None], cfg,
                                  logits_dtype=jnp.float32)
        want = np.asarray(reference.logits(params, jnp.asarray(tokens), cfg))
        assert np.abs(np.asarray(got)[0, :, :cfg.vocab_size]
                      - want).max() < TOL


def test_loss_and_gradients_match_the_reference(model):
    """`loss_fn` and its gradient through the pattern scan against the
    reference's own loss differentiated: every leaf of the tree."""
    cfg, params = model
    tokens = jnp.asarray(np.random.default_rng(23).integers(
        1, cfg.vocab_size, (1, 17)))

    def ours(p):
        return lm.loss_fn(p, tokens, cfg, rope=lm.make_rope(cfg))

    def theirs(p):
        lp = jnp.stack([reference.token_logprobs(p, t, cfg) for t in tokens])
        return -jnp.mean(lp)
    value, grads = jax.value_and_grad(ours)(params)
    want_value, want = jax.value_and_grad(theirs)(params)
    assert abs(float(value) - float(want_value)) < TOL
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    flat_want = jax.tree.leaves(want)
    assert len(flat) == len(flat_want)
    for (path, g), w in zip(flat, flat_want):
        scale = np.abs(np.asarray(w)).max()
        assert scale > 0, path
        assert np.abs(np.asarray(g - w)).max() < TOL * max(scale, 1.0), path


@pytest.mark.parametrize("fault", [
    "no_dt_norm", "conv_without_bias", "decay_positive", "state_of_8"])
def test_a_planted_fault_fails_the_comparison(model, fault):
    """What the comparisons above can see: the same forward over a tree or
    a configuration that is off in one place."""
    cfg, params = model
    m = dict(params["transformer"]["layers"]["mamba"]["mamba"])
    if fault == "no_dt_norm":
        m["dt_norm"] = {"scale": 3.0 * m["dt_norm"]["scale"]}
    elif fault == "conv_without_bias":
        m["conv_bias"] = jnp.zeros_like(m["conv_bias"])
    elif fault == "decay_positive":
        m["A_log"] = m["A_log"] + 1.0
    else:
        m["A_log"] = m["A_log"].at[:, 8:].set(30.0)   # states 8.. forget at once
    off = jax.tree.map(lambda a: a, params)
    off["transformer"]["layers"]["mamba"] = {
        **params["transformer"]["layers"]["mamba"], "mamba": m}
    tokens = np.random.default_rng(1).integers(1, cfg.vocab_size, 37)
    got, _ = lm.model_forward(off, jnp.asarray(tokens)[None], cfg,
                              logits_dtype=jnp.float32)
    want = np.asarray(reference.logits(params, jnp.asarray(tokens), cfg))
    assert np.abs(np.asarray(got)[0, :, :cfg.vocab_size] - want).max() \
        > 100 * TOL


@pytest.mark.parametrize("change,parallel,match", [
    (dict(num_layers=6), {}, "28 entries"),
    (dict(layer_types=("mamba", "conv") * 14), {}, "'conv' AND 'mamba'"),
    (dict(layer_types=("mamba", "window") * 14), {}, "'mamba' | 'full_att"),
    (dict(mamba_d_conv=1), {}, "mamba_d_conv=1"),
    (dict(first_k_dense_replace=1), {}, "leading dense stack"),
    (dict(sliding_window=16), {}, "refused with MLA"),
    (dict(use_bias=True), {}, "refused with MLA"),
    ({}, dict(tensor_parallel=2), "one device only"),
    ({}, dict(pipeline_parallel=2), "one device only"),
    ({}, dict(context_parallel=2), "one device only"),
    (dict(attention_impl="ring"), {}, "context-parallel"),
])
def test_model_refusals_by_name(change, parallel, match):
    cfg = dataclasses.replace(MODEL_PRESETS["jamba2-3b-tiny"](), **change)
    with pytest.raises(AssertionError, match=match):
        MegatronConfig(model=cfg, parallel=ParallelConfig(**parallel)
                       ).validate(2 if parallel else 1)
