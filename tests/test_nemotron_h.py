"""NVIDIA-Nemotron-3-Super-120B-A12B on the normal path (PR 52): layers that
are ONE pre-norm sublayer each (`cfg.one_sublayer`: a Mamba-2 mixer,
attention, or the experts alone), Mamba-2 mixers (models/mamba2.py over
ops/ssd_scan.py) and experts in a latent (models/moe.py). The model as
`finetune.py` builds it (`parse_cli` -> `model_init` -> `model_forward` /
`loss_fn`) against the float32 reference (`benchmark/reference/
nemotron_h.py`: a token at a time, no cache, no state carried, the
sequential recurrence). Float32 throughout: 1e-4 on logits of magnitude ~3,
on the loss and on gradients relative to their largest entry."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import nemotron_h as reference
from megatron_tpu.arguments import parse_cli
from megatron_tpu.config import (MODEL_PRESETS, NEMOTRON_3_SUPER_PATTERN,
                                 MegatronConfig, ModelConfig, ParallelConfig,
                                 nemotron_h_layer_types)
from megatron_tpu.models import language_model as lm
from megatron_tpu.models.transformer import _pattern_period

TOL = 1e-4
# as tests/test_jamba.py: matrices at sqrt(64) x 0.11 = 0.9 of gain, the
# published widths' sqrt(4096) x 0.02, so that a sublayer adds to the stream
# what it adds at width
STD = 0.11


def _model(**over):
    cfg = dataclasses.replace(
        MODEL_PRESETS["nemotron-3-super-tiny"](), compute_dtype="float32",
        init_method_std=STD, **over)
    return cfg, lm.model_init(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def model():
    return _model()


def _logits(params, cfg, tokens):
    got, _ = lm.model_forward(params, jnp.asarray(tokens)[None], cfg,
                              rope=lm.make_rope(cfg),
                              logits_dtype=jnp.float32)
    return np.asarray(got)[0, :, :cfg.vocab_size]


def test_presets_hold_the_published_sizes():
    cfg = MODEL_PRESETS["nemotron-3-super"]()
    assert isinstance(cfg, ModelConfig)
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_kv_heads, cfg.kv_channels, cfg.ffn_hidden_size,
            cfg.vocab_size, cfg.max_position_embeddings) == \
        (88, 4096, 32, 2, 128, 2688, 131072, 262144)
    assert (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.mamba_n_groups,
            cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_chunk_size,
            cfg.mamba_d_inner, cfg.mamba2_conv_channels,
            cfg.mamba_conv_bias, cfg.mamba_proj_bias, cfg.norm_epsilon) == \
        (128, 64, 8, 128, 4, 128, 8192, 10240, True, False, 1e-5)
    assert (cfg.num_experts, cfg.router_experts, cfg.moe_top_k,
            cfg.moe_latent_size, cfg.moe_shared_expert_ffn,
            cfg.n_shared_experts, cfg.moe_routed_scaling_factor,
            cfg.activation, cfg.moe_scoring_func) == \
        (512, 512, 22, 1024, 5376, 1, 5.0, "squared_relu", "sigmoid")
    types = cfg.layer_types
    assert len(NEMOTRON_3_SUPER_PATTERN) == 88
    assert types == nemotron_h_layer_types(NEMOTRON_3_SUPER_PATTERN)
    assert (types.count("mamba2"), types.count("full_attention"),
            types.count("moe")) == (40, 8, 40)
    assert types[:11] == nemotron_h_layer_types("MEMEMEM*EME")
    assert cfg.one_sublayer and cfg.state_kind == "mamba2"
    assert not cfg.use_rotary_emb and not cfg.tie_embed_logits
    assert lm.make_rope(cfg) is None
    # a slot's state a Mamba-2 layer: 4 MiB of float32 matrix a head, and
    # 3 x 10,240 bf16 depthwise inputs; one attention layer's 1,024 B a token
    assert cfg.ssm_state_shape == (128, 64, 128)
    assert cfg.ssm_state_width * 4 == 4 * 2 ** 20
    assert cfg.conv_state_shape == (3, 10240)
    assert cfg.kv_row_width * 2 == 1024
    MegatronConfig(model=cfg).validate(1)
    MegatronConfig(model=MODEL_PRESETS["nemotron-3-super-tiny"]()).validate(1)


def test_the_cli_builds_the_cells_cut():
    """`--model nemotron-3-super` cut as the benchmark's configuration cuts
    it: eleven layers, 128 experts held under a router of 512, a quarter of
    the vocabulary; 4,648 M parameters, each kind stacked apart, each layer
    one norm and one sublayer."""
    cfg, _ = parse_cli(
        ["--model", "nemotron-3-super", "--num_layers", "11", "--layer_types",
         "mamba2,moe,mamba2,moe,mamba2,moe,mamba2,full_attention,moe,mamba2,"
         "moe", "--num_experts", "128", "--vocab_size", "32768", "--bf16"],
        n_devices=1)
    m = cfg.model
    assert m.params_dtype == m.compute_dtype == "bfloat16"
    assert (m.num_experts, m.router_experts, m.moe_first_expert) == \
        (128, 512, 0)
    assert (m.kv_layers, m.state_layers, m.layers_of("moe")) == (1, 5, 5)
    assert _pattern_period(m.layer_types) == (2, 3)     # ME x 3, then M*EME
    shapes = jax.eval_shape(lambda: lm.model_init(jax.random.PRNGKey(0), m))
    assert round(sum(int(np.prod(x.shape))
                     for x in jax.tree.leaves(shapes)) / 1e5) == 46482
    stack = shapes["transformer"]["layers"]
    assert set(stack) == {"mamba2", "full_attention", "moe"}
    assert set(stack["mamba2"]) == {"input_norm", "mamba2"}
    assert set(stack["full_attention"]) == {"input_norm", "attention"}
    assert set(stack["moe"]) == {"input_norm", "mlp"}
    mixer, mlp = stack["mamba2"]["mamba2"], stack["moe"]["mlp"]
    assert mixer["in_proj"].shape == (5, 4096, 18560)
    assert mixer["conv"].shape == (5, 4, 10240)
    assert mixer["A_log"].shape == mixer["D"].shape == (5, 128)
    assert mixer["norm"]["scale"].shape == (5, 8192)
    assert mixer["out_proj"].shape == (5, 8192, 4096)
    assert stack["full_attention"]["attention"]["wkv"].shape == (1, 4096, 512)
    assert mlp["router"].shape == (5, 4096, 512)
    assert mlp["w1"].shape == (5, 128, 1024, 2688)
    assert mlp["w2"].shape == (5, 128, 2688, 1024)
    assert mlp["latent_in"].shape == (5, 4096, 1024)
    assert mlp["latent_out"].shape == (5, 1024, 4096)
    assert mlp["shared"]["w1"].shape == (5, 4096, 5376)
    assert shapes["lm_head"].shape == (4096, 32768)
    assert "mtp" not in shapes


def test_the_initialiser_is_mamba2s(model):
    _, params = model
    m = params["transformer"]["layers"]["mamba2"]["mamba2"]
    a = np.exp(np.asarray(m["A_log"]))
    assert 1.0 <= a.min() and a.max() <= 16.0 and a.std() > 1.0
    assert np.all(np.asarray(m["D"]) == 1.0)
    assert np.all(np.asarray(m["norm"]["scale"]) == 1.0)
    dt = np.asarray(jax.nn.softplus(m["dt_bias"]))
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 0.1 * 1.001


@pytest.mark.parametrize("impl", ["dot", "flash"])
def test_forward_without_a_cache_matches_reference(impl):
    cfg, params = _model(attention_impl=impl)
    tokens = np.random.default_rng(0).integers(1, cfg.vocab_size, (2, 37))
    for toks in tokens:
        want = np.asarray(reference.logits(params, jnp.asarray(toks), cfg))
        assert np.abs(want).max() > 1.0
        assert np.abs(_logits(params, cfg, toks) - want).max() < TOL


@pytest.mark.parametrize("pattern", ["M*EM", "*EMM*E", "MEM*EMEM*EME"])
def test_patterns_no_pairing_covers(pattern):
    """Two mixers in a row (`M*`), an `E` behind a `*`, a leading `*`: each
    letter is a layer of its own at its own index, whatever stands next to
    it."""
    cfg, params = _model(num_layers=len(pattern),
                         layer_types=nemotron_h_layer_types(pattern))
    MegatronConfig(model=cfg).validate(1)
    stack = params["transformer"]["layers"]
    for kind, letter in (("mamba2", "M"), ("full_attention", "*"),
                         ("moe", "E")):
        assert stack[kind]["input_norm"]["scale"].shape[0] == \
            pattern.count(letter)
    tokens = np.random.default_rng(len(pattern)).integers(
        1, cfg.vocab_size, 23)
    want = np.asarray(reference.logits(params, jnp.asarray(tokens), cfg))
    assert np.abs(_logits(params, cfg, tokens) - want).max() < TOL


def test_loss_and_gradients_match_the_reference(model):
    """`loss_fn` and its gradient through the pattern scan and the `einsum`
    form of the chunked scan against the reference's own loss (the
    sequential recurrence) differentiated: every leaf of the tree but the
    choosing bias, which chooses and is not valued."""
    cfg, params = model
    tokens = jnp.asarray(np.random.default_rng(23).integers(
        1, cfg.vocab_size, (1, 21)))

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
        if "e_score_correction_bias" in jax.tree_util.keystr(path):
            assert scale == 0 and np.abs(np.asarray(g)).max() == 0
            continue
        assert scale > 0, path
        assert np.abs(np.asarray(g - w)).max() < TOL * max(scale, 1.0), path


@pytest.mark.parametrize("fault", sorted(reference.FAULTS))
def test_a_fault_in_the_reference_fails_the_comparison(model, fault):
    """What the comparisons above can see: each piece of the mathematics
    the benchmark's control plants a fault in (`benchmark/tests/
    ssd_fault_at_width.py`) moves the logits by a hundred tolerances here,
    but a float32 state rounded to bfloat16 (ten: 1.2e-3; behind every token
    or where a pool in bfloat16 would store it, which with every position
    checked is every token too) and a state dropped every 2,048 rows (none in
    37 rows: the serving tests' chunks see it)."""
    cfg, params = model
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        1, cfg.vocab_size, 37))
    want = np.asarray(reference.token_logprobs(params, tokens, cfg))
    off = np.asarray(reference.token_logprobs(
        params, tokens, cfg, faults=frozenset({fault})))
    least = {"state_reset": 0, "state_bf16": 10,
             "pool_bf16": 10}.get(fault, 100) * TOL
    assert (np.abs(off - want).max() > least) == (fault != "state_reset")


def test_the_reference_padded_behind_live_reads_what_the_unpadded_reads(
        model):
    """`reference.checked`: tokens padded behind `live` (the benchmark
    compiles the reference at ONE length for both of its checked requests
    and the window's prompts) read the log-probabilities, the choices and
    the states the unpadded tokens read; the second state is the one a row
    AHEAD, which is the first state of the tokens one longer."""
    cfg, params = model
    tokens = np.random.default_rng(2).integers(1, cfg.vocab_size, 38)
    pad = jnp.asarray(np.concatenate([tokens, np.zeros(9, tokens.dtype)]))
    plain = reference.checked(params, jnp.asarray(tokens[:37]), 37, cfg, 5)
    padded = jax.jit(
        lambda p, t, live: reference.checked(p, t, live, cfg, 5))(
            params, pad, jnp.int32(37))
    longer = reference.checked(params, jnp.asarray(tokens), 38, cfg, 5)

    def close(a, b):
        return np.abs(np.asarray(a) - np.asarray(b)).max() < 1e-5
    assert close(padded["logprobs"], plain["logprobs"])
    assert (np.asarray(padded["chosen"])[:, :36]
            == np.asarray(plain["chosen"])).all()
    assert padded["states"].shape[:2] == (2, cfg.layers_of("mamba2"))
    assert close(padded["states"][0], plain["states"][0])
    assert close(plain["states"][1], plain["states"][0])     # no row ahead
    assert close(padded["states"][1], longer["states"][0])
    assert not close(padded["states"][1], padded["states"][0])


def test_jambas_and_lfm2s_trees_are_unchanged_by_the_new_kinds():
    """A two-sublayer pattern builds what it built: a mixer, a feed-forward
    and two norms a layer, under the names it had."""
    for name, kinds in (("jamba2-3b-tiny", {"mamba", "full_attention"}),
                        ("lfm2-8b-a1b-tiny", {"conv", "full_attention"})):
        cfg = MODEL_PRESETS[name]()
        assert not cfg.one_sublayer
        shapes = jax.eval_shape(
            lambda: lm.model_init(jax.random.PRNGKey(0), cfg))
        for group in shapes["transformer"].values():
            assert set(group) <= kinds      # LFM2's dense group: conv alone
            for kind, layer in group.items():
                mixer = "attention" if kind == "full_attention" else kind
                assert set(layer) == {mixer, "mlp", "input_norm",
                                      "post_attn_norm"}
    jamba = MODEL_PRESETS["jamba2-3b"]()
    assert jamba.mamba_d_inner == 5120 and jamba.ssm_state_shape == (16, 5120)
    assert MODEL_PRESETS["lfm2-8b-a1b"]().ssm_state_shape is None


@pytest.mark.parametrize("change,parallel,match", [
    (dict(num_layers=6), {}, "11 entries"),
    (dict(layer_types=("mamba2", "mamba") * 5 + ("moe",)), {},
     "never both readings"),
    (dict(layer_types=nemotron_h_layer_types("MEMEMEM*EM-")), {},
     "'mlp' .* is refused"),
    (dict(layer_types=nemotron_h_layer_types("MEMEMEMMEME")), {},
     "needs a 'mamba2' and a 'full_attention'"),
    (dict(mamba_n_groups=3), {}, "multiple of mamba_n_groups"),
    (dict(first_k_dense_replace=1, dense_ffn_hidden_size=64), {},
     "first_k_dense_replace"),
    (dict(hc_mult=2), {}, "hc_mult"),
    (dict(num_experts=1, moe_router_experts=None, moe_latent_size=None,
          moe_shared_expert_ffn=None, n_shared_experts=0,
          moe_score_correction_bias=False, moe_scoring_func="softmax",
          moe_routed_scaling_factor=1.0, moe_dispatch="sort"), {},
     "needs num_experts > 1"),
    # a model without positions meets MLA's own rule before the pattern's
    (dict(q_lora_rank=16, kv_lora_rank=16, qk_rope_head_dim=16,
          qk_nope_head_dim=8, v_head_dim=8), {}, "MLA .* rotary attention"),
    (dict(sliding_window=16), {}, "refused with MLA"),
    (dict(moe_dispatch="sort"), {}, "dropless"),
    ({}, dict(tensor_parallel=2), "one device only"),
    ({}, dict(pipeline_parallel=2), "one device only"),
    ({}, dict(context_parallel=2), "one device only"),
    (dict(attention_impl="ring"), {}, "context-parallel"),
])
def test_model_refusals_by_name(change, parallel, match):
    cfg = dataclasses.replace(MODEL_PRESETS["nemotron-3-super-tiny"](),
                              **change)
    with pytest.raises(AssertionError, match=match):
        MegatronConfig(model=cfg, parallel=ParallelConfig(**parallel)
                       ).validate(2 if parallel else 1)
