"""Kimi-Linear-48B-A3B-Instruct on the normal path (PR 58): Kimi Delta
Attention layers (models/kda.py over ops/kda_chunk.py) three to one beside
NoPE latent attention with ONE query matrix (models/mla.py), behind a
leading dense layer, with sigmoid-routed experts and a shared one. The model
as `finetune.py` builds it (`parse_cli` -> `model_init` -> `model_forward` /
`loss_fn`) against the float32 reference (`benchmark/reference/
kimi_linear.py`: a token at a time, no cache, no state carried, full heads).
Float32 throughout: 1e-4 on logits, on the loss and on gradients relative to
their largest entry."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import kimi_linear as reference
from megatron_tpu.arguments import parse_cli
from megatron_tpu.config import (MODEL_PRESETS, MegatronConfig, ModelConfig,
                                 ParallelConfig, kimi_linear_layer_types)
from megatron_tpu.inference.generation import init_kv_caches, prefill_chunk
from megatron_tpu.models import language_model as lm
from megatron_tpu.models.attention import LatentStateCache
from megatron_tpu.models.moe import moe_apply
from megatron_tpu.models.transformer import _pattern_period

TOL = 1e-4
# matrices at sqrt(64) x 0.11 = 0.9 of gain, the published widths' sqrt(2304)
# x 0.02, so that a sublayer adds to the stream what it adds at width
STD = 0.11
CUT = ["--model", "kimi-linear", "--num_layers", "8", "--layer_types",
       "kda,kda,kda,full_attention,kda,kda,kda,full_attention",
       "--num_experts", "64", "--vocab_size", "40960", "--bf16"]


def _model(**over):
    cfg = dataclasses.replace(
        MODEL_PRESETS["kimi-linear-tiny"](), compute_dtype="float32",
        init_method_std=STD, **over)
    return cfg, lm.model_init(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def model():
    return _model()


def _logits(params, cfg, tokens):
    got, _ = lm.model_forward(params, jnp.asarray(tokens)[None], cfg,
                              logits_dtype=jnp.float32)
    return np.asarray(got)[0, :, :cfg.vocab_size]


def test_presets_hold_the_published_sizes():
    cfg = MODEL_PRESETS["kimi-linear"]()
    assert isinstance(cfg, ModelConfig)
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_attention_heads,
            cfg.ffn_hidden_size, cfg.dense_ffn_hidden_size, cfg.vocab_size,
            cfg.max_position_embeddings, cfg.norm_epsilon) == \
        (27, 2304, 32, 1024, 9216, 163840, 1048576, 1e-5)
    assert (cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_conv_kernel,
            cfg.kda_gate_rank, cfg.kda_d_inner) == (32, 128, 4, 128, 4096)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_channels,
            cfg.mla_nope, cfg.use_rotary_emb) == \
        (None, 512, 128, 64, 128, 64, True, False)
    assert (cfg.num_experts, cfg.router_experts, cfg.moe_top_k,
            cfg.n_shared_experts, cfg.moe_routed_scaling_factor,
            cfg.first_k_dense_replace, cfg.moe_scoring_func,
            cfg.moe_score_correction_bias, cfg.moe_norm_topk_prob) == \
        (256, 256, 8, 1, 2.446, 1, "sigmoid", True, True)
    types = cfg.layer_types
    assert types == kimi_linear_layer_types(27)
    assert [l + 1 for l, k in enumerate(types) if k == "full_attention"] == \
        [4, 8, 12, 16, 20, 24, 27]
    assert (types.count("kda"), types.count("full_attention")) == (20, 7)
    assert cfg.state_kind == "kda" and not cfg.one_sublayer
    assert lm.make_rope(cfg) is None and not cfg.tie_embed_logits
    # a slot's state a KDA layer: 2 MiB of float32 matrices and 3 x 12,288
    # bf16 depthwise inputs; an MLA layer's row 1,152 B a token
    assert cfg.ssm_state_shape == (32, 128, 128)
    assert cfg.ssm_state_width * 4 == 2 * 2 ** 20
    assert cfg.conv_state_shape == (3, 12288)
    assert cfg.kv_row_width * 2 == 1152
    MegatronConfig(model=cfg).validate(1)
    MegatronConfig(model=MODEL_PRESETS["kimi-linear-tiny"]()).validate(1)


def test_the_cli_builds_the_cells_cut():
    """`--model kimi-linear` cut as the benchmark's configuration cuts it:
    published layers 1 to 8, 64 experts held under a router of 256, a
    quarter of the vocabulary: 3,772.4 M parameters by ISSUE 58's count and
    the output gates' six biases of 4,096 (`assumed`), each kind stacked
    apart in its group."""
    cfg, _ = parse_cli(CUT, n_devices=1)
    m = cfg.model
    assert m.params_dtype == m.compute_dtype == "bfloat16"
    assert (m.num_experts, m.router_experts, m.moe_first_expert) == \
        (64, 256, 0)
    assert (m.kv_layers, m.state_layers) == (2, 6)
    # behind the dense layer: K K A K K K A, two of a kind then a tail
    assert _pattern_period(m.layer_types[1:]) == (1, 2)
    shapes = jax.eval_shape(lambda: lm.model_init(jax.random.PRNGKey(0), m))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert count == 3_772_393_408 and round((count - 6 * 4096) / 1e5) == 37724
    stacks = shapes["transformer"]
    assert set(stacks) == {"dense", "moe"}
    assert set(stacks["dense"]) == {"kda"}
    assert set(stacks["moe"]) == {"kda", "full_attention"}
    mixer = stacks["moe"]["kda"]["kda"]
    assert mixer["in_proj"].shape == (5, 2304, 12288)
    assert mixer["conv"].shape == (5, 4, 12288)
    assert mixer["low_proj"].shape == (5, 2304, 288)
    assert mixer["f_b"].shape == mixer["g_b"].shape == (5, 128, 4096)
    assert mixer["A_log"].shape == (5, 32)
    assert mixer["dt_bias"].shape == mixer["g_bias"].shape == (5, 4096)
    assert mixer["norm"]["scale"].shape == (5, 128)
    assert mixer["out_proj"].shape == (5, 4096, 2304)
    attn = stacks["moe"]["full_attention"]["attention"]
    assert set(attn) == {"wq", "wkv_a", "kv_norm", "wkv_b", "wo"}
    assert attn["wq"].shape == (2, 2304, 32 * 192)
    assert attn["wkv_a"].shape == (2, 2304, 576)
    assert attn["wkv_b"].shape == (2, 512, 32 * 256)
    mlp = stacks["moe"]["full_attention"]["mlp"]
    assert mlp["router"].shape == (2, 2304, 256)
    assert mlp["w1"].shape == (2, 64, 2304, 2048)
    assert stacks["dense"]["kda"]["mlp"]["w1"].shape == (1, 2304, 2, 9216)
    assert shapes["lm_head"].shape == (2304, 40960)
    # what a slot costs: 2,304 B a token, 12,582,912 B of state, 442,368 B
    # of depthwise inputs
    from megatron_tpu.serving.kv_pool import slot_nbytes
    assert m.kv_layers * m.kv_row_width * 2 == 2304
    assert slot_nbytes(m, 32768) == 32768 * 2304 + 12_582_912 + 442_368


def test_the_initialiser_gives_a_memory(model):
    _, params = model
    m = params["transformer"]["moe"]["kda"]["kda"]
    a = np.exp(np.asarray(m["A_log"]))
    assert 1.0 <= a.min() and a.max() <= 16.0
    dt = np.asarray(jax.nn.softplus(m["dt_bias"]))
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 0.1 * 1.001
    assert np.all(np.asarray(m["norm"]["scale"]) == 1.0)


@pytest.mark.parametrize("impl", ["dot", "flash"])
def test_forward_without_a_cache_matches_reference(impl):
    cfg, params = _model(attention_impl=impl)
    tokens = np.random.default_rng(0).integers(1, cfg.vocab_size, (2, 37))
    for toks in tokens:
        want = np.asarray(reference.logits(params, jnp.asarray(toks), cfg))
        assert np.abs(want).max() > 1.0
        assert np.abs(_logits(params, cfg, toks) - want).max() < TOL


@pytest.mark.parametrize("types,dense", [
    ("KAKK", 1), ("AKKA", 1), ("KKKAKKKAKKA", 2)])
def test_patterns_no_period_covers(types, dense):
    """An attention layer first behind the dense one, a tail off the
    period, two dense layers: each layer at its own index in its own kind's
    stack and cache."""
    kinds = tuple({"K": "kda", "A": "full_attention"}[c] for c in types)
    cfg, params = _model(num_layers=len(kinds), layer_types=kinds,
                         first_k_dense_replace=dense)
    MegatronConfig(model=cfg).validate(1)
    tokens = np.random.default_rng(len(types)).integers(
        1, cfg.vocab_size, 23)
    want = np.asarray(reference.logits(params, jnp.asarray(tokens), cfg))
    assert np.abs(_logits(params, cfg, tokens) - want).max() < TOL


def test_loss_and_gradients_match_the_reference(model):
    """`loss_fn` and its gradient through the pattern scan and the rule's
    recurrence against the reference's own loss differentiated: every leaf
    of the tree but the choosing bias, which chooses and is not valued."""
    cfg, params = model
    tokens = jnp.asarray(np.random.default_rng(23).integers(
        1, cfg.vocab_size, (1, 21)))
    value, grads = jax.value_and_grad(
        lambda p: lm.loss_fn(p, tokens, cfg))(params)
    want_value, want = reference.loss_and_grads(
        params, tokens, jnp.ones((1, 20)), cfg)
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
    """What the comparison above can see: each piece of the mathematics the
    benchmark's control plants a fault in moves the log-probabilities by a
    hundred tolerances here, but a state or a sum in bfloat16 (several) and
    what only a chunk's edge at 4,096 shows (none in 37 rows: the serving
    tests' chunks see those)."""
    cfg, params = model
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        1, cfg.vocab_size, 37))
    want = np.asarray(reference.token_logprobs(params, tokens, cfg))
    off = np.asarray(reference.token_logprobs(
        params, tokens, cfg, faults=frozenset({fault})))
    edge = fault in ("state_reset", "conv_reset")
    least = {"state_bf16": 3, "sums_bf16": 3}.get(fault, 100) * TOL
    assert (np.abs(off - want).max() > (0 if edge else least)) == (not edge)


def test_nope_and_one_query_matrix_expanded_is_absorbed(model):
    """`models/mla.py` with `q_lora_rank` None and `mla_nope`: a prefill at
    offset 0 takes the expanded form, a continuation chunk and a decode step
    the absorbed one over the latent rows already held; all three read what
    the forward with no cache reads."""
    cfg, params = model
    toks = jnp.asarray(np.random.default_rng(4).integers(
        1, cfg.vocab_size, (1, 40)))
    full = _logits(params, cfg, toks[0])
    caches = init_kv_caches(cfg, 1, 64, dtype=jnp.float32)
    assert isinstance(caches, LatentStateCache)
    assert caches.c.shape == (2, 1, 40, 64) and caches.offset.shape == (2,)
    pad = lambda t, n: jnp.pad(t, ((0, 0), (0, n - t.shape[1])))  # noqa: E731
    caches, last = prefill_chunk(params, pad(toks[:, :20], 32), caches, cfg,
                                 rope=None, last_idx=19, next_offset=20)
    assert np.abs(np.asarray(last)[:cfg.vocab_size] - full[19]).max() < TOL
    caches, last = prefill_chunk(params, pad(toks[:, 20:37], 32), caches, cfg,
                                 rope=None, last_idx=16, next_offset=37)
    assert np.abs(np.asarray(last)[:cfg.vocab_size] - full[36]).max() < TOL
    assert caches.offset.tolist() == [37, 37]
    for i in range(37, 40):
        got, caches = lm.model_forward(params, toks[:, i:i + 1], cfg,
                                       kv_caches=caches,
                                       logits_dtype=jnp.float32)
        assert np.abs(np.asarray(got)[0, 0, :cfg.vocab_size]
                      - full[i]).max() < TOL


def test_the_reference_padded_behind_live_reads_what_the_unpadded_reads(
        model):
    """`reference.checked`: tokens padded behind `live` read what the
    unpadded read; the second state and the second depthwise inputs are
    those a row AHEAD, the first of the tokens one longer."""
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
    assert padded["states"].shape[:2] == (2, cfg.layers_of("kda"))
    assert padded["inputs"].shape == (2, 6, 3, 3 * cfg.kda_d_inner)
    assert padded["latent"].shape == (2, reference.LATENT_ROWS,
                                      cfg.kv_row_width)
    for part in ("states", "inputs"):
        assert close(padded[part][0], plain[part][0])
        assert close(padded[part][1], longer[part][0])
        assert not close(padded[part][1], padded[part][0])
    assert close(padded["latent"], plain["latent"])
    assert close(padded["latent"][:, 1:], longer["latent"][:, :-1])


def test_four_shares_add_up_to_the_uncut_layer():
    """The share tied to the model: an expert layer of 8 experts under the
    uncut reference against the four shares of 2 experts each through
    `moe_apply` (`moe_first_expert` 0, 2, 4, 6 under `moe_router_experts`
    8); the shared expert, which every chip computes alike, is counted
    once."""
    cfg, params = _model()
    mlp = params["transformer"]["moe"]["kda"]["mlp"]
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
        assert np.abs(np.asarray(out[0] - (mine + alike))).max() < TOL
        assert np.abs(np.asarray(alike - shared)).max() < 1e-6
        parts.append(np.asarray(out[0] - alike))      # the routed part
        assert np.abs(parts[-1]).max() > 1e-2
    assert np.abs(sum(parts) + np.asarray(shared)
                  - np.asarray(routed + shared)).max() < TOL


@pytest.mark.parametrize("change,parallel,match", [
    (dict(num_layers=6), {}, "8 entries"),
    (dict(layer_types=("kda", "mamba") * 4), {}, "one of"),
    (dict(layer_types=("kda",) * 8), {}, "stand beside MLA"),
    (dict(kv_lora_rank=None), {}, "stand beside MLA"),
    (dict(mla_nope=False), {}, "MLA .* rotary attention"),
    (dict(hc_mult=2), {}, "hc_mult"),
    (dict(kda_conv_kernel=1), {}, "kda_conv_kernel >= 2"),
    (dict(sliding_window=16), {}, "sliding_window"),
    (dict(moe_dispatch="sort"), {}, "dropless"),
    ({}, dict(tensor_parallel=2), "one device only"),
    ({}, dict(pipeline_parallel=2), "one device only"),
    (dict(attention_impl="ring"), {}, "context-parallel"),
])
def test_model_refusals_by_name(change, parallel, match):
    cfg = dataclasses.replace(MODEL_PRESETS["kimi-linear-tiny"](), **change)
    with pytest.raises(AssertionError, match=match):
        MegatronConfig(model=cfg, parallel=ParallelConfig(**parallel)
                       ).validate(2 if parallel else 1)


def test_the_other_state_kinds_stay_refused_with_mla():
    """The refusal was lifted for 'kda' | 'full_attention' alone."""
    cfg = dataclasses.replace(
        MODEL_PRESETS["jamba2-3b-tiny"](), use_rotary_emb=True,
        kv_lora_rank=16, qk_rope_head_dim=16, qk_nope_head_dim=8,
        v_head_dim=8, kv_channels=16)
    with pytest.raises(AssertionError, match="refused with MLA .* but for"):
        MegatronConfig(model=cfg).validate(1)
