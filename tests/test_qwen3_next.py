"""Qwen3-Next-80B-A3B-Instruct on the normal path (PR 60): Gated DeltaNet
layers (models/gated_delta.py over ops/kda_chunk.py's scalar-decay forms)
three to one beside gated softmax attention over keys and values (an output
gate, a zero-centred norm a head, a quarter of each head rotated:
models/attention.py), 512 softmax-routed experts beside a gated shared one.
The model as `finetune.py` builds it (`parse_cli` -> `model_init` ->
`model_forward` / `loss_fn`) against the float32 reference (`benchmark/
reference/qwen3_next.py`: a token at a time, no cache, no state carried).
Float32 throughout: 1e-4 on logits, on the loss and on gradients relative to
their largest entry."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import qwen3_next as reference
from megatron_tpu.arguments import parse_cli
from megatron_tpu.config import (MODEL_PRESETS, MegatronConfig, ModelConfig,
                                 ParallelConfig, qwen3_next_layer_types)
from megatron_tpu.models import language_model as lm
from megatron_tpu.models.attention import (ConvKVCache, _folded_update_attend,
                                           attention_apply, attention_init)
from megatron_tpu.models.moe import moe_apply
from megatron_tpu.models.norms import apply_norm, norm_init
from megatron_tpu.models.rope import apply_rotary, precompute_freqs
from megatron_tpu.models.transformer import _pattern_period

TOL = 1e-4
# tests/test_kimi_linear.py says why
STD = 0.11
PERIODS = ",".join(["linear_attention"] * 3 + ["full_attention"]) 
CUT = ["--model", "qwen3-next", "--num_layers", "8", "--layer_types",
       PERIODS + "," + PERIODS, "--num_experts", "128", "--vocab_size",
       "37984", "--bf16"]


def drawn(params, seed=7):
    """A trained model's zero-centred scales are not 0: w ~ N(0, 0.1^2) on
    every norm but the mixers' own (whose scale is w itself), so that 1 + w
    against w shows."""
    def bump(path, leaf):
        name = jax.tree_util.keystr(path)
        if "norm" in name and "gdn" not in name:
            key = jax.random.fold_in(jax.random.PRNGKey(seed),
                                     hash(name) % (1 << 30))
            return leaf + 0.1 * jax.random.normal(key, leaf.shape, leaf.dtype)
        return leaf
    return jax.tree_util.tree_map_with_path(bump, params)


def _model(**over):
    cfg = dataclasses.replace(
        MODEL_PRESETS["qwen3-next-tiny"](), compute_dtype="float32",
        init_method_std=STD, **over)
    return cfg, drawn(lm.model_init(jax.random.PRNGKey(0), cfg))


@pytest.fixture(scope="module")
def model():
    return _model()


def _logits(params, cfg, tokens):
    got, _ = lm.model_forward(params, jnp.asarray(tokens)[None], cfg,
                              rope=lm.make_rope(cfg),
                              logits_dtype=jnp.float32)
    return np.asarray(got)[0, :, :cfg.vocab_size]


def test_presets_hold_the_published_sizes():
    cfg = MODEL_PRESETS["qwen3-next"]()
    assert isinstance(cfg, ModelConfig)
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_kv_heads, cfg.kv_channels, cfg.ffn_hidden_size,
            cfg.moe_shared_expert_ffn, cfg.vocab_size,
            cfg.max_position_embeddings, cfg.norm_epsilon, cfg.rope_theta) \
        == (48, 2048, 16, 2, 256, 512, 512, 151936, 262144, 1e-6, 1e7)
    assert (cfg.gdn_key_heads, cfg.gdn_value_heads, cfg.gdn_key_head_dim,
            cfg.gdn_value_head_dim, cfg.gdn_conv_kernel,
            cfg.gdn_conv_channels) == (16, 32, 128, 128, 4, 8192)
    assert (cfg.partial_rotary_factor, cfg.rotary_dim, cfg.attn_output_gate,
            cfg.qk_head_norm, cfg.norm_type) == \
        (0.25, 64, True, True, "rmsnorm_1p")
    assert (cfg.num_experts, cfg.router_experts, cfg.moe_top_k,
            cfg.n_shared_experts, cfg.moe_shared_expert_gate,
            cfg.moe_scoring_func, cfg.moe_norm_topk_prob,
            cfg.moe_routed_scaling_factor, cfg.first_k_dense_replace) == \
        (512, 512, 10, 1, True, "softmax", True, 1.0, 0)
    types = cfg.layer_types
    assert types == qwen3_next_layer_types(48, 4)
    assert [i for i, k in enumerate(types) if k == "full_attention"] == \
        list(range(3, 48, 4))
    assert (types.count("linear_attention"),
            types.count("full_attention")) == (36, 12)
    assert cfg.state_kind == "linear_attention" and not cfg.one_sublayer
    assert cfg.conv_state_shape == (3, 8192)
    assert cfg.ssm_state_shape == (32, 128, 128)
    assert lm.make_rope(cfg, 64).cos.shape == (64, 32)
    assert not cfg.tie_embed_logits and cfg.mtp_num_layers == 0
    MegatronConfig(model=cfg).validate(1)


def test_the_cli_builds_the_cells_cut():
    """`--model qwen3-next` cut as the benchmark's configuration cuts it:
    published layers 0 to 7 (two whole periods), 128 experts held under a
    router of 512, a quarter of the vocabulary: 3,667 M parameters by ISSUE
    60's count, each kind stacked apart."""
    cfg, _ = parse_cli(CUT, n_devices=1)
    m = cfg.model
    assert m.params_dtype == m.compute_dtype == "bfloat16"
    assert (m.num_experts, m.router_experts, m.moe_first_expert) == \
        (128, 512, 0)
    assert (m.kv_layers, m.state_layers) == (2, 6)
    assert _pattern_period(m.layer_types) == (4, 2)
    shapes = jax.eval_shape(lambda: lm.model_init(jax.random.PRNGKey(0), m))
    count = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes))
    assert round(count / 1e6) == 3667
    stacks = shapes["transformer"]["layers"]
    assert set(stacks) == {"linear_attention", "full_attention"}
    mixer = stacks["linear_attention"]["gdn"]
    assert sum(int(np.prod(x.shape[1:])) for x in jax.tree.leaves(mixer)) \
        == 33_718_464
    assert mixer["in_proj"].shape == (6, 2048, 12288)
    assert mixer["ba_proj"].shape == (6, 2048, 64)
    assert mixer["conv"].shape == (6, 4, 8192)
    assert mixer["A_log"].shape == mixer["dt_bias"].shape == (6, 32)
    assert mixer["norm"]["scale"].shape == (6, 128)
    assert mixer["out_proj"].shape == (6, 4096, 2048)
    attn = stacks["full_attention"]["attention"]
    assert set(attn) == {"wq", "wkv", "wo", "q_norm", "k_norm"}
    assert sum(int(np.prod(x.shape[1:])) for x in jax.tree.leaves(attn)) \
        == 27_263_488
    assert attn["wq"].shape == (2, 2048, 8192)
    assert attn["wkv"].shape == (2, 2048, 1024)
    assert attn["q_norm"]["scale"].shape == (2, 256)
    mlp = stacks["linear_attention"]["mlp"]
    assert mlp["router"].shape == (6, 2048, 512)
    assert mlp["w1"].shape == (6, 128, 2048, 1024)
    assert mlp["shared"]["w1"].shape == (6, 2048, 2, 512)
    assert mlp["shared_gate"].shape == (6, 2048, 1)
    # the slice is 37,984 (296.75 lane tiles); the program holds every
    # vocabulary padded to whole tiles of 128 and masks the 32 beyond it
    assert m.vocab_size == 37984 and m.padded_vocab_size == 38016
    assert shapes["lm_head"].shape == (2048, 38016)
    # what a slot costs: 2 x 2,048 B a token, 12 MiB of state, 294,912 B of
    # depthwise inputs
    from megatron_tpu.serving.kv_pool import slot_nbytes
    assert slot_nbytes(m, 32768) == 32768 * 4096 + 12_582_912 + 294_912


def test_the_initialiser_gives_a_memory(model):
    cfg, params = model
    mixer = params["transformer"]["layers"]["linear_attention"]["gdn"]
    step = jax.nn.softplus(mixer["dt_bias"])
    assert 0.0009 < float(step.min()) and float(step.max()) < 0.11
    a = jnp.exp(mixer["A_log"])
    assert 1.0 <= float(a.min()) and float(a.max()) <= 16.0
    norms = params["transformer"]["layers"]["full_attention"]
    assert float(jnp.abs(norms["input_norm"]["scale"]).max()) < 0.6


@pytest.mark.parametrize("impl", ["dot", "flash"])
def test_forward_without_a_cache_matches_reference(impl):
    cfg, params = _model(attention_impl=impl)
    tokens = np.random.default_rng(1).integers(1, cfg.vocab_size, 37)
    want = np.asarray(reference.logits(params, jnp.asarray(tokens), cfg))
    assert np.abs(want).max() > 0.5
    assert np.abs(_logits(params, cfg, tokens) - want).max() < TOL


def test_loss_and_gradients_match_the_reference(model):
    """`loss_fn` and its gradient through the pattern scan and the rule's
    recurrence against the reference's own loss differentiated: every leaf
    of the tree."""
    cfg, params = model
    tokens = jnp.asarray(np.random.default_rng(23).integers(
        1, cfg.vocab_size, (1, 21)))
    rope = lm.make_rope(cfg)
    value, grads = jax.value_and_grad(
        lambda p: lm.loss_fn(p, tokens, cfg, rope=rope))(params)
    want_value, want = reference.loss_and_grads(
        params, tokens, jnp.ones((1, 20)), cfg)
    assert abs(float(value) - float(want_value)) < TOL
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    flat_want = jax.tree.leaves(want)
    assert len(flat) == len(flat_want)
    for (path, g), w in zip(flat, flat_want):
        scale = np.abs(np.asarray(w)).max()
        assert scale > 0, path
        assert np.abs(np.asarray(g - w)).max() < TOL * max(scale, 1.0), path


@pytest.mark.parametrize("fault", sorted(reference.FAULTS))
def test_a_fault_in_the_reference_fails_the_comparison(model, fault):
    """Each piece of the mathematics the benchmark's control plants a fault
    in moves the log-probabilities by a hundred tolerances here, but a state
    in bfloat16 (several) and what only a chunk's edge shows (none in 37
    rows under an edge of 4,096; both with the edge at 16)."""
    cfg, params = model
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        1, cfg.vocab_size, 37))
    want = np.asarray(reference.token_logprobs(params, tokens, cfg))
    off = np.asarray(reference.token_logprobs(
        params, tokens, cfg, faults=frozenset({fault})))
    edge = fault in ("state_reset", "conv_reset")
    least = {"state_bf16": 3}.get(fault, 100) * TOL
    assert (np.abs(off - want).max() > (0 if edge else least)) == (not edge)


@pytest.mark.parametrize("fault", ["state_reset", "conv_reset"])
def test_a_chunks_edge_shows_where_there_is_one(model, fault, monkeypatch):
    cfg, params = model
    monkeypatch.setattr(reference, "EDGE", 16)
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        1, cfg.vocab_size, 37))
    want = np.asarray(reference.token_logprobs(params, tokens, cfg))
    off = np.asarray(reference.token_logprobs(
        params, tokens, cfg, faults=frozenset({fault})))
    assert np.abs(off - want)[:15].max() == 0
    assert np.abs(off - want).max() > 10 * TOL


def test_the_reference_padded_behind_live_reads_what_the_unpadded_reads(
        model):
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
    assert padded["states"].shape == (2, 6, 4, 16, 16)
    assert padded["inputs"].shape == (2, 6, 3, cfg.gdn_conv_channels)
    assert padded["keys"].shape == (2, reference.KEY_ROWS, 2 * 16)
    for part in ("states", "inputs"):
        assert close(padded[part][0], plain[part][0])
        assert close(padded[part][1], longer[part][0])
        assert not close(padded[part][1], padded[part][0])
    assert close(padded["keys"], plain["keys"])
    assert close(padded["keys"][:, 1:], longer["keys"][:, :-1])


def test_four_shares_add_up_to_the_uncut_layer():
    """The share tied to the model: an expert layer of 8 experts under the
    uncut reference against the four shares of 2 experts each through
    `moe_apply` (`moe_first_expert` 0, 2, 4, 6 under `moe_router_experts`
    8); the GATED shared expert, which every chip computes alike, is counted
    once; and the held share's program is the reference given the same
    share."""
    cfg, params = _model()
    mlp = params["transformer"]["layers"]["linear_attention"]["mlp"]
    at = 2
    u = jax.random.normal(jax.random.PRNGKey(5), (1, 19, cfg.hidden_size))
    routed, shared, w = reference.experts(mlp, u[0], cfg, at)
    assert (np.asarray(w) > 0).sum(axis=1).tolist() == [cfg.moe_top_k] * 19
    assert abs(float(np.asarray(w).sum(axis=1).max()) - 1.0) < 1e-6
    _, ungated, _ = reference.experts(mlp, u[0], cfg, at,
                                      faults=frozenset({"no_shared_gate"}))
    assert np.abs(np.asarray(ungated - shared)).max() > 1e-2
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
        assert np.abs(parts[-1]).max() > 1e-3
    assert np.abs(sum(parts) + np.asarray(shared)
                  - np.asarray(routed + shared)).max() < TOL


# ---- attention's three additions, each alone and together -----------------

def _attention(**over):
    base = dict(num_layers=1, hidden_size=64, num_attention_heads=4,
                num_kv_heads=2, kv_channels=16, ffn_hidden_size=64,
                vocab_size=64, seq_length=64, use_rotary_emb=True,
                attention_impl="dot", compute_dtype="float32",
                init_method_std=STD, norm_epsilon=1e-6)
    cfg = ModelConfig(**{**base, **over}).derived()
    params = drawn(attention_init(jax.random.PRNGKey(3), cfg))
    return cfg, params


def _plain(params, x, cfg, *, gate, norm_1p, turned):
    """The attention sublayer in plain float32, the three additions as
    arguments (benchmark/reference/qwen3_next.py's, with each one an
    option)."""
    s, n, nkv, d = x.shape[0], 4, 2, 16
    qg = (x @ params["wq"]).reshape(s, n, -1)
    q = qg[..., :d]
    kv = (x @ params["wkv"]).reshape(s, 2, nkv, d)
    k, v = kv[:, 0], kv[:, 1]
    if "q_norm" in params:
        def head_norm(p, t):
            t = t / jnp.sqrt(jnp.mean(t * t, -1, keepdims=True) + 1e-6)
            return t * ((1.0 + p["scale"]) if norm_1p else p["scale"])
        q, k = head_norm(params["q_norm"], q), head_norm(params["k_norm"], k)
    q, k = (reference._rotary(t, 10000.0, turned) for t in (q, k))
    k, v = jnp.repeat(k, 2, axis=1), jnp.repeat(v, 2, axis=1)
    scores = jnp.einsum("snd,tnd->nst", q, k) / 4.0
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    out = jnp.einsum("nst,tnd->snd", jax.nn.softmax(scores, -1), v)
    out = out.reshape(s, n * d)
    if gate:
        out = out * jax.nn.sigmoid(qg[..., d:].reshape(s, n * d))
    return out @ params["wo"]


@pytest.mark.parametrize("name,over,plain", [
    ("partial_rotary", dict(partial_rotary_factor=0.25),
     dict(gate=False, norm_1p=False, turned=4)),
    ("gate", dict(attn_output_gate=True),
     dict(gate=True, norm_1p=False, turned=16)),
    ("norm_1p", dict(qk_head_norm=True, norm_type="rmsnorm_1p"),
     dict(gate=False, norm_1p=True, turned=16)),
    ("head_norm_w", dict(qk_head_norm=True),
     dict(gate=False, norm_1p=False, turned=16)),
    ("together", dict(partial_rotary_factor=0.25, attn_output_gate=True,
                      qk_head_norm=True, norm_type="rmsnorm_1p"),
     dict(gate=True, norm_1p=True, turned=4)),
])
def test_attentions_additions_against_the_plain_form(name, over, plain):
    cfg, params = _attention(**over)
    rope = lm.make_rope(cfg, 64)
    assert rope.cos.shape[-1] == plain["turned"] // 2
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 23, 64))
    got, _ = attention_apply(params, x, cfg, rope_cos=rope.cos,
                             rope_sin=rope.sin)
    for row, out in zip(x, got):
        want = _plain(params, row, cfg, **plain)
        assert float(jnp.abs(want).max()) > 0.05
        assert float(jnp.abs(out - want).max()) < TOL
    # and each addition shows: the plain form without it is another function
    other = dict(plain, **{"partial_rotary": dict(turned=16),
                           "gate": dict(gate=False),
                           "norm_1p": dict(norm_1p=False),
                           "head_norm_w": dict(norm_1p=True),
                           "together": dict(norm_1p=False)}[name])
    off = _plain(params, x[0], cfg, **other)
    assert float(jnp.abs(got[0] - off).max()) > 50 * TOL


def test_partial_rotary_at_one_is_todays():
    """A factor of 1.0 builds today's tables and takes today's path: the
    same jaxpr, character for character."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 4, 16))
    cos, sin = precompute_freqs(16, 32)
    whole = apply_rotary(x, cos, sin)
    assert float(jnp.abs(whole[:, 1:, :, 4:] - x[:, 1:, :, 4:]).max()) > 0.1
    cfg_a, _ = _attention()
    cfg_b, _ = _attention(partial_rotary_factor=1.0)
    assert cfg_a == cfg_b and cfg_a.rotary_dim == 16
    # a quarter turned: the first 4 channels are the whole rotation of a
    # head of 4, the other 12 untouched
    cos4, sin4 = precompute_freqs(4, 32)
    part = apply_rotary(x, cos4, sin4)
    assert bool((part[..., 4:] == x[..., 4:]).all())
    assert float(jnp.abs(part[..., :4]
                         - apply_rotary(x[..., :4], cos4, sin4)).max()) == 0
    assert float(jnp.abs(part[:, 1:, :, :4] - x[:, 1:, :, :4]).max()) > 0.1


def test_zero_centred_norm():
    p = norm_init("rmsnorm_1p", 8)
    assert float(jnp.abs(p["scale"]).max()) == 0.0
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 8))
    plain = apply_norm("rmsnorm", norm_init("rmsnorm", 8), x, 1e-6)
    assert float(jnp.abs(apply_norm("rmsnorm_1p", p, x, 1e-6)
                         - plain).max()) < 1e-6
    w = {"scale": 0.1 * jax.random.normal(jax.random.PRNGKey(1), (8,))}
    got = apply_norm("rmsnorm_1p", w, x, 1e-6)
    assert float(jnp.abs(got - plain * (1.0 + w["scale"])).max()) < 1e-6


@pytest.mark.parametrize("offset", [0, 128, 640])
def test_folded_flash_form_is_the_products_over_the_region(offset):
    """A chunk of 128 rows of 4 heads over 2 kv heads of 256 channels at
    offsets 0, 128 and a deep one, through the flash form that reads the
    folded rows [max_seq, n_kv x 256] at the offset (its XLA blockwise
    fallback here; `tests/test_tpu_compile.py` compiles the kernel) against
    `over_the_region`, which a head narrower than a lane tile still takes."""
    base = dict(num_layers=4, hidden_size=64, num_attention_heads=4,
                num_kv_heads=2, ffn_hidden_size=64, vocab_size=64,
                seq_length=64, compute_dtype="float32",
                layer_types=("conv", "full_attention") * 2)
    flash = ModelConfig(**base, kv_channels=256,
                        attention_impl="flash").derived()
    dot = dataclasses.replace(flash, attention_impl="dot")
    keys = jax.random.split(jax.random.PRNGKey(offset), 5)
    q = jax.random.normal(keys[0], (1, 128, 4, 256))
    k, v = (jax.random.normal(kk, (1, 128, 2, 256)) for kk in keys[1:3])
    cache = ConvKVCache.create(flash, 1, 1024, jnp.float32)
    cache = cache._replace(
        k=jax.random.normal(keys[3], cache.k.shape),
        v=jax.random.normal(keys[4], cache.v.shape),
        offset=jnp.full_like(cache.offset, offset))
    outs = []
    for cfg in (flash, dot):
        out, new = jax.jit(lambda c, cfg=cfg: _folded_update_attend(
            q, k, v, c, 1, cfg, scale=1 / 16.0))(cache)
        outs.append(out)
        assert int(new.offset[1]) == offset + 128 and int(new.offset[0]) \
            == offset
    assert float(jnp.abs(outs[1]).max()) > 0.1
    assert float(jnp.abs(outs[0] - outs[1]).max()) < TOL
    # the flash form makes no [heads, rows, max_seq] scores
    text = str(jax.make_jaxpr(lambda c: _folded_update_attend(
        q, k, v, c, 1, flash, scale=1 / 16.0))(cache))
    assert "f32[1,4,128,1024]" not in text
    assert "f32[1,4,128,1024]" in str(jax.make_jaxpr(
        lambda c: _folded_update_attend(q, k, v, c, 1, dot,
                                        scale=1 / 16.0))(cache))


# ---- validate's words -------------------------------------------------------

@pytest.mark.parametrize("change,parallel,match", [
    (dict(num_layers=6), {}, "8 entries"),
    (dict(layer_types=("linear_attention", "mamba") * 4), {}, "one of"),
    (dict(layer_types=("linear_attention", "kda") * 4), {}, "one of"),
    (dict(layer_types=("linear_attention",) * 8), {},
     "stand beside attention layers over keys and values"),
    (dict(kv_lora_rank=16, qk_rope_head_dim=16, qk_nope_head_dim=8,
          v_head_dim=8), {},
     "stand beside attention layers over keys and values"),
    (dict(gdn_value_heads=3), {}, "a multiple of gdn_key_heads"),
    (dict(gdn_conv_kernel=1), {}, "gdn_conv_kernel >= 2"),
    (dict(hc_mult=2), {}, "hc_mult"),
    (dict(first_k_dense_replace=1, dense_ffn_hidden_size=64), {},
     "first_k_dense_replace"),
    (dict(mtp_num_layers=1), {}, "mtp_num_layers"),
    (dict(sliding_window=16), {}, "sliding_window"),
    (dict(moe_dispatch="sort"), {}, "dropless"),
    (dict(partial_rotary_factor=0.2), {}, "whole number of pairs"),
    (dict(norm_type="rms"), {}, "norm_type"),
    ({}, dict(tensor_parallel=2), "one device"),
    ({}, dict(pipeline_parallel=2), "one device"),
    (dict(attention_impl="ring"), {}, "context-parallel"),
])
def test_model_refusals_by_name(change, parallel, match):
    cfg = dataclasses.replace(MODEL_PRESETS["qwen3-next-tiny"](), **change)
    with pytest.raises(AssertionError, match=match):
        MegatronConfig(model=cfg, parallel=ParallelConfig(**parallel)
                       ).validate(2 if parallel else 1)


def test_the_gate_and_the_partial_rotary_stay_refused_elsewhere():
    """Neither has been run with MLA, with a stack of window and full
    layers, or on a mesh; the shared expert's gate is the dropless path's."""
    joyai = MODEL_PRESETS["joyai-llm-flash-tiny"]()
    for change in (dict(attn_output_gate=True),
                   dict(partial_rotary_factor=0.5)):
        with pytest.raises(AssertionError, match="on one device: MLA"):
            MegatronConfig(model=dataclasses.replace(joyai, **change)
                           ).validate(1)
    falcon = MODEL_PRESETS["falcon-tiny"]()
    with pytest.raises(AssertionError, match="no head"):
        MegatronConfig(
            model=dataclasses.replace(falcon, attn_output_gate=True),
            parallel=ParallelConfig(tensor_parallel=2)).validate(2)
    olmoe = dataclasses.replace(MODEL_PRESETS["olmoe-tiny"](),
                                moe_shared_expert_gate=True)
    with pytest.raises(AssertionError, match="moe_shared_expert_gate"):
        MegatronConfig(model=olmoe).validate(1)
