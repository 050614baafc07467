"""Single-device model-core tests.

Ports the *contracts* of the reference's unit tests (SURVEY.md §4): GLU
activations vs analytic reference (ref: tests/test_activations.py:12-47),
norm/rope correctness, GQA/MQA equivalence properties, causality, and
loss-at-init sanity.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.config import ModelConfig, falcon_config, gpt_config, llama2_config
from megatron_tpu.models.attention import KVCache, attention_apply, attention_init
from megatron_tpu.models.language_model import loss_fn, make_rope, model_forward, model_init
from megatron_tpu.models.mlp import activation_fn
from megatron_tpu.models.norms import layernorm, layernorm_init, rmsnorm, rmsnorm_init
from megatron_tpu.models.rope import apply_rotary, precompute_freqs


class TestActivations:
    """(ref: tests/test_activations.py — GLU family vs torch reference)"""

    def test_swiglu(self):
        x = jnp.linspace(-3, 3, 16)
        a, b = x, x + 1
        expected = (x * jax.nn.sigmoid(x)) * (x + 1)
        np.testing.assert_allclose(activation_fn("swiglu", a, b), expected, rtol=1e-6)

    def test_geglu(self):
        a = jnp.linspace(-3, 3, 16)
        b = jnp.ones(16) * 2
        got = activation_fn("geglu", a, b)
        expected = jax.nn.gelu(a, approximate=False) * 2
        np.testing.assert_allclose(got, expected, rtol=1e-6)

    def test_reglu_liglu(self):
        a = jnp.array([-1.0, 2.0])
        b = jnp.array([3.0, 4.0])
        np.testing.assert_allclose(activation_fn("reglu", a, b), [0.0, 8.0])
        np.testing.assert_allclose(activation_fn("liglu", a, b), [-3.0, 8.0])

    def test_squared_relu(self):
        a = jnp.array([-2.0, 3.0])
        np.testing.assert_allclose(activation_fn("squared_relu", a), [0.0, 9.0])


class TestNorms:
    def test_rmsnorm_matches_formula(self):
        p = rmsnorm_init(64)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 64))
        got = rmsnorm(p, x, eps=1e-5)
        expected = x / np.sqrt(np.mean(np.square(np.asarray(x)), -1, keepdims=True) + 1e-5)
        np.testing.assert_allclose(got, expected, rtol=1e-5)

    def test_layernorm_zero_mean_unit_var(self):
        p = layernorm_init(64)
        x = jax.random.normal(jax.random.PRNGKey(0), (3, 64)) * 5 + 2
        y = np.asarray(layernorm(p, x, eps=1e-6))
        np.testing.assert_allclose(y.mean(-1), 0.0, atol=1e-5)
        np.testing.assert_allclose(y.var(-1), 1.0, atol=1e-3)

    def test_fp32_stats_under_bf16(self):
        p = rmsnorm_init(128)
        x = (jax.random.normal(jax.random.PRNGKey(1), (4, 128)) * 100).astype(jnp.bfloat16)
        y = rmsnorm(p, x)
        assert y.dtype == jnp.bfloat16
        assert bool(jnp.all(jnp.isfinite(y.astype(jnp.float32))))


class TestRoPE:
    def test_rotation_preserves_norm(self):
        cos, sin = precompute_freqs(64, 128)
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 4, 64))
        y = apply_rotary(x, cos, sin)
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(x), axis=-1),
            np.linalg.norm(np.asarray(y), axis=-1), rtol=1e-4)

    def test_position_zero_identity(self):
        cos, sin = precompute_freqs(32, 8)
        x = jax.random.normal(jax.random.PRNGKey(0), (1, 1, 2, 32))
        y = apply_rotary(x, cos, sin)
        np.testing.assert_allclose(y, x, atol=1e-6)

    def test_relative_property(self):
        """q(m)·k(n) depends only on m-n for rotary embeddings."""
        hd = 32
        cos, sin = precompute_freqs(hd, 64)
        q = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 1, hd))
        k = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 1, hd))
        # use same vector at every position
        q = jnp.broadcast_to(q[:, :1], q.shape)
        k = jnp.broadcast_to(k[:, :1], k.shape)
        qr, kr = apply_rotary(q, cos, sin), apply_rotary(k, cos, sin)
        d1 = jnp.sum(qr[0, 10, 0] * kr[0, 5, 0])
        d2 = jnp.sum(qr[0, 40, 0] * kr[0, 35, 0])
        np.testing.assert_allclose(d1, d2, rtol=1e-4)

    def test_scaling_factor_interpolates(self):
        cos1, sin1 = precompute_freqs(32, 16, scaling_factor=1.0)
        cos2, sin2 = precompute_freqs(32, 32, scaling_factor=2.0)
        # position 2k with factor 2 == position k with factor 1
        np.testing.assert_allclose(cos2[::2], cos1, rtol=1e-5)
        np.testing.assert_allclose(sin2[::2], sin1, rtol=1e-5)


def tiny_cfg(**kw) -> ModelConfig:
    base = dict(num_layers=2, hidden_size=64, num_attention_heads=4,
                vocab_size=128, make_vocab_size_divisible_by=64,
                seq_length=32, compute_dtype="float32")
    base.update(kw)
    return ModelConfig(**base).derived()


class TestAttention:
    def test_causality(self):
        """Future tokens must not affect earlier positions."""
        cfg = tiny_cfg()
        p = attention_init(jax.random.PRNGKey(0), cfg)
        rope = make_rope(cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, 64))
        y1, _ = attention_apply(p, x, cfg, rope_cos=rope.cos, rope_sin=rope.sin)
        x2 = x.at[:, 10:].set(99.0)
        y2, _ = attention_apply(p, x2, cfg, rope_cos=rope.cos, rope_sin=rope.sin)
        np.testing.assert_allclose(y1[:, :10], y2[:, :10], atol=1e-5)

    def test_gqa_equals_mha_when_kv_replicated(self):
        """With kv weights tiled to all heads, GQA == MHA output."""
        cfg_mha = tiny_cfg()
        cfg_gqa = tiny_cfg(num_kv_heads=2)
        p = attention_init(jax.random.PRNGKey(0), cfg_gqa)
        hd = cfg_gqa.kv_channels
        # build MHA weights replicating each kv head across its group
        wkv = p["wkv"].reshape(64, 2, cfg_gqa.num_kv_heads, hd)
        wkv_mha = jnp.repeat(wkv, 2, axis=2).reshape(64, -1)
        p_mha = dict(p, wkv=wkv_mha)
        rope = make_rope(cfg_gqa)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 64))
        y_gqa, _ = attention_apply(p, x, cfg_gqa, rope_cos=rope.cos, rope_sin=rope.sin)
        y_mha, _ = attention_apply(p_mha, x, cfg_mha, rope_cos=rope.cos, rope_sin=rope.sin)
        np.testing.assert_allclose(y_gqa, y_mha, atol=1e-5)

    def test_kv_cache_matches_full_forward(self):
        """Incremental decode == full-sequence forward
        (contract of InferenceParams, ref: forward_step.py:17-42)."""
        cfg = tiny_cfg(num_kv_heads=2)
        p = attention_init(jax.random.PRNGKey(0), cfg)
        rope = make_rope(cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 12, 64))
        y_full, _ = attention_apply(p, x, cfg, rope_cos=rope.cos, rope_sin=rope.sin)
        cache = KVCache.create(1, 1, 32, cfg.num_kv_heads, cfg.kv_channels,
                               jnp.float32)
        # prefill 8, then decode 4 one at a time
        y_pre, cache = attention_apply(p, x[:, :8], cfg, rope_cos=rope.cos,
                                       rope_sin=rope.sin, kv_cache=cache,
                                       cache_layer=0)
        outs = [y_pre]
        for t in range(8, 12):
            y_t, cache = attention_apply(p, x[:, t:t + 1], cfg, rope_cos=rope.cos,
                                         rope_sin=rope.sin, kv_cache=cache,
                                         cache_layer=0)
            outs.append(y_t)
        y_inc = jnp.concatenate(outs, axis=1)
        np.testing.assert_allclose(y_inc, y_full, atol=1e-4)


class TestFullModel:
    @pytest.mark.parametrize("cfg_fn", [
        lambda: tiny_cfg(),
        lambda: tiny_cfg(num_kv_heads=1, parallel_attn=True, norm_type="layernorm",
                         activation="gelu", tie_embed_logits=True),
        lambda: tiny_cfg(use_rotary_emb=False, use_position_embedding=True,
                         use_bias=True, activation="gelu", norm_type="layernorm",
                         tie_embed_logits=True),
    ], ids=["llama-ish", "falcon-ish", "gpt-ish"])
    def test_loss_at_init_near_uniform(self, cfg_fn):
        cfg = cfg_fn()
        params = model_init(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
        loss = float(loss_fn(params, toks, cfg))
        assert abs(loss - np.log(cfg.vocab_size)) < 1.0

    def test_logits_shape_and_padded_vocab_masked(self):
        cfg = tiny_cfg()
        params = model_init(jax.random.PRNGKey(0), cfg)
        toks = jnp.zeros((1, 8), jnp.int32)
        logits, _ = model_forward(params, toks, cfg)
        assert logits.shape == (1, 8, cfg.padded_vocab_size)

    def test_overfit_tiny_batch(self):
        """Model can memorize a small batch — end-to-end learning sanity
        (analogue of the reference's verify/overfit gate, SURVEY.md §7 stage 3)."""
        import optax
        cfg = tiny_cfg()
        params = model_init(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
        opt = optax.adam(1e-3)
        state = opt.init(params)

        @jax.jit
        def step(params, state):
            loss, g = jax.value_and_grad(loss_fn)(params, toks, cfg)
            updates, state = opt.update(g, state)
            return optax.apply_updates(params, updates), state, loss

        losses = []
        for _ in range(60):
            params, state, loss = step(params, state)
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.5, losses[::10]

    def test_deterministic_forward(self):
        cfg = tiny_cfg(hidden_dropout=0.1, attention_dropout=0.1)
        params = model_init(jax.random.PRNGKey(0), cfg)
        toks = jnp.zeros((1, 8), jnp.int32)
        l1, _ = model_forward(params, toks, cfg, deterministic=True)
        l2, _ = model_forward(params, toks, cfg, deterministic=True)
        np.testing.assert_array_equal(l1, l2)

    def test_dropout_active_in_training_mode(self):
        cfg = tiny_cfg(hidden_dropout=0.5)
        params = model_init(jax.random.PRNGKey(0), cfg)
        toks = jnp.zeros((1, 8), jnp.int32)
        l1, _ = model_forward(params, toks, cfg, rng=jax.random.PRNGKey(1),
                              deterministic=False)
        l2, _ = model_forward(params, toks, cfg, rng=jax.random.PRNGKey(2),
                              deterministic=False)
        assert not np.allclose(np.asarray(l1), np.asarray(l2))
