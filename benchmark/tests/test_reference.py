"""The plain reference against the program's own forward at a tiny size:
logits, loss and gradients, for the 7B block (one LayerNorm, one kv head) and
the 40B block (two LayerNorms, grouped kv heads)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import falcon as reference
from megatron_tpu.config import falcon_config
from megatron_tpu.models import language_model as lm

BLOCKS = {
    "7b-like": dict(),
    "40b-like": dict(num_attention_heads=8, num_kv_heads=2,
                     parallel_layernorm=True),
}


@pytest.fixture(params=sorted(BLOCKS))
def case(request):
    cfg = falcon_config("tiny", vocab_size=384, seq_length=48,
                        compute_dtype="float32", attention_impl="dot",
                        **BLOCKS[request.param])
    params = lm.model_init(jax.random.PRNGKey(7), cfg)
    # non-trivial norms, so that a swapped scale or bias would show
    params = jax.tree.map(
        lambda x: x + 0.05 * jax.random.normal(jax.random.PRNGKey(x.size),
                                               x.shape, x.dtype), params)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 49), 1, 384)
    mask = (jax.random.uniform(jax.random.PRNGKey(9), (2, 48)) > 0.2
            ).astype(jnp.float32)
    return cfg, params, tokens, mask


def test_logits(case):
    cfg, params, tokens, _ = case
    want, _ = lm.model_forward(params, tokens[:, :-1], cfg)
    got = jnp.stack([reference.logits(params, t[:-1], cfg) for t in tokens])
    # float32 on both sides: only the order of summation differs
    np.testing.assert_allclose(got, want[..., :cfg.vocab_size],
                               rtol=2e-4, atol=2e-4)


def test_loss_and_gradients(case):
    cfg, params, tokens, mask = case

    def program(p):
        return jnp.mean(jnp.stack([
            lm.loss_fn(p, tokens[i:i + 1], cfg, loss_mask=mask[i:i + 1])
            for i in range(2)]))

    def ref(p):
        return reference.batch_loss(p, tokens, mask, cfg)

    lw, gw = jax.value_and_grad(program)(params)
    lg, gg = jax.value_and_grad(ref)(params)
    assert abs(float(lw) - float(lg)) < 1e-5
    for a, b in zip(jax.tree.leaves(gg), jax.tree.leaves(gw)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-6)


def test_token_logprobs_are_the_loss(case):
    cfg, params, tokens, _ = case
    lp = reference.token_logprobs(params, tokens[0], cfg)
    ones = jnp.ones((48,), jnp.float32)
    assert abs(float(-lp.mean())
               - float(reference.loss(params, tokens[0], ones, cfg))) < 1e-6
