"""`test_reference.py`'s case for the Qwen3-Next reference
(`reference/qwen3_next.py`: the delta rule with one decay a head token by
token, gated attention with a zero-centred norm a head and a quarter of a
head rotated, a share of softmax-routed experts beside a gated shared one),
in a file of its own: the accepted file is not this PR's to edit. Against
the program's own forward at the tiny preset, float32: logits, and its token
log-probabilities are its loss."""
import dataclasses
import inspect

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import qwen3_next
from megatron_tpu.config import MODEL_PRESETS
from megatron_tpu.models import language_model as lm


def test_qwen3_next_reference_logits_loss_and_logprobs():
    cfg = dataclasses.replace(MODEL_PRESETS["qwen3-next-tiny"](),
                              compute_dtype="float32", init_method_std=0.11,
                              num_experts=4)        # 4 of 8 held
    params = lm.model_init(jax.random.PRNGKey(7), cfg)
    # a trained model's zero-centred scales are not 0
    params["final_norm"]["scale"] = 0.1 * jax.random.normal(
        jax.random.PRNGKey(9), params["final_norm"]["scale"].shape)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (29,), 1, 512)
    want, _ = lm.model_forward(params, tokens[None, :-1], cfg,
                               rope=lm.make_rope(cfg))
    got = qwen3_next.logits(params, tokens[:-1], cfg)
    np.testing.assert_allclose(got, want[0, :, :cfg.vocab_size],
                               rtol=2e-4, atol=2e-4)
    lp = qwen3_next.token_logprobs(params, tokens, cfg)
    ones = jnp.ones((1, 28), jnp.float32)
    assert abs(float(-lp.mean()) - float(
        qwen3_next.loss(params, tokens[None], ones, cfg))) < 1e-6


def test_qwen3_next_reference_imports_nothing_of_the_program():
    source = inspect.getsource(qwen3_next)
    code = source.split('"""', 2)[2]
    assert "megatron_tpu" not in code and "pallas" not in code
