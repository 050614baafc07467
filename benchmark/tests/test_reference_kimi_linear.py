"""`test_reference.py`'s case for the Kimi Linear reference
(`reference/kimi_linear.py`: the delta rule token by token, NoPE MLA with
full heads, a share of the experts), in a file of its own: the accepted
file is not this PR's to edit. Against the program's own forward at the tiny
preset, float32: logits, and its token log-probabilities are its loss."""
import dataclasses
import inspect

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import kimi_linear
from megatron_tpu.config import MODEL_PRESETS
from megatron_tpu.models import language_model as lm


def test_kimi_linear_reference_logits_loss_and_logprobs():
    cfg = dataclasses.replace(MODEL_PRESETS["kimi-linear-tiny"](),
                              compute_dtype="float32", init_method_std=0.11,
                              num_experts=4)        # 4 of 8 held
    params = lm.model_init(jax.random.PRNGKey(7), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(8), (29,), 1, 512)
    want, _ = lm.model_forward(params, tokens[None, :-1], cfg)
    got = kimi_linear.logits(params, tokens[:-1], cfg)
    np.testing.assert_allclose(got, want[0, :, :cfg.vocab_size],
                               rtol=2e-4, atol=2e-4)
    lp = kimi_linear.token_logprobs(params, tokens, cfg)
    ones = jnp.ones((1, 28), jnp.float32)
    assert abs(float(-lp.mean()) - float(
        kimi_linear.loss(params, tokens[None], ones, cfg))) < 1e-6


def test_kimi_linear_reference_imports_nothing_of_the_program():
    source = inspect.getsource(kimi_linear)
    code = source.split('"""', 2)[2]
    assert "megatron_tpu" not in code and "pallas" not in code
