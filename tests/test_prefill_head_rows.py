"""A served prefill's head runs on the one row it keeps (PR 49).

Every served prefill program, whole (`ServingEngine._prefill_fn`) or chunked
(`_chunk_fwd_fn` -> `generation.prefill_chunk`), hands `model_forward` the
last real position of each row as `logits_rows`, and final norm and head run
on those rows alone: no size test chooses, no model needs the other rows.
Two things are held here over the tiny preset of every served kind: (a) the
one-row logits are that row of the whole bucket's, and (b) no traced prefill
or chunk program holds an array `[B, bucket, padded_vocab]`.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.config import MODEL_PRESETS, ServingConfig
from megatron_tpu.inference import Generator
from megatron_tpu.models import language_model as lm
from megatron_tpu.serving import ServingEngine
from tests.test_kv_inplace import _eqns, _shape

SERVED = ["falcon-tiny", "olmoe-tiny", "joyai-llm-flash-tiny",
          "command-a-plus-tiny", "lfm2-8b-a1b-tiny", "xing4.0-29b-a4b-tiny",
          "jamba2-3b-tiny"]
# a vocabulary that is no other width of any tiny preset, so that a shape
# [B, bucket, VOCAB] can only be a bucket's logits
VOCAB, B_PRE, BUCKET = 1408, 2, 16


def _model(name, **overrides):
    cfg = dataclasses.replace(MODEL_PRESETS[name](), vocab_size=VOCAB,
                              **overrides)
    assert cfg.padded_vocab_size == VOCAB
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    params.pop("mtp", None)      # a server does not load the module
    return cfg, params


@pytest.mark.parametrize("name", SERVED)
def test_one_row_of_the_head_is_that_row_of_the_whole(name):
    """`model_forward(..., logits_rows=r)` against row `r` of the whole
    logits, a padded bucket of 32 with `r` short of its end (another `r` a
    sequence), to the tighter tolerance of the two `head_on_last_rows` cases
    this replaces (1e-4, float32 on the CPU)."""
    cfg, params = _model(name, compute_dtype="float32")
    rope = lm.make_rope(cfg)
    tokens = jnp.asarray(np.random.default_rng(3).integers(
        1, VOCAB, size=(B_PRE, 32)), jnp.int32)
    rows = jnp.asarray([20, 7], jnp.int32)

    @jax.jit
    def both(params, tokens, rows):
        whole, _ = lm.model_forward(params, tokens, cfg, rope=rope)
        one, _ = lm.model_forward(params, tokens, cfg, rope=rope,
                                  logits_rows=rows)
        return whole, one

    whole, one = both(params, tokens, rows)
    assert one.shape == (B_PRE, 1, VOCAB) and one.dtype == jnp.float32
    want = np.stack([np.asarray(whole)[i, int(r)]
                     for i, r in enumerate(rows)])
    assert np.abs(want).max() > 1e-2
    np.testing.assert_allclose(np.asarray(one)[:, 0], want, rtol=0, atol=1e-4)


@pytest.mark.parametrize("name", SERVED)
def test_no_served_prefill_makes_a_buckets_logits(name):
    """The traced `_prefill_fn` (2 rows of a 16-row bucket) and
    `_chunk_fwd_fn` (a 16-row chunk) of an engine: no variable of either is
    `[B, bucket, padded_vocab]`, and each does hold the `[B, 1, padded_vocab]`
    rows the head makes in their place."""
    cfg, params = _model(name)
    gen = Generator(params, cfg, eos_id=-1, pad_id=0)
    serving = ServingConfig(num_slots=3, max_len=64, prefill_bucket=BUCKET,
                            prefill_max_batch=B_PRE,
                            prefill_chunk=BUCKET).validate(cfg)
    eng = ServingEngine(gen, serving, start=False)
    try:
        programs = {
            "prefill": (B_PRE, eng._prefill_fn, (
                eng._p_dec, eng.pool.caches, eng._last_logits, eng._rngs,
                jnp.zeros((B_PRE, BUCKET), jnp.int32),
                jnp.full((B_PRE,), 7, jnp.int32), jnp.arange(B_PRE),
                jnp.zeros((B_PRE, 2), jnp.uint32), None, None)),
            "chunk": (1, eng._chunk_fwd_fn, (
                eng._p_dec, eng.pool.make_prefill_caches(1),
                jnp.zeros((1, BUCKET), jnp.int32), jnp.int32(6),
                jnp.int32(7), None, None))}
        for which, (b, fn, args) in programs.items():
            shapes = set()
            for eqn in _eqns(jax.make_jaxpr(fn)(*args).jaxpr):
                shapes.update(_shape(v) for v in eqn.outvars)
            whole = {s for s in shapes if s[-2:] == (BUCKET, VOCAB)}
            assert not whole, (which, whole)
            assert (b, 1, VOCAB) in shapes, which
    finally:
        eng.close()
