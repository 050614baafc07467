"""`ops/quantized.py::wcast` in a program that reads each weight once.

A program that carries a KV cache (decode, prefill, chunk, verify) rounds a
float32 weight to bfloat16's grid in float32 (`reduce_precision`) and then
narrows it, on the layer's slice, so that the chip's compiler fuses slice,
rounding and narrowing into the product's operand and makes no bf16 copy of
the stack (PERF.md section 6, PR 34). Three things are held here, on the
CPU: (a) the pair is `w.astype(bfloat16)` bit for bit; (b) the traced
serving programs hold it ahead of every float32 weight's narrowing, the
training loop and every bf16-held program hold none; (c) the engine's
logits are the parent's, to the bit."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.config import MODEL_PRESETS, ServingConfig
from megatron_tpu.inference import Generator
from megatron_tpu.models import attention, language_model as lm, mlp
from megatron_tpu.ops.quantized import W8, wcast

SLOTS, CAP, B_PRE, BUCKET = 3, 64, 2, 16


def _f32(bits):
    return np.asarray(bits, np.uint32).view(np.float32)


# float32 bit patterns by what they exercise; bf16 keeps the upper 16 bits
# and rounds on the lower 16
CASES = {
    "random_bits": np.random.default_rng(34).integers(
        0, 2 ** 32, size=1 << 16, dtype=np.uint64).astype(np.uint32),
    "ties_to_even": [0x3F808000, 0x3F818000, 0x3F828000, 0xBF808000,
                     0xBF818000, 0x3F807FFF, 0x3F808001, 0x3F818001],
    "subnormals": [0x00000001, 0x00007FFF, 0x00008000, 0x00008001,
                   0x00018000, 0x007FFFFF, 0x80008000, 0x807F8000],
    "zeros": [0x00000000, 0x80000000],
    "infinities": [0x7F800000, 0xFF800000],
    "nans": [0x7FC00000, 0xFFC00000, 0x7F800001, 0x7FBFFFFF, 0x7FFFFFFF],
    "largest_finite": [0x7F7FFFFF, 0xFF7FFFFF, 0x7F7F7FFF, 0x7F7F8000],
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_rounded_then_narrowed_is_astype_bit_for_bit(case):
    w = jnp.asarray(_f32(CASES[case]))
    got = jax.jit(lambda a: wcast(a, jnp.bfloat16, read_once=True))(w)
    want = w.astype(jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got).view(np.uint16),
                                  np.asarray(want).view(np.uint16))


def test_float16_keeps_the_bare_cast():
    """`reduce_precision` to float16's 5 exponent bits flushes what
    `astype` rounds to a float16 subnormal: the rule covers a narrowing
    that keeps the exponent's width, and float16 is not one."""
    w = jnp.asarray(_f32([0x33800000, 0x38000000, 0x3F801000, 0x00000001]))
    text = str(jax.make_jaxpr(
        lambda a: wcast(a, jnp.float16, read_once=True))(w))
    assert "reduce_precision" not in text
    np.testing.assert_array_equal(
        np.asarray(wcast(w, jnp.float16, read_once=True)).view(np.uint16),
        np.asarray(w.astype(jnp.float16)).view(np.uint16))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_nothing_is_emitted_where_nothing_narrows(dtype):
    w = jnp.ones((4, 4), dtype)
    text = str(jax.make_jaxpr(lambda a: wcast(a, w.dtype, read_once=True))(w))
    assert "reduce_precision" not in text and "convert" not in text


def test_int8_weights_pass_through():
    w8 = W8(q=jnp.ones((4, 4), jnp.int8), scale=jnp.ones((4,)))
    assert wcast(w8, jnp.bfloat16, read_once=True) is w8


# ---------------------------------------------------------------------------
# (b) which traced programs hold the rounding
# ---------------------------------------------------------------------------

# float32 matrices a layer sends through `wcast`: Falcon's q, kv and output
# projections and both MLP products; OLMoE's three projections (its expert
# banks go to the grouped product, which rounds in VMEM)
CAST_IN_LAYER = {"falcon-tiny": 5, "olmoe-tiny": 3}


def _engine(cfg, params):
    from megatron_tpu.serving import ServingEngine
    gen = Generator(params, cfg, eos_id=0, pad_id=0)
    serving = ServingConfig(num_slots=SLOTS, max_len=CAP,
                            prefill_bucket=BUCKET,
                            prefill_max_batch=B_PRE).validate(cfg)
    return ServingEngine(gen, serving, start=False)


def _decode_args(eng):
    return (eng._p_dec, eng.pool.caches, eng._last_logits, eng._rngs,
            eng._d_lengths, eng._d_temps, eng._d_top_ks, eng._d_top_ps,
            eng._d_reject, eng._d_masks, None, None)


def _prefill_args(eng, tokens=None):
    tokens = (jnp.zeros((B_PRE, BUCKET), jnp.int32) if tokens is None
              else tokens)
    return (eng._p_dec, eng.pool.caches, eng._last_logits, eng._rngs,
            tokens, jnp.full((B_PRE,), 7, jnp.int32), jnp.arange(B_PRE),
            jnp.zeros((B_PRE, 2), jnp.uint32), None, None)


def _traced(model, program, weights):
    cfg = dataclasses.replace(MODEL_PRESETS[model](), vocab_size=512)
    params = lm.model_init(jax.random.PRNGKey(0), cfg,
                           dtype=jnp.dtype(weights))
    if program == "plain_loop":
        rope = lm.make_rope(cfg)
        return jax.make_jaxpr(jax.value_and_grad(
            lambda p, t: lm.loss_fn(p, t, cfg, rope=rope)))(
                params, jnp.zeros((2, 33), jnp.int32))
    eng = _engine(cfg, params)
    try:
        if program == "decode":
            return jax.make_jaxpr(eng._decode_fn)(*_decode_args(eng))
        return jax.make_jaxpr(eng._prefill_fn)(*_prefill_args(eng))
    finally:
        eng.close()


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold
    (the scanned layer, a `cond`'s branches, a nested `jit`)."""
    for eqn in jaxpr.eqns:
        yield jaxpr, eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


@pytest.mark.parametrize("weights", ["float32", "bfloat16"])
@pytest.mark.parametrize("program", ["decode", "prefill", "plain_loop"])
@pytest.mark.parametrize("model", sorted(CAST_IN_LAYER))
def test_which_programs_round_in_place(model, program, weights):
    found = [(scope, eqn) for scope, eqn
             in _equations(_traced(model, program, weights).jaxpr)
             if eqn.primitive.name == "reduce_precision"]
    if program == "plain_loop" or weights == "bfloat16":
        assert not found
        return
    # the scanned layer is traced once: one rounding a float32 matrix, and
    # what it rounds is narrowed to bf16 and nothing else
    assert len(found) == CAST_IN_LAYER[model]
    for scope, eqn in found:
        assert eqn.params["exponent_bits"] == 8
        assert eqn.params["mantissa_bits"] == 7
        assert eqn.invars[0].aval.dtype == jnp.float32
        readers = [e for e in scope.eqns if eqn.outvars[0] in e.invars]
        assert [e.primitive.name for e in readers] == ["convert_element_type"]
        assert readers[0].params["new_dtype"] == jnp.bfloat16


# ---------------------------------------------------------------------------
# (c) the engine's logits are the parent's
# ---------------------------------------------------------------------------

def _logits(monkeypatch, parents_cast):
    if parents_cast:
        bare = lambda w, dtype, *, read_once=False: wcast(w, dtype)
        monkeypatch.setattr(attention, "wcast", bare)
        monkeypatch.setattr(mlp, "wcast", bare)
    cfg = dataclasses.replace(MODEL_PRESETS["falcon-tiny"](), vocab_size=512)
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    eng = _engine(cfg, params)
    try:
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B_PRE, BUCKET),
                                    1, 512)
        prefill = jax.jit(eng._prefill_fn)
        text = str(jax.make_jaxpr(eng._prefill_fn)(
            *_prefill_args(eng, tokens)))
        assert ("reduce_precision" in text) != parents_cast
        pool, last, rngs = prefill(*_prefill_args(eng, tokens))
        args = list(_decode_args(eng))
        args[1:4] = pool, last, rngs
        args[4] = jnp.asarray([7, 7, 0], jnp.int32)
        out = jax.jit(eng._decode_fn)(*args)
        return np.asarray(last), np.asarray(out[1])
    finally:
        eng.close()


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_engine_logits_are_the_parents_bit_for_bit(monkeypatch, program):
    i = ["prefill", "decode"].index(program)
    with monkeypatch.context() as m:
        parent = _logits(m, parents_cast=True)[i]
    change = _logits(monkeypatch, parents_cast=False)[i]
    assert np.isfinite(change).all() and np.abs(change).max() > 0
    np.testing.assert_array_equal(change.view(np.uint32),
                                  parent.view(np.uint32))
