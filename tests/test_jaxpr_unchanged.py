"""The programs of the one-kind models are the ones they were.

PR 31 put a second attention (MLA over a latent cache), a second stack (dense
layers ahead of expert layers), a second router form and an MTP loss term
through `stack_apply`, `layer_apply`, `moe_apply`, `kv_pool` and the engine.
None of it may reach a model that has none of it: the traced decode and
prefill programs of the engine and the plain loop (the training loss, forward
and backward) of `falcon-tiny` and `olmoe-tiny` are held, character for
character, to digests taken at the parent commit (280f7aa) with this file's
own `digests()`: `PYTHONPATH=. python tests/test_jaxpr_unchanged.py` prints
them. A later PR that changes one of these programs on purpose prints them
again and says so.

PR 32 changed the two `decode` programs on purpose (the sampler's top-k and
top-p filters moved under one `lax.cond`, `inference/sampling.py::
_filter_rows`) and printed them again at its parent's tree (4cf2126) plus
that change. The four `prefill` and `plain_loop` digests came out as they
were at 280f7aa: no prefill and no training program holds the sampler.

PR 34 changed the four serving programs (`decode` and `prefill` of both
models) on purpose and printed them again at its parent's tree (7011b80)
plus that change: a program that carries a KV cache rounds each float32
weight to bf16's grid in float32 before it narrows it, and makes each
attention projection a product of its own (`ops/quantized.py::wcast`,
`models/attention.py::_project`). The two `plain_loop` digests came out as
they were: the proof that no training program changed.

PR 49 changed the two `prefill` programs on purpose and printed them again
at its parent's tree (31bbf77) plus that change: a served prefill hands
`model_forward` each row's last real position as `logits_rows`, so final
norm and head run on those rows alone and no `[B, bucket, padded_vocab]`
array is made (`serving/engine.py::_prefill_fn`; the size test that used
to choose between the two forms is gone with its bound). The two `decode`
and the two `plain_loop` digests came out as they were at 31bbf77: the
proof that no decode and no training program changed.

PR 60 opened models/attention.py (an output gate, a zero-centred norm a
head, a rotary over part of a head, the flash kernel over folded rows of
several kv heads), models/moe.py (a gate on the shared expert), models/
rope.py, ops/kda_chunk.py and ops/flash_attention*.py for a fifth state kind
("linear_attention", models/gated_delta.py). All twelve digests above came
out as they were. The pattern models' `decode` and `chunk` programs (the
engine's decode step and `generation.prefill_chunk` over a slot's cache at a
traced offset: `PATTERN_AT_PARENT`), taken at its parent's tree (bc8a185)
with `pattern_digests()`, are held beside them from here on; `PYTHONPATH=.
python tests/test_jaxpr_unchanged.py` prints both tables.
"""
import hashlib
import re

import jax
import jax.numpy as jnp
import pytest

from megatron_tpu.config import MODEL_PRESETS, ServingConfig
from megatron_tpu.inference import Generator
from megatron_tpu.models import language_model as lm
from megatron_tpu.serving import ServingEngine

SLOTS, CAP, B_PRE, BUCKET = 3, 64, 2, 16

AT_PARENT = {
    "falcon-tiny": {"decode": "fef49c4b476ecdfa",
                    "prefill": "9a98779a35531d88",
                    "plain_loop": "5ec7bf8d21be92cf"},
    "olmoe-tiny": {"decode": "338d2003810367f2",
                   "prefill": "4e3decc3ccd67b58",
                   "plain_loop": "c88e314638cb0a8f"},
    # PR 58 opened models/mla.py (one-matrix queries, NoPE, a cache of
    # latent rows beside a state): the two MLA models' programs, taken at
    # its parent's tree (f15e986), came out the same on its own
    "joyai-llm-flash-tiny": {"decode": "bddfdbff9197e616",
                             "prefill": "8261f95d31eb9035",
                             "plain_loop": "073a800e6993f50c"},
    "xing4.0-29b-a4b-tiny": {"decode": "82beae2cd9e46c81",
                             "prefill": "21603a803e31715d",
                             "plain_loop": "3c572c42d9b52012"},
}


# the models whose layers follow `cfg.layer_types`: their served programs
PATTERN_AT_PARENT = {
    "kimi-linear-tiny": {"decode": "e57c32f4a3ae314c",
                         "chunk": "821f54af91e2004c"},
    "lfm2-8b-a1b-tiny": {"decode": "47c962e8eda7c907",
                         "chunk": "86176a780940a85b"},
    "jamba2-3b-tiny": {"decode": "db08124a4273ec2c",
                       "chunk": "a42439af7aa15326"},
    "nemotron-3-super-tiny": {"decode": "f99cf24fb657fce7",
                              "chunk": "33153838e4faff67"},
}


def _programs(model):
    import dataclasses
    # the presets' own dtypes (float32 weights, bf16 compute), as the
    # benchmark's cells run them
    cfg = dataclasses.replace(MODEL_PRESETS[model](), vocab_size=512)
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    gen = Generator(params, cfg, eos_id=0, pad_id=0)
    serving = ServingConfig(num_slots=SLOTS, max_len=CAP,
                            prefill_bucket=BUCKET,
                            prefill_max_batch=B_PRE).validate(cfg)
    eng = ServingEngine(gen, serving, start=False)
    try:
        state = (eng._p_dec, eng.pool.caches, eng._last_logits, eng._rngs)
        grid = (eng._d_lengths, eng._d_temps, eng._d_top_ks, eng._d_top_ps)
        yield "decode", eng._decode_fn, (
            *state, *grid, eng._d_reject, eng._d_masks, None, None)
        yield "prefill", eng._prefill_fn, (
            *state, jnp.zeros((B_PRE, BUCKET), jnp.int32),
            jnp.full((B_PRE,), 7, jnp.int32), jnp.arange(B_PRE),
            jnp.zeros((B_PRE, 2), jnp.uint32), None, None)
        tokens = jnp.zeros((2, 33), jnp.int32)
        rope = lm.make_rope(cfg)
        yield "plain_loop", jax.value_and_grad(
            lambda p, t: lm.loss_fn(p, t, cfg, rope=rope)), (params, tokens)
    finally:
        eng.close()


def digests(model):
    out = {}
    # the precision tests/conftest.py sets, so that the script and the test
    # print the same
    with jax.default_matmul_precision("highest"):
        for name, fn, args in _programs(model):
            text = re.sub(r"0x[0-9a-f]+", "0x",
                          str(jax.make_jaxpr(fn)(*args)))
            out[name] = hashlib.sha256(text.encode()).hexdigest()[:16]
    return out


def _pattern_programs(model):
    import dataclasses
    cfg = dataclasses.replace(MODEL_PRESETS[model](), vocab_size=512)
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    gen = Generator(params, cfg, eos_id=0, pad_id=0)
    serving = ServingConfig(num_slots=SLOTS, max_len=CAP,
                            prefill_bucket=BUCKET, prefill_chunk=BUCKET,
                            prefill_max_batch=1).validate(cfg)
    eng = ServingEngine(gen, serving, start=False)
    try:
        state = (eng._p_dec, eng.pool.caches, eng._last_logits, eng._rngs)
        grid = (eng._d_lengths, eng._d_temps, eng._d_top_ks, eng._d_top_ps)
        yield "decode", eng._decode_fn, (
            *state, *grid, eng._d_reject, eng._d_masks, None, None)
        yield "chunk", eng._chunk_fwd_fn, (
            eng._p_dec, eng._zero_sub(), jnp.zeros((1, BUCKET), jnp.int32),
            jnp.int32(6), jnp.int32(BUCKET + 7), None, None)
    finally:
        eng.close()


def pattern_digests(model):
    out = {}
    with jax.default_matmul_precision("highest"):
        for name, fn, args in _pattern_programs(model):
            text = re.sub(r"0x[0-9a-f]+", "0x",
                          str(jax.make_jaxpr(fn)(*args)))
            out[name] = hashlib.sha256(text.encode()).hexdigest()[:16]
    return out


@pytest.mark.parametrize("model", sorted(AT_PARENT))
def test_programs_are_the_parents(model):
    assert digests(model) == AT_PARENT[model]


@pytest.mark.parametrize("model", sorted(PATTERN_AT_PARENT))
def test_pattern_programs_are_the_parents(model):
    assert pattern_digests(model) == PATTERN_AT_PARENT[model]


if __name__ == "__main__":
    import json
    import sys
    models = sys.argv[1:] or sorted(PATTERN_AT_PARENT)
    print(json.dumps({m: digests(m) for m in sorted(AT_PARENT)}, indent=4))
    print(json.dumps({m: pattern_digests(m) for m in models}, indent=4))
