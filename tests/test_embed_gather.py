"""`ops/embed_gather.py`: the token gather of a served program that reads
the word embedding table where it lies (PERF.md section 6, PR 53).

Held here, on the CPU with the kernel interpreted: (a) the kernel's rows
are `emb[tokens].astype(bfloat16)` bit for bit; (b) which gathers the rule
sends through it, by what a call can see; (c) off the chip every traced
program keeps `emb[tokens]`, and an engine whose programs are made to take
the kernel produces the parent's logits to the bit.
tests/test_tpu_compile.py holds the rule's layout proxy to the chip's
compiler."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.config import ServingConfig, falcon_config
from megatron_tpu.inference import Generator
from megatron_tpu.models import language_model as lm
from megatron_tpu.ops import embed_gather as eg

# a hidden that is no multiple of 128 (and of 8 and 16, the tiles' rows),
# a vocabulary of four lane blocks
VOCAB, HIDDEN = 512, 176

IDS = {
    "first": [0],
    "last": [VOCAB - 1],
    "both_sides_of_a_block_edge": [127, 128, 255, 256, 383, 384],
    "repeated": [5, 5, 5, 300, 5, 300],
    "one_block": [129, 140, 200, 255, 130],
    "odd_count": list(range(3, 3 + 13 * 37, 37)),
    "random": np.random.default_rng(53).integers(0, VOCAB, 67).tolist(),
}


def _table(dtype):
    # values that round (float32) on their way to bf16, signs and scales mixed
    t = jax.random.normal(jax.random.PRNGKey(0), (VOCAB, HIDDEN),
                          jnp.float32)
    return (t * jnp.exp2(jax.random.randint(
        jax.random.PRNGKey(1), (VOCAB, 1), -20, 20))).astype(dtype)


def _bits(x):
    return np.asarray(x).view(np.uint16)


# ---------------------------------------------------------------------------
# (a) bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(IDS))
def test_rows_are_the_plain_gathers_bit_for_bit(case, dtype):
    table = _table(dtype)
    ids = jnp.asarray(IDS[case], jnp.int32)
    got = jax.jit(eg.lane_block_gather)(table, ids)
    want = table[ids].astype(jnp.bfloat16)
    assert got.dtype == jnp.bfloat16 and got.shape == (len(IDS[case]), HIDDEN)
    np.testing.assert_array_equal(_bits(got), _bits(want))


def test_an_id_out_of_range_reads_the_nearest_row():
    table = _table("float32")
    got = eg.lane_block_gather(table, jnp.asarray([-3, VOCAB + 9], jnp.int32))
    want = table[jnp.asarray([0, VOCAB - 1])].astype(jnp.bfloat16)
    np.testing.assert_array_equal(_bits(got), _bits(want))


# ---------------------------------------------------------------------------
# (b) the rule
# ---------------------------------------------------------------------------

FALCON_7B, FALCON_40B, OLMOE = (65024, 4544), (65024, 8192), (50304, 2048)

# (table, rows, cached, mesh, table dtype, compute dtype, backend) -> kernel
RULE = {
    "falcon_7b_decode_64": (FALCON_7B, 64, True, False, "float32",
                            "bfloat16", "tpu", True),
    "falcon_7b_prefill_256": (FALCON_7B, 256, True, False, "float32",
                              "bfloat16", "tpu", True),
    "falcon_7b_prefill_512": (FALCON_7B, 512, True, False, "float32",
                              "bfloat16", "tpu", True),
    "falcon_7b_prefill_768": (FALCON_7B, 768, True, False, "float32",
                              "bfloat16", "tpu", True),
    "falcon_7b_two_prompts_of_512": (FALCON_7B, 1024, True, False,
                                     "float32", "bfloat16", "tpu", False),
    "falcon_7b_prefill_1536": (FALCON_7B, 1536, True, False, "float32",
                               "bfloat16", "tpu", False),
    "falcon_7b_2048_rows_cached": (FALCON_7B, 2048, True, False, "float32",
                                   "bfloat16", "tpu", False),
    "falcon_7b_bf16_table_64": (FALCON_7B, 64, True, False, "bfloat16",
                                "bfloat16", "tpu", True),
    # half the bytes a block, two thirds of the copy's: the edge moves out
    "falcon_7b_bf16_table_1024": (FALCON_7B, 1024, True, False, "bfloat16",
                                  "bfloat16", "tpu", True),
    "falcon_7b_bf16_table_1280": (FALCON_7B, 1280, True, False, "bfloat16",
                                  "bfloat16", "tpu", False),
    "falcon_7b_training_64": (FALCON_7B, 64, False, False, "float32",
                              "bfloat16", "tpu", False),
    "falcon_7b_training_2048": (FALCON_7B, 2048, False, False, "float32",
                                "bfloat16", "tpu", False),
    "falcon_7b_sharded": (FALCON_7B, 64, True, True, "float32", "bfloat16",
                          "tpu", False),
    "falcon_7b_float32_rows": (FALCON_7B, 64, True, False, "float32",
                               "float32", "tpu", False),
    "falcon_7b_on_the_cpu": (FALCON_7B, 64, True, False, "float32",
                             "bfloat16", "cpu", False),
    "falcon_40b_decode": (FALCON_40B, 64, True, False, "float32", "bfloat16",
                          "tpu", False),
    "olmoe_hidden_2048_decode": (OLMOE, 24, True, False, "float32",
                                 "bfloat16", "tpu", False),
    "olmoe_hidden_2048_prefill": (OLMOE, 512, True, False, "float32",
                                  "bfloat16", "tpu", False),
    "vocabulary_of_a_part_block": ((65000, 4544), 64, True, False,
                                   "float32", "bfloat16", "tpu", False),
}


@pytest.mark.parametrize("case", sorted(RULE))
def test_which_gathers_read_lane_blocks(case):
    shape, rows, cached, mesh, table_dtype, dtype, backend, want = RULE[case]
    assert eg.reads_lane_blocks(
        shape, jnp.dtype(table_dtype), jnp.dtype(dtype), rows=rows,
        cached=cached, mesh=mesh, backend=backend) is want


def test_the_edge_lies_between_the_cells_768_and_1024_rows():
    """(d) is a ratio of two byte counts, not a row count somebody set: at
    Falcon-7B's widths it falls between the largest prefill program the
    chip timed faster than the copy and the smallest it timed slower."""
    rows = [r for r in range(64, 4096, 64) if eg.reads_lane_blocks(
        FALCON_7B, jnp.float32, jnp.bfloat16, rows=r, cached=True,
        mesh=False, backend="tpu")]
    assert rows == list(range(64, rows[-1] + 64, 64))
    assert 768 <= rows[-1] < 1024


# ---------------------------------------------------------------------------
# (c) the traced programs
# ---------------------------------------------------------------------------

SLOTS, CAP, B_PRE, BUCKET = 3, 64, 2, 16
# a vocabulary whose copy costs more bytes than a prefill's 32 blocks, (d)
ENGINE_VOCAB = 4096


def _engine():
    from megatron_tpu.serving import ServingEngine
    # the preset's dtypes (float32 weights, bf16 compute) at a hidden of
    # one and a half lane tiles
    cfg = falcon_config("tiny", hidden_size=192, num_attention_heads=3,
                        vocab_size=ENGINE_VOCAB)
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    gen = Generator(params, cfg, eos_id=0, pad_id=0)
    serving = ServingConfig(num_slots=SLOTS, max_len=CAP,
                            prefill_bucket=BUCKET,
                            prefill_max_batch=B_PRE).validate(cfg)
    return ServingEngine(gen, serving, start=False)


def _programs(eng):
    """(prefill, its arguments, decode, its arguments less the prefill's
    results)."""
    state = (eng._p_dec, eng.pool.caches, eng._last_logits, eng._rngs)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B_PRE, BUCKET), 1,
                                ENGINE_VOCAB)
    prefill = (*state, tokens, jnp.full((B_PRE,), 7, jnp.int32),
               jnp.arange(B_PRE), jnp.zeros((B_PRE, 2), jnp.uint32), None,
               None)
    decode = [*state, jnp.asarray([7, 7, 0], jnp.int32), eng._d_temps,
              eng._d_top_ks, eng._d_top_ps, eng._d_reject, eng._d_masks,
              None, None]
    return prefill, decode


def _as_on_a_tpu(monkeypatch):
    """The rule answers as a TPU's trace would have it; the kernel itself
    stays interpreted."""
    monkeypatch.setattr(eg, "reads_lane_blocks", functools.partial(
        eg.reads_lane_blocks, backend="tpu"))


def _logits(monkeypatch, kernel):
    if kernel:
        _as_on_a_tpu(monkeypatch)
    eng = _engine()
    try:
        prefill, decode = _programs(eng)
        for fn, args in ((eng._prefill_fn, prefill),
                         (eng._decode_fn, decode)):
            text = str(jax.make_jaxpr(fn)(*args))
            assert ("pallas_call" in text) == kernel
        pool, last, rngs = jax.jit(eng._prefill_fn)(*prefill)
        decode[1:4] = pool, last, rngs
        out = jax.jit(eng._decode_fn)(*decode)
        return np.asarray(last), np.asarray(out[1])
    finally:
        eng.close()


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_engine_logits_are_the_plain_gathers_bit_for_bit(monkeypatch,
                                                         program):
    i = ["prefill", "decode"].index(program)
    with monkeypatch.context() as m:
        plain = _logits(m, kernel=False)[i]
    kernel = _logits(monkeypatch, kernel=True)[i]
    assert np.isfinite(kernel).all() and np.abs(kernel).max() > 0
    np.testing.assert_array_equal(kernel.view(np.uint32),
                                  plain.view(np.uint32))


def test_a_training_step_keeps_the_plain_gather(monkeypatch):
    """No cache: the loss and its gradient trace `emb[tokens]` even where
    everything else about the call would take the kernel."""
    _as_on_a_tpu(monkeypatch)
    cfg = falcon_config("tiny", hidden_size=192, num_attention_heads=3,
                        vocab_size=ENGINE_VOCAB)
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    rope = lm.make_rope(cfg)
    text = str(jax.make_jaxpr(jax.value_and_grad(
        lambda p, t: lm.loss_fn(p, t, cfg, rope=rope)))(
            params, jnp.zeros((2, 33), jnp.int32)))
    assert "pallas_call" not in text


def test_under_a_mesh_the_gather_stays_plain(monkeypatch):
    from jax.sharding import Mesh
    from megatron_tpu.parallel import sharding as shd
    _as_on_a_tpu(monkeypatch)
    table = jnp.zeros((ENGINE_VOCAB, HIDDEN), jnp.float32)
    tokens = jnp.zeros((2, 4), jnp.int32)

    def traced():
        return str(jax.make_jaxpr(lambda t, i: eg.embed_tokens(
            t, i, jnp.bfloat16, cached=True))(table, tokens))
    assert "pallas_call" in traced()
    devices = np.asarray(jax.devices()[:2]).reshape(1, 1, 2)
    mesh = Mesh(devices, ("dp", "pp", "tp"))
    with shd.activation_shardings(mesh, shd.make_logical_rules()):
        assert "pallas_call" not in traced()
    one = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1, 1),
               ("dp", "pp", "tp"))
    with shd.activation_shardings(one, shd.make_logical_rules()):
        assert "pallas_call" in traced()
