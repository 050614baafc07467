"""The layer loop appends to the KV cache in place.

`attention_apply` takes the cache STACKED over layers and the layer's
index, writes the layer's new tokens at (layer, row, position) of that
buffer and reads its layer of it for the products; `stack_apply` carries
the stack through the loop. Two kinds of test, both on the CPU:

- STRUCTURE: the traced decode / verify / prefill programs of the engine
  and `Generator`'s decode never cut a layer out of the stack to update it
  and write it back. Read off the jaxpr, nothing is compiled.
- VALUES: every write branch against a plain NumPy model of "layer i, row
  r, positions offset .. offset+s-1"; the other layers' bytes unchanged.

The token-exact suites (test_serving*, test_pp_serving, test_lora_serving,
test_olmoe) are the oracle for the path as a whole.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.config import ModelConfig, ServingConfig
from megatron_tpu.inference import Generator, SamplingParams
from megatron_tpu.inference import generation
from megatron_tpu.models import language_model as lm
from megatron_tpu.models.attention import (KVCache, attention_apply,
                                           attention_init)
from megatron_tpu.ops.quantized import quantize_rows
from megatron_tpu.serving import ServingEngine

# distinct sizes, so that a shape names one thing
L, SLOTS, CAP, HD, B_PRE, SPEC_K = 3, 5, 48, 16, 2, 2


def tiny_cfg(n_kv, **overrides):
    base = dict(num_layers=L, hidden_size=64, num_attention_heads=4,
                num_kv_heads=n_kv, vocab_size=96, seq_length=CAP,
                make_vocab_size_divisible_by=32, compute_dtype="float32")
    base.update(overrides)
    return ModelConfig(**base).derived()


# ---------------------------------------------------------------------------
# structure
# ---------------------------------------------------------------------------

# primitives that only pass a value into or out of an inner jaxpr
CONTROL = {"scan", "while", "cond", "pjit", "jit", "closed_call",
           "core_call", "remat", "checkpoint", "custom_jvp_call",
           "custom_vjp_call"}
# what may produce a whole stacked cache: an update in place, or its
# allocation; and what may consume one: an update in place (as the operand
# updated), or the read of a part of it
WRITES = {"scatter", "dynamic_update_slice"}
PRODUCERS = WRITES | {"broadcast_in_dim"}
READS = {"dynamic_slice"}


def _inner_jaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (tuple, list)) else (v,)):
            j = getattr(j, "jaxpr", j)
            if hasattr(j, "eqns"):
                yield j


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in _inner_jaxprs(eqn):
            yield from _eqns(inner)


def _shape(v):
    return tuple(getattr(v.aval, "shape", ()))


def check_in_place(jaxpr, stacked):
    """Assert the in-place discipline on `jaxpr` for the stacked cache
    shapes `stacked`; returns counts, so that a caller can see the check
    met what it looks for."""
    stacked = set(stacked)
    layer = {s[1:] for s in stacked} | {(1,) + s[1:] for s in stacked}
    seen = {"carried": 0, "writes": 0, "layer_reads": 0}
    for eqn in _eqns(jaxpr):
        name = eqn.primitive.name
        ins = [_shape(v) for v in eqn.invars]
        outs = [_shape(v) for v in eqn.outvars]
        if name == "dynamic_update_slice":
            assert ins[1] not in layer, (
                f"a whole layer {ins[1]} is written back into {ins[0]}")
        if name.startswith("scatter"):
            assert ins[0] not in layer, (
                f"a scatter into a copy of one layer {ins[0]}")
        if name in CONTROL:
            if name == "scan":
                seen["carried"] += any(s in stacked for s in outs)
            continue
        if any(s in stacked for s in outs):
            assert name in PRODUCERS, (
                f"{name} makes a whole stacked cache {outs}")
        for pos, s in enumerate(ins):
            if s not in stacked:
                continue
            assert (name in WRITES and pos == 0) or name in READS, (
                f"{name} reads a whole stacked cache (operand {pos})")
            seen["writes"] += name in WRITES
            seen["layer_reads"] += name in READS and outs[0] in layer
    return seen


def _stacked_shapes(n_kv, quant, batches):
    last = (HD, 1) if quant else (HD,)
    return {(L, b, CAP, n_kv, d) for b in batches for d in last}


@functools.lru_cache(maxsize=None)
def _engine(kv_dtype, n_kv):
    cfg = tiny_cfg(n_kv)
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    gen = Generator(params, cfg, eos_id=0, pad_id=0)
    serving = ServingConfig(num_slots=SLOTS, max_len=CAP, kv_dtype=kv_dtype,
                            speculative_k=SPEC_K, prefill_bucket=16,
                            prefill_max_batch=B_PRE).validate(cfg)
    return gen, ServingEngine(gen, serving, start=False)


def _program(which, kv_dtype, n_kv):
    """(function, arguments) of one cached program, as the engine or the
    generator would call it."""
    gen, eng = _engine(kv_dtype, n_kv)
    state = (eng._p_dec, eng.pool.caches, eng._last_logits, eng._rngs)
    grid = (eng._d_lengths, eng._d_temps, eng._d_top_ks, eng._d_top_ps)
    if which == "decode":
        return eng._decode_fn, (*state, *grid, eng._d_reject, eng._d_masks,
                                None, None)
    if which == "verify":
        drafts = jnp.zeros((SLOTS, SPEC_K), jnp.int32)
        return eng._verify_fn, (*state, *grid, drafts, eng._d_reject,
                                eng._d_masks, eng._d_free_dmask,
                                eng._d_no_guess, None, None)
    if which == "prefill":
        return eng._prefill_fn, (
            *state, jnp.zeros((B_PRE, 16), jnp.int32),
            jnp.full((B_PRE,), 7, jnp.int32), jnp.arange(B_PRE),
            jnp.zeros((B_PRE, 2), jnp.uint32), None, None)
    assert which == "generate"
    fn = functools.partial(
        generation._decode_fn, cfg=gen.cfg, max_len=CAP, min_prompt=4,
        sp=SamplingParams(temperature=0.0), eos_id=0, pad_id=0,
        rope=gen.rope, kv_dtype=jnp.dtype(kv_dtype))
    return fn, (gen.params, jnp.zeros((SLOTS, CAP), jnp.int32),
                jnp.full((SLOTS,), 4, jnp.int32), jax.random.PRNGKey(0))


@pytest.mark.parametrize("n_kv", [1, 4], ids=["mqa", "mha"])
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("which",
                         ["decode", "verify", "prefill", "generate"])
def test_no_layer_is_copied_out_or_written_back(which, kv_dtype, n_kv):
    fn, args = _program(which, kv_dtype, n_kv)
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    seen = check_in_place(
        jaxpr, _stacked_shapes(n_kv, kv_dtype == "int8", (SLOTS, B_PRE)))
    n_leaves = 4 if kv_dtype == "int8" else 2   # k, v (and their scales)
    assert seen["carried"] >= 1, seen             # the layer loop's carry
    assert seen["writes"] >= n_leaves, seen       # each leaf written there
    assert seen["layer_reads"] >= n_leaves, seen  # and its layer read


def test_the_check_refuses_a_layer_sliced_and_written_back():
    """The form this replaced: cut layer i out, update the copy, put the
    whole copy back. The checker has to see it."""
    def old(stack, new, i):
        one = jax.lax.dynamic_index_in_dim(stack, i, 0, keepdims=False)
        one = one.at[jnp.arange(SLOTS), 3].set(new)
        return jax.lax.dynamic_update_index_in_dim(stack, one, i, 0)

    shape = (L, SLOTS, CAP, 1, HD)
    jaxpr = jax.make_jaxpr(old)(jnp.zeros(shape), jnp.ones((SLOTS, 1, HD)),
                                jnp.int32(1)).jaxpr
    with pytest.raises(AssertionError, match="layer"):
        check_in_place(jaxpr, {shape})


# ---------------------------------------------------------------------------
# values
# ---------------------------------------------------------------------------

def _attn_cfg(**overrides):
    # no rotary, bias or QK-norm: the k and v written are x @ wkv, which
    # NumPy can say
    return tiny_cfg(2, use_rotary_emb=False, **overrides)


def _random_cache(rng, batch, cap, n_kv, dtype, offset):
    """A stacked cache full of noise, so that an untouched byte shows."""
    shape = (L, batch, cap, n_kv, HD)
    quant = jnp.dtype(dtype) == jnp.int8
    if quant:
        k, v = (rng.randint(-127, 128, shape).astype(np.int8)
                for _ in range(2))
    else:
        k, v = (rng.randn(*shape).astype(np.float32) for _ in range(2))
    offset = np.broadcast_to(np.asarray(offset, np.int32),
                             (L,) + np.shape(offset))
    k_scale, v_scale = (
        jnp.asarray(rng.rand(*shape[:4], 1).astype(np.float32) + 0.5)
        if quant else None for _ in range(2))
    return KVCache(jnp.asarray(k, dtype), jnp.asarray(v, dtype),
                   jnp.asarray(offset), k_scale, v_scale)


def _numpy_model(cache, layer, k_new, v_new, positions):
    """`positions` [b, s]: where row r's token t lands in layer `layer`
    (-1: nowhere). Later tokens win, as a ring's overwrite does."""
    want = {f: None if getattr(cache, f) is None
            else np.array(getattr(cache, f)) for f in cache._fields}
    quant = cache.k.dtype == jnp.int8
    if quant:
        (k_new, ks), (v_new, vs) = quantize_rows(k_new), quantize_rows(v_new)
    for r in range(positions.shape[0]):
        for t in range(positions.shape[1]):
            p = positions[r, t]
            if p < 0:
                continue
            want["k"][layer, r, p] = np.asarray(k_new[r, t])
            want["v"][layer, r, p] = np.asarray(v_new[r, t])
            if quant:
                want["k_scale"][layer, r, p] = np.asarray(ks[r, t])
                want["v_scale"][layer, r, p] = np.asarray(vs[r, t])
    want["offset"][layer] += positions.shape[1]
    return want


W = 8  # the rolling cases' window, and their cache's capacity

# name: (cache dtype, capacity, offset (scalar, or one per row), tokens s,
#        sliding window)
WRITE_CASES = {
    "per_slot_step": ("float32", 24, [3, 0, 11, 23], 1, None),
    "per_slot_verify_window_parked_at_cap": (
        "float32", 24, [3, 23, 11, 22], 3, None),
    "scalar_prefill": ("float32", 24, 0, 8, None),
    "scalar_chunk": ("float32", 24, 5, 4, None),
    "rolling_scalar_step_wraps": ("float32", W, 13, 1, W),
    "rolling_scalar_prefill": ("float32", W, 0, 6, W),
    "rolling_per_slot_step_wraps": ("float32", W, [13, 2, 8, 7], 1, W),
    "int8_per_slot_step": ("int8", 24, [3, 0, 11, 23], 1, None),
    "int8_per_slot_verify_window": ("int8", 24, [3, 23, 11, 22], 3, None),
    "int8_scalar_chunk": ("int8", 24, 5, 4, None),
    "int8_rolling_scalar_step_wraps": ("int8", W, 13, 1, W),
    "bf16_per_slot_step": ("bfloat16", 24, [3, 0, 11, 23], 1, None),
}


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("case", sorted(WRITE_CASES))
def test_write_lands_at_layer_row_position(case, layer):
    dtype, cap, offset, s, window = WRITE_CASES[case]
    cfg = _attn_cfg(sliding_window=window)
    rng = np.random.RandomState(len(case) + layer)
    batch = 4
    params = attention_init(jax.random.PRNGKey(1), cfg)
    x = jnp.asarray(rng.randn(batch, s, cfg.hidden_size), jnp.float32)
    cache = _random_cache(rng, batch, cap, cfg.num_kv_heads, dtype, offset)

    apply = jax.jit(lambda c, i: attention_apply(
        params, x, cfg, kv_cache=c, cache_layer=i))
    out, got = apply(cache, jnp.int32(layer))

    kv = (np.asarray(x) @ np.asarray(params["wkv"])).reshape(
        batch, s, 2, cfg.num_kv_heads, HD)
    positions = (np.broadcast_to(np.asarray(offset), (batch,))[:, None]
                 + np.arange(s)[None, :])
    if window is not None:
        positions = positions % cap
    positions = np.where(positions < cap, positions, -1)  # past the end: gone
    want = _numpy_model(cache, layer, kv[:, :, 0], kv[:, :, 1], positions)

    assert np.isfinite(np.asarray(out)).all()
    for field, w in want.items():
        g = getattr(got, field)
        if w is None:
            assert g is None, field
            continue
        g = np.asarray(g)
        assert g.dtype == w.dtype and g.shape == w.shape, field
        others = [j for j in range(L) if j != layer]
        # the other layers: bit for bit what they were
        np.testing.assert_array_equal(g[others], w[others], err_msg=field)
        if field in ("k", "v") and dtype != "int8":
            # written values are the products cast to the cache's dtype;
            # NumPy's float32 product differs in the last bits
            tol = 2e-2 if dtype == "bfloat16" else 1e-5
            np.testing.assert_allclose(
                g[layer].astype(np.float32), np.asarray(
                    jnp.asarray(w[layer]).astype(cache.k.dtype)
                ).astype(np.float32), atol=tol, err_msg=field)
        elif field in ("k", "v"):
            # int8: a rounding of the product may fall either side
            assert np.abs(g[layer].astype(np.int32)
                          - w[layer].astype(np.int32)).max() <= 1, field
        elif field == "offset":
            np.testing.assert_array_equal(g[layer], w[layer], err_msg=field)
        else:
            np.testing.assert_allclose(g[layer], w[layer], rtol=1e-5,
                                       err_msg=field)
    # untouched positions of the written layer: bit for bit too
    hit = np.zeros((batch, cap), bool)
    for r in range(batch):
        hit[r, positions[r][positions[r] >= 0]] = True
    for field in ("k", "v"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, field))[layer][~hit],
            np.asarray(getattr(cache, field))[layer][~hit], err_msg=field)
