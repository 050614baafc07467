"""Block-native decode attention kernel vs the dot/contiguous reference.

The kernel (ops/block_attention_pallas.py) reads the serving pool's
flat block arena through the per-slot block map — the paged-attention
read the engine uses to drop the resolve_view/scatter_view bracket.
On CPU it runs in pallas interpret mode (the dropout-RNG precedent
from flash_attention_pallas: the kernel body uses only interpret-able
ops), so the full numerics suite runs hermetically in tier-1 under
JAX_PLATFORMS=cpu; tests/test_tpu_compile.py compiles it for the chip.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.models.attention import _dot_attention
from megatron_tpu.ops.block_attention_pallas import (
    block_native_attention, contiguous_pool_attention)


def _gather_view(arena, bmap, s):
    """Contiguous [cap, nkv, *] view of slot s — the resolve_view
    reference the kernel must agree with."""
    return np.concatenate([np.asarray(arena[int(b)]) for b in bmap[s]],
                          axis=0)


def ref_block_attention(q, ka, va, bmap, lengths, scale, ks=None,
                        vs=None):
    """Per-slot causal attention over the map-resolved contiguous view
    (full-row fp32 softmax — the engine's dot-path numerics)."""
    S, w, nq, hd = q.shape
    nkv = ka.shape[2]
    g = nq // nkv
    cap = bmap.shape[1] * ka.shape[1]
    out = np.zeros((S, w, nq, hd), np.float32)
    for s in range(S):
        k = _gather_view(ka, bmap, s).astype(np.float32)
        v = _gather_view(va, bmap, s).astype(np.float32)
        if ks is not None:
            k = k * _gather_view(ks, bmap, s).astype(np.float32)
            v = v * _gather_view(vs, bmap, s).astype(np.float32)
        for j in range(w):
            qp = int(lengths[s]) + j
            for h in range(nq):
                sc = (q[s, j, h].astype(np.float32) * scale) \
                    @ k[:, h // g, :].T
                sc = np.where(np.arange(cap) <= qp, sc, -1e30)
                p = np.exp(sc - sc.max())
                out[s, j, h] = (p / p.sum()) @ v[:, h // g, :]
    return out


def _arena(rs, T, B, nkv, hd, dtype):
    if dtype == np.int8:
        ka = rs.randint(-127, 127, (T, B, nkv, hd)).astype(np.int8)
        va = rs.randint(-127, 127, (T, B, nkv, hd)).astype(np.int8)
        ks = (rs.rand(T, B, nkv, 1).astype(np.float32) * 0.02)
        vs = (rs.rand(T, B, nkv, 1).astype(np.float32) * 0.02)
        return ka, va, ks, vs
    ka = rs.randn(T, B, nkv, hd).astype(dtype)
    va = rs.randn(T, B, nkv, hd).astype(dtype)
    return ka, va, None, None


def _run(q, ka, va, bmap, lengths, scale, B, ks=None, vs=None):
    return np.asarray(block_native_attention(
        jnp.asarray(q), jnp.asarray(ka), jnp.asarray(va),
        jnp.asarray(bmap), jnp.asarray(lengths), scale=scale,
        k_scale=None if ks is None else jnp.asarray(ks),
        v_scale=None if vs is None else jnp.asarray(vs),
        interpret=True))


@pytest.mark.parametrize("nq,nkv", [(4, 4), (4, 2), (4, 1)])
def test_decode_matches_reference_scattered_map(nq, nkv):
    """w == 1 decode over a PERMUTED physical map — the scattered
    block chains the gather/scatter bracket used to linearize."""
    S, B, nb, hd = 4, 8, 6, 16
    T = S * nb + 1
    rs = np.random.RandomState(0)
    ka, va, _, _ = _arena(rs, T, B, nkv, hd, np.float32)
    q = rs.randn(S, 1, nq, hd).astype(np.float32)
    bmap = np.stack([rs.permutation(T - 1)[:nb]
                     for _ in range(S)]).astype(np.int32)
    lengths = np.array([1, 13, B * nb - 1, 24], np.int32)
    got = _run(q, ka, va, bmap, lengths, hd ** -0.5, B)
    want = ref_block_attention(q, ka, va, bmap, lengths, hd ** -0.5)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("w", [2, 5])
def test_verify_window_causal_within_window(w):
    """w > 1: the speculative verify grid — query j at position
    length + j, causal WITHIN the window (later queries see earlier
    window positions, never vice versa)."""
    S, B, nb, nq, nkv, hd = 3, 8, 5, 4, 2, 16
    T = S * nb + 1
    rs = np.random.RandomState(1)
    ka, va, _, _ = _arena(rs, T, B, nkv, hd, np.float32)
    q = rs.randn(S, w, nq, hd).astype(np.float32)
    bmap = np.stack([rs.permutation(T - 1)[:nb]
                     for _ in range(S)]).astype(np.int32)
    lengths = np.array([3, B - 1, 2 * B], np.int32)  # tail straddles
    got = _run(q, ka, va, bmap, lengths, hd ** -0.5, B)
    want = ref_block_attention(q, ka, va, bmap, lengths, hd ** -0.5)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_int8_dequant_in_kernel():
    """int8 arena + per-(token, head) scales: the kernel dequantizes
    inside, and must agree with dequantize-then-dot."""
    S, w, B, nb, nq, nkv, hd = 3, 3, 8, 4, 6, 3, 8
    T = S * nb + 1
    rs = np.random.RandomState(2)
    ka, va, ks, vs = _arena(rs, T, B, nkv, hd, np.int8)
    q = rs.randn(S, w, nq, hd).astype(np.float32)
    bmap = np.stack([rs.permutation(T - 1)[:nb]
                     for _ in range(S)]).astype(np.int32)
    lengths = np.array([0, 9, 17], np.int32)
    got = _run(q, ka, va, bmap, lengths, hd ** -0.5, B, ks, vs)
    want = ref_block_attention(q, ka, va, bmap, lengths, hd ** -0.5,
                               ks, vs)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_partial_tail_block_masked():
    """Length mid-block: positions past `length` in the tail block
    (stale garbage in the arena) must not contribute. Poison them
    with huge values and require the clean-view answer."""
    S, B, nb, nq, nkv, hd = 1, 8, 3, 2, 1, 16
    T = S * nb + 1
    rs = np.random.RandomState(3)
    ka, va, _, _ = _arena(rs, T, B, nkv, hd, np.float32)
    q = rs.randn(S, 1, nq, hd).astype(np.float32)
    bmap = np.arange(nb, dtype=np.int32)[None]
    length = B + 3  # tail block live through position B+3
    # poison every position PAST the query position in the tail block
    ka[bmap[0, 1], 4:] = 1e4
    va[bmap[0, 1], 4:] = 1e4
    # ...and the entirely-dead third block
    ka[bmap[0, 2]] = 1e4
    va[bmap[0, 2]] = 1e4
    lengths = np.array([length], np.int32)
    got = _run(q, ka, va, bmap, lengths, hd ** -0.5, B)
    want = ref_block_attention(q, ka, va, bmap, lengths, hd ** -0.5)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    assert np.all(np.abs(got) < 1e3), "poisoned dead positions leaked"


def test_idle_trash_row_is_finite():
    """An idle grid row (length 0, map parked wholly on the TRASH
    block) reads one garbage position — output is garbage but must be
    FINITE (the engine discards it; a NaN would poison the non-finite
    guard)."""
    S, B, nb, nq, nkv, hd = 2, 8, 4, 4, 2, 16
    T = S * nb + 1
    rs = np.random.RandomState(4)
    ka, va, _, _ = _arena(rs, T, B, nkv, hd, np.float32)
    q = rs.randn(S, 1, nq, hd).astype(np.float32)
    bmap = np.stack([np.full(nb, T - 1), np.arange(nb)]).astype(np.int32)
    lengths = np.array([0, 11], np.int32)
    got = _run(q, ka, va, bmap, lengths, hd ** -0.5, B)
    assert np.all(np.isfinite(got))
    # the live row is still exact
    want = ref_block_attention(q, ka, va, bmap, lengths, hd ** -0.5)
    np.testing.assert_allclose(got[1], want[1], rtol=2e-5, atol=2e-5)


def test_aliased_prefix_blocks_shared():
    """Two slots aliasing the same physical prefix blocks (the prefix
    cache's copy-on-write hit) read identical prefix content."""
    S, B, nb, nq, nkv, hd = 2, 8, 4, 4, 2, 16
    T = S * nb + 1
    rs = np.random.RandomState(5)
    ka, va, _, _ = _arena(rs, T, B, nkv, hd, np.float32)
    shared = [0, 1]
    bmap = np.array([shared + [2, 3], shared + [4, 5]], np.int32)
    q0 = rs.randn(1, 1, nq, hd).astype(np.float32)
    q = np.concatenate([q0, q0], axis=0)  # same query both slots
    plen = 2 * B  # both positioned right at the shared-prefix edge
    # the engine appends each slot's own token at position plen (its
    # first FRESH block) before the read — same token here, so the
    # whole live window is identical across the aliased slots
    ka[2, 0] = ka[4, 0]
    va[2, 0] = va[4, 0]
    lengths = np.array([plen, plen], np.int32)
    got = _run(q, ka, va, bmap, lengths, hd ** -0.5, B)
    # identical queries + aliased (identical) live KV -> identical out
    np.testing.assert_array_equal(got[0], got[1])


def test_bf16_payload_dequantizes_like_dot():
    """bf16 arena: the kernel casts to fp32 exactly like the dot
    path's astype — agreement at fp32 tolerance of the bf16 payload."""
    S, B, nb, nq, nkv, hd = 2, 8, 4, 4, 2, 16
    T = S * nb + 1
    rs = np.random.RandomState(6)
    ka = jnp.asarray(rs.randn(T, B, nkv, hd), jnp.bfloat16)
    va = jnp.asarray(rs.randn(T, B, nkv, hd), jnp.bfloat16)
    q = rs.randn(S, 1, nq, hd).astype(np.float32)
    bmap = np.stack([rs.permutation(T - 1)[:nb]
                     for _ in range(S)]).astype(np.int32)
    lengths = np.array([7, 20], np.int32)
    got = _run(q, np.asarray(ka.astype(jnp.float32)),
               np.asarray(va.astype(jnp.float32)), bmap, lengths,
               hd ** -0.5, B)
    got_bf = np.asarray(block_native_attention(
        jnp.asarray(q), ka, va, jnp.asarray(bmap),
        jnp.asarray(lengths), scale=hd ** -0.5,
        interpret=True))
    np.testing.assert_allclose(got_bf, got, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_stacked_arena_reads_the_indexed_layer(layer):
    """The arena stacked over L = 3 layers with the layer's index (a traced
    scalar, as the layer loop hands it down): the answer is that layer's
    under its scattered map, whatever the other two hold."""
    L, S, w, B, nb, nq, nkv, hd = 3, 3, 2, 8, 4, 4, 2, 16
    T = S * nb + 1
    rs = np.random.RandomState(8)
    ka, va, _, _ = _arena(rs, L * T, B, nkv, hd, np.float32)
    ka, va = ka.reshape(L, T, B, nkv, hd), va.reshape(L, T, B, nkv, hd)
    q = rs.randn(S, w, nq, hd).astype(np.float32)
    bmap = np.stack([rs.permutation(T - 1)[:nb]
                     for _ in range(S)]).astype(np.int32)
    lengths = np.array([0, B + 3, 3 * B - 1], np.int32)
    got = np.asarray(jax.jit(
        lambda i: block_native_attention(
            jnp.asarray(q), jnp.asarray(ka), jnp.asarray(va),
            jnp.asarray(bmap), jnp.asarray(lengths), scale=hd ** -0.5,
            layer=i, interpret=True))(jnp.int32(layer)))
    want = ref_block_attention(q, ka[layer], va[layer], bmap, lengths,
                               hd ** -0.5)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kind", ["bf16", "int8"])
@pytest.mark.parametrize("w", [1, 3])
@pytest.mark.parametrize("nq,nkv", [(4, 4), (4, 2)], ids=["mha", "gqa"])
def test_contiguous_pool_is_the_identity_chain(nq, nkv, w, kind):
    """The slot pool as `KVCache` holds it, [L, slots, max_len, nkv, hd],
    read as blocks of B rows where it lies: every layer of L = 3 against
    `_dot_attention` over that layer read whole, at lengths on and either
    side of a block's edge. Everything past what a row's last query may see
    holds large finite values: none may reach the result."""
    L, B, nb, hd = 3, 8, 4, 16
    max_len = B * nb
    lens = [0, 1, B - 1, B, B + 1, max_len - 1]
    S = len(lens)
    rs = np.random.RandomState(9)
    past = np.arange(max_len)[None, :] > np.asarray(lens)[:, None] + w - 1
    ks = vs = None
    if kind == "int8":
        k = rs.randint(-127, 127, (L, S, max_len, nkv, hd)).astype(np.int8)
        v = rs.randint(-127, 127, (L, S, max_len, nkv, hd)).astype(np.int8)
        ks = rs.rand(L, S, max_len, nkv, 1).astype(np.float32) * 0.02
        vs = rs.rand(L, S, max_len, nkv, 1).astype(np.float32) * 0.02
        k[:, past], v[:, past] = 127, 127
        ks[:, past], vs[:, past] = 1e3, 1e3
        k_ref, v_ref = k.astype(np.float32) * ks, v.astype(np.float32) * vs
    else:
        k = rs.randn(L, S, max_len, nkv, hd).astype(np.float32)
        v = rs.randn(L, S, max_len, nkv, hd).astype(np.float32)
        k[:, past], v[:, past] = 1e4, 1e4
        k, v = jnp.asarray(k, jnp.bfloat16), jnp.asarray(v, jnp.bfloat16)
        k_ref, v_ref = (np.asarray(k.astype(jnp.float32)),
                        np.asarray(v.astype(jnp.float32)))
    q = rs.randn(S, w, nq, hd).astype(np.float32)
    lengths = jnp.asarray(lens, jnp.int32)
    read = jax.jit(lambda i: contiguous_pool_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), lengths, layer=i,
        rows=B, scale=hd ** -0.5,
        k_scale=None if ks is None else jnp.asarray(ks),
        v_scale=None if vs is None else jnp.asarray(vs)))
    for layer in range(L):
        got = np.asarray(read(jnp.int32(layer)))
        want = np.asarray(_dot_attention(
            jnp.asarray(q), jnp.asarray(k_ref[layer]),
            jnp.asarray(v_ref[layer]), causal=True, softmax_fp32=True,
            scale=hd ** -0.5, q_offset=lengths))
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        assert np.all(np.abs(got) < 1e2), "what lies past a length leaked"


@pytest.mark.slow
def test_onchip_shapes_compile_and_match():
    """Production-shaped run (128-lane head_dim, 16-token blocks,
    long chains) — exercised off the fast tier; on a real TPU this is
    the compiled-kernel path (interpret on CPU)."""
    S, B, nb, nq, nkv, hd = 8, 16, 32, 8, 4, 128
    T = S * nb + 1
    rs = np.random.RandomState(7)
    ka, va, _, _ = _arena(rs, T, B, nkv, hd, np.float32)
    q = rs.randn(S, 1, nq, hd).astype(np.float32)
    bmap = np.stack([rs.permutation(T - 1)[:nb]
                     for _ in range(S)]).astype(np.int32)
    lengths = rs.randint(1, nb * B - 1, S).astype(np.int32)
    interp = jax.default_backend() != "tpu"
    got = np.asarray(block_native_attention(
        jnp.asarray(q), jnp.asarray(ka), jnp.asarray(va),
        jnp.asarray(bmap), jnp.asarray(lengths), scale=hd ** -0.5,
        interpret=interp))
    want = ref_block_attention(q, ka, va, bmap, lengths, hd ** -0.5)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
