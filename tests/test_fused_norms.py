"""Pallas fused norm kernels vs the canonical jnp implementations.

Contract port of the reference's fused-kernel tests
(ref: megatron/fused_kernels/tests/test_fused_kernels.py — fused LN
compared against module outputs): fwd and full vjp equality, fp32 stats
under bf16 inputs, odd row counts. Interpret mode (CPU-hermetic);
tests/test_tpu_compile.py compiles both kernels for the chip.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.models.norms import layernorm, rmsnorm
from megatron_tpu.ops.fused_norms import (_pick_rows, pallas_layernorm,
                                          pallas_rmsnorm)


@pytest.fixture(params=[(32, 128), (6, 256), (40, 512)])
def shapes(request):
    return request.param


def _data(rows, h, dtype=jnp.float32, seed=0):
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(k1, (rows, h), dtype) * 2.0 + 0.3
    scale = jax.random.normal(k2, (h,), dtype) * 0.1 + 1.0
    bias = jax.random.normal(k3, (h,), dtype) * 0.1
    dy = jax.random.normal(k4, (rows, h), dtype)
    return x, scale, bias, dy


class TestRMSNorm:
    def test_forward_matches_jnp(self, shapes):
        rows, h = shapes
        x, scale, _, _ = _data(rows, h)
        ref = rmsnorm({"scale": scale}, x)
        got = pallas_rmsnorm(x, scale, 1e-5, True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-6, atol=2e-6)

    def test_grads_match_jnp(self, shapes):
        rows, h = shapes
        x, scale, _, dy = _data(rows, h)

        def f_ref(x, s):
            return jnp.sum(rmsnorm({"scale": s}, x) * dy)

        def f_pal(x, s):
            return jnp.sum(pallas_rmsnorm(x, s, 1e-5, True) * dy)

        gx_r, gs_r = jax.grad(f_ref, argnums=(0, 1))(x, scale)
        gx_p, gs_p = jax.grad(f_pal, argnums=(0, 1))(x, scale)
        np.testing.assert_allclose(np.asarray(gx_p), np.asarray(gx_r),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(gs_p), np.asarray(gs_r),
                                   rtol=1e-5, atol=1e-5)

    def test_bf16_input_fp32_stats(self):
        x, scale, _, _ = _data(16, 256)
        xb = x.astype(jnp.bfloat16)
        ref = rmsnorm({"scale": scale.astype(jnp.bfloat16)}, xb)
        got = pallas_rmsnorm(xb, scale.astype(jnp.bfloat16), 1e-5, True)
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(ref, np.float32),
            rtol=2e-2, atol=2e-2)

    def test_3d_shape(self):
        x, scale, _, _ = _data(24, 128)
        x3 = x.reshape(2, 12, 128)
        ref = rmsnorm({"scale": scale}, x3)
        got = pallas_rmsnorm(x3, scale, 1e-5, True)
        assert got.shape == (2, 12, 128)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-6, atol=2e-6)


class TestLayerNorm:
    def test_forward_matches_jnp(self, shapes):
        rows, h = shapes
        x, scale, bias, _ = _data(rows, h)
        ref = layernorm({"scale": scale, "bias": bias}, x)
        got = pallas_layernorm(x, scale, bias, 1e-5, True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-6, atol=2e-6)

    def test_grads_match_jnp(self, shapes):
        rows, h = shapes
        x, scale, bias, dy = _data(rows, h)

        def f_ref(x, s, b):
            return jnp.sum(layernorm({"scale": s, "bias": b}, x) * dy)

        def f_pal(x, s, b):
            return jnp.sum(pallas_layernorm(x, s, b, 1e-5, True) * dy)

        g_r = jax.grad(f_ref, argnums=(0, 1, 2))(x, scale, bias)
        g_p = jax.grad(f_pal, argnums=(0, 1, 2))(x, scale, bias)
        for a, b in zip(g_p, g_r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)


def test_pick_rows_tiles_and_bounds():
    assert _pick_rows(1024, 4096) % 8 == 0
    # rows need NOT divide the block any more (callers zero-pad): a prime
    # row count must still get a real multi-row block, not a 1-row grid
    assert _pick_rows(1021, 4096) % 8 == 0 and _pick_rows(1021, 4096) >= 8
    # huge h: block shrinks to fit VMEM budget
    assert _pick_rows(4096, 16384) * 16384 * 4 <= (1 << 21)


def test_prime_row_count_pads_and_matches():
    """ADVICE r2 (low): prime b*s must not collapse to a 1-row grid; the
    zero-pad path must stay numerically exact, including weight grads."""
    from megatron_tpu.ops.fused_norms import pallas_rmsnorm
    rng = jax.random.PRNGKey(7)
    x = jax.random.normal(rng, (13, 128), jnp.float32)  # 13 rows: prime
    scale = jax.random.normal(jax.random.fold_in(rng, 1), (128,))
    dy = jax.random.normal(jax.random.fold_in(rng, 2), (13, 128))

    def ref(x, s):
        r = jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)
        return x * r * s

    got = pallas_rmsnorm(x, scale, 1e-5, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref(x, scale)),
                               rtol=1e-5, atol=1e-5)
    g_r = jax.grad(lambda x, s: jnp.sum(ref(x, s) * dy),
                   argnums=(0, 1))(x, scale)
    g_p = jax.grad(lambda x, s: jnp.sum(
        pallas_rmsnorm(x, s, 1e-5, True) * dy), argnums=(0, 1))(x, scale)
    for a, b in zip(g_p, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_prime_row_count_layernorm_pads_and_matches():
    """Same pad-path exactness for LayerNorm — covers the db bias-grad
    partial, which has no RMSNorm analogue."""
    from megatron_tpu.ops.fused_norms import pallas_layernorm
    rng = jax.random.PRNGKey(11)
    x = jax.random.normal(rng, (13, 128), jnp.float32)
    scale = jax.random.normal(jax.random.fold_in(rng, 1), (128,))
    bias = jax.random.normal(jax.random.fold_in(rng, 2), (128,))
    dy = jax.random.normal(jax.random.fold_in(rng, 3), (13, 128))

    def ref(x, s, b):
        mu = jnp.mean(x, -1, keepdims=True)
        xc = x - mu
        r = jax.lax.rsqrt(jnp.mean(xc * xc, -1, keepdims=True) + 1e-5)
        return xc * r * s + b

    got = pallas_layernorm(x, scale, bias, 1e-5, True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref(x, scale, bias)),
                               rtol=1e-5, atol=1e-5)
    g_r = jax.grad(lambda x, s, b: jnp.sum(ref(x, s, b) * dy),
                   argnums=(0, 1, 2))(x, scale, bias)
    g_p = jax.grad(lambda x, s, b: jnp.sum(
        pallas_layernorm(x, s, b, 1e-5, True) * dy),
        argnums=(0, 1, 2))(x, scale, bias)
    for a, b in zip(g_p, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
