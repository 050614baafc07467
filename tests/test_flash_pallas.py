"""Pallas flash-attention kernel vs the XLA blockwise/dot references.

The kernel is the TPU replacement for flash_attn (SURVEY.md K1-K3 +
flash_attn); on CPU it runs in pallas interpret mode, so the same numerics
checks run hermetically in CI.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.ops.flash_attention import _blockwise_attention
from megatron_tpu.ops.flash_attention_pallas import pallas_flash_attention


def ref_attention(q, k, v, causal=True):
    b, sq, nq, d = q.shape
    nkv = k.shape[2]
    g = nq // nkv
    qg = q.astype(jnp.float32).reshape(b, sq, nkv, g, d)
    s = jnp.einsum("bsngd,btnd->bngst", qg, k.astype(jnp.float32)) * d**-0.5
    if causal:
        mask = jnp.tril(jnp.ones((sq, k.shape[1]), bool))
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bngst,btnd->bsngd", p, v.astype(jnp.float32))
    return o.reshape(b, sq, nq, d)


@pytest.mark.parametrize("nq,nkv", [(4, 4), (4, 2), (4, 1)])
def test_forward_matches_reference(nq, nkv):
    b, s, d = 2, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, nq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, nkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, nkv, d), jnp.float32)
    got = pallas_flash_attention(q, k, v, True, None, 128, 128, True)
    want = ref_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_noncausal_forward():
    b, s, d = 1, 128, 64
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, s, 4, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, 2, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, 2, d), jnp.float32)
    got = pallas_flash_attention(q, k, v, False, None, 64, 64, True)
    want = ref_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("nq,nkv", [(4, 4), (4, 2)])
def test_backward_matches_reference(nq, nkv):
    b, s, d = 1, 128, 64
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (b, s, nq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, nkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, nkv, d), jnp.float32)

    def loss_pallas(q, k, v):
        o = pallas_flash_attention(q, k, v, True, None, 64, 64, True)
        return jnp.sum(o * jnp.cos(o))

    def loss_ref(q, k, v):
        o = ref_attention(q, k, v)
        return jnp.sum(o * jnp.cos(o))

    g_got = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    g_want = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-4, atol=5e-4)


def test_dispatch_through_flash_attention():
    """ops.flash_attention uses the pallas kernel on TPU; on CPU the XLA
    blockwise path and the (interpreted) kernel must agree."""
    from megatron_tpu.ops.flash_attention import flash_attention
    b, s, d = 1, 128, 64
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, s, 4, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, 2, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, 2, d), jnp.float32)
    xla = flash_attention(q, k, v, causal=True, use_pallas=False)
    pallas = pallas_flash_attention(q, k, v, True, None, 64, 64, True)
    np.testing.assert_allclose(np.asarray(pallas), np.asarray(xla),
                               rtol=2e-5, atol=2e-5)


def ref_attention_segs(q, k, v, segment_ids, causal=True):
    b, sq, nq, d = q.shape
    nkv = k.shape[2]
    g = nq // nkv
    qg = q.astype(jnp.float32).reshape(b, sq, nkv, g, d)
    s = jnp.einsum("bsngd,btnd->bngst", qg, k.astype(jnp.float32)) * d**-0.5
    mask = segment_ids[:, :, None] == segment_ids[:, None, :]
    if causal:
        mask = mask & jnp.tril(jnp.ones((sq, sq), bool))[None]
    s = jnp.where(mask[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bngst,btnd->bsngd", p, v.astype(jnp.float32))
    return o.reshape(b, sq, nq, d)


def _seg_pattern(b, s):
    """Documents of uneven length, incl. a boundary mid-block and a doc
    spanning multiple 128-blocks (the shapes that break naive block
    skipping)."""
    seg = np.zeros((b, s), np.int32)
    seg[:, 100:230] = 1   # crosses the 128 boundary
    seg[:, 230:] = 2      # spans blocks 1-3 at s=512
    return jnp.asarray(seg)


class TestSegmentMasking:
    """EOD-reset block-diagonal masking inside the kernel
    (ref: --reset_attention_mask, megatron/utils.py:137-194) — every row
    of a foreign-document block is fully masked, which is exactly the
    case the MASK_CLAMP guard exists for."""

    def test_forward_matches_reference(self):
        b, s, nq, nkv, d = 2, 512, 4, 2, 64
        ks = jax.random.split(jax.random.PRNGKey(4), 3)
        q = jax.random.normal(ks[0], (b, s, nq, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, s, nkv, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, s, nkv, d), jnp.float32)
        seg = _seg_pattern(b, s)
        segf = seg.astype(jnp.float32)
        got = pallas_flash_attention(q, k, v, True, None, 128, 128, True,
                                     segf, segf)
        want = ref_attention_segs(q, k, v, seg)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_backward_matches_reference(self):
        b, s, nq, nkv, d = 1, 256, 4, 2, 64
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        q = jax.random.normal(ks[0], (b, s, nq, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, s, nkv, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, s, nkv, d), jnp.float32)
        seg = _seg_pattern(b, s)
        segf = seg.astype(jnp.float32)

        def loss_pallas(q, k, v):
            o = pallas_flash_attention(q, k, v, True, None, 128, 128,
                                       True, segf, segf)
            return jnp.sum(o * o)

        def loss_ref(q, k, v):
            o = ref_attention_segs(q, k, v, seg)
            return jnp.sum(o * o)

        g_p = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
        g_r = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b2 in zip(g_p, g_r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b2),
                                       rtol=2e-4, atol=2e-4)

    def test_blockwise_fallback_matches_reference(self):
        from megatron_tpu.ops.flash_attention import _blockwise_attention
        b, s, nq, nkv, d = 2, 320, 4, 2, 32  # 320: pads to 2x256 blocks
        ks = jax.random.split(jax.random.PRNGKey(6), 3)
        q = jax.random.normal(ks[0], (b, s, nq, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, s, nkv, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, s, nkv, d), jnp.float32)
        seg = _seg_pattern(b, s)
        got = _blockwise_attention(q, k, v, causal=True, scale=None,
                                   block_kv=256, segment_ids=seg)
        want = ref_attention_segs(q, k, v, seg)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_attention_apply_flash_segments_match_dot(self):
        """The EOD-reset model path: attention_impl=flash with
        segment_ids must equal the dot path (which was the ONLY path
        that supported segments before)."""
        import dataclasses

        from megatron_tpu.config import ModelConfig
        from megatron_tpu.models.attention import (attention_apply,
                                                   attention_init)
        cfg = ModelConfig(num_layers=2, hidden_size=64,
                          num_attention_heads=4, num_kv_heads=2,
                          vocab_size=128, seq_length=256,
                          use_rotary_emb=False,
                          compute_dtype="float32").derived()
        params = attention_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 256, 64))
        seg = _seg_pattern(2, 256)
        outs = {}
        for impl in ("dot", "flash"):
            c = dataclasses.replace(cfg, attention_impl=impl)
            out, _ = attention_apply(params, x, c, segment_ids=seg)
            outs[impl] = np.asarray(out)
        np.testing.assert_allclose(outs["flash"], outs["dot"],
                                   rtol=2e-4, atol=2e-4)


class TestSlidingWindow:
    """Mistral-style banded causal attention (--sliding_window W): each
    token sees at most the previous W positions; the kernel skips whole
    blocks outside the band in fwd AND both backward kernels."""

    @staticmethod
    def _ref(q, k, v, window):
        b, sq, nq, d = q.shape
        nkv = k.shape[2]
        g = nq // nkv
        qg = q.astype(jnp.float32).reshape(b, sq, nkv, g, d)
        s = jnp.einsum("bsngd,btnd->bngst", qg,
                       k.astype(jnp.float32)) * d**-0.5
        pos = jnp.arange(sq)
        mask = (pos[:, None] >= pos[None, :]) & \
               (pos[:, None] - pos[None, :] < window)
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bngst,btnd->bsngd", p, v.astype(jnp.float32))
        return o.reshape(b, sq, nq, d)

    @pytest.mark.parametrize("window", [96, 128, 300])
    def test_forward_matches_reference(self, window):
        # windows below, at, and above the 128 block size: exercises the
        # skip-behind-the-band predicate and the partial band block
        b, s, nq, nkv, d = 2, 512, 4, 2, 64
        ks = jax.random.split(jax.random.PRNGKey(7), 3)
        q = jax.random.normal(ks[0], (b, s, nq, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, s, nkv, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, s, nkv, d), jnp.float32)
        got = pallas_flash_attention(q, k, v, True, None, 128, 128, True,
                                     None, None, window)
        want = self._ref(q, k, v, window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_backward_matches_reference(self):
        b, s, nq, nkv, d, window = 1, 256, 4, 2, 64, 100
        ks = jax.random.split(jax.random.PRNGKey(8), 3)
        q = jax.random.normal(ks[0], (b, s, nq, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, s, nkv, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, s, nkv, d), jnp.float32)

        def loss_pallas(q, k, v):
            o = pallas_flash_attention(q, k, v, True, None, 128, 128,
                                       True, None, None, window)
            return jnp.sum(o * o)

        def loss_ref(q, k, v):
            return jnp.sum(self._ref(q, k, v, window) ** 2)

        g_p = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
        g_r = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b2 in zip(g_p, g_r):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b2),
                                       rtol=2e-4, atol=2e-4)

    def test_blockwise_fallback_matches_reference(self):
        from megatron_tpu.ops.flash_attention import _blockwise_attention
        b, s, nq, nkv, d, window = 2, 320, 4, 2, 32, 70
        ks = jax.random.split(jax.random.PRNGKey(9), 3)
        q = jax.random.normal(ks[0], (b, s, nq, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, s, nkv, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, s, nkv, d), jnp.float32)
        got = _blockwise_attention(q, k, v, causal=True, scale=None,
                                   block_kv=256, sliding_window=window)
        want = self._ref(q, k, v, window)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_attention_apply_flash_matches_dot(self):
        """Model-level: --sliding_window under attention_impl flash vs
        dot, incl. the cached-decode dot path (q_offset band)."""
        import dataclasses

        from megatron_tpu.config import ModelConfig
        from megatron_tpu.models.attention import (attention_apply,
                                                   attention_init)
        cfg = ModelConfig(num_layers=2, hidden_size=64,
                          num_attention_heads=4, num_kv_heads=2,
                          vocab_size=128, seq_length=256,
                          use_rotary_emb=False, sliding_window=60,
                          compute_dtype="float32").derived()
        params = attention_init(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 256, 64))
        outs = {}
        for impl in ("dot", "flash"):
            c = dataclasses.replace(cfg, attention_impl=impl)
            out, _ = attention_apply(params, x, c)
            outs[impl] = np.asarray(out)
        np.testing.assert_allclose(outs["flash"], outs["dot"],
                                   rtol=2e-4, atol=2e-4)


def test_sliding_window_config_guards():
    import dataclasses

    from megatron_tpu.config import (MegatronConfig, ModelConfig,
                                     TrainingConfig)
    base = ModelConfig(num_layers=2, hidden_size=64,
                       num_attention_heads=4, vocab_size=128,
                       seq_length=64)
    with pytest.raises(AssertionError, match="sliding_window"):
        MegatronConfig(
            model=dataclasses.replace(base, sliding_window=0),
            training=TrainingConfig(micro_batch_size=1,
                                    global_batch_size=1),
        ).validate(n_devices=1)
    # non-causal callers must not silently lose the window
    from megatron_tpu.models.attention import (attention_apply,
                                               attention_init)
    cfg = dataclasses.replace(base, sliding_window=16,
                              use_rotary_emb=False,
                              compute_dtype="float32").derived()
    params = attention_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 64))
    with pytest.raises(AssertionError, match="causal self-attention"):
        attention_apply(params, x, cfg, causal=False)
    # ring configs must not pre-permute for a ring that won't run
    from megatron_tpu.parallel.ring_attention import data_zigzag_cp
    ring_cfg = dataclasses.replace(cfg, attention_impl="ring")
    assert data_zigzag_cp(ring_cfg, 64) == 0


class TestKernelDropout:
    """In-kernel attention dropout (counter-based hash RNG; VERDICT r4
    #5). The mask is REGENERATED in the forward and both backward
    kernels from (seed, head, block coords) — these tests pin: exact
    determinism per seed, rate-0 exactness, unbiasedness around the
    no-dropout output, the keep fraction, and the backward's mask
    regeneration via finite differences."""

    def _qkv(self, b=1, s=256, nq=2, nkv=2, d=64, seed=0):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (b, s, nq, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, s, nkv, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, s, nkv, d), jnp.float32)
        return q, k, v

    def _seed(self, val):
        from megatron_tpu.ops.flash_attention_pallas import STAT_LANES
        return jnp.full((1, STAT_LANES), float(val), jnp.float32)

    def _run(self, q, k, v, rate, seed, bq=128, bkv=128):
        return pallas_flash_attention(q, k, v, True, None, bq, bkv, True,
                                      None, None, None, rate,
                                      self._seed(seed))

    def test_rate0_and_determinism_and_seed_sensitivity(self):
        q, k, v = self._qkv()
        base = pallas_flash_attention(q, k, v, True, None, 128, 128, True)
        a1 = self._run(q, k, v, 0.3, 7)
        a2 = self._run(q, k, v, 0.3, 7)
        b2 = self._run(q, k, v, 0.3, 8)
        np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
        assert np.abs(np.asarray(a1) - np.asarray(b2)).max() > 1e-3
        assert np.abs(np.asarray(a1) - np.asarray(base)).max() > 1e-3

    @pytest.mark.slow
    def test_unbiased_and_keep_fraction(self):
        """Mean over seeds -> no-dropout output (CLT band), and the
        realized keep fraction of the hash stream is binomially sane."""
        q, k, v = self._qkv(seed=1)
        base = pallas_flash_attention(q, k, v, True, None, 128, 128, True)
        rate, n_seeds = 0.3, 192
        outs = jnp.stack([self._run(q, k, v, rate, 100 + i)
                          for i in range(n_seeds)])
        m = np.asarray(jnp.mean(outs, axis=0))
        sd = np.asarray(jnp.std(outs, axis=0))
        tol = 6.0 * sd.max() / np.sqrt(n_seeds) + 1e-4
        assert np.abs(m - np.asarray(base)).max() < tol

        from megatron_tpu.ops.flash_attention_pallas import _dropout_keep
        keep = _dropout_keep(jnp.int32(12345), jnp.int32(3),
                             jnp.int32(0), jnp.int32(0), 256, 256, rate)
        frac = float(jnp.mean(keep.astype(jnp.float32)))
        # 256*256 = 65536 draws: binomial std ~ 0.0018; allow 6 sigma
        assert abs(frac - (1 - rate)) < 0.011, frac

    def test_backward_regenerates_forward_mask(self):
        """Forward AND all three gradients must match a dense softmax-
        then-dropout reference built with the SAME hash mask
        (reconstructed outside the kernel via _dropout_keep) — only true
        if fwd, dq, and dkv kernels all regenerate identical masks and
        the dS = P∘(Z∘dP − delta) algebra is right."""
        from megatron_tpu.ops.flash_attention_pallas import _dropout_keep
        b, s, n, d = 1, 128, 2, 32
        q, k, v = self._qkv(b=b, s=s, nq=n, nkv=n, d=d, seed=2)
        rate, seed, bq, bkv = 0.4, 11, 64, 64

        Z = np.zeros((b, n, s, s), np.float32)
        for bi in range(b):
            for h in range(n):
                for qi in range(s // bq):
                    for ki in range(s // bkv):
                        kp = _dropout_keep(
                            jnp.int32(seed), jnp.int32(bi * n + h),
                            jnp.int32(qi), jnp.int32(ki), bq, bkv, rate)
                        Z[bi, h, qi * bq:(qi + 1) * bq,
                          ki * bkv:(ki + 1) * bkv] = np.asarray(kp)
        Z = jnp.asarray(Z) / (1.0 - rate)

        def dense_ref(q, k, v):
            s_ = jnp.einsum("bqnd,bknd->bnqk", q, k) * d ** -0.5
            mask = jnp.tril(jnp.ones((s, s), bool))
            s_ = jnp.where(mask[None, None], s_, -1e30)
            p = jax.nn.softmax(s_, axis=-1)
            return jnp.einsum("bnqk,bknd->bqnd", p * Z, v)

        def loss_p(q, k, v):
            return jnp.sum(self._run(q, k, v, rate, seed, bq, bkv) ** 2)

        def loss_r(q, k, v):
            return jnp.sum(dense_ref(q, k, v) ** 2)

        o_p = self._run(q, k, v, rate, seed, bq, bkv)
        np.testing.assert_allclose(np.asarray(o_p),
                                   np.asarray(dense_ref(q, k, v)),
                                   rtol=1e-5, atol=1e-5)
        gp = jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
        for a, want in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(want),
                                       rtol=1e-4, atol=1e-4)

    @pytest.mark.slow
    def test_matches_xla_blockwise_statistics(self):
        """Both impls are unbiased around the same target with the SAME
        1/(1-p) scaling convention. Per-element CLT bands are wide here
        (short peaky rows make dropout variance large), so the sharp
        statistic is the regression coefficient of the seed-mean onto
        the no-dropout output: c = <m, base>/<base, base> must be 1 for
        both — a keep-prob or scaling mismatch shifts c by the
        mismatch ratio while its sampling noise is ~1/sqrt(N*elements)."""
        q, k, v = self._qkv(seed=2, s=128)
        rate, n = 0.25, 96
        pall = jnp.stack([self._run(q, k, v, rate, 50 + i, bq=64, bkv=64)
                          for i in range(n)]).mean(0)
        xla = jnp.stack([
            _blockwise_attention(q, k, v, causal=True, scale=None,
                                 block_kv=64, dropout_rate=rate,
                                 dropout_rng=jax.random.PRNGKey(50 + i))
            for i in range(n)]).mean(0)
        base = np.asarray(
            pallas_flash_attention(q, k, v, True, None, 64, 64, True))
        for name, m in (("pallas", pall), ("xla", xla)):
            c = float(np.sum(np.asarray(m) * base) / np.sum(base * base))
            assert abs(c - 1.0) < 0.02, (name, c)

    def test_dropout_composes_with_sliding_window_and_segments(self):
        """Dropout + banded mask + segment mask in one kernel call stay
        finite and deterministic."""
        from megatron_tpu.ops.flash_attention_pallas import _seg_lanes
        q, k, v = self._qkv(s=256)
        seg = jnp.concatenate([jnp.zeros((1, 128)), jnp.ones((1, 128))],
                              axis=1).astype(jnp.float32)
        o1 = pallas_flash_attention(q, k, v, True, None, 128, 128, True,
                                    seg, seg, 64, 0.3, self._seed(5))
        o2 = pallas_flash_attention(q, k, v, True, None, 128, 128, True,
                                    seg, seg, 64, 0.3, self._seed(5))
        np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
        assert np.isfinite(np.asarray(o1)).all()


# ---- the products' operand dtype is the rows' dtype (PR 42) ---------------
#
# A kernel product takes q, k, v, do as they arrive and rounds p / ds to that
# dtype where they enter a product (`_dot_attention`'s own rounding of its
# probabilities); sums, softmax and statistics are float32. The cases below
# run every kernel entry on bf16 and on float32 rows.

BF16_EPS = 2.0 ** -8        # half a step of bf16's grid at 1

# The tolerance of every bf16 comparison below, in those half-steps and
# relative to the compared array's largest value: each side rounds its
# result to bf16 (one half-step each), the kernel rounds p and ds, the plain
# path its scores, its probabilities and every intermediate of its backward
# pass. The cases read at most 1.7 (each side against float32 arithmetic on
# the same rows: the kernel at most 1.2, the plain path 1.5); a lost scale
# or a mask off by a row reads tens.
BF16_STEPS = 4
F32_TOL = {"out": 2e-5, "grad": 5e-4}     # this file's float32 tolerances


def _rows(dtype, b, sq, sk, nq, nkv, d, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    mk = lambda key, s, n: jax.random.normal(  # noqa: E731
        key, (b, s, n, d), jnp.float32).astype(dtype)
    return (mk(ks[0], sq, nq), mk(ks[1], sk, nkv), mk(ks[2], sk, nkv),
            mk(ks[3], sq, nq))


def _close(got, want, dtype, kind):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    if dtype == jnp.float32:
        tol = F32_TOL[kind]
        np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    else:
        assert np.abs(got - want).max() <= (
            BF16_STEPS * BF16_EPS * np.abs(want).max())


ALIGNED_CASES = {
    # name: (nq, nkv, segments, window)
    "causal": (4, 4, False, None),
    "gqa": (4, 2, False, None),
    "mqa": (4, 1, False, None),
    "segments": (4, 2, True, None),
    "window": (4, 2, False, 100),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case", sorted(ALIGNED_CASES))
def test_rows_dtype_forward_and_gradients(case, dtype):
    """The forward and all three gradients against `_dot_attention` on the
    SAME rows: bf16 rows within bf16's grid (above), float32 rows at this
    file's float32 tolerances, i.e. the numbers the kernel gave before its
    products followed the rows' dtype."""
    from megatron_tpu.models.attention import _dot_attention
    nq, nkv, segs, window = ALIGNED_CASES[case]
    b, s, d = 1, 256, 64
    q, k, v, do = _rows(dtype, b, s, s, nq, nkv, d, seed=11)
    seg = _seg_pattern(b, s) if segs else None
    segf = seg.astype(jnp.float32) if segs else None

    def kernel(q, k, v):
        return pallas_flash_attention(q, k, v, True, None, 128, 128, True,
                                      segf, segf, window)

    def plain(q, k, v):
        return _dot_attention(q, k, v, causal=True, softmax_fp32=True,
                              scale=d ** -0.5, segment_ids=seg,
                              sliding_window=window)

    got, got_vjp = jax.vjp(kernel, q, k, v)
    want, want_vjp = jax.vjp(plain, q, k, v)
    assert got.dtype == dtype
    _close(got, want, dtype, "out")
    for g, w in zip(got_vjp(do), want_vjp(do)):
        assert g.dtype == dtype
        _close(g, w, dtype, "grad")


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("off,start,window", [(512, 0, None),
                                              (512, 200, 300)])
def test_rows_dtype_offset_kernel(off, start, window, dtype):
    """The chunk's kernel, keys heads-major as the cache holds them: 256
    queries at position `off` of 768 keys, keys before `start` empty."""
    from megatron_tpu.models.attention import _dot_attention
    from megatron_tpu.ops.flash_attention_pallas import \
        pallas_flash_attention_offset
    b, sq, sk, nq, nkv, d = 1, 256, 768, 4, 2, 128
    q, k, v, _ = _rows(dtype, b, sq, sk, nq, nkv, d, seed=12)
    got = pallas_flash_attention_offset(
        q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), jnp.int32(off),
        jnp.int32(start), sliding_window=window, block_q=128, block_kv=128,
        interpret=True, kv_heads_major=True)
    pos = jnp.arange(sk)
    want = _dot_attention(
        q, k, v, causal=True, softmax_fp32=True, scale=d ** -0.5,
        q_offset=off, sliding_window=window,
        kv_positions=jnp.where(pos >= start, pos, 2 ** 30))
    assert got.dtype == dtype
    _close(got, want, dtype, "out")


def _pallas_calls(fn, *args):
    """{kernel's name: its pallas_call equation} for every kernel that
    `fn(*args)` traces."""
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found[eqn.params["jaxpr"].debug_info.func_name] = eqn
            else:
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def _dots(jaxpr):
    """(lhs dtype, rhs dtype, out dtype) of every dot_general in `jaxpr`,
    the bodies of its `pl.when`s included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield tuple(str(x.aval.dtype)
                        for x in (*eqn.invars, *eqn.outvars))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _dots(sub)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_every_kernel_product_takes_the_rows_dtype(dtype):
    """Structure, so that no later edit puts a cast back: inside each of the
    four pallas_calls every dot_general's two operands have the rows' dtype
    and its result is float32. 2 products in each forward kernel, 3 in the
    dq kernel, 4 in the dk/dv kernel."""
    from megatron_tpu.ops.flash_attention_pallas import \
        pallas_flash_attention_offset
    q, k, v, do = _rows(dtype, 1, 256, 256, 4, 2, 128, seed=13)
    segf = _seg_pattern(1, 256).astype(jnp.float32)
    seed = jnp.full((1, 8), 3.0, jnp.float32)

    def fwd_bwd(q, k, v):      # segments and dropout on: every cast site
        out, vjp = jax.vjp(
            lambda *a: pallas_flash_attention(
                *a, True, None, 128, 128, True, segf, segf, None, 0.1, seed),
            q, k, v)
        return out, vjp(do)

    def chunk(q, k, v):
        return pallas_flash_attention_offset(
            q, k, v, jnp.int32(0), jnp.int32(0), block_q=128, block_kv=128,
            interpret=True)

    calls = {**_pallas_calls(fwd_bwd, q, k, v),
             **_pallas_calls(chunk, q, k, v)}
    dots = {n: list(_dots(e.params["jaxpr"])) for n, e in calls.items()}
    name = str(jnp.dtype(dtype))
    assert {n: len(d) for n, d in dots.items()} == {
        "_fwd_kernel": 2, "_fwd_kernel_offset": 2, "_bwd_dq_kernel": 3,
        "_bwd_dkv_kernel": 4}, dots
    for kernel, ds in dots.items():
        assert all(d == (name, name, "float32") for d in ds), (kernel, ds)


# ---- the forward's blocks and the blocks it does not fetch (PR 42) --------

def _runs(qi, ki, bq, bkv, off, window, start):
    """The kernels' own skip rule for a causal block pair, on plain ints."""
    q_first = off + qi * bq
    run = ki * bkv <= q_first + bq - 1
    if window is not None:
        run = run and ki * bkv + bkv - 1 > q_first - window
    return run and ki * bkv + bkv - 1 >= start


@pytest.mark.parametrize("bq,bkv,off,window,start", [
    (128, 128, 0, None, 0), (256, 128, 0, None, 0), (128, 256, 0, None, 0),
    (128, 128, 0, 200, 0), (256, 128, 0, 100, 0), (128, 128, 512, None, 0),
    (128, 128, 384, 300, 200), (128, 256, 512, 512, 512)])
def test_skipped_blocks_are_not_fetched(bq, bkv, off, window, start):
    """`_kv_block_index` hands a step that runs its own block, and a step
    the kernel skips the nearest block that runs, so that the index does
    not change over a run of skipped steps and nothing is fetched for them
    (aligned: `off` 0; the chunk's kernel: `off`, `start`)."""
    from megatron_tpu.ops.flash_attention_pallas import _kv_block_index
    sq, sk = 512, 1024
    num_q, num_kv = sq // bq, sk // bkv
    for qi in range(num_q):
        ran = [ki for ki in range(num_kv)
               if _runs(qi, ki, bq, bkv, off, window, start)]
        assert ran == list(range(ran[0], ran[-1] + 1))
        for ki in range(num_kv):
            got = int(_kv_block_index(ki, off + qi * bq, bq, bkv, num_kv,
                                      window, kv_start=start))
            assert got == min(max(ki, ran[0]), ran[-1]), (qi, ki)


def _grids(fn, *args):
    return {name: tuple(eqn.params["grid_mapping"].grid)
            for name, eqn in _pallas_calls(fn, *args).items()}


def test_default_blocks():
    """With no block given (the model's calls) a forward takes blocks of
    1,024; the two backward kernels and a forward with dropout, which hold
    four and more [bq, bkv] float32 arrays, keep 512 (what VMEM holds:
    tests/test_tpu_compile.py compiles both at real widths)."""
    q, k, v, do = _rows(jnp.bfloat16, 1, 2048, 2048, 2, 1, 64, seed=14)
    seed = jnp.full((1, 8), 3.0, jnp.float32)

    def plain(q, k, v):
        out, vjp = jax.vjp(pallas_flash_attention, q, k, v)
        return out, vjp(do)

    def dropped(q, k, v):
        return pallas_flash_attention(q, k, v, True, None, 1024, 1024, False,
                                      None, None, None, 0.1, seed)

    assert _grids(plain, q, k, v) == {
        "_fwd_kernel": (1, 2, 2, 2), "_bwd_dq_kernel": (1, 2, 4, 4),
        "_bwd_dkv_kernel": (1, 2, 4, 4)}
    assert _grids(dropped, q, k, v) == {"_fwd_kernel": (1, 2, 4, 4)}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_default_blocks_match_plain_attention(dtype):
    """The blocks the models run with (1,024 forward, 512 backward) at the
    training cells' 2,048 rows, MQA: forward and gradients against
    `_dot_attention` on the same rows."""
    from megatron_tpu.models.attention import _dot_attention
    from megatron_tpu.ops.flash_attention_pallas import (DEFAULT_BLOCK_KV,
                                                         DEFAULT_BLOCK_Q)
    d = 64
    q, k, v, do = _rows(dtype, 1, 2048, 2048, 2, 1, d, seed=15)
    got, got_vjp = jax.vjp(
        lambda *a: pallas_flash_attention(*a, True, None, DEFAULT_BLOCK_Q,
                                          DEFAULT_BLOCK_KV, True), q, k, v)
    want, want_vjp = jax.vjp(
        lambda *a: _dot_attention(*a, causal=True, softmax_fp32=True,
                                  scale=d ** -0.5), q, k, v)
    _close(got, want, dtype, "out")
    for g, w in zip(got_vjp(do), want_vjp(do)):
        _close(g, w, dtype, "grad")
