"""Mixture-of-Experts (models/moe.py) — beyond the reference (SURVEY.md
§2.8 lists expert parallelism as absent there).

Contracts:
- dispatch bookkeeping: with ample capacity every top-k choice lands in
  exactly one expert slot and combine weights renormalize over k;
- E=1 degenerates to the dense MLP exactly (router prob == 1);
- a tiny MoE model trains (loss decreases, aux loss finite and active);
- tp-sharded (expert-parallel) loss matches single-device;
- the validate() restriction to pipeline_parallel == 1 holds.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.config import ModelConfig
from megatron_tpu.models.moe import moe_apply, moe_axes, moe_capacity, moe_init


def _cfg(**kw):
    base = dict(num_layers=2, hidden_size=64, num_attention_heads=4,
                ffn_hidden_size=96, vocab_size=128, seq_length=32,
                make_vocab_size_divisible_by=128, compute_dtype="float32",
                num_experts=4, moe_top_k=2, moe_capacity_factor=2.0)
    base.update(kw)
    return ModelConfig(**base).derived()


def test_dispatch_accounts_every_kept_token():
    from megatron_tpu.models.moe import moe_dispatch
    b, s, E, K = 2, 32, 4, 2
    key = jax.random.PRNGKey(7)
    probs = jax.nn.softmax(jax.random.normal(key, (b, s, E)), axis=-1)
    gates, idx = jax.lax.top_k(probs, K)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

    # ample capacity: every (token, k) choice must land
    C = s * K
    D, W = moe_dispatch(idx, gates, E, C)
    D, W = np.asarray(D), np.asarray(W)
    # each token occupies exactly K slots, all with weight summing to 1
    np.testing.assert_allclose(D.sum(axis=(2, 3)), K)
    np.testing.assert_allclose(W.sum(axis=(2, 3)), 1.0, rtol=1e-6)
    # a slot holds at most one token (no double booking)
    assert D.sum(axis=1).max() <= 1.0 + 1e-6
    # the slot a token got carries exactly its gate for that expert
    for bi in range(b):
        for si in range(s):
            for k in range(K):
                e = int(idx[bi, si, k])
                w_slot = W[bi, si, e].sum()
                np.testing.assert_allclose(w_slot, gates[bi, si, k],
                                           rtol=1e-6)

    # capacity 1: each expert accepts exactly min(assigned, 1) tokens
    D1, _ = moe_dispatch(idx, gates, E, 1)
    per_expert = np.asarray(D1).sum(axis=(1, 3))  # [b, E]
    assert per_expert.max() <= 1.0 + 1e-6
    # and drops really happen (s*K >> E slots)
    assert np.asarray(D1).sum() < np.asarray(D).sum()

    cfg = _cfg(moe_capacity_factor=8.0)
    assert moe_capacity(cfg, 32) == int(np.ceil(2 * 32 * 8.0 / 4))


def test_moe_forward_finite_and_aux_sane():
    cfg = _cfg(moe_capacity_factor=8.0)  # ample: nothing drops
    params = moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))
    y, aux = moe_apply(params, x, cfg)
    assert y.shape == x.shape
    assert np.isfinite(np.asarray(y)).all()
    # aux near its balanced value E * sum(f*p) ~ 1 for a random router
    assert 0.5 < float(aux) < 4.0


class TestSortDispatch:
    """The sort-based dispatch (default) against the dense GShard oracle:
    identical routing semantics (k-round priority, in-round sequence
    priority, capacity drops), matching forward AND gradients, with
    dispatch memory linear in s instead of quadratic."""

    @pytest.mark.parametrize("cap", [8.0, 0.5])  # ample / forces drops
    def test_forward_and_grads_match_dense(self, cap):
        cfg_s = _cfg(moe_capacity_factor=cap, moe_dispatch="sort")
        cfg_d = dataclasses.replace(cfg_s, moe_dispatch="dense")
        params = moe_init(jax.random.PRNGKey(0), cfg_s)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))

        def run(cfg):
            def f(p, xx):
                y, aux = moe_apply(p, xx, cfg)
                return jnp.sum(y * y) + aux
            val, grads = jax.value_and_grad(f)(params, x)
            y, _ = moe_apply(params, x, cfg)
            return y, val, grads

        y_s, v_s, g_s = run(cfg_s)
        y_d, v_d, g_d = run(cfg_d)
        np.testing.assert_allclose(np.asarray(y_s), np.asarray(y_d),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(float(v_s), float(v_d), rtol=1e-5)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5), g_s, g_d)

    def test_dispatch_memory_linear_in_s(self):
        """Doubling s must not ~4x the jitted temp footprint (the dense
        [b,s,E,C] tensor does exactly that; sort is O(sK))."""
        def temp_bytes(cfg, s):
            params = moe_init(jax.random.PRNGKey(0), cfg)
            x = jnp.zeros((1, s, cfg.hidden_size))
            f = jax.jit(lambda p, xx: moe_apply(p, xx, cfg)[0])
            m = f.lower(params, x).compile().memory_analysis()
            return m.temp_size_in_bytes

        # E=32 so the dense dispatch tensor dominates temp at small h
        big = _cfg(num_experts=32, moe_top_k=2, moe_capacity_factor=4.0)
        s0, s1 = 512, 2048
        sort_ratio = (temp_bytes(big, s1)
                      / max(temp_bytes(big, s0), 1))
        dense_ratio = (
            temp_bytes(dataclasses.replace(big, moe_dispatch="dense"), s1)
            / max(temp_bytes(
                dataclasses.replace(big, moe_dispatch="dense"), s0), 1))
        assert sort_ratio < 6.0, sort_ratio        # ~linear (4x s)
        assert dense_ratio > 10.0, dense_ratio     # ~quadratic
        assert sort_ratio < dense_ratio / 2

    def test_slot_assignment_matches_dense_bookkeeping(self):
        """Token-level check against moe_dispatch's one-hots: same kept
        set, same expert slots, at a capacity that forces drops."""
        from megatron_tpu.models.moe import _sort_route, moe_dispatch
        s, E, K, C = 32, 4, 2, 5
        probs = jax.nn.softmax(
            jax.random.normal(jax.random.PRNGKey(3), (1, s, E)), axis=-1)
        gates, idx = jax.lax.top_k(probs, K)
        D, _ = moe_dispatch(idx, gates, E, C)   # [1, s, E, C]
        D = np.asarray(D[0])
        e, tok, g, pos, keep = map(
            np.asarray, _sort_route(idx[0], gates[0], E, C))
        for j in range(K * s):
            if keep[j]:
                assert D[tok[j], e[j], pos[j]] == 1.0, j
            else:
                # dense dropped it too: that token has no slot at e[j]
                assert D[tok[j], e[j]].sum() == 0.0, j


def test_single_expert_equals_dense_mlp():
    from megatron_tpu.models.mlp import mlp_apply
    cfg = _cfg(num_experts=1, moe_top_k=1)
    # build the MoE with E=1 manually (config validate would route to
    # the dense MLP; this checks the math degenerates correctly)
    cfg_moe = dataclasses.replace(cfg, num_experts=1, moe_top_k=1,
                                  moe_capacity_factor=1.0)
    params = moe_init(jax.random.PRNGKey(0), cfg_moe)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 64))
    y, aux = moe_apply(params, x, cfg_moe)
    # an expert's GLU matrix is [h, 2f] (gate, then value columns), the
    # dense MLP's [h, 2, f]
    dense_params = {"w1": params["w1"][0].reshape(64, 2, -1),
                    "w2": params["w2"][0]}
    y_dense = mlp_apply(dense_params, x, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_dense),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(float(aux), 1.0, rtol=1e-5)  # E*1*1


def test_glu_expert_shapes():
    cfg = _cfg(activation="swiglu")
    params = moe_init(jax.random.PRNGKey(0), cfg)
    assert params["w1"].shape == (4, 64, 2 * 96)  # gate, then value
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 64))
    y, _ = moe_apply(params, x, cfg)
    assert y.shape == x.shape
    # axes align leaf-for-leaf with params
    jax.tree.map(lambda p, a: None, params, moe_axes(cfg),
                 is_leaf=lambda t: isinstance(t, tuple))


def test_moe_model_trains_and_aux_flows():
    from megatron_tpu.models.language_model import loss_fn, model_init
    cfg = _cfg(activation="swiglu")
    params = model_init(jax.random.PRNGKey(0), cfg)
    # expert bank exists in the stacked tree
    assert params["transformer"]["mlp"]["router"].shape == (2, 64, 4)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, 128)

    @jax.jit
    def step(params):
        loss, g = jax.value_and_grad(loss_fn)(params, tokens, cfg)
        return jax.tree.map(lambda p, gg: p - 0.05 * gg, params, g), loss, g

    losses = []
    for _ in range(15):
        params, loss, g = step(params)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.2, losses
    # aux loss reaches the router: its grads are nonzero
    g_router = np.asarray(g["transformer"]["mlp"]["router"])
    assert np.abs(g_router).max() > 0


def test_biased_experts_match_biased_dense():
    """use_bias must reach the expert bank (gpt2-style configs), not be
    silently dropped: E=1 biased MoE == biased dense MLP."""
    from megatron_tpu.models.mlp import mlp_apply
    cfg = _cfg(num_experts=1, moe_top_k=1, moe_capacity_factor=1.0,
               use_bias=True, activation="gelu", use_rotary_emb=False,
               use_position_embedding=True)
    params = moe_init(jax.random.PRNGKey(0), cfg)
    # nonzero biases so the equality actually tests them
    params["b1"] = jax.random.normal(jax.random.PRNGKey(2),
                                     params["b1"].shape) * 0.1
    params["b2"] = jax.random.normal(jax.random.PRNGKey(3),
                                     params["b2"].shape) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 32, 64))
    y, _ = moe_apply(params, x, cfg)
    dense = {"w1": params["w1"][0], "w2": params["w2"][0],
             "b1": params["b1"][0], "b2": params["b2"][0]}
    y_dense = mlp_apply(dense, x, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_dense),
                               rtol=2e-5, atol=2e-5)


def test_moe_requires_experts_divisible_by_tp():
    from megatron_tpu.config import (MegatronConfig, ParallelConfig,
                                     TrainingConfig)
    with pytest.raises(AssertionError, match="shard evenly"):
        MegatronConfig(
            model=_cfg(num_experts=6, moe_top_k=2),
            parallel=ParallelConfig(tensor_parallel=4),
            training=TrainingConfig(micro_batch_size=2,
                                    global_batch_size=4),
        ).validate(n_devices=8)


def test_mixtral_preset_dropless_capacity_tracks_overrides():
    """The dropless capacity default must be computed from the FINAL
    num_experts/moe_top_k (post-overrides), and an explicit
    capacity_factor must win."""
    from megatron_tpu.config import mixtral_config
    assert mixtral_config("8x7b").moe_capacity_factor == 8 / 2
    assert mixtral_config("8x7b", moe_top_k=1).moe_capacity_factor == 8 / 1
    assert mixtral_config("tiny", num_experts=8).moe_capacity_factor == 8 / 2
    assert mixtral_config("8x7b",
                          moe_capacity_factor=1.25).moe_capacity_factor == 1.25
    # the real weights support 32k positions even at the 4096 default seq
    assert mixtral_config("8x7b").max_position_embeddings == 32768
    with pytest.raises(ValueError, match="unknown mixtral size"):
        mixtral_config("7b")


def test_moe_pp2_validates():
    """The pp=1 restriction is lifted: router aux threads through every
    pipeline schedule (parallel/pipeline.py _chunk_ret)."""
    from megatron_tpu.config import (MegatronConfig, ParallelConfig,
                                     TrainingConfig)
    MegatronConfig(
        model=_cfg(num_layers=4),
        parallel=ParallelConfig(pipeline_parallel=2),
        training=TrainingConfig(micro_batch_size=1, global_batch_size=4),
    ).validate(n_devices=8)


def test_moe_pp_with_split_expert_axis_rejected():
    """pp>1 + a SPLIT expert axis must fail in validate() (a python
    error), never reach the XLA partitioner CHECK (a hard SIGABRT —
    docs/parallelism.md). Covers tp-split, dp-split, and the
    underivable-dp bypass."""
    from megatron_tpu.config import (MegatronConfig, ParallelConfig,
                                     TrainingConfig)

    def build(par):
        return MegatronConfig(
            model=_cfg(num_layers=4),
            parallel=par,
            training=TrainingConfig(micro_batch_size=1,
                                    global_batch_size=4))

    with pytest.raises(AssertionError, match="partitioner CHECK"):
        build(ParallelConfig(pipeline_parallel=2,
                             tensor_parallel=2)).validate(n_devices=8)
    with pytest.raises(AssertionError, match="partitioner CHECK"):
        build(ParallelConfig(pipeline_parallel=2, expert_axis="dp")
              ).validate(n_devices=8)  # dp derives to 4
    # unknown dp cannot silently pass as 1 (validate() without
    # n_devices is a supported pattern)
    with pytest.raises(AssertionError, match="dp known at validate"):
        build(ParallelConfig(pipeline_parallel=2, expert_axis="dp")
              ).validate()
    # pp>1 with the expert axis unsplit stays accepted
    build(ParallelConfig(pipeline_parallel=2, expert_axis="dp",
                         data_parallel=1)).validate()


@pytest.mark.slow
class TestMoEPipelined:
    """MoE inside pipeline chunks: pp2 loss AND grads must equal the
    sequential (pp=1) model — aux included — for both 1F1B modes, the
    interleaved vpp2 variant, and the lockstep gpipe schedule."""

    def _setup(self):
        from megatron_tpu.config import ModelConfig
        from megatron_tpu.models.language_model import loss_fn, model_init
        cfg = _cfg(num_layers=4, moe_capacity_factor=8.0,
                   attention_impl="dot")
        params = model_init(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 2, 33),
                                    0, 128)
        flat = tokens.reshape(8, 33)

        def seq_loss(p):
            per_mb = [loss_fn(p, tokens[i], cfg) for i in range(4)]
            return sum(per_mb) / 4
        want_loss, want_grads = jax.value_and_grad(seq_loss)(params)
        return cfg, params, tokens, want_loss, want_grads

    @pytest.mark.parametrize("mode", ["recompute", "store", "vpp2",
                                      "gpipe"])
    def test_pp2_matches_sequential(self, devices, mode):
        from conftest import make_test_mesh
        from megatron_tpu.parallel.pipeline import (gpt_1f1b_fns,
                                                    gpt_1f1b_streams,
                                                    pipeline_loss_fn,
                                                    pipeline_train_1f1b)
        cfg, params, tokens, want_loss, want_grads = self._setup()
        mesh = make_test_mesh(devices, pp=2)
        with jax.set_mesh(mesh):
            if mode == "gpipe":
                def f(p):
                    return pipeline_loss_fn(p, tokens, cfg, mesh)
                loss, grads = jax.jit(
                    jax.value_and_grad(f))(params)
            else:
                streams = gpt_1f1b_streams(tokens, cfg)
                intake, chunk, head = gpt_1f1b_fns(cfg)

                def f(p):
                    return pipeline_train_1f1b(
                        p, streams, cfg, mesh, intake_fn=intake,
                        chunk_fn=chunk, head_loss_fn=head,
                        batch_shape=(2, 32),
                        store_activations=(mode == "store"),
                        vpp=2 if mode == "vpp2" else 1)
                loss, grads = jax.jit(f)(params)
        np.testing.assert_allclose(float(loss), float(want_loss),
                                   rtol=2e-5)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5),
            grads, want_grads)


def test_moe_greedy_decode_matches_full_forward():
    """MoE through the KV-cache decode loop: per-token routing is
    position-independent, so cached greedy decode must equal the
    no-cache argmax oracle exactly (same contract as the dense model,
    tests/test_inference.py)."""
    from megatron_tpu.inference import Generator, SamplingParams
    from megatron_tpu.models import language_model as lm
    cfg = _cfg(activation="swiglu", vocab_size=96,
               make_vocab_size_divisible_by=32, seq_length=64,
               max_position_embeddings=64)
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    gen = Generator(params, cfg, eos_id=0, pad_id=0)
    prompt = [5, 17, 3, 42]
    tokens, _, _ = gen.generate([prompt], 8,
                                sampling=SamplingParams(temperature=0.0))
    rope = lm.make_rope(cfg)
    seq = list(prompt)
    for _ in range(8):
        logits, _ = lm.model_forward(params, jnp.asarray([seq]), cfg,
                                     rope=rope)
        nxt = int(jnp.argmax(logits[0, -1, :cfg.vocab_size]))
        seq.append(nxt)
        if nxt == 0:
            break
    np.testing.assert_array_equal(np.asarray(tokens[0, :len(seq)]),
                                  np.asarray(seq))


def test_moe_checkpoint_roundtrip(tmp_path):
    """The expert bank rides the generic pytree checkpoint path: save,
    restore, bit-identical params incl. router and per-expert weights."""
    from megatron_tpu.config import (MegatronConfig, OptimizerConfig,
                                     TrainingConfig)
    from megatron_tpu.training import checkpointing as ckpt
    from megatron_tpu.training import init_train_state

    cfg = MegatronConfig(
        model=_cfg(activation="swiglu"),
        optimizer=OptimizerConfig(lr=1e-3),
        training=TrainingConfig(micro_batch_size=1, global_batch_size=1,
                                train_iters=1),
    ).validate(n_devices=1)
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    ckpt.save_checkpoint(str(tmp_path), state, cfg, iteration=3)
    restored, it, _ = ckpt.load_checkpoint(str(tmp_path), state)
    assert it == 3
    for a, b in zip(jax.tree.leaves(state.params),
                    jax.tree.leaves(restored.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_moe_tp_expert_parallel_matches_single(devices):
    """Expert parallelism IS the 'experts'-axis tp sharding: loss under
    tp2 (2 experts per device) must match the single-device run."""
    from megatron_tpu.config import (MegatronConfig, OptimizerConfig,
                                     ParallelConfig, TrainingConfig)
    from megatron_tpu.parallel.mesh import build_mesh
    from megatron_tpu.training import init_train_state, make_train_step

    losses = {}
    for tp in (1, 2):
        cfg = MegatronConfig(
            model=_cfg(activation="swiglu", compute_dtype="bfloat16"),
            optimizer=OptimizerConfig(lr=1e-3, clip_grad=1.0,
                                      optimizer="sgd"),
            parallel=ParallelConfig(tensor_parallel=tp),
            training=TrainingConfig(micro_batch_size=tp,
                                    global_batch_size=8, train_iters=2),
        ).validate(n_devices=8)
        mesh = build_mesh(cfg.parallel)
        state = init_train_state(jax.random.PRNGKey(0), cfg)
        step = make_train_step(cfg, mesh=mesh, donate=False)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 8, 33), 0,
                                    128)
        batch = {"tokens": tokens,
                 "loss_mask": jnp.ones((1, 8, 32), jnp.float32)}
        for i in range(2):
            state, m = step(state, batch, jax.random.fold_in(
                jax.random.PRNGKey(0), i))
        losses[tp] = float(m["lm_loss"])
    np.testing.assert_allclose(losses[2], losses[1], rtol=5e-3)


def test_moe_dp_expert_axis_with_zero1_shardings(devices):
    """expert_axis='dp' + ZeRO-1: the bank's experts dim already carries
    'dp', so distributed_opt_sharding must NOT add 'dp' to a second dim
    (DuplicateSpecError regression, round-5 review)."""
    from megatron_tpu.config import (MegatronConfig, OptimizerConfig,
                                     ParallelConfig, TrainingConfig)
    from megatron_tpu.models.language_model import model_init
    from megatron_tpu.parallel.mesh import build_mesh
    from megatron_tpu.training.train_step import state_shardings

    cfg = MegatronConfig(
        model=_cfg(activation="swiglu"),
        parallel=ParallelConfig(data_parallel=2, expert_axis="dp",
                                use_distributed_optimizer=True),
        training=TrainingConfig(micro_batch_size=4, global_batch_size=8),
    ).validate(n_devices=2)
    mesh = build_mesh(cfg.parallel, devices=jax.devices()[:2])
    shapes = jax.eval_shape(
        lambda: model_init(jax.random.PRNGKey(0), cfg.model))
    sh = state_shardings(cfg, mesh, shapes)  # raised before the fix
    mu_w1 = sh.opt_state.mu["transformer"]["mlp"]["w1"]
    assert [a for a in mu_w1.spec if a == "dp"] == ["dp"]


@pytest.mark.slow
def test_moe_dp_expert_parallel_matches_single(devices):
    """expert_axis='dp' (GShard-style EP over the data axis): dp2 with
    the expert bank dp-sharded must match the dp1/tp1 run."""
    from megatron_tpu.config import (MegatronConfig, OptimizerConfig,
                                     ParallelConfig, TrainingConfig)
    from megatron_tpu.parallel.mesh import build_mesh
    from megatron_tpu.training import init_train_state, make_train_step

    losses = {}
    for dp in (1, 2):
        cfg = MegatronConfig(
            model=_cfg(activation="swiglu", compute_dtype="bfloat16"),
            optimizer=OptimizerConfig(lr=1e-3, clip_grad=1.0,
                                      optimizer="sgd"),
            parallel=ParallelConfig(data_parallel=dp, expert_axis="dp"),
            training=TrainingConfig(micro_batch_size=8 // dp,
                                    global_batch_size=8, train_iters=2),
        ).validate(n_devices=dp)
        mesh = build_mesh(cfg.parallel, devices=jax.devices()[:dp])
        state = init_train_state(jax.random.PRNGKey(0), cfg)
        step = make_train_step(cfg, mesh=mesh, donate=False)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 8, 33), 0,
                                    128)
        batch = {"tokens": tokens,
                 "loss_mask": jnp.ones((1, 8, 32), jnp.float32)}
        for i in range(2):
            state, m = step(state, batch, jax.random.fold_in(
                jax.random.PRNGKey(0), i))
        losses[dp] = float(m["lm_loss"])
    np.testing.assert_allclose(losses[2], losses[1], rtol=5e-3)


def test_mixtral_preset_generates_end_to_end():
    """Flagship composition: the mixtral-tiny preset (MoE + GQA + RoPE
    theta 1e6 + dropless capacity) decodes greedily through the KV cache,
    and adding a sliding window (banded attention + rolling cache)
    composes with the expert bank."""
    from megatron_tpu.config import mixtral_config
    from megatron_tpu.inference import Generator, SamplingParams
    from megatron_tpu.models.language_model import model_init

    for window in (None, 24):
        cfg = mixtral_config(
            "tiny", num_layers=2, hidden_size=64, num_attention_heads=4,
            num_kv_heads=2, ffn_hidden_size=96, vocab_size=96,
            seq_length=128, make_vocab_size_divisible_by=32,
            sliding_window=window, compute_dtype="float32")
        params = model_init(jax.random.PRNGKey(0), cfg)
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        toks, lens, lp = gen.generate(
            [[5, 17, 3, 42]], 30, sampling=SamplingParams(temperature=0.0))
        assert np.isfinite(np.asarray(lp)).all(), f"window={window}"
        region = np.asarray(toks)[0, 4:int(lens[0])]
        assert (region >= 0).all() and (region < 96).all()
