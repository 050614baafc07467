"""A step of several micro-batches sums each weight gradient into its float32
accumulator inside the backward pass (`ops/grad_accum.py`): the step's
results are those of the plain sum, and the pass that added a stacked
gradient to a carried accumulator is gone from the program.

The reference is written here: per micro-batch `jax.grad`, a Python sum in
float32, `apply_optimizer`. `unfused_step(..., loop="scan")` is the step as
it was before (`lax.scan` over the micro-batches, `acc + grads` in its body):
what the test of the program's structure has to be able to see.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.config import (MODEL_PRESETS, MegatronConfig, ModelConfig,
                                 OptimizerConfig, TrainingConfig)
from megatron_tpu.models import language_model as lm
from megatron_tpu.models import transformer as tfm
from megatron_tpu.models.norms import apply_norm
from megatron_tpu.training import optimizer as opt
from megatron_tpu.training import scheduler
from megatron_tpu.training.train_step import (TrainState, state_from_params,
                                              train_step)
from megatron_tpu.utils import tracing

N_MICRO, SEQ, VOCAB = 4, 16, 64
PATTERN = ("conv", "full_attention", "conv", "full_attention", "conv", "conv")


def _preset(name, **over):
    return dataclasses.replace(
        MODEL_PRESETS[name](), vocab_size=VOCAB, seq_length=SEQ,
        compute_dtype="float32", tie_embed_logits=True, **over).derived()


def _model(case) -> ModelConfig:
    """Two layers (more where a pattern needs them), a tied head, float32."""
    if case == "moe":
        return _preset("olmoe-tiny", moe_aux_loss_coeff=0.01)
    if case == "period":            # window, window, window, full: x 2
        return _preset("command-a-plus-tiny", num_layers=8)
    if case == "pattern":           # a dense layer; two periods and a tail
        return _preset("lfm2-8b-a1b-tiny", num_layers=len(PATTERN),
                       first_k_dense_replace=1, layer_types=PATTERN)
    return ModelConfig(
        num_layers=2, hidden_size=32, num_attention_heads=2,
        vocab_size=VOCAB, seq_length=SEQ,
        compute_dtype="float16" if case == "fp16" else "float32",
        recompute_granularity="full" if case == "recompute" else None,
    ).derived()


def _own_head_loss(mcfg):
    """A loss of the caller's own, as `pretrain_bert.py` has one: its stack
    goes through `transformer.py`'s scan, its table and head are used here."""
    rope = lm.make_rope(mcfg)

    def loss(params, mb, rng):
        x = params["embedding"]["word_embeddings"][mb["tokens"][:, :-1]]
        x, _, _ = tfm.stack_apply(params["transformer"], x, mcfg,
                                  rope_cos=rope.cos, rope_sin=rope.sin)
        x = apply_norm(mcfg.norm_type, params["final_norm"], x,
                       mcfg.norm_epsilon)
        logp = jax.nn.log_softmax(x @ params["own_head"])
        picked = jnp.take_along_axis(
            logp, mb["tokens"][:, 1:, None], axis=-1)[..., 0]
        return -jnp.mean(picked)
    return loss


def _setup(case, n_micro=N_MICRO):
    mcfg = _model(case)
    cfg = MegatronConfig(
        model=mcfg,
        optimizer=OptimizerConfig(lr=1e-3, initial_loss_scale=2.0 ** 10),
        training=TrainingConfig(micro_batch_size=1, global_batch_size=n_micro,
                                train_iters=4)).validate(n_devices=1)
    params = lm.model_init(jax.random.PRNGKey(0), mcfg)
    loss_fn = None
    if case == "custom_loss":
        params = dict(params, own_head=0.1 * jax.random.normal(
            jax.random.PRNGKey(5), (mcfg.hidden_size, VOCAB)))
        loss_fn = _own_head_loss(mcfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1),
                                (n_micro, 1, SEQ + 1), 0, VOCAB - 1)
    if case == "fp16":
        # one micro-batch overflows: it alone holds the last token, whose
        # embedding is past float16's range
        table = params["embedding"]["word_embeddings"]
        params["embedding"]["word_embeddings"] = table.at[VOCAB - 1].set(1e5)
        tokens = tokens.at[2, 0, 3].set(VOCAB - 1)
    batch = {"tokens": tokens,
             "loss_mask": jnp.ones((n_micro, 1, SEQ), jnp.float32)}
    return cfg, state_from_params(params, cfg), batch, loss_fn


def _micro_loss(cfg, loss_fn, scale, n_micro):
    rope = lm.make_rope(cfg.model)

    def micro_loss(params, mb, rng):
        if loss_fn is not None:
            loss = loss_fn(params, mb, rng)
        else:
            loss = lm.loss_fn(params, mb["tokens"], cfg.model,
                              loss_mask=mb["loss_mask"], rope=rope, rng=rng)
        return loss * scale / n_micro, loss
    return jax.value_and_grad(micro_loss, has_aux=True)


def unfused_step(state, batch, rng, cfg, loss_fn=None, loop="python"):
    n_micro = batch["tokens"].shape[0]
    grad_fn = _micro_loss(cfg, loss_fn, state.opt_state.scaler.scale, n_micro)

    def add(acc, mb, i):
        grads_acc, loss_acc = acc
        (_, loss), grads = grad_fn(state.params, mb, jax.random.fold_in(rng, i))
        return jax.tree.map(lambda a, g: a + g.astype(jnp.float32),
                            grads_acc, grads), loss_acc + loss

    acc = (jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                        state.params), jnp.zeros((), jnp.float32))
    if loop == "scan":
        acc, _ = jax.lax.scan(lambda acc, xs: (add(acc, *xs), None), acc,
                              (batch, jnp.arange(n_micro)))
    else:
        for i in range(n_micro):
            acc = add(acc, jax.tree.map(lambda x: x[i], batch), i)
    grads, loss_sum = acc
    lr = scheduler.learning_rate(state.iteration, cfg.optimizer, cfg.training)
    wd = scheduler.weight_decay(state.iteration, cfg.optimizer, cfg.training)
    params, opt_state, metrics = opt.apply_optimizer(
        state.params, grads, state.opt_state, cfg.optimizer, lr, wd)
    return (TrainState(params, opt_state, state.iteration + 1),
            {"lm_loss": loss_sum / n_micro, **metrics})


def _flat(tree):
    return {jax.tree_util.keystr(k): np.asarray(v)
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}


CASES = ("dense", "moe", "period", "pattern", "recompute", "fp16",
         "custom_loss")


@pytest.mark.parametrize("case", CASES)
def test_the_step_returns_what_the_plain_sum_returns(case):
    cfg, state, batch, loss_fn = _setup(case)
    rng = jax.random.PRNGKey(2)
    new, metrics = jax.jit(functools.partial(
        train_step, cfg=cfg, loss_fn=loss_fn))(state, batch, rng)
    want, want_metrics = jax.jit(functools.partial(
        unfused_step, cfg=cfg, loss_fn=loss_fn))(state, batch, rng)

    for key in ("lm_loss", "grad_norm", "found_inf"):
        np.testing.assert_allclose(metrics[key], want_metrics[key],
                                   rtol=1e-6, err_msg=key)
    assert int(metrics["found_inf"]) == (case == "fp16")
    got, ref, old = (_flat((s.params, s.opt_state.mu, s.opt_state.nu))
                     for s in (new, want, state))
    for name, value in got.items():
        if case == "fp16":                  # a skipped step: state unchanged
            np.testing.assert_array_equal(value, old[name], err_msg=name)
        elif "word_embeddings" in name or case == "pattern":
            # the table's gradient has two producers (the head's product
            # and the lookup's scatter), added in the compiler's order:
            # 1e-6 of the leaf's size, where the two nearly cancel too.
            # The pattern's tail layer is joined outside a loop, where the
            # CPU's compiler folds `acc + dW` into the experts' product
            # (those rows alone differ, in the last bit). A moment to 1e-6
            # of the leaf's size; a parameter to a thousandth of a step
            # of Adam's, which divides a gradient by its own size
            atol = (1e-3 * cfg.optimizer.lr if name.startswith("[0]")
                    else 1e-6 * np.abs(ref[name]).max())
            np.testing.assert_allclose(value, ref[name], rtol=1e-6,
                                       atol=atol, err_msg=name)
        else:
            # every other leaf has one, and its sum is the same sum
            np.testing.assert_array_equal(value, ref[name], err_msg=name)
    fused = tracing.startup_scalars()["grad_accum_fused_share"]
    if case == "custom_loss":
        # the stack went through the scan; the table, the final norm and
        # the head are the loss's own and were added at the top
        stack = sum(4 * x.size for x in jax.tree.leaves(
            state.params["transformer"]))
        every = sum(4 * x.size for x in jax.tree.leaves(state.params))
        assert fused == pytest.approx(stack / every) and 0.0 < fused < 1.0
    else:
        assert fused == 1.0


def test_a_sharded_step_sums_what_one_device_sums():
    """dp 2 x tp 2 with sequence parallelism and ZeRO-1: GSPMD reduces a
    micro-batch's gradient over `dp` on its way into the accumulator, which
    keeps the parameters' sharding."""
    from megatron_tpu.config import ParallelConfig
    from megatron_tpu.parallel import mesh as mesh_mod
    from megatron_tpu.training import make_train_step
    from megatron_tpu.training.train_step import state_shardings
    cfg, state, batch, _ = _setup("dense")
    batch = jax.tree.map(lambda x: jnp.concatenate([x, x[::-1]], axis=1),
                         batch)                     # two rows a micro-batch
    want, want_metrics = jax.jit(functools.partial(
        unfused_step, cfg=cfg))(state, batch, jax.random.PRNGKey(2))
    cfg = dataclasses.replace(
        cfg, parallel=ParallelConfig(
            tensor_parallel=2, sequence_parallel=True,
            use_distributed_optimizer=True),
        training=dataclasses.replace(cfg.training, global_batch_size=8),
    ).validate(n_devices=4)
    mesh = mesh_mod.build_mesh(cfg.parallel, devices=jax.devices()[:4])
    sharded = jax.device_put(state, state_shardings(cfg, mesh, state.params))
    new, metrics = make_train_step(cfg, mesh=mesh, donate=False)(
        sharded, batch, jax.random.PRNGKey(2))
    assert tracing.startup_scalars()["grad_accum_fused_share"] == 1.0
    for key in ("lm_loss", "grad_norm"):
        np.testing.assert_allclose(metrics[key], want_metrics[key],
                                   rtol=1e-5, err_msg=key)
    ref = _flat(want.opt_state.mu)
    for name, value in _flat(new.opt_state.mu).items():
        np.testing.assert_allclose(value, ref[name], rtol=1e-4,
                                   atol=1e-5 * np.abs(ref[name]).max(),
                                   err_msg=name)


# ---------------------------------------------------------------------
# by structure
# ---------------------------------------------------------------------
def _walk(jaxpr):
    """Every equation, with the jaxpr that holds it."""
    for eqn in jaxpr.eqns:
        yield jaxpr, eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)


def accumulate_passes(jaxpr, shapes):
    """The `add`s inside a scan's body whose one operand is that scan's
    carry and whose other is what a scan inside the body stacked, both of a
    parameter's shape: a stacked gradient added to a carried accumulator."""
    found = []
    for _, outer in _walk(jaxpr):
        if outer.primitive.name != "scan":
            continue
        body = outer.params["jaxpr"].jaxpr
        n_consts, n_carry = outer.params["num_consts"], outer.params["num_carry"]
        carried = set(body.invars[n_consts:n_consts + n_carry])
        stacked = {}                          # var -> itself, through casts
        for eqn in body.eqns:
            if eqn.primitive.name == "scan":
                for v in eqn.outvars[eqn.params["num_carry"]:]:
                    stacked[v] = v
            elif eqn.primitive.name == "convert_element_type" \
                    and eqn.invars[0] in stacked:
                stacked[eqn.outvars[0]] = eqn.invars[0]
            elif eqn.primitive.name in ("add", "add_any"):
                a, b = eqn.invars
                if tuple(eqn.outvars[0].aval.shape) in shapes and (
                        (a in carried and b in stacked)
                        or (b in carried and a in stacked)):
                    found.append(eqn)
    return found


def zero_trees(jaxpr, shapes):
    """The float32 zeros of a parameter's shape the program makes."""
    return [eqn for _, eqn in _walk(jaxpr)
            if eqn.primitive.name == "broadcast_in_dim"
            and eqn.outvars[0].aval.dtype == jnp.float32
            and tuple(eqn.outvars[0].aval.shape) in shapes
            and getattr(eqn.invars[0], "val", None) == 0.0]


def _stacked_shapes(params):
    return {tuple(x.shape) for x in jax.tree.leaves(params["transformer"])}


def test_no_stacked_gradient_is_added_to_a_carried_accumulator():
    cfg, state, batch, _ = _setup("dense")
    rng = jax.random.PRNGKey(2)
    shapes = _stacked_shapes(state.params)
    n_stacked = len(jax.tree.leaves(state.params["transformer"]))

    before = jax.make_jaxpr(functools.partial(
        unfused_step, cfg=cfg, loop="scan"))(state, batch, rng)
    assert len(accumulate_passes(before.jaxpr, shapes)) == n_stacked

    after = jax.make_jaxpr(functools.partial(train_step, cfg=cfg))(
        state, batch, rng)
    assert accumulate_passes(after.jaxpr, shapes) == []
    assert tracing.startup_scalars()["grad_accum_fused_share"] == 1.0


def test_one_micro_batch_keeps_no_accumulator():
    cfg, state, batch, _ = _setup("dense", n_micro=1)
    rng = jax.random.PRNGKey(2)
    # of the stacks' shapes: the table's has zeros of its own in any
    # backward pass, which the lookup's gradient is scattered into
    shapes = _stacked_shapes(state.params)
    step = jax.make_jaxpr(functools.partial(train_step, cfg=cfg))(
        state, batch, rng)
    assert zero_trees(step.jaxpr, shapes) == []
    assert tracing.startup_scalars()["grad_accum_fused_share"] == 0.0
    # what the reader above can see: the step as it was makes one a leaf
    before = jax.make_jaxpr(functools.partial(
        unfused_step, cfg=cfg, loop="scan"))(state, batch, rng)
    assert len(zero_trees(before.jaxpr, shapes)) >= len(
        jax.tree.leaves(state.params["transformer"]))

    new, metrics = jax.jit(functools.partial(train_step, cfg=cfg))(
        state, batch, rng)
    want, want_metrics = jax.jit(functools.partial(
        unfused_step, cfg=cfg))(state, batch, rng)
    np.testing.assert_array_equal(metrics["lm_loss"], want_metrics["lm_loss"])
    for name, value in _flat(new.params).items():
        np.testing.assert_array_equal(value, _flat(want.params)[name],
                                      err_msg=name)
