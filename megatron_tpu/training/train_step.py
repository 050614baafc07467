"""The jitted training step: microbatch accumulation + optimizer apply.

TPU-native equivalent of train_step + the no-pipelining forward-backward
schedule (ref: megatron/training.py:391-449, megatron/schedules.py:213-250).
The reference's step is an imperative pipeline —
zero grad buffers -> per-microbatch fwd/bwd accumulating into `main_grad`
buffers -> reduce_model_grads (DP allreduce) -> optimizer.step -> lr step.
Here the same dataflow is one jitted function:

- microbatch loop = `lax.scan` over the leading microbatch dim, whose carry
  is the fp32 gradient accumulators (== the contiguous main_grad buffer of
  model/distributed.py:75-171 without the buffer bookkeeping). A
  micro-batch's gradients are not added to them after its backward pass:
  the accumulators go down into it (ops/grad_accum.py), each weight
  gradient is summed into its accumulator where the product writes it, and
  what the micro-batch's `grad` hands back ARE the new accumulators (==
  the reference's --gradient_accumulation_fusion, "dW += dY^T X" into
  main_grad). A leaf whose forward takes no accumulator with it is added
  here, as a pass of its own. A step of one micro-batch keeps no
  accumulator: its gradients are the step's;
- the DP grad all-reduce (ref: distributed.py:202-232) is emitted by GSPMD
  because batch activations are 'dp'-sharded while params are replicated;
- loss scaling per microbatch matches schedules.py:176-186
  (loss * scale / num_microbatches);
- lr/wd come from the pure scheduler, optimizer apply from
  training/optimizer.py with identical skip-on-inf semantics.

Pipeline-parallel steps replace the scan body with the 1F1B schedule from
megatron_tpu/parallel/pipeline.py; everything else is unchanged.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from megatron_tpu.config import MegatronConfig
from megatron_tpu.models import language_model as lm
from megatron_tpu.ops import grad_accum
from megatron_tpu.training import optimizer as opt
from megatron_tpu.training import scheduler
from megatron_tpu.utils import tracing


class TrainState(NamedTuple):
    params: Any
    opt_state: opt.OptState
    iteration: jax.Array  # i32: completed iterations (incl. skipped)


def state_from_params(params, cfg: MegatronConfig) -> TrainState:
    """Fresh TrainState around an existing param tree (any model family).
    fp16 compute seeds the dynamic loss scaler (ref: Float16Optimizer
    grad-scaler wiring, optimizer.py:469-530)."""
    return TrainState(
        params=params,
        opt_state=opt.init_optimizer(
            params, cfg.optimizer,
            compute_dtype=jnp.float16
            if cfg.model.compute_dtype == "float16" else jnp.float32),
        iteration=jnp.zeros((), jnp.int32),
    )


def init_train_state(rng, cfg: MegatronConfig) -> TrainState:
    return state_from_params(lm.model_init(rng, cfg.model), cfg)


def train_step(
    state: TrainState,
    batch: dict,
    rng,
    cfg: MegatronConfig,
    rope: Optional[lm.RopeTables] = None,
    wd_mask=None,
    loss_fn=None,
):
    """One full iteration over `num_microbatches` microbatches.

    batch: {"tokens": [n_micro, micro_bs, seq+1] int32,
            "loss_mask": optional [n_micro, micro_bs, seq] }
    Returns (new_state, metrics).
    """
    mcfg = cfg.model
    # any leaf's leading dim is the microbatch count (custom losses may
    # have no "tokens" key — e.g. T5's text_enc/text_dec)
    n_micro = jax.tree.leaves(batch)[0].shape[0]
    loss_scale = state.opt_state.scaler.scale

    if rope is None:
        rope = lm.make_rope(mcfg)

    deterministic = (mcfg.hidden_dropout == 0.0 and mcfg.attention_dropout == 0.0)

    def micro_grads(accs, mb, i):
        """(micro-batch `i`'s float32 gradients added to `accs`, its loss);
        the gradients alone where `accs` is None."""
        mb_rng = jax.random.fold_in(rng, i) if rng is not None else None
        # the leaves, by their place among `jax.tree.leaves`, whose
        # gradients leave the backward pass already added to `accs`
        summed = set()

        def micro_loss(params, accs):
            with grad_accum.accumulating(params, accs, summed):
                if loss_fn is not None:
                    # pluggable per-microbatch loss — the analogue of the
                    # reference's forward_step_func extension point (ref:
                    # training.py:54 pretrain signature; pretrain_bert.py /
                    # pretrain_t5.py forward_step)
                    loss = loss_fn(params, mb, mb_rng)
                else:
                    loss = lm.loss_fn(params, mb["tokens"], mcfg,
                                      loss_mask=mb["loss_mask"], rope=rope,
                                      rng=mb_rng, deterministic=deterministic,
                                      position_ids=mb.get("position_ids"),
                                      segment_ids=mb.get("segment_ids"))
            # scaled loss for backward (ref: schedules.py:176-186): the
            # optimizer unscales; dividing by n_micro here makes the
            # accumulated grad the mean over microbatches.
            return loss * loss_scale / n_micro, loss

        (_, loss), (grads, new_accs) = jax.value_and_grad(
            micro_loss, argnums=(0, 1), has_aux=True)(state.params, accs)
        grads, treedef = jax.tree.flatten(
            jax.tree.map(lambda g: g.astype(jnp.float32), grads))
        nbytes = [4 * g.size for g in grads]
        tracing.note_grad_accum(sum(nbytes[k] for k in summed), sum(nbytes))
        if accs is None:
            return treedef.unflatten(grads), loss
        # a leaf whose accumulator went down into the backward pass comes
        # back as the new one, and its own gradient holds what reached it by
        # another way: as a rule nothing, zeros that XLA drops. Whatever
        # nobody took is added here, in a pass of its own.
        sums = [new if k in summed else acc for k, (acc, new) in enumerate(
            zip(jax.tree.leaves(accs), jax.tree.leaves(new_accs)))]
        return treedef.unflatten(
            [acc + g for acc, g in zip(sums, grads)]), loss

    mb_stream = dict(batch)
    if "tokens" in mb_stream and mb_stream.get("loss_mask") is None:
        mb_stream["loss_mask"] = jnp.ones(
            (n_micro,) + (batch["tokens"].shape[1], batch["tokens"].shape[2] - 1),
            jnp.float32)
    if n_micro == 1:
        # nothing to add to: the micro-batch's gradients are the step's
        grads, loss_sum = micro_grads(
            None, jax.tree.map(lambda x: x[0], mb_stream), 0)
    else:
        def body(carry, xs):
            accs, loss_acc = carry
            accs, loss = micro_grads(accs, *xs)
            return (accs, loss_acc + loss), None

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             state.params)
        (grads, loss_sum), _ = jax.lax.scan(
            body, (zeros, jnp.zeros((), jnp.float32)),
            (mb_stream, jnp.arange(n_micro)))
    return _finish_step(state, grads, loss_sum / n_micro, cfg, wd_mask)


def _finish_step(state: TrainState, grads, loss, cfg: MegatronConfig,
                 wd_mask):
    """Shared optimizer tail: lr/wd schedule -> apply -> metrics."""
    lr = scheduler.learning_rate(state.iteration, cfg.optimizer,
                                 cfg.training)
    wd = scheduler.weight_decay(state.iteration, cfg.optimizer, cfg.training)
    new_params, new_opt_state, ometrics = opt.apply_optimizer(
        state.params, grads, state.opt_state, cfg.optimizer, lr, wd,
        wd_mask=wd_mask)
    new_state = TrainState(params=new_params, opt_state=new_opt_state,
                           iteration=state.iteration + 1)
    metrics = {"lm_loss": loss, "lr": lr, "wd": wd, **ometrics}
    if cfg.training.log_params_norm:  # ref: --log_params_norm
        metrics["params_norm"] = opt.global_grad_norm(new_params)
    return new_state, metrics


def custom_pipelined_train_step(
    state: TrainState,
    batch: dict,
    rng,
    cfg: MegatronConfig,
    mesh,
    spec,            # factory: (model_cfg, deterministic) -> (intake, chunk, head)
    wd_mask=None,
):
    """Train step for custom-loss models (BERT-family) pipelined via the
    generic 1F1B core — the reference's forward_step_func plugged into its
    1F1B schedule (ref: schedules.py:606-722). The batch dict itself is the
    stream pytree ([n_micro, ...] leaves)."""
    from megatron_tpu.parallel import pipeline as pl

    mcfg = cfg.model
    deterministic = (mcfg.hidden_dropout == 0.0 and
                     mcfg.attention_dropout == 0.0)
    intake, chunk, head = spec(mcfg, deterministic)
    tokens = batch["tokens"]
    loss, grads = pl.pipeline_train_1f1b(
        state.params, batch, mcfg, mesh,
        intake_fn=intake, chunk_fn=chunk, head_loss_fn=head,
        batch_shape=(tokens.shape[1], tokens.shape[2]),
        rng=None if deterministic else rng,
        cotangent_seed=state.opt_state.scaler.scale,
        store_activations=cfg.parallel.pipeline_store_activations,
        vpp=cfg.parallel.virtual_pipeline_chunks)
    return _finish_step(state, grads, loss, cfg, wd_mask)


def derived_pipelined_train_step(
    state: TrainState,
    batch: dict,
    rng,
    cfg: MegatronConfig,
    mesh,
    pipelined_loss_fn,   # (params, batch, rng) -> scalar, pipelined inside
    wd_mask=None,
):
    """Train step for models that pipeline inside their own loss function
    (T5's two-pass encoder/decoder, models/t5.py t5_pipeline_loss_fn) with
    the backward derived by jax.grad."""
    loss_scale = state.opt_state.scaler.scale

    def total_loss(params):
        loss = pipelined_loss_fn(params, batch, rng)
        return loss * loss_scale, loss

    (_, loss), grads = jax.value_and_grad(total_loss,
                                          has_aux=True)(state.params)
    grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
    return _finish_step(state, grads, loss, cfg, wd_mask)


def pipelined_train_step(
    state: TrainState,
    batch: dict,
    rng,
    cfg: MegatronConfig,
    mesh,
    rope: Optional[lm.RopeTables] = None,
    wd_mask=None,
):
    """Train step with the transformer stack pipelined over 'pp'
    (ref: schedules.py:606-722 1F1B — see parallel/pipeline.py).

    Default schedule is hand-written 1F1B: per-stage live memory is flat in
    n_micro (the reference's 1F1B memory bound), with vpp>1 dispatching to
    the interleaved 1F1B variant (same bound). schedule="gpipe" uses the
    lockstep scan whose backward is derived by jax.grad (memory grows with
    n_micro)."""
    from megatron_tpu.parallel import pipeline as pl

    mcfg = cfg.model
    loss_scale = state.opt_state.scaler.scale
    deterministic = (mcfg.hidden_dropout == 0.0 and
                     mcfg.attention_dropout == 0.0)
    if rope is None:
        rope = lm.make_rope(mcfg)

    use_1f1b = cfg.parallel.pipeline_schedule == "1f1b"
    if use_1f1b:
        # data-level ring-cp zigzag (as in the unpipelined loss_fn): the
        # streams are permuted once and every chunk's ring attention runs
        # permute-free
        from megatron_tpu.parallel.ring_attention import data_zigzag_cp
        zz_cp = data_zigzag_cp(mcfg, batch["tokens"].shape[2] - 1,
                               segment_ids=batch.get("segment_ids"))
        intake, chunk, head = pl.gpt_1f1b_fns(mcfg, rope=rope,
                                              deterministic=deterministic,
                                              cp_pre_zigzag=zz_cp > 0)
        streams = pl.gpt_1f1b_streams(
            batch["tokens"], mcfg, loss_mask=batch.get("loss_mask"),
            position_ids=batch.get("position_ids"),
            segment_ids=batch.get("segment_ids"), zigzag_cp=zz_cp)
        n_b = batch["tokens"].shape[1]
        n_s = batch["tokens"].shape[2] - 1
        loss, grads = pl.pipeline_train_1f1b(
            state.params, streams, mcfg, mesh,
            intake_fn=intake, chunk_fn=chunk, head_loss_fn=head,
            batch_shape=(n_b, n_s),
            rng=None if deterministic else rng,
            cotangent_seed=loss_scale,
            store_activations=cfg.parallel.pipeline_store_activations,
            vpp=cfg.parallel.virtual_pipeline_chunks)
    else:
        def total_loss(params):
            loss = pl.pipeline_loss_fn(
                params, batch["tokens"], mcfg, mesh,
                vpp=cfg.parallel.virtual_pipeline_chunks,
                loss_mask=batch.get("loss_mask"), rope=rope,
                rng=None if deterministic else rng,
                deterministic=deterministic,
                position_ids=batch.get("position_ids"),
                segment_ids=batch.get("segment_ids"))
            return loss * loss_scale, loss

        grad_fn = jax.value_and_grad(total_loss, has_aux=True)
        (_, loss), grads = grad_fn(state.params)
    grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
    return _finish_step(state, grads, loss, cfg, wd_mask)


def param_shardings(cfg: MegatronConfig, mesh, rules=None, axes_fn=None):
    """NamedShardings for the model param tree on `mesh` — the same mapping
    make_train_step uses (shared by the eval step and inference)."""
    from megatron_tpu.parallel import sharding as shd
    if rules is None:
        rules = shd.make_logical_rules(cfg.parallel.sequence_parallel,
                                      expert_axis=cfg.parallel.expert_axis)
    axes = axes_fn(cfg.model) if axes_fn else lm.model_axes(cfg.model)
    return shd.tree_logical_to_sharding(mesh, axes, rules)


def state_shardings(cfg: MegatronConfig, mesh, param_shapes, rules=None,
                    axes_fn=None, has_opt: bool = True):
    """The full TrainState sharding tree the sharded train step uses —
    ONE source shared by make_train_step and offline tools
    (tools/checkpoint_util.py), so a pre-flight validation proves the
    layout the real step will actually run. `param_shapes`: the param
    tree (arrays or ShapeDtypeStructs) for the ZeRO-1 divisibility
    decisions."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from megatron_tpu.parallel import sharding as shd
    if rules is None:
        rules = shd.make_logical_rules(cfg.parallel.sequence_parallel,
                                      expert_axis=cfg.parallel.expert_axis)
    axes = axes_fn(cfg.model) if axes_fn else lm.model_axes(cfg.model)
    param_sh = shd.tree_logical_to_sharding(mesh, axes, rules)
    scalar_sh = NamedSharding(mesh, P())
    opt_sh = None
    if has_opt:
        if cfg.parallel.use_distributed_optimizer:
            # ZeRO-1: Adam moments additionally sharded over 'dp'
            # (ref: optimizer/distrib_optimizer.py; see
            # parallel/sharding.py:distributed_opt_sharding)
            moment_sh = shd.tree_distributed_opt_sharding(
                mesh, axes, rules, param_shapes,
                pipelined=cfg.parallel.pipeline_parallel > 1)
        else:
            moment_sh = param_sh
        opt_sh = opt.OptState(
            step=scalar_sh,
            mu=moment_sh,
            nu=moment_sh if cfg.optimizer.optimizer == "adam" else None,
            scaler=opt.ScalerState(scalar_sh, scalar_sh, scalar_sh),
        )
    return TrainState(params=param_sh, opt_state=opt_sh,
                      iteration=scalar_sh)


class _MeshContextStep:
    """Callable wrapping a jitted step so each call runs with the ambient
    mesh set (required by the partial-manual shard_map inside)."""

    def __init__(self, fn, mesh):
        self._fn = fn
        self._mesh = mesh

    def __call__(self, *args, **kwargs):
        with jax.set_mesh(self._mesh):
            return self._fn(*args, **kwargs)


def make_train_step(cfg: MegatronConfig, mesh=None, rules=None, donate=True,
                    loss_fn=None, init_params_fn=None, axes_fn=None,
                    pipelined_spec=None, pipelined_loss_fn=None):
    """Build the jitted train step, optionally sharded over `mesh`.

    With a mesh, parameters/optimizer state get shardings from the model's
    logical axes via the rules table, and the batch is 'dp'-sharded on the
    microbatch-batch dim — GSPMD then inserts the TP psums and the DP grad
    all-reduce the reference hand-codes. pp>1 dispatches to the pipelined
    step (collective-permute 1F1B, parallel/pipeline.py).

    Custom-loss models pipeline via one of:
    - `pipelined_spec`: factory (model_cfg, deterministic) ->
      (intake_fn, chunk_fn, head_loss_fn) plugged into the generic 1F1B
      core (single-stack models, e.g. models/bert.py bert_1f1b_fns);
    - `pipelined_loss_fn`: (params, batch, rng) -> scalar that pipelines
      internally with a derived backward (encoder-decoder models, e.g.
      models/t5.py t5_pipeline_loss_fn).
    """
    rope = lm.make_rope(cfg.model)
    # weight-decay mask from logical axes: the stacked 'layers' dim must not
    # count toward the >=2-D decay rule (a stacked norm scale [L, h] is 1-D
    # per layer and decay-exempt — ref: optimizer/__init__.py:36-42)
    axes = axes_fn(cfg.model) if axes_fn else lm.model_axes(cfg.model)
    init = init_params_fn or (
        lambda: lm.model_init(jax.random.PRNGKey(0), cfg.model))
    if loss_fn is not None and axes_fn is None:
        wd_mask = None  # unknown custom param structure: in-step ndim rule
    else:
        # ONE rule source: the shared helper, fed abstract shapes
        wd_mask = opt.weight_decay_mask(jax.eval_shape(init), axes)

    pipelined = mesh is not None and cfg.parallel.pipeline_parallel > 1
    if pipelined:
        if pipelined_spec is not None:
            # the spec path runs the 1F1B core (vpp>=1: the interleaved
            # variant handles virtual stages since round 4) but not the
            # lockstep gpipe schedule — fail loudly rather than train a
            # different schedule than asked
            assert cfg.parallel.pipeline_schedule == "1f1b", (
                "pipelined_spec models run the 1F1B core only; drop "
                "--pipeline_schedule gpipe")
            fn = functools.partial(custom_pipelined_train_step, cfg=cfg,
                                   mesh=mesh, spec=pipelined_spec,
                                   wd_mask=wd_mask)
        elif pipelined_loss_fn is not None:
            fn = functools.partial(derived_pipelined_train_step, cfg=cfg,
                                   mesh=mesh,
                                   pipelined_loss_fn=pipelined_loss_fn,
                                   wd_mask=wd_mask)
        else:
            assert loss_fn is None, (
                "pp>1 with a custom loss needs pipelined_spec (single-stack "
                "models, see models/bert.py bert_1f1b_fns) or "
                "pipelined_loss_fn (encoder-decoder, see models/t5.py "
                "t5_pipeline_loss_fn)")
            fn = functools.partial(pipelined_train_step, cfg=cfg, mesh=mesh,
                                   rope=rope, wd_mask=wd_mask)
    else:
        fn = functools.partial(train_step, cfg=cfg, rope=rope,
                               wd_mask=wd_mask, loss_fn=loss_fn)
    if mesh is None:
        return jax.jit(fn, donate_argnums=(0,) if donate else ())

    from jax.sharding import NamedSharding, PartitionSpec as P
    from megatron_tpu.parallel import sharding as shd

    if rules is None:
        rules = shd.make_logical_rules(cfg.parallel.sequence_parallel,
                                      expert_axis=cfg.parallel.expert_axis)

    # run tracing under the activation-sharding context so model-level
    # `constrain` calls (sequence parallelism, logits vocab sharding) become
    # real with_sharding_constraint ops — see parallel/sharding.py
    base_fn = fn

    def fn(*args, **kwargs):
        with shd.activation_shardings(mesh, rules):
            return base_fn(*args, **kwargs)

    state_sh = state_shardings(cfg, mesh, jax.eval_shape(init), rules=rules,
                               axes_fn=axes_fn)
    scalar_sh = NamedSharding(mesh, P())
    # pytree-prefix sharding: every batch leaf is [n_micro, batch, ...],
    # dp-sharded on the batch dim — rank-2 spec so 2-D leaves (e.g. BERT's
    # is_random) and 3-D leaves (tokens, masks) both accept it
    batch_sh = NamedSharding(mesh, P(None, "dp"))
    jitted = jax.jit(
        fn,
        in_shardings=(state_sh, batch_sh, scalar_sh),
        out_shardings=(state_sh, None),
        donate_argnums=(0,) if donate else (),
    )
    if pipelined:
        return _MeshContextStep(jitted, mesh)
    return jitted
