"""Training driver: the `pretrain` loop.

TPU-native equivalent of megatron/training.py — `pretrain` (:54-167), the
`_train` loop (:639-751), `training_log` (:452-626), `evaluate` (:754-807) —
plus the SIGTERM checkpoint-and-exit and timed-exit semantics
(ref: megatron/dist_signal_handler.py:50-81, training.py:712-748).

Differences by design:
- One process drives all local devices (single-controller JAX); the
  "dataloader only on tp-rank-0 then broadcast flags" machinery
  (ref: training.py:855-939) dissolves — the host feeds a globally-sharded
  batch via jax.device_put against the dp-sharded spec.
- train_step is one compiled program (training/train_step.py); timers wrap it
  with block_until_ready instead of CUDA syncs.
- Host/device overlap (async dispatch, the default): the loop never
  blocks on a step. Per-step metrics stay device-resident in a
  `_MetricsWindow` (handles only; D2H copies started early via
  copy_to_host_async) and are materialized in ONE `_device_fetch` per
  log window; skip/NaN accounting and the divergence guard replay the
  window's per-step floats at the flush — identical decisions to the
  step-exact path, at most log_interval-1 steps late (rollback restores
  a checkpoint either way). Input batches are lifted to the dp-sharded
  device layout in the prefetch producer thread (batch N+1's transfer
  overlaps step N). `--sync_metrics` restores the fetch-every-step
  behavior; `--profile` traces whichever of the two the job runs.
- Host spans (`utils/tracing.py`): `mtpu/train/data_next`, `step`,
  `flush`, `eval`, `save` land in a profiler trace beside the device's
  events; with no profiler session they cost nothing.
"""
from __future__ import annotations

import inspect
import signal
import time
from typing import Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from megatron_tpu.config import MegatronConfig, ResilienceConfig
from megatron_tpu.resilience import (DivergenceGuard, GuardAction,
                                     StepWatchdog, TrainingDivergedError,
                                     get_fault_injector)
# NOTE: the package __init__ re-exports the train_step FUNCTION under the
# same name as its module, so `import ...train_step as ts` would resolve to
# the function attribute — import the symbols directly instead
from megatron_tpu.training.train_step import (TrainState, init_train_state,
                                              make_train_step)
from megatron_tpu.data.samplers import PrefetchIterator
from megatron_tpu.training.microbatches import MicrobatchCalculator
from megatron_tpu.utils.logging import make_writer, print_rank_0
from megatron_tpu.utils.timers import Timers
from megatron_tpu.utils.tracing import (phase, ready, span, start_trace,
                                        startup_scalars, step_span)


def _device_fetch(tree):
    """ONE device→host transfer for a pytree of device values — THE
    sync seam of the training path. Every metrics/eval fetch funnels
    through here so sync-cadence tests (tests/test_async_dispatch.py)
    can count host syncs by wrapping this one function."""
    return jax.device_get(tree)


class _MetricsWindow:
    """Device-resident per-step metrics between host syncs.

    `push` keeps a step's scalar jax.Arrays as handles (no sync, no
    float()) and — with `eager_d2h` (accelerator backends) — starts
    their D2H copies as soon as the step is dispatched, so `flush`
    materializes the whole window in ONE already-overlapped
    `_device_fetch` — the loop's only block point in async mode."""

    def __init__(self, eager_d2h: bool = False):
        self._eager_d2h = eager_d2h
        self._its = []
        self._metrics = []

    def __len__(self):
        return len(self._its)

    def push(self, iteration: int, metrics: dict):
        if self._eager_d2h:
            for v in metrics.values():
                start = getattr(v, "copy_to_host_async", None)
                if start is not None:
                    try:
                        start()
                    except Exception:
                        pass  # backend without async D2H: flush works
        self._its.append(iteration)
        self._metrics.append(metrics)

    def flush(self):
        """-> [(iteration, {name: float})] in step order; empties the
        window. One `_device_fetch` regardless of window length."""
        if not self._its:
            return []
        with span("train/flush"):
            vals = _device_fetch(self._metrics)
        out = [(it, {k: float(v) for k, v in m.items()})
               for it, m in zip(self._its, vals)]
        self._its, self._metrics = [], []
        return out


def _iter_state(it) -> Optional[dict]:
    """Exact-resume state of a data iterator (samplers.state_dict
    protocol), or None for plain generators that have none."""
    get_state = getattr(it, "state_dict", None)
    return get_state() if get_state is not None else None


def _accepts_kwargs(fn, *names) -> bool:
    """True when `fn` takes every keyword in `names` (or **kwargs) —
    the back-compat seam for the save_fn / reset_data_fn hook contracts
    growing data_state/quarantine arguments."""
    try:
        params = inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return False
    if any(p.kind is inspect.Parameter.VAR_KEYWORD
           for p in params.values()):
        return True
    return all(n in params for n in names)


def _call_save_fn(save_fn, state, iteration, consumed_samples,
                  data_state, quarantine):
    """save_fn with the exact-resume extras when it accepts them
    (finetune.py / run_pretrain do); legacy 3-arg save hooks keep
    working unchanged."""
    if _accepts_kwargs(save_fn, "data_state", "quarantine"):
        return save_fn(state, iteration, consumed_samples,
                       data_state=data_state, quarantine=quarantine)
    return save_fn(state, iteration, consumed_samples)


def _call_reset_data_fn(reset_data_fn, consumed_samples, rollbacks,
                        data_state):
    """reset_data_fn(consumed, rollbacks[, data_state=...]): hooks that
    take data_state rebuild the stream at the EXACT checkpointed
    position (bit-identical replay); legacy 2-arg hooks are called as
    before."""
    if _accepts_kwargs(reset_data_fn, "data_state"):
        return reset_data_fn(consumed_samples, rollbacks,
                             data_state=data_state)
    return reset_data_fn(consumed_samples, rollbacks)


def _make_batch_lift(mesh, batch_sh):
    """The input lift: host batch pytree -> committed device arrays in
    the layout the jitted step consumes (dp-sharded batch dim under a
    mesh, globally-sharded under multi-process, plain placement
    otherwise). Applied one batch AHEAD of the step that consumes it
    so the H2D transfer overlaps the previous step's device time."""
    if batch_sh is not None:
        from megatron_tpu.parallel.multihost import make_global_batch
        return lambda b: make_global_batch(b, mesh, batch_sh)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        sh = NamedSharding(mesh, PartitionSpec(None, "dp"))
        return lambda b: jax.device_put(b, sh)
    return jax.device_put


class SignalState:
    """SIGTERM -> graceful checkpoint-and-exit
    (ref: dist_signal_handler.py:50-81). Single-controller: no all-gather of
    the signal needed — one process decision is globally consistent."""

    def __init__(self):
        self.received = False

    def install(self):
        def handler(signum, frame):
            self.received = True
        try:
            signal.signal(signal.SIGTERM, handler)
        except ValueError:
            pass  # non-main thread (tests)
        return self


def training_log(metrics: dict, iteration: int, consumed_samples: int,
                 elapsed_per_iter: float, tokens_per_sec: float,
                 writer, skipped_total: int, nan_total: int,
                 quarantined_total: int = 0) -> str:
    """Format + emit the per-interval dashboard line
    (ref: training.py:452-626). `quarantined_total` counts poison-batch
    steps deterministically skipped by divergence rollbacks (only shown
    once non-zero — see docs/resilience.md)."""
    loss = float(metrics["lm_loss"])
    lr = float(metrics["lr"])
    gnorm = float(metrics["grad_norm"])
    lscale = float(metrics.get("loss_scale", 1.0))
    line = (f"iteration {iteration} | consumed samples {consumed_samples} | "
            f"elapsed time per iteration (ms): {elapsed_per_iter*1000:.1f} | "
            f"tokens/s: {tokens_per_sec:.1f} | learning rate: {lr:.3E} | "
            f"lm loss: {loss:.6E} | loss scale: {lscale:.1f} | "
            f"grad norm: {gnorm:.3f} | skipped iterations: {skipped_total} | "
            f"nan iterations: {nan_total}")
    if quarantined_total:
        line += f" | quarantined iterations: {quarantined_total}"
        writer.add_scalar("resilience/quarantined iterations",
                          quarantined_total, iteration)
    writer.add_scalar("lm-loss-training/lm loss", loss, iteration)
    writer.add_scalar("learning-rate/learning rate", lr, iteration)
    writer.add_scalar("grad-norm/grad norm", gnorm, iteration)
    writer.add_scalar("loss-scale/loss scale", lscale, iteration)
    writer.add_scalar("throughput/tokens per sec", tokens_per_sec, iteration)
    if "params_norm" in metrics:  # ref: --log_params_norm
        pn = float(metrics["params_norm"])
        line += f" | params norm: {pn:.3f}"
        writer.add_scalar("params-norm/params norm", pn, iteration)
    if "num_zeros" in metrics:  # ref: --log_num_zeros_in_grad
        writer.add_scalar("num-zeros/num zeros",
                          float(metrics["num_zeros"]), iteration)
    return line


def evaluate(state: TrainState, eval_iterator, eval_step_fn,
             eval_iters: int, mesh=None, batch_sh=None) -> dict:
    """(ref: training.py:754-807) mean lm loss + ppl over eval_iters batches.
    `batch_sh` lifts host batches to global arrays on multi-host runs (same
    invariant as the train path). A finite `eval_iterator` that runs dry
    mid-eval stops early and averages over the batches actually seen —
    an exhausted validation split must not kill the training run. With
    ZERO batches seen (the iterator was already dead) returns None so
    the caller skips reporting instead of logging a fake 0.0 loss.

    The per-batch losses stay device-resident (handles only) and are
    fetched in ONE transfer after the loop — the old code float()'d,
    i.e. host-synced, once per eval batch, serializing the eval stream.
    The host-order float accumulation is kept so the reported mean is
    bit-identical to the per-step-fetch version."""
    losses = []
    seen = 0
    for _ in range(eval_iters):
        try:
            batch = next(eval_iterator)
        except StopIteration:
            print_rank_0(f"evaluate: valid iterator exhausted after "
                         f"{seen}/{eval_iters} batches; "
                         + ("averaging over the batches seen" if seen
                           else "skipping this eval interval"))
            break
        if batch_sh is not None:
            from megatron_tpu.parallel.multihost import make_global_batch
            batch = make_global_batch(batch, mesh, batch_sh)
        losses.append(eval_step_fn(state.params, batch))
        seen += 1
    if seen == 0:
        return None
    total = 0.0
    for v in _device_fetch(losses):
        total += float(v)
    mean = total / seen
    return {"lm loss": mean, "lm loss ppl": float(np.exp(min(mean, 20.0)))}


def train(
    cfg: MegatronConfig,
    train_iterator: Iterator[dict],
    valid_iterator: Optional[Iterator[dict]] = None,
    mesh=None,
    state: Optional[TrainState] = None,
    rng=None,
    start_iteration: int = 0,
    consumed_samples: int = 0,
    save_fn: Optional[Callable] = None,
    step_kwargs: Optional[dict] = None,
    load_fn: Optional[Callable] = None,
    reset_data_fn: Optional[Callable] = None,
    quarantine_log: Optional[list] = None,
):
    """The `_train` loop (ref: training.py:639-751). `train_iterator` yields
    {"tokens": [n_micro, mbs, seq+1], "loss_mask": [n_micro, mbs, seq]}.
    `step_kwargs` forwards to make_train_step (loss_fn / init_params_fn /
    axes_fn — the pretrain_bert/t5/ict entry points' extension hook,
    mirroring the reference's forward_step_func argument to `pretrain`).
    Returns (state, consumed_samples).

    Resilience hooks (cfg.resilience, docs/resilience.md): `load_fn()
    -> LoadedCheckpoint | (state, iteration, consumed_samples) | None`
    restores the newest valid checkpoint when the divergence guard
    orders a rollback; `reset_data_fn(consumed_samples, rollbacks[,
    data_state=...]) -> iterator` rebuilds the training stream at the
    EXACT checkpointed position (samplers state_dict protocol). The
    loop then replays the identical batch order but deterministically
    SKIPS the quarantined step window (checkpoint iteration, trigger
    iteration] — no update runs on the poison batches, the window is
    recorded in `quarantine_log` + checkpoint metadata, and the data
    order is never re-seeded. `save_fn(state, iteration, consumed[,
    data_state=, quarantine=])` persists the iterator state alongside
    the weights so an interrupted run resumes bit-exact. Without
    `load_fn`, a guard breach aborts with TrainingDivergedError
    instead of burning compute on a dead run. A
    `step_timeout_s` watchdog (armed after the first, compile-heavy
    step) dumps stacks, attempts a final checkpoint, and exits with a
    distinct code when a step wedges. An active FaultInjector
    (resilience/faults.py) can poison batches / stall steps here — the
    chaos-test entry points."""
    # the start-up record's last phase: from here to the return of the
    # first step's flush, closed at the once-only site below
    first_step = phase("first_step")
    first_step.__enter__()
    # async by default: the loop blocks once per log window (the
    # metrics flush), not per step; sync_metrics restores the
    # step-exact barriers (docstring "Host/device overlap")
    sync_metrics = cfg.training.sync_metrics
    # Dispatch overlap (run-ahead + committed device_put input lift) is
    # gated to non-cpu backends: CPU jax 0.4.x recycles donated buffers
    # of an in-flight step while they are still referenced — observed
    # as heap corruption on the checkpoint-resume path and wrong decode
    # tokens in the serving engine (same backend bug family as the
    # rollback fresh-copy note below). The cpu harness keeps the old
    # blocking dispatch; the windowed metrics-FETCH cadence — what the
    # sync tests pin — is pure host logic and stays identical on every
    # backend.
    overlap_dispatch = (not sync_metrics
                        and jax.default_backend() != "cpu")
    step_barrier = not sync_metrics and not overlap_dispatch
    timers = Timers(barrier_free=not sync_metrics)
    wandb_kwargs = {}
    if cfg.training.wandb_logger:
        tr = cfg.training
        wandb_kwargs = {k: v for k, v in dict(
            project=tr.wandb_project or "megatron_tpu",
            entity=tr.wandb_entity, run_id=tr.wandb_id,
            resume=tr.wandb_resume).items() if v}
    writer = make_writer(cfg.training.tensorboard_dir,
                         use_wandb=cfg.training.wandb_logger,
                         **wandb_kwargs)
    signals = SignalState().install()

    if rng is None:
        rng = jax.random.PRNGKey(cfg.training.seed)
    if state is None:
        init_params_fn = (step_kwargs or {}).get("init_params_fn")
        with jax.default_device(jax.devices()[0]) if mesh is None else _nullcontext():
            if init_params_fn is not None:
                # custom model family (BERT/T5/ICT): build state from ITS
                # param tree, not the GPT default
                from megatron_tpu.training.train_step import \
                    state_from_params
                state = state_from_params(init_params_fn(), cfg)
            else:
                state = init_train_state(rng, cfg)

    step_fn = make_train_step(cfg, mesh=mesh, **(step_kwargs or {}))

    calc = MicrobatchCalculator(
        cfg.training.global_batch_size, cfg.training.micro_batch_size,
        cfg.parallel.data_parallel or 1, cfg.training.rampup_batch_size)

    iteration = start_iteration
    skipped_total = 0
    nan_total = 0
    quarantined_total = 0
    # audit trail of poison-batch windows skipped by rollbacks; seeded
    # from the loaded checkpoint so the history survives restarts, and
    # persisted into every later checkpoint's metadata
    quarantine_log = list(quarantine_log or [])
    data_state_now: Optional[dict] = None  # iterator state at the
    # CURRENT step's batch (snapshotted before any look-ahead pull)
    eval_step_fn = None  # built lazily once, reused across eval intervals
    t_start = time.perf_counter()
    interval_t0 = time.perf_counter()
    interval_iters = 0
    seq_len = cfg.model.seq_length
    trace_active = False

    res = getattr(cfg, "resilience", None) or ResilienceConfig()
    guard = DivergenceGuard(
        max_consecutive_nonfinite=res.max_consecutive_nonfinite,
        loss_spike_factor=res.loss_spike_factor,
        loss_spike_window=res.loss_spike_window,
        max_rollbacks=res.max_rollbacks)
    injector = get_fault_injector()
    base_rng = rng
    watchdog = None
    if res.step_timeout_s:
        def _watchdog_checkpoint():
            # best-effort final checkpoint from the monitor thread; the
            # closure reads the loop's CURRENT state/iteration
            if save_fn is not None:
                _call_save_fn(save_fn, state, iteration, consumed_samples,
                              data_state_now, quarantine_log)
        wd_timeout = res.step_timeout_s
        if overlap_dispatch:
            # run-ahead dispatch: the host only observes device
            # progress at window FLUSHES (between them dispatch always
            # "progresses"), and a healthy flush legitimately blocks
            # for up to a whole window of device time — so the deadline
            # covers a window, not a step. The cost is detection
            # latency scaled by log_interval (docs/resilience.md);
            # --sync_metrics restores the per-step deadline. Per-step-
            # barrier backends (sync mode, the cpu harness) heartbeat
            # every iteration and keep the original deadline.
            wd_timeout = res.step_timeout_s * max(
                cfg.training.log_interval, 1)
            print_rank_0(
                f"watchdog: async metrics scales the step deadline to "
                f"one log window: {wd_timeout:.1f}s "
                f"(step_timeout_s={res.step_timeout_s:.1f} x "
                f"log_interval={cfg.training.log_interval}); use "
                f"--sync_metrics for per-step hang detection")
        watchdog = StepWatchdog(wd_timeout,
                                on_timeout=_watchdog_checkpoint,
                                exit_code=res.watchdog_exit_code)

    # pod-scale feeding: host batches must become globally sharded arrays
    # when >1 process drives the mesh (single-process: identity)
    batch_sh = None
    if mesh is not None and jax.process_count() > 1:
        from jax.sharding import NamedSharding, PartitionSpec
        batch_sh = NamedSharding(mesh, PartitionSpec(None, "dp"))

    # device-side input double buffering ("prefetch_ahead"): batch N+1
    # is pulled from the iterator and jax.device_put against the
    # dp-sharded spec RIGHT AFTER step N's async dispatch, so its H2D
    # transfer rides under step N's device time instead of sitting on
    # step N+1's dispatch path. Main-thread only: device ops from the
    # prefetch producer thread race the dispatch and abort inside XLA
    # on CPU jax 0.4.x. Disabled under rampup (the look-ahead would use
    # a stale microbatch count) and under an active FaultInjector
    # (which corrupts HOST arrays per step call, in order).
    lift_fn = (_make_batch_lift(mesh, batch_sh)
               if overlap_dispatch and injector is None else None)
    prefetch_ahead = (lift_fn is not None
                      and cfg.training.rampup_batch_size is None)
    pending_batch = None
    pending_stop: Optional[StopIteration] = None

    # host-side batch assembly overlaps device compute (the reference's
    # DataLoader-worker overlap, ref: data_samplers.py num_workers).
    # Not under batch-size rampup: prefetched batches would lag the
    # calculator's phase switch and skew the consumed-samples accounting
    if (cfg.data.num_workers > 0
            and cfg.training.rampup_batch_size is None
            and not isinstance(train_iterator, PrefetchIterator)):
        train_iterator = PrefetchIterator(train_iterator)

    window = _MetricsWindow(eager_d2h=overlap_dispatch)
    last_metrics: dict = {}
    memory_reported = False

    try:
        while iteration < cfg.training.train_iters:
            if watchdog is not None:
                watchdog.heartbeat()
            calc.update(consumed_samples)
            # batch-size rampup: propagate the current microbatch count into the
            # iterator so the yielded batch matches what we account for below.
            # Each ramp phase changes the batch shape -> one jit recompile per
            # phase (bounded by the ramp step count).
            if hasattr(train_iterator, "num_microbatches"):
                train_iterator.num_microbatches = calc.num_microbatches
            stop_exc: Optional[StopIteration] = None
            if pending_batch is not None:
                # lifted one step ago; its H2D transfer overlapped the
                # previous step's device time
                batch, pending_batch = pending_batch, None
            elif pending_stop is not None:
                # deferred iterator exhaustion
                stop_exc, pending_stop = pending_stop, None
            else:
                with span("train/data_next"):
                    try:
                        batch = next(train_iterator)
                    except StopIteration as stop:
                        # exhausted mid-window: the steps already
                        # dispatched must still reach the guard and the
                        # skip/NaN counters (the step-exact path
                        # observed every one of them before this raise)
                        # — skip the step, fall through to the flush,
                        # then re-raise below
                        stop_exc = stop
                    else:
                        if injector is not None:
                            step_call = injector.next_step_call()
                            injector.maybe_delay(step_call)
                            batch = injector.corrupt_batch(batch,
                                                           step_call)
                        if lift_fn is not None:
                            batch = lift_fn(batch)
                        elif batch_sh is not None:
                            from megatron_tpu.parallel.multihost import \
                                make_global_batch
                            batch = make_global_batch(batch, mesh,
                                                      batch_sh)
            if stop_exc is None and save_fn is not None:
                # snapshot the iterator at THIS step's batch, before the
                # look-ahead pull below advances it — a checkpoint at
                # iteration N must resume with batch N+1, not N+2
                data_state_now = _iter_state(train_iterator)
            if stop_exc is None:
                step_rng = jax.random.fold_in(rng, iteration)
                if (cfg.training.profile and not trace_active
                        and iteration == cfg.training.profile_step_start):
                    start_trace(cfg.training.profile_dir
                                or cfg.training.tensorboard_dir
                                or "/tmp/megatron_tpu_trace")
                    trace_active = True
                t_step = timers("train-step", log_level=0)
                t_step.ensure_started()  # async: ONE span per window
                with step_span("train/step", iteration):
                    state, metrics = step_fn(state, batch, step_rng)
                if sync_metrics:
                    # exact-sync path: block on this step's result
                    # before closing the span (the old per-step
                    # block_until_ready)
                    t_step.stop(sync_on=metrics["lm_loss"])
                elif step_barrier:
                    # cpu-backend donation guard (see step_barrier
                    # above): completion barrier only, no host transfer
                    jax.block_until_ready(metrics["lm_loss"])
                if (watchdog is not None and watchdog.started
                        and not overlap_dispatch):
                    # per-step barriers make each iteration real device
                    # progress — keep the per-step heartbeat (and
                    # deadline) on these paths; the run-ahead path
                    # heartbeats at flushes against its window-scaled
                    # deadline
                    watchdog.heartbeat()
                if (trace_active
                        and iteration >= cfg.training.profile_step_end):
                    jax.profiler.stop_trace()
                    trace_active = False
                    print_rank_0(f"profiler trace written "
                                 f"({cfg.training.profile_step_start}.."
                                 f"{cfg.training.profile_step_end})")

                iteration += 1
                interval_iters += 1
                consumed_samples += calc.global_batch_size
                window.push(iteration, metrics)

                if (prefetch_ahead and pending_batch is None
                        and pending_stop is None
                        and iteration < cfg.training.train_iters):
                    # the double-buffer fill: pull + lift batch N+1
                    # while step N runs (the dispatch above did not
                    # block). Exhaustion is deferred to the next loop
                    # turn so a finite iterator still serves its last
                    # batch.
                    try:
                        with span("train/data_next"):
                            pending_batch = lift_fn(next(train_iterator))
                    except StopIteration as stop:
                        pending_stop = stop

            # window flush points: every step when sync; else log/eval/
            # save/exit boundaries, the run end, and the first step
            # (whose flush doubles as the post-compile barrier that
            # arms the watchdog and grounds the memory report)
            trcfg = cfg.training
            log_due = iteration % trcfg.log_interval == 0
            eval_due = bool(valid_iterator is not None
                            and trcfg.eval_interval
                            and iteration % trcfg.eval_interval == 0)
            save_due = bool(save_fn is not None and trcfg.save_interval
                            and iteration % trcfg.save_interval == 0)
            # exit conditions (ref: training.py:712-748), decided ONCE
            # per iteration and reused by the exit block below — a
            # SIGTERM (or the duration clock) crossing between two
            # independent reads would exit with an unflushed window
            exit_msgs = []
            if signals.received:
                exit_msgs.append(
                    "SIGTERM received: checkpointing and exiting")
            if (trcfg.exit_interval
                    and iteration % trcfg.exit_interval == 0):
                exit_msgs.append(f"exiting at iteration {iteration} "
                                 "(exit_interval)")
            if trcfg.exit_duration_in_mins is not None:
                mins = (time.perf_counter() - t_start) / 60.0
                if mins > trcfg.exit_duration_in_mins:
                    exit_msgs.append(f"exiting after {mins:.1f} min "
                                     "(exit_duration)")
            exit_due = bool(exit_msgs)
            flush_due = (sync_metrics or log_due or eval_due or save_due
                         or exit_due or stop_exc is not None
                         or iteration >= trcfg.train_iters
                         or iteration == start_iteration + 1)

            rollback_at = None
            if flush_due and len(window):
                flushed = window.flush()  # the window's ONE host sync
                if not sync_metrics:
                    t_step.stop_if_started()
                for it, m in flushed:
                    last_metrics = m
                    found_inf = bool(m["found_inf"])
                    if found_inf:
                        skipped_total += 1
                    if not np.isfinite(m["lm_loss"]):
                        nan_total += 1
                    if guard.enabled:
                        action = guard.observe(m["lm_loss"], found_inf)
                        if action is GuardAction.ROLLBACK:
                            # steps past the trigger (≤ window-1, already
                            # executed by the async run-ahead) are
                            # discarded: the step-exact path never ran
                            # them and the restore erases their effect,
                            # so guard state and skip/nan counters stay
                            # identical across both modes
                            rollback_at = it
                            break
                if watchdog is not None:
                    watchdog.heartbeat()
                    if not watchdog.started:
                        # arm only now: the first step's jit compile
                        # (barrier'd by the first-step flush above) is
                        # unrelated to the steady-state deadline
                        watchdog.start()
                if not memory_reported:
                    # HBM report after the first step has actually run
                    # (ref: training.py:522-524 report_memory_flag)
                    memory_reported = True
                    from megatron_tpu.utils.logging import report_memory
                    report_memory("after first step")
                    # ... and start-up is over: the step has compiled
                    # (or loaded) and run
                    first_step.__exit__(None, None, None)
                    ready()
                    for k, v in startup_scalars().items():
                        writer.add_scalar(f"startup/{k}", v, iteration)

            if rollback_at is not None:
                exhausted = guard.note_rollback()
                if exhausted:
                    raise TrainingDivergedError(
                        f"divergence persisted through "
                        f"{guard.rollbacks - 1} rollback(s) at "
                        f"iteration {rollback_at}; aborting cleanly")
                if load_fn is None:
                    raise TrainingDivergedError(
                        f"divergence at iteration {rollback_at} "
                        f"({guard.max_consecutive_nonfinite} "
                        "consecutive non-finite steps or loss "
                        "spike) with no checkpoint to roll back "
                        "to — configure --save to enable rollback")
                print_rank_0(
                    f"divergence guard: rolling back at iteration "
                    f"{rollback_at} (rollback {guard.rollbacks}/"
                    f"{res.max_rollbacks})")
                loaded = load_fn()
                if loaded is None or loaded[0] is None:
                    raise TrainingDivergedError(
                        "rollback requested but no restorable "
                        "checkpoint was found")
                # rematerialize as fresh uncommitted buffers (a
                # REAL copy — np.asarray/jnp.asarray are zero-copy
                # on CPU): the step executable was compiled against
                # the ORIGINAL state's placement and DONATES its
                # inputs, so feeding it the restorer's committed /
                # aliased arrays lets the donation clobber the very
                # buffers the restore returned (NaN garbage or a
                # segfault on CPU jax 0.4.x)
                state = jax.tree.map(
                    lambda x: jnp.array(np.asarray(x), copy=True),
                    loaded[0])
                iteration, consumed_samples = (int(loaded[1]),
                                               int(loaded[2]))
                # re-seeded STEP randomness (dropout etc.) for the
                # replayed segment — the DATA order is never re-seeded
                rng = jax.random.fold_in(base_rng,
                                         0x5EED + guard.rollbacks)
                if reset_data_fn is not None:
                    if isinstance(train_iterator, PrefetchIterator):
                        train_iterator.close()
                    # exact replay: the stream is rebuilt at the
                    # checkpoint's saved iterator state (same seed,
                    # same order) — never a shifted seed
                    train_iterator = _call_reset_data_fn(
                        reset_data_fn, consumed_samples,
                        guard.rollbacks,
                        getattr(loaded, "data_state", None))
                    # the look-ahead batch belongs to the OLD stream
                    pending_batch, pending_stop = None, None
                    # poison-batch quarantine: the replayed order would
                    # re-serve the exact batches that diverged, so the
                    # window (checkpoint iteration, trigger iteration]
                    # is skipped BY CONSTRUCTION — batches are pulled
                    # and discarded (no train step, like the optimizer's
                    # skip-as-select but decided up front), iteration /
                    # consumed_samples advance so the iteration↦batch
                    # mapping downstream of the window is identical to
                    # an undiverged run. Repeated divergence past the
                    # window still burns the rollback budget above and
                    # escalates to TrainingDivergedError.
                    q_from, q_count = iteration + 1, 0
                    q_consumed0 = consumed_samples
                    while iteration < rollback_at:
                        calc.update(consumed_samples)
                        if hasattr(train_iterator, "num_microbatches"):
                            train_iterator.num_microbatches = \
                                calc.num_microbatches
                        try:
                            next(train_iterator)
                        except StopIteration:
                            break  # stream shorter than the window
                        iteration += 1
                        consumed_samples += calc.global_batch_size
                        q_count += 1
                        if watchdog is not None:
                            watchdog.heartbeat()
                    if q_count:
                        quarantined_total += q_count
                        # actual consumed delta, not q_count ×
                        # global_batch_size: under rampup the batch
                        # size changes per step inside the window
                        q_samples = consumed_samples - q_consumed0
                        quarantine_log.append({
                            "from_iteration": q_from,
                            "to_iteration": iteration,
                            "samples": q_samples,
                            "rollback": guard.rollbacks,
                        })
                        # the skipped window counts as completed (empty)
                        # iterations — keep state.iteration (lr
                        # schedule, logs) aligned with the loop clock
                        state = TrainState(
                            params=state.params,
                            opt_state=state.opt_state,
                            iteration=jnp.asarray(iteration, jnp.int32))
                        print_rank_0(
                            f"divergence guard: quarantined iterations "
                            f"[{q_from}, {iteration}] ({q_count} steps, "
                            f"{q_samples} samples) — exact data order "
                            "replayed, poison window skipped "
                            "deterministically")
                    data_state_now = _iter_state(train_iterator)
                    if (cfg.data.num_workers > 0
                            and cfg.training.rampup_batch_size is None
                            and not isinstance(train_iterator,
                                               PrefetchIterator)):
                        train_iterator = PrefetchIterator(
                            train_iterator)
                interval_t0 = time.perf_counter()
                interval_iters = 0
                continue

            if stop_exc is not None:
                # exhaustion, now with the window drained and no
                # rollback ordered by the replay — surface it as the
                # step-exact path did
                raise stop_exc

            if log_due:
                dt = (time.perf_counter() - interval_t0) / max(interval_iters, 1)
                toks = calc.global_batch_size * seq_len / dt
                line = training_log(last_metrics, iteration,
                                    consumed_samples, dt, toks,
                                    writer, skipped_total, nan_total,
                                    quarantined_total)
                print_rank_0(line)
                if cfg.training.log_timers_to_tensorboard:
                    timers.write(["train-step"], writer, iteration,
                                 reset=False)
                print_rank_0(timers.log())
                interval_t0 = time.perf_counter()
                interval_iters = 0

            if eval_due:
                if eval_step_fn is None:
                    sk = step_kwargs or {}
                    eval_step_fn = _make_eval_step(
                        cfg, mesh, loss_fn=sk.get("loss_fn"),
                        axes_fn=sk.get("axes_fn"))
                # eval time is unrelated to step health: suspend the
                # step deadline for its duration
                with (watchdog.suspend() if watchdog is not None
                      else _nullcontext()), span("train/eval"):
                    results = evaluate(state, valid_iterator,
                                       eval_step_fn,
                                       cfg.training.eval_iters,
                                       mesh=mesh, batch_sh=batch_sh)
                if results is not None:
                    print_rank_0(f"validation at iteration {iteration}: "
                                 f"{results}")
                    for k, v in results.items():
                        writer.add_scalar(f"lm-loss-validation/{k}", v,
                                          iteration)

            should_save = save_due
            # the SAME exit decision the flush saw (exit_due above);
            # re-read the duration clock and SIGTERM once the window is
            # drained — an eval/save sweep above can burn minutes past
            # the budget the pre-sweep reading missed, and exiting on
            # the fresh reading is safe exactly when no unobserved
            # steps would be dropped
            exiting = exit_due
            if not exiting and len(window) == 0:
                if signals.received:
                    exit_msgs.append(
                        "SIGTERM received: checkpointing and exiting")
                if trcfg.exit_duration_in_mins is not None:
                    mins = (time.perf_counter() - t_start) / 60.0
                    if mins > trcfg.exit_duration_in_mins:
                        exit_msgs.append(f"exiting after {mins:.1f} min "
                                         "(exit_duration)")
                exiting = bool(exit_msgs)
            for msg in exit_msgs:
                print_rank_0(msg)
            if should_save or (exiting and save_fn is not None):
                # a slow sync save is not a hung STEP — suspend the
                # deadline while it runs
                with (watchdog.suspend() if watchdog is not None
                      else _nullcontext()), span("train/save"):
                    _call_save_fn(save_fn, state, iteration,
                                  consumed_samples, data_state_now,
                                  quarantine_log)
            if exiting:
                break
    finally:
        first_step.__exit__(None, None, None)  # a run that never flushed
        if watchdog is not None:
            watchdog.stop()
        # flush an in-flight profiler trace so early exits still produce it
        if trace_active:
            jax.profiler.stop_trace()
        if isinstance(train_iterator, PrefetchIterator):
            train_iterator.close()  # stop the producer, free its buffers
        # publish any in-flight async checkpoint even on abnormal
        # exit: the write is durable, only the tracker is pending
        from megatron_tpu.training.checkpointing import \
            finalize_async_saves
        finalize_async_saves()
    writer.flush()
    return state, consumed_samples


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False


def _make_eval_step(cfg: MegatronConfig, mesh=None, loss_fn=None,
                    axes_fn=None):
    """Jitted eval loss with the SAME mesh/sharding treatment as the train
    step — without in_shardings, eval of a sharded state would re-layout or
    OOM (round-1 VERDICT item 10). pp>1 evaluates through the pipelined
    loss so the stage-sharded params are consumed in place. A custom
    `loss_fn` (BERT/T5/ICT families, make_train_step contract) replaces
    the GPT lm loss; `axes_fn` supplies its param axes."""
    from megatron_tpu.models import language_model as lm
    rope = lm.make_rope(cfg.model)
    pipelined = (mesh is not None and cfg.parallel.pipeline_parallel > 1
                 and loss_fn is None)

    def eval_step(params, batch):
        if loss_fn is not None:
            n_micro = jax.tree.leaves(batch)[0].shape[0]

            def body(acc, mb):
                return acc + loss_fn(params, mb, None), None

            total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                    batch)
            return total / n_micro
        tokens = batch["tokens"]
        n_micro = tokens.shape[0]
        mask = batch.get("loss_mask")
        if mask is None:
            mask = jnp.ones((n_micro, tokens.shape[1], tokens.shape[2] - 1),
                            jnp.float32)
        if pipelined:
            from megatron_tpu.parallel.pipeline import pipeline_loss_fn
            return pipeline_loss_fn(
                params, tokens, cfg.model, mesh,
                vpp=cfg.parallel.virtual_pipeline_chunks,
                loss_mask=mask, rope=rope, deterministic=True)

        def body(acc, xs):
            tok, m = xs
            loss = lm.loss_fn(params, tok, cfg.model, loss_mask=m,
                              rope=rope, deterministic=True)
            return acc + loss, None

        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                (tokens, mask))
        return total / n_micro

    if mesh is None:
        return jax.jit(eval_step)

    from jax.sharding import NamedSharding, PartitionSpec as P
    from megatron_tpu.parallel import sharding as shd
    from megatron_tpu.training.train_step import (_MeshContextStep,
                                                  param_shardings)
    rules = shd.make_logical_rules(cfg.parallel.sequence_parallel,
                                      expert_axis=cfg.parallel.expert_axis)

    def eval_with_ctx(params, batch):
        with shd.activation_shardings(mesh, rules):
            return eval_step(params, batch)

    jitted = jax.jit(
        eval_with_ctx,
        in_shardings=(param_shardings(cfg, mesh, rules=rules,
                                      axes_fn=axes_fn),
                      NamedSharding(mesh, P(None, "dp"))),
    )
    return _MeshContextStep(jitted, mesh) if pipelined else jitted
