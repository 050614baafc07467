"""Driver `train_job`: a pre-training job through the program's own loop.

The job (a file under `benchmark/traffic/`) is a list of `finetune.py`
arguments plus a description of the corpus. Set-up writes the corpus from
the seed in the indexed format, builds the data pipeline with
`finetune.build_data`, makes the train state on the device(s) with
`finetune.init_state`, and hands both to `training.loop.train` in its default
asynchronous mode, on the main thread, with no `save_fn`.

Reading the clock. The loop runs ahead of the device and blocks only where
it flushes its metrics window, in `loop._device_fetch` (the seam the repo's
own `tests/test_async_dispatch.py` and `tools/bench_sync.py` wrap). The clock
is read as that call returns: the device has then finished a known step. The
window opens at the job's `warm_flushes`-th flush and closes at the first
flush `--seconds` or more later; the rate is all the steps between the two
over all the time between them. Whatever the loop does in that time (data,
dispatch, the flush itself) is inside.
"""
from __future__ import annotations

import math
import os
import shutil
import tempfile
import time

import numpy as np

from benchmark import flops, loadgen, trace as trace_mod
from benchmark.context import Context, Run, span, start_profiler

# Loss of the loop's first step against the float32 reference on the same
# parameters and micro-batches. The step computes in bf16 (8 significant
# bits) with float32 accumulation and softmax; per-token errors of a few
# 1e-2 in a logit are unbiased to first order and the loss is a mean over
# 16,384 tokens, so the two agree to ~1e-3 (PR 22 saw 2e-4 between a
# sharded and an unsharded bf16 forward; PR 24 measured the figures in
# PERF.md §6). A forward in a lower precision than bf16, or one that left
# out a term of the block, moves the loss by far more than 0.02.
TOL_FIRST_LOSS = 0.02


def write_corpus(prefix: str, vocab: int, corpus: dict, seed: int) -> int:
    """Seeded documents of heavy-tailed lengths in the indexed format
    `finetune.py` reads. Tokens are Zipf-distributed over a seeded alphabet
    (id 0, the end-of-document id, is never used), so that a few steps
    lower the loss. Returns the number of tokens written."""
    from megatron_tpu.data.indexed_dataset import IndexedDatasetBuilder
    rng = np.random.default_rng(seed)
    lengths = loadgen.lognormal_quantiles(
        corpus["documents"], corpus["length_median"], corpus["length_sigma"],
        corpus["length_min"], corpus["length_max"])
    rng.shuffle(lengths)
    alphabet = rng.choice(np.arange(1, vocab),
                          size=min(corpus["alphabet"], vocab // 2),
                          replace=False)
    p = 1.0 / np.arange(1, len(alphabet) + 1)
    p /= p.sum()
    tokens = alphabet[rng.choice(len(alphabet), size=int(lengths.sum()), p=p)]
    b = IndexedDatasetBuilder(prefix)
    at = 0
    for n in lengths:
        b.add_item(tokens[at:at + n])
        b.end_document()
        at += n
    b.finalize()
    return int(at)


def job_argv(ctx: Context, corpus_prefix: str) -> list:
    job = ctx.traffic
    iters = job["warm_steps"] + math.ceil(ctx.seconds * job["max_steps_per_s"])
    return [*ctx.config["cli"], *job["cli"],
            "--data_path", corpus_prefix, "--split", "100,0,0",
            "--seed", str(ctx.seed), "--train_iters", str(iters)]


def run(ctx: Context) -> Run:
    import jax
    import jax.numpy as jnp
    import finetune
    from benchmark.reference import falcon as reference
    from megatron_tpu.arguments import parse_cli
    from megatron_tpu.data.samplers import PrefetchIterator
    from megatron_tpu.parallel.mesh import build_mesh
    from megatron_tpu.training import loop

    job = ctx.traffic
    n_dev = len(ctx.devices)
    tmp = tempfile.mkdtemp(prefix="bench-train-")
    try:
        corpus_prefix = os.path.join(tmp, "corpus", "docs")
        os.makedirs(os.path.dirname(corpus_prefix))
        cfg, _ = parse_cli(job_argv(ctx, corpus_prefix), n_devices=n_dev)
        corpus_tokens = write_corpus(corpus_prefix, cfg.model.vocab_size,
                                     job["corpus"], ctx.seed)
        mesh = build_mesh(cfg.parallel) if n_dev > 1 else None  # as finetune
        rng = jax.random.PRNGKey(cfg.training.seed)
        state = finetune.init_state(cfg, mesh, rng)
        train_it = finetune.build_data(cfg, None, 0, mesh=mesh)[0]

        # the reference's loss on the first step's micro-batches, from the
        # parameters the loop is about to donate; a second iterator over
        # the same dataset yields the same first batch
        first = next(finetune.build_data(cfg, None, 0, mesh=mesh)[0])
        ref_loss = float(jax.jit(
            lambda p, t, m: reference.batch_loss(p, t, m, cfg.model))(
                state.params,
                jnp.asarray(first["tokens"].reshape(
                    -1, first["tokens"].shape[-1])),
                jnp.asarray(first["loss_mask"].reshape(
                    -1, first["loss_mask"].shape[-1]))))

        tokens_per_step = cfg.training.global_batch_size * cfg.model.seq_length
        flushes = []     # (clock, steps done, losses of this flush)
        waits = []       # (clock, seconds the loop waited for its batch)
        mark = {"open": None, "close": None, "trace": None}
        trace_dir = os.path.join(tmp, "trace")

        class Stream(PrefetchIterator):
            """The loop's own background prefetch (a PrefetchIterator
            handed in is not wrapped again), with the wait of each pull on
            the clock and an end when the window is over."""
            stop = False

            def __next__(self):
                if self.stop:
                    raise StopIteration
                t0 = time.monotonic()
                with span("data_next"):
                    batch = super().__next__()
                waits.append((t0, time.monotonic() - t0))
                return batch

        stream = Stream(train_it)
        real_fetch = loop._device_fetch

        def fetch(tree):
            with span("flush"):
                out = real_fetch(tree)
            now = time.monotonic()
            done = (flushes[-1][1] if flushes else 0) + len(out)
            flushes.append((now, done, [float(m["lm_loss"]) for m in out]))
            if mark["open"] is None:
                if len(flushes) >= job["warm_flushes"]:
                    mark["open"] = len(flushes) - 1
            elif mark["close"] is None:
                if now - flushes[mark["open"]][0] >= ctx.seconds:
                    mark["close"] = len(flushes) - 1
                    if ctx.trace:     # the traced cycle follows the window
                        start_profiler(trace_dir)
                        mark["trace"] = len(flushes) - 1
                    else:
                        stream.stop = True
            elif mark["trace"] is not None and not stream.stop:
                jax.profiler.stop_trace()
                stream.stop = True
            return out

        loop._device_fetch = fetch
        try:
            loop.train(cfg, stream, None, mesh=mesh, state=state, rng=rng)
        except StopIteration:
            pass                     # the stream's end is the run's end
        finally:
            loop._device_fetch = real_fetch
            stream.close()

        if mark["close"] is None:
            raise SystemExit(
                f"the job ended after {flushes[-1][1] if flushes else 0} "
                f"steps, before a window of {ctx.seconds} s closed: raise "
                "max_steps_per_s in the job file")
        (t0, s0, _), (t1, s1, _) = flushes[mark["open"]], flushes[mark["close"]]
        window_s, steps = t1 - t0, s1 - s0
        tok_s_chip = steps * tokens_per_step / window_s / n_dev
        losses = [x for _, _, ls in flushes for x in ls]
        in_window = [x for f in flushes[mark["open"] + 1:mark["close"] + 1]
                     for x in f[2]]
        compiles = ctx.compiles.between(t0, t1)
        checks = {
            "first_loss": losses[0], "reference_first_loss": ref_loss,
            "first_loss_tolerance": TOL_FIRST_LOSS,
            "first_loss_matches_reference":
                abs(losses[0] - ref_loss) <= TOL_FIRST_LOSS,
            "losses_finite": all(math.isfinite(x) for x in in_window),
            "last_loss": in_window[-1],
            "last_loss_below_first": in_window[-1] < losses[0],
            "compilations_in_window": compiles,
            "steps_in_window": steps, "corpus_tokens": corpus_tokens,
        }
        correct = (checks["first_loss_matches_reference"]
                   and checks["losses_finite"]
                   and checks["last_loss_below_first"] and compiles == 0)
        trace = None
        traced_steps = 0
        if mark["trace"] is not None:
            xplane = trace_mod.find_xplane(trace_dir)
            trace = trace_mod.load(xplane) if xplane else None
            traced_steps = (flushes[mark["trace"] + 1][1]
                            - flushes[mark["trace"]][1])
        return Run(
            correct=correct, attempted=steps, failed=0,
            end_to_end={
                "train_tokens_per_s_per_chip": tok_s_chip,
                "setup_s": ctx.setup_seconds(t0)},
            samples={
                "window_s": window_s, "steps": steps, "chips": n_dev,
                "tokens_per_step": tokens_per_step,
                "data_wait_s": sum(dt for t, dt in waits if t0 <= t < t1),
                "train_flops_per_token": flops.train_flops_per_token(
                    **flops.shapes_of(cfg.model)),
                "traced_steps": traced_steps,
                "micro_batches_per_step": cfg.num_microbatches},
            checks=checks, window_s=window_s, ctx=ctx, trace=trace)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
