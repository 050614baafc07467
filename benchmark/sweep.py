"""Find the knee of a serving cell again: one process, one set-up, the
cell's mix offered at several fixed rates in turn.

    python benchmark/sweep.py --workload falcon-7b.serve-chat --rates 4,6,8,10,12,14

On the chip only. One line of JSON per rate goes to standard output: what
was offered, what was completed, the tails, and the queue left when the
window closed. The knee is the highest rate at which the queue does not
grow: tokens completed keep up with tokens offered and the queue wait stays
a small part of the time to first token. The cell's `rate_rps` is four
fifths of it. Between rates the engine drains, so that one rate's backlog
is not the next one's start.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True, help="requests/s, comma-separated")
    p.add_argument("--seconds", type=float, default=25.0)
    p.add_argument("--seed", type=int, default=2024)
    args = p.parse_args(argv)
    sys.path[:0] = [ROOT]
    from benchmark import loadgen, run as bench_run
    from benchmark.context import Context
    from benchmark.stats import percentile, with_failures

    out = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)
    _, cell, config, mix = bench_run.load_cell(args.workload)
    from megatron_tpu.utils.compile_cache import ensure_compile_cache
    ensure_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("the sweep is a chip measurement: no TPU found")
    ctx = Context(root=ROOT, cell=cell, config=config, traffic=mix, seed=args.seed, seconds=args.seconds,
                  trace=False, devices=jax.devices()[:1], peaks=None,
                  compiles=bench_run.CompileCounter(),
                  t_process_start=bench_run.T_PROCESS_START)
    from benchmark.by_name import load_module
    driver = load_module("drivers", mix["driver"])
    mcfg, params, engine = driver.build_engine(ctx)
    try:
        driver.warm_up(engine, mcfg, mix, args.seed)
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            at = dict(mix, rate_rps=rate)
            arrivals = loadgen.schedule(at, args.seed + i, args.seconds)
            prompts = loadgen.prompts_for(arrivals, mcfg.vocab_size,
                                          args.seed + i)
            m = driver.offer(engine, at, arrivals, prompts, args.seconds,
                             ctx.compiles)
            queue_at_close = engine.queue_depth()
            done = [x for x in m["ttft_s"] if x is not None]
            rec = {
                "rate_rps": rate, "window_s": m["window_s"],
                "attempted": m["attempted"], "failed": m["failed"],
                "tokens_per_s": m["counters"]["tokens_generated"] / m["window_s"],
                "offered_tokens_per_s": sum(
                    a.output_len for a in arrivals
                    if a.phase == "window") / args.seconds,
                "tokens_per_decode_step": m["counters"]["tokens_generated"]
                / max(m["counters"]["decode_steps"], 1),
                "decode_steps_per_s": m["counters"]["decode_steps"] / m["window_s"],
                "ttft_p50_ms": 1e3 * percentile(done, 50) if done else None,
                "ttft_p95_ms": 1e3 * percentile(
                    with_failures(m["ttft_s"], m["run_s"]), 95),
                "tpot_p50_ms": 1e3 * percentile(
                    with_failures(m["tpot_s"], m["run_s"]), 50),
                "tpot_p95_ms": 1e3 * percentile(
                    with_failures(m["tpot_s"], m["run_s"]), 95),
                "queue_wait_p95_ms": 1e3 * percentile(m["queue_wait_s"], 95)
                if m["queue_wait_s"] else None,
                "gen_late_p95_ms": 1e3 * percentile(m["gen_late_s"], 95)
                if m["gen_late_s"] else None,
                "queue_depth_after": queue_at_close,
                "compilations_in_window": m["compilations_in_window"],
                "run_s": m["run_s"]}
            out.write(json.dumps(rec) + "\n")
            out.flush()
            t_drain = time.monotonic() + 120
            while engine.queue_depth() and time.monotonic() < t_drain:
                time.sleep(0.2)
            time.sleep(2.0)
    finally:
        engine.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
