"""One-command runner for the queued serving on-chip A/Bs.

The serving perf claims sit in an ordered PERF_NOTES queue with no
accelerator number behind them. This tool folds the pending SERVING
queue into one run, each A/B a child process of its own (this parent
imports no JAX, so it never holds the chip its children need):

  item 8  — tools/bench_block_attn.py  (block-native kernel vs the
            resolve/scatter bracket)
  item 9  — tools/bench_lora.py       (multi-tenant adapter gather
            cost: base vs one vs mixed)
  item 10 — tools/bench_disagg.py     (interleave vs disaggregated +
            serving-tp decode scaling)
  item 12 — tools/bench_phase_topology.py (symmetric vs asymmetric
            prefill_tp:decode_tp splits on one device budget)
  item 13 — tools/bench_pp_serving.py  (layer-staged decode: pp=2 at
            waves 1 and 2 vs the mono engine, bubble vs claw-back)

Each tool runs as its own subprocess with an independent timeout, its one-line JSON record is
collected, and this tool emits ONE combined record — `results[<name>]`
is the child's record, or `{"error"/"timeout": ...}` when it failed —
plus per-tool rc/wall so the PERF_NOTES queue can be marked off from a
single log line. `--smoke` passes each child its smoke/tiny arguments
(the CPU harness tier); on chip, run it bare.

  python tools/bench_serving_queue.py [--smoke] [--only a,b]
                                      [--timeout_s T] [--out FILE]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# (name, script, smoke args, full args) — queue order: cheapest first so
# a mid-window kill still leaves records
QUEUE = [
    ("block_attn", "bench_block_attn.py", ["--smoke"], []),
    ("lora", "bench_lora.py", ["--smoke"], []),
    ("disagg", "bench_disagg.py", ["--smoke"], []),
    # per-phase topology splits (symmetric vs decode-heavy vs
    # prefill-heavy on one budget; greedy arms token-agree)
    ("phase_topology", "bench_phase_topology.py", ["--smoke"], []),
    # structured output + COW n-best (constrained-vs-free mask-upload
    # cadence, n=1x4-vs-n=4 one-prefill fan-out)
    ("structured", "bench_structured.py", ["--smoke"], []),
    # pipeline-sharded serving (mono vs serving_pp=2 at waves 1 and 2;
    # greedy arms token-agree, bubble gauge pinned to (S-1)/(W+S-1))
    ("pp_serving", "bench_pp_serving.py", ["--smoke"], []),
]


def main(argv=None):
    p = argparse.ArgumentParser("bench_serving_queue",
                                description=__doc__)
    p.add_argument("--out", default="/tmp/bench_serving_queue.log")
    p.add_argument("--smoke", action="store_true",
                   help="pass each child its smoke arguments")
    p.add_argument("--only", type=str, default=None,
                   help="comma-separated subset of queue names "
                        f"({','.join(n for n, *_ in QUEUE)})")
    p.add_argument("--timeout_s", type=float, default=600.0,
                   help="per-tool budget (independent — one hang "
                        "cannot eat the window)")
    args = p.parse_args(argv)

    here = os.path.dirname(os.path.abspath(__file__))
    only = (set(x.strip() for x in args.only.split(","))
            if args.only else None)
    results, runs = {}, []
    for name, script, smoke_args, full_args in QUEUE:
        if only is not None and name not in only:
            continue
        child_out = f"/tmp/bench_queue_{name}.log"
        try:
            # a stale record from a previous run must never pass for
            # this run's result when the child crashes before writing
            os.remove(child_out)
        except FileNotFoundError:
            pass
        cmd = [sys.executable, os.path.join(here, script),
               "--out", child_out] \
            + (smoke_args if args.smoke else full_args)
        t0 = time.monotonic()
        try:
            proc = subprocess.run(cmd, stdout=subprocess.PIPE,
                                  stderr=sys.stderr,
                                  timeout=args.timeout_s)
            rc = proc.returncode
        except subprocess.TimeoutExpired:
            rc = None
        wall = time.monotonic() - t0
        runs.append({"tool": script, "name": name, "rc": rc,
                     "wall_s": round(wall, 1)})
        if rc is None:
            results[name] = {"timeout": args.timeout_s}
            continue
        try:
            with open(child_out) as f:
                results[name] = json.loads(f.read().splitlines()[-1])
        except Exception as e:  # noqa: BLE001 — a failed child's record
            results[name] = {"error": f"rc={rc}: {e!r}"}
        print(f"bench_serving_queue: {name} rc={rc} "
              f"({wall:.1f}s)", file=sys.stderr)

    # deliberately NO jax import in the parent: on TPU the parent
    # holding the chip would wedge every child's backend init — the
    # children report their own device kind in their records
    device = next((r.get("device") for r in results.values()
                   if isinstance(r, dict) and "device" in r), "unknown")
    record = {
        "bench": "serving_queue",
        "device": device,
        "smoke": bool(args.smoke),
        "runs": runs,
        "results": results,
        "all_green": all(r["rc"] == 0 for r in runs) and bool(runs),
    }
    line = json.dumps(record)
    print(line, flush=True)
    with open(args.out, "w") as f:
        f.write(line + "\n")
    return 0 if record["all_green"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
