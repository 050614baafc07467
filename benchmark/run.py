"""One run of one benchmark cell: the command `BENCHMARK.json` names.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data found by name: the cell in `BENCHMARK.json`,
its configuration in `benchmark/configs/<config>.json`, its traffic mix or
training job in `benchmark/traffic/<traffic>.json`, the code that drives the
program in `benchmark/drivers/<driver>.py` (named by the traffic file), and
one reader per per-layer metric in `benchmark/layer_metrics/<metric>.py`.
No list of names lives in code. See `benchmark/README.md`.

The last line of standard output is the result, and nothing else is written
there: file descriptor 1 points at standard error for the whole run (the
package logger and the engine's threads write to it), and the one line goes
to the saved descriptor at the end.
"""
from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()       # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(workload: str):
    """(BENCHMARK.json, the cell's entry, its configuration file, its
    traffic file), all found by the names in BENCHMARK.json."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return (bench, cell, load_json(ROOT, entry["file"]),
            load_json(HERE, "traffic", cell["traffic"] + ".json"))


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class CompileCounter:
    """Counts XLA compilations by the time they end. A program that is
    read from the persistent cache counts too: it is a program the window
    had not seen, and loading it takes the place of work."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.ends = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.ends.append(time.monotonic())

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.ends if t0 <= t <= t1)


def device_record(jax, devices) -> dict:
    peaks = [((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": max(peaks)}


def main(argv=None, require_tpu: bool = True) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    # fd 1 -> stderr for the whole run; the result goes to `out`, last
    sys.stdout.flush()
    out = os.fdopen(os.dup(1), "w")
    os.dup2(2, 1)

    bench, cell, config, traffic = load_cell(args.workload)

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    # the program keeps its compile cache where JAX_COMPILATION_CACHE_DIR
    # says, else at <checkout>/.jax_cache: a fixed path inside the checkout
    from megatron_tpu.utils.compile_cache import ensure_compile_cache
    ensure_compile_cache()
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found platform "
                         f"{devices[0].platform!r}; there is no CPU fallback")
    if len(devices) < cell["chips"]:
        raise SystemExit(f"{args.workload} needs {cell['chips']} chips, "
                         f"JAX found {len(devices)}")
    devices = devices[:cell["chips"]]
    peaks = load_json(HERE, "peaks.json").get(devices[0].device_kind)
    if peaks is None and require_tpu:
        raise SystemExit(f"device kind {devices[0].device_kind!r} is not in "
                         "benchmark/peaks.json: add it with its source")

    from benchmark.context import Context
    ctx = Context(root=ROOT, cell=cell, config=config, traffic=traffic,
                  seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace), devices=devices, peaks=peaks,
                  compiles=CompileCounter(),
                  t_process_start=T_PROCESS_START)
    from benchmark.by_name import load_module
    driver = load_module("drivers", traffic["driver"])
    run = driver.run(ctx)

    wanted = bench["per_layer"] if args.trace else bench["end_to_end"]
    metrics = {}
    for m in wanted:
        if not applies(m, args.workload):
            continue
        if args.trace:
            value = load_module("layer_metrics", m["name"]).read(run)
        else:
            value = run.end_to_end.get(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    device = device_record(jax, devices)
    result = {"correct": bool(run.correct), "attempted": int(run.attempted),
              "failed": int(run.failed), "metrics": metrics, "device": device,
              "checks": run.checks, "window_s": run.window_s}
    if args.trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {"device_ops": run.trace.top_ops(10),
                               "idle_gaps": run.trace.idle_gaps(10)}
    sys.stderr.flush()
    out.write(json.dumps(result) + "\n")
    out.flush()
    return 0


if __name__ == "__main__":
    # run as a script, sys.path[0] is benchmark/: its modules are imported
    # as the package `benchmark` from the checkout's root instead
    sys.path[0] = ROOT
    sys.exit(main())
