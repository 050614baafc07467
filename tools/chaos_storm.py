#!/usr/bin/env python
"""chaos_storm: seeded SLO-storm conformance for the brownout ladder.

chaos_mesh answers "does a random legal CONFIG survive a random fault
storm?". This tool answers the orthogonal question: "does a fixed
config survive a random LOAD storm within its SLOs — and degrade the
way the ladder promises while it does?". One seed derives a
trace-driven workload (bursty/Poisson arrivals, multi-turn sessions,
adapter skew, prompt-length and decode-length mixtures) which is
replayed at several OFFERED-LOAD multiples of the engine's measured
sustainable rate (the `--arms` sweep, default 0.5x/1x/2x), against an
engine running the full degradation ladder (`degrade_ladder=4`,
docs/serving.md "Overload, degradation & SLO conformance").

Laws checked per seed (serving/invariants.py perf laws 8-11, plus the
structural sweep):

  - slo_bounds      TTFT bounded at the 1x (target-utilization) arm,
                    per-request mean ITL p99 bounded across ALL arms.
                    Bounds derive from a serial calibration phase, with
                    generous slack: CPU jitter is noise, a stalled loop
                    is a regression.
  - goodput_floor   completed-token goodput stays above a floor of the
                    generated total even while the 2x arm sheds.
  - shed_monotone   shed fraction is non-decreasing in offered load
                    across arms (a harness tolerance absorbs run-to-run
                    scheduling noise).
  - degrade_revert  the polled brownout-level series stays within
                    [0, max_level], RISES under the 2x arm, and is
                    fully back at level 0 after the storm drains —
                    brownout, not blackout, and no sticky degradation.
  - zero stranded   every submitted-and-admitted future resolves.
  - token_exact     every COMPLETED request matches the serial oracle
                    for its OWN effective config: a level-2 clamp
                    rewrites max_new_tokens/best_of at admission, so
                    the oracle keys off the request object's fields,
                    not the caller's — degraded output is shorter,
                    never different.

`--inject_slo_regression` arms a real serve_delay fault (an 8s engine
loop stall mid-storm) and REQUIRES the SLO law to catch it, printing
the one-line seed repro — the checker-not-vacuous pin for the perf
laws, same contract as chaos_mesh's `--inject_violation`.

Every record carries the seed + full repro line; `--smoke` runs the
fixed seed set of the slow test tier.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import random
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from megatron_tpu.utils.compile_cache import ensure_compile_cache  # noqa: E402
from tools import chaos_common as cc  # noqa: E402

N_DEVICES = 4

# smoke = the bench-extras / slow-tier gate: plain greedy storm, a
# speculative engine (exercises the level-1 spec-off rung bit-exactly),
# and an adapter-skewed multi-tenant storm (fp32 activations per the
# oracle-numerics rule in chaos_common.auto_compute_dtype)
SMOKE_SEEDS = [(17, ()), (29, ("spec",)), (41, ("adapters",))]

DEFAULT_ARMS = (0.5, 1.0, 2.0)
SHED_TOLERANCE = 0.15     # adjacent-arm shed-fraction noise allowance
GOODPUT_FLOOR = 0.5
LORA_RANK, LORA_ALPHA = 4, 8.0


# ---------------------------------------------------------------------
# seeded config + workload trace
# ---------------------------------------------------------------------
def sample_config(rng: random.Random, require=()):
    """Serving kwargs for the stormed engine. Unlike chaos_mesh this is
    mostly FIXED — the storm varies load, not topology — but the spec /
    adapter axes stay seeded so the ladder's level-1 and level-2 rungs
    meet real traffic. Thresholds are lowered from the production
    defaults so a 2x arm on the tiny CPU model actually climbs the
    ladder within a smoke-sized trace."""
    kw = {
        "num_slots": 2,
        "max_queue": rng.choice([6, 8]),
        "max_len": 96,
        "shed_on_overload": True,
        "priority_levels": 2,
        "degrade_ladder": 4,
        "degrade_raise_at": (0.25, 0.5, 1.0, 2.0),
        "degrade_hysteresis": 0.5,
        "degrade_dwell_up": 2,
        "degrade_dwell_down": 4,
        "degrade_max_new_tokens": 6,
        # engine-side SLO counters: generous wall-clock bounds (the
        # harness-side calibrated bounds are the real law; these pin
        # that the /metrics counters wire end to end)
        "slo_ttft_ms": 30_000.0,
        "slo_itl_p99_ms": 30_000.0,
    }
    if "spec" in require or (not require and rng.random() < 0.3):
        kw["speculative_k"] = 2
    if "adapters" in require:
        kw["adapter_slots"] = 2
    return kw


def build_trace(rng: random.Random, serving_kw: dict, n_requests: int,
                new_tokens: int, adapters=()):
    """The seeded workload trace: a list of spec dicts replayed (with
    arm-scaled interarrival gaps) by every arm. Axes: bursty arrivals
    (burst_every/burst_len), prompt-length mixture, decode-length
    mixture, priority skew (70% best-effort — the level-3 shed class),
    adapter skew (80/20 toward one hot tenant), a multi-turn session
    fraction (follow-ups extend an earlier request's prompt with its
    completion), and a small n-best fan-out fraction (meets the level-2
    best_of clamp)."""
    greedy_only = bool(serving_kw.get("speculative_k"))
    max_len = serving_kw["max_len"]
    adapters = list(adapters)
    specs = []
    for i in range(n_requests):
        long_prompt = rng.random() < 0.3
        plen = rng.randint(16, 28) if long_prompt else rng.randint(4, 8)
        spec = {
            "prompt": [rng.randrange(1, 128) for _ in range(plen)],
            "max_new_tokens": (new_tokens if rng.random() < 0.7
                               else max(2, new_tokens // 2)),
            "seed": rng.randrange(1 << 16),
            "priority": 1 if rng.random() < 0.3 else 0,
            "adapter_id": None,
            "n": 1, "best_of": None,
            "session_of": None,
            # seeded-stochastic rows are oracle-exact EXCEPT under
            # speculation (chaos_common.serial_oracle contract), so a
            # spec engine storms greedy
            "temperature": (0.0 if greedy_only or rng.random() < 0.6
                            else 0.8),
        }
        if adapters and rng.random() < 0.5:
            # 80/20 skew: one hot tenant, a cold tail
            spec["adapter_id"] = (adapters[0] if rng.random() < 0.8
                                  else rng.choice(adapters))
        if i >= 2 and rng.random() < 0.25:
            spec["session_of"] = rng.randrange(i)  # multi-turn follow-up
        elif spec["priority"] and rng.random() < 0.3:
            spec["n"], spec["best_of"] = 1, 2     # small n-best fan-out
        # admission guard: prompt + decode must fit the pool row even
        # after a session follow-up extends the prompt
        spec["prompt"] = spec["prompt"][:max_len - new_tokens - 16]
        specs.append(spec)
    # arrival schedule in UNITS of the sustainable interarrival gap:
    # Poisson (exponential gaps) with periodic bursts arriving back to
    # back — the p99-ITL-under-burst law needs real bursts
    gaps, burst_every, burst_len = [], rng.randint(5, 8), rng.randint(3, 4)
    for i in range(n_requests):
        in_burst = (i % burst_every) < burst_len and i > 0
        gaps.append(0.0 if in_burst else rng.expovariate(1.0))
    return specs, gaps


# ---------------------------------------------------------------------
# serial oracle (effective-config keyed)
# ---------------------------------------------------------------------
def make_oracle(gen, adapter_factors: dict):
    """`fn(req) -> expected tokens` for invariants.check_token_exact.
    Keys the serial reference off the REQUEST's own fields — after a
    level-2 clamp those are the effective (rewritten) max_new_tokens
    and fan-out, which is exactly the contract: degraded completions
    are token-exact vs their own effective config's serial run."""
    from megatron_tpu.inference.generation import (Generator,
                                                   SamplingParams)
    gens, cache = {None: gen}, {}

    def _gen_for(adapter_id):
        if adapter_id not in gens:
            from megatron_tpu.training.lora import merge_lora
            params = merge_lora(gen.params, adapter_factors[adapter_id],
                                gen.cfg, LORA_RANK, LORA_ALPHA)
            gens[adapter_id] = Generator(params, gen.cfg,
                                         eos_id=-1, pad_id=0)
        return gens[adapter_id]

    def want(req):
        sp = req.sampling
        key = (req.adapter_id, tuple(req.prompt), req.max_new_tokens,
               req.seed, (sp.temperature, sp.top_k, sp.top_p))
        if key not in cache:
            t, lens, _ = _gen_for(req.adapter_id).generate(
                [list(req.prompt)], req.max_new_tokens,
                sampling=SamplingParams(temperature=sp.temperature,
                                        top_k=sp.top_k, top_p=sp.top_p),
                seed=req.seed)
            cache[key] = t[0, :lens[0]].tolist()
        return cache[key]

    return want


# ---------------------------------------------------------------------
# storm driver
# ---------------------------------------------------------------------
class _LevelPoller:
    """Background sampler of health()["degrade_level"] — the series the
    degrade_revert law judges. 10ms cadence is well under the dwell
    window, so no transition can slip between samples unseen."""

    def __init__(self, engine, period_s: float = 0.01):
        self.levels, self._stop = [], threading.Event()
        self._t = threading.Thread(
            target=self._run, args=(engine, period_s), daemon=True)

    def _run(self, engine, period_s):
        while not self._stop.is_set():
            self.levels.append(int(engine.health()["degrade_level"]))
            time.sleep(period_s)

    def __enter__(self):
        self._t.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._t.join(timeout=5.0)


def calibrate(engine, rng: random.Random, new_tokens: int) -> float:
    """Measured per-request service time (s) on the quiet engine —
    warmup (compile) excluded. The sustainable interarrival gap at
    1x offered load is service_time / num_slots."""
    warm = engine.submit([1, 2, 3], new_tokens)
    warm.result(timeout=120.0)
    times = []
    for _ in range(3):
        t0 = time.monotonic()
        r = engine.submit([rng.randrange(1, 128) for _ in range(6)],
                          new_tokens, seed=rng.randrange(1 << 16))
        r.result(timeout=120.0)
        times.append(time.monotonic() - t0)
    return max(sum(times) / len(times), 1e-3)


def run_arm(engine, specs, gaps, mult: float, base_gap_s: float):
    """Replay the trace at `mult` x the sustainable rate. Returns
    (tracked GenRequests, per-arm stats). Submit-time 429s (queue full
    / brownout shed) are the SHED bucket; Retry-After hints are checked
    >= 1s inline — the herd-clamp satellite, enforced where the storm
    actually sheds."""
    from megatron_tpu.serving import SamplingOptions
    from megatron_tpu.serving.scheduler import QueueFullError
    tracked, stats = [], {"mult": mult, "submitted": 0, "shed": 0,
                          "bad_retry_after": 0, "stranded": 0,
                          "completed": 0, "failed": 0,
                          "ttft_ms": [], "itl_ms": []}
    done_prompts = {}   # trace index -> (prompt, generated) for sessions
    t_next = time.monotonic()
    for i, (spec, gap) in enumerate(zip(specs, gaps)):
        t_next += gap * base_gap_s / max(mult, 1e-6)
        time.sleep(max(0.0, t_next - time.monotonic()))
        prompt = list(spec["prompt"])
        parent = done_prompts.get(spec["session_of"])
        if parent is not None:
            # multi-turn: the follow-up turn carries the whole prior
            # exchange (prompt + completion) plus the new user tokens
            prompt = (parent[0] + parent[1])[-24:] + prompt[:6]
        stats["submitted"] += 1
        try:
            r = engine.submit(
                prompt, spec["max_new_tokens"],
                SamplingOptions(temperature=spec["temperature"]),
                seed=spec["seed"], priority=spec["priority"],
                adapter_id=spec["adapter_id"],
                n=spec["n"], best_of=spec["best_of"])
        except QueueFullError as e:   # OverloadShedError subclasses it
            stats["shed"] += 1
            if e.retry_after is not None and e.retry_after < 1:
                stats["bad_retry_after"] += 1
            continue
        tracked.append((i, r))
    for i, r in tracked:
        try:
            r.result(timeout=120.0)
        except TimeoutError:
            stats["stranded"] += 1
            continue
        except Exception:  # noqa: BLE001 — typed-enough: it RESOLVED
            stats["failed"] += 1
            continue
        stats["completed"] += 1
        children = getattr(r, "children", None) or [r]
        done_prompts[i] = (list(children[0].prompt),
                           list(children[0].generated))
        for c in children:
            if c.ttft is not None:
                stats["ttft_ms"].append(c.ttft * 1e3)
            gen = len(c.generated)
            if gen > 1 and c.finish_time and c.first_token_time:
                stats["itl_ms"].append(
                    (c.finish_time - c.first_token_time) * 1e3
                    / (gen - 1))
    stats["shed_frac"] = stats["shed"] / max(stats["submitted"], 1)
    return [r for _, r in tracked], stats


def run_one(seed: int, require=(), n_requests: int = 10,
            new_tokens: int = 8, arms=DEFAULT_ARMS,
            inject_slo_regression: bool = False) -> dict:
    """One seeded storm across all arms. record["ok"] is the verdict,
    record["repro"] the one-line reproduction."""
    from megatron_tpu.resilience import FaultInjector, use_fault_injector
    from megatron_tpu.serving import invariants

    rng = random.Random(seed)
    t0 = time.monotonic()
    arms = tuple(sorted(arms))
    repro = (f"python tools/chaos_storm.py --seed {seed}"
             + (f" --require {','.join(require)}" if require else "")
             + f" --requests {n_requests} --new_tokens {new_tokens}"
             + f" --arms {','.join(str(a) for a in arms)}"
             + (" --inject_slo_regression" if inject_slo_regression
                else ""))
    serving_kw = sample_config(rng, require)
    record = {"metric": "storm_requests_conformant",
              "unit": ("completed requests, every perf + structural "
                       "law green"),
              "seed": seed, "repro": repro, "require": list(require),
              "config": {k: v for k, v in serving_kw.items()
                         if k not in ("slo_ttft_ms", "slo_itl_p99_ms")},
              "completed": False, "ok": False, "violations": []}

    engine, gen = cc.tiny_engine(serving_kw)
    adapter_factors = {}
    try:
        if serving_kw.get("adapter_slots"):
            adapter_factors = cc.make_adapters(gen.cfg, 2, rank=LORA_RANK)
            for aid, factors in sorted(adapter_factors.items()):
                engine.register_adapter(aid, factors=factors,
                                        rank=LORA_RANK, alpha=LORA_ALPHA)
        specs, gaps = build_trace(rng, serving_kw, n_requests,
                                  new_tokens,
                                  adapters=sorted(adapter_factors))
        svc_s = calibrate(engine, rng, new_tokens)
        base_gap_s = svc_s / serving_kw["num_slots"]
        # calibrated bounds, generous: CPU scheduling jitter must not
        # page anyone; a wedged loop / O(n) regression must
        ttft_bound_ms = 30 * svc_s * 1e3 + 5_000
        itl_bound_ms = 50 * svc_s * 1e3 / max(new_tokens, 1) + 2_000
        injector = None
        if inject_slo_regression:
            # a real mid-storm regression: stall the engine loop 8s
            # early in the first arm (the injector's serve-step counter
            # starts at install, after calibration). Everything queued
            # behind the stall blows a tightened TTFT bound — the law
            # MUST catch it (checker-not-vacuous)
            injector = FaultInjector(serve_delay_calls={5: 8.0})

        all_reqs, arm_stats = [], []
        with _LevelPoller(engine) as poller:
            ctx = (use_fault_injector(injector) if injector is not None
                   else _null_ctx())
            with ctx:
                for mult in arms:
                    reqs, stats = run_arm(engine, specs, gaps, mult,
                                          base_gap_s)
                    all_reqs.extend(reqs)
                    arm_stats.append(stats)
            # drain: the revert law needs the ladder walked back to 0,
            # which the idle engine loop does on dwell_down evaluations
            deadline = time.monotonic() + 30.0
            while (engine.health()["degrade_level"]
                   and time.monotonic() < deadline):
                time.sleep(0.05)
            time.sleep(0.1)   # a final settled sample for the series

        # ---- laws ---------------------------------------------------
        sweep = cc.invariant_sweep(engine, reqs=all_reqs,
                                   oracles=[make_oracle(gen,
                                                        adapter_factors)],
                                   strict=True, timeout=120.0)
        violations = list(sweep.get("violations", []))
        stranded = sum(s["stranded"] for s in arm_stats)
        if stranded:
            violations.append(f"[stranded] {stranded} futures never "
                              "resolved")
        bad_ra = sum(s["bad_retry_after"] for s in arm_stats)
        if bad_ra:
            violations.append(f"[retry_after] {bad_ra} shed responses "
                              "hinted Retry-After < 1s")

        if inject_slo_regression:
            # the stall fires in the FIRST arm, so the law judges the
            # whole storm's TTFT series against the tightened bound
            samples = {"ttft_all": [v for s in arm_stats
                                    for v in s["ttft_ms"]]}
            bounds = {"ttft_all": (0.9, 4_000.0)}
        else:
            target = next((s for s in arm_stats if s["mult"] == 1.0),
                          arm_stats[len(arm_stats) // 2])
            samples = {"ttft_1x": target["ttft_ms"],
                       "itl_all": [v for s in arm_stats
                                   for v in s["itl_ms"]]}
            bounds = {"ttft_1x": (0.95, ttft_bound_ms),
                      "itl_all": (0.99, itl_bound_ms)}
        slo_violated = False
        try:
            record["slo"] = invariants.check_slo_bounds(samples, bounds)
        except invariants.InvariantViolation as e:
            slo_violated = True
            if not inject_slo_regression:
                violations.append(str(e))
        if not inject_slo_regression:
            # load-shape laws only hold for an UNfaulted storm (the
            # injected 8s stall legitimately skews arm-0 shedding)
            for check, kwargs in (
                    (invariants.check_shed_monotone,
                     {"arms": [(s["mult"], s["shed_frac"])
                               for s in arm_stats],
                      "tolerance": SHED_TOLERANCE}),
                    (invariants.check_goodput_floor,
                     {"snapshot": engine.metrics.snapshot(),
                      "floor": GOODPUT_FLOOR}),
                    (invariants.check_degrade_revert,
                     {"levels": poller.levels,
                      "max_level": serving_kw["degrade_ladder"],
                      "require_rise": max(arms) >= 2.0})):
                try:
                    check(**kwargs)
                except invariants.InvariantViolation as e:
                    violations.append(str(e))

        record["arms"] = [{k: v for k, v in s.items()
                          if k not in ("ttft_ms", "itl_ms")}
                          for s in arm_stats]
        record["degrade_peak"] = max(poller.levels or [0])
        record["degrade_final"] = (poller.levels or [0])[-1]
        snap = engine.metrics.snapshot()
        record["counters"] = {
            k: snap[k]
            for k in ("degrade_transitions", "slo_ttft_violations",
                      "slo_itl_violations", "goodput_tokens",
                      "requests_shed")}
        record["bounds_ms"] = {"ttft_1x": round(ttft_bound_ms, 1),
                               "itl_all": round(itl_bound_ms, 1)}
        record["value"] = sum(s["completed"] for s in arm_stats)
        record["violations"] = violations
        if inject_slo_regression:
            # verdict inverts: ok iff the injected stall WAS caught
            record["injected_caught"] = slo_violated
            record["ok"] = slo_violated and not violations
        else:
            record["ok"] = not violations
        record["completed"] = record["ok"]
    finally:
        engine.close()
    record["wall_s"] = round(time.monotonic() - t0, 1)
    if not record["ok"]:
        print(f"chaos_storm: VIOLATION — repro: {record['repro']}",
              file=sys.stderr)
        for v in record["violations"]:
            print(f"  {v}", file=sys.stderr)
    return record


class _null_ctx:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def run_smoke(n_requests: int, new_tokens: int) -> dict:
    runs = [run_one(seed, require, n_requests=n_requests,
                    new_tokens=new_tokens)
            for seed, require in SMOKE_SEEDS]
    # the vacuity pin rides along: one injected regression MUST trip
    inj = run_one(SMOKE_SEEDS[0][0], SMOKE_SEEDS[0][1],
                  n_requests=n_requests, new_tokens=new_tokens,
                  inject_slo_regression=True)
    runs.append(inj)
    ok = all(r["ok"] for r in runs)
    return {
        "metric": "storm_seeds_green",
        "value": sum(1 for r in runs if r["ok"]),
        "unit": (f"seeded storms with every perf law green (of "
                 f"{len(runs)}: plain/speculative/adapters + one "
                 "injected-regression catch)"),
        "completed": ok,
        "ok": ok,
        "seed": SMOKE_SEEDS[0][0],
        "seeds": [list(s) for s in SMOKE_SEEDS],
        "runs": runs,
        "wall_s": round(sum(r["wall_s"] for r in runs), 1),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=None,
                    help="run ONE seeded storm (config biases + "
                         "workload trace + arrival schedule all derive "
                         "from it)")
    ap.add_argument("--require", type=str, default="",
                    help="comma-separated config biases (part of the "
                         "repro line): spec, adapters")
    ap.add_argument("--smoke", action="store_true",
                    help="fixed seed set for bench extras / CI: plain, "
                         "speculative, and adapter-skew storms plus "
                         "one injected-SLO-regression catch")
    ap.add_argument("--requests", type=int, default=10,
                    help="trace length per arm")
    ap.add_argument("--new_tokens", type=int, default=8,
                    help="max decode length per request")
    ap.add_argument("--arms", type=str, default="0.5,1.0,2.0",
                    help="offered-load multiples of the calibrated "
                         "sustainable rate, comma-separated")
    ap.add_argument("--inject_slo_regression", action="store_true",
                    help="stall the engine loop mid-storm and REQUIRE "
                         "the SLO law to catch it (exit 0 iff caught) "
                         "— the perf-law checker-not-vacuous pin")
    ap.add_argument("--out", type=str, default=None,
                    help="also write the JSON record here")
    args = ap.parse_args(argv)

    cc.force_host_devices(N_DEVICES)
    ensure_compile_cache()
    require = tuple(t for t in args.require.split(",") if t)
    arms = tuple(float(a) for a in args.arms.split(","))

    if args.smoke:
        record = run_smoke(args.requests, args.new_tokens)
    else:
        seed = args.seed if args.seed is not None else 17
        record = run_one(seed, require, n_requests=args.requests,
                         new_tokens=args.new_tokens, arms=arms,
                         inject_slo_regression=args.inject_slo_regression)
    cc.emit_record(record, args.out, seed=record.get("seed", 0))
    return 0 if record["completed"] else 1


if __name__ == "__main__":
    sys.exit(main())
