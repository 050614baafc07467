"""Scripted serving chaos drill: overload + injected engine faults
through a REAL engine, measure that nothing strands.

tests/test_serving.py proves each overload/failure path in isolation;
this tool composes them into ONE run the way a saturated replica's bad
hour would — offered load far above slot capacity, a NaN-poisoned
slot, a wedged decode iteration, a crash-looping step — and asserts
the engine's three survival contracts end-to-end:

1. **no stranded futures**: every submitted request resolves, as a
   completion or a TYPED error (shed/504/503/RuntimeError) — never a
   hang;
2. **hang recovery**: a wedged iteration is detected by the watchdog
   within `engine_step_timeout_s`, the in-flight futures fail, the
   supervisor restarts the loop, and a fresh probe request completes;
3. **crash-loop containment**: when every restart crashes again, the
   circuit breaker trips after `max_engine_restarts`, queued work
   resolves 503, `health()` reports unhealthy, and new submits raise
   EngineUnhealthyError.

Every drill finishes with a system-wide `invariants.check_all` sweep
(serving/invariants.py): the drill's own assertions pin its scenario,
the sweep pins the laws that must hold under ANY scenario (request
conservation, typed terminals, KV accounting, schema, healthz).

Emits ONE BENCH-style JSON record on stdout (and to --out), like
chaos_train.py, so hang-recovery regressions surface in the
`BENCH_*.json` extras. The scaffolding (tiny engine builders, serial
oracles, outcome resolvers) lives in tools/chaos_common.py, shared
with chaos_router.py / chaos_upgrade.py / chaos_mesh.py.

  JAX_PLATFORMS=cpu python tools/chaos_serve.py --smoke [--out FILE]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from megatron_tpu.utils.compile_cache import ensure_compile_cache
from tools.chaos_common import (emit_record, invariant_sweep,
                                make_adapters as _make_adapters,
                                pool_mode as _pool_mode,
                                resolve_all as _resolve_all,
                                tiny_engine as _tiny_engine)


def overload_drill(new_tokens: int, spec_k: int = 0,
                   pool_kwargs=None, n_adapters: int = 2) -> dict:
    """Offered load >> slot capacity with priorities, early shedding,
    preemption, one NaN-poisoned slot — speculative decoding when
    spec_k > 0, and `n_adapters` LoRA adapters INTERLEAVED through the
    traffic (multi-tenant serving under chaos). Contract: every
    submitted future resolves; sheds fail fast at submit; at least one
    preemption fires and every preempted request still resolves; and
    every request that COMPLETES — preempted-and-resumed included — is
    token-exact vs ITS OWN adapter's serial oracle (base weights with
    that adapter's A·B merged in): uncommitted draft state must drop
    cleanly, and preemption must save+restore the slot's adapter_idx
    with the rest of its state (a resumed victim decoding under the
    WRONG adapter would show up here as a token mismatch)."""
    from megatron_tpu.inference.generation import (Generator,
                                                   SamplingParams)
    from megatron_tpu.resilience import FaultInjector, use_fault_injector
    from megatron_tpu.serving import OverloadShedError, SamplingOptions

    rank, alpha = 4, 8.0
    eng, gen = _tiny_engine(dict(
        num_slots=2, max_queue=64, max_len=128, priority_levels=2,
        shed_on_overload=True, preemption=True, max_engine_restarts=2,
        speculative_k=spec_k, adapter_slots=n_adapters or 0,
        adapter_rank=rank, **(pool_kwargs or {})))
    adapters = _make_adapters(gen.cfg, n_adapters, rank)
    for aid, factors in sorted(adapters.items()):
        eng.register_adapter(aid, factors=factors, rank=rank,
                             alpha=alpha)
    # round-robin adapter assignment over [base, t-0, t-1, ...]
    cycle = [None] + sorted(adapters)

    def aid_for(i):
        return cycle[i % len(cycle)]

    # greedy: seed-independent, so the exactness oracle is one serial
    # generate per (adapter, prompt, n) — preemption/speculation must
    # not move a single token
    sampling = SamplingOptions(temperature=0.0)
    reqs, shed = [], 0
    # NaN-poison one active slot a few steps in: the non-finite guard
    # must fail exactly that REQUEST while the grid keeps decoding
    injector = FaultInjector(serve_nan_calls={6: 0})
    try:
        with use_fault_injector(injector):
            # warmup: compile + give the shed estimator its first
            # service-time sample (it never sheds blind)
            eng.generate([3, 1, 4], 2, sampling, seed=0)
            # wave 1 — capacity pressure: low-priority work fills both
            # slots and the queue (a repeated motif gives the
            # self-drafting matcher something to look up) ...
            for i in range(6):
                reqs.append((eng.submit([5 + i, 2, 7, 2, 7],
                                        new_tokens, sampling, seed=i,
                                        priority=0,
                                        adapter_id=aid_for(i)),
                             [5 + i, 2, 7, 2, 7], new_tokens,
                             aid_for(i)))
            # ... wait until low-priority work actually OCCUPIES the
            # slots (otherwise the priority queue simply serves the
            # high-priority wave first and nothing needs preempting) ...
            t_wait = time.monotonic() + 30
            while (eng.health()["active_slots"] < 2
                   and time.monotonic() < t_wait):
                time.sleep(0.002)
            # ... then high-priority arrivals preempt running slots
            # (preempt-mid-round: the victim's in-window draft state
            # is uncommitted by construction and must just vanish —
            # and its adapter pin must release/re-acquire cleanly)
            for i in range(3):
                n = max(new_tokens // 2, 2)
                reqs.append((eng.submit([9, 8 + i], n, sampling,
                                        seed=100 + i, priority=1,
                                        adapter_id=aid_for(i + 1)),
                             [9, 8 + i], n, aid_for(i + 1)))
            # wave 2 — hopeless deadlines: the estimator (fed by the
            # warmup completion) sheds these at SUBMIT time
            for i in range(16):
                try:
                    reqs.append((eng.submit([2, i + 1], new_tokens,
                                            sampling, seed=200 + i,
                                            deadline_s=0.001,
                                            adapter_id=aid_for(i)),
                                 [2, i + 1], new_tokens, aid_for(i)))
                except OverloadShedError:
                    shed += 1
            outcomes = _resolve_all([r for r, _, _, _ in reqs])
        snap = eng.metrics.snapshot()
        health = eng.health()
        # exactness sweep over everything that finished OK — each
        # request against ITS adapter's merged-weights serial oracle
        oracles = {None: gen}
        if n_adapters:
            from megatron_tpu.training.lora import merge_lora
            for aid, factors in adapters.items():
                oracles[aid] = Generator(
                    merge_lora(gen.params, factors, gen.cfg, rank,
                               alpha),
                    gen.cfg, eos_id=-1, pad_id=0)
        serial_cache, exact, checked = {}, True, 0
        adapter_checked = 0
        for r, prompt, n, aid in reqs:
            if r.state.value != "finished":
                continue
            key = (aid, tuple(prompt), n)
            if key not in serial_cache:
                t, lens, _ = oracles[aid].generate(
                    [prompt], n,
                    sampling=SamplingParams(temperature=0.0))
                serial_cache[key] = t[0, :lens[0]].tolist()
            checked += 1
            if aid is not None:
                adapter_checked += 1
            if r.prompt + r.generated != serial_cache[key]:
                exact = False
        # system-wide law sweep (serving/invariants.py): conservation,
        # typed terminals, KV accounting, schema, healthz — on top of
        # the drill's own scenario assertions
        inv = invariant_sweep(eng, [r for r, _, _, _ in reqs])
    finally:
        eng.close()
    fired = {k: sum(1 for f, _ in injector.fired if f == k)
             for k in ("serve_nan",)}
    return {
        "submitted": len(reqs), "shed_at_submit": shed,
        "outcomes": outcomes,
        "preemptions": int(snap["preemptions"]),
        "requests_shed": int(snap["requests_shed"]),
        "nonfinite_logit_fails": int(snap["nonfinite_logit_fails"]),
        "nan_faults_fired": fired["serve_nan"],
        "speculative_k": spec_k,
        "spec_rounds": int(snap["spec_rounds"]),
        "draft_tokens": int(snap["draft_tokens"]),
        "adapters": n_adapters,
        "adapter_loads": int(snap["adapter_loads"]),
        "adapter_rows_checked": adapter_checked,
        "completed_token_exact": exact,
        "completed_checked": checked,
        "healthy_after": bool(health["healthy"]),
        "invariants_ok": inv["ok"],
        "invariant_violations": inv["violations"],
        "ok": (outcomes["stranded"] == 0
               and shed + int(snap["requests_shed"]) >= 1
               and int(snap["preemptions"]) >= 1
               and int(snap["nonfinite_logit_fails"])
               >= fired["serve_nan"] > 0
               and exact and checked >= 1
               and (spec_k == 0 or int(snap["spec_rounds"]) >= 1)
               and (n_adapters == 0
                    or (int(snap["adapter_loads"]) >= 1
                        and adapter_checked >= 1))
               and health["healthy"] and inv["ok"]),
    }


def hang_drill(timeout_s: float, stall_s: float, spec_k: int = 0,
               pool_kwargs=None) -> dict:
    """A wedged decode iteration: the watchdog must fail the in-flight
    futures within its deadline and the supervisor must restart the
    loop once the stalled dispatch returns — measured as the wall time
    from the hang-victim's failure to a fresh probe completing. With
    spec_k > 0 the wedged iteration is a speculative window: the
    restart must drop its uncommitted draft state with the rest of the
    device state, and the greedy probe must come back token-exact."""
    from megatron_tpu.inference.generation import SamplingParams
    from megatron_tpu.resilience import FaultInjector, use_fault_injector
    from megatron_tpu.serving import SamplingOptions

    eng, gen = _tiny_engine(dict(
        num_slots=1, max_queue=16, max_len=128,
        engine_step_timeout_s=timeout_s, max_engine_restarts=2,
        speculative_k=spec_k, **(pool_kwargs or {})))
    sampling = SamplingOptions(temperature=0.0)
    try:
        # warmup: compiles done AND the watchdog armed (it arms only
        # after the first completed iteration)
        eng.generate([1, 2, 3], 2, sampling, seed=0)
        injector = FaultInjector(serve_delay_calls={1: stall_s})
        with use_fault_injector(injector):
            victim = eng.submit([4, 5, 4, 5], 8, sampling, seed=1)
            t0 = time.monotonic()
            try:
                victim.result(timeout=stall_s + timeout_s + 30)
                victim_failed = False
            except TimeoutError:
                victim_failed = False
            except Exception:  # noqa: BLE001 — the watchdog failed it
                victim_failed = True
            detect_s = time.monotonic() - t0
            # the supervisor restarts after the stalled dispatch
            # returns; a fresh probe must then complete normally
            probe = eng.submit([6, 7, 6, 7], 4, sampling, seed=2)
            probe_toks, _ = probe.result(timeout=60)
            recovery_s = time.monotonic() - t0
        t, lens, _ = gen.generate([[6, 7, 6, 7]], 4,
                                  sampling=SamplingParams(
                                      temperature=0.0))
        probe_exact = probe_toks == t[0, :lens[0]].tolist()
        health = eng.health()
        snap = eng.metrics.snapshot()
        inv = invariant_sweep(eng, [victim, probe])
    finally:
        eng.close()
    return {
        "watchdog_timeout_s": timeout_s, "stall_s": stall_s,
        "victim_failed_typed": victim_failed,
        "detect_s": round(detect_s, 3),
        "recovery_s": round(recovery_s, 3),
        "engine_restarts": int(snap["engine_restarts"]),
        "speculative_k": spec_k,
        "probe_token_exact": probe_exact,
        "healthy_after": bool(health["healthy"]),
        "invariants_ok": inv["ok"],
        "invariant_violations": inv["violations"],
        "ok": (victim_failed and inv["ok"]
               and int(snap["engine_restarts"]) >= 1
               # the victim must fail by watchdog detection (deadline +
               # poll slack), i.e. strictly before the stalled dispatch
               # itself would have returned and failed it anyway
               and detect_s < stall_s + timeout_s
               and probe_exact
               and health["healthy"] and health["state"] == "running"),
    }


def crash_loop_drill(spec_k: int = 0, pool_kwargs=None) -> dict:
    """Every step crashes: the supervisor restarts max_engine_restarts
    times, then trips the circuit breaker. Everything in flight or
    queued resolves with a typed error, health() reports unhealthy,
    and new submits raise EngineUnhealthyError (the server's 503).
    With spec_k > 0 the crashing step is a speculative window — the
    restart/breaker path must behave identically (draft state is
    host-side and dies with the window)."""
    from megatron_tpu.resilience import FaultInjector, use_fault_injector
    from megatron_tpu.serving import EngineUnhealthyError, SamplingOptions

    eng, _ = _tiny_engine(dict(
        num_slots=1, max_queue=16, max_len=128, max_engine_restarts=1,
        speculative_k=spec_k, **(pool_kwargs or {})))
    sampling = SamplingOptions(temperature=1.0)
    try:
        eng.generate([1, 2], 2, sampling, seed=0)  # warmup
        injector = FaultInjector(
            serve_crash_calls=set(range(1, 64)))
        with use_fault_injector(injector):
            reqs = [eng.submit([3 + i], 4, sampling, seed=i)
                    for i in range(4)]
            outcomes = _resolve_all(reqs, timeout=60)
        health = eng.health()
        snap = eng.metrics.snapshot()
        try:
            eng.submit([9], 2, sampling, seed=99)
            submit_rejected_503 = False
        except EngineUnhealthyError:
            submit_rejected_503 = True
        # the laws hold on a BROKEN engine too: every request terminal
        # exactly once, healthz consistently unhealthy, schema stable
        inv = invariant_sweep(eng, reqs)
    finally:
        eng.close()
    return {
        "submitted": 4, "outcomes": outcomes,
        "engine_restarts": int(snap["engine_restarts"]),
        "breaker_open": bool(health["circuit_breaker_open"]),
        "state": health["state"],
        "submit_rejected_503": submit_rejected_503,
        "invariants_ok": inv["ok"],
        "invariant_violations": inv["violations"],
        "ok": (outcomes["stranded"] == 0 and outcomes["ok"] == 0
               and int(snap["engine_restarts"]) == 1
               and health["circuit_breaker_open"]
               and not health["healthy"]
               and submit_rejected_503 and inv["ok"]),
    }


def run_chaos(new_tokens: int, timeout_s: float, stall_s: float,
              spec_k: int = 0, block: int = 16,
              block_native: bool = True, n_adapters: int = 2) -> dict:
    t0 = time.monotonic()
    pool_kwargs = _pool_mode(block, block_native)
    overload = overload_drill(new_tokens, spec_k, pool_kwargs,
                              n_adapters=n_adapters)
    hang = hang_drill(timeout_s, stall_s, spec_k, pool_kwargs)
    crash = crash_loop_drill(spec_k, pool_kwargs)
    wall_s = time.monotonic() - t0
    ok = overload["ok"] and hang["ok"] and crash["ok"]
    return {
        "metric": "serve_chaos_hang_recovery_s",
        "value": hang["recovery_s"],
        "unit": (f"s hang-detect->restart->serve (watchdog "
                 f"{timeout_s}s, stall {stall_s}s)"),
        "vs_baseline": None,
        "completed": ok,
        "speculative_k": spec_k,
        "kv_block_size": block or None,
        "block_native_attn": bool(block and block_native),
        "adapters": n_adapters,
        "overload": overload,
        "hang": hang,
        "crash_loop": crash,
        "wall_s": round(wall_s, 1),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fixed scenario for bench extras / CI")
    ap.add_argument("--new_tokens", type=int, default=24,
                    help="decode length of the overload wave's requests")
    ap.add_argument("--watchdog_s", type=float, default=1.0,
                    help="engine_step_timeout_s for the hang drill")
    ap.add_argument("--stall_s", type=float, default=3.0,
                    help="injected serve_delay for the hang drill")
    ap.add_argument("--speculative_k", type=int, default=4,
                    help="run every drill with speculative decoding at "
                         "this k (0 = the pre-speculative drills): "
                         "preempt-mid-round / crash-restart / "
                         "watchdog-hang must drop uncommitted draft "
                         "state cleanly — resumed requests token-exact, "
                         "no stranded futures")
    ap.add_argument("--adapters", type=int, default=2,
                    help="run the overload drill with this many LoRA "
                         "adapters interleaved through the traffic "
                         "(multi-tenant serving under chaos): every "
                         "completed request pins token-exact against "
                         "its OWN adapter's merged-weights serial "
                         "oracle — preempt/resume must save+restore "
                         "the slot's adapter binding (0 = adapterless "
                         "drills)")
    ap.add_argument("--kv_block_size", type=int, default=16,
                    help="run every drill on the BLOCK-granular pool "
                         "at this block size — the production layout "
                         "gets the chaos coverage, not only the "
                         "whole-region fallback (0 = whole-region)")
    ap.add_argument("--no_block_native", action="store_true",
                    help="keep the resolve/scatter bracket instead of "
                         "the block-native attention kernel (the "
                         "kernel is on by default wherever legal)")
    ap.add_argument("--out", type=str, default=None,
                    help="also write the JSON record here")
    args = ap.parse_args(argv)

    ensure_compile_cache()
    if args.smoke:
        args.new_tokens, args.watchdog_s, args.stall_s = 16, 1.0, 2.5

    record = run_chaos(args.new_tokens, args.watchdog_s, args.stall_s,
                       args.speculative_k, args.kv_block_size,
                       not args.no_block_native, args.adapters)
    emit_record(record, args.out, seed=0)  # scripted: fixed workload
    return 0 if record["completed"] else 1


if __name__ == "__main__":
    sys.exit(main())
