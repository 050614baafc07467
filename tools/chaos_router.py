"""Scripted front-door chaos drill: replica kill / wedge / host-tier
corruption over a REAL 2-replica router, measure that nothing strands
and nothing moves a token.

tools/chaos_serve.py proves one ENGINE survives its bad hour; this
tool proves the ROUTER in front of N engines survives a replica's bad
hour (docs/serving.md "Front door"). Three drills, each through a real
`EngineRouter` over two real `ServingEngine` replicas sharing one tiny
model:

1. **replica kill**: one replica dies mid-traffic (`close()` — the
   in-process analogue of the process being OOM-killed). Contract:
   zero accepted requests are lost — every future resolves, every
   COMPLETED request (requeued-and-retried ones included) is
   token-exact vs a serial single-replica run — the router ejects the
   dead replica (`router_failovers`), retries its work on the survivor
   (`router_retries`), `/healthz` reports DEGRADED (not down), and new
   submits keep succeeding.
2. **wedge one replica**: one replica's fetch seam stalls past its
   watchdog deadline mid-decode. Contract: the watchdog fails the
   wedged work, the router retries it on the survivor token-exact,
   and once the stalled replica's supervisor restarts it, the router
   re-admits it through a half-open canary — ending with BOTH
   replicas back in rotation.
3. **host-tier corruption**: a demoted prefix's host bytes are flipped.
   Contract: the checksum catches it (`host_tier_checksum_misses`),
   the request recomputes and stays token-exact — a corrupt demotion
   is a MISS, never wrong tokens — while an uncorrupted entry restores
   (`host_tier_hits`) token-exact.
4. **kill-the-prefill-half / kill-the-decode-half** (docs/serving.md
   "Sharded & disaggregated serving"): over a DISAGGREGATED 2-replica
   router — each replica a (prefill-group, decode-group) device pair —
   one replica permanently loses one HALF (its prefill or decode
   dispatch raises, the in-process analogue of that chip group dying).
   Contract: the half-dead replica's supervisor exhausts its restarts
   and trips the breaker, the router ejects the REPLICA (a pair with a
   dead half is a dead pair), every accepted request resolves
   token-exact on the surviving pair (token-exact resubmission covers
   a dead half exactly like a dead replica), `/healthz` reports
   DEGRADED (not down), and the survivor keeps handing off
   (`handoffs` still advances). Skipped with a note when the backend
   has < 4 devices (2 replicas x 2 groups); the CPU smoke forces a
   4-virtual-device host platform.
5. **kill-one-stage** (docs/serving.md "Pipeline-sharded serving"):
   over a router of 2 PIPELINE-SHARDED replicas — each a serving_pp=2
   stage chain of 2 devices — one replica permanently loses a layer
   STAGE (its stage-1 decode program raises, the in-process analogue
   of that stage's chip group dying). Contract: a chain with a dead
   stage is a dead chain — the supervisor's restart re-crashes (the
   compiled stage programs survive restarts, so the dead stage stays
   dead), the breaker trips, the router ejects the replica, every
   accepted request resolves token-exact on the surviving chain, and
   the survivor still runs STAGED (its per-stage trace counters stay
   [1, 1] — ejection caused zero recompiles). Skipped with a note
   when the backend has < 4 devices (2 replicas x 2 stages).

Every drill finishes with a system-wide `invariants.check_all` sweep
(serving/invariants.py): per-replica request conservation + KV
accounting + schema, plus the router-level degraded-not-down healthz
law — on top of each drill's own scenario assertions.

Emits ONE BENCH-style JSON record on stdout (and to --out), like
chaos_serve.py, so front-door regressions surface in the
`BENCH_*.json` extras. The scaffolding (tiny router builder, serial
oracle, outcome resolver) lives in tools/chaos_common.py, shared with
chaos_serve.py / chaos_upgrade.py / chaos_mesh.py.

  JAX_PLATFORMS=cpu python tools/chaos_router.py --smoke [--out FILE]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from megatron_tpu.utils.compile_cache import ensure_compile_cache
from tools.chaos_common import (emit_record, force_host_devices,
                                invariant_sweep,
                                resolve_exact as _resolve_exact,
                                serial_oracle as _serial_oracle,
                                tiny_router as _tiny_router)


def kill_drill(new_tokens: int) -> dict:
    from megatron_tpu.serving import SamplingOptions

    router, engines, gen = _tiny_router(dict(
        num_slots=2, max_queue=64, max_len=128,
        enable_prefix_cache=True, kv_block_size=16,
        block_native_attn=True))
    sampling = SamplingOptions(temperature=0.0)
    want = _serial_oracle(gen)
    try:
        # warmup both replicas (compiles + a health baseline)
        for eng in engines:
            eng.generate([3, 1, 4], 2, sampling, seed=0)
        reqs = []
        for i in range(8):
            p = [5 + i, 2, 7, 2, 7]
            reqs.append((router.submit(p, new_tokens, sampling, seed=i),
                         p, new_tokens))
        # wait until SOME work is actually decoding, then kill replica 0
        t_wait = time.monotonic() + 30
        while (engines[0].health()["active_slots"]
               + engines[1].health()["active_slots"] < 2
               and time.monotonic() < t_wait):
            time.sleep(0.002)
        engines[0].close()
        outcomes, exact = _resolve_exact(reqs, want)
        health = router.health()
        snap = router.aggregate_snapshot()
        # the front door still serves after losing a replica
        post = router.submit([9, 9, 8], 4, sampling, seed=99)
        post_toks, _ = post.result(timeout=60)
        post_exact = post_toks == want([9, 9, 8], 4)
        inv = invariant_sweep(router, [r for r, _, _ in reqs] + [post])
    finally:
        router.close()
    return {
        "submitted": len(reqs), "outcomes": outcomes,
        "completed_token_exact": exact,
        "router_failovers": int(snap["router_failovers"]),
        "router_retries": int(snap["router_retries"]),
        "health_state": health["state"],
        "healthz_ready": bool(health["healthy"]),
        "post_kill_serve_exact": post_exact,
        "invariants_ok": inv["ok"],
        "invariant_violations": inv["violations"],
        "ok": (outcomes["stranded"] == 0 and outcomes["error"] == 0
               and outcomes["ok"] == len(reqs) and exact
               and int(snap["router_failovers"]) >= 1
               and health["state"] == "degraded" and health["healthy"]
               and post_exact and inv["ok"]),
    }


def wedge_drill(new_tokens: int, timeout_s: float,
                stall_s: float) -> dict:
    from megatron_tpu.serving import SamplingOptions

    router, engines, gen = _tiny_router(
        dict(num_slots=1, max_queue=32, max_len=128,
             engine_step_timeout_s=timeout_s, max_engine_restarts=2),
        heartbeat_s=timeout_s)
    sampling = SamplingOptions(temperature=0.0)
    want = _serial_oracle(gen)
    try:
        for eng in engines:
            # warmup: compiles done AND each watchdog armed
            eng.generate([1, 2, 3], 2, sampling, seed=0)
        # wedge replica 0's sync seam: the next window stalls past the
        # watchdog deadline (the in-process analogue of a device hang)
        orig_fetch = engines[0]._fetch
        fired = []

        def stalling_fetch(tree):
            if not fired:
                fired.append(1)
                time.sleep(stall_s)
            return orig_fetch(tree)

        engines[0]._fetch = stalling_fetch
        reqs = []
        for i in range(4):
            p = [4 + i, 5, 4, 5]
            reqs.append((router.submit(p, new_tokens, sampling,
                                       seed=i), p, new_tokens))
        outcomes, exact = _resolve_exact(
            reqs, want, timeout=stall_s + timeout_s + 60)
        snap = router.aggregate_snapshot()
        # the wedged replica's supervisor restarts it; the router must
        # re-admit it via a half-open canary — poll until both UP
        recovered = False
        t_wait = time.monotonic() + stall_s + 30
        while time.monotonic() < t_wait:
            h = router.health()
            if h["state"] == "running" and h["replicas_up"] == 2:
                recovered = True
                break
            # traffic drives the canary: PROBING needs a request
            try:
                router.submit([8, 8], 2, sampling, seed=7).result(30)
            except Exception:  # noqa: BLE001
                pass
            time.sleep(0.05)
        health = router.health()
        inv = invariant_sweep(router, [r for r, _, _ in reqs])
    finally:
        router.close()
    return {
        "watchdog_timeout_s": timeout_s, "stall_s": stall_s,
        "submitted": len(reqs), "outcomes": outcomes,
        "completed_token_exact": exact,
        "router_failovers": int(snap["router_failovers"]),
        "router_retries": int(snap["router_retries"]),
        "wedged_fired": bool(fired),
        "recovered_both_up": recovered,
        "health_state": health["state"],
        "invariants_ok": inv["ok"],
        "invariant_violations": inv["violations"],
        "ok": (outcomes["stranded"] == 0 and outcomes["error"] == 0
               and exact and bool(fired) and recovered and inv["ok"]),
    }


def host_tier_drill(new_tokens: int) -> dict:
    from megatron_tpu.serving import SamplingOptions

    router, engines, gen = _tiny_router(dict(
        num_slots=2, max_queue=32, max_len=128,
        enable_prefix_cache=True, kv_block_size=16, block_native_attn=True,
        retained_slots=1,
        host_kv_bytes=1 << 22))
    sampling = SamplingOptions(temperature=0.0)
    want = _serial_oracle(gen)
    prefix = list(range(2, 20))  # > one 16-token block
    try:
        # warm ONLY replica 0 (drives affinity too: later prefix
        # traffic must route back to it via prefix_peek)
        engines[0].generate(prefix, new_tokens, sampling, seed=0)
        # churn retained entries so the prefix demotes to host RAM
        engines[0].generate([40, 41, 42], 2, sampling, seed=0)
        engines[0].generate([50, 51, 52], 2, sampling, seed=0)
        tier = engines[0]._host_tier
        demoted = len(tier) >= 1
        # phase 1 — clean restore through the ROUTER: affinity must
        # pick replica 0, the tier must hit, tokens must be exact
        p1 = prefix + [90, 91]
        affinity = router.prefix_peek(p1)
        t1, _ = router.submit(p1, new_tokens, sampling,
                              seed=1).result(60)
        exact1 = t1 == want(p1, new_tokens)
        snap1 = router.aggregate_snapshot()
        # phase 2 — churn the device-resident retained copies out
        # first (a device hit would legitimately win over the host
        # entry), then corrupt every demoted long entry and hit again:
        # checksum must catch it, the request must recompute exactly
        engines[0].generate([60, 61, 62], 2, sampling, seed=0)
        engines[0].generate([70, 71, 72], 2, sampling, seed=0)
        for ent in tier._entries.values():
            if ent.length >= 16:
                ent.arrays["k"].view("uint8").flat[0] ^= 0xFF
        p2 = prefix + [92, 93]
        t2, _ = router.submit(p2, new_tokens, sampling,
                              seed=2).result(60)
        exact2 = t2 == want(p2, new_tokens)
        snap2 = router.aggregate_snapshot()
        inv = invariant_sweep(router)
    finally:
        router.close()
    return {
        "demoted": demoted,
        "affinity_peek_tokens": int(affinity),
        "host_tier_demotions": int(snap2["host_tier_demotions"]),
        "host_tier_hits": int(snap2["host_tier_hits"]),
        "host_tier_checksum_misses":
            int(snap2["host_tier_checksum_misses"]),
        "clean_restore_exact": exact1,
        "corrupt_restore_exact": exact2,
        "invariants_ok": inv["ok"],
        "invariant_violations": inv["violations"],
        "ok": (demoted and affinity >= 16
               and int(snap1["host_tier_hits"]) >= 1 and exact1
               and int(snap2["host_tier_checksum_misses"]) >= 1
               and exact2 and inv["ok"]),
    }


def _tiny_disagg_router(new_tokens: int):
    """2-replica router over DISAGGREGATED engines: 4 devices, each
    replica a (prefill-group, decode-group) pair. A dead half keeps
    raising: one restart then the breaker — the replica must go
    hard-down fast so the router ejects it (max_engine_restarts=1)."""
    return _tiny_router(
        dict(num_slots=2, max_queue=64, max_len=128, kv_block_size=16,
             disaggregate_prefill=True, max_engine_restarts=1),
        heartbeat_s=2.0, probe_backoff_s=30.0, compute="bfloat16",
        devices_per=2)


def kill_half_drill(new_tokens: int, half: str) -> dict:
    """Kill one replica's prefill OR decode chip group mid-traffic
    and pin token-exact resubmission on the surviving pair."""
    import jax

    from megatron_tpu.serving import SamplingOptions

    if len(jax.devices()) < 4:
        return {"skipped": f"{len(jax.devices())} device(s) < 4 "
                           "(2 disaggregated replicas)", "ok": True}
    router, engines, gen = _tiny_disagg_router(new_tokens)
    sampling = SamplingOptions(temperature=0.0)
    want = _serial_oracle(gen)
    try:
        for eng in engines:
            eng.generate([3, 1, 4], 2, sampling, seed=0)

        def dead(*a, **k):
            raise RuntimeError(f"injected: {half} half down "
                               "(chip group lost)")

        # the half dies PERMANENTLY: every dispatch on it raises, so
        # the supervisor's restart re-crashes and the breaker trips
        if half == "prefill":
            engines[0]._chunk_fwd = dead
        else:
            engines[0]._decode = dead
        reqs = []
        for i in range(6):
            p = [5 + i, 2, 7, 2, 7]
            reqs.append((router.submit(p, new_tokens, sampling, seed=i),
                         p, new_tokens))
        outcomes, exact = _resolve_exact(reqs, want)
        health = router.health()
        snap = router.aggregate_snapshot()
        # the surviving PAIR still serves end-to-end — prefill group,
        # handoff, decode group
        post = router.submit([9, 9, 8], 4, sampling, seed=99)
        post_toks, _ = post.result(timeout=60)
        post_exact = post_toks == want([9, 9, 8], 4)
        snap_post = router.aggregate_snapshot()
        inv = invariant_sweep(router, [r for r, _, _ in reqs] + [post])
    finally:
        router.close()
    return {
        "half": half,
        "submitted": len(reqs), "outcomes": outcomes,
        "completed_token_exact": exact,
        "router_failovers": int(snap["router_failovers"]),
        "router_retries": int(snap["router_retries"]),
        "health_state": health["state"],
        "healthz_ready": bool(health["healthy"]),
        "post_kill_serve_exact": post_exact,
        "survivor_handoffs": int(snap_post["handoffs"]),
        "invariants_ok": inv["ok"],
        "invariant_violations": inv["violations"],
        "ok": (outcomes["stranded"] == 0 and outcomes["error"] == 0
               and outcomes["ok"] == len(reqs) and exact
               and int(snap["router_failovers"]) >= 1
               and health["state"] == "degraded" and health["healthy"]
               and post_exact and int(snap_post["handoffs"]) >= 1
               and inv["ok"]),
    }


def kill_stage_drill(new_tokens: int) -> dict:
    """Kill one replica's layer stage mid-traffic and pin token-exact
    resubmission on the surviving stage chain."""
    import jax

    from megatron_tpu.serving import SamplingOptions

    if len(jax.devices()) < 4:
        return {"skipped": f"{len(jax.devices())} device(s) < 4 "
                           "(2 pipeline-sharded replicas)", "ok": True}
    # each replica is a 2-stage chain (1 device per stage); a dead
    # stage keeps raising: one restart then the breaker
    router, engines, gen = _tiny_router(
        dict(num_slots=2, max_queue=64, max_len=128, kv_block_size=16,
             serving_pp=2, decode_tp=1, max_engine_restarts=1),
        heartbeat_s=2.0, probe_backoff_s=30.0, compute="bfloat16",
        devices_per=2)
    sampling = SamplingOptions(temperature=0.0)
    want = _serial_oracle(gen)
    try:
        for eng in engines:
            eng.generate([3, 1, 4], 2, sampling, seed=0)

        def dead(*a, **k):
            raise RuntimeError("injected: stage 1 down (stage chip "
                               "group lost)")

        # the stage dies PERMANENTLY: _restart_session keeps the
        # compiled stage programs (no retrace on restart), so the
        # patched program re-crashes the restarted loop and the
        # breaker trips
        engines[0]._pp_dec[1] = dead
        reqs = []
        for i in range(6):
            p = [5 + i, 2, 7, 2, 7]
            reqs.append((router.submit(p, new_tokens, sampling, seed=i),
                         p, new_tokens))
        outcomes, exact = _resolve_exact(reqs, want)
        health = router.health()
        snap = router.aggregate_snapshot()
        # the surviving CHAIN still serves end-to-end — embedding on
        # stage 0, activation crossing, head on stage 1
        post = router.submit([9, 9, 8], 4, sampling, seed=99)
        post_toks, _ = post.result(timeout=60)
        post_exact = post_toks == want([9, 9, 8], 4)
        survivor_traces = list(engines[1]._pp_decode_traces)
        survivor_staged = isinstance(engines[1].pool.caches, list)
        inv = invariant_sweep(router, [r for r, _, _ in reqs] + [post])
    finally:
        router.close()
    return {
        "submitted": len(reqs), "outcomes": outcomes,
        "completed_token_exact": exact,
        "router_failovers": int(snap["router_failovers"]),
        "router_retries": int(snap["router_retries"]),
        "health_state": health["state"],
        "healthz_ready": bool(health["healthy"]),
        "post_kill_serve_exact": post_exact,
        "survivor_stage_traces": survivor_traces,
        "survivor_staged": survivor_staged,
        "serving_pp_gauge": float(snap["serving_pp"]),
        "invariants_ok": inv["ok"],
        "invariant_violations": inv["violations"],
        "ok": (outcomes["stranded"] == 0 and outcomes["error"] == 0
               and outcomes["ok"] == len(reqs) and exact
               and int(snap["router_failovers"]) >= 1
               and health["state"] == "degraded" and health["healthy"]
               and post_exact and survivor_staged
               and survivor_traces == [1, 1]
               and float(snap["serving_pp"]) == 2.0
               and inv["ok"]),
    }


def run_chaos(new_tokens: int, timeout_s: float, stall_s: float) -> dict:
    t0 = time.monotonic()
    kill = kill_drill(new_tokens)
    wedge = wedge_drill(new_tokens, timeout_s, stall_s)
    host = host_tier_drill(new_tokens)
    kill_prefill = kill_half_drill(new_tokens, "prefill")
    kill_decode = kill_half_drill(new_tokens, "decode")
    kill_stage = kill_stage_drill(new_tokens)
    wall_s = time.monotonic() - t0
    ok = (kill["ok"] and wedge["ok"] and host["ok"]
          and kill_prefill["ok"] and kill_decode["ok"]
          and kill_stage["ok"])
    return {
        "metric": "router_chaos_failover_retries",
        "value": kill["router_retries"] + wedge["router_retries"],
        "unit": ("requeued-and-retried requests across kill+wedge "
                 "drills (all token-exact, zero lost)"),
        "vs_baseline": None,
        "completed": ok,
        "kill": kill,
        "wedge": wedge,
        "host_tier": host,
        "kill_prefill_half": kill_prefill,
        "kill_decode_half": kill_decode,
        "kill_stage": kill_stage,
        "wall_s": round(wall_s, 1),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fixed scenario for bench extras / CI")
    ap.add_argument("--new_tokens", type=int, default=24,
                    help="decode length of the drill requests")
    ap.add_argument("--watchdog_s", type=float, default=1.0,
                    help="engine_step_timeout_s for the wedge drill")
    ap.add_argument("--stall_s", type=float, default=3.0,
                    help="injected fetch stall for the wedge drill")
    ap.add_argument("--out", type=str, default=None,
                    help="also write the JSON record here")
    args = ap.parse_args(argv)

    # the disaggregated kill-half drills need 4 devices (2 replicas x
    # 2 chip groups)
    force_host_devices(4)
    ensure_compile_cache()
    if args.smoke:
        args.new_tokens, args.watchdog_s, args.stall_s = 12, 1.0, 2.5

    record = run_chaos(args.new_tokens, args.watchdog_s, args.stall_s)
    emit_record(record, args.out, seed=0)  # scripted: fixed workload
    return 0 if record["completed"] else 1


if __name__ == "__main__":
    sys.exit(main())
