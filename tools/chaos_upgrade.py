"""Scripted live-weight chaos drill: rolling fleet upgrades under
sustained traffic with failures injected at the worst moments, measure
that nothing 503s, nothing strands, and nothing moves a token.

tools/chaos_router.py proves the ROUTER survives a replica's bad hour;
this tool proves the fleet survives its WEIGHT UPGRADES (docs/serving.md
"Live weights & rolling upgrade"). Three drills, each over a real
`EngineRouter` with real `ServingEngine` replicas and real
manifest-sealed checkpoints on disk:

1. **rolling upgrade under load + kill the DRAINING replica mid-swap**:
   traffic flows while `rolling_upgrade` walks the fleet; the moment
   replica 0 enters its planned drain, it is killed (`close()` — the
   in-process analogue of the pod dying mid-upgrade). Contract: the
   rollout ABORTS typed (`RollingUpgradeError`), the fleet is
   DEGRADED-not-down and keeps serving, zero futures strand, and every
   COMPLETED request is token-exact vs a serial oracle at its admitted
   version (N or N+1 — a mid-rollout fleet legitimately serves both).
2. **corrupt-checkpoint publish mid-watch**: a `CheckpointWatcher`
   drives the fleet; a GOOD publish upgrades it hands-free, then a
   CORRUPT publish lands. Contract: the manifest gate refuses it before
   any device transfer, the fleet stays on the good version,
   `weight_swap_failures` counts it, and the watcher does NOT retry the
   same tag (no restart loop) — but the NEXT good publish applies.
3. **upgrade racing the disaggregated handoff**: a rolling upgrade over
   DISAGGREGATED replicas (each a prefill-group/decode-group pair)
   under live traffic. Contract: zero 503s, every completion
   token-exact at its admitted version — which pins that the swap lands
   on BOTH chip groups atomically per replica (a prefill-N / decode-N+1
   split would corrupt tokens, not just flip versions) — and the
   survivors keep handing off throughout.

Every drill finishes with a system-wide `invariants.check_all` sweep
(serving/invariants.py) — the conservation / typed-terminal / KV /
schema / healthz laws hold through every refused swap and aborted
rollout, on top of the drills' own version-exactness assertions.

Emits ONE BENCH-style JSON record on stdout (and to --out), like
chaos_router.py, so live-weight regressions surface in the
`BENCH_*.json` extras. The scaffolding (tiny model/fleet builders,
checkpoint publish helpers, serial oracle) lives in
tools/chaos_common.py, shared with chaos_serve.py / chaos_router.py /
chaos_mesh.py.

  JAX_PLATFORMS=cpu python tools/chaos_upgrade.py --smoke [--out FILE]
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from megatron_tpu.utils.compile_cache import ensure_compile_cache
from tools.chaos_common import (corrupt_payload as _corrupt_payload,
                                emit_record, force_host_devices,
                                invariant_sweep,
                                publish_checkpoint as _publish,
                                serial_oracle as _serial_oracle,
                                tiny_generator, tiny_model_cfg)


def _model_cfg():
    return tiny_model_cfg(compute="float32")


def _versioned_fleet(serving_kwargs, n_replicas=2, devices_per=None):
    """(router, engines, gen_v1, gen_v2, ckpt_root, ckpt_v2): a fleet
    serving version 1 with version 2 already published to disk."""
    import jax

    from megatron_tpu.config import ServingConfig
    from megatron_tpu.serving import EngineRouter, ServingEngine

    model = _model_cfg()
    # eos_id=-1: no early EOS, deterministic request lifetimes
    gen1 = tiny_generator(model, seed=0)
    gen2 = tiny_generator(model, seed=1)
    root = tempfile.mkdtemp(prefix="chaos_upgrade_")
    d2 = _publish(root, model, gen2.params, 2)
    serving = ServingConfig(**serving_kwargs).validate(model)
    if devices_per:
        devs = jax.devices()
        engines = [ServingEngine(gen1, serving,
                                 devices=devs[i * devices_per:
                                              (i + 1) * devices_per])
                   for i in range(n_replicas)]
    else:
        engines = [ServingEngine(gen1, serving)
                   for _ in range(n_replicas)]
    router = EngineRouter(engines, max_retries=2,
                          heartbeat_timeout_s=3.0, probe_backoff_s=0.2)
    return router, engines, gen1, gen2, root, d2


def _load_workers(router, new_tokens, n_workers=3):
    """Background greedy traffic: (results, stop, threads). Each result
    is (prompt, seed, tokens|None, error|None)."""
    from megatron_tpu.serving import SamplingOptions
    sampling = SamplingOptions(temperature=0.0)
    results, stop = [], threading.Event()
    lock = threading.Lock()

    def worker(wid):
        i = 0
        while not stop.is_set():
            p = [3 + (wid + i) % 5, 7, 11]
            seed = 1000 * wid + i
            try:
                r = router.submit(p, new_tokens, sampling, seed=seed)
                toks, _ = r.result(timeout=120)
                with lock:
                    results.append((p, seed, toks, None))
            except Exception as e:  # noqa: BLE001 — counted by caller
                with lock:
                    results.append((p, seed, None, e))
            i += 1

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(n_workers)]
    for t in threads:
        t.start()
    return results, stop, threads


def _classify(results, want1, want2, new_tokens):
    """(errors, at_v1, at_v2, mismatches) over completed results."""
    errors, v1, v2, bad = [], 0, 0, []
    for p, seed, toks, err in results:
        if err is not None:
            errors.append(repr(err))
        elif toks == want1(p, new_tokens, seed):
            v1 += 1
        elif toks == want2(p, new_tokens, seed):
            v2 += 1
        else:
            bad.append((p, seed, toks))
    return errors, v1, v2, bad


def kill_draining_drill(new_tokens: int) -> dict:
    """Rolling upgrade under load; kill the DRAINING replica mid-swap.
    The rollout must abort typed with the fleet degraded-not-down and
    every completion token-exact at its admitted version."""
    from megatron_tpu.serving import RollingUpgradeError, SamplingOptions

    router, engines, gen1, gen2, root, d2 = _versioned_fleet(
        dict(num_slots=2, max_queue=64, max_len=128))
    want1, want2 = _serial_oracle(gen1), _serial_oracle(gen2)
    sampling = SamplingOptions(temperature=0.0)
    try:
        for eng in engines:
            eng.generate([3, 1, 4], 2, sampling, seed=0)
        # widen the mid-swap window deterministically: replica 0's
        # apply stalls briefly (the _fetch-seam monkeypatch idiom of
        # chaos_router), so the kill below reliably lands while the
        # replica is DRAINING or mid-apply — never after a completed
        # upgrade. A long direct request adds real drain work too.
        orig_apply = engines[0]._apply_swap

        def slow_apply(ticket):
            time.sleep(0.5)
            return orig_apply(ticket)

        engines[0]._apply_swap = slow_apply
        engines[0].submit([2, 2, 2], 80, sampling, seed=0)
        results, stop, threads = _load_workers(router, new_tokens)
        time.sleep(0.2)

        aborted = []

        def upgrade():
            try:
                router.rolling_upgrade(d2, swap_timeout_s=120)
            except RollingUpgradeError as e:
                aborted.append(repr(e))

        up = threading.Thread(target=upgrade)
        up.start()
        # the kill: the moment replica 0 enters its planned drain
        t0 = time.monotonic()
        while not router.replicas[0].upgrading \
                and time.monotonic() - t0 < 30:
            time.sleep(0.002)
        time.sleep(0.05)
        engines[0].close()  # the draining replica dies mid-swap
        up.join(timeout=180)
        time.sleep(0.3)
        stop.set()
        for t in threads:
            t.join()
        errors, v1, v2, bad = _classify(results, want1, want2,
                                        new_tokens)
        health = router.health()
        snap = router.aggregate_snapshot()
        # the degraded fleet still serves (on version 1 — the rollout
        # died before any replica upgraded)
        post = router.submit([9, 9, 8], 4, sampling, seed=99)
        post_toks, _ = post.result(timeout=60)
        post_exact = post_toks == want1([9, 9, 8], 4, 99)
        inv = invariant_sweep(router, [post])
    finally:
        router.close()
    return {
        "submitted": len(results), "errors": len(errors),
        "completed_v1": v1, "completed_v2": v2,
        "version_mismatches": len(bad),
        "rollout_aborted_typed": len(aborted) == 1,
        "health_state": health["state"],
        "healthz_ready": bool(health["healthy"]),
        "weight_swap_failures": int(snap["weight_swap_failures"]),
        "post_kill_serve_exact": post_exact,
        "invariants_ok": inv["ok"],
        "invariant_violations": inv["violations"],
        "ok": (not errors and not bad and len(aborted) == 1
               and health["state"] == "degraded" and health["healthy"]
               and post_exact and (v1 + v2) == len(results)
               and (v1 + v2) >= 4 and inv["ok"]),
    }


def corrupt_watch_drill(new_tokens: int) -> dict:
    """CheckpointWatcher drives the fleet: a good publish upgrades it
    hands-free; a corrupt publish is refused at the manifest gate with
    the fleet staying put and NO retry loop; the next good publish
    applies."""
    import jax

    from megatron_tpu.inference.generation import Generator
    from megatron_tpu.models import language_model as lm
    from megatron_tpu.serving import CheckpointWatcher, SamplingOptions

    router, engines, gen1, gen2, root, d2 = _versioned_fleet(
        dict(num_slots=2, max_queue=64, max_len=128))
    want2 = _serial_oracle(gen2)
    sampling = SamplingOptions(temperature=0.0)
    model = _model_cfg()
    try:
        for eng in engines:
            eng.generate([3, 1, 4], 2, sampling, seed=0)
        watcher = CheckpointWatcher(router, root, interval_s=0.1)
        # beat 1: the good v2 publish (already on disk) applies
        applied = watcher.poll_once()
        snap1 = router.aggregate_snapshot()
        v2_serving = (snap1["weight_version_min"] == 2.0
                      == snap1["weight_version_max"])
        r = router.submit([5, 6, 7], new_tokens, sampling, seed=5)
        toks, _ = r.result(timeout=60)
        exact_v2 = toks == want2([5, 6, 7], new_tokens, 5)
        # beat 2: a CORRUPT v3 publish — refused, counted, no loop
        p3 = lm.model_init(jax.random.PRNGKey(2), model)
        d3 = _publish(root, model, p3, 3)
        _corrupt_payload(d3)
        refused = not watcher.poll_once()
        failures_1 = watcher.failures
        re_polled = not watcher.poll_once()  # same tag: skipped
        failures_2 = watcher.failures
        snap2 = router.aggregate_snapshot()
        stayed = (snap2["weight_version_min"] == 2.0
                  == snap2["weight_version_max"])
        # beat 3: the NEXT good publish applies
        p4 = lm.model_init(jax.random.PRNGKey(3), model)
        _publish(root, model, p4, 4)
        recovered = watcher.poll_once()
        snap3 = router.aggregate_snapshot()
        v4_serving = (snap3["weight_version_min"] == 4.0
                      == snap3["weight_version_max"])
        health = router.health()
        inv = invariant_sweep(router)
    finally:
        router.close()
    return {
        "good_publish_applied": bool(applied),
        "fleet_on_v2": v2_serving, "serve_exact_v2": exact_v2,
        "corrupt_publish_refused": refused,
        "no_retry_loop": re_polled and failures_1 == failures_2 == 1,
        "fleet_stayed_on_v2": stayed,
        "weight_swap_failures": int(snap2["weight_swap_failures"]),
        "next_publish_applied": bool(recovered),
        "fleet_on_v4": v4_serving,
        "health_state": health["state"],
        "invariants_ok": inv["ok"],
        "invariant_violations": inv["violations"],
        "ok": (applied and v2_serving and exact_v2 and refused
               and re_polled and failures_2 == 1 and stayed
               and int(snap2["weight_swap_failures"]) >= 1
               and recovered and v4_serving
               and health["state"] == "running" and inv["ok"]),
    }


def disagg_race_drill(new_tokens: int) -> dict:
    """Rolling upgrade racing the prefill->decode handoff on a
    DISAGGREGATED fleet: zero 503s, every completion token-exact at its
    admitted version (pins the per-replica both-groups-atomic swap),
    handoffs keep advancing."""
    import jax

    if len(jax.devices()) < 4:
        return {"skipped": f"{len(jax.devices())} device(s) < 4 "
                           "(2 disaggregated replicas)", "ok": True}
    router, engines, gen1, gen2, root, d2 = _versioned_fleet(
        dict(num_slots=2, max_queue=64, max_len=128, kv_block_size=16,
             disaggregate_prefill=True),
        devices_per=2)
    want1, want2 = _serial_oracle(gen1), _serial_oracle(gen2)
    from megatron_tpu.serving import SamplingOptions
    sampling = SamplingOptions(temperature=0.0)
    try:
        for eng in engines:
            eng.generate([3, 1, 4], 2, sampling, seed=0)
        results, stop, threads = _load_workers(router, new_tokens)
        time.sleep(0.3)
        version = router.rolling_upgrade(d2, swap_timeout_s=120)
        time.sleep(0.3)
        stop.set()
        for t in threads:
            t.join()
        errors, v1, v2, bad = _classify(results, want1, want2,
                                        new_tokens)
        snap = router.aggregate_snapshot()
        health = router.health()
        # the upgraded fleet still hands off end to end at v2
        pre_handoffs = int(snap["handoffs"])
        post = router.submit([9, 9, 8], 4, sampling, seed=99)
        post_toks, _ = post.result(timeout=60)
        post_exact = post_toks == want2([9, 9, 8], 4, 99)
        snap_post = router.aggregate_snapshot()
        inv = invariant_sweep(router, [post])
    finally:
        router.close()
    return {
        "submitted": len(results), "errors": len(errors),
        "completed_v1": v1, "completed_v2": v2,
        "version_mismatches": len(bad),
        "upgraded_to": version.label,
        "rolling_upgrades": int(snap["rolling_upgrades"]),
        "health_state": health["state"],
        "handoffs": int(snap_post["handoffs"]),
        "post_upgrade_serve_exact": post_exact,
        "invariants_ok": inv["ok"],
        "invariant_violations": inv["violations"],
        "ok": (not errors and not bad and (v1 + v2) == len(results)
               and (v1 + v2) >= 4 and v2 >= 1
               and int(snap["rolling_upgrades"]) == 1
               and health["state"] == "running" and post_exact
               and int(snap_post["handoffs"]) > pre_handoffs
               and inv["ok"]),
    }


def run_chaos(new_tokens: int) -> dict:
    t0 = time.monotonic()
    kill = kill_draining_drill(new_tokens)
    watch = corrupt_watch_drill(new_tokens)
    disagg = disagg_race_drill(new_tokens)
    wall_s = time.monotonic() - t0
    ok = kill["ok"] and watch["ok"] and disagg["ok"]
    return {
        "metric": "upgrade_chaos_swap_failures_contained",
        "value": (kill.get("weight_swap_failures", 0)
                  + watch.get("weight_swap_failures", 0)),
        "unit": ("refused/failed swaps across the kill + corrupt-watch "
                 "drills (fleet kept serving through every one)"),
        "vs_baseline": None,
        "completed": ok,
        "kill_draining": kill,
        "corrupt_watch": watch,
        "disagg_race": disagg,
        "wall_s": round(wall_s, 1),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fixed scenario for bench extras / CI")
    ap.add_argument("--new_tokens", type=int, default=12,
                    help="decode length of the drill requests")
    ap.add_argument("--out", type=str, default=None,
                    help="also write the JSON record here")
    args = ap.parse_args(argv)

    # the disaggregated race drill needs 4 devices (2 replicas x 2 chip
    # groups)
    force_host_devices(4)
    ensure_compile_cache()
    if args.smoke:
        args.new_tokens = 8

    record = run_chaos(args.new_tokens)
    emit_record(record, args.out, seed=0)  # scripted: fixed workload
    return 0 if record["completed"] else 1


if __name__ == "__main__":
    sys.exit(main())
