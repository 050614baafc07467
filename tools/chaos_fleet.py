"""Multi-PROCESS front-door chaos drill: a real fleet of replica
server processes behind the remote router, under seeded fault
schedules — nothing strands, nothing moves a token.

tools/chaos_router.py drills the router over N in-process engines;
this tool crosses the process boundary (docs/serving.md "Front
door"): each replica is `chaos_fleet.py --serve_replica` — a REAL
`MegatronServer --replica_mode` process on its own port, stdlib HTTP
transport — and the parent drives an `EngineRouter` over
`RemoteReplica` clients, so every fault below exercises the actual
wire path (SSE streams, typed transport faults, Last-Event-ID
resume, health probes over TCP). Four drills, seeded:

1. **sigkill**: one replica process is SIGKILLed mid-decode.
   Contract: zero stranded futures, every COMPLETED request
   token-exact vs the parent's serial oracle (failover resubmits by
   seed), the router reports DEGRADED (not down) and keeps accepting;
   after a respawn on the same port the half-open canary re-admits
   the replica — the fleet ends at full strength.
2. **sigstop**: one replica is SIGSTOPped (a wedged process: TCP
   still connects, nothing answers). Contract: health probes time
   out -> missed heartbeats eject it, in-flight streams fail over
   token-exact, and after SIGCONT the canary path re-admits it.
3. **flaky_proxy**: one replica is reached only through a seeded
   fault shim (refuse / truncate-after-N-bytes / added latency on
   every connection). Contract: each injected fault lands as a TYPED
   transport error inside the retry/reconnect/failover machinery —
   outcomes stay token-exact, no bare exceptions escape.
4. **restart**: a replica is SIGKILLed and respawned WHILE traffic
   flows (the mid-storm restart). Contract: traffic submitted across
   the restart window resolves token-exact and the fleet returns to
   full strength.

Every drill finishes with a fleet-mode `invariants.check_all` sweep
(serving/invariants.py): the router aggregates per-replica invariant
reports over HTTP (`GET /invariants`), so per-replica request
conservation + KV accounting + schema run INSIDE each replica
process while the router-level degraded-not-down law runs here. A
replica that is dead at sweep time is recorded unreachable, not
convicted.

Emits ONE JSON record on stdout (and to --out) carrying the seed and
a repro line, so a CI-logged violation reproduces from the log line
alone:

  JAX_PLATFORMS=cpu python tools/chaos_fleet.py --smoke [--out FILE]
  JAX_PLATFORMS=cpu python tools/chaos_fleet.py --seed 7 --replicas 3
"""
from __future__ import annotations

import argparse
import os
import random
import signal
import socket
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from megatron_tpu.utils.compile_cache import ensure_compile_cache
from tools.chaos_common import (IntTokenizer, emit_record, free_port,
                                invariant_sweep,
                                resolve_exact as _resolve_exact,
                                serial_oracle as _serial_oracle,
                                spawn_replica, tiny_generator,
                                tiny_model_cfg, wait_replica_ready)

# the replica processes and the parent's serial oracle must build the
# IDENTICAL tiny model (same seed, same dtype, same binary) — that is
# what makes cross-process token-exactness a real check and not a
# coincidence
REPLICA_SERVING = dict(num_slots=4, max_queue=64,
                       enable_prefix_cache=True, kv_block_size=16)


# ---------------------------------------------------------------------
# replica child mode
# ---------------------------------------------------------------------
def serve_replica(port: int) -> int:
    """`--serve_replica`: run ONE tiny engine as a standalone
    `--replica_mode` server process on 127.0.0.1:port. The parent
    talks to it exclusively over HTTP."""
    from megatron_tpu.config import ServingConfig
    from megatron_tpu.inference.server import MegatronServer
    cfg = tiny_model_cfg()
    gen = tiny_generator(cfg)
    serving = ServingConfig(replica_mode=True,
                            **REPLICA_SERVING).validate(cfg)
    server = MegatronServer(gen, IntTokenizer(), serving=serving)
    server._run_stdlib("127.0.0.1", port)
    return 0


# ---------------------------------------------------------------------
# parent-side fleet handle
# ---------------------------------------------------------------------
class Fleet:
    """N replica processes + the remote router over them, plus the
    process handles the drills SIGKILL/SIGSTOP."""

    def __init__(self, n: int, heartbeat_s: float = 2.0):
        from megatron_tpu.serving import EngineRouter
        from megatron_tpu.serving.metrics import ServingMetrics
        from megatron_tpu.serving.remote import RemoteReplica
        self.ports = [free_port() for _ in range(n)]
        self.procs = [spawn_replica(p) for p in self.ports]
        for port, proc in zip(self.ports, self.procs):
            wait_replica_ready(f"127.0.0.1:{port}", proc=proc)
        self.counters = ServingMetrics()
        self.replicas = [
            RemoteReplica(f"127.0.0.1:{port}", counters=self.counters,
                          connect_timeout_s=2.0, read_timeout_s=5.0,
                          max_retries=2, digest_interval_s=0.5)
            for port in self.ports]
        self.router = EngineRouter(self.replicas, metrics=self.counters,
                                   max_retries=2,
                                   heartbeat_timeout_s=heartbeat_s,
                                   probe_backoff_s=0.2)

    def respawn(self, i: int) -> None:
        self.procs[i] = spawn_replica(self.ports[i])
        wait_replica_ready(f"127.0.0.1:{self.ports[i]}",
                           proc=self.procs[i])

    def close(self) -> None:
        try:
            self.router.close()
        except Exception:  # noqa: BLE001 — teardown is best-effort
            pass
        for p in self.procs:
            try:
                if p.poll() is None:
                    p.kill()
                p.wait(timeout=10)
            except Exception:  # noqa: BLE001
                pass


def _sampling():
    from megatron_tpu.serving import SamplingOptions
    return SamplingOptions(temperature=0.0)


def submit_batch(router, rng: random.Random, n_reqs: int,
                 new_tokens: int, seed0: int = 0):
    """n_reqs greedy requests over seeded random 4-token prompts
    (vocab 1..127 — 0 is the pad id). Greedy keeps the oracle
    seed-independent; UNIQUE seeds still ride along so the failover
    resubmission path carries them token-exact."""
    sampling = _sampling()
    reqs = []
    for i in range(n_reqs):
        p = [rng.randint(1, 127) for _ in range(4)]
        reqs.append((router.submit(p, new_tokens, sampling,
                                   seed=seed0 + i), p, new_tokens))
    return reqs


def wait_readmitted(fleet: Fleet, timeout: float = 90.0):
    """Drive the half-open re-admission path: DOWN->PROBING needs a
    probe WINDOW and a trial request, so poll router health AND feed
    tiny canary submits until every replica is back in rotation.
    Returns (readmitted, canary_reqs) — the canaries join the drill's
    resolve/sweep so they can never strand silently."""
    sampling = _sampling()
    deadline = time.monotonic() + timeout
    canaries = []
    while time.monotonic() < deadline:
        h = fleet.router.health()
        if h.get("replicas_up", 0) >= len(fleet.replicas):
            return True, canaries
        r = fleet.router.submit([3, 1, 4, 1], 2, sampling, seed=0)
        try:
            r.result(timeout=30)
        except Exception:  # noqa: BLE001 — classified in resolve
            pass
        canaries.append((r, [3, 1, 4, 1], 2))
        time.sleep(0.25)
    return False, canaries


def _drill_wrap(fleet: Fleet, want, name: str, body: dict,
                reqs) -> dict:
    """Shared drill tail: resolve every future token-exact, then run
    the fleet-mode invariant sweep over HTTP."""
    outcomes, exact = _resolve_exact(reqs, want)
    inv = invariant_sweep(fleet.router, [r for r, _, _ in reqs],
                          strict=True)
    body.update({
        "drill": name, "outcomes": outcomes, "exact": exact,
        "stranded": outcomes["stranded"],
        "invariants_ok": bool(inv.get("ok")),
        "violations": [str(v) for v in inv.get("violations", [])],
    })
    body["ok"] = (exact and outcomes["stranded"] == 0
                  and body["invariants_ok"]
                  and all(body.get(k, True) for k in
                          ("degraded_not_down", "post_ok",
                           "readmitted", "typed_only")))
    return body


# ---------------------------------------------------------------------
# drills
# ---------------------------------------------------------------------
def drill_sigkill(fleet: Fleet, want, rng: random.Random,
                  new_tokens: int, n_reqs: int) -> dict:
    victim = rng.randrange(len(fleet.procs))
    reqs = submit_batch(fleet.router, rng, n_reqs, new_tokens)
    time.sleep(0.2)  # let decode start so the kill lands mid-stream
    fleet.procs[victim].kill()
    fleet.procs[victim].wait()
    # the front door still serves after losing a process
    post = fleet.router.submit([9, 9, 8, 7], 4, _sampling(), seed=99)
    post_toks, _ = post.result(timeout=60)
    health = fleet.router.health()
    # bring the fleet back to full strength: same port, new process
    fleet.respawn(victim)
    readmitted, canaries = wait_readmitted(fleet)
    return _drill_wrap(fleet, want, "sigkill", {
        "victim": victim,
        "post_ok": post_toks == want([9, 9, 8, 7], 4),
        "degraded_not_down": health["accepting"],
        "state_after_kill": health["state"],
        "readmitted": readmitted,
    }, reqs + [(post, [9, 9, 8, 7], 4)] + canaries)


def drill_sigstop(fleet: Fleet, want, rng: random.Random,
                  new_tokens: int, n_reqs: int) -> dict:
    victim = rng.randrange(len(fleet.procs))
    reqs = submit_batch(fleet.router, rng, n_reqs, new_tokens)
    time.sleep(0.2)
    os.kill(fleet.procs[victim].pid, signal.SIGSTOP)
    try:
        # more traffic INTO the wedge: probes time out, heartbeats
        # lapse, the wedged replica ejects, this work fails over
        reqs += submit_batch(fleet.router, rng, n_reqs, new_tokens,
                             seed0=100)
        time.sleep(0.5)
        health = fleet.router.health()
    finally:
        os.kill(fleet.procs[victim].pid, signal.SIGCONT)
    readmitted, canaries = wait_readmitted(fleet)
    return _drill_wrap(fleet, want, "sigstop", {
        "victim": victim,
        "degraded_not_down": health["accepting"],
        "readmitted": readmitted,
    }, reqs + canaries)


class FlakyProxy(threading.Thread):
    """Seeded per-connection TCP fault shim in front of ONE replica:
    each accepted connection draws a verdict from the seeded rng —
    refuse (close before a byte), cut (truncate the upstream->client
    stream after a seeded byte budget: a mid-body reset / truncated
    SSE), delay (per-chunk added latency), or clean pump. The client
    side sees exactly the fault taxonomy remote.py types."""

    def __init__(self, upstream_port: int, seed: int,
                 refuse_p: float = 0.15, cut_p: float = 0.15,
                 delay_s: float = 0.03):
        super().__init__(daemon=True, name="flaky-proxy")
        self.upstream_port = upstream_port
        self.port = free_port()
        self._rng = random.Random(seed)
        self.refuse_p, self.cut_p, self.delay_s = refuse_p, cut_p, delay_s
        self.faults = {"refuse": 0, "cut": 0, "delay": 0, "clean": 0}
        self._listen = socket.socket()
        self._listen.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listen.bind(("127.0.0.1", self.port))
        self._listen.listen(64)
        self._stop = threading.Event()

    def run(self):
        while not self._stop.is_set():
            try:
                client, _ = self._listen.accept()
            except OSError:
                return
            # verdicts draw in ACCEPT order on this one thread, so a
            # seed pins the fault schedule
            r = self._rng.random()
            budget = self._rng.randint(64, 600)
            verdict = ("refuse" if r < self.refuse_p
                       else "cut" if r < self.refuse_p + self.cut_p
                       else "delay" if r < self.refuse_p + self.cut_p
                       + 0.25 else "clean")
            self.faults[verdict] += 1
            threading.Thread(target=self._handle, daemon=True,
                             args=(client, verdict, budget)).start()

    def _handle(self, client, verdict: str, budget: int):
        try:
            if verdict == "refuse":
                client.close()
                return
            up = socket.create_connection(
                ("127.0.0.1", self.upstream_port), timeout=5.0)
        except OSError:
            client.close()
            return

        def pump(src, dst, limit=None, delay=0.0):
            moved = 0
            try:
                while True:
                    data = src.recv(4096)
                    if not data:
                        break
                    if limit is not None and moved + len(data) > limit:
                        data = data[:max(0, limit - moved)]
                        if data:
                            dst.sendall(data)
                        break  # truncate: reset mid-body
                    if delay:
                        time.sleep(delay)
                    dst.sendall(data)
                    moved += len(data)
            except OSError:
                pass
            finally:
                for s in (src, dst):
                    try:
                        s.close()
                    except OSError:
                        pass

        threading.Thread(target=pump, args=(client, up),
                         daemon=True).start()
        pump(up, client,
             limit=budget if verdict == "cut" else None,
             delay=self.delay_s if verdict == "delay" else 0.0)

    def close(self):
        self._stop.set()
        try:
            self._listen.close()
        except OSError:
            pass


def drill_flaky_proxy(fleet: Fleet, want, rng: random.Random,
                      new_tokens: int, n_reqs: int, seed: int) -> dict:
    """A SECOND router whose first replica is only reachable through
    the fault shim (the other direct) — the shared replica processes
    serve both routers concurrently, which is itself load."""
    from megatron_tpu.serving import EngineRouter, ServiceUnavailableError
    from megatron_tpu.serving.metrics import ServingMetrics
    from megatron_tpu.serving.remote import RemoteReplica
    proxy = FlakyProxy(fleet.ports[0], seed=seed)
    proxy.start()
    counters = ServingMetrics()
    replicas = [
        RemoteReplica(f"127.0.0.1:{proxy.port}", counters=counters,
                      connect_timeout_s=2.0, read_timeout_s=5.0,
                      max_retries=2, digest_interval_s=0.5),
        RemoteReplica(f"127.0.0.1:{fleet.ports[-1]}", counters=counters,
                      connect_timeout_s=2.0, read_timeout_s=5.0,
                      max_retries=2, digest_interval_s=0.5)]
    router = EngineRouter(replicas, metrics=counters, max_retries=2,
                          heartbeat_timeout_s=2.0, probe_backoff_s=0.2)
    typed_only = True
    reqs = []
    try:
        sampling = _sampling()
        for i in range(n_reqs):
            p = [rng.randint(1, 127) for _ in range(4)]
            try:
                reqs.append((router.submit(p, new_tokens, sampling,
                                           seed=i), p, new_tokens))
            except ServiceUnavailableError:
                pass  # typed admission-time refusal: acceptable
            except Exception:  # noqa: BLE001 — the drill's whole point
                typed_only = False
        outcomes, exact = _resolve_exact(reqs, want)
        snap = router.aggregate_snapshot()
        inv = invariant_sweep(router, [r for r, _, _ in reqs],
                              strict=True)
    finally:
        router.close()
        proxy.close()
    body = {
        "drill": "flaky_proxy", "outcomes": outcomes, "exact": exact,
        "stranded": outcomes["stranded"], "typed_only": typed_only,
        "proxy_faults": proxy.faults,
        "remote_retries": snap.get("router_remote_retries", 0.0),
        "remote_timeouts": snap.get("router_remote_timeouts", 0.0),
        "invariants_ok": bool(inv.get("ok")),
        "violations": [str(v) for v in inv.get("violations", [])],
    }
    body["ok"] = (exact and outcomes["stranded"] == 0 and typed_only
                  and body["invariants_ok"])
    return body


def drill_restart(fleet: Fleet, want, rng: random.Random,
                  new_tokens: int, n_reqs: int) -> dict:
    """Mid-storm restart: the kill AND the respawn both land while
    traffic is in flight."""
    victim = rng.randrange(len(fleet.procs))
    reqs = submit_batch(fleet.router, rng, n_reqs, new_tokens)
    time.sleep(0.15)
    fleet.procs[victim].kill()
    fleet.procs[victim].wait()
    # storm continues while the process is gone...
    reqs += submit_batch(fleet.router, rng, n_reqs, new_tokens,
                         seed0=200)
    # ...and while it comes back
    fleet.respawn(victim)
    reqs += submit_batch(fleet.router, rng, n_reqs, new_tokens,
                         seed0=300)
    readmitted, canaries = wait_readmitted(fleet)
    return _drill_wrap(fleet, want, "restart", {
        "victim": victim, "readmitted": readmitted,
    }, reqs + canaries)


# ---------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------
DRILLS = ("sigkill", "sigstop", "flaky_proxy", "restart")


def run_chaos(seed: int, n_replicas: int, new_tokens: int,
              n_reqs: int, drills) -> dict:
    rng = random.Random(seed)
    fleet = Fleet(n_replicas)
    want = _serial_oracle(tiny_generator(tiny_model_cfg()))
    results = {}
    fns = {"sigkill": drill_sigkill, "sigstop": drill_sigstop,
           "restart": drill_restart}
    try:
        for name in drills:
            try:
                if name == "flaky_proxy":
                    results[name] = drill_flaky_proxy(
                        fleet, want, rng, new_tokens, n_reqs, seed)
                elif name in fns:
                    results[name] = fns[name](fleet, want, rng,
                                              new_tokens, n_reqs)
                else:
                    raise SystemExit(f"unknown drill {name!r}")
            except SystemExit:
                raise
            except Exception as e:  # noqa: BLE001 — a crashed drill
                # is a VIOLATION with a record, not a stack trace
                # without one (the record carries the repro line)
                results[name] = {
                    "drill": name, "ok": False, "invariants_ok": False,
                    "crash": f"{type(e).__name__}: {e}"}
        snap = fleet.router.aggregate_snapshot()
    finally:
        fleet.close()
    completed = all(r.get("ok") for r in results.values())
    record = {
        "tool": "chaos_fleet", "completed": completed,
        "replicas": n_replicas, "new_tokens": new_tokens,
        "drills": results,
        "invariants_ok": all(r.get("invariants_ok")
                             for r in results.values()),
        "fleet_counters": {
            k: snap.get(k, 0.0)
            for k in ("router_failovers", "router_retries",
                      "router_remote_timeouts", "router_remote_retries",
                      "router_probe_failures", "fleet_replicas_up")},
        "repro": (f"python tools/chaos_fleet.py --seed {seed} "
                  f"--replicas {n_replicas} --new_tokens {new_tokens} "
                  f"--requests {n_reqs}"),
    }
    return record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--serve_replica", action="store_true",
                    help="child mode: run ONE replica server process. "
                         "Every replica process wants a device of its "
                         "own: run the drill with one device per "
                         "replica, or on the CPU (JAX_PLATFORMS=cpu) — "
                         "two replicas cannot share one chip")
    ap.add_argument("--port", type=int, default=0,
                    help="child mode: port to serve on")
    ap.add_argument("--seed", type=int, default=0,
                    help="fault-schedule seed (printed in the record)")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--new_tokens", type=int, default=12)
    ap.add_argument("--requests", type=int, default=8,
                    help="requests per drill batch")
    ap.add_argument("--drills", type=str, default=",".join(DRILLS),
                    help="comma list from: " + ",".join(DRILLS))
    ap.add_argument("--smoke", action="store_true",
                    help="2 replicas, sigkill drill only (CI extras)")
    ap.add_argument("--out", type=str, default=None,
                    help="also write the JSON record here")
    args = ap.parse_args(argv)

    ensure_compile_cache()
    if args.serve_replica:
        if not args.port:
            ap.error("--serve_replica requires --port")
        return serve_replica(args.port)

    drills = [d for d in args.drills.split(",") if d]
    if args.smoke:
        args.replicas, args.new_tokens, args.requests = 2, 12, 6
        drills = ["sigkill"]

    record = run_chaos(args.seed, args.replicas, args.new_tokens,
                       args.requests, drills)
    emit_record(record, args.out, seed=args.seed)
    if not record["completed"]:
        print(f"VIOLATION — repro: {record['repro']}",
              file=sys.stderr)
    return 0 if record["completed"] else 1


if __name__ == "__main__":
    sys.exit(main())
