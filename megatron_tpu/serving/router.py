"""Prefix-affinity router with health-driven failover: the in-process
front door over N `ServingEngine` replicas.

One engine replica dies with its process (or its crash-loop breaker);
the ROADMAP's "millions of users" means N replicas behind a router
that survives any one of them crashing, wedging, or draining. The
signals were all built by earlier PRs — `health()` liveness + breaker
state (PR 6), queue/shed accounting, service-time EWMA — this module
consumes them:

- **Cache-aware routing** (SGLang-style, PAPERS.md): each request goes
  to the replica whose prefix index holds the LONGEST match for its
  prompt (`ServingEngine.prefix_peek` — a cheap, racy-by-design
  host-side read of the PrefixIndex + host KV tier), ties broken by
  least-loaded: (queue_depth + busy slots) x the replica's
  service-time EWMA, both straight from the `health()` snapshot.
- **Health-driven failover**: a replica whose snapshot reports
  draining, breaker-tripped, a dead loop — or which has not produced a
  healthy snapshot within `heartbeat_timeout_s` (wedged counts after
  the grace) — is EJECTED from rotation (`router_failovers`). Work
  it already failed (or work stuck on it past the heartbeat grace) is
  resubmitted to a survivor with bounded retries + backoff
  (`router_retries`), the ORIGINAL arrival id preserved so the retry
  re-enters the survivor's EDF queue at its original position. Every
  request is submitted with a concrete seed, so a full resubmission
  regenerates the identical token stream — retried completions are
  token-exact (chaos-pinned). Only when EVERY replica is down does
  submit fail with `NoReplicaAvailableError` (HTTP 503).
- **Half-open recovery**: a DOWN replica whose health snapshot turns
  healthy again re-enters as PROBING — exactly ONE canary request is
  routed to it; success promotes it to full rotation, failure demotes
  it back with `probe_backoff_s` before the next probe.

Degradation is exact: with one replica the pick is the identity and a
healthy replica's requests never retry, so behavior matches the bare
engine (the server only builds a router for `num_replicas >= 2`,
test-pinned).

Thread contract: `submit`/`cancel`/`health`/`queue_depth` run on HTTP
threads under the router lock; retries are driven by the CALLER's
thread inside `RouterRequest.wait_done`/`wait_token` (every future a
caller waits on resolves — there is no router thread to die).
"""
from __future__ import annotations

import threading
import time
from typing import List, Optional, Sequence

from megatron_tpu.serving.metrics import _BASE_COUNTERS, ServingMetrics
from megatron_tpu.serving.request import (RequestState, SamplingOptions,
                                          ServiceUnavailableError)
from megatron_tpu.serving.scheduler import (AdmissionError,
                                            EngineUnhealthyError)
from megatron_tpu.utils.logging import print_rank_0

UP, DOWN, PROBING = "up", "down", "probing"

# gauges summed across replicas in the aggregate /metrics snapshot
# (prefill_devices/decode_devices: the fleet's per-phase chip
# footprint — the placement plan's aggregate-visible shape)
_SUM_GAUGES = ("queue_depth", "active_slots", "num_slots",
               "kv_blocks_used", "kv_blocks_retained", "kv_bytes_wasted",
               "kv_pool_bytes", "kv_ring_bytes", "kv_full_bytes",
               "conv_state_bytes", "ssm_state_bytes", "ssd_state_bytes",
               "kda_state_bytes", "gdn_state_bytes",
               "active_adapters", "prefill_devices", "decode_devices")
# gauges reported as the WORST replica (max) — per-request /
# per-group readings where summing fractions would be meaningless
# (same treatment as the *_ms latency keys below). The per-phase tp
# widths ride here too: summing widths across replicas would invent a
# mesh no engine runs. degrade_level is max by CONTRACT (serving/
# degrade.py): a fleet scrape reports its most-degraded replica.
# kv_gather_bytes_per_step / kv_attn_path were the PR 13 lesson's
# recurrence — present in every engine snapshot but in NEITHER
# aggregation list, so fleet scrapes silently zeroed them; the
# metrics._BASE_GAUGES coverage test now pins that every
# always-present gauge has an aggregation rule.
_MAX_GAUGES = ("handoff_bytes_per_req", "prefill_group_busy",
               "decode_group_busy", "prefill_tp", "decode_tp",
               "kv_gather_bytes_per_step", "kv_attn_path",
               "kv_bytes_per_token", "kv_bytes_per_slot", "degrade_level",
               # pipeline-sharded decode: stage depth / wave count are
               # per-replica mesh shapes (summing would invent a
               # pipeline no engine runs), the bubble is an idle
               # FRACTION, and the residual-crossing bytes are a
               # per-step per-replica reading like the gather gauge
               "serving_pp", "pp_waves", "pp_stage_bubble",
               "pp_activation_bytes_per_step")


class NoReplicaAvailableError(ServiceUnavailableError):
    """Every replica is ejected/down — the HTTP layer maps this to 503
    (the router-level analogue of the breaker's EngineUnhealthyError)."""


class RollingUpgradeError(RuntimeError):
    """A rolling fleet upgrade aborted partway: the failing replica
    stayed on (or rolled back to) its previous weights and re-enters
    rotation through the normal half-open canary — the FLEET KEEPS
    SERVING throughout (replicas already upgraded stay on the new
    version; the rest stay on the old one, which the weight_version
    min/max gauges make visible)."""


class _Replica:
    __slots__ = ("idx", "engine", "state", "last_health",
                 "last_healthy_t", "down_until", "canary", "canary_t",
                 "upgrading")

    def __init__(self, idx: int, engine):
        self.idx = idx
        self.engine = engine
        self.state = UP
        self.last_health: dict = {}
        self.last_healthy_t = time.monotonic()
        self.down_until = 0.0
        self.canary = None  # RouterRequest probing this replica
        self.canary_t = 0.0
        # planned drain (rolling_upgrade): held DOWN — out of rotation,
        # queued/in-flight work fails over through the normal retry
        # path — until the swap verdict re-admits or re-ejects it
        self.upgrading = False


class RouterRequest:
    """The future a router caller holds: a facade over the CURRENT
    attempt's `GenRequest`, resubmitting on retryable failures. Token
    reads (`generated`, `wait_token`) delegate to the live attempt —
    after a retry the new attempt regenerates the identical stream
    (same prompt/seed/sampling), so a streaming consumer's already-
    emitted indices replay bit-equal and it simply waits for the
    regeneration to pass its cursor."""

    def __init__(self, router: "EngineRouter", spec: dict):
        self._router = router
        self.spec = spec
        self.arrival_id: Optional[int] = None
        self.attempts = 0
        self.inner = None          # current attempt's GenRequest
        self.replica: Optional[_Replica] = None
        self.cancelled = False
        self._terminal = None      # ("ok"|"err", GenRequest) | ("exc", e)
        self._lock = threading.RLock()
        self._last_health_check = 0.0  # rate-limits _pump's re-check

    # -- facade fields the HTTP layer / tests read ---------------------
    @property
    def id(self):
        return self.arrival_id

    @property
    def prompt(self) -> List[int]:
        return self.spec["prompt"]

    @property
    def generated(self) -> List[int]:
        inner = self.inner
        return inner.generated if inner is not None else []

    @property
    def gen_logprobs(self) -> List[float]:
        inner = self.inner
        return inner.gen_logprobs if inner is not None else []

    @property
    def state(self):
        if self._terminal is not None and self._terminal[0] == "ok":
            return RequestState.FINISHED
        if self._terminal is not None:
            return RequestState.FAILED
        inner = self.inner
        return inner.state if inner is not None else RequestState.QUEUED

    def done(self) -> bool:
        return self._terminal is not None

    def cancel(self):
        self.cancelled = True
        inner, rep = self.inner, self.replica
        if inner is not None and rep is not None:
            rep.engine.cancel(inner)

    # -- retry pump (caller thread) ------------------------------------
    def _settle(self, terminal: str, attempt_ok: Optional[bool]):
        """Mark terminal; report the attempt verdict to the canary
        machinery (None = inconclusive: clears the canary slot without
        promoting or re-ejecting)."""
        self._terminal = (terminal, self.inner)
        self._router._note_attempt(self.replica, self, ok=attempt_ok)

    def _on_inner_done(self):
        with self._lock:
            if self._terminal is not None:
                return
            inner = self.inner
            if not inner.done():
                return  # a concurrent pump already retried this attempt
            if inner.state is RequestState.FINISHED and inner.error is None:
                self._settle("ok", True)
                return
            kind = getattr(inner, "error_kind", "error")
            if self.cancelled or kind in ("deadline", "grammar"):
                # client gave up / SLO burned / constrained generation
                # dead-ended: a retry cannot help (a grammar dead end
                # is deterministic in (grammar, prompt, seed) — every
                # replica would walk into the same wall) — terminal
                # here, inconclusive for the replica (neither outcome
                # says the replica itself is broken)
                self._settle("err", None)
                return
            # retryable infra failure (engine crash/shutdown/hang/drain)
            self._retry(f"attempt on replica {self.replica.idx} failed: "
                        f"{inner.error}")

    def _retry(self, why: str):
        failed = self.replica
        if self.attempts >= self._router.max_retries:
            inner = self.inner
            if inner is not None and not inner.done():
                # exhaustion can settle on a still-RUNNING inner (a
                # wedged replica's cancel may never be consumed):
                # fail it NOW so result() raises the typed retryable
                # 503, not a TimeoutError-shaped 500. Idempotent —
                # first terminal transition wins if the engine races.
                inner.fail(
                    "router: failover retries exhausted "
                    f"({self._router.max_retries}) after replica "
                    f"failures; retry against another front door "
                    f"({why})", kind="unavailable")
            self._settle("err", False)
            return
        self._router._note_attempt(failed, self, ok=False)
        self._router.metrics.count("router_retries")
        self.attempts += 1
        time.sleep(min(self._router.retry_backoff_s * self.attempts, 1.0))
        try:
            self._router._dispatch(
                self, exclude=(failed.idx,) if failed is not None else ())
        except Exception as e:  # noqa: BLE001 — typed 503/429 preserved
            self._terminal = ("exc", e)
        else:
            print_rank_0(f"router: requeued request {self.arrival_id} "
                         f"onto replica {self.replica.idx} "
                         f"(attempt {self.attempts + 1}; {why})")

    def _pump(self, step: float, token_i: Optional[int] = None):
        """One wait-and-check beat: wait on the current attempt (the
        per-token condition when a streaming cursor passes `token_i` —
        tokens deliver the moment they land, not at the poll edge),
        then detect a mid-flight replica ejection (the attempt may
        never resolve on a wedged-and-ejected replica — cancel it
        there and retry on a survivor instead of stranding the
        caller). The health re-check is rate-limited per request so N
        waiting callers don't serialize health() refreshes on the
        router lock every beat."""
        inner, rep = self.inner, self.replica
        if token_i is None:
            inner._done.wait(step)
        else:
            inner.wait_token(token_i, step)
        if inner.done():
            self._on_inner_done()
            return
        now = time.monotonic()
        if now - self._last_health_check < 0.5:
            return
        self._last_health_check = now
        if rep is not None and self._router._check_replica(rep) == DOWN \
                and not inner.done():
            with self._lock:
                if self._terminal is None and self.inner is inner:
                    rep.engine.cancel(inner)
                    self._retry(f"replica {rep.idx} ejected mid-flight")

    def wait_done(self, timeout: Optional[float] = None) -> bool:
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        while self._terminal is None:
            step = 0.25
            if deadline is not None:
                rem = deadline - time.monotonic()
                if rem <= 0:
                    return False
                step = min(step, rem)
            self._pump(step)
        return True

    def wait_token(self, i: int, timeout: Optional[float] = None) -> bool:
        """True once token i exists on the live attempt or the request
        is terminal — the streaming cursor's wait, driving the same
        retry pump as `wait_done`."""
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        while True:
            inner = self.inner
            if inner is not None and len(inner.generated) > i:
                return True
            if self._terminal is not None:
                return True
            step = 0.25
            if deadline is not None:
                rem = deadline - time.monotonic()
                if rem <= 0:
                    return False
                step = min(step, rem)
            self._pump(step, token_i=i)

    def result(self, timeout: Optional[float] = None):
        if not self.wait_done(timeout):
            raise TimeoutError(
                f"router request {self.arrival_id} still pending "
                f"(attempt {self.attempts + 1})")
        kind, val = self._terminal
        if kind == "exc":
            raise val
        # "ok" returns the tokens; "err" raises the typed error —
        # both via the settled attempt's own result()
        return val.result(timeout=0.001)


class EngineRouter:
    """In-process front door over N engine replicas (module docstring
    has the policy). API-compatible with `ServingEngine` where the HTTP
    layer touches it: submit/cancel/generate/drain/close/health/
    queue_depth/metrics/max_len."""

    def __init__(self, engines: Sequence, metrics: Optional[ServingMetrics]
                 = None, max_retries: int = 2,
                 heartbeat_timeout_s: float = 5.0,
                 probe_backoff_s: float = 0.5,
                 retry_backoff_s: float = 0.05):
        assert engines, "router needs at least one replica"
        self.replicas = [_Replica(i, e) for i, e in enumerate(engines)]
        self.metrics = metrics if metrics is not None else ServingMetrics()
        self.max_retries = max(int(max_retries), 0)
        self.heartbeat_timeout_s = float(heartbeat_timeout_s)
        self.probe_backoff_s = float(probe_backoff_s)
        self.retry_backoff_s = float(retry_backoff_s)
        # canary verdicts are settled by the canary's WAITING caller;
        # an abandoned caller (disconnect, caller-side timeout) would
        # otherwise pin the replica in PROBING forever — after this
        # long with no verdict the canary slot frees and the next
        # request probes afresh
        self.canary_timeout_s = max(self.heartbeat_timeout_s * 2, 10.0)
        self.max_len = min(e.max_len for e in engines)
        self._lock = threading.RLock()

    # ------------------------------------------------------------------
    # health tracking / ejection / half-open probing
    # ------------------------------------------------------------------
    def _eval_replica(self, rep: _Replica, now: float) -> str:
        """Refresh one replica's snapshot and classify it. DOWN when the
        snapshot is unobtainable, reports a hard-down state (breaker
        open, draining, loop dead), or no healthy snapshot has been
        seen within the heartbeat deadline (a wedged replica gets that
        grace — its watchdog may restart it — then is ejected)."""
        try:
            h = rep.engine.health()
        except Exception:  # snapshot itself failed: missed heartbeat
            h = None
        if h is not None:
            rep.last_health = h
        hard_down = (h is None or h.get("circuit_breaker_open")
                     or h.get("state") in ("draining", "unhealthy")
                     or not h.get("loop_alive", False))
        if not hard_down and h.get("healthy") \
                and h.get("state") == "running":
            rep.last_healthy_t = now
        missed = now - rep.last_healthy_t > self.heartbeat_timeout_s
        return DOWN if (hard_down or missed) else UP

    def _check_replica(self, rep: _Replica) -> str:
        with self._lock:
            self._refresh_one(rep, time.monotonic())
            return rep.state

    def _refresh_one(self, rep: _Replica, now: float):
        if rep.upgrading:
            # planned drain (rolling_upgrade): the replica is healthy
            # but held out of rotation like a DOWN one — its work fails
            # over through the SAME retry path — and no canary runs
            # until the swap verdict decides re-admission
            rep.state = DOWN
            rep.canary = None
            return
        verdict = self._eval_replica(rep, now)
        if verdict == DOWN:
            if rep.state != DOWN:
                self.metrics.count("router_failovers")
                why = (rep.last_health or {}).get("state", "no heartbeat")
                print_rank_0(
                    f"router: replica {rep.idx} ejected ({why}); "
                    "traffic fails over to survivors")
                rep.state = DOWN
                rep.down_until = now + self.probe_backoff_s
                rep.canary = None
        elif rep.state == DOWN and now >= rep.down_until:
            # healthy snapshot again: half-open — admit ONE canary
            rep.state = PROBING
            rep.canary = None
            print_rank_0(f"router: replica {rep.idx} half-open "
                         "(awaiting canary)")
        elif rep.state == PROBING and rep.canary is not None \
                and now - rep.canary_t > self.canary_timeout_s:
            # abandoned canary (its caller stopped pumping): free the
            # slot so the next request probes afresh instead of the
            # replica idling in PROBING forever
            rep.canary = None
            print_rank_0(f"router: replica {rep.idx} canary abandoned "
                         f"(> {self.canary_timeout_s:.0f}s); re-probing")

    def _refresh_locked(self):
        now = time.monotonic()
        for rep in self.replicas:
            self._refresh_one(rep, now)

    def _note_attempt(self, rep: Optional[_Replica], rreq,
                      ok: Optional[bool]):
        """Canary bookkeeping: the probing replica's single canary
        promotes it (success) or re-ejects it (failure); None is
        inconclusive (cancel/deadline) — the canary slot frees and the
        next pick sends a fresh canary."""
        if rep is None:
            return
        with self._lock:
            if rep.canary is not rreq:
                return
            rep.canary = None
            if rep.state != PROBING or ok is None:
                return
            if ok:
                rep.state = UP
                print_rank_0(f"router: replica {rep.idx} canary "
                             "succeeded; back in full rotation")
            else:
                rep.state = DOWN
                rep.down_until = time.monotonic() + self.probe_backoff_s
                print_rank_0(f"router: replica {rep.idx} canary failed; "
                             "ejected again")

    # ------------------------------------------------------------------
    # routing
    # ------------------------------------------------------------------
    def _load(self, rep: _Replica) -> float:
        """Least-loaded tie-break: work queued ahead x observed service
        time (the PR 6 admission signals, read from the snapshot)."""
        h = rep.last_health or {}
        waiting = (h.get("queue_depth", 0) + h.get("active_slots", 0)
                   + h.get("prefilling", 0))
        return float(waiting) * max(
            float(h.get("service_time_ewma_ms", 0.0)), 1.0)

    def _pick_locked(self, tokens: Sequence[int], exclude=(),
                     adapter_id=None):
        """(replica, is_canary): longest `prefix_peek` match among UP
        replicas, then ADAPTER LOCALITY (a replica already holding the
        request's adapter on device — 2 — beats one a host-restore or
        disk reload away — 1; serving/adapters.py), ties by
        least-loaded. Prefix affinity outranks adapter locality
        because a prefix hit saves forward FLOPs every time while a
        cold adapter load is paid once and then resident. A PROBING
        replica with no canary in flight takes ONE request first —
        that request IS the canary."""
        self._refresh_locked()
        for rep in self.replicas:
            if rep.idx in exclude:
                continue
            if rep.state == PROBING and rep.canary is None:
                return rep, True
        best, best_key = None, None
        for rep in self.replicas:
            if rep.idx in exclude or rep.state != UP:
                continue
            pfx = rep.engine.prefix_peek(tokens, adapter_id)
            apeek = (rep.engine.adapter_peek(adapter_id)
                     if adapter_id is not None else 0)
            key = (-pfx, -apeek, self._load(rep), rep.idx)
            if best_key is None or key < best_key:
                best, best_key = rep, key
        if best is None:
            # no UP replica and every PROBING one has a canary in
            # flight (e.g. a whole-fleet blip just recovered): route
            # to a probing replica anyway — it is healthy-by-snapshot
            # and serving its canary; 503 is reserved for replicas
            # that are actually DOWN
            for rep in self.replicas:
                if rep.idx not in exclude and rep.state == PROBING:
                    return rep, False
        return best, False

    def _dispatch(self, rreq: RouterRequest, exclude=()):
        """Route one attempt. Tries candidates in pick order; a
        submit-time rejection by one replica (queue full / breaker)
        moves on to the next. Raises the last per-replica error when
        every candidate rejected, NoReplicaAvailableError when no
        candidate exists at all (every replica down)."""
        spec = rreq.spec
        tried = set()
        relaxed = False
        last_err: Optional[Exception] = None
        while True:
            with self._lock:
                rep, is_canary = self._pick_locked(
                    spec["prompt"], exclude=tried | set(exclude),
                    adapter_id=spec.get("adapter_id"))
                if rep is None and exclude and not relaxed:
                    # the excluded (just-failed) replica may be the only
                    # one left standing — re-admit it rather than 503
                    relaxed = True
                    rep, is_canary = self._pick_locked(
                        spec["prompt"], exclude=tried,
                        adapter_id=spec.get("adapter_id"))
                if rep is None:
                    break
                if is_canary:
                    rep.canary = rreq
                    rep.canary_t = time.monotonic()
            tried.add(rep.idx)
            try:
                inner = rep.engine.submit(
                    spec["prompt"], spec["max_new_tokens"],
                    spec["sampling"], seed=spec["seed"],
                    priority=spec["priority"],
                    deadline_s=spec["deadline_s"],
                    arrival_id=rreq.arrival_id,
                    adapter_id=spec.get("adapter_id"),
                    response_format=spec.get("response_format"))
            except AdmissionError:
                with self._lock:
                    if rep.canary is rreq:
                        rep.canary = None
                raise  # 400: no replica can serve an inadmissible request
            except Exception as e:  # noqa: BLE001 — per-replica reject
                last_err = e
                with self._lock:
                    if rep.canary is rreq:
                        rep.canary = None
                    if isinstance(e, EngineUnhealthyError):
                        # breaker open: hard-eject without waiting for
                        # the next health refresh
                        self._refresh_one(rep, time.monotonic())
                continue
            with self._lock:
                rreq.inner = inner
                rreq.replica = rep
                if rreq.arrival_id is None:
                    rreq.arrival_id = inner.id
            return
        if last_err is not None:
            raise last_err
        raise NoReplicaAvailableError(
            f"all {len(self.replicas)} replicas are down "
            "(ejected by health checks); retry later")

    # ------------------------------------------------------------------
    # public API (ServingEngine-shaped)
    # ------------------------------------------------------------------
    def submit(self, prompt: Sequence[int], max_new_tokens: int = 64,
               sampling: SamplingOptions = SamplingOptions(),
               seed: int = 0, priority: int = 0,
               deadline_s: Optional[float] = None,
               arrival_id: Optional[int] = None,
               adapter_id=None, response_format=None, n: int = 1,
               best_of: Optional[int] = None) -> RouterRequest:
        # structured output rides the spec dict straight through to the
        # replica engine (each attempt recompiles the FSM at admission,
        # so a failover resubmission replays the identical constrained
        # stream). Fan-out does NOT: the retry pump is a facade over
        # ONE GenRequest, and a FanoutRequest aggregate has no
        # state/error_kind surface for it — typed refusal, not a wedge
        # (docs/serving.md capability matrix).
        if (best_of or n or 1) > 1:
            raise AdmissionError(
                "parallel sampling (n/best_of > 1) is not supported "
                "behind the EngineRouter; submit to a replica engine "
                "directly or fan out client-side with n=1 requests")
        rreq = RouterRequest(self, dict(
            prompt=list(prompt), max_new_tokens=int(max_new_tokens),
            sampling=sampling, seed=int(seed), priority=int(priority),
            deadline_s=deadline_s, adapter_id=adapter_id,
            response_format=response_format))
        if arrival_id is not None:
            # an upstream front tier resubmitting across the process
            # boundary pins the ORIGINAL arrival position here, so the
            # first attempt's EDF tie-break matches the original run
            rreq.arrival_id = int(arrival_id)
        # (requests_received is counted by the replica each attempt
        # lands on — the aggregate snapshot sums those; counting here
        # too would double it)
        self._dispatch(rreq)
        return rreq

    def generate(self, prompt: Sequence[int], max_new_tokens: int = 64,
                 sampling: SamplingOptions = SamplingOptions(),
                 seed: int = 0, timeout: Optional[float] = None):
        return self.submit(prompt, max_new_tokens, sampling,
                           seed).result(timeout)

    def cancel(self, rreq: RouterRequest):
        rreq.cancel()

    @property
    def engines(self) -> List:
        """The replica engines, in index order — the invariant
        checker's (serving/invariants.py) walk surface: a router sweep
        is each replica's engine sweep plus the router-level healthz /
        aggregate-schema laws."""
        return [rep.engine for rep in self.replicas]

    def queue_depth(self) -> int:
        n = 0
        for rep in self.replicas:
            try:
                n += rep.engine.queue_depth()
            except Exception:  # noqa: BLE001 — a dead replica queues 0
                pass
        return n

    def prefix_peek(self, tokens: Sequence[int], adapter_id=None) -> int:
        return max(rep.engine.prefix_peek(tokens, adapter_id)
                   for rep in self.replicas)

    def adapter_peek(self, adapter_id) -> int:
        return max(rep.engine.adapter_peek(adapter_id)
                   for rep in self.replicas)

    def register_adapter(self, adapter_id, path: Optional[str] = None,
                         factors=None, rank: Optional[int] = None,
                         alpha: float = 1.0):
        """Register on EVERY replica: failover must be able to resume
        an adapter request on any survivor (each bank loads lazily —
        registration is host-side bookkeeping + eager validation)."""
        for rep in self.replicas:
            rep.engine.register_adapter(adapter_id, path=path,
                                        factors=factors, rank=rank,
                                        alpha=alpha)

    # ------------------------------------------------------------------
    # rolling fleet upgrade (docs/serving.md "Live weights & rolling
    # upgrade"; serving/weights.py)
    # ------------------------------------------------------------------
    def rolling_upgrade(self, ckpt_dir: str,
                        swap_timeout_s: Optional[float] = None,
                        canary_timeout_s: float = 60.0):
        """Zero-downtime fleet upgrade to `ckpt_dir`, one replica at a
        time through drain → swap → canary → re-admit, reusing the
        UP→DOWN→PROBING machinery:

        - DRAIN: the replica is held DOWN (`upgrading`) — new traffic
          routes to survivors, and its queued/in-flight work fails over
          through the PR 10 retry path, resubmitted token-exact to
          replicas still serving the OLD version (same prompt/seed →
          identical stream). Work already decoding may simply finish on
          the draining replica instead — either way every completion is
          token-exact at its admitted version, and nothing 503s while
          at least one survivor stands.
        - SWAP: `engine.swap_weights` — manifest gate, host staging,
          recompile-free flip between iterations. A refusal (corrupt
          checkpoint, device error) leaves the replica ON ITS OLD
          WEIGHTS; it re-enters rotation via the normal half-open
          canary and the rollout ABORTS with the fleet still serving
          (`RollingUpgradeError`).
        - CANARY: the router itself drives one probe request through
          the upgraded replica — it must COMPLETE under the new weights
          (and the replica must still report accepting) before
          re-admission, so an idle fleet still upgrades and a broken
          swap never takes live traffic.
        - RE-ADMIT: promotion back to UP; the walk moves to the next
          replica only after the canary passes, so at most ONE replica
          is ever out of rotation.

        Returns the new `WeightVersion`. Counts `rolling_upgrades` on a
        completed rollout; a staging refusal counts
        `weight_swap_failures` once on the router, per-replica apply
        failures on the replica that refused."""
        from megatron_tpu.serving.weights import (WeightSwapError,
                                                  load_staged)
        # stage ONCE, before anything drains: every replica serves the
        # SAME model, so one host buffer feeds the whole rollout — a
        # corrupt publish is refused here with zero availability cost
        # (no replica left rotation), and an N-replica fleet pays one
        # disk read + deep verification instead of N
        example = None
        for rep in self.replicas:
            try:
                example = rep.engine.gen.params
                break
            except Exception:  # noqa: BLE001 — a dead or REMOTE replica
                continue
        if example is None:
            # all-remote fleet (serving/remote.py): no replica exposes
            # local params to stage against, and host buffers cannot
            # cross the process boundary anyway — pass staged=None so
            # each replica stages itself from ckpt_dir (shared
            # storage) inside its own swap_weights; the walk below
            # keeps the drain→swap→canary choreography and its abort
            # semantics unchanged, the fleet just pays one disk read
            # per process instead of one total
            staged = None
        else:
            try:
                staged = load_staged(ckpt_dir, example)
            except WeightSwapError as e:
                self.metrics.count("weight_swap_failures")
                raise RollingUpgradeError(
                    f"rolling upgrade refused before any replica "
                    f"drained: {e} — the fleet keeps serving") from e
        version = None
        for rep in self.replicas:
            # a replica that is ALREADY hard-down (breaker open, loop
            # dead) has nothing serving to drain and nothing to swap
            # onto — skipping it lets the healthy rest of the fleet
            # take the new weights instead of one dead replica
            # blocking every rollout; it re-stages when it comes back
            # (a restarted/replaced replica boots host-first from the
            # current publish)
            try:
                h = rep.engine.health()
            except Exception:  # noqa: BLE001 — unreachable == down
                h = None
            if h is None or h.get("circuit_breaker_open") \
                    or not h.get("loop_alive", False):
                print_rank_0(
                    f"router: rolling upgrade — skipping replica "
                    f"{rep.idx} (already down: "
                    f"{(h or {}).get('detail', 'unreachable')}); it "
                    "re-stages from the current publish when it "
                    "returns")
                continue
            with self._lock:
                rep.upgrading = True
                rep.state = DOWN
                rep.canary = None
            print_rank_0(f"router: rolling upgrade — replica {rep.idx} "
                         "draining (traffic fails over to survivors)")
            try:
                version = rep.engine.swap_weights(
                    ckpt_dir, timeout=swap_timeout_s, staged=staged)
            except Exception as e:
                # the failed swap left the replica on its OLD weights
                # (the manifest gate / placement failure flipped
                # nothing): re-admit via the normal half-open canary,
                # abort the rollout, fleet keeps serving
                with self._lock:
                    rep.upgrading = False
                    rep.state = DOWN
                    rep.down_until = time.monotonic()
                raise RollingUpgradeError(
                    f"rolling upgrade aborted at replica {rep.idx}: "
                    f"{e} — the fleet keeps serving (already-upgraded "
                    "replicas stay on the new version; this and later "
                    "replicas stay on the old one)") from e
            ok = self._canary_probe(rep, timeout=canary_timeout_s)
            with self._lock:
                rep.upgrading = False
                if ok:
                    rep.state = UP
                    rep.last_healthy_t = time.monotonic()
                else:
                    rep.state = DOWN
                    rep.down_until = (time.monotonic()
                                      + self.probe_backoff_s)
            if not ok:
                raise RollingUpgradeError(
                    f"rolling upgrade aborted: replica {rep.idx} "
                    f"failed its post-swap canary under "
                    f"{version.label}; it stays ejected (half-open "
                    "re-admission applies) and the fleet keeps serving")
            print_rank_0(f"router: replica {rep.idx} upgraded to "
                         f"{version.label} and re-admitted (canary "
                         "passed)")
        if version is None:
            # every replica was skipped as already-down: nothing
            # swapped, so this is not a completed rollout
            raise RollingUpgradeError(
                "rolling upgrade applied to no replica (every replica "
                "is already down); the fleet has nothing serving to "
                "upgrade")
        self.metrics.count("rolling_upgrades")
        return version

    def _canary_probe(self, rep: _Replica, timeout: float = 60.0) -> bool:
        """One router-driven canary on a just-swapped replica: a tiny
        greedy request submitted DIRECTLY to the engine (bypassing
        rotation — the replica is still held out) must complete under
        the new weights, and the replica must still report accepting."""
        try:
            req = rep.engine.submit(
                [1], 1, SamplingOptions(temperature=0.0), seed=0,
                deadline_s=max(timeout, 1.0))
            req.result(timeout=timeout)
            return bool(rep.engine.health().get("accepting"))
        except Exception:  # noqa: BLE001 — any failure fails the canary
            return False

    def health(self) -> dict:
        """Router-level `/healthz` payload: `state` distinguishes
        DEGRADED (some replicas down, still serving — stays ready/200)
        from DOWN (no replica left — 503). Per-replica summaries ride
        along for operators."""
        with self._lock:
            self._refresh_locked()
            states = [rep.state for rep in self.replicas]
            up = sum(1 for s in states if s != DOWN)
            # the fleet-health gauge a front-tier scrape leads with —
            # pushed here (every probe refreshes replica states) so a
            # /metrics-only scraper sees it move without ever
            # touching /healthz
            self.metrics.set_fleet_gauge(up)
            if up == len(states):
                state = "running"
            elif up > 0:
                state = "degraded"
            else:
                state = "down"
            reps = []
            for rep in self.replicas:
                h = rep.last_health or {}
                reps.append({
                    "idx": rep.idx, "router_state": rep.state,
                    "state": h.get("state", "unknown"),
                    "healthy": bool(h.get("healthy", False)),
                    "queue_depth": int(h.get("queue_depth", 0)),
                    "active_slots": int(h.get("active_slots", 0)),
                    "service_time_ewma_ms":
                        float(h.get("service_time_ewma_ms", 0.0)),
                    # brownout visibility: which replicas are shedding
                    # service (the aggregate /metrics reports the max;
                    # here operators see WHICH replica it is)
                    "degrade_level": int(h.get("degrade_level", 0)),
                    # mixed-version visibility mid-rollout
                    "weight_version": h.get("weight_version",
                                            "unversioned"),
                    # the per-phase placement plan each replica
                    # currently runs (None on topology-free engines) —
                    # a fleet mid-replan shows differing splits here
                    "placement": h.get("placement"),
                    "upgrading": rep.upgrading,
                })
        return {
            "healthy": up > 0,
            "accepting": up > 0,
            "state": state,
            "loop_alive": any(r.get("healthy") or r["router_state"] != DOWN
                              for r in reps),
            "replicas_up": up,
            "num_replicas": len(self.replicas),
            "queue_depth": self.queue_depth(),
            "replicas": reps,
            "detail": "" if up else "all replicas down",
        }

    def aggregate_snapshot(self) -> dict:
        """Router `/metrics`: base counters and occupancy gauges summed
        across replicas, router-level counters (failovers/retries/
        stream_reconnects) overlaid from the router's own registry,
        latency/rate keys reported as the worst replica (max)."""
        out = self.metrics.snapshot()
        versions = []
        for rep in self.replicas:
            try:
                snap = rep.engine.metrics.snapshot()
            except Exception:  # noqa: BLE001
                continue
            for k in _BASE_COUNTERS + _SUM_GAUGES:
                out[k] = out.get(k, 0.0) + snap.get(k, 0.0)
            for k, v in snap.items():
                if k.endswith("_ms") or k in (("tokens_per_s",
                                               "slot_occupancy")
                                              + _MAX_GAUGES):
                    out[k] = max(out.get(k, 0.0), v)
            versions.append(float(snap.get("weight_version", 0.0)))
        # live-weight serving: the version gauge aggregates as
        # per-replica MIN/MAX — a mid-rollout fleet shows min < max on
        # one scrape (docs/serving.md "Live weights & rolling upgrade");
        # the plain key reports the fleet FLOOR (what every replica is
        # guaranteed to serve at least)
        out["weight_version_min"] = min(versions) if versions else 0.0
        out["weight_version_max"] = max(versions) if versions else 0.0
        out["weight_version"] = out["weight_version_min"]
        out["num_replicas"] = float(len(self.replicas))
        # overlay the CURRENT rotation state rather than whatever the
        # last health() push recorded — an aggregate scrape must never
        # report a stale fleet gauge next to fresh replica counters
        out["fleet_replicas_up"] = float(
            sum(1 for rep in self.replicas if rep.state != DOWN))
        return out

    def drain(self, timeout: Optional[float] = None) -> bool:
        ok = True
        for rep in self.replicas:
            ok = rep.engine.drain(timeout) and ok
        return ok

    def close(self):
        for rep in self.replicas:
            rep.engine.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
