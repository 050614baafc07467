"""REST text-generation server.

TPU-native port of the reference's Flask server
(ref: megatron/text_generation_server.py:17-241 + tools/
run_text_generation_server.py:60-84): same `/api` PUT contract —
{"prompts": [...], "tokens_to_generate": N, "temperature": ..,
 "top_k": .., "top_p": .., "logprobs": bool, "beam_width": int|absent} ->
{"text": [...], "segments"/"logprobs": ...}.

Beyond the reference, `/api` routes through the continuous-batching
engine (megatron_tpu/serving): each prompt becomes an independent
request that joins the persistent decode batch at token granularity, so
a long request no longer blocks every other caller. The reference's
serial one-lock path is kept behind `ServingConfig(serial_fallback=
True)` (and always serves beam search, which stays whole-batch). Proper
HTTP statuses on BOTH transport backends: 400 for invalid payloads
(shared validator), 429 when the bounded admission queue overflows, the
engine sheds on overload, or the engine is draining for shutdown, 503
for queued work dropped by a drain and for an unhealthy engine (the
supervisor's crash-loop circuit breaker tripped), 504 when a request
outlives its effective deadline, 500 for internal errors. 429/503
responses carry a `Retry-After` header and the current queue depth in
the JSON body, so clients and load balancers can back off instead of
hammering a saturated replica. `GET /metrics` exposes the
ServingMetrics snapshot; `GET /healthz` is the separate liveness/
readiness probe (engine loop alive, circuit-breaker state, slot
occupancy, queue depth) — host-state reads only, so a wedged decode
cannot wedge the probe. Payloads may carry `priority` (higher wins
admission ordering and, with ServingConfig.preemption, may preempt
running slots) and `deadline_s` (per-request SLO overriding
request_deadline_s). SIGTERM drains gracefully: stop admitting, finish
in-flight slots, then exit.

Front door (docs/serving.md "Front door"): `ServingConfig(
num_replicas=N)` puts N full engine replicas behind the in-process
prefix-affinity router (serving/router.py) — health-driven failover,
token-exact retry on survivors, degraded-vs-down /healthz. Payloads
with `stream: true` (single prompt) switch the response to SSE
(`text/event-stream`) on BOTH transports: one `token` event per
committed token with `id:` = its monotonic index, a terminal `done` or
typed `error` event, and reconnect-resume via `stream_id` +
`Last-Event-ID` (the engine holds committed tokens per request, so
resume replays the tail — no duplicated or missing tokens).

The reference needs a rank-0 Flask thread that broadcasts a GENERATE/BEAM
signal to all other ranks sitting in a receive loop
(ref: text_generation_server.py:22-31); single-controller JAX needs none of
that — one process serves and drives all chips, over the standard
library's http.server.
"""
from __future__ import annotations

import itertools
import json
import math
import secrets
import threading
from typing import Optional, Tuple

from megatron_tpu.inference.api import (beam_search_and_post_process,
                                        generate_and_post_process)
from megatron_tpu.inference.generation import Generator
from megatron_tpu.utils import tracing
from megatron_tpu.utils.logging import print_rank_0

MAX_PROMPTS = 128


class _StreamEntry:
    """Registry row for one SSE stream: the live request handle (its
    `generated` list IS the resume buffer) plus the TTL bookkeeping."""

    __slots__ = ("sid", "req", "created", "done_t")

    def __init__(self, sid: str, req):
        import time as _time
        self.sid = sid
        self.req = req
        self.created = _time.monotonic()
        self.done_t = None  # set when first observed done; TTL runs


def _is_stream_body(body) -> bool:
    import types as _types
    return isinstance(body, _types.GeneratorType)


def validate_generate_payload(payload) -> Optional[str]:
    """Request validator: returns an error message (→ HTTP 400) or
    None. Mirrors the reference's checks
    (ref: text_generation_server.py:31-228), which it answered with
    200 + {"message": ...}."""
    if not isinstance(payload, dict):
        return "request body must be a JSON object"
    has_text = "prompts" in payload
    has_tokens = "prompt_tokens" in payload
    if has_text and has_tokens:
        return "prompts and prompt_tokens are mutually exclusive"
    if not has_text and not has_tokens:
        return "prompts argument required"
    if has_tokens:
        # replica-mode wire format (serving/remote.py): the front tier
        # already tokenized, so rows of token ids skip this replica's
        # tokenizer — the stream stays token-exact across the process
        # hop and across a failover resubmission
        rows = payload["prompt_tokens"]
        if not isinstance(rows, list) or not rows:
            return "prompt_tokens must be a non-empty list"
        if len(rows) > MAX_PROMPTS:
            return f"Maximum number of prompts is {MAX_PROMPTS}"
        for r in rows:
            if not isinstance(r, list) or not r or not all(
                    isinstance(t, int) and not isinstance(t, bool)
                    for t in r):
                return ("prompt_tokens rows must be non-empty lists "
                        "of integer token ids")
        n_prompts = len(rows)
    else:
        prompts = payload["prompts"]
        if not isinstance(prompts, list) or not prompts:
            return "prompts must be a non-empty list"
        if len(prompts) > MAX_PROMPTS:
            return f"Maximum number of prompts is {MAX_PROMPTS}"
        if not all(isinstance(p, str) and p for p in prompts):
            return "prompts must be non-empty strings"
        n_prompts = len(prompts)
    try:
        n = int(payload.get("tokens_to_generate", 64))
    except (TypeError, ValueError):
        return "tokens_to_generate must be an integer"
    if n < 0:
        return "tokens_to_generate must be >= 0"
    # sampling + SLO knobs must coerce cleanly — a list/dict/None here
    # would otherwise surface as a 500 from deep inside the handler
    for field, conv in (("temperature", float), ("top_k", int),
                        ("top_p", float), ("length_penalty", float),
                        ("beam_width", int), ("random_seed", int),
                        ("priority", int), ("deadline_s", float),
                        ("arrival_id", int)):
        v = payload.get(field)
        if v is None:
            continue
        try:
            conv(v)
        except (TypeError, ValueError):
            return f"{field} must be a number"
    if payload.get("deadline_s") is not None:
        # json.loads happily parses NaN/Infinity; a NaN deadline would
        # make every expiry comparison False (an unreapable request)
        # AND poison the scheduler's sort key, scrambling EDF order
        # for OTHER requests — reject at the boundary
        import math as _math
        d = float(payload["deadline_s"])
        if not _math.isfinite(d) or d <= 0.0:
            return "deadline_s must be a finite number > 0"
    if payload.get("beam_width") and n_prompts > 1:
        # (ref: beam-search rejects multi-prompt requests)
        return "With beam_search only one prompt is allowed"
    if has_tokens and payload.get("beam_width"):
        # beam search runs the serial path, which needs text prompts
        return "prompt_tokens requires the serving-engine path; beam " \
               "search is text-prompt only"
    aid = payload.get("adapter_id")
    if aid is not None and not isinstance(aid, (str, int)):
        # multi-tenant LoRA serving: the id is an opaque registry key
        # (unknown ids 400 at submit via UnknownAdapterError)
        return "adapter_id must be a string or integer"
    if aid is not None and payload.get("beam_width"):
        return "beam search runs the serial path; adapters require " \
               "the serving engine"
    rf = payload.get("response_format")
    if rf is not None:
        # structured output (docs/serving.md "Structured output &
        # n-best"): shape-validate HERE so a malformed grammar 400s
        # identically on both transports; whether the pattern COMPILES
        # is the engine's admission check (also a 400)
        from megatron_tpu.serving.structured import \
            validate_response_format
        msg = validate_response_format(rf)
        if msg is not None:
            return f"response_format: {msg}"
    for field in ("n", "best_of"):
        v = payload.get(field)
        if v is None:
            continue
        # bool is an int subclass; `"n": true` must not mean 1
        if isinstance(v, bool) or not isinstance(v, int):
            return f"{field} must be an integer"
        if v < 1:
            return f"{field} must be >= 1"
    n_samples = payload.get("n")
    best_of = payload.get("best_of")
    if n_samples is not None and best_of is not None \
            and n_samples > best_of:
        return f"n ({n_samples}) must be <= best_of ({best_of})"
    if (best_of or n_samples or 1) > 1 and payload.get("beam_width"):
        return "beam search does not compose with n/best_of parallel " \
               "sampling"
    return None


class MegatronServer:
    """(ref: text_generation_server.py:229-241 MegatronServer)"""

    def __init__(self, generator: Generator, tokenizer, serving=None,
                 request_timeout: float = 600.0, weight_version=None):
        from megatron_tpu.config import ServingConfig
        self.generator = generator
        self.tokenizer = tokenizer
        # a fleet front tier (serving.fleet) holds NO weights — the
        # replica processes do — so generator may be None there; every
        # route that forwards locally (serial, beam) guards on it
        self.serving = (serving if serving is not None
                        else ServingConfig()).validate(
            generator.cfg if generator is not None else None)
        self._lock = threading.Lock()  # serial paths: one at a time (ref: :37)
        self._request_counter = itertools.count()
        self._timeout = request_timeout
        # SSE stream registry: stream_id -> live request handle, so a
        # dropped connection resumes via Last-Event-ID (the engine
        # already holds every committed token on the request — resume
        # is a replay of the tail, not recomputation)
        self._streams: dict = {}
        self._streams_lock = threading.Lock()
        self._trace_lock = threading.Lock()  # one profiler session at a time
        self.engine = None
        if self.serving.fleet:
            # fleet front tier (docs/serving.md "Front door"): the SAME
            # EngineRouter, but each replica is a RemoteReplica client
            # over a standalone --replica_mode server process — health
            # polling, typed transport faults, token-exact failover,
            # and rolling upgrades all run over TCP. The shared
            # ServingMetrics registry is BOTH the router's overlay
            # registry and the transport-fault counter sink, so one
            # /metrics scrape shows fleet counters next to the summed
            # per-replica ones.
            from megatron_tpu.serving import EngineRouter
            from megatron_tpu.serving.metrics import ServingMetrics
            from megatron_tpu.serving.remote import RemoteReplica
            counters = ServingMetrics()
            replicas = [
                RemoteReplica(
                    addr.strip(), counters=counters,
                    connect_timeout_s=self.serving
                    .remote_connect_timeout_s,
                    read_timeout_s=self.serving.remote_read_timeout_s,
                    max_retries=self.serving.remote_max_retries,
                    digest_interval_s=self.serving
                    .remote_digest_interval_s)
                for addr in self.serving.fleet.split(",")
                if addr.strip()]
            self.engine = EngineRouter(
                replicas, metrics=counters,
                max_retries=self.serving.router_max_retries,
                heartbeat_timeout_s=self.serving
                .router_heartbeat_timeout_s)
        elif not self.serving.serial_fallback:
            from megatron_tpu.serving import ServingEngine
            from megatron_tpu.serving.topology import devices_per_engine
            # serving-mesh topology (docs/serving.md "Sharded &
            # disaggregated serving" / "Per-phase topology &
            # placement"): each replica occupies its own window of the
            # device list — decode_tp chips for the decode group plus
            # prefill_tp more for the prefill group when disaggregated
            # (each phase its own width; both default to serving_tp),
            # or exactly placement_budget chips when the placement
            # optimizer holds the split — so an EngineRouter replica is
            # a (prefill-group, decode-group) PAIR and killing either
            # half fails over like any replica death. per == 1 passes
            # devices=None (the topology-free engine, bit-identical).
            per = devices_per_engine(self.serving)
            slices = [None] * self.serving.num_replicas
            if per > 1:
                import jax
                devs = jax.devices()
                need = per * self.serving.num_replicas
                assert len(devs) >= need, (
                    f"serving topology needs {need} devices "
                    f"({self.serving.num_replicas} replicas x {per}) "
                    f"but the backend has {len(devs)}")
                slices = [devs[i * per:(i + 1) * per]
                          for i in range(self.serving.num_replicas)]
            if self.serving.num_replicas > 1:
                # N full engine replicas (own KV pool / queue /
                # supervisor each, same weights) behind the in-process
                # prefix-affinity router. num_replicas=1 builds NO
                # router at all — the bare engine, bit-identical to
                # the single-replica server (test-pinned).
                from megatron_tpu.serving import EngineRouter
                engines = [ServingEngine(generator, self.serving,
                                         devices=sl,
                                         weight_version=weight_version)
                           for sl in slices]
                self.engine = EngineRouter(
                    engines,
                    max_retries=self.serving.router_max_retries,
                    heartbeat_timeout_s=
                    self.serving.router_heartbeat_timeout_s)
            else:
                self.engine = ServingEngine(generator, self.serving,
                                            devices=slices[0],
                                            weight_version=weight_version)
        # live-weight serving (docs/serving.md "Live weights & rolling
        # upgrade"): watch the training tracker and drive the engine /
        # fleet to every newly published checkpoint — the
        # zero-operator-action half of the training->serving loop
        self._watcher = None
        if self.engine is not None and \
                getattr(self.serving, "watch_checkpoints", None):
            from megatron_tpu.serving.weights import CheckpointWatcher
            initial_tag = None
            if weight_version is not None:
                # staged at boot from this very root: the CURRENT
                # tracker tag (whatever its spelling — "release"
                # included) is what the fleet already serves; seeding
                # with it stops the first poll from redundantly
                # re-swapping the boot checkpoint through a full
                # drain->swap->canary walk
                try:
                    import os as _os

                    from megatron_tpu.serving.weights import \
                        manifest_digest
                    from megatron_tpu.training.checkpointing import \
                        read_tracker
                    tag = read_tracker(self.serving.watch_checkpoints)
                    # only when the tracker still names what we STAGED
                    # — a publish that landed between staging and here
                    # must NOT be skipped. Iteration tags compare by
                    # number; a "release" tag compares by manifest
                    # digest (the iteration alone can't distinguish
                    # "we staged the release dir" from "release
                    # published after we staged iter_N").
                    if tag == str(weight_version.iteration):
                        initial_tag = tag
                    elif tag == "release" and manifest_digest(
                            _os.path.join(
                                self.serving.watch_checkpoints,
                                "release")) == weight_version.digest:
                        initial_tag = tag
                except Exception:  # noqa: BLE001 — racing a publish
                    initial_tag = str(weight_version.iteration)
            self._watcher = CheckpointWatcher(
                self.engine, self.serving.watch_checkpoints,
                interval_s=self.serving.watch_interval_s,
                initial_tag=initial_tag).start()

    def close(self):
        if self._watcher is not None:
            self._watcher.close()
        if self.engine is not None:
            self.engine.close()

    def drain(self, timeout: Optional[float] = 120.0) -> bool:
        """Graceful shutdown: stop admitting, finish in-flight slots,
        then stop the engine. Serial mode has no queue to drain — the
        one-lock path finishes its current batch when the process
        exits."""
        if self.engine is None:
            return True
        drained = self.engine.drain(timeout)
        if not drained:
            self.engine.close()  # stragglers fail hard rather than hang
        return drained

    def install_sigterm_drain(self, shutdown_cb=None) -> bool:
        """SIGTERM -> drain + stop serving (the k8s/rolling-restart
        contract: the pod gets its grace period to finish in-flight
        work). `shutdown_cb` stops the HTTP front end once the drain
        completes. Returns False outside the main thread (signal
        handlers can only install there — tests drive `drain()`
        directly)."""
        import signal as _signal

        def _on_sigterm(signum, frame):
            print_rank_0("SIGTERM: draining serving engine "
                         "(no new admissions; finishing in-flight)")
            t = threading.Thread(target=self._drain_and_shutdown,
                                 args=(shutdown_cb,), daemon=True,
                                 name="sigterm-drain")
            t.start()

        try:
            _signal.signal(_signal.SIGTERM, _on_sigterm)
            return True
        except ValueError:  # not the main thread
            return False

    def _drain_and_shutdown(self, shutdown_cb):
        self.drain()
        if shutdown_cb is not None:
            shutdown_cb()

    def _seed_for(self, payload) -> int:
        """Explicit random_seed stays deterministic; unseeded requests
        mix real entropy with a per-process counter so traffic differs
        run-to-run AND request-to-request (the old counter-only fallback
        restarted at 0 every process start, making 'unseeded' traffic
        identical across restarts)."""
        if payload.get("random_seed") is not None:
            return int(payload["random_seed"])
        return (secrets.randbits(31)
                ^ (next(self._request_counter) & 0x7FFFFFFF))

    def handle(self, payload: dict,
               headers: Optional[dict] = None) -> Tuple[int, object]:
        """(ref: text_generation_server.py:31-228 MegatronGenerate.put).
        Returns (http_status, body) — body is a JSON-able dict, or a
        GENERATOR of SSE-formatted strings when the payload asked for
        `stream: true` (both transports detect that and switch to
        `text/event-stream`). `headers` carries the request headers
        (Last-Event-ID for stream resume)."""
        from megatron_tpu.serving import (AdmissionError,
                                          DeadlineExceededError,
                                          EngineUnhealthyError,
                                          GrammarDeadEndError,
                                          QueueFullError,
                                          ServiceUnavailableError)
        try:
            if isinstance(payload, dict) \
                    and payload.get("prompt_tokens") is not None \
                    and not self.serving.replica_mode:
                # the pre-tokenized wire format is the FRONT TIER's
                # protocol to a replica process; a public server keeps
                # speaking text prompts (its tokenizer is the contract)
                return 400, {"message":
                             "prompt_tokens is the replica-mode wire "
                             "format (run the server with "
                             "--replica_mode); send text prompts"}
            if isinstance(payload, dict) and payload.get("cancel"):
                # remote cancel (serving/remote.py RemoteReplica
                # .cancel): best-effort eviction of a stream the front
                # tier abandoned — frees the slot instead of decoding
                # tokens nobody will read
                return self._handle_cancel(payload)
            if isinstance(payload, dict) and payload.get("stream"):
                # streaming validates inside (a RESUME payload carries
                # only stream_id — no prompts to validate)
                return self._handle_stream(payload, headers or {})
            err = validate_generate_payload(payload)
            if err is not None:
                return 400, {"message": err}
            if payload.get("beam_width"):
                if self.generator is None:
                    return 400, {"message":
                                 "beam search forwards locally; a "
                                 "fleet front tier holds no weights"}
                err = self._stale_fallback_error("beam search")
                if err is not None:
                    return 409, {"message": err}
                return 200, self._handle_beam(payload)
            if payload.get("serial") and self.generator is None:
                return 400, {"message":
                             "the serial route forwards locally; a "
                             "fleet front tier holds no weights"}
            if payload.get("prompt_tokens") is not None \
                    and (self.engine is None or payload.get("serial")):
                return 400, {"message":
                             "prompt_tokens requires the serving-"
                             "engine path (drop 'serial': true / "
                             "serial_fallback)"}
            if self.engine is not None and not payload.get("serial"):
                return 200, self._handle_engine(payload)
            if self.engine is not None:
                err = self._stale_fallback_error("the serial route")
                if err is not None:
                    return 409, {"message": err}
            if payload.get("adapter_id") is not None:
                # the serial path threads no adapter bank — silently
                # decoding the BASE model would be wrong output, not a
                # degraded mode
                return 400, {"message":
                             "adapter_id requires the serving-engine "
                             "path (drop 'serial': true / "
                             "serial_fallback)"}
            if payload.get("response_format") is not None or \
                    (payload.get("best_of") or payload.get("n") or 1) > 1:
                # same reasoning: the serial path has no FSM masking
                # and no slot grid to fan out on — unconstrained /
                # single-sample output would be wrong, not degraded
                return 400, {"message":
                             "response_format and n/best_of require "
                             "the serving-engine path (drop 'serial': "
                             "true / serial_fallback)"}
            return 200, self._handle_serial(payload)
        except EngineUnhealthyError as e:
            # crash-loop circuit breaker open: this replica cannot
            # serve — 503 so the client/LB retries against another one
            return 503, self._backoff_body(str(e), retry_after=30)
        except QueueFullError as e:
            # bounded-queue overflow, early load shedding
            # (OverloadShedError subclasses this), or a draining
            # engine — all retryable, all carry the backoff hint
            return 429, self._backoff_body(
                str(e), retry_after=getattr(e, "retry_after", None),
                queue_depth=getattr(e, "queue_depth", None))
        except DeadlineExceededError as e:
            # per-request deadline expiry (payload deadline_s /
            # ServingConfig.request_deadline_s): the engine evicted the
            # request — gateway-timeout semantics, retryable
            return 504, {"message": str(e)}
        except ServiceUnavailableError as e:
            # queued work dropped by a graceful drain: retry elsewhere
            return 503, self._backoff_body(str(e), retry_after=5)
        except AdmissionError as e:
            # only explicit admission failures are client errors; a bare
            # ValueError from inside the model stack stays a 500 (it is
            # a server fault, not a fixable request)
            return 400, {"message": str(e)}
        except GrammarDeadEndError as e:
            # constrained generation reached a state with NO legal
            # token: the request was well-formed (not a 400) and the
            # server is healthy (not a 500) — the generation itself is
            # unprocessable, which is exactly what 422 means. Not
            # retryable as-is: the same grammar + budget + seed dead-
            # ends again; the client should loosen one of them.
            return 422, {"message": str(e)}
        except Exception as e:  # noqa: BLE001 — 500 with message, both paths
            return 500, {"message": str(e)}

    def _stale_fallback_error(self, what: str) -> Optional[str]:
        """The serial/beam fallback routes forward through the
        Generator's ORIGINAL params, which a live-weight hot swap
        deliberately never touches (sibling replicas share one
        Generator). Once any engine replica has swapped, those routes
        would silently serve the OLD weights under a fleet reporting
        the new version — a correctness lie, so they answer 409 typed
        instead. Serial-only servers (engine=None) never swap and are
        unaffected."""
        if self.engine is None:
            return None
        try:
            snap = (self.engine.aggregate_snapshot()
                    if hasattr(self.engine, "aggregate_snapshot")
                    else self.engine.metrics.snapshot())
            swapped = snap.get("weight_swaps", 0) > 0
        except Exception:  # noqa: BLE001 — can't tell: let it through
            swapped = False
        if not swapped:
            return None
        return (f"{what} is unavailable after a live-weight hot swap: "
                "it forwards through the server's original startup "
                "weights, not the engine's current version — restart "
                "the server on the new checkpoint to use it")

    def _backoff_body(self, message: str,
                      retry_after: Optional[int] = None,
                      queue_depth: Optional[int] = None) -> dict:
        """JSON body for 429/503: the message plus the machine-readable
        backoff hint (`retry_after`, seconds — also emitted as the
        Retry-After header by both transports) and the current queue
        depth, so clients can back off proportionally to the backlog
        instead of hammering a saturated replica."""
        if queue_depth is None:
            queue_depth = (self.engine.queue_depth()
                           if self.engine is not None else 0)
        # ceil-clamp to >= 1s: a remote replica's hint arrives as a
        # FLOAT, and int(0.5) == 0 would emit Retry-After: 0 — telling
        # every shed client to retry immediately, a synchronized herd
        # at the worst possible moment (and response_headers would
        # drop the falsy header entirely). Sub-second estimates round
        # UP; absent hints default to 1.
        hint = (1 if retry_after is None
                else max(1, int(math.ceil(float(retry_after)))))
        return {"message": message,
                "retry_after": hint,
                "queue_depth": int(queue_depth)}

    @staticmethod
    def response_headers(body: dict) -> dict:
        """Extra HTTP headers for a response body (shared by both
        transports): a `retry_after` hint in the body becomes the
        standard Retry-After header."""
        if isinstance(body, dict) and body.get("retry_after"):
            return {"Retry-After": str(int(body["retry_after"]))}
        return {}

    def healthz(self) -> Tuple[int, dict]:
        """Liveness/readiness for `/healthz` — separate from `/metrics`
        (a scrape-schema document) so probes get a stable, tiny,
        host-state-only answer: 200 only while the engine ACCEPTS new
        work; 503 once the crash-loop circuit breaker is open, the
        loop is wedged/dead, or a drain started (a draining replica
        rejects every new request — the probe must pull it out of
        rotation, that is the whole point of a readiness signal).
        Serial mode has no engine loop to probe."""
        if self.engine is None:
            return 200, {"healthy": True, "serving": "serial"}
        self._gc_streams()  # probes double as the registry's sweeper
        h = self.engine.health()
        # `accepting` is the readiness verdict both the engine and the
        # router compute (a DEGRADED router — some replicas down, at
        # least one serving — stays ready: pulling the whole front
        # door would turn a partial failure into a total one)
        ok = bool(h.get("accepting",
                        h.get("healthy") and h.get("state") == "running"
                        and h.get("loop_alive")))
        return (200 if ok else 503), h

    def _handle_beam(self, payload: dict) -> dict:
        prompts = payload["prompts"]
        # same length admission as the other routes: positions past the
        # RoPE table would silently clamp, not error
        prompt_ids = self._preflight_lengths(
            payload, self.generator.cfg.max_position_embeddings,
            "max_position_embeddings")
        with self._lock:
            texts, scores = beam_search_and_post_process(
                self.generator, self.tokenizer, prompts[0],
                tokens_to_generate=int(payload.get("tokens_to_generate",
                                                   64)),
                beam_size=int(payload["beam_width"]),
                length_penalty=float(payload.get("length_penalty", 1.0)),
                add_BOS=bool(payload.get("add_BOS", False)),
                prompt_ids=prompt_ids[0])
            return {"text": texts, "score": scores}

    def _preflight_lengths(self, payload: dict, max_total: int,
                           what: str):
        """Tokenize-and-check before generating, so oversize/empty
        prompts 400 as AdmissionError on EVERY route (a bare ValueError
        escaping the model stack stays a 500 — it is a server fault).
        Returns the token ids (BOS applied) so no route tokenizes
        twice."""
        from megatron_tpu.serving import AdmissionError
        n = int(payload.get("tokens_to_generate", 64))
        if payload.get("prompt_tokens") is not None:
            # replica-mode wire format: rows are ALREADY token ids (the
            # front tier tokenized; add_BOS was applied there too) —
            # only the length admission runs here, so an oversize row
            # still 400s identically to a text prompt
            prompt_ids = []
            for i, row in enumerate(payload["prompt_tokens"]):
                ids = [int(t) for t in row]
                if len(ids) + n > max_total:
                    raise AdmissionError(
                        f"prompt {i} ({len(ids)} tokens) + tokens_to_"
                        f"generate ({n}) exceeds {what}={max_total}")
                prompt_ids.append(ids)
            return prompt_ids
        add_bos = bool(payload.get("add_BOS", False))
        prompt_ids = []
        for i, p in enumerate(payload["prompts"]):
            ids = self.tokenizer.tokenize(p)
            if add_bos and self.tokenizer.bos is not None:
                ids = [self.tokenizer.bos] + ids
            if not ids:
                raise AdmissionError(
                    f"prompt {i} tokenized to zero tokens")
            if len(ids) + n > max_total:
                raise AdmissionError(
                    f"prompt {i} ({len(ids)} tokens) + tokens_to_"
                    f"generate ({n}) exceeds {what}={max_total}")
            prompt_ids.append(ids)
        return prompt_ids

    def _handle_serial(self, payload: dict) -> dict:
        """The reference's whole-batch path: one generation at a time."""
        prompt_ids = self._preflight_lengths(
            payload, self.generator.cfg.max_position_embeddings,
            "max_position_embeddings")
        with self._lock:
            texts, tokens, logprobs = generate_and_post_process(
                self.generator, self.tokenizer, payload["prompts"],
                tokens_to_generate=int(payload.get("tokens_to_generate",
                                                   64)),
                temperature=float(payload.get("temperature", 1.0)),
                top_k=int(payload.get("top_k", 0)),
                top_p=float(payload.get("top_p", 0.0)),
                add_BOS=bool(payload.get("add_BOS", False)),
                return_output_log_probs=bool(payload.get("logprobs",
                                                         False)),
                seed=self._seed_for(payload),
                prompt_ids=prompt_ids)
        out = {"text": texts, "segments": tokens}
        if logprobs is not None:
            out["logprobs"] = logprobs
        return out

    def _handle_engine(self, payload: dict) -> dict:
        """Continuous-batching path: each prompt is an independent
        request interleaved with all other traffic. Prompt i of a
        multi-prompt payload uses seed+i (a single seeded prompt
        reproduces the serial path token-for-token; multi-prompt
        payloads sample independently per row instead of sharing the
        serial path's one batch-wide key).

        With `n`/`best_of` each prompt fans out into best_of
        independently seeded samples (seed+i, seed+i+1, ... would
        collide across prompts, so prompt i's fan-out seeds from
        seed + i*best_of) and the response's text/segments/logprobs
        entries for that prompt become LISTS of the n best
        completions. `response_format` rides through to the engine's
        grammar-constrained decoding (docs/serving.md)."""
        from megatron_tpu.serving import (OverloadShedError,
                                          QueueFullError, SamplingOptions)
        n = int(payload.get("tokens_to_generate", 64))
        sampling = SamplingOptions(
            temperature=float(payload.get("temperature", 1.0)),
            top_k=int(payload.get("top_k", 0)),
            top_p=float(payload.get("top_p", 0.0)))
        want_lp = bool(payload.get("logprobs", False))
        seed = self._seed_for(payload)
        rf = payload.get("response_format")
        n_samples = int(payload.get("n", 1) or 1)
        best_of = int(payload.get("best_of", n_samples) or n_samples)
        fanout = best_of > 1
        # SLO fields: priority orders admission (and may preempt, with
        # ServingConfig.preemption); deadline_s overrides the engine
        # default for THIS request (validated numeric above)
        priority = int(payload.get("priority", 0) or 0)
        deadline_s = payload.get("deadline_s")
        deadline_s = None if deadline_s is None else float(deadline_s)
        # replica mode: a resubmitted failover request carries its
        # ORIGINAL arrival position across the wire, so it re-enters
        # this replica's EDF queue where its first attempt stood
        # (prompt i offsets by i to keep multi-prompt rows distinct)
        aid0 = payload.get("arrival_id")
        aid0 = None if aid0 is None else int(aid0)
        # tokenize + validate EVERY prompt before submitting ANY, so a
        # bad prompt 400s without leaving earlier rows decoding for a
        # response that will never be read
        prompt_ids = self._preflight_lengths(payload, self.engine.max_len,
                                             "max_len")
        # Submit in waves: a payload with more prompts than the queue
        # bound (the reference's contract allows up to MAX_PROMPTS=128)
        # drains its OWN completed rows to make room instead of failing.
        # 429 fires only when the queue is full of OTHER traffic before
        # this payload served a single row.
        import time as _time
        deadline = _time.monotonic() + self._timeout
        reqs: dict = {}
        results: dict = {}
        pending: list = []
        try:
            for i, ids in enumerate(prompt_ids):
                while True:
                    try:
                        reqs[i] = self.engine.submit(
                            ids, n, sampling,
                            seed=seed + i * best_of,
                            priority=priority, deadline_s=deadline_s,
                            arrival_id=(None if aid0 is None
                                        else aid0 + i),
                            adapter_id=payload.get("adapter_id"),
                            response_format=rf, n=n_samples,
                            best_of=best_of)
                        pending.append(i)
                        break
                    except OverloadShedError:
                        # early shedding says this row can no longer
                        # make its deadline — retrying in the wave
                        # would only burn a worker thread toward a
                        # slow 500; fail the payload FAST with the
                        # retryable 429 the feature exists to produce
                        # (already-submitted siblings are cancelled by
                        # the outer handler)
                        raise
                    except QueueFullError:
                        if pending:
                            # make room by draining our oldest row
                            j = pending.pop(0)
                            results[j] = reqs[j].result(
                                timeout=self._timeout)
                        elif results:
                            # our rows are all done; OTHER traffic holds
                            # the queue — wait for room, bounded. On
                            # deadline this is a timeout (500), NOT a
                            # 429: retrying would redo work already
                            # spent on the served rows
                            if _time.monotonic() > deadline:
                                raise RuntimeError(
                                    "timed out waiting for queue space "
                                    f"after serving {len(results)} of "
                                    f"{len(prompt_ids)} prompts")
                            _time.sleep(0.05)
                        else:
                            raise  # genuine backpressure: nothing served
            for j in pending:
                results[j] = reqs[j].result(timeout=self._timeout)
        except Exception:
            # rejection/timeout dooms the whole payload: cancel every
            # sibling still in flight so the slot grid is not kept busy
            # decoding output nobody will read
            for r in reqs.values():
                self.engine.cancel(r)
            raise
        texts, tokens, logprobs = [], [], []
        for i in range(len(prompt_ids)):
            plen = len(reqs[i].prompt)
            if fanout:
                # FanoutRequest.result(): the n best samples, each a
                # (tokens, logprobs) pair — per-prompt entries become
                # lists of n completions
                toks_list, lps_list = results[i]
                texts.append([self.tokenizer.detokenize(t)
                              for t in toks_list])
                tokens.append(toks_list)
                logprobs.append([[0.0] * plen + lp for lp in lps_list])
                continue
            toks, gen_lps = results[i]
            texts.append(self.tokenizer.detokenize(toks))
            tokens.append(toks)
            # serial-contract shape: one value per OUTPUT token; prompt
            # positions are zero (the serial path fills some in-prompt
            # positions with scoring values — an artifact of its
            # bucketed prefill, not part of the contract)
            logprobs.append([0.0] * plen + gen_lps)
        out = {"text": texts, "segments": tokens}
        if want_lp:
            out["logprobs"] = logprobs
        return out

    # ------------------------------------------------------------------
    # SSE streaming (docs/serving.md "Front door": streaming protocol)
    # ------------------------------------------------------------------
    @staticmethod
    def _sse(data: dict, event: Optional[str] = None,
             event_id: Optional[int] = None) -> str:
        """One SSE frame. Token events carry `id:` = the MONOTONIC
        token index, which is what makes `Last-Event-ID` resume exact:
        the client replays nothing and misses nothing."""
        lines = []
        if event_id is not None:
            lines.append(f"id: {event_id}")
        if event:
            lines.append(f"event: {event}")
        lines.append("data: " + json.dumps(data))
        return "\n".join(lines) + "\n\n"

    def _req_weight_version(self, req) -> str:
        """Weight-version label of the replica serving `req` right now:
        router-backed requests read their CURRENT attempt's replica (a
        failed-over stream reports the survivor's version), bare-engine
        requests read the engine."""
        rep = getattr(req, "replica", None)
        eng = rep.engine if rep is not None else self.engine
        v = getattr(eng, "weight_version", None)
        return v.label if v is not None else "unversioned"

    def _count_metric(self, name: str):
        m = getattr(self.engine, "metrics", None)
        if m is not None:
            m.count(name)

    def _gc_streams(self):
        """Sweep the stream registry. Runs on every stream request AND
        on the /metrics + /healthz scrape paths — a monitored server
        sweeps periodically even when no new stream ever arrives, so
        finished/abandoned entries (each pinning a live request and
        its token lists) cannot outlive their TTL indefinitely."""
        import time as _time
        with self._streams_lock:
            self._gc_streams_locked(_time.monotonic())

    def _gc_streams_locked(self, now: float):
        ttl = float(self.serving.stream_ttl_s)
        for sid in list(self._streams):
            e = self._streams[sid]
            if e.done_t is None and e.req.done():
                e.done_t = now
            if e.done_t is not None and now - e.done_t > ttl:
                del self._streams[sid]
            elif e.done_t is None and now - e.created > ttl + self._timeout:
                # a router-backed request's done() only settles when a
                # caller pumps it — an abandoned stream (client gone,
                # nobody waiting) would otherwise sit here forever.
                # Past the request timeout + resume TTL nobody can
                # legitimately resume it: cancel and drop.
                try:
                    self.engine.cancel(e.req)
                except Exception:  # noqa: BLE001 — GC is best-effort
                    pass
                del self._streams[sid]

    def _handle_stream(self, payload: dict, headers) -> Tuple[int, object]:
        """`stream: true` payloads: fresh streams submit one request
        and return an SSE generator; resume payloads (`stream_id` set)
        re-attach to the live request and replay its committed tail
        from `Last-Event-ID` + 1 — the engine holds every committed
        token on the request, so resume is a replay, not a recompute."""
        import time as _time
        if self.engine is None:
            return 400, {"message": "streaming requires the continuous-"
                                    "batching engine (serial_fallback "
                                    "serves whole completions only)"}
        last = headers.get("Last-Event-ID") if headers else None
        if last is None:
            last = payload.get("last_event_id")
        try:
            last = int(last) if last is not None else -1
        except (TypeError, ValueError):
            return 400, {"message": "Last-Event-ID must be an integer "
                                    "token index"}
        sid = payload.get("stream_id")
        if sid is not None:
            with self._streams_lock:
                self._gc_streams_locked(_time.monotonic())
                entry = self._streams.get(sid)
            if entry is None:
                return 404, {"message": f"unknown or expired stream_id "
                                        f"{sid!r}; start a new stream"}
            self._count_metric("stream_reconnects")
            if getattr(entry.req, "children", None):
                return 200, self._stream_events_fanout(entry,
                                                       start=last + 1,
                                                       resumed=True)
            return 200, self._stream_events(entry, start=last + 1,
                                            resumed=True)
        err = validate_generate_payload(payload)
        if err is not None:
            return 400, {"message": err}
        if payload.get("beam_width"):
            return 400, {"message": "beam search is whole-batch; it "
                                    "does not stream"}
        n_rows = len(payload.get("prompts")
                     or payload.get("prompt_tokens") or ())
        if n_rows != 1:
            return 400, {"message": "streaming supports exactly one "
                                    "prompt per request"}
        n_samples = int(payload.get("n", 1) or 1)
        best_of = int(payload.get("best_of", n_samples) or n_samples)
        if best_of > 1 and n_samples != best_of:
            # n-best selection needs every sample finished before any
            # can be ranked — incompatible with streaming tokens as
            # they commit. Fan-out streams deliver ALL samples.
            return 400, {"message": "streaming requires n == best_of "
                                    "(n-best selection cannot stream; "
                                    "drop best_of or stream all "
                                    "samples)"}
        from megatron_tpu.serving import SamplingOptions
        prompt_ids = self._preflight_lengths(payload, self.engine.max_len,
                                             "max_len")
        sampling = SamplingOptions(
            temperature=float(payload.get("temperature", 1.0)),
            top_k=int(payload.get("top_k", 0)),
            top_p=float(payload.get("top_p", 0.0)))
        deadline_s = payload.get("deadline_s")
        aid = payload.get("arrival_id")
        req = self.engine.submit(
            prompt_ids[0], int(payload.get("tokens_to_generate", 64)),
            sampling, seed=self._seed_for(payload),
            priority=int(payload.get("priority", 0) or 0),
            deadline_s=None if deadline_s is None else float(deadline_s),
            arrival_id=None if aid is None else int(aid),
            adapter_id=payload.get("adapter_id"),
            response_format=payload.get("response_format"),
            n=n_samples, best_of=best_of)
        sid = secrets.token_hex(8)
        entry = _StreamEntry(sid, req)
        with self._streams_lock:
            self._gc_streams_locked(_time.monotonic())
            self._streams[sid] = entry
        if getattr(req, "children", None):
            return 200, self._stream_events_fanout(entry, start=0,
                                                   resumed=False)
        return 200, self._stream_events(entry, start=0, resumed=False)

    def _stream_events(self, entry: "_StreamEntry", start: int,
                       resumed: bool):
        """The SSE event generator: `start` frame (stream_id for later
        resumes), one `token` frame per committed token with `id:` =
        its monotonic index, then exactly one terminal frame — `done`
        with the full text, or `error` with the typed HTTP status a
        non-streaming caller would have seen (a mid-stream replica
        crash lands here as a clean terminal event, never a silent
        hang; a retryable one invites reconnect-or-resubmit)."""
        from megatron_tpu.serving import (DeadlineExceededError,
                                          EngineUnhealthyError,
                                          GrammarDeadEndError,
                                          QueueFullError,
                                          ServiceUnavailableError)
        import time as _time
        req = entry.req
        yield self._sse({"stream_id": entry.sid, "resumed": resumed,
                         "next_index": max(start, 0),
                         # the weight version of the replica actually
                         # serving this stream — every start frame, so
                         # a mixed-version fleet (mid-rolling-upgrade)
                         # is observable per stream, resumes included
                         "weight_version": self._req_weight_version(req)},
                        event="start")
        i = max(start, 0)
        # same overall budget the non-streaming path enforces via
        # result(timeout): a stuck request must end in a terminal
        # frame, not an open connection that never emits again
        stream_deadline = _time.monotonic() + self._timeout
        while True:
            gen = req.generated
            if i < len(gen):
                lps = req.gen_logprobs
                data = {"index": i, "token": int(gen[i]),
                        "text": self.tokenizer.detokenize([int(gen[i])])}
                if i < len(lps):
                    data["logprob"] = float(lps[i])
                yield self._sse(data, event="token", event_id=i)
                i += 1
                continue
            if req.done():
                break
            if _time.monotonic() > stream_deadline:
                # buffered tokens above were all delivered; the
                # request itself is stuck — terminal frame, not an
                # open connection that never emits again
                yield self._sse(
                    {"message": f"stream timed out after "
                                f"{self._timeout:.0f}s waiting for "
                                "tokens", "status": 500,
                     "retryable": True,
                     "committed": len(req.generated)}, event="error")
                return
            # wait_token drives the router's retry pump too, so a
            # failed-over request keeps streaming from a survivor
            req.wait_token(i, timeout=0.25)
        try:
            toks, _ = req.result(timeout=self._timeout)
        except Exception as e:  # noqa: BLE001 — typed terminal frame
            if isinstance(e, DeadlineExceededError):
                status = 504
            elif isinstance(e, (ServiceUnavailableError,
                                EngineUnhealthyError)):
                status = 503
            elif isinstance(e, QueueFullError):
                status = 429
            elif isinstance(e, GrammarDeadEndError):
                status = 422  # constrained generation got stuck —
                # deterministic for this (grammar, prompt, seed), so
                # never retryable
            else:
                status = 500
            yield self._sse({"message": str(e), "status": status,
                             "retryable": status in (429, 503),
                             "committed": len(req.generated)},
                            event="error")
            return
        yield self._sse({"text": self.tokenizer.detokenize(toks),
                         "segments": toks,
                         "generated": len(req.generated)}, event="done")

    def _stream_events_fanout(self, entry: "_StreamEntry", start: int,
                              resumed: bool):
        """SSE generator for n>1 fan-out streams (docs/api.md
        "Parallel sampling"). Frames are SAMPLE-MAJOR: sample 0 streams
        to completion, then sample 1, ... — a single GLOBAL monotonic
        frame id spans all samples, so `Last-Event-ID` resume is as
        exact as the single-sample protocol (walk the children in
        order, skip frames below `start`). Each token frame carries
        `sample` (which child) alongside its per-sample `index`. A
        child's typed failure emits an `error` frame tagged with its
        sample and the stream CONTINUES to the remaining samples; the
        terminal `done` frame reports every completed text."""
        from megatron_tpu.serving import (DeadlineExceededError,
                                          EngineUnhealthyError,
                                          GrammarDeadEndError,
                                          QueueFullError,
                                          ServiceUnavailableError)
        import time as _time
        agg = entry.req
        yield self._sse(
            {"stream_id": entry.sid, "resumed": resumed,
             "next_index": max(start, 0), "n": agg.n,
             "weight_version": self._req_weight_version(agg.children[0])},
            event="start")
        gid = 0  # global frame counter across ALL samples
        start = max(start, 0)
        stream_deadline = _time.monotonic() + self._timeout
        texts, errors = [], []
        for k, req in enumerate(agg.children):
            i = 0
            while True:
                gen = req.generated
                if i < len(gen):
                    if gid >= start:
                        lps = req.gen_logprobs
                        data = {"sample": k, "index": i,
                                "token": int(gen[i]),
                                "text": self.tokenizer.detokenize(
                                    [int(gen[i])])}
                        if i < len(lps):
                            data["logprob"] = float(lps[i])
                        yield self._sse(data, event="token",
                                        event_id=gid)
                    gid += 1
                    i += 1
                    continue
                if req.done():
                    break
                if _time.monotonic() > stream_deadline:
                    yield self._sse(
                        {"message": f"stream timed out after "
                                    f"{self._timeout:.0f}s waiting "
                                    "for tokens", "status": 500,
                         "retryable": True, "sample": k,
                         "committed": len(req.generated)},
                        event="error")
                    return
                req.wait_token(i, timeout=0.25)
            try:
                toks, _ = req.result(timeout=self._timeout)
                texts.append(self.tokenizer.detokenize(toks))
            except Exception as e:  # noqa: BLE001 — typed per-sample frame
                if isinstance(e, DeadlineExceededError):
                    status = 504
                elif isinstance(e, (ServiceUnavailableError,
                                    EngineUnhealthyError)):
                    status = 503
                elif isinstance(e, QueueFullError):
                    status = 429
                elif isinstance(e, GrammarDeadEndError):
                    status = 422
                else:
                    status = 500
                errors.append({"sample": k, "status": status})
                yield self._sse({"message": str(e), "status": status,
                                 "retryable": status in (429, 503),
                                 "sample": k,
                                 "committed": len(req.generated)},
                                event="error")
        yield self._sse({"text": texts, "n": agg.n,
                         "completed": len(texts),
                         "failed": errors}, event="done")

    def metrics_snapshot(self) -> dict:
        if self.engine is None:
            return {"serving": "serial"}
        self._gc_streams()  # scrapes double as the registry's sweeper
        if hasattr(self.engine, "aggregate_snapshot"):
            # router: base counters summed across replicas + the
            # router-level failover/retry/stream counters overlaid
            return self.engine.aggregate_snapshot()
        return self.engine.metrics.snapshot()

    # ------------------------------------------------------------------
    # replica/fleet control plane (serving/remote.py speaks these)
    # ------------------------------------------------------------------
    def _handle_cancel(self, payload: dict) -> Tuple[int, dict]:
        """`{"stream_id": ..., "cancel": true}`: evict a live stream —
        the front tier's best-effort cleanup when a client vanished or
        a request failed over to a survivor, so this replica's slot
        stops decoding tokens nobody will read."""
        import time as _time
        if self.engine is None:
            return 400, {"message": "cancel requires the serving engine"}
        sid = payload.get("stream_id")
        if not isinstance(sid, str) or not sid:
            return 400, {"message": "cancel requires a stream_id"}
        with self._streams_lock:
            self._gc_streams_locked(_time.monotonic())
            entry = self._streams.get(sid)
        if entry is None:
            # idempotent: an already-collected stream is as cancelled
            # as it gets — the front tier's retry must not 4xx-loop
            return 200, {"cancelled": False, "stream_id": sid,
                         "message": "unknown or already-expired stream"}
        self.engine.cancel(entry.req)
        return 200, {"cancelled": True, "stream_id": sid}

    def handle_admin(self, payload: dict) -> Tuple[int, dict]:
        """`PUT /admin` (replica/fleet processes): the control-plane
        ops a remote front tier drives over the wire — swap_weights
        (each replica stages itself from shared storage; a router-
        fronted process runs its own rolling_upgrade), register_adapter
        (path-only: factors cannot cross the process boundary), drain,
        trace (the operator's profiler capture, `_capture_trace`),
        requests (the engines' own record of their newest requests,
        `_request_rows`).
        Refusals stay typed: 409 for a rejected swap (the process
        keeps serving its old weights) or a second trace, 400 for bad
        requests."""
        if self.engine is None:
            return 400, {"message": "admin ops require the serving "
                                    "engine (serial_fallback has no "
                                    "control plane)"}
        if not isinstance(payload, dict):
            return 400, {"message": "request body must be a JSON object"}
        op = payload.get("op")
        if op == "swap_weights":
            ckpt = payload.get("ckpt_dir")
            if not ckpt:
                return 400, {"message": "swap_weights requires ckpt_dir"}
            timeout = payload.get("timeout")
            timeout = float(timeout) if timeout is not None else 120.0
            from megatron_tpu.serving.router import RollingUpgradeError
            from megatron_tpu.serving.weights import WeightSwapError
            try:
                if hasattr(self.engine, "rolling_upgrade"):
                    version = self.engine.rolling_upgrade(
                        str(ckpt), swap_timeout_s=timeout)
                else:
                    version = self.engine.swap_weights(str(ckpt),
                                                       timeout=timeout)
            except (WeightSwapError, RollingUpgradeError) as e:
                # refused swap: the old weights still serve — conflict
                # with current state, not a server fault
                return 409, {"message": str(e)}
            return 200, {"label": version.label,
                         "iteration": int(getattr(version, "iteration",
                                                  0) or 0)}
        if op == "register_adapter":
            aid = payload.get("adapter_id")
            if aid is None:
                return 400, {"message": "register_adapter requires "
                                        "adapter_id"}
            from megatron_tpu.serving import AdmissionError
            try:
                rank = payload.get("rank")
                self.engine.register_adapter(
                    aid, path=payload.get("path"),
                    rank=None if rank is None else int(rank),
                    alpha=float(payload.get("alpha", 1.0)))
            except AdmissionError as e:
                return 400, {"message": str(e)}
            return 200, {"registered": aid}
        if op == "drain":
            timeout = payload.get("timeout")
            drained = self.engine.drain(
                float(timeout) if timeout is not None else 120.0)
            return 200, {"drained": bool(drained)}
        if op == "trace":
            return self._capture_trace(payload)
        if op == "requests":
            return self._request_rows(payload)
        return 400, {"message": f"unknown admin op {op!r} (swap_weights"
                                " | register_adapter | drain | trace"
                                " | requests)"}

    REQUESTS_DEFAULT_N = 32

    def _request_rows(self, payload: dict) -> Tuple[int, dict]:
        """`{"op": "requests", "n": N}`: the newest N rows (by
        `t_submit`) of the record this process's engines keep of their
        requests (`utils/tracing.py::request_record`, a row's fields in
        its docstring), each with its four segments worked out in
        seconds, the slowest first token first and a request that has
        none yet ahead of all: why a request was slow, with no profiler
        session. `now` is `time.monotonic()`, the clock of the stamps."""
        import time as _time
        from megatron_tpu.utils.tracing import request_record
        try:
            n = int(payload.get("n", self.REQUESTS_DEFAULT_N))
        except (TypeError, ValueError):
            n = 0
        if n < 1:
            return 400, {"message": "requests n must be a positive "
                                    "integer"}
        newest = sorted(request_record(), key=lambda r: r.t_submit)[-n:]

        def first_token_s(row):
            return (math.inf if row.t_first is None
                    else row.t_first - row.t_submit)
        newest.sort(key=first_token_s, reverse=True)
        return 200, {"now": _time.monotonic(),
                     "requests": [r.as_dict() for r in newest]}

    TRACE_MAX_S = 30.0

    def _capture_trace(self, payload: dict) -> Tuple[int, dict]:
        """`{"op": "trace", "seconds": s, "dir": d}`: one
        `jax.profiler` session of `s` seconds (at most TRACE_MAX_S: the
        trace of a busy engine grows by some 1.5 MB a second) written
        under `d`, Python
        tracer off, while the engine keeps serving. The reply comes when
        the trace is written. The engine's `mtpu/serve/...` spans
        (utils/tracing.py) land in it beside the device's events. The
        profiler is one per process, so a second capture is refused."""
        import time as _time
        import jax
        from megatron_tpu.utils.tracing import start_trace
        out = payload.get("dir")
        if not out or not isinstance(out, str):
            return 400, {"message": "trace requires dir"}
        try:
            seconds = float(payload.get("seconds", 5.0))
        except (TypeError, ValueError):
            seconds = float("nan")
        if not 0.0 < seconds < math.inf:
            return 400, {"message": "trace seconds must be a positive "
                                    "number"}
        seconds = min(seconds, self.TRACE_MAX_S)
        if not self._trace_lock.acquire(blocking=False):
            return 409, {"message": "a trace is already being captured"}
        try:
            try:
                start_trace(out)
            except RuntimeError as e:
                # a session this server did not start (jax allows one)
                return 409, {"message": str(e)}
            try:
                _time.sleep(seconds)
            finally:
                jax.profiler.stop_trace()
        finally:
            self._trace_lock.release()
        return 200, {"dir": out, "seconds": seconds}

    def invariant_report(self, strict: bool = False) -> dict:
        """`GET /invariants`: this process runs its OWN sweep
        (serving/invariants.py) on its live engines — KV accounting
        and in-flight walks need the real objects, which cannot cross
        the wire — and serves the verdict. The fleet's `check_all`
        folds each replica's report into the fleet-wide sweep. Default
        strict=False: a live replica is rarely quiesced; the caller
        opts into the strict accounting sweep once traffic stops."""
        from megatron_tpu.serving.invariants import (
            _Sweep, _check_remote_engine, check_engine,
            check_router_health, check_schema)
        if self.engine is None:
            return {"engines": 0, "laws_checked": [], "violations": [],
                    "ok": True}
        sweep = _Sweep()
        unreachable = []
        engines = getattr(self.engine, "engines", None)
        is_router = engines is not None
        if not is_router:
            engines = [self.engine]
        for e in engines:
            try:
                if hasattr(e, "invariant_report"):
                    # fleet mode: a RemoteReplica client — the replica
                    # process runs its OWN sweep and ships the report;
                    # an unreachable (killed/ejected) replica is
                    # recorded, not convicted — the router-level laws
                    # below must still show degraded-not-down
                    res = _check_remote_engine(e, strict, sweep)
                    if "unreachable" in res:
                        unreachable.append(res["remote"])
                else:
                    check_engine(e, strict=strict, sweep=sweep)
            except Exception as ex:  # noqa: BLE001 — a sweep crash is
                # itself a reportable violation, not a 500
                sweep.violations.append(
                    ("sweep", f"check_engine raised {type(ex).__name__}:"
                              f" {ex}"))
        if is_router:
            try:
                check_router_health(self.engine.health(), sweep=sweep)
                check_schema(self.engine.aggregate_snapshot(),
                             router=True, sweep=sweep)
            except Exception as ex:  # noqa: BLE001
                sweep.violations.append(
                    ("sweep", f"router sweep raised "
                              f"{type(ex).__name__}: {ex}"))
        report = {"engines": len(engines),
                  "laws_checked": list(sweep.checked),
                  "violations": [[law, detail]
                                 for law, detail in sweep.violations],
                  "ok": not sweep.violations}
        if unreachable:
            report["unreachable"] = unreachable
        return report

    def affinity_digest(self) -> dict:
        """`GET /affinity` (replica mode): the compact routing digest a
        remote front tier peeks instead of calling prefix_peek over
        the wire per request — per-namespace cumulative-CRC32 block
        chains plus adapter residency (engine.affinity_digest). A
        router-fronted process merges its replicas' digests (union of
        chains, max residency): affinity is a hint, so over-claiming
        a hit costs a suboptimal pick, never a wrong token."""
        if self.engine is None:
            return {"granularity": 0, "namespaces": {}, "adapters": {}}
        engines = getattr(self.engine, "engines", None)
        if engines is None:
            return self.engine.affinity_digest()
        merged: dict = {"granularity": 0, "namespaces": {},
                        "adapters": {}}
        for e in engines:
            try:
                d = e.affinity_digest()
            except Exception:  # noqa: BLE001 — a dead replica has none
                continue
            merged["granularity"] = merged["granularity"] or \
                int(d.get("granularity", 0))
            for label, chain in d.get("namespaces", {}).items():
                bucket = merged["namespaces"].setdefault(label, set())
                bucket.update(chain)
            for aid, lvl in d.get("adapters", {}).items():
                merged["adapters"][aid] = max(
                    merged["adapters"].get(aid, 0), int(lvl))
        merged["namespaces"] = {label: sorted(v) for label, v
                                in merged["namespaces"].items()}
        return merged

    def run(self, host: str = "0.0.0.0", port: int = 5000):
        self._run_stdlib(host, port)

    def _run_stdlib(self, host, port):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
        server = self

        class Handler(BaseHTTPRequestHandler):
            def _send(self, status: int, body: dict):
                data = json.dumps(body).encode()
                self.send_response(status)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(data)))
                for k, v in server.response_headers(body).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(data)

            def _send_stream(self, status: int, gen):
                """SSE response: no Content-Length, one flushed write
                per event. A dropped client (BrokenPipe) stops the
                WRITER only — the request keeps decoding server-side,
                and a reconnect with Last-Event-ID resumes the tail."""
                self.send_response(status)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-cache")
                self.send_header("Connection", "close")
                self.end_headers()
                try:
                    for chunk in gen:
                        self.wfile.write(chunk.encode())
                        self.wfile.flush()
                except (ConnectionError, OSError):
                    pass  # client gone; stream resumable via registry
                finally:
                    gen.close()

            def do_PUT(self):
                from urllib.parse import urlsplit
                path = urlsplit(self.path).path.rstrip("/")
                if path not in ("/api", "/admin"):
                    self.send_error(404)
                    return
                length = int(self.headers.get("Content-Length", 0))
                try:
                    payload = json.loads(self.rfile.read(length) or b"{}")
                except json.JSONDecodeError as e:
                    self._send(400, {"message": f"invalid JSON: {e}"})
                    return
                try:
                    if path == "/admin":
                        status, body = server.handle_admin(payload)
                    else:
                        status, body = server.handle(payload,
                                                     headers=self.headers)
                except Exception as e:  # pragma: no cover — handle()
                    status, body = 500, {"message": str(e)}
                if _is_stream_body(body):
                    self._send_stream(status, body)
                else:
                    self._send(status, body)

            def do_GET(self):
                from urllib.parse import parse_qs, urlsplit
                parts = urlsplit(self.path)
                path = parts.path.rstrip("/")
                if path == "/metrics":
                    self._send(200, server.metrics_snapshot())
                elif path == "/healthz":
                    status, body = server.healthz()
                    self._send(status, body)
                elif path == "/invariants":
                    qs = parse_qs(parts.query)
                    strict = (qs.get("strict", ["0"])[0]
                              not in ("0", "", "false"))
                    try:
                        self._send(200,
                                   server.invariant_report(strict=strict))
                    except Exception as e:  # noqa: BLE001 — report, not 500
                        self._send(500, {"message": str(e)})
                elif path == "/affinity":
                    try:
                        self._send(200, server.affinity_digest())
                    except Exception as e:  # noqa: BLE001
                        self._send(500, {"message": str(e)})
                else:
                    self.send_error(404)

            def log_message(self, fmt, *a):
                pass

        print_rank_0(f"serving (http.server) on {host}:{port}/api")
        httpd = ThreadingHTTPServer((host, port), Handler)
        tracing.ready()          # bound and listening: start-up is over
        # SIGTERM drains in-flight work, then shutdown() unblocks
        # serve_forever for a clean exit (rolling-restart contract)
        self.install_sigterm_drain(shutdown_cb=httpd.shutdown)
        httpd.serve_forever()
        httpd.server_close()
