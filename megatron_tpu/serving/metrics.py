"""Serving metrics registry: queue depth, TTFT, tokens/s, occupancy.

The reference's server has no observability at all; the training side
here already has writer plumbing (utils/logging.py make_writer — TB /
wandb / null). `ServingMetrics` is the serving-side registry those
writers consume: counters and latency reservoirs updated from the
engine loop and HTTP threads, snapshotted as plain floats.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Deque, Dict, Optional, Tuple

from megatron_tpu.utils.tracing import (RequestRing, RequestRow,
                                        startup_scalars)


def _percentile(sorted_vals, q: float) -> float:
    """Nearest-rank percentile over an already-sorted sequence. Total
    on degenerate input: an empty window (a /metrics scrape before the
    first request) returns 0.0, and q is clamped into [0, 1] so a
    caller typo can never index out of range."""
    vals = list(sorted_vals)
    if not vals:
        return 0.0
    idx = min(len(vals) - 1, max(0, int(q * len(vals))))
    return vals[idx]


# counters a snapshot always carries (as 0.0 before any traffic):
# scrapers and the bench tools key on these without .get() guards, and
# a /metrics scrape of a fresh engine must look like an idle engine,
# not a different schema
_BASE_COUNTERS = (
    # request-conservation law (serving/invariants.py; every terminal
    # transition is counted EXACTLY ONCE through GenRequest's
    # _on_terminal hook, so on a quiesced engine):
    #   requests_received == requests_completed + requests_rejected
    #                        + requests_failed + requests_cancelled
    #                        + requests_expired
    # requests_rejected covers submit-time refusals (queue full, shed,
    # 400s — requests_shed is its early-shedding SUBSET); requests_
    # failed covers post-admission failures (crash/hang/breaker/drain/
    # non-finite/adapter); cancelled and expired are caller
    # cancellations and deadline deaths. A live engine additionally
    # carries its in-flight requests on the left side.
    "requests_received", "requests_admitted", "requests_completed",
    "requests_rejected", "requests_failed",
    "requests_cancelled", "requests_expired",
    "tokens_generated", "decode_steps", "host_syncs",
    "wasted_decode_steps", "sampling_uploads",
    # decode steps dispatched while some live row's knobs asked for a
    # top-k / top-p filter: over decode_steps, the share of steps that
    # still pay the two vocabulary sorts (inference/sampling.py)
    "sample_filter_steps",
    # blocks of the KV pool a decode step's attention read, up to each
    # row's length (ops/block_attention_pallas.py), and the blocks of the
    # regions it would have read whole: read / held is the share of the
    # pool's bytes a step moves. 0 / 0 where every region is read whole
    "kv_blocks_read", "kv_blocks_held",
    # key blocks of a sequence's latent rows that a continuation chunk's
    # absorbed attention read, over its query blocks and MLA layers
    # (models/mla.py::absorbed_key_blocks: up to the last block a query
    # block can see), and the blocks of the whole region over the same:
    # read / held is the share of the region a chunk reads. 0 / 0 without
    # latent rows and where no prompt is chunked
    "latent_chunk_blocks_read", "latent_chunk_blocks_held",
    # first tokens handed to their requests ahead of the decode window
    # that commits them (engine._deliver_first): over the requests
    # admitted less the resumes, the share of first tokens that waited
    # for no decode step. first_token_mismatches = windows that then
    # drew another token than the one handed over (the request fails;
    # stays 0)
    "first_tokens_early", "first_token_mismatches",
    # admissions (placements, resumes included) by `_admit`, and those of
    # them made while a decode window ran (engine._fetch_admitting): their
    # ratio is how often a prompt did not wait for the host to come round.
    # early_admit_declined_prefilling = windows that ended with a prompt
    # queued which the one-program rule held back (a chunk, a prefix hit
    # or a resume was owed the next iteration's prefill program)
    "admits_total", "admits_early", "early_admit_declined_prefilling",
    # compile-ahead (engine._await_program), once a program the loop
    # reached: found compiled by the engine's pool; still under way there
    # (the loop waited, programs_awaited_s seconds in all); compiled by the
    # loop's own call (nobody had handed it over, or the pool's size is 0)
    "programs_compiled_ahead", "programs_awaited", "programs_awaited_s",
    "programs_compiled_inline",
    "prefill_calls", "prefill_prompts",
    # prefix cache / chunked prefill (docs/serving.md):
    # prefix_hit_tokens counts tokens MATCHED at lookup (including
    # hits forfeited to slot pressure); prefill_tokens_saved counts
    # tokens whose forward was actually replaced by a region clone
    "prefix_hits", "prefix_hit_tokens", "prefill_tokens_saved",
    "prefill_chunks", "prefill_forward_tokens",
    # overload & failure (docs/serving.md "Overload & failure
    # behavior"): requests_shed = early load shedding at submit
    # (subset of requests_rejected), preemptions = running slots
    # evicted for a higher-priority arrival, engine_restarts =
    # supervisor loop restarts after a crashed/hung step,
    # nonfinite_logit_fails = per-slot NaN/inf-logits guard firings
    # (the poisoned REQUEST fails, the engine survives)
    "requests_shed", "preemptions", "engine_restarts",
    "nonfinite_logit_fails",
    # speculative decoding (docs/serving.md "Speculative decoding"):
    # spec_rounds = batched draft/verify dispatches, draft_tokens =
    # drafts proposed for active slots, accepted_tokens = drafts the
    # verify forward accepted (accepted/draft is the acceptance-rate
    # A/B seam, like prefill_forward_tokens was for the prefix cache),
    # spec_fallback_steps = iterations a speculative engine fell back
    # to the plain decode step because no running slot proposed a draft
    "spec_rounds", "draft_tokens", "accepted_tokens",
    "spec_fallback_steps",
    # front door (docs/serving.md "Front door"): router_failovers =
    # replicas ejected from rotation (health-driven), router_retries =
    # attempts resubmitted to a survivor after a replica failure,
    # host_tier_hits = prefix restores served from the host-RAM KV
    # tier, host_tier_demotions = retained block lists demoted to host
    # memory on eviction, host_tier_checksum_misses = demoted entries
    # dropped because their checksum no longer verified (a corrupt
    # demotion is a MISS, never wrong tokens), stream_reconnects =
    # SSE streams resumed via Last-Event-ID
    "router_failovers", "router_retries", "host_tier_hits",
    "host_tier_demotions", "host_tier_checksum_misses",
    "stream_reconnects",
    # multi-tenant LoRA serving (serving/adapters.py): adapter_loads =
    # device-bank writes (cold load, host restore, or disk reload),
    # adapter_evictions = LRU demotions of resident adapters under
    # bank pressure, adapter_host_hits = loads served from the
    # checksummed host-RAM overflow instead of disk,
    # adapter_host_checksum_misses = demoted copies dropped because
    # their checksum no longer verified (a corrupt demotion is a
    # reload-from-disk miss, never wrong weights)
    "adapter_loads", "adapter_evictions", "adapter_host_hits",
    "adapter_host_checksum_misses",
    # sharded + disaggregated serving (docs/serving.md "Sharded &
    # disaggregated serving"): handoffs = completed prefill-group ->
    # decode-group block transfers (one per admission on a
    # disaggregated engine; 0 on single-group engines)
    "handoffs",
    # live-weight serving (docs/serving.md "Live weights & rolling
    # upgrade"): weight_swaps = in-place hot swaps applied on a running
    # engine (zero recompiles, token-safe swap point),
    # weight_swap_failures = checkpoints refused at the manifest gate
    # or failed during staging/placement (the engine kept serving the
    # old weights each time), rolling_upgrades = completed fleet
    # rollouts through the router's drain->swap->canary walk
    "weight_swaps", "weight_swap_failures", "rolling_upgrades",
    # structured output + parallel sampling (serving/structured.py,
    # docs/serving.md "Structured output & n-best"):
    # structured_requests = grammar-constrained requests admitted,
    # mask_uploads = per-slot vocab-mask device uploads — incremented
    # ONLY when a slot's FSM state actually changes (a self-loop state
    # re-uses the resident row; the "uploads only on state change"
    # contract is counter-pinned on this), grammar_dead_ends =
    # structured requests failed typed (422) because every candidate
    # token was masked, fanout_requests = n>1 parallel-sampling
    # fan-outs admitted, fanout_samples = total samples those fan-outs
    # expanded into (each sample also counts in requests_received, so
    # the conservation law holds unchanged)
    "structured_requests", "mask_uploads", "grammar_dead_ends",
    "fanout_requests", "fanout_samples",
    # networked front door (serving/remote.py, docs/serving.md "Front
    # door"): router_remote_timeouts = remote calls that hit a
    # connect/read timeout (the replica may be wedged, not dead),
    # router_remote_retries = transport-level retry attempts the
    # RemoteReplica client made (backoff+jitter; distinct from
    # router_retries, which counts whole-request resubmissions to a
    # SURVIVOR), router_probe_failures = health probes (GET /healthz)
    # that failed with a typed transport fault — the signal that walks
    # a replica through UP -> DOWN -> EJECTED
    "router_remote_timeouts", "router_remote_retries",
    "router_probe_failures",
    # per-phase placement (serving/placement.py, docs/serving.md
    # "Per-phase topology & placement"): placement_replans = times the
    # optimizer's plan CHANGED the (prefill_tp, decode_tp) split and
    # was applied — only ever at the rolling-upgrade drain barrier,
    # never mid-serve (a held plan counts nothing)
    "placement_replans",
    # graceful degradation + SLO conformance (serving/degrade.py,
    # docs/serving.md "Overload, degradation & SLO conformance"):
    # degrade_transitions = brownout-ladder level changes (either
    # direction — a storm that rises to level 3 and reverts counts 6),
    # slo_ttft_violations = first tokens that arrived after
    # `slo_ttft_ms`, slo_itl_violations = sync windows in which a
    # slot's next committed token arrived more than `slo_itl_p99_ms`
    # after its previous one (host-visible inter-token gap — what an
    # SSE consumer actually sees), goodput_tokens = generated tokens of
    # COMPLETED requests that met their TTFT SLO (with no SLO
    # configured every completed request's tokens count — goodput then
    # equals completed work, so the gauge is meaningful on any config)
    "degrade_transitions", "slo_ttft_violations", "slo_itl_violations",
    "goodput_tokens",
)

# gauges a snapshot always carries (0.0 before any traffic), by the
# exact attribute name each is stored under — `snapshot()` builds its
# gauge block from THIS tuple, so a gauge added to __init__ but not
# listed here simply never reaches /metrics (loud in tests, not a
# silent schema fork). The router's aggregation test walks this tuple
# to prove every gauge survives a fleet scrape (the PR 13 lesson:
# gauges in neither _SUM_GAUGES nor _MAX_GAUGES silently zero).
_BASE_GAUGES = (
    "queue_depth", "active_slots", "num_slots",
    "kv_blocks_used", "kv_blocks_retained", "kv_bytes_wasted",
    "kv_gather_bytes_per_step", "kv_attn_path",
    "kv_bytes_per_token", "kv_pool_bytes",
    "kv_bytes_per_slot", "kv_ring_bytes", "kv_full_bytes",
    "conv_state_bytes", "ssm_state_bytes", "ssd_state_bytes",
    "kda_state_bytes", "gdn_state_bytes",
    "active_adapters", "handoff_bytes_per_req",
    "prefill_group_busy", "decode_group_busy",
    "prefill_tp", "decode_tp", "prefill_devices", "decode_devices",
    "serving_pp", "pp_waves", "pp_stage_bubble",
    "pp_activation_bytes_per_step",
    "weight_version", "fleet_replicas_up", "degrade_level",
)


class ServingMetrics:
    """Thread-safe registry. All record_* methods are cheap (no device
    sync); `snapshot()` computes derived stats on demand."""

    def __init__(self, max_samples: int = 4096,
                 throughput_window_s: float = 30.0):
        self._lock = threading.Lock()
        self._counters: Dict[str, int] = collections.defaultdict(int)
        # one row a request (utils/tracing.py's table): what the
        # ttft / queue-wait / latency percentiles below are made from,
        # and what `tracing.request_record()` hands out
        self.requests = RequestRing(max_samples)
        # (timestamp, tokens emitted that step) for the tokens/s window
        self._token_events: Deque[Tuple[float, int]] = collections.deque(
            maxlen=max_samples)
        self._window_s = throughput_window_s
        # occupancy accumulators (slot-steps busy / slot-steps total)
        self._busy_slot_steps = 0
        self._total_slot_steps = 0
        # gauges pushed by the engine
        self.queue_depth = 0
        self.active_slots = 0
        self.num_slots = 0
        # KV-pool gauges (docs/serving.md observability): blocks in use
        # / pinned by retained prefixes (whole-region pools report in
        # region units), and reserved-minus-live bytes — the
        # internal-fragmentation gauge the block-granular pool exists
        # to shrink
        self.kv_blocks_used = 0
        self.kv_blocks_retained = 0
        self.kv_bytes_wasted = 0
        # attention-path A/B seam (docs/serving.md "Block-native
        # decode attention"): kv_gather_bytes_per_step = bytes any
        # resolve_view/scatter_view full-pool bracket moved, averaged
        # over the last sync window's decode/verify dispatches —
        # "kernel on => gather bytes == 0 on the decode path" is a
        # CPU-pinnable assertion on this gauge, not an on-chip claim.
        # kv_attn_path encodes which path the engine compiled:
        # 0 = whole-region (no blocks), 1 = block pool through the
        # resolve/scatter bracket, 2 = block-native Pallas kernel.
        self.kv_gather_bytes_per_step = 0
        self.kv_attn_path = 0
        # the pool's own count of what a cached token costs across layers
        # (2 x kv heads x head dim x itemsize a layer, or a latent row) and
        # of the bytes it holds, pushed once when the engine builds it
        self.kv_bytes_per_token = 0
        self.kv_pool_bytes = 0
        # what one slot reserves, and the pool's bytes by kind: the window
        # layers' rings (0 in a pool of one kind) and the whole regions
        self.kv_bytes_per_slot = 0
        self.kv_ring_bytes = 0
        self.kv_full_bytes = 0
        # the convolution layers' state, whatever the slots hold (0 in a
        # pool of keys and values alone)
        self.conv_state_bytes = 0
        # the scans' float32 state, whatever the slots hold (0 where no
        # layer is a "mamba" mixer)
        self.ssm_state_bytes = 0
        # the chunked scans' float32 state, a matrix a head (0 where no
        # layer is a "mamba2" mixer)
        self.ssd_state_bytes = 0
        # the delta rule's float32 state, a matrix a head (0 where no layer
        # is a "kda" mixer)
        self.kda_state_bytes = 0
        # a Gated DeltaNet rule's float32 state, a matrix a value head (0
        # where no layer is a "linear_attention" mixer)
        self.gdn_state_bytes = 0
        # multi-tenant LoRA serving: device-resident (non-identity)
        # adapters right now — 0 on adapterless engines, pushed by the
        # engine on pool churn like the KV gauges
        self.active_adapters = 0
        # sharded + disaggregated serving gauges (always present, 0 on
        # single-group engines): handoff_bytes_per_req = bytes the most
        # recent prefill->decode handoff moved — the "only the
        # sequence's live blocks" pin (ceil(plen/B) * block bytes,
        # never a cap region); prefill_group_busy / decode_group_busy =
        # instantaneous occupancy of each chip group at the last sync
        # window (pending prefills > 0 -> 1.0; active slots /
        # num_slots), the phase-interference A/B seam
        self.handoff_bytes_per_req = 0
        self.prefill_group_busy = 0.0
        self.decode_group_busy = 0.0
        # per-phase topology gauges (always present, 0 on
        # topology-free engines): the tp width and device count of
        # each phase group as CURRENTLY placed — the placement plan's
        # observable footprint. A symmetric engine reports
        # prefill == decode == serving_tp; the router's aggregate sums
        # the device counts fleet-wide and maxes the widths.
        self.prefill_tp = 0.0
        self.decode_tp = 0.0
        self.prefill_devices = 0.0
        self.decode_devices = 0.0
        # pipeline-sharded decode (serving/pp.py, docs/serving.md
        # "Pipeline-sharded serving"): layer-stage count and wave
        # count the staged programs run under (0s on topology-free
        # engines, serving_pp=1 pp_waves=1 on a pure-tp topology),
        # the 1F1B idle fraction (S-1)/(W+S-1), and the bytes the
        # [rows, hidden] residual crosses stage seams per full decode
        # step. Pushed once at build — static facts of the topology.
        self.serving_pp = 0.0
        self.pp_waves = 0.0
        self.pp_stage_bubble = 0.0
        self.pp_activation_bytes_per_step = 0.0
        # live-weight serving: the checkpoint ITERATION currently on
        # the serving mesh (0 = unversioned startup weights). Always
        # present; the router's aggregate carries it as per-replica
        # min/max so a mixed-version fleet mid-rollout is visible on
        # one scrape.
        self.weight_version = 0.0
        # networked front door: replicas currently UP in the router's
        # rotation (0 on a plain engine — the gauge is always present
        # so a fresh fleet scrape never mutates the schema; the
        # router's aggregate overwrites it with the live count)
        self.fleet_replicas_up = 0.0
        # graceful degradation (serving/degrade.py): the brownout
        # ladder's current level — 0 = full service (also the reading
        # on ladder-disabled engines, so the schema never forks). The
        # router aggregates it as MAX: a fleet scrape reports its
        # most-degraded replica.
        self.degrade_level = 0.0

    # ---- recording ---------------------------------------------------
    def count(self, name: str, n: int = 1):
        with self._lock:
            self._counters[name] += n

    def record_admitted(self, row: RequestRow):
        """A request's first admission: its row enters the ring."""
        with self._lock:
            self._counters["requests_admitted"] += 1
        self.requests.keep(row)

    def record_completed(self, gen_tokens: int,
                         good_tokens: Optional[int] = None):
        """`good_tokens` is the SLO-conformant share of `gen_tokens`
        (the goodput ledger); callers without an SLO pass None and
        every completed token counts as goodput."""
        with self._lock:
            self._counters["requests_completed"] += 1
            self._counters["tokens_generated"] += gen_tokens
            self._counters["goodput_tokens"] += (
                gen_tokens if good_tokens is None else good_tokens)

    def set_kv_gauges(self, blocks_used: int, blocks_retained: int,
                      bytes_wasted: int):
        """Engine-pushed KV-pool occupancy/fragmentation gauges (from
        SlotKVPool.kv_gauges, refreshed every step window)."""
        with self._lock:
            self.kv_blocks_used = int(blocks_used)
            self.kv_blocks_retained = int(blocks_retained)
            self.kv_bytes_wasted = int(bytes_wasted)

    def set_adapter_gauge(self, active: int):
        """Engine-pushed count of device-resident LoRA adapters
        (serving/adapters.py AdapterBank.active_count)."""
        with self._lock:
            self.active_adapters = int(active)

    def set_handoff_gauge(self, nbytes: int):
        """Engine-pushed: bytes the just-completed prefill->decode
        block handoff moved (disaggregated engines only)."""
        with self._lock:
            self.handoff_bytes_per_req = int(nbytes)

    def set_group_gauges(self, prefill_busy: float, decode_busy: float):
        """Engine-pushed per sync window: instantaneous prefill/decode
        chip-group occupancy (single-group engines report the same
        numbers — prefill pending vs slot occupancy — so the schema
        never forks on the topology)."""
        with self._lock:
            self.prefill_group_busy = float(prefill_busy)
            self.decode_group_busy = float(decode_busy)

    def set_topology_gauges(self, prefill_tp: int, decode_tp: int,
                            prefill_devices: int, decode_devices: int):
        """Engine-pushed at build and at every applied placement
        re-plan: the per-phase widths and device counts the compiled
        programs currently run under (0s on topology-free engines)."""
        with self._lock:
            self.prefill_tp = float(prefill_tp)
            self.decode_tp = float(decode_tp)
            self.prefill_devices = float(prefill_devices)
            self.decode_devices = float(decode_devices)

    def set_pp_gauges(self, serving_pp: int, pp_waves: int,
                      stage_bubble: float,
                      activation_bytes: int) -> None:
        """Engine-pushed at build: the pipeline-sharded decode layout
        (stage count / wave count), its analytic 1F1B bubble fraction,
        and the per-step residual-crossing traffic (0s at
        serving_pp=1 — no seams, no bubble)."""
        with self._lock:
            self.serving_pp = float(serving_pp)
            self.pp_waves = float(pp_waves)
            self.pp_stage_bubble = float(stage_bubble)
            self.pp_activation_bytes_per_step = float(activation_bytes)

    def set_weight_version(self, iteration) -> None:
        """Engine-pushed at startup staging and every applied hot swap:
        the checkpoint iteration the compiled programs now consume."""
        with self._lock:
            self.weight_version = float(iteration)

    def set_fleet_gauge(self, replicas_up: int) -> None:
        """Router-pushed: replicas currently UP in rotation (the
        fleet-health gauge a front-tier scrape leads with)."""
        with self._lock:
            self.fleet_replicas_up = float(replicas_up)

    def set_degrade_gauge(self, level: int) -> None:
        """Engine-pushed on every brownout-ladder transition (and once
        at build): the current degradation level."""
        with self._lock:
            self.degrade_level = float(level)

    def set_pool_gauges(self, pool):
        """`SlotKVPool.bytes_per_token()`, `.nbytes()`, `.bytes_per_slot()`,
        `.ring_nbytes()`, `.full_nbytes()`, `.conv_state_nbytes()`,
        `.ssm_state_nbytes()`, `.ssd_state_nbytes()`,
        `.kda_state_nbytes()` and `.gdn_state_nbytes()`, as the pool counts
        them."""
        with self._lock:
            self.kv_bytes_per_token = int(pool.bytes_per_token())
            self.kv_pool_bytes = int(pool.nbytes())
            self.kv_bytes_per_slot = int(pool.bytes_per_slot())
            self.kv_ring_bytes = int(pool.ring_nbytes())
            self.kv_full_bytes = int(pool.full_nbytes())
            self.conv_state_bytes = int(pool.conv_state_nbytes())
            self.ssm_state_bytes = int(pool.ssm_state_nbytes())
            self.ssd_state_bytes = int(pool.ssd_state_nbytes())
            self.kda_state_bytes = int(pool.kda_state_nbytes())
            self.gdn_state_bytes = int(pool.gdn_state_nbytes())

    def set_attn_gauges(self, gather_bytes_per_step: int, path: int):
        """Engine-pushed attention-path gauges (per sync window):
        bytes a resolve/scatter bracket moved per decode/verify step
        (0 when the block-native kernel — or a whole-region pool —
        dispatched), and the compiled path code (0 region / 1 block
        view / 2 block-native kernel)."""
        with self._lock:
            self.kv_gather_bytes_per_step = int(gather_bytes_per_step)
            self.kv_attn_path = int(path)

    def record_step(self, active_slots: int, num_slots: int,
                    tokens_emitted: int, queue_depth: int):
        now = time.monotonic()
        with self._lock:
            self._counters["decode_steps"] += 1
            self._busy_slot_steps += active_slots
            self._total_slot_steps += num_slots
            self._token_events.append((now, tokens_emitted))
            self.queue_depth = queue_depth
            self.active_slots = active_slots
            self.num_slots = num_slots

    # ---- derived -----------------------------------------------------
    def tokens_per_s(self) -> float:
        now = time.monotonic()
        with self._lock:
            events = [(t, n) for t, n in self._token_events
                      if now - t <= self._window_s]
        if len(events) < 2:
            return 0.0
        span = max(now - events[0][0], 1e-9)
        return sum(n for _, n in events) / span

    def snapshot(self) -> Dict[str, float]:
        with self._lock:
            counters = dict(self._counters)
            occ = (self._busy_slot_steps / self._total_slot_steps
                   if self._total_slot_steps else 0.0)
            # always present (0.0 before traffic) like the base
            # counters: the /metrics schema never mutates mid-run.
            # Built from _BASE_GAUGES so the gauge schema lives in ONE
            # place — attribute names ARE the scrape keys.
            gauges = {k: float(getattr(self, k)) for k in _BASE_GAUGES}
        # the rows themselves, not copies: a stamp is written once and
        # `outcome` last, so a live row reads as far as it has come
        rows = self.requests.live()
        ttft = sorted(r.t_first - r.t_submit for r in rows
                      if r.t_first is not None)
        qwait = sorted(r.t_admit - r.t_submit for r in rows
                       if r.t_admit is not None)
        lat = sorted(r.t_finish - r.t_submit for r in rows
                     if r.outcome == "completed")
        out = {k: 0.0 for k in _BASE_COUNTERS}
        out.update({k: float(v) for k, v in counters.items()})
        out.update(gauges)
        out.update({
            "ttft_p50_ms": _percentile(ttft, 0.50) * 1e3,
            "ttft_p95_ms": _percentile(ttft, 0.95) * 1e3,
            "queue_wait_p50_ms": _percentile(qwait, 0.50) * 1e3,
            "queue_wait_p95_ms": _percentile(qwait, 0.95) * 1e3,
            "queue_wait_p99_ms": _percentile(qwait, 0.99) * 1e3,
            "latency_p50_ms": _percentile(lat, 0.50) * 1e3,
            "latency_p95_ms": _percentile(lat, 0.95) * 1e3,
            "tokens_per_s": self.tokens_per_s(),
            "slot_occupancy": occ,
        })
        # dispatch-overlap cadence (engine host_syncs / prefill_calls
        # counters): syncs per decode step — 1/decode_sync_interval —
        # and prompts amortized per batched prefill call. Always
        # present (0.0 before traffic) so the /metrics schema never
        # mutates mid-run — scrapers key on a fixed key set.
        steps = counters.get("decode_steps", 0)
        out["host_syncs_per_step"] = (
            counters.get("host_syncs", 0) / steps if steps else 0.0)
        calls = counters.get("prefill_calls", 0)
        out["prompts_per_prefill"] = (
            counters.get("prefill_prompts", 0) / calls if calls else 0.0)
        # the PROCESS's start-up and compile ledger (utils/tracing.py):
        # the same six keys on every engine of the process, from the
        # first scrape. compiles_after_ready is the recompile alarm.
        out.update(startup_scalars())
        return out

    def report(self, writer, step: Optional[int] = None):
        """Push the snapshot through a utils/logging writer (TB / wandb /
        NullWriter)."""
        snap = self.snapshot()
        step = int(step if step is not None
                   else snap.get("decode_steps", 0))
        for k, v in snap.items():
            writer.add_scalar(f"serving/{k}", v, step)
        writer.flush()
        return snap
