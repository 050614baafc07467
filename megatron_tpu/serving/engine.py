"""Continuous-batching serving engine.

The reference (and the serial port in inference/server.py) generates one
whole batch at a time behind a lock: a 128-prompt request's entire
prefill + decode blocks every other caller. This engine implements
Orca-style iteration-level scheduling over a vLLM-style pooled KV cache,
TPU-native:

- ONE persistent jitted decode step over a fixed grid of `num_slots`
  batch slots — static shapes, compiled exactly once, no per-request
  retrace. Per-slot sequence positions ride the vector KV-cache offsets
  (models/attention.py), per-slot sampling knobs ride
  `sample_batched` (inference/sampling.py), per-request seeds ride a
  [slots, 2] PRNG-key grid.
- Each slot owns a region of a pre-allocated KV pool
  (serving/kv_pool.py, built by init_kv_caches — int8 and
  sliding-window ROLLING layouts included). Admission prefills a
  request at batch=1 and inserts its KV into the slot region via
  `lax.dynamic_update_slice`; eviction on EOS/max-tokens frees the slot
  with no copying.
- A bounded FIFO (serving/scheduler.py) provides backpressure; the
  engine loop drains it into free slots between decode steps, so
  new requests join the running batch at token granularity.
- Host/device overlap: `decode_sync_interval=K` chains K decode
  dispatches on device-resident state (lengths ride the device and
  self-increment) and fetches all K sampled tokens in ONE transfer —
  syncs/token = 1/K, at the cost of up to K-1 wasted slot-steps per
  finished request and K-1 extra steps of admission latency (EOS /
  eviction / admission decide at sync boundaries). Sampling knobs and
  lengths keep cached device copies re-uploaded only on slot churn,
  and queued same-length-bucket admissions coalesce into one batched
  prefill call (`prefill_max_batch`).
- The first token leaves with its prefill. A prefill writes the
  prompt's last logits and the request's key into its slot's row; the
  head of the next decode step draws the token. `_step` draws it ahead
  of that step with one small program on the same arrays
  (`_draw_ahead`: it reads and returns, no key advances), queues the
  step behind it, fetches the draw and hands each token to its request
  (`_deliver_first`: `first_token_time`, `wait_token(0)`) while the
  step runs. The step draws the same token from the same key and
  `_commit` checks it, appends nothing twice and runs everything else
  on it. It engages in every window whose first round is a plain
  decode round, by what the engine sees and by no option; a window
  that opens with a speculative verify round, a row whose drawn
  log-probability is not finite or whose grammar is at a dead end, a
  session the watchdog flagged and a preemption resume (it has its
  tokens) are left to the commit whole. Counters:
  `first_tokens_early`, `first_token_mismatches` (stays 0).
- A prompt that lands while a decode window runs is admitted at once.
  The engine thread waits for a window's tokens and for a submission
  alike (`_fetch_admitting`); if a request is ready it runs the same
  `_admit` the iteration would have run, so the prefill is on the
  device's queue behind the window and starts as the window's last
  program ends, not a host round trip later. It is the iteration's ONE
  prefill program: taken only in a plain window (no verify round, no
  grammar row), with a slot free, nothing owed in `_prefilling`, no
  swap pending, the engine neither draining nor flagged, once a
  window, for the requests at the queue's head that make one
  `_prefill_group` call (anything else goes back as it came); and the
  iteration after it skips `_advance_prefill` once, so between two
  windows a running request waits for what one `_admit` groups plus
  one chunk, as before, the early program standing in the chunk's
  place. No option: what the engine sees decides. Counters:
  `admits_early`, `admits_total`, `early_admit_declined_prefilling`.
- Compile-ahead. Nothing compiles in the loop that the engine could have
  known about earlier: an engine on one device owns a small pool of
  compile threads (`COMPILE_THREADS`) and hands a program over the moment
  it knows the program will be called: the decode step and the first
  tokens' draw at construction; a chunked prompt's key, chunk programs
  and landing at `submit()`, once it is queued (`_hand_programs`); a
  group's one prefill where its batch is known, as the loop starts for
  what was queued before it (`_hand_groups`) and for every group of a
  pop before the first is dispatched (`_hand_group`). The functions that
  pick a program in the loop pick it there, and each program's argument
  list is made in one place for both (`_decode_args`, `_prefill_args`,
  `_chunk_args`, `_insert_args`): the pool lowers the jitted program for
  those arguments as shapes and compiles it, which fills JAX's own
  caches, so the loop's call compiles nothing. The loop, reaching a
  program for the first time (`_await_program`), finds it compiled,
  waits for that one future, or compiles it itself as it always did
  (`_verify`, a prefix hit's programs, everything on a serving mesh or
  over a pipeline's stages). A compile that raises in the pool raises in
  the loop, where its own would have. No option: what the engine sees
  decides. Counters: `programs_compiled_ahead`, `programs_awaited`,
  `programs_awaited_s`, `programs_compiled_inline`.
- Prefix-cache KV reuse (`enable_prefix_cache`, SGLang's
  RadixAttention made slot-grid native): finished slots RETAIN their
  KV on an LRU list (serving/kv_pool.py) and a host-side radix index
  (serving/prefix_index.py) matches new prompts against running +
  retained slots at prefill-bucket granularity. A hit slices the
  shared region out of the pool (`slice_slot` — the read half of
  `clone_prefix`) and forwards ONLY the suffix, so the shared tokens
  cost one on-device region copy instead of L forward layers.
- Chunked prefill (`prefill_chunk`, Sarathi-Serve): prompts/suffixes
  longer than the chunk split into pieces the loop interleaves with
  decode steps — one chunk per engine iteration — so a long prompt's
  prefill no longer stalls every in-flight decode for its whole
  duration. The in-progress KV accumulates in a batch-1 cache OUTSIDE
  the pool (`generation.prefill_chunk` appends each chunk at the
  cache's offset) and lands in the slot region with one
  `insert_prefill` when the last chunk completes.

- Speculative decoding on the slot grid (`speculative_k`, Leviathan
  et al. — PAPERS.md): steady-state decode streams all params + the KV
  slice to emit ONE token per slot, so it is HBM-bandwidth-bound. Each
  engine iteration instead proposes k draft tokens per running slot
  (host-side self-drafting n-gram prompt-lookup by default;
  `drafter=` is the pluggable seam) and verifies ALL slots' drafts in
  ONE batched [slots, k+1]-token forward — the multi-token append at
  nonzero offset (`generation.prefill_chunk`) generalized to the grid
  with per-slot vector offsets (`generation.verify_tokens`). Greedy
  rows accept by exact match (token-exact vs non-speculative);
  stochastic rows by standard point-mass rejection sampling, with the
  residual distribution carried as a per-slot banned token into the
  next round's first sample. Per-slot accept counts ride the
  device-resident lengths, so the cache offset simply REWINDS to the
  accepted length and rejected-position KV is overwritten
  write-before-read — the invariant bucketed prefill already relies
  on. k is a compile-time bucket: the decode+verify pair compiles
  exactly once, and the whole thing composes with
  `decode_sync_interval=K` chaining (accept counts and the residual
  carry stay on device between syncs), preemption, and the prefix
  cache (a parked or retained slot carries only committed tokens —
  draft state is host-side and droppable).
- Overload robustness (docs/serving.md "Overload & failure behavior"):
  admission is priority + earliest-deadline-first with optional early
  load shedding (serving/scheduler.py), and a queued higher-priority
  request with no allocatable slot PREEMPTS the lowest-priority
  running slot — the victim's KV parks in a batch-1 sub-cache
  (`slice_slot`, the read half of `clone_prefix`) together with its
  carried logits row and PRNG key, and it resumes later with one
  `insert_prefill`: no re-prefill, token-exact vs never-preempted,
  decode trace untouched (preemption is slot bookkeeping plus two
  region copies through already-compiled programs). If the parked
  buffers are dropped (engine restart, park budget), the victim
  replays its effective prompt through prefill instead — still
  token-exact, the host-side PRNG copy survives.
- Live-weight hot swap (docs/serving.md "Live weights & rolling
  upgrade"): `swap_weights(ckpt_dir)` verifies checkpoint N+1 against
  its SHA-256 manifest, stages it HOST-side (NumPy), holds new
  admissions while in-flight work completes under N, then flips the
  param refs under the compiled programs between two iterations —
  identical shapes/shardings, zero recompiles, KV arena untouched.
  Pre-swap admissions are byte-identical to an engine at N, post-swap
  to a fresh engine at N+1; a corrupt/mid-publish checkpoint is a
  typed refusal that leaves N serving. Prefix/host-tier state is
  swept AND namespaced by a weight generation so N-era KV can never
  serve under N+1.
- Engine supervisor: the loop runs under a supervisor that restarts it
  after a crashed or hung step (resilience/watchdog.py in
  detection-only mode detects the hang and fails the in-flight futures
  so none strand). A restart fails only the slotted requests it must
  (their device state is suspect), requeues queued/prefilling work,
  and resets the pool; after `max_engine_restarts` the crash-loop
  circuit breaker trips — the engine goes unhealthy, `submit` raises
  EngineUnhealthyError (HTTP 503) and `/healthz` reports it. A
  per-slot non-finite-logits guard fails a poisoned REQUEST (NaN/inf
  logits) without taking the engine down.

Seeded determinism: a request with seed s reproduces the serial
`Generator.generate([prompt], ..., seed=s)` output token-for-token —
the engine burns the same number of PRNG splits the serial path spends
on its bucketed in-prompt steps, and `sample_batched` is row-for-row
bit-identical to `sample`.
"""
from __future__ import annotations

import math
import os
import threading
import time
import traceback
from concurrent.futures import (CancelledError, Future,
                                ThreadPoolExecutor)
from concurrent.futures import TimeoutError as FutureTimeout
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from megatron_tpu.inference.generation import (PREFILL_BUCKET, Generator,
                                               prefill_chunk, verify_tokens)
from megatron_tpu.inference.sampling import (rows_need_filter,
                                             sample_batched,
                                             verify_draft_probs)
from megatron_tpu.models import language_model as lm
from megatron_tpu.models import mla
from megatron_tpu.models.attention import KVCache
from megatron_tpu.resilience.faults import get_fault_injector
from megatron_tpu.serving.kv_pool import (SlotKVPool, block_native_cache,
                                          batch_row, insert_blocks,
                                          insert_prefill,
                                          pack_block_native, resolve_view,
                                          scatter_view, slice_blocks,
                                          slice_slot)
from megatron_tpu.serving.degrade import DegradeController
from megatron_tpu.serving.metrics import ServingMetrics
from megatron_tpu.serving.prefix_index import PrefixIndex
from megatron_tpu.serving.request import (FanoutRequest, GenRequest,
                                          RequestState, SamplingOptions)
from megatron_tpu.serving.scheduler import (AdmissionError,
                                            AdmissionScheduler,
                                            EngineUnhealthyError,
                                            OverloadShedError)
from megatron_tpu.serving.spec_decode import (NGramDrafter,
                                              build_draft_rounds)
from megatron_tpu.serving.structured import (GrammarCompileError,
                                             compile_response_format)
from megatron_tpu.utils import compile_cache
from megatron_tpu.utils.logging import print_rank_0
from megatron_tpu.utils.tracing import (keep_requests, note_program, phase,
                                        span)

from megatron_tpu.config import SERVING_KV_DTYPES as _KV_DTYPES


def _burned_key(seed, burn):
    """`PRNGKey(seed)`, then `split(key)[0]` applied `burn` times.
    `burn` is DATA (a masked update under a fixed-length loop), so the
    jitted forms below compile once per batch shape and never per burn
    count; the `jax.random` calls are the serial chain's own, so the
    key is bit-identical to it."""
    return jax.lax.fori_loop(
        0, PREFILL_BUCKET - 1,
        lambda i, key: jnp.where(i < burn, jax.random.split(key)[0], key),
        jax.random.PRNGKey(seed))


# one device call per request / per prefill group; the result stays on
# the device, uncommitted, and feeds _insert / _prefill as it is
_burned_key_jit = jax.jit(_burned_key)
_burned_keys_jit = jax.jit(jax.vmap(_burned_key))


def _draw_ahead(last_logits, rngs, temps, top_ks, top_ps, rejects, masks,
                *, vocab_size):
    """What the head of the next plain decode step will draw for every
    row of the grid, and the chosen token's log-probability under the raw
    logits: `_decode_fn`'s own first lines on the same arrays. It reads
    and returns; the keys advance and the state moves in the decode step
    alone, which draws the same tokens again (`sample_batched` is
    row-for-row bit-identical, so a grid cut into waves draws them too)."""
    step_keys = jax.vmap(jax.random.split)(rngs)[:, 1]
    toks = sample_batched(step_keys, last_logits, temperature=temps,
                          top_k=top_ks, top_p=top_ps, vocab_size=vocab_size,
                          banned=rejects, mask=masks)
    lp = jax.nn.log_softmax(last_logits, axis=-1)
    return toks, jnp.take_along_axis(lp, toks[:, None], axis=-1)[:, 0]


# one shape an engine (the whole grid): compiled with the decode step
_draw_ahead_jit = jax.jit(_draw_ahead, static_argnames=("vocab_size",))

# threads of an engine's compile pool (the module docstring's
# "Compile-ahead"), never more than the host's cores. 0: no pool, every
# program compiles where the loop first calls it. Tracing and lowering
# hold the interpreter's lock and so run one at a time whatever this
# says; what overlaps is the backend (the compile, or the cache's read
# and the executable's load) with the next program's tracing. From one
# sweep of 2, 3 and 4 on the chip (CHANGES.md, PR 56)
COMPILE_THREADS = 3

_COMPILED = Future()        # a program the loop's own call compiled
_COMPILED.set_result(None)


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _shape_of(x):
    """What `lower` reads of an argument the loop will pass: its shape
    and type and, where it is committed, its placement. The buffer is
    not touched: the loop may have donated it by now."""
    if not isinstance(x, jax.Array):
        return x        # a ShapeDtypeStruct, a numpy scalar
    return jax.ShapeDtypeStruct(
        x.shape, x.dtype, weak_type=x.weak_type,
        sharding=x.sharding if x.committed else None)


class EngineHungError(RuntimeError):
    """Raised by the loop when the watchdog flagged a wedged iteration
    that eventually returned — the supervisor treats it as a crash and
    restarts the session."""


class _PendingPrefill:
    """A request mid-prefill: it owns a pool slot (reserved at
    admission) but its KV accumulates in `sub`, a batch-1 cache OUTSIDE
    the pool, so the K-chained decode dispatches — which write garbage
    for every inactive grid row — can never touch it. `pos` is the
    number of prompt tokens whose KV `sub` holds (starts at the cloned
    prefix length on a hit); `last` is the logits row of the most
    recent chunk's final real token (only the LAST chunk's value is
    consumed, as the sampling logits at prompt position plen-1).
    `tokens` is the sequence being prefilled — `req.prompt` for a fresh
    request, `req.effective_prompt()` (prompt + generated so far) for a
    preemption replay.

    Block-granular pools additionally carry the reserved physical
    `blocks` (refs held since admission; the slot's map stays on TRASH
    until activation installs them, so idle grid writes can't touch
    aliased prefix blocks), `pfx_blocks` (the aliased block count —
    the insert's copy-on-write boundary), and `installed` (whether the
    map row was installed, which decides who unrefs the blocks on an
    aborted prefill)."""

    __slots__ = ("req", "slot", "sub", "pos", "rng0", "last", "tokens",
                 "blocks", "pfx_blocks", "installed", "aidx",
                 "on_decode")

    def __init__(self, req: GenRequest, slot: int, sub, pos: int, rng0,
                 tokens: Optional[List[int]] = None,
                 blocks: Optional[List[int]] = None, pfx_blocks: int = 0):
        self.req = req
        self.slot = slot
        self.sub = sub
        self.pos = pos
        self.rng0 = rng0
        self.last = None
        self.tokens = list(tokens) if tokens is not None else req.prompt
        self.blocks = blocks
        self.pfx_blocks = pfx_blocks
        self.installed = False
        # adapter bank row the chunks forward under (0 = identity;
        # resolved + pinned at admission — serving/adapters.py)
        self.aidx = int(req.bank_idx)
        # disaggregated engines: True when `sub` already lives on the
        # DECODE group (a preemption park resumed in place) — its
        # activation inserts directly, no prefill->decode handoff
        self.on_decode = False


class _SwapTicket:
    """One pending weight hot swap: the host-staged tree rides in from
    the calling thread, the engine thread applies it at the swap point
    (between two iterations, in-flight work drained), and the caller
    waits on `done` for the verdict. `taken` flips (under the engine
    cond) the moment the engine commits to applying, so a timing-out
    caller can tell 'still waiting for the barrier — cancellable' from
    'mid-apply — wait for the verdict'."""

    __slots__ = ("staged", "done", "taken", "version", "error")

    def __init__(self, staged):
        self.staged = staged
        self.done = threading.Event()
        self.taken = False
        self.version = None
        self.error: Optional[BaseException] = None


class _HostSrc:
    """Prefix-lookup source living in the host-RAM KV tier (not in a
    slot or retained entry): carries the tier key. `_start_pending`
    restores it into a fresh batch-1 sub via device_put — no block
    aliasing, no pool surgery."""

    __slots__ = ("key",)

    def __init__(self, key):
        self.key = key


class ServingEngine:
    """Drives generation for many concurrent requests through one
    compiled decode step. Construct from a `Generator` (whose params /
    config / mesh treatment / rope tables are reused as-is)."""

    # a restart this long ago no longer counts toward the crash-loop
    # circuit breaker: the breaker exists to catch a LOOP (every
    # restart crashing again within moments), not to accumulate
    # isolated recovered faults over a replica's weeks-long lifetime
    # into permanent 503
    RESTART_DECAY_S = 300.0

    @phase("engine")
    def __init__(self, generator: Generator, serving=None,
                 metrics: Optional[ServingMetrics] = None,
                 writer=None, report_interval: int = 100,
                 start: bool = True, drafter=None, devices=None,
                 weight_version=None, token_strings=None):
        from megatron_tpu.config import ServingConfig
        self.gen = generator
        cfg = generator.cfg
        self.cfg = cfg
        # the one decision of what this model's pool may serve
        # (serving/capabilities.py), before anything is built on it
        self.serving = (serving if serving is not None
                        else ServingConfig()).validate(cfg)
        self.max_len = self.serving.max_len or cfg.max_position_embeddings
        self.num_slots = self.serving.num_slots
        kv_dtype = (generator.kv_cache_dtype
                    if self.serving.kv_dtype is None
                    else _KV_DTYPES[self.serving.kv_dtype])
        # serving mesh (serving/topology.py; docs/serving.md "Sharded
        # & disaggregated serving"): with serving_tp > 1 (or
        # disaggregation) the engine's programs run under the training
        # mesh treatment — weights tp-sharded by the training rules,
        # the KV arena on the kv-head axis, dispatch data replicated —
        # and a disaggregated engine additionally holds a second
        # weight copy on its prefill chip group. topo None (the
        # default) keeps every code path below byte-for-byte what it
        # was: _p_dec/_p_pre ARE generator.params and the jits route
        # through Generator._jit exactly as before.
        from megatron_tpu.serving.topology import (build_topology,
                                                   devices_per_engine,
                                                   resolve_phase_tp)
        # per-replica device window, kept verbatim for the placement
        # re-mesh at the upgrade barrier (None = the topology takes the
        # process default device list)
        self._device_window = (list(devices) if devices is not None
                               else None)
        # signal-driven placement (serving/placement.py): the STATIC
        # plan is chosen here — explicit prefill_tp/decode_tp widths
        # win whenever they fit; an explicit placement_budget with no
        # widths lets the optimizer pick the split. Signals only exist
        # later, and a re-plan is only ever applied at the quiesced
        # swap/upgrade barrier (_apply_swap).
        self._placement_auto = self.serving.placement_auto
        self._placement_plan = None
        if self._placement_auto:
            from megatron_tpu.serving.placement import plan_placement
            budget = devices_per_engine(self.serving)
            explicit = (self.serving.prefill_tp or self.serving.decode_tp
                        or not self.serving.placement_budget)
            self._placement_plan = plan_placement(
                budget, cfg, signals=None,
                current=(resolve_phase_tp(self.serving) if explicit
                         else None))
        self.topo = build_topology(self._planned_serving(),
                                   devices=devices)
        self._disagg = (self.topo is not None
                        and self.topo.disaggregated)
        # pipeline-sharded decode (serving/pp.py; docs/serving.md
        # "Pipeline-sharded serving"): S layer-stage sub-meshes, every
        # compiled program a chain of per-stage segments. 1 = off — the
        # staged machinery below never constructs and every code path
        # is byte-for-byte the pre-pp engine.
        self._pp = (self.topo.serving_pp if self.topo is not None else 1)
        self._pp_waves = (self.topo.pp_waves if self.topo is not None
                          else 1)
        if self.topo is not None:
            assert generator.mesh is None, (
                "serving_tp/disaggregate_prefill build their own "
                "serving mesh — construct the Generator WITHOUT mesh= "
                "(the engine owns placement; a Generator mesh would "
                "fight it)")
            _jit_dec, _jit_pre = self._place_weights(generator.params)
        else:
            src = generator.params
            if any(isinstance(leaf, np.ndarray)
                   for leaf in jax.tree.leaves(src)):
                # HOST-STAGED source weights (serving/weights.py
                # host_params / load_staged — the PR 13 residency fix
                # on topology-free engines too): commit exactly ONE
                # device copy for the compiled programs; the
                # generator's host tree stays the staging buffer and
                # never becomes device-resident.
                src = jax.device_put(src)
            self._p_dec = self._p_pre = src
            _jit_dec = _jit_pre = self.gen._jit
        with phase("engine.pool"):
            self.pool = SlotKVPool(
                cfg, self.num_slots, self.max_len, dtype=kv_dtype,
                retained_limit=self.serving.retained_slots,
                block_size=self.serving.kv_block_size)
        # every program closes over the rotary tables as constants. On a
        # pool of rings and regions, and on one with a convolution state,
        # they are cut to this engine's positions: a published context of
        # 200,000 rows made each serialised program 283 MB where 32,768 are
        # served, more than the chip's compile cache takes (PERF.md section
        # 6, PR 33; 128,000 rows, 33 MB in each of thirteen programs, kept
        # every run of PR 37's cell cold), and so under YaRN, whose
        # published context is the stretched one (Xing4.0's 262,144 rows:
        # 245 MB a program where 16,384 are served, PR 41). Every other
        # pool keeps the generator's, and so the programs it had
        # (tests/test_jaxpr_unchanged.py; ROADMAP S22)
        self._rope = generator.rope
        if (self.pool.hybrid or self.pool.conv_layers
                or cfg.rope_scaling_type == "yarn") \
                and self._rope is not None:
            self._rope = type(self._rope)(
                *(t[:self.max_len] for t in self._rope))
        if self.topo is not None:
            self.topo.place_pool(self.pool)
        # block-granular pool: the static per-slot block map is
        # resolved at dispatch (kv_pool.resolve_view/scatter_view
        # bracket every compiled program), so the one-compile contract
        # survives and outputs are BIT-IDENTICAL to the whole-region
        # pool — only the retention/alias/free accounting changes
        self._blocks_on = self.pool.blocks_enabled
        # block-NATIVE attention (--block_native_attn): the decode /
        # verify / batched-prefill programs consume the arena THROUGH
        # the block map (Pallas kernel + per-row insert_blocks) and
        # the resolve/scatter bracket never runs on the hot path —
        # zero O(pool-bytes) gather traffic per step, token-exact vs
        # the bracketed path (test-pinned). Auto-off without
        # kv_block_size (no arena to index); ROLLING pools keep the
        # bracket (the ring's slot->position map breaks the kernel's
        # position arithmetic), as every sliding-window model does.
        self._kernel_on = self._blocks_on and self.serving.block_native_attn
        # gather/scatter observability (kv_gather_bytes_per_step /
        # kv_attn_path gauges): one resolve or scatter moves a full
        # contiguous view; dispatch sites accumulate into
        # _bracket_bytes (engine thread only) and _step flushes the
        # per-step average each sync window
        self._view_bytes = self.pool.view_nbytes()
        self._bracket_bytes = 0
        self._attn_path = (2 if self._kernel_on
                           else 1 if self._blocks_on else 0)
        # rows of the blocks a decode step's attention reads the pool in,
        # up to each slot's length (`kv_blocks_read` / `kv_blocks_held`);
        # 0 where it reads every slot's region whole
        self._attend_rows = self._attend_block_rows()
        self._prefix_on = bool(self.serving.enable_prefix_cache)
        self._chunk = self.serving.prefill_chunk
        self._preempt_on = bool(self.serving.preemption)
        self._spec_k = self.serving.speculative_k
        self.drafter = drafter if drafter is not None else NGramDrafter()
        # test seam: set to a list to record per-round (window tokens,
        # accept counts) for the serial-replay exactness pin
        self._spec_trace = None
        # block mode indexes at BLOCK granularity (hits must be
        # block-aligned for map aliasing; validate() requires the
        # block size to be a prefill_bucket multiple, so suffix shapes
        # still land in the existing jit buckets)
        self._index = PrefixIndex(self.pool.block_size if self._blocks_on
                                  else max(self.serving.prefill_bucket, 1))
        # a retained slot's (or block-mode retained prefix's) KV is
        # reclaimed lazily (alloc pressure / retain overflow) — forget
        # its prefixes the moment that happens
        self.pool.on_reclaim = self._index.remove
        # host-RAM KV tier (docs/serving.md "Front door"): when block
        # pressure evicts a RetainedPrefix, demote its block list to
        # host memory instead of dropping it; a later prefix hit
        # restores via device_put. 0 bytes = off, bit-identical to the
        # tier-less engine (test-pinned). Rolling rings never demote
        # (a ring restore is only sound at the exact length — not
        # worth a host copy that usually misses).
        self._host_tier = None
        if self.serving.host_kv_bytes:
            from megatron_tpu.serving.host_tier import HostKVTier
            self._host_tier = HostKVTier(self.serving.host_kv_bytes,
                                         self._index.granularity)
            self.pool.on_evict_entry = self._demote_entry
        self._prefilling: List[_PendingPrefill] = []
        self._admitting: List[GenRequest] = []  # mid-_admit pops
        self._sub0 = None  # lazily-built zero template for miss starts
        self.scheduler = AdmissionScheduler(
            self.serving.max_queue, max_total_len=self.max_len,
            num_slots=self.num_slots,
            shed_on_overload=self.serving.shed_on_overload,
            default_deadline_s=self.serving.request_deadline_s)
        self.scheduler.notify = self._wake
        # busy-slot feed for the shed estimate (reads host arrays the
        # engine thread owns — a racy read only skews the estimate)
        self.scheduler.active_fn = (
            lambda: int(self._active.sum()) + len(self._prefilling))
        self.metrics = metrics if metrics is not None else ServingMetrics()
        self.metrics.set_pool_gauges(self.pool)
        # a request's own record (utils/tracing.py's table): the ring is
        # the metrics', the process keeps it past `close()`. Engine
        # thread only: the rows admitted and still without a first token;
        # while a window's fetch is awaited, and only then, the list of
        # the rows admitted meanwhile (`_step` stamps their `t_device` as
        # the fetch returns); the prefill programs [count, padded rows]
        # dispatched and not yet known finished
        self.engine_id = keep_requests(self.metrics.requests)
        self._awaiting: list = []
        self._behind_window: Optional[list] = None
        self._unfetched = [0, 0]
        # graceful degradation (serving/degrade.py): None when the
        # brownout ladder is disabled — the None path is the
        # bit-identical pre-ladder engine (test-pinned). The
        # controller is HOST state like the scheduler queue: it
        # deliberately survives supervisor restarts (_restart_session
        # rebuilds device state only) — a replica that wedged under
        # overload must not come back at level 0 and re-admit the
        # flood that wedged it.
        self.degrade = DegradeController.from_config(self.serving)
        # SLO targets in seconds (observability only — the counters
        # and the goodput ledger, never scheduling)
        self._slo_ttft_s = (self.serving.slo_ttft_ms / 1e3
                            if self.serving.slo_ttft_ms else None)
        self._slo_itl_s = (self.serving.slo_itl_p99_ms / 1e3
                           if self.serving.slo_itl_p99_ms else None)
        self._writer = writer
        self._report_interval = max(report_interval, 1)

        # multi-tenant LoRA serving (serving/adapters.py): a device-
        # resident bank of per-layer A/B factors, indexed per slot by
        # adapter_idx — plain data next to the KV block map, so decode /
        # verify / prefill keep ONE compile each with adapters on, and
        # adapter_slots=0 passes adapters=None (today's graph, bit-
        # identical). The bank's stacked pytree is NOT donated: it
        # survives restarts and in-flight dispatches read the buffer
        # they captured while loads replace it functionally.
        self._adapter_slots = self.serving.adapter_slots
        self._adapters_on = self._adapter_slots > 0
        self.adapters = None
        if self._adapters_on:
            from megatron_tpu.serving.adapters import AdapterBank
            bank_sh = bank_sh_pre = None
            if self.topo is not None:
                # tp-sharded bank rows: B factors by their projection
                # out-dim specs, like the base weights (topology.py);
                # a disaggregated engine keeps a mirror copy on the
                # prefill mesh for the chunk forward
                bank_sh = self.topo.adapter_shardings()
                if self._disagg:
                    bank_sh_pre = self.topo.adapter_shardings(
                        self.topo.prefill_mesh)
            self.adapters = AdapterBank(
                cfg, self._adapter_slots, self.serving.adapter_rank,
                host_bytes=self.serving.adapter_host_bytes,
                metrics=self.metrics, shardings=bank_sh,
                prefill_shardings=bank_sh_pre)

        S, Vp = self.num_slots, cfg.padded_vocab_size
        # per-slot device state (functionally replaced every step)
        self._last_logits = jnp.zeros((S, Vp), jnp.float32)
        self._rngs = jnp.zeros((S, 2), jnp.uint32)
        # per-slot host state (engine thread only)
        self._lengths = np.zeros(S, np.int32)
        self._active = np.zeros(S, bool)
        self._temps = np.ones(S, np.float32)
        self._top_ks = np.zeros(S, np.int32)
        self._top_ps = np.zeros(S, np.float32)
        self._slot_req: List[Optional[GenRequest]] = [None] * S
        # cached DEVICE copies of the per-slot state: sampling knobs and
        # lengths only change on slot churn (admit/evict), so they are
        # re-uploaded only when the dirty flags say so instead of
        # jnp.asarray'ing 4 host arrays every decode step. Between
        # churns the lengths chain device-side through the decode calls.
        self._d_lengths = self._upload_chained(self._lengths)
        self._d_temps = jnp.asarray(self._temps)
        self._d_top_ks = jnp.asarray(self._top_ks)
        self._d_top_ps = jnp.asarray(self._top_ps)
        # does some slot's knobs ask for top-k / top-p? Recomputed with
        # each upload of the three arrays above: it is the predicate of
        # sampling._filter_rows' cond, kept for `sample_filter_steps`
        self._filter_live = False
        # speculative-decode residual carry: per-slot token a stochastic
        # rejection banned from the NEXT first sample (-1 = none); the
        # host mirror is exact at sync boundaries (it rides the window
        # fetch) and re-uploads with the lengths on slot churn
        self._reject = np.full(S, -1, np.int32)
        self._d_reject = self._upload_chained(self._reject)
        # per-slot adapter bank row (0 = identity): changes only on
        # slot churn, re-uploaded with the lengths; idle rows ride the
        # identity adapter so their garbage decode is the base model's
        self._adapter_idx = np.zeros(S, np.int32)
        self._d_adapter_idx = jnp.asarray(self._adapter_idx)
        # grammar-constrained decoding (serving/structured.py): the
        # per-slot [padded_vocab] legal-token bitmask applied at
        # sample_batched's post-filter seam. Free rows are ALL-True
        # (bit-identical to mask=None — one trace serves mixed grids);
        # a structured row carries its FSM state's mask over [:V] with
        # the pad tail False, so a dead-end state yields an all-False
        # row and the sampler's -1 sentinel. `_mask_state` mirrors each
        # row's FSM state on the host (-1 = free row): the device rows
        # re-upload ONLY when some row's state actually changed
        # (`mask_uploads`) — a self-loop transition re-uses the
        # resident copy.
        self._masks = np.ones((S, Vp), np.bool_)
        self._d_masks = jnp.asarray(self._masks)
        self._mask_state = np.full(S, -1, np.int64)
        self._masks_dirty = False
        # tokenizer piece strings the per-request TokenFSMs compose
        # over (None = byte-level identity, structured.py
        # default_token_strings — the harness-scale ASCII models)
        self._token_strings = token_strings
        self._sampling_dirty = True
        self._lengths_dirty = True
        # KV gauges recompute only after pool churn (admit / evict /
        # retain / preempt): the coverage walk is O(blocks) host work
        # that has no place in a churn-free decode window
        self._kv_dirty = True
        self._sync_interval = max(self.serving.decode_sync_interval, 1)
        self._prefill_max_batch = max(
            min(self.serving.prefill_max_batch, self.num_slots), 1)

        # compile-ahead: the pool, the programs handed to it (any thread,
        # under the lock) and those the loop has reached (its own set).
        # No pool on a mesh: there the grid's arrays take the placement
        # of the first program that writes them (a committed array's
        # type names its mesh), so a program lowered for them as they
        # stand now is not the one the loop will call
        threads = min(COMPILE_THREADS, os.cpu_count() or 1)
        if self.topo is not None or generator.mesh is not None:
            threads = 0
        self._compiler = None
        if threads > 0:
            self._compiler = ThreadPoolExecutor(
                threads, thread_name_prefix="serving-compile")
            # every thread starts here (each waits for the last): handing
            # a program over from `submit()` never waits for one to start
            gate = threading.Barrier(threads)
            for _ in range(threads):
                self._compiler.submit(gate.wait)
        self._programs_lock = threading.Lock()
        self._sub0_lock = threading.Lock()
        self._compile_programs(_jit_dec, _jit_pre)
        # per-phase topology gauges + the placement plan, visible from
        # the first scrape (0s on topology-free engines — the schema
        # never forks on the topology)
        if self.topo is not None:
            d = self.topo.describe()
            self.metrics.set_topology_gauges(
                d["prefill_tp"], d["decode_tp"],
                d["prefill_devices"], d["decode_devices"])
            from megatron_tpu.serving import pp as pps
            self.metrics.set_pp_gauges(
                d["serving_pp"], d["pp_waves"],
                pps.pp_bubble(d["serving_pp"], d["pp_waves"]),
                pps.activation_bytes_per_step(
                    self.num_slots, cfg.hidden_size,
                    cfg.compute_dtype, d["serving_pp"]))
        self._steps = 0
        # a window's fetch runs on this thread where the engine thread
        # waits for it and for a submission alike (`_fetch_admitting`);
        # made at the first such window. `_early_program` is the span's
        # stats of the prefill program that the last window admitted
        # while it ran: the next iteration's, in the chunk's place
        self._fetcher: Optional[ThreadPoolExecutor] = None
        self._early_program: Optional[dict] = None
        self._cond = threading.Condition()
        self._stop = False
        self._draining = False
        self._deadline_s = self.serving.request_deadline_s
        self._broken: Optional[str] = None
        # live-weight serving (serving/weights.py; docs/serving.md
        # "Live weights & rolling upgrade"): the version the compiled
        # programs currently consume (None = unversioned startup
        # weights), the prefix-namespace GENERATION that bumps at every
        # applied swap (KV computed under version N becomes structurally
        # invisible to post-swap lookups — the adapter-namespace
        # pattern applied to base weights), and the pending-swap ticket
        # the loop applies between iterations once in-flight work
        # drains.
        self.weight_version = weight_version
        self._weight_gen = 0
        self._pending_swap: Optional[_SwapTicket] = None
        if weight_version is not None:
            self.metrics.set_weight_version(weight_version.iteration)
        # supervisor state: restarts consumed, wedged-iteration flag
        # (set by the watchdog thread), and the detection-only watchdog
        # itself (armed lazily after the first completed step so the
        # compile-heavy warmup can't trip it)
        self._restarts = 0
        self._last_restart_t: Optional[float] = None
        self._wedged = False
        self._max_restarts = max(self.serving.max_engine_restarts, 0)
        self._watchdog = None
        self._idle_wait = 0.5
        if self.serving.engine_step_timeout_s:
            from megatron_tpu.resilience.watchdog import StepWatchdog
            self._watchdog = StepWatchdog(
                self.serving.engine_step_timeout_s,
                on_timeout=self._on_hang, exit_process=False,
                dump_stacks=False)
            # idle waits must heartbeat faster than the deadline, or an
            # EMPTY engine would look hung
            self._idle_wait = min(
                0.5, self.serving.engine_step_timeout_s / 4.0)
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="serving-engine")
        if start:
            self._thread.start()

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def submit(self, prompt: Sequence[int], max_new_tokens: int = 64,
               sampling: SamplingOptions = SamplingOptions(),
               seed: int = 0, priority: int = 0,
               deadline_s: Optional[float] = None,
               arrival_id: Optional[int] = None,
               adapter_id=None, response_format=None,
               n: int = 1, best_of: Optional[int] = None):
        """Non-blocking: enqueue and return the request handle. Raises
        QueueFullError (→ 429) when the bounded queue is full,
        OverloadShedError (→ 429 + Retry-After) when early shedding
        fires, EngineUnhealthyError (→ 503) when the crash-loop
        circuit breaker is open, and AdmissionError (→ 400) when the
        request can never fit. `priority` clamps into
        [0, priority_levels); `deadline_s` overrides the engine-wide
        request_deadline_s for this request. `arrival_id` (router
        failover retries only) preserves a resubmitted request's
        original queue position. `adapter_id` selects a registered LoRA
        adapter (None = base model); an unknown id (or any id on an
        adapterless engine) is an AdmissionError → 400.

        `response_format` (docs/serving.md "Structured output &
        n-best"): a grammar the output must conform to —
        {"type": "regex", "pattern": ...} or {"type": "json_schema",
        "schema": ...}. Compiled ONCE here into a TokenFSM
        (serving/structured.py); a malformed/unsupported/unsatisfiable
        grammar is an AdmissionError → 400. At runtime the request's
        tokens are sampled under the FSM's per-state vocab mask; a
        dead end fails it typed (GrammarDeadEndError → 422).

        `n` / `best_of` (parallel sampling): decode `best_of`
        (default n) independently seeded samples of ONE prompt — seed,
        seed+1, ... — and return the `n` highest-logprob completions.
        With best_of > 1 the return value is a FanoutRequest
        aggregating the child GenRequests; the children alias the
        leader's prompt KV blocks copy-on-write (one prefill per
        fan-out on prefix-cache engines). Each child is token-exact vs
        a serial run at its own seed."""
        with span("serve/submit") as sp:
            req = self._submit(prompt, max_new_tokens, sampling, seed,
                               priority, deadline_s, arrival_id,
                               adapter_id, response_format, n, best_of)
            sp.set_metadata(rid=req.id)
        return req

    def _submit(self, prompt, max_new_tokens, sampling, seed, priority,
                deadline_s, arrival_id, adapter_id, response_format, n,
                best_of):
        if self._broken:
            # pre-admission gate: the breaker bounces callers before
            # the request is even constructed — deliberately OUTSIDE
            # the received/rejected accounting (the conservation law
            # covers requests the front door actually took in)
            raise EngineUnhealthyError(
                f"engine unhealthy (circuit breaker open): "
                f"{self._broken}")
        # fan-out shape errors are pre-accounting refusals too (the
        # request set was never even constructed): the HTTP boundary
        # 400s these before they get here; this guards API callers
        n = int(n)
        best_of = n if best_of is None else int(best_of)
        if not 1 <= n <= best_of:
            raise AdmissionError(
                f"need 1 <= n <= best_of, got n={n} best_of={best_of}")
        if best_of > self.num_slots:
            raise AdmissionError(
                f"best_of={best_of} exceeds the engine's {self.num_slots}"
                " slots: the fan-out could never decode concurrently")
        # brownout level 2+ (serving/degrade.py): cap fan-out and
        # length for NEW admissions — applied BEFORE the received count
        # so accounting, the child requests and the serial oracle all
        # see the same EFFECTIVE config (the clamped values ARE the
        # request's config; token-exactness holds by construction).
        # best_of clamps to n — the exploration samples beyond what the
        # caller gets back are the first work to go.
        if self.degrade is not None and self.degrade.cap_work():
            best_of = n
            max_new_tokens = min(int(max_new_tokens),
                                 self.serving.degrade_max_new_tokens)
        # received is counted FIRST (once per SAMPLE — each child is a
        # unit of terminal accounting) so that every submit-time
        # refusal below (adapter 400, grammar 400, draining 429, queue
        # full, shed) lands in requests_rejected against matching
        # requests_received — the conservation law requests_received ==
        # completed + rejected + failed + cancelled + expired
        # (serving/invariants.py) holds by construction, not by
        # auditing call sites
        self.metrics.count("requests_received", best_of)
        try:
            if adapter_id is not None:
                from megatron_tpu.serving.adapters import \
                    UnknownAdapterError
                if self.adapters is None:
                    raise UnknownAdapterError(
                        f"adapter_id {adapter_id!r} on an engine "
                        "serving no adapters (adapter_slots=0)")
                if not self.adapters.known(adapter_id):
                    raise UnknownAdapterError(
                        f"unknown adapter_id {adapter_id!r}: register "
                        "it before submitting requests against it")
            if self._draining:
                from megatron_tpu.serving.scheduler import QueueFullError
                raise QueueFullError(
                    "engine draining (shutdown in progress); retry "
                    "against another replica", retry_after=5,
                    queue_depth=self.scheduler.depth())
            fsm = None
            if response_format is not None:
                # ONE compile shared by every sample of the fan-out;
                # compile failures are admission refusals (→ 400),
                # never runtime errors
                try:
                    fsm = compile_response_format(
                        response_format, self.cfg.vocab_size,
                        token_strings=self._token_strings,
                        eos_id=self.gen.eos_id)
                except GrammarCompileError as e:
                    raise AdmissionError(
                        f"response_format does not compile: {e}") from e
            priority = max(0, min(int(priority),
                                  self.serving.priority_levels - 1))
            # brownout levels 3/4 (serving/degrade.py): shed the
            # lowest priority class (3) or every new admission (4) —
            # AFTER the received count, so the shed lands in
            # requests_shed/requests_rejected against matching
            # requests_received like every other submit-time refusal
            if self.degrade is not None and self.degrade.shed_priority(
                    priority, self.serving.priority_levels):
                what = ("all new admissions shed"
                        if self.degrade.level >= 4
                        else "lowest-priority admissions shed")
                raise OverloadShedError(
                    f"brownout level {self.degrade.level}: {what} — "
                    "retry later or against another replica",
                    retry_after=self.scheduler.retry_after_hint(),
                    queue_depth=self.scheduler.depth())
            children: List[GenRequest] = []
            for i in range(best_of):
                req = GenRequest(list(prompt), max_new_tokens, sampling,
                                 seed + i, priority=priority,
                                 deadline_s=deadline_s,
                                 arrival_id=(arrival_id if i == 0
                                             else None),
                                 adapter_id=adapter_id)
                req.response_format = response_format
                req.fsm = fsm
                req.sample_index = i
                if i > 0:
                    # sample 0 is the PREFILL LEADER: siblings gate
                    # their admission on its prompt KV being indexed
                    # so they alias it copy-on-write (_admit)
                    req.fanout_leader = children[0]
                # terminal-accounting hook: the request's FIRST
                # terminal transition — wherever it happens (engine
                # loop, watchdog thread, cancel path, drain, breaker)
                # — counts exactly one of
                # requests_{completed,failed,cancelled,expired}
                req._on_terminal = self._count_terminal
                children.append(req)
            if fsm is not None:
                self.metrics.count("structured_requests", best_of)
            if max_new_tokens == 0:
                # nothing to decode: the serial path returns the prompt
                # row unchanged — short-circuit without occupying a
                # slot, but through the SAME admission check (an
                # oversize prompt must 400 on both routes)
                self.scheduler.check_admissible(children[0])
                for req in children:
                    req.mark_admitted()
                    req.record.t_admit = req.record.t_device = \
                        req.admit_time
                    self.metrics.record_admitted(req.record)
                    req.finish()
            elif best_of == 1:
                self.scheduler.submit(children[0])
            else:
                # atomic batch admission: all samples queue or none do
                # (a half-admitted fan-out would return fewer than n)
                self.scheduler.submit_many(children)
            if best_of > 1:
                self.metrics.count("fanout_requests")
                self.metrics.count("fanout_samples", best_of)
        except OverloadShedError:
            self.metrics.count("requests_shed", best_of)
            self.metrics.count("requests_rejected", best_of)
            raise
        except Exception:
            self.metrics.count("requests_rejected", best_of)
            raise
        if max_new_tokens:      # queued: its programs go to the pool
            self._hand_programs(len(children[0].prompt))
        if best_of == 1:
            return children[0]
        return FanoutRequest(children, n)

    def _count_terminal(self, req: GenRequest, outcome: str):
        """GenRequest._on_terminal hook (any thread; fires exactly once
        per request — the terminal transition is atomic): the SINGLE
        choke point for ALL terminal accounting, so the request-
        conservation invariant cannot drift as failure paths are
        added. Completions count here too (record_completed, with the
        token payload) — do NOT add per-site record_completed
        calls, they would double-count requests_completed and break
        the law. The request's row is closed here, and enters the ring
        here if the request was never admitted."""
        row = req.record
        row.prompt_tokens = len(req.prompt)
        row.generated = len(req.generated)
        row.t_submit, row.t_finish = req.submit_time, req.finish_time
        row.outcome = outcome  # last: who reads it reads a whole row
        self.metrics.requests.keep(row)
        if outcome == "completed":
            # goodput ledger: a completed request whose first token
            # blew the TTFT SLO delivered its tokens too late to be
            # useful work — they count in tokens_generated but not
            # goodput_tokens. Without an SLO every completed token is
            # goodput (the gauge stays meaningful on any config).
            gen = len(req.generated)
            good = gen
            ttft = req.ttft
            if self._slo_ttft_s is not None and ttft is not None \
                    and ttft > self._slo_ttft_s:
                good = 0
            self.metrics.record_completed(gen, good_tokens=good)
        else:
            self.metrics.count("requests_" + outcome)

    def cancel(self, req):
        """Best-effort cancellation: a QUEUED request is dropped and
        failed immediately; a RUNNING one is flagged and evicted at the
        next decode step (frees its slot without decoding to
        completion). Used by the HTTP layer to avoid orphaned work when
        a multi-prompt payload fails partway through submission. A
        FanoutRequest aggregate cancels every child."""
        for child in getattr(req, "children", None) or [req]:
            child.cancel()
            if not child.done():
                self.scheduler.cancel(child)
        self._wake()

    def generate(self, prompt: Sequence[int], max_new_tokens: int = 64,
                 sampling: SamplingOptions = SamplingOptions(),
                 seed: int = 0, timeout: Optional[float] = None):
        """Blocking convenience: submit + wait. Returns (tokens,
        logprobs) with tokens = prompt + generated."""
        return self.submit(prompt, max_new_tokens, sampling,
                           seed).result(timeout)

    def close(self):
        """Stop the loop; fail queued and in-flight requests. Safe on a
        never-started (start=False) engine."""
        self._fail_pending_swap("engine closing")
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        if self._thread.ident is not None:  # was started
            self._thread.join(timeout=30)
        if self._watchdog is not None:
            self._watchdog.stop()
        if self._fetcher is not None:
            self._fetcher.shutdown(wait=False)
        if self._compiler is not None:
            self._compiler.shutdown(wait=False, cancel_futures=True)
            snap = self.metrics.snapshot()
            print_rank_0(
                "serving engine closed: of the programs its loop reached, "
                f"{snap['programs_compiled_ahead']:.0f} compiled ahead, "
                f"{snap['programs_awaited']:.0f} awaited "
                f"({snap['programs_awaited_s']:.1f} s), "
                f"{snap['programs_compiled_inline']:.0f} compiled by the "
                "loop itself")
        for req in self.scheduler.close():
            req.fail("engine shut down")
        for req in self._slot_req:
            if req is not None and req.state is RequestState.RUNNING:
                req.fail("engine shut down")
        for st in self._prefilling:
            if not st.req.done():
                st.req.fail("engine shut down")

    def health(self) -> dict:
        """Liveness/readiness snapshot for `/healthz` (separate from
        the `/metrics` counters): supervisor state, circuit breaker,
        slot occupancy, queue depth — plus the ROUTING SIGNALS the
        front-door router consumes (`free_slots`, `kv_blocks_retained`,
        `service_time_ewma_ms`; schema pinned by a test so the router
        contract can't drift). Host-state reads only — never touches
        the device, so a wedged decode cannot wedge the health endpoint
        too; the pool-accounting reads race the engine thread
        harmlessly (a stale count only skews a routing hint)."""
        # read each flag ONCE: healthy/state/accepting must derive from
        # the SAME snapshot, or the watchdog thread flipping _wedged
        # between two reads yields a self-contradictory payload
        # (state 'running' with healthy False) — the healthz
        # consistency law (serving/invariants.py) holds per payload
        broken = self._broken
        draining = self._draining
        wedged = self._wedged
        state = ("unhealthy" if broken else
                 "draining" if draining else
                 "wedged" if wedged else "running")
        # free_rows, NOT free_count: the latter's memoized
        # reclaimable-block walk is engine-thread-only; these reads
        # come from HTTP probe threads
        free_slots = int(self.pool.free_rows())
        kv_retained = int(self.pool.retained_count())
        healthy = broken is None and not wedged
        loop_alive = self._thread.is_alive()
        return {
            "healthy": healthy,
            "state": state,
            "accepting": healthy and state == "running" and loop_alive,
            "loop_alive": loop_alive,
            "circuit_breaker_open": broken is not None,
            "engine_restarts": self._restarts,
            "max_engine_restarts": self._max_restarts,
            "active_slots": int(self._active.sum()),
            "prefilling": len(self._prefilling),
            "num_slots": self.num_slots,
            "queue_depth": self.scheduler.depth(),
            "free_slots": free_slots,
            "kv_blocks_retained": kv_retained,
            "service_time_ewma_ms":
                self.scheduler.service_time_ewma() * 1e3,
            # brownout ladder (serving/degrade.py): the router
            # aggregates the bare level across replicas as MAX; 0 is
            # both "full service" and the ladderless reading, so the
            # schema never forks. "degrade" carries the controller's
            # full shape (None when the ladder is disabled).
            "degrade_level": (self.degrade.level
                              if self.degrade is not None else 0),
            "degrade": (self.degrade.describe()
                        if self.degrade is not None else None),
            # adapter-locality routing signal (0 on adapterless
            # engines; cheap dict read, HTTP-thread safe)
            "active_adapters": (self.adapters.active_count()
                                if self.adapters is not None else 0),
            # serving-mesh topology (static per engine between replan
            # barriers; operators and the chaos drills read which half
            # a replica lost)
            "serving_tp": (self.topo.tp if self.topo is not None
                           else 1),
            "disaggregated": self._disagg,
            # per-phase topology + the live placement plan
            # (docs/serving.md "Per-phase topology & placement"):
            # width/device-count keys are ALWAYS present (1s on
            # topology-free engines — the schema never forks);
            # "placement" carries the resolved layout plus the plan's
            # budget/reason when a placement optimizer ran, None on a
            # topology-free engine
            "prefill_tp": (self.topo.prefill_tp
                           if self.topo is not None else 1),
            "decode_tp": (self.topo.decode_tp
                          if self.topo is not None else 1),
            "prefill_devices": (self.topo.describe()["prefill_devices"]
                                if self.topo is not None else 1),
            "decode_devices": (self.topo.decode_tp
                               if self.topo is not None else 1),
            "placement": self._placement_health(),
            # static admission bound, served over the wire so a remote
            # front tier can pre-flight lengths without holding weights
            "max_len": int(self.max_len),
            # live-weight serving: the version the compiled programs
            # consume right now ("unversioned" until a staged startup
            # or first swap sets it) — the mixed-fleet observability
            # signal (docs/serving.md "Live weights")
            "weight_version": (self.weight_version.label
                               if self.weight_version is not None
                               else "unversioned"),
            "weight_iteration": (self.weight_version.iteration
                                 if self.weight_version is not None
                                 else 0),
            "weight_swap_pending": self._pending_swap is not None,
            "detail": broken or "",
        }

    def invariant_state(self) -> dict:
        """Read-only snapshot for the system-wide invariant checker
        (serving/invariants.py). The in-flight pieces (slot requests,
        pending prefills, mid-admit pops, queue depth) feed the
        request-conservation law; the weight generation feeds the
        namespace-isolation check. Host reads only — but unlike
        `health()` this walks engine-thread-owned lists, so the STRICT
        accounting sweeps should run against a quiesced (idle, drained,
        or closed) engine; the live sweep only consumes the racy counts
        as a conservative in-flight bound."""
        slot_reqs = [(slot, r) for slot, r in enumerate(self._slot_req)
                     if r is not None]
        pend = [(st.req, st.slot, st.blocks, st.installed)
                for st in self._prefilling]
        admitting = list(self._admitting)
        # in-flight counts only NON-terminal requests: a watchdog-
        # failed slotted request (or a cancelled one lingering in the
        # queue until the next pop) has already been terminal-counted
        live = (sum(1 for _, r in slot_reqs if not r.done())
                + sum(1 for r, _, _, _ in pend if not r.done())
                + sum(1 for r in admitting if not r.done())
                + self.scheduler.live_depth())
        return {
            "slot_requests": slot_reqs,
            "prefilling": pend,
            "admitting": admitting,
            "queue_depth": self.scheduler.depth(),
            "in_flight": live,
            "weight_gen": self._weight_gen,
            "lengths": self._lengths.copy(),
            "active": self._active.copy(),
        }

    def prefix_peek(self, tokens: Sequence[int], adapter_id=None) -> int:
        """Longest cached prefix (device index OR host tier) this
        replica could serve `tokens` with UNDER `adapter_id`'s
        namespace — the router's cache-affinity signal. Called from
        HTTP threads while the engine thread mutates the index: reads
        only, and any racy-iteration error degrades to 0 (affinity is
        a hint, admission re-resolves the real hit on the engine
        thread)."""
        if not self._prefix_on or not tokens:
            return 0
        ns = None
        if adapter_id is not None:
            # the index is keyed by (id, registration generation), so
            # the peek resolves the CURRENT generation — KV from an
            # older registration of the same id is invisible
            if self.adapters is None:
                return 0
            ns = self.adapters.namespace(adapter_id)
            if ns is None:
                return 0
        toks = list(tokens)
        try:
            wns = self._ns(ns)  # current weight generation only
            src, hit = self._index.lookup(toks, len(toks) - 1,
                                          namespace=wns)
            best = hit if src is not None else 0
            if self._host_tier is not None:
                _, hhit = self._host_tier.lookup(toks, len(toks) - 1,
                                                 namespace=wns)
                best = max(best, hhit)
            return int(best)
        except Exception:  # noqa: BLE001 — cross-thread peek
            return 0

    def affinity_digest(self) -> dict:
        """Compact routing-affinity summary a REMOTE front tier polls
        (serving/remote.py; docs/serving.md "Front door"): per-namespace
        cumulative CRC32 chains over the prefix index's block paths
        (device index + host tier, current weight generation only) plus
        the adapter-residency map. A remote `prefix_peek` recomputes
        the same chain over its prompt and counts consecutive matches —
        no token ever crosses the wire, and a hash collision or stale
        digest only skews a HINT (admission re-resolves on this
        replica's engine thread). HTTP-thread safe like prefix_peek:
        reads only, racy iteration degrades to an empty digest."""
        import zlib as _zlib
        out: dict = {"granularity": 0, "namespaces": {}, "adapters": {}}
        if self.adapters is not None:
            try:
                out["adapters"] = {str(a): int(self.adapters.peek(a))
                                   for a in self.adapters.ids()}
            except Exception:  # noqa: BLE001 — cross-thread peek
                pass
        if not self._prefix_on:
            return out
        out["granularity"] = int(self._index.granularity)
        ns: dict = {}

        def _walk(index):
            for blocks in list(index._blocks.values()):
                if not blocks:
                    continue
                tag = blocks[0]  # ("ns", (weight_gen, adapter_ns))
                if not (isinstance(tag, tuple) and len(tag) == 2
                        and tag[0] == "ns"):
                    continue
                wns = tag[1]
                if not (isinstance(wns, tuple) and len(wns) == 2):
                    continue
                wg, ans = wns
                if wg != self._weight_gen:
                    continue  # stale-version KV is invisible remotely too
                label = ("" if ans is None
                         else str(ans[0] if isinstance(ans, tuple)
                                  else ans))
                bucket = ns.setdefault(label, set())
                cum = 0
                for b in blocks[1:]:
                    cum = _zlib.crc32(
                        ",".join(str(int(t)) for t in b).encode(), cum)
                    bucket.add(cum)

        try:
            _walk(self._index)
            if self._host_tier is not None:
                _walk(self._host_tier._index)
        except Exception:  # noqa: BLE001 — racy cross-thread walk
            return {"granularity": 0, "namespaces": {},
                    "adapters": out["adapters"]}
        out["namespaces"] = {k: sorted(v) for k, v in ns.items()}
        return out

    def register_adapter(self, adapter_id, path: Optional[str] = None,
                         factors=None, rank: Optional[int] = None,
                         alpha: float = 1.0):
        """Make `adapter_id` servable on this replica (validated
        eagerly; serving/adapters.py). Raises on an adapterless engine
        — register requires `adapter_slots > 0`."""
        if self.adapters is None:
            raise RuntimeError(
                "this engine serves no adapters (adapter_slots=0); "
                "set ServingConfig.adapter_slots to register adapters")
        self.adapters.register(adapter_id, path=path, factors=factors,
                               rank=rank, alpha=alpha)

    def adapter_peek(self, adapter_id) -> int:
        """Adapter-locality routing signal: 2 = device-resident on
        this replica, 1 = registered (host tier / disk reload away),
        0 = unknown. Cheap dict reads — safe from HTTP threads."""
        if self.adapters is None or adapter_id is None:
            return 0
        return self.adapters.peek(adapter_id)

    def queue_depth(self) -> int:
        return self.scheduler.depth()

    def _upload_chained(self, host):
        """Device copy of a per-slot array that CHAINS through the
        decode/verify calls (lengths, the residual carry): between
        uploads the programs' own outputs feed the next call, and on a
        serving mesh those are committed to it. A plain jnp.asarray is
        an uncommitted single-device array — a different input placement,
        so the call after an upload would trace and compile the program
        a second time. Uploads therefore land where the outputs live:
        replicated on the decode mesh."""
        if self.topo is None or self.topo.serving_pp > 1:
            return jnp.asarray(host)
        return jax.device_put(
            host, self.topo.replicated(self.topo.decode_mesh))

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Graceful shutdown: stop admitting (queued-but-unstarted
        requests fail immediately with a retry-later error; new submits
        are rejected the same way), let every IN-FLIGHT slot decode to
        completion, then stop the loop. Returns True when all in-flight
        work finished within `timeout` (None = wait indefinitely);
        False leaves the stragglers to `close()`'s hard failure. The
        SIGTERM handler in inference/server.py calls this so a rolling
        restart never truncates a response mid-stream."""
        self._draining = True
        self._fail_pending_swap("engine draining")
        backlog = self.scheduler.close()
        for req in backlog:
            # accepted-then-dropped work is a FAILURE (retryable 503),
            # not a submit-time rejection — the terminal hook counts
            # requests_failed per request
            req.fail("engine draining (shutdown in progress); retry "
                     "against another replica", kind="unavailable")
        self._wake()
        if self._thread.ident is not None:
            self._thread.join(timeout)
        drained = not self._thread.is_alive()
        if drained:
            if self._watchdog is not None:
                self._watchdog.stop()
            # this engine's own count of Python traces, then the compile
            # ledger's rows for the same programs: what the process
            # compiled, and what it loaded from the persistent cache
            by = compile_cache.ledger()["by_program"]
            rows = ", ".join(
                f"{name} {by[name]['programs'] - by[name]['hits']}"
                f"/{by[name]['hits']}"
                for name in ("_decode_fn", "_prefill_fn", "_chunk_fwd_fn",
                             "_verify_fn") if name in by)
            print_rank_0(
                "serving engine drained: all in-flight requests "
                f"completed (program traces: decode={self._decode_traces}"
                f" prefill={self._prefill_traces}"
                f" chunk={self._chunk_traces}"
                f" verify={self._verify_traces}; this process "
                f"compiled/loaded from the cache: {rows or 'none'})")
        return drained

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # ------------------------------------------------------------------
    # live-weight hot swap (docs/serving.md "Live weights & rolling
    # upgrade"; serving/weights.py)
    # ------------------------------------------------------------------
    def swap_weights(self, ckpt_dir: str,
                     timeout: Optional[float] = None, staged=None):
        """In-place weight hot swap on the RUNNING engine — zero
        downtime, zero recompiles, token-safe.

        Order of operations is the contract:
        1. STAGE host-side on the calling thread: the checkpoint
           verifies against its SHA-256 manifest and loads into NumPy
           (serving/weights.py `load_staged`) BEFORE anything touches a
           device. A corrupt/truncated/mid-publish checkpoint raises a
           typed `WeightSwapError` here — the engine keeps serving the
           current weights, `weight_swap_failures` counts it.
        2. SWAP POINT on the engine thread: new admissions HOLD (queued
           work waits, nothing is rejected), in-flight slots and
           prefills run to completion under the CURRENT weights, then
           between two iterations the staged tree device-puts through
           `topology.place_params` onto the serving mesh(es) (both the
           prefill and decode groups of a disaggregated engine, in one
           host step) and the param refs under the compiled programs
           flip. Shapes/shardings are identical, so the jit caches hit
           — ZERO recompiles (test-pinned) — and the KV pool arena
           survives untouched.
        3. VERSION HYGIENE: the prefix index rebuilds, retained
           prefixes and host-tier entries drop, the weight-generation
           namespace bumps (a post-swap admission structurally cannot
           clone KV computed under the old weights), queued requests
           carrying mid-stream resume state fail typed/retryable, and
           every registered adapter's generation bumps
           (serving/adapters.py `bump_generations`).

        The result: requests admitted BEFORE the swap are pure version
        N (byte-identical to a never-swapped engine), requests admitted
        AFTER are pure N+1 (byte-identical to a fresh engine at N+1).

        Returns the new `WeightVersion`. Raises `WeightSwapError`
        (typed refusal — current weights keep serving) on a manifest/
        staging/placement failure or when the in-flight drain exceeds
        `timeout` (default `ServingConfig.swap_timeout_s`). `staged`
        (a `StagedWeights`) skips the verify+load step — the rolling
        upgrade stages ONCE at the router and hands every replica the
        same host buffer instead of paying N disk reads + deep
        verifications per rollout."""
        from megatron_tpu.serving.weights import (WeightSwapError,
                                                  load_staged)
        old = (self.weight_version.label
               if self.weight_version is not None else "unversioned")
        if self._broken:
            raise WeightSwapError(
                f"engine unhealthy (circuit breaker open): {self._broken}"
                " — nothing to swap onto")
        if staged is None:
            try:
                staged = load_staged(ckpt_dir, self.gen.params)
            except WeightSwapError:
                self.metrics.count("weight_swap_failures")
                raise
        ticket = _SwapTicket(staged)
        with self._cond:
            if self._stop or self._draining:
                self.metrics.count("weight_swap_failures")
                raise WeightSwapError(
                    "engine stopping/draining; a shutting-down replica "
                    "does not swap")
            if self._pending_swap is not None:
                self.metrics.count("weight_swap_failures")
                raise WeightSwapError(
                    "a weight swap is already in progress on this "
                    "engine")
            self._pending_swap = ticket
            self._cond.notify_all()
        budget = (timeout if timeout is not None
                  else self.serving.swap_timeout_s)
        if not ticket.done.wait(budget):
            with self._cond:
                if self._pending_swap is ticket and not ticket.taken:
                    # still waiting at the barrier: cancel — the engine
                    # resumes admissions, nothing changed
                    self._pending_swap = None
                    self.metrics.count("weight_swap_failures")
                    raise WeightSwapError(
                        f"weight swap timed out after {budget:.1f}s "
                        "waiting for in-flight work to drain; the "
                        f"engine keeps serving {old}")
            # the engine committed to applying (device_put in flight,
            # bounded work — but a big tree over a slow link can take
            # a while): wait the full budget again for the verdict
            if not ticket.done.wait(max(budget, 60.0)):
                # the placement is STILL in flight: its verdict is
                # genuinely unknown — the swap may yet land. Do not
                # claim failure (and do not count one): the apply path
                # counts weight_swaps/sets the gauge itself if it
                # completes; the caller re-checks health().
                raise WeightSwapError(
                    f"weight swap verdict still pending after "
                    f"{budget + max(budget, 60.0):.0f}s (device "
                    "placement in flight); it may still complete — "
                    "check health()['weight_version'] before retrying")
        if ticket.error is not None:
            self.metrics.count("weight_swap_failures")
            raise WeightSwapError(
                f"weight swap failed during device placement "
                f"({ticket.error!r}); the engine keeps serving {old}"
            ) from ticket.error
        if ticket.version is None:
            # breaker tripped / engine closed mid-swap
            self.metrics.count("weight_swap_failures")
            raise WeightSwapError(
                f"weight swap aborted (engine went down mid-swap); "
                f"last known version {old}")
        return ticket.version

    def _apply_swap(self, ticket: _SwapTicket):
        """Engine thread, at the swap point (no active slots, no
        pending prefills): place the staged tree and flip the param
        refs. The placement either succeeds wholly or raises BEFORE any
        ref flips — a device error leaves the engine on the old weights
        (the rollback is that nothing moved)."""
        staged = ticket.staged
        try:
            # placement re-plan hook (serving/placement.py): the swap
            # barrier is THE quiesced moment (no active slots, no
            # pending prefills, admissions held), so it is the only
            # place a `placement_auto` engine re-decides its
            # prefill:decode split from the observed signals. A changed
            # split re-meshes (staged weights land directly on the NEW
            # meshes — one placement, not two) and re-pays the compile
            # bill here; an unchanged split just refreshes the plan's
            # reason and takes the zero-recompile path below.
            replanned = False
            if (self._placement_auto and self.topo is not None
                    and self._placement_plan is not None):
                from megatron_tpu.serving.placement import (
                    plan_placement, signals_from_snapshot)
                plan = plan_placement(
                    self._placement_plan.budget, self.cfg,
                    signals=signals_from_snapshot(
                        self.metrics.snapshot()),
                    current=(self.topo.prefill_tp, self.topo.decode_tp))
                if plan.split() != (self.topo.prefill_tp,
                                    self.topo.decode_tp):
                    self._apply_placement(plan, staged.params)
                    p_dec, p_pre = self._p_dec, self._p_pre
                    replanned = True
                else:
                    self._placement_plan = plan  # held — fresher reason
            if not replanned:
                if self.topo is not None and self.topo.serving_pp > 1:
                    # staged swap: the new tree splits and lands
                    # stage-for-stage on the existing sub-meshes —
                    # identical shapes/shardings, so the per-stage
                    # programs cache-hit like the mono swap
                    p_dec, _ = self.topo.place_stage_params(
                        staged.params, self.cfg)
                    p_pre = p_dec
                elif self.topo is not None:
                    p_dec, _ = self.topo.place_params(
                        staged.params, self.cfg, self.topo.decode_mesh)
                    if self._disagg:
                        p_pre, _ = self.topo.place_params(
                            staged.params, self.cfg,
                            self.topo.prefill_mesh)
                    else:
                        p_pre = p_dec
                else:
                    p_dec = p_pre = jax.device_put(staged.params)
                # surface device/placement errors HERE, not inside some
                # later compiled dispatch where the supervisor would
                # treat them as an engine crash
                jax.block_until_ready(p_dec)
                if p_pre is not p_dec:
                    jax.block_until_ready(p_pre)
        except Exception as e:  # noqa: BLE001 — typed refusal upstream
            ticket.error = e
            ticket.done.set()
            return
        # THE SWAP POINT: both chip groups' param refs flip in one host
        # step — atomic per replica (the disagg chaos drill pins it).
        # Shapes/shardings/avals are identical, so every compiled
        # program cache-hits: zero recompiles.
        self._p_dec, self._p_pre = p_dec, p_pre
        self.weight_version = staged.version
        try:
            self._swap_hygiene(staged)
        except Exception:
            # the refs ALREADY flipped — the engine IS on the new
            # weights — so resolve the ticket as a landed swap, then
            # re-raise: the supervisor's restart rebuilds the pool /
            # index / parked state from scratch, a SUPERSET of the
            # hygiene this block failed to finish (no N-era KV
            # survives a session restart). Never leave the caller
            # hanging on an unresolved ticket.
            self.metrics.count("weight_swaps")
            self.metrics.set_weight_version(staged.version.iteration)
            ticket.version = staged.version
            ticket.done.set()
            raise
        self.metrics.count("weight_swaps")
        self.metrics.set_weight_version(staged.version.iteration)
        ticket.version = staged.version
        print_rank_0(
            f"serving engine: weights hot-swapped to "
            f"{staged.version.label} between iterations "
            + ("(placement re-planned — compile bill paid at the "
               "barrier)" if replanned else "(zero recompiles)"))
        ticket.done.set()

    def _swap_hygiene(self, staged):
        """Post-flip version hygiene (acceptance: a post-swap admission
        can never clone N-era KV under N+1 weights)."""
        self._weight_gen += 1
        self._index = PrefixIndex(
            self.pool.block_size if self._blocks_on
            else max(self.serving.prefill_bucket, 1))
        self.pool.on_reclaim = self._index.remove  # rebind to NEW index
        dropped = self.pool.drop_retained()
        tier_dropped = 0
        if self._host_tier is not None:
            tier_dropped = self._host_tier.clear()
        # no active slots at the barrier: every row re-parks at 0 (the
        # retained park-at-final-length rows just died with their
        # entries)
        self._lengths[:] = 0
        self._reject[:] = -1
        self._lengths_dirty = True
        self._kv_dirty = True
        # queued requests carrying MID-STREAM resume state committed
        # tokens under the old weights; resuming/replaying them under
        # the new ones would mix versions inside one stream — fail them
        # typed + retryable (the router resubmits token-exact on a
        # replica still serving the old version)
        for req in self.scheduler.drop_resumed():
            req.fail(
                "weights hot-swapped while this preempted request "
                "was queued: its committed tokens were generated "
                f"under the previous version and cannot continue "
                f"under {staged.version.label} — resubmit",
                kind="unavailable")  # terminal hook counts it failed
        # adapters were trained against the OLD base: bump every
        # registration generation (rows unmap, host copies drop, prefix
        # namespaces change; mid-flight pinned streams fail typed at
        # re-acquire — serving/adapters.py)
        if self.adapters is not None:
            self.adapters.bump_generations()
        print_rank_0(
            f"serving engine: version hygiene swept {dropped} retained "
            f"prefix(es) and {tier_dropped} host-tier entr(ies) for "
            f"{staged.version.label}")

    def _fail_pending_swap(self, msg: str):
        """Resolve a pending (never-applied) swap ticket when the
        engine goes down — its caller must not hang on the event."""
        with self._cond:
            ticket, self._pending_swap = self._pending_swap, None
        if ticket is not None and not ticket.done.is_set():
            ticket.done.set()  # version stays None -> typed abort

    def _attend_block_rows(self) -> int:
        """The block the Pallas kernel of ops/block_attention_pallas.py
        reads this engine's pool in: the arena's own under
        `block_native_attn`, what `pool_block_rows` gives a contiguous
        `KVCache` pool (the question `attention_apply` asks as it traces a
        decode step), else 0: a latent or hybrid pool, a pool of pipeline
        stages, a bracketed block view and every pool on the dot path."""
        if self._kernel_on:
            return int(self.serving.kv_block_size)
        caches = self.pool.caches
        if not isinstance(caches, KVCache):
            return 0
        from megatron_tpu.ops.block_attention_pallas import pool_block_rows
        return pool_block_rows(
            caches.k.shape, caches.k.dtype, per_slot=True,
            queries=(self.num_slots, 1, self.cfg.num_attention_heads),
            window=self.cfg.sliding_window is not None,
            mesh=self.topo is not None
            or self.gen.mesh is not None) or 0

    def _count_kv_blocks(self, spec_round, spec_k: int):
        """`kv_blocks_read`: over the window's dispatches and the grid's
        rows, the blocks up to the row's last query (a parked row reads its
        first), from the lengths the device holds: the host's, one further a
        chained step (a verify round's accepted tokens are not known yet:
        counted as one). `kv_blocks_held`: every row's whole region."""
        rows = self._attend_rows
        nb = self.pool.cap // rows
        lengths = self._lengths.astype(np.int64)
        read = 0
        for r, spec in enumerate(spec_round):
            last = np.minimum(lengths + r, self.max_len - 1) \
                + (spec_k if spec else 0)
            read += int((np.minimum(last // rows, nb - 1) + 1).sum())
        self.metrics.count("kv_blocks_read", read)
        self.metrics.count("kv_blocks_held",
                           len(spec_round) * self.num_slots * nb)

    # ------------------------------------------------------------------
    # per-phase placement (serving/placement.py + serving/topology.py;
    # docs/serving.md "Per-phase topology & placement")
    # ------------------------------------------------------------------
    def _planned_serving(self):
        """The config the topology builds from: `self.serving` with the
        placement plan's widths substituted. Identity when no plan —
        the explicit widths ARE the plan."""
        if self._placement_plan is None:
            return self.serving
        import dataclasses
        return dataclasses.replace(
            self.serving,
            prefill_tp=self._placement_plan.prefill_tp,
            decode_tp=self._placement_plan.decode_tp)

    def _placement_health(self):
        """`health()["placement"]`: the resolved per-phase layout,
        annotated with the optimizer's budget/reason when a plan
        exists. None on topology-free engines (nothing was placed)."""
        if self.topo is None:
            return None
        out = dict(self.topo.describe())
        if self._placement_plan is not None:
            out["budget"] = self._placement_plan.budget
            out["reason"] = self._placement_plan.reason
        else:
            out["budget"] = None
            out["reason"] = "explicit"
        return out

    def _place_weights(self, params):
        """Place `params` (host-staged NumPy or device tree) for the
        current topology — one resident copy per phase group, each laid
        out under its OWN width's rules — and return the per-group jit
        factories the compiled programs build from. The constructor and
        the placement re-mesh share this path."""
        cfg = self.cfg
        for phase, tp in (("prefill", self.topo.prefill_tp),
                          ("decode", self.topo.decode_tp)):
            assert cfg.num_attention_heads % tp == 0 and \
                cfg.num_kv_heads % tp == 0 and \
                cfg.padded_vocab_size % tp == 0, (
                f"{phase} serving width {tp} (prefill_tp/decode_tp/"
                f"serving_tp) must divide the head counts "
                f"({cfg.num_attention_heads} q / {cfg.num_kv_heads} "
                f"kv) and the padded vocab ({cfg.padded_vocab_size}): "
                "ServingConfig.validate's rule, held here for the widths "
                "of a placement plan, which are not the configuration's")
        if self.topo.serving_pp > 1:
            # pipeline-sharded decode: the model tree splits into
            # per-stage slices, each resident ONLY on its own stage
            # sub-mesh (serving/pp.py) — no device ever holds another
            # stage's layers. _p_dec/_psh_dec become stage-indexed
            # lists; the prefill group aliases them (disaggregation is
            # rejected under serving_pp) and the returned factories go
            # unused — _compile_programs routes to
            # _compile_pp_programs, which builds the per-stage jits
            # directly.
            self._p_dec, self._psh_dec = self.topo.place_stage_params(
                params, cfg)
            self._p_pre, self._psh_pre = self._p_dec, self._psh_dec
            return self._jit_factories()
        self._p_dec, self._psh_dec = self.topo.place_params(
            params, cfg, self.topo.decode_mesh)
        if self._disagg:
            self._p_pre, self._psh_pre = self.topo.place_params(
                params, cfg, self.topo.prefill_mesh)
        else:
            self._p_pre, self._psh_pre = self._p_dec, self._psh_dec
        return self._jit_factories()

    def _jit_factories(self):
        """(decode-group, prefill-group) jit builders against the
        CURRENT topology + param shardings."""
        _jit_dec = (lambda fn, n_array_args, donate_argnums=():
                    self.topo._jit(self.topo.decode_mesh,
                                   self._psh_dec, fn, n_array_args,
                                   donate_argnums))
        _jit_pre = (lambda fn, n_array_args, donate_argnums=():
                    self.topo._jit(self.topo.prefill_mesh,
                                   self._psh_pre, fn, n_array_args,
                                   donate_argnums))
        return _jit_dec, _jit_pre

    @phase("engine.programs")
    def _compile_programs(self, _jit_dec, _jit_pre):
        """Build every compiled program against the current topology.
        Called once at construction and again only at an applied
        placement re-plan (the quiesced barrier — a re-mesh is the one
        event that legitimately re-pays the compile bill; trace
        counters reset because a new program set is a new one-compile
        epoch)."""
        if self.topo is not None and self.topo.serving_pp > 1:
            # pipeline-sharded decode: per-stage program chains behind
            # wrappers with the EXACT mono signatures — every dispatch
            # site below stays untouched
            self._compile_pp_programs()
            return self._new_program_set()
        S, Vp = self.num_slots, self.cfg.padded_vocab_size
        self._decode_traces = 0  # trace count — MUST stay 1 in steady state
        self._prefill_traces = 0  # one per (batch, prompt-length) bucket
        # lengths (arg 4) chains device-side but is NOT donated: it is
        # [S] int32 (nothing to save), and donating a buffer that the
        # next chained call consumes while the previous one is still in
        # flight hits the CPU jax 0.4.x donation-aliasing bug the
        # rollback path in training/loop.py documents (observed here as
        # rare wrong tokens on the 8-virtual-device CPU mesh)
        self._decode = _jit_dec(self._decode_fn, n_array_args=11,
                                donate_argnums=(1, 2, 3))
        # speculative verify: ONE trace for the enabled k (drafts are
        # a fixed [S, k] shape — k is a compile-time bucket), compiled
        # alongside the decode step the first window dispatches it.
        # Same donation set and the same lengths/rejects no-donate rule
        # as _decode (both chain device-side across a window).
        self._verify_traces = 0
        self._verify = _jit_dec(self._verify_fn, n_array_args=14,
                                donate_argnums=(1, 2, 3))
        # resident grammar-neutral verify args (all-True per-position
        # masks + no-guess sentinel): windows with no structured row
        # dispatch these unchanged buffers, so the masked verify trace
        # costs free traffic nothing
        if self._spec_k:
            self._d_free_dmask = jnp.ones((S, self._spec_k, Vp),
                                          jnp.bool_)
            self._d_no_guess = jnp.full((S,), -1, jnp.int32)
        # one jit; jax retraces per (batch-bucket, padded prompt length)
        # combo (both bucketed — _prefill_bucket / _batch_bucket — so
        # the cache hits across request sizes and arrival bursts)
        self._prefill = _jit_dec(self._prefill_fn, n_array_args=9,
                                 donate_argnums=(1, 2, 3))
        # prefix-cache / chunked-prefill programs (slot indices and
        # offsets are traced scalars — one compile serves every slot):
        # _slice reads a region out of the pool (the read half of
        # kv_pool.clone_prefix; start=0 on a miss just yields a
        # masked-garbage batch-1 cache at offset 0), _chunk_fwd appends
        # one chunk at the sub-cache's offset (retraces per padded
        # chunk length, same bucketing as _prefill), _insert is the
        # write half — the whole region lands in the dst slot and the
        # slot activates. `sub` is deliberately NOT donated across the
        # _chunk_fwd chain: chained donation of a consumed-in-flight
        # buffer hits the CPU jax 0.4.x aliasing bug documented at
        # _decode above.
        self._chunk_traces = 0
        self._slice = _jit_dec(self._slice_fn, n_array_args=3)
        # the chunk forward is the PREFILL-group program: on a
        # disaggregated engine it compiles against the prefill mesh's
        # weight copy (every other program below is decode-group)
        self._chunk_fwd = _jit_pre(self._chunk_fwd_fn, n_array_args=6)
        self._insert = _jit_dec(self._insert_fn, n_array_args=8,
                                donate_argnums=(1, 2, 3))
        # block-mode variants: slice by explicit physical-block list,
        # insert through the slot's map row with the aliased-prefix
        # copy-on-write boundary
        self._slice_blk = _jit_dec(self._slice_blocks_fn,
                                   n_array_args=3)
        self._insert_blk = _jit_dec(self._insert_blocks_fn,
                                    n_array_args=9,
                                    donate_argnums=(1, 2, 3))
        # disaggregated handoff programs: land the transferred live
        # blocks on the decode group (pad-to-cap + insert_blocks +
        # activation fused — one compile per live-block count), and
        # widen a transferred prefix onto the prefill group for
        # suffix chunks (the hit's decode->prefill ride)
        self._handoff_insert = _jit_dec(self._handoff_insert_fn,
                                        n_array_args=8,
                                        donate_argnums=(1, 2, 3))
        self._pad_sub_pre = _jit_pre(self._pad_sub_pre_fn,
                                     n_array_args=2)
        self._new_program_set()

    # ------------------------------------------------------------------
    # compile-ahead (the module docstring's paragraph)
    # ------------------------------------------------------------------
    def _new_program_set(self):
        """The jits are new (construction, a re-plan's re-mesh): none is
        compiled, handed over or reached. What every window runs
        whatever arrives goes to the pool now: the decode step and,
        where no window opens with a verify round, the first tokens'
        draw. `_verify` waits for its first round in the loop, as it
        did: a drafter may never propose."""
        with self._programs_lock:
            self._programs = {}
        self._reached = set()
        self._compile_ahead(("decode",), self._decode, self._decode_args)
        if not self._spec_k:
            self._compile_ahead(("draw",), _draw_ahead_jit,
                                self._draw_args,
                                vocab_size=self.cfg.vocab_size)

    def _compile_ahead(self, key, program, make_args, **static):
        """Any thread: hand `program` to the pool unless `key` is
        compiled or under way. The pool lowers it for what `make_args()`
        gives there (the loop's own argument list, every array of it as
        `_shape_of` sees it) and compiles it, which fills JAX's own
        caches (trace, lowering, executable, the persistent cache): the
        loop's later call compiles nothing. What has no `lower` (the
        per-stage chains of `serving_pp`: no one program) stays the
        loop's."""
        if self._compiler is None or not hasattr(program, "lower") \
                or key in self._programs:
            return

        def compile_():
            args = jax.tree.map(_shape_of, make_args())
            program.lower(*args, **static).compile()

        with self._programs_lock:
            if key in self._programs:
                return
            try:
                self._programs[key] = self._compiler.submit(compile_)
            except RuntimeError:        # the engine is closed
                pass

    def _await_program(self, key, reqs=()):
        """Engine thread, ahead of a program's dispatch: a set lookup
        every time but the program's first, where it counts how the loop
        found it. Compiled by the pool: `programs_compiled_ahead`. Under
        way there: the loop waits for that one future
        (`programs_awaited`, `programs_awaited_s`), no longer than the
        latest deadline of `reqs`, the requests the dispatch is for, and
        not past a stop or the watchdog's flag; where it gives up, as
        where nobody handed the program over, its own call compiles
        (`programs_compiled_inline`), as every call did before there was
        a pool. A compile that raised in the pool raises here, where the
        loop's own would have, and the key is forgotten: the next
        request of that shape hands it over again."""
        if key in self._reached:
            return
        self._reached.add(key)
        with self._programs_lock:
            fut = self._programs.setdefault(key, _COMPILED)
        if fut is _COMPILED:
            return self._count_program("programs_compiled_inline")
        waited = 0.0
        if not fut.done():
            ends = [r.absolute_deadline(self._deadline_s) for r in reqs]
            end = max(ends) if ends and None not in ends else None
            t0 = time.monotonic()
            while not (fut.done() or self._stop or self._wedged
                       or (end is not None and time.monotonic() > end)):
                try:
                    fut.exception(timeout=self._idle_wait)
                except FutureTimeout:
                    pass
                except CancelledError:      # by close()
                    break
            waited = time.monotonic() - t0
        if not fut.done() or fut.cancelled():
            return self._count_program("programs_compiled_inline", waited)
        if fut.exception() is not None:
            self._reached.discard(key)
            with self._programs_lock:
                if self._programs.get(key) is fut:
                    del self._programs[key]
            raise fut.exception()
        self._count_program("programs_awaited" if waited
                            else "programs_compiled_ahead", waited)

    def _count_program(self, counter: str, waited: float = 0.0):
        self.metrics.count(counter)
        if waited:
            self.metrics.count("programs_awaited_s", waited)
        note_program(counter, waited)

    # The argument lists of the programs the pool compiles, each made in
    # ONE place: the loop passes what these return, the pool lowers for it
    def _decode_args(self):
        on = self._adapters_on
        return (self._p_dec, self.pool.caches, self._last_logits,
                self._rngs, self._d_lengths, self._d_temps, self._d_top_ks,
                self._d_top_ps, self._d_reject, self._d_masks,
                self.adapters.stacked if on else None,
                self._d_adapter_idx if on else None)

    def _draw_args(self):
        return (self._last_logits, self._rngs, self._d_temps,
                self._d_top_ks, self._d_top_ps, self._d_reject,
                self._d_masks)

    def _prefill_args(self, tokens, plens, slots, rng0s, aidxs):
        return (self._p_dec, self.pool.caches, self._last_logits,
                self._rngs, tokens, plens, slots, rng0s,
                self.adapters.stacked if self._adapters_on else None,
                aidxs)

    def _chunk_args(self, sub, tokens, last_idx, next_offset, aidx1):
        # the PREFILL-group bank copy (== stacked on single-group
        # topologies; serving/adapters.py stacked_prefill)
        return (self._p_pre, sub, tokens, last_idx, next_offset,
                self.adapters.stacked_prefill if self._adapters_on
                else None, aidx1)

    def _insert_args(self, sub, slot, plen, pfx_blocks, last, rng0):
        """Of the program that lands a finished prefill in its slot:
        `_insert_blk` over a block-granular pool, else `_insert`."""
        tail = (pfx_blocks, last, rng0) if self._blocks_on else (last, rng0)
        return (self._p_dec, self.pool.caches, self._last_logits,
                self._rngs, sub, slot, plen) + tail

    def _hand_programs(self, plen: int):
        """`submit`'s thread, with the request queued: the programs its
        prompt of `plen` tokens is certain to call go to the pool, picked
        by the functions that pick them in the loop (`_single`,
        `_chunk_shape`, `_insert_args`). That is the pending path's: the
        key, each chunk's program, the landing. With the prefix cache
        on, a hit decides path and shapes at admission, and nothing is
        handed over here; a disaggregated engine's landing has the shape
        of the blocks that crossed and stays the loop's; a group's one
        prefill has the batch the loop pops (`_hand_groups`,
        `_hand_group`)."""
        if self._compiler is None or self._prefix_on \
                or not self._single(plen):
            return
        aidx1 = _i32(1) if self._adapters_on else None
        self._compile_ahead(("key",), _burned_key_jit,
                            lambda: (np.int64(0), np.int32(0)))
        pos = 0
        while pos < plen:
            n, padded = self._chunk_shape(pos, plen)
            self._compile_ahead(
                ("chunk", padded), self._chunk_fwd,
                lambda padded=padded: self._chunk_args(
                    self._zero_sub(), _i32(1, padded), _i32(), _i32(),
                    aidx1))
            pos += n
        if not self._disagg:
            row = self._last_logits     # a chunk's last logits are a row
            self._compile_ahead(
                ("insert",),
                self._insert_blk if self._blocks_on else self._insert,
                lambda: self._insert_args(
                    self._zero_sub(), _i32(), _i32(), _i32(),
                    jax.ShapeDtypeStruct(row.shape[1:], row.dtype),
                    jax.ShapeDtypeStruct((2,), jnp.uint32)))

    def _hand_group(self, B: int, padded: int):
        """The two programs of one `_prefill_group` call: `_admit` hands
        over every group of a pop before it dispatches the first."""
        aidxs = _i32(B) if self._adapters_on else None
        self._compile_ahead(
            ("keys", B), _burned_keys_jit,
            lambda: (np.zeros(B, np.int64), np.zeros(B, np.int32)))
        self._compile_ahead(
            ("prefill", B, padded), self._prefill,
            lambda: self._prefill_args(
                _i32(B, padded), _i32(B), _i32(B),
                jax.ShapeDtypeStruct((B, 2), jnp.uint32), aidxs))

    def _hand_groups(self):
        """The loop's first act: what was queued before it ran (a
        warm-up queued whole, a restart's requeued prompts) will be
        popped together, so its groups are known now: of each padded
        length, groups of `prefill_max_batch` and what is left over
        (`AdmissionScheduler.group_by_bucket`). Not with the prefix
        cache on: a hit is no group's."""
        if self._compiler is None or self._prefix_on:
            return
        count, cap = {}, self._prefill_max_batch
        for r in self.scheduler.queued():
            if r.parked is None and r.resume_rng is None \
                    and not self._single(len(r.prompt)):
                padded = self._prefill_bucket(len(r.prompt))
                count[padded] = count.get(padded, 0) + 1
        for padded, n in count.items():
            self._hand_group(self._batch_bucket(min(n, cap)), padded)
            if n > cap and n % cap:
                self._hand_group(self._batch_bucket(n % cap), padded)

    def _single(self, plen: int, hit: bool = False,
                resumed: bool = False) -> bool:
        """Does a prompt go the pending path (batch-1 chunks, then one
        landing) and not into a group's one prefill? Disaggregated
        engines route EVERY admission through it: the batch-1 chunk
        forward is the unit that runs on the prefill group, and
        activation is the block handoff."""
        return bool(hit or resumed or self._disagg
                    or (self._chunk is not None and plen > self._chunk))

    def _zero_sub(self):
        """The shared ZERO template a miss starts from, in place of a
        full region copy out of the pool for content the offset-0 mask
        never reads. Sharing one template across admissions is safe
        because _chunk_fwd never donates its input — every chunk returns
        fresh buffers. Made once, by whoever asks first (the loop, or
        the pool as it lowers a chunk program)."""
        with self._sub0_lock:
            if self._sub0 is None:
                full0 = self.pool.make_prefill_caches(1)
                if self._pp > 1:
                    # staged template: stage i's [L/S]-layer zero
                    # slice committed to stage i's sub-mesh — the
                    # chunk chain consumes the list stage-for-stage
                    from megatron_tpu.serving import pp as pps
                    self._sub0 = [
                        self.topo.place_kv_tree(
                            pps.stage_kv(full0, self._pp, i), mesh)
                        for i, mesh in enumerate(self.topo.stage_meshes)]
                elif self.topo is not None:
                    # commit the template to the PREFILL mesh once:
                    # left uncommitted, every miss admission's
                    # first chunk would re-transfer a full
                    # cap-region of zeros to the prefill group —
                    # the exact cross-group cap-region copy the
                    # disaggregation design exists to avoid
                    self._sub0 = self.topo.place_kv_tree(
                        full0, self.topo.prefill_mesh)
                else:
                    self._sub0 = full0
            return self._sub0

    # ------------------------------------------------------------------
    # pipeline-sharded program chains (serving_pp > 1)
    # ------------------------------------------------------------------
    def _pp_put(self, x, i):
        """Replicate a dispatch-data array onto stage i's sub-mesh —
        the [S, hidden] residual (and the few small metadata rows that
        ride with it) crossing a stage seam via ONE device_put, the
        same transfer primitive the disaggregated P→D handoff uses."""
        if x is None:
            return None
        return jax.device_put(
            x, self.topo.replicated(self.topo.stage_meshes[i]))

    def _pp_stage_lora(self):
        """Per-stage slices of the adapter bank's stacked factor tree
        (serving/pp.py stage_lora), each resident on its own stage
        sub-mesh under the bank's projection shardings. Re-sliced only
        when the bank's stacked ref changed (loads replace it
        functionally); [None]*S with adapters off."""
        if not self._adapters_on:
            return [None] * self._pp
        src = self.adapters.stacked
        if self._pp_lora_src is not src:
            from megatron_tpu.serving import pp as pps
            stages = []
            for i, mesh in enumerate(self.topo.stage_meshes):
                sliced = pps.stage_lora(src, self.cfg, self._pp, i)
                stages.append(jax.device_put(
                    sliced, self.topo.adapter_shardings(mesh)))
            self._pp_lora_src = src
            self._pp_lora = stages
        return self._pp_lora

    def _compile_pp_programs(self):
        """Build the staged program set for `serving_pp = S > 1`: each
        mono program becomes a chain of per-stage jitted segments —
        stage i runs its own contiguous layer slice against its own
        layer-partitioned KV arena slice on its own sub-mesh, and the
        [rows, hidden] residual activation crosses each seam via one
        `device_put`. The chains hide behind Python wrappers with the
        EXACT mono signatures/returns, assigned to `self._decode` /
        `_verify` / `_prefill` / `_chunk_fwd` / `_slice_blk` /
        `_insert_blk`, so every dispatch site in the engine stays
        byte-for-byte untouched; `self.pool.caches` and `st.sub` become
        stage-indexed LISTS the wrappers thread through.

        Chaining contiguous layer slices is bit-identical math to the
        mono full-depth scan (two half-depth lax.scans chained == one),
        which is what makes the serving_pp=2-vs-1 token-exactness gate
        exact rather than approximate. Sampling, the accept logic, and
        per-slot state live on stage 0 (intake) except the speculative
        accept computation, which needs the head's logits and therefore
        runs on stage S-1 with its outputs transferred back.

        `pp_waves = W > 1` splits the slot grid into W row-waves of
        S_slots/W rows: each stage segment compiles ONCE at the wave
        width (the wave's row origin `w0` is a traced operand of the
        wave_view/wave_scatter bracket) and the wrapper dispatches the
        W waves back-to-back — async dispatch plus the functional
        per-stage arena carry gives the 1F1B overlap (wave 1 runs
        stage 0 while wave 0 runs stage 1), shrinking the idle bubble
        to (S-1)/(W+S-1) (`pp_stage_bubble`)."""
        from megatron_tpu.serving import kv_pool as kvp
        from megatron_tpu.serving import pp as pps
        topo, cfg, pool = self.topo, self.cfg, self.pool
        S_pp, W = self._pp, self._pp_waves
        S, Vp = self.num_slots, cfg.padded_vocab_size
        Sw = S // W
        Ls = cfg.num_layers // S_pp
        max_len = self.max_len
        adapters_on = self._adapters_on
        rope = self._rope

        def _stage_jit(i, fn, n_array_args, donate_argnums=()):
            return topo._jit(topo.stage_meshes[i], self._psh_dec[i],
                             fn, n_array_args, donate_argnums)

        # trace counters: the mono counters live on the stage-0
        # segments (so the steady-state `decode_traces == 1` pin reads
        # identically), and the per-stage lists pin ONE compile per
        # stage per program
        self._decode_traces = 0
        self._prefill_traces = 0
        self._verify_traces = 0
        self._chunk_traces = 0
        self._pp_decode_traces = [0] * S_pp
        self._pp_verify_traces = [0] * S_pp
        self._pp_lora_src = None
        self._pp_lora = None
        if self._spec_k:
            self._d_free_dmask = jnp.ones((S, self._spec_k, Vp),
                                          jnp.bool_)
            self._d_no_guess = jnp.full((S,), -1, jnp.int32)

        # ---- decode chain (one wave-width compile per stage) ---------
        def _dec0(params0, bkv0, last_w, rngs_w, lengths_w, temps_w,
                  top_ks_w, top_ps_w, rejects_w, masks_w, lora0,
                  aidx_w, w0):
            # stage 0 = the mono _decode_fn's sample + embed + first
            # layer slice (same ops, same order — see _decode_fn for
            # the semantics of every piece)
            self._decode_traces += 1
            self._pp_decode_traces[0] += 1
            adapters = (lora0, aidx_w) if adapters_on else None
            view = pps.wave_view(bkv0, w0, Sw, lengths=lengths_w)
            split = jax.vmap(jax.random.split)(rngs_w)
            new_rngs, step_keys = split[:, 0], split[:, 1]
            toks = sample_batched(step_keys, last_w,
                                  temperature=temps_w, top_k=top_ks_w,
                                  top_p=top_ps_w,
                                  vocab_size=cfg.vocab_size,
                                  banned=rejects_w, mask=masks_w)
            lp = jax.nn.log_softmax(last_w, axis=-1)
            tok_lp = jnp.take_along_axis(lp, toks[:, None],
                                         axis=-1)[:, 0]
            x = pps.embed_tokens(params0, toks[:, None], cfg,
                                 position_ids=lengths_w[:, None])
            x, view = pps.stage_forward(params0, x, cfg, rope=rope,
                                        kv_caches=view, layer_offset=0,
                                        position_ids=lengths_w[:, None],
                                        adapters=adapters)
            bkv0 = pps.wave_scatter(bkv0, w0, view)
            new_lengths = jnp.minimum(lengths_w + 1,
                                      jnp.int32(max_len - 1))
            return (bkv0, x, new_rngs, toks, tok_lp, new_lengths,
                    jnp.full_like(rejects_w, -1))

        def _make_dec_tail(si):
            lo = si * Ls
            is_last = si == S_pp - 1

            def _dec_i(params_i, bkv_i, x, lengths_w, lora_i, aidx_w,
                       w0):
                self._pp_decode_traces[si] += 1
                adapters = (lora_i, aidx_w) if adapters_on else None
                view = pps.wave_view(bkv_i, w0, Sw, lengths=lengths_w)
                x, view = pps.stage_forward(
                    params_i, x, cfg, rope=rope, kv_caches=view,
                    layer_offset=lo,
                    position_ids=lengths_w[:, None], adapters=adapters)
                bkv_i = pps.wave_scatter(bkv_i, w0, view)
                if is_last:
                    logits = pps.stage_head(params_i, x, cfg,
                                            logits_dtype=jnp.float32)
                    return bkv_i, logits[:, 0]
                return bkv_i, x
            return _dec_i

        # stage 0 donates its KV slice and the rng state (both have
        # same-shaped outputs); last_logits is NOT donated here — the
        # fresh logits come off the LAST stage's head, so stage 0 has
        # no output to alias the old buffer onto
        self._pp_dec = [_stage_jit(0, _dec0, 12, (1, 3))] + [
            _stage_jit(i, _make_dec_tail(i), 6, (1,))
            for i in range(1, S_pp)]

        def _decode_pp(params_u, pools, last_logits, rngs, lengths,
                       temps, top_ks, top_ps, rejects, masks, lora_u,
                       aidx):
            lora_st = self._pp_stage_lora()
            new_pools = list(pools)
            outs = []
            for w in range(W):
                sl = slice(w * Sw, (w + 1) * Sw)

                def ws(a):
                    return a if (W == 1 or a is None) else a[sl]

                w0 = jnp.int32(w * Sw)
                out0 = self._pp_dec[0](
                    self._p_dec[0], new_pools[0], ws(last_logits),
                    ws(rngs), ws(lengths), ws(temps), ws(top_ks),
                    ws(top_ps), ws(rejects), ws(masks), lora_st[0],
                    ws(aidx), w0)
                new_pools[0] = out0[0]
                x, lw, ai = out0[1], ws(lengths), ws(aidx)
                for i in range(1, S_pp):
                    new_pools[i], x = self._pp_dec[i](
                        self._p_dec[i], new_pools[i],
                        self._pp_put(x, i), self._pp_put(lw, i),
                        lora_st[i], self._pp_put(ai, i), w0)
                outs.append((self._pp_put(x, 0),) + tuple(out0[2:]))
            if W == 1:
                last, new_rngs, toks, tok_lp, new_len, new_rej = outs[0]
            else:
                last, new_rngs, toks, tok_lp, new_len, new_rej = [
                    jnp.concatenate([o[j] for o in outs], axis=0)
                    for j in range(6)]
            return (new_pools, last, new_rngs, toks, tok_lp, new_len,
                    new_rej)

        self._decode = _decode_pp

        # ---- speculative verify chain (whole-grid: pp_waves > 1 is
        # rejected with speculative_k) ---------------------------------
        def _ver0(params0, bkv0, last, rngs, lengths, temps, top_ks,
                  top_ps, drafts, rejects, t0_masks, lora0, aidx):
            self._verify_traces += 1
            self._pp_verify_traces[0] += 1
            adapters = (lora0, aidx) if adapters_on else None
            view = pps.wave_view(bkv0, jnp.int32(0), S, lengths=lengths)
            split = jax.vmap(jax.random.split)(rngs)
            new_rngs, step_keys = split[:, 0], split[:, 1]
            toks0 = sample_batched(step_keys, last, temperature=temps,
                                   top_k=top_ks, top_p=top_ps,
                                   vocab_size=cfg.vocab_size,
                                   banned=rejects, mask=t0_masks)
            lp0 = jax.nn.log_softmax(last, axis=-1)
            lp0 = jnp.take_along_axis(lp0, toks0[:, None], -1)[:, 0]
            window = jnp.concatenate([toks0[:, None], drafts], axis=1)
            w = window.shape[1]
            positions = jnp.minimum(lengths[:, None] + jnp.arange(w),
                                    jnp.int32(max_len - 1))
            x = pps.embed_tokens(params0, window, cfg,
                                 position_ids=positions)
            x, view = pps.stage_forward(params0, x, cfg, rope=rope,
                                        kv_caches=view, layer_offset=0,
                                        position_ids=positions,
                                        adapters=adapters)
            bkv0 = pps.wave_scatter(bkv0, jnp.int32(0), view)
            return bkv0, x, new_rngs, window, toks0, lp0, step_keys

        def _make_ver_mid(si):
            lo = si * Ls

            def _ver_i(params_i, bkv_i, x, lengths, lora_i, aidx):
                self._pp_verify_traces[si] += 1
                adapters = (lora_i, aidx) if adapters_on else None
                w = x.shape[1]
                positions = jnp.minimum(
                    lengths[:, None] + jnp.arange(w),
                    jnp.int32(max_len - 1))
                view = pps.wave_view(bkv_i, jnp.int32(0), S,
                                     lengths=lengths)
                x, view = pps.stage_forward(
                    params_i, x, cfg, rope=rope, kv_caches=view,
                    layer_offset=lo, position_ids=positions,
                    adapters=adapters)
                bkv_i = pps.wave_scatter(bkv_i, jnp.int32(0), view)
                return bkv_i, x
            return _ver_i

        def _make_ver_last(si):
            lo = si * Ls

            def _ver_last(params_i, bkv_i, x, lengths, temps, top_ks,
                          top_ps, drafts, draft_masks, guess0, toks0,
                          lp0, step_keys, lora_i, aidx):
                # stage S-1 = the mono _verify_fn's tail: last layer
                # slice, head, and the full accept computation verbatim
                # (see _verify_fn for the semantics)
                self._pp_verify_traces[si] += 1
                adapters = (lora_i, aidx) if adapters_on else None
                k = drafts.shape[1]
                w = x.shape[1]
                positions = jnp.minimum(
                    lengths[:, None] + jnp.arange(w),
                    jnp.int32(max_len - 1))
                view = pps.wave_view(bkv_i, jnp.int32(0), S,
                                     lengths=lengths)
                x, view = pps.stage_forward(
                    params_i, x, cfg, rope=rope, kv_caches=view,
                    layer_offset=lo, position_ids=positions,
                    adapters=adapters)
                bkv_i = pps.wave_scatter(bkv_i, jnp.int32(0), view)
                logits = pps.stage_head(params_i, x, cfg,
                                        logits_dtype=jnp.float32)
                ctx = logits[:, :k]
                probs, targets = verify_draft_probs(
                    ctx, drafts, temperature=temps, top_k=top_ks,
                    top_p=top_ps, vocab_size=cfg.vocab_size,
                    mask=draft_masks)

                def row_unifs(rk):
                    return jax.vmap(lambda i: jax.random.uniform(
                        jax.random.fold_in(rk, i)))(
                            jnp.arange(1, k + 1))

                u = jax.vmap(row_unifs)(step_keys)
                greedy_rows = (temps == 0.0) | (top_ks == 1)
                accept = jnp.where(greedy_rows[:, None],
                                   drafts == targets,
                                   u < probs) & (drafts >= 0)
                gate_ok = (guess0 < 0) | (toks0 == guess0)
                accept &= gate_ok[:, None]
                allow = (lengths[:, None] + 1 + jnp.arange(k)[None, :]
                         <= jnp.int32(max_len - 1))
                acc = (accept & allow).astype(jnp.int32)
                a = jnp.sum(jnp.cumprod(acc, axis=1), axis=1)
                lp = jax.nn.log_softmax(ctx, axis=-1)
                draft_lp = jnp.take_along_axis(
                    lp, drafts[..., None], -1)[..., 0]
                tok_lp = jnp.concatenate([lp0[:, None], draft_lp], 1)
                new_last = jnp.take_along_axis(
                    logits, a[:, None, None], 1)[:, 0].astype(
                        jnp.float32)
                a_idx = jnp.clip(a, 0, k - 1)
                d_stop = jnp.take_along_axis(drafts,
                                             a_idx[:, None], 1)[:, 0]
                allow_stop = jnp.take_along_axis(allow,
                                                 a_idx[:, None], 1)[:, 0]
                new_rejects = jnp.where(
                    gate_ok & (a < k) & allow_stop & (d_stop >= 0),
                    d_stop, jnp.int32(-1)).astype(jnp.int32)
                new_lengths = jnp.minimum(lengths + 1 + a,
                                          jnp.int32(max_len - 1))
                return (bkv_i, new_last, tok_lp, a, new_lengths,
                        new_rejects)
            return _ver_last

        self._pp_ver = ([_stage_jit(0, _ver0, 12, (1, 3))]
                        + [_stage_jit(i, _make_ver_mid(i), 6, (1,))
                           for i in range(1, S_pp - 1)]
                        + [_stage_jit(S_pp - 1,
                                      _make_ver_last(S_pp - 1), 14,
                                      (1,))])

        def _verify_pp(params_u, pools, last_logits, rngs, lengths,
                       temps, top_ks, top_ps, drafts, rejects, masks,
                       d_masks, guess0, lora_u, aidx):
            lora_st = self._pp_stage_lora()
            new_pools = list(pools)
            out0 = self._pp_ver[0](
                self._p_dec[0], new_pools[0], last_logits, rngs,
                lengths, temps, top_ks, top_ps, drafts, rejects,
                masks, lora_st[0], aidx)
            new_pools[0] = out0[0]
            x = out0[1]
            new_rngs, window, toks0, lp0, step_keys = out0[2:]
            for i in range(1, S_pp - 1):
                new_pools[i], x = self._pp_ver[i](
                    self._p_dec[i], new_pools[i], self._pp_put(x, i),
                    self._pp_put(lengths, i), lora_st[i],
                    self._pp_put(aidx, i))
            li = S_pp - 1
            lout = self._pp_ver[li](
                self._p_dec[li], new_pools[li], self._pp_put(x, li),
                self._pp_put(lengths, li), self._pp_put(temps, li),
                self._pp_put(top_ks, li), self._pp_put(top_ps, li),
                self._pp_put(drafts, li), self._pp_put(d_masks, li),
                self._pp_put(guess0, li), self._pp_put(toks0, li),
                self._pp_put(lp0, li), self._pp_put(step_keys, li),
                lora_st[li], self._pp_put(aidx, li))
            new_pools[li] = lout[0]
            return (new_pools, self._pp_put(lout[1], 0), new_rngs,
                    window, self._pp_put(lout[2], 0),
                    self._pp_put(lout[3], 0), self._pp_put(lout[4], 0),
                    self._pp_put(lout[5], 0))

        self._verify = _verify_pp

        # ---- batched prefill chain -----------------------------------
        def _pre0(params0, bkv0, tokens, plens, slots, lora0, aidxs):
            adapters = (lora0, aidxs) if adapters_on else None
            B = tokens.shape[0]
            caches = pps.stage_kv(pool.make_prefill_caches(B), S_pp, 0)
            x = pps.embed_tokens(params0, tokens, cfg,
                                 offset=caches.offset[0])
            x, caches = pps.stage_forward(params0, x, cfg, rope=rope,
                                          kv_caches=caches,
                                          layer_offset=0,
                                          adapters=adapters)
            view = pps.wave_view(bkv0, jnp.int32(0), S)
            for i in range(B):
                def row(t):
                    return jax.lax.dynamic_slice_in_dim(t, i, 1, axis=1)
                sub = caches._replace(
                    k=row(caches.k), v=row(caches.v),
                    k_scale=(None if caches.k_scale is None
                             else row(caches.k_scale)),
                    v_scale=(None if caches.v_scale is None
                             else row(caches.v_scale)))
                view = kvp.insert_prefill(view, sub, slots[i], plens[i])
            bkv0 = pps.wave_scatter(bkv0, jnp.int32(0), view)
            return bkv0, x

        def _make_pre_tail(si):
            lo = si * Ls
            is_last = si == S_pp - 1

            def _pre_i(params_i, bkv_i, x, plens, slots, lora_i, aidxs):
                adapters = (lora_i, aidxs) if adapters_on else None
                B = x.shape[0]
                caches = pps.stage_kv(pool.make_prefill_caches(B),
                                      S_pp, si)
                x2, caches = pps.stage_forward(params_i, x, cfg,
                                               rope=rope,
                                               kv_caches=caches,
                                               layer_offset=lo,
                                               adapters=adapters)
                view = pps.wave_view(bkv_i, jnp.int32(0), S)
                for i in range(B):
                    def row(t):
                        return jax.lax.dynamic_slice_in_dim(t, i, 1,
                                                            axis=1)
                    sub = caches._replace(
                        k=row(caches.k), v=row(caches.v),
                        k_scale=(None if caches.k_scale is None
                                 else row(caches.k_scale)),
                        v_scale=(None if caches.v_scale is None
                                 else row(caches.v_scale)))
                    view = kvp.insert_prefill(view, sub, slots[i],
                                              plens[i])
                bkv_i = pps.wave_scatter(bkv_i, jnp.int32(0), view)
                if is_last:
                    # the head on each row's last real position alone
                    x2 = jnp.take_along_axis(
                        x2, (plens - 1)[:, None, None], axis=1)
                    logits = pps.stage_head(params_i, x2, cfg,
                                            logits_dtype=jnp.float32)
                    return bkv_i, logits[:, 0]
                return bkv_i, x2
            return _pre_i

        def _pre_act0(params0, last_logits, rngs, lasts, slots, rng0s):
            B = lasts.shape[0]
            for i in range(B):
                last_logits = last_logits.at[slots[i]].set(lasts[i])
                rngs = rngs.at[slots[i]].set(rng0s[i])
            return last_logits, rngs

        self._pp_pre = [_stage_jit(0, _pre0, 6, (1,))] + [
            _stage_jit(i, _make_pre_tail(i), 6, (1,))
            for i in range(1, S_pp)]
        self._pp_pre_act = _stage_jit(0, _pre_act0, 5, (1, 2))

        def _prefill_pp(params_u, pools, last_logits, rngs, tokens,
                        plens, slots, rng0s, lora_u, aidxs):
            lora_st = self._pp_stage_lora()
            new_pools = list(pools)
            new_pools[0], x = self._pp_pre[0](
                self._p_dec[0], new_pools[0], tokens, plens, slots,
                lora_st[0], aidxs)
            for i in range(1, S_pp):
                new_pools[i], x = self._pp_pre[i](
                    self._p_dec[i], new_pools[i], self._pp_put(x, i),
                    self._pp_put(plens, i), self._pp_put(slots, i),
                    lora_st[i], self._pp_put(aidxs, i))
            last_logits, rngs = self._pp_pre_act(
                self._p_dec[0], last_logits, rngs, self._pp_put(x, 0),
                slots, rng0s)
            return new_pools, last_logits, rngs

        self._prefill = _prefill_pp

        # ---- chunked-prefill chain (st.sub is a stage-indexed list) --
        def _chunk0(params0, sub0, tokens, next_offset, lora0, aidx1):
            self._chunk_traces += 1
            adapters = (lora0, aidx1) if adapters_on else None
            x = pps.embed_tokens(params0, tokens, cfg,
                                 offset=sub0.offset[0])
            x, sub0 = pps.stage_forward(params0, x, cfg, rope=rope,
                                        kv_caches=sub0, layer_offset=0,
                                        adapters=adapters)
            sub0 = sub0._replace(
                offset=jnp.full_like(sub0.offset, next_offset))
            return sub0, x

        def _make_chunk_tail(si):
            lo = si * Ls
            is_last = si == S_pp - 1

            def _chunk_mid(params_i, sub_i, x, next_offset, lora_i,
                           aidx1):
                adapters = (lora_i, aidx1) if adapters_on else None
                x, sub_i = pps.stage_forward(params_i, x, cfg,
                                             rope=rope, kv_caches=sub_i,
                                             layer_offset=lo,
                                             adapters=adapters)
                sub_i = sub_i._replace(
                    offset=jnp.full_like(sub_i.offset, next_offset))
                return sub_i, x

            def _chunk_last(params_i, sub_i, x, next_offset, last_idx,
                            lora_i, aidx1):
                adapters = (lora_i, aidx1) if adapters_on else None
                x, sub_i = pps.stage_forward(params_i, x, cfg,
                                             rope=rope, kv_caches=sub_i,
                                             layer_offset=lo,
                                             adapters=adapters)
                sub_i = sub_i._replace(
                    offset=jnp.full_like(sub_i.offset, next_offset))
                x = jax.lax.dynamic_slice_in_dim(x, last_idx, 1, axis=1)
                logits = pps.stage_head(params_i, x, cfg,
                                        logits_dtype=jnp.float32)
                return sub_i, logits[0, 0]
            return _chunk_last if is_last else _chunk_mid

        # `sub` is deliberately NOT donated across the chunk chain —
        # the same CPU jax 0.4.x aliasing rule as the mono _chunk_fwd
        self._pp_chunk = [_stage_jit(0, _chunk0, 5)] + [
            _stage_jit(i, _make_chunk_tail(i),
                       6 if i == S_pp - 1 else 5)
            for i in range(1, S_pp)]

        def _chunk_pp(params_u, subs, tokens, last_idx, next_offset,
                      lora_u, aidx1):
            lora_st = self._pp_stage_lora()
            new_subs = list(subs)
            new_subs[0], x = self._pp_chunk[0](
                self._p_dec[0], new_subs[0], tokens, next_offset,
                lora_st[0], aidx1)
            for i in range(1, S_pp - 1):
                new_subs[i], x = self._pp_chunk[i](
                    self._p_dec[i], new_subs[i], self._pp_put(x, i),
                    self._pp_put(next_offset, i), lora_st[i],
                    self._pp_put(aidx1, i))
            li = S_pp - 1
            new_subs[li], last = self._pp_chunk[li](
                self._p_dec[li], new_subs[li], self._pp_put(x, li),
                self._pp_put(next_offset, li),
                self._pp_put(last_idx, li), lora_st[li],
                self._pp_put(aidx1, li))
            return new_subs, self._pp_put(last, 0)

        self._chunk_fwd = _chunk_pp

        # ---- block slice / insert chains -----------------------------
        def _slice_i(params_i, bkv_i, blocks, start):
            return kvp.slice_blocks(bkv_i, blocks, start)

        def _ins0(params0, bkv0, last_logits, rngs, sub0, slot, plen,
                  pfx_blocks, last, rng0):
            bkv0 = kvp.insert_blocks(bkv0, sub0, slot, plen, pfx_blocks)
            last_logits = last_logits.at[slot].set(last)
            rngs = rngs.at[slot].set(rng0)
            return bkv0, last_logits, rngs

        def _ins_i(params_i, bkv_i, sub_i, slot, plen, pfx_blocks):
            return kvp.insert_blocks(bkv_i, sub_i, slot, plen,
                                     pfx_blocks)

        self._pp_slice = [_stage_jit(i, _slice_i, 3)
                          for i in range(S_pp)]
        self._pp_ins = [_stage_jit(0, _ins0, 9, (1, 2, 3))] + [
            _stage_jit(i, _ins_i, 5, (1,)) for i in range(1, S_pp)]

        def _slice_blk_pp(params_u, pools, blocks, start):
            return [self._pp_slice[i](self._p_dec[i], pools[i],
                                      self._pp_put(blocks, i),
                                      self._pp_put(start, i))
                    for i in range(S_pp)]

        def _insert_blk_pp(params_u, pools, last_logits, rngs, subs,
                           slot, plen, pfx_blocks, last, rng0):
            new_pools = list(pools)
            new_pools[0], last_logits, rngs = self._pp_ins[0](
                self._p_dec[0], new_pools[0], last_logits, rngs,
                subs[0], slot, plen, pfx_blocks, last, rng0)
            for i in range(1, S_pp):
                new_pools[i] = self._pp_ins[i](
                    self._p_dec[i], new_pools[i], subs[i],
                    self._pp_put(slot, i), self._pp_put(plen, i),
                    self._pp_put(pfx_blocks, i))
            return new_pools, last_logits, rngs

        self._slice_blk = _slice_blk_pp
        self._insert_blk = _insert_blk_pp

        # unreachable under serving_pp (blocks are REQUIRED, so the
        # whole-region slice/insert never dispatch; disaggregation and
        # the host tier are rejected by validate, which the
        # constructor calls) — None so an accidental dispatch fails loudly
        self._slice = None
        self._insert = None
        self._handoff_insert = None
        self._pad_sub_pre = None

    def _apply_placement(self, plan, params):
        """Re-mesh the engine under `plan` and place `params` (the
        just-staged host tree) on the new meshes — ONLY ever called
        from the quiesced swap barrier (_apply_swap: no active slots,
        no pending prefills, admissions held). Build order keeps the
        refusal property: the new topology and both weight placements
        are staged into LOCALS first, so a device failure leaves every
        live ref (old topology, old programs, old weights) untouched
        and the swap refuses typed. After the commit point the KV
        arena reshards value-preservingly (device_put re-lays the
        kv-head axis out for the new decode width — retained prefixes
        and the block map survive verbatim), the adapter bank
        re-commits per group, and the per-phase programs rebuild: the
        recompile bill is paid HERE, at the barrier, never mid-serve."""
        import dataclasses
        from megatron_tpu.serving.topology import ServingTopology
        planned = dataclasses.replace(self.serving,
                                      prefill_tp=plan.prefill_tp,
                                      decode_tp=plan.decode_tp)
        topo = ServingTopology(planned, devices=self._device_window)
        p_dec, psh_dec = topo.place_params(params, self.cfg,
                                           topo.decode_mesh)
        if topo.disaggregated:
            p_pre, psh_pre = topo.place_params(params, self.cfg,
                                               topo.prefill_mesh)
        else:
            p_pre, psh_pre = p_dec, psh_dec
        jax.block_until_ready(p_dec)
        if p_pre is not p_dec:
            jax.block_until_ready(p_pre)
        # COMMIT POINT — flip the topology and every placement with it
        self._placement_plan = plan
        self.topo = topo
        self._disagg = topo.disaggregated
        self._p_dec, self._psh_dec = p_dec, psh_dec
        self._p_pre, self._psh_pre = p_pre, psh_pre
        topo.place_pool(self.pool)
        if self.adapters is not None:
            self.adapters.reshard(
                topo.adapter_shardings(),
                topo.adapter_shardings(topo.prefill_mesh)
                if topo.disaggregated else None)
        self._sub0 = None  # zero template re-commits on the new mesh
        # the per-slot device state chains through the old programs'
        # outputs, so it sits COMMITTED on the old decode mesh — mixing
        # it into the new programs is a device-mismatch error. The grid
        # is quiet (every slot idle), so the values are the idle
        # defaults plus sampling knobs: re-place them on the new mesh.
        rep = topo.replicated(topo.decode_mesh)
        for name in ("_last_logits", "_rngs", "_d_lengths", "_d_temps",
                     "_d_top_ks", "_d_top_ps", "_d_reject",
                     "_d_adapter_idx", "_d_masks"):
            setattr(self, name,
                    jax.device_put(getattr(self, name), rep))
        # queued preemption victims hold parked sub-caches committed to
        # the OLD mesh: drop the refs — they resume via the replay
        # fallback (re-prefill from the effective prompt), which is
        # token-exact by construction
        self.scheduler.clear_parked()
        self._compile_programs(*self._jit_factories())
        d = topo.describe()
        self.metrics.set_topology_gauges(
            d["prefill_tp"], d["decode_tp"],
            d["prefill_devices"], d["decode_devices"])
        self.metrics.count("placement_replans")
        print_rank_0(
            "serving engine: placement re-planned to "
            f"prefill_tp={plan.prefill_tp} decode_tp={plan.decode_tp} "
            f"({plan.reason}) at the upgrade drain barrier")

    # ------------------------------------------------------------------
    # device programs
    # ------------------------------------------------------------------
    def _decode_fn(self, params, pool, last_logits, rngs, lengths,
                   temps, top_ks, top_ps, rejects, masks, lora, aidx):
        """ONE interleaved decode step for the whole slot grid: sample
        each slot's next token from its carried logits, then forward all
        slots' tokens (s=1) through the model with per-slot positions.
        Inactive slots ride along too (static shapes): hard-freed rows
        park at length 0 (their position-0 write is overwritten by the
        next prefill insert), while prefix-retained rows park at their
        FINAL length so the garbage writes land past every cloneable
        prefix instead of clobbering the retained KV (see _evict).

        `lengths` is the DEVICE copy of the per-slot positions and is
        returned incremented, so K chained calls advance positions
        without a host round-trip (decode_sync_interval). The clamp at
        max_len-1 only ever binds for rows idling past their eviction
        inside a window — admission guarantees a live row never needs a
        position past max_len-1 — and keeps their rope/cache indices in
        bounds until the boundary re-upload re-parks them.

        `rejects` is the speculative residual carry: when a
        speculative window's last verify round ended in a stochastic
        rejection, the next sample for that slot must draw from the
        residual distribution — the processed distribution with the
        rejected draft masked out — so a plain decode step dispatched
        after it (drafter came up empty → spec_fallback_steps) applies
        the ban and returns it CLEARED. Non-speculative engines always
        pass all -1, which is bit-identical to the pre-speculative
        step (sample_batched's banned<0 contract).

        `masks` is the grammar seam ([S, Vp] bool): each structured
        row's FSM-legal vocabulary for its NEXT token, applied by
        sample_batched after banned at the post-temp/top-k/top-p
        point (serving/structured.py). Free rows carry all-True rows
        — bit-identical to no mask — so one grid, one trace serves
        mixed traffic. A dead-end row (all-False) samples the -1
        sentinel; the host evicts it typed (GrammarDeadEndError)
        before the token is ever consumed, and the s=1 forward of the
        sentinel below is harmless garbage into a row about to be
        freed.

        Block-granular pools pass a BlockKV here: the per-slot block
        map resolves into the contiguous slot-grid view at the top and
        the updated view scatters back at the bottom — pure data
        movement bracketing the identical program, so outputs are
        bit-identical with blocks on vs off and the trace count stays
        one (block indices are data). With `block_native_attn` the
        bracket DISAPPEARS instead: the forward consumes a
        BlockKVCache (arena + map) and the Pallas block kernel walks
        each slot's chain in place — same outputs, zero full-pool
        gather/scatter traffic.

        `lora`/`aidx` are the adapter bank's stacked factors and the
        per-slot bank rows (serving/adapters.py): the forward adds each
        row's low-rank delta to the q/k/v/o projections — indices are
        DATA like the block map, one trace. Both are None (empty
        pytrees) with adapters off, which lowers to today's graph."""
        self._decode_traces += 1
        adapters = (lora, aidx) if self._adapters_on else None
        bkv = None
        if self._kernel_on:
            bkv, pool = pool, block_native_cache(pool)
        elif self._blocks_on:
            bkv, pool = pool, resolve_view(pool)
        cfg = self.cfg
        split = jax.vmap(jax.random.split)(rngs)  # [S, 2, 2]
        new_rngs, step_keys = split[:, 0], split[:, 1]
        toks = sample_batched(step_keys, last_logits,
                              temperature=temps, top_k=top_ks,
                              top_p=top_ps, vocab_size=cfg.vocab_size,
                              banned=rejects, mask=masks)
        # logprob of the chosen token under the RAW carried logits —
        # the serial path's convention (generation.py _decode_fn)
        lp = jax.nn.log_softmax(last_logits, axis=-1)
        tok_lp = jnp.take_along_axis(lp, toks[:, None], axis=-1)[:, 0]
        # `lengths` is the source of truth for every row's position;
        # broadcast them over layers into the pool
        L = pool.offset.shape[0]
        pool = pool._replace(offset=jnp.broadcast_to(
            lengths[None, :], (L, lengths.shape[0])).astype(jnp.int32))
        logits, pool = lm.model_forward(
            params, toks[:, None], cfg, kv_caches=pool,
            position_ids=lengths[:, None], rope=self._rope,
            logits_dtype=jnp.float32, adapters=adapters)
        new_lengths = jnp.minimum(lengths + 1,
                                  jnp.int32(self.max_len - 1))
        if bkv is not None:
            pool = (pack_block_native(pool, bkv.map) if self._kernel_on
                    else scatter_view(bkv, pool))
        return (pool, logits[:, 0], new_rngs, toks, tok_lp, new_lengths,
                jnp.full_like(rejects, -1))

    def _verify_fn(self, params, pool, last_logits, rngs, lengths,
                   temps, top_ks, top_ps, drafts, rejects, t0_masks,
                   draft_masks, guess0, lora, aidx):
        """ONE speculative draft/verify round for the whole slot grid
        (`speculative_k`): sample each slot's next token t0 from its
        carried logits (the residual distribution when `rejects` bans
        last round's rejected draft), forward [t0, d_1..d_k] — all
        slots, one [S, k+1] dispatch — through the pool at per-slot
        vector offsets (generation.verify_tokens), then accept each
        slot's drafts left-to-right: exact-match vs the argmax for
        greedy rows, u < p_processed(d) point-mass rejection sampling
        for stochastic rows (verify_draft_probs — the SAME
        temperature/top-k/top-p pipeline sample_batched draws from),
        each draft position consuming its own folded PRNG key.

        Commits per slot = 1 + accepted in [1, k+1]: t0 plus the
        accepted draft prefix. The all-accept bonus and the rejection
        correction are NOT committed in-round — the carried logits
        become the row at the last committed token, so the next round's
        t0 IS that token, sampled through the engine's one invariant
        (carried logits = distribution for the next token) with the
        residual ban applied on a real rejection. Lengths advance by
        1+a — the cache offset REWINDS below the k+1 writes, and
        rejected-position KV is overwritten write-before-read by the
        next dispatch (the bucketed-prefill invariant). The accept
        mask is ANDed with a capacity clamp (draft j's write must land
        at <= max_len-1), so finishing/idle rows never commit past the
        region and the returned lengths clamp like the decode step's.

        Returns (pool, new_last_logits, new_rngs, window [S, k+1],
        window_logprobs [S, k+1], accepted [S], new_lengths,
        new_rejects) — the host consumes 1+accepted tokens per live
        row and discards the rest.

        `lora`/`aidx`: per-slot adapter deltas (see _decode_fn) — the
        verify window forwards under each row's OWN adapter, so
        speculative decoding composes with multi-tenant serving at one
        trace.

        Grammar seam (serving/structured.py): `t0_masks` [S, Vp] is
        each row's FSM-legal vocabulary for t0 (all-True for free
        rows), `draft_masks` [S, k, Vp] the per-position legal sets
        the HOST pre-walked along [guess0, d_1..d_k] (all-True for
        free rows), and `guess0` [S] the drafter's host-known guess
        for t0 (-1 = no guess / free row). The masks for positions
        1..k are only valid if the device's t0 equals the guess the
        host stepped its FSM with, so acceptance is gated on
        toks0 == guess0 for rows carrying a real guess — a wrong
        guess rejects the round's drafts (misalignment costs
        acceptance, never correctness — the contract chained rounds
        already have). verify_draft_probs zeroes illegal drafts'
        target probabilities under draft_masks, so an FSM-illegal
        draft can never be accepted; a gate rejection is NOT a
        stochastic rejection, so it never sets the residual carry."""
        self._verify_traces += 1
        adapters = (lora, aidx) if self._adapters_on else None
        bkv = None
        if self._kernel_on:
            # block-native verify: the [S, k+1] window forwards
            # through the SAME Pallas block kernel as decode (causal
            # within the window) — speculative decoding keeps one
            # trace and drops the bracket too
            bkv, pool = pool, block_native_cache(pool)
        elif self._blocks_on:
            bkv, pool = pool, resolve_view(pool)
        cfg = self.cfg
        k = drafts.shape[1]
        split = jax.vmap(jax.random.split)(rngs)  # [S, 2, 2]
        new_rngs, step_keys = split[:, 0], split[:, 1]
        # t0 consumes the SAME split key the plain decode step would,
        # and the accept uniforms FOLD off it (positions 1..k) without
        # advancing the chain — so a slot whose drafts are all filler
        # commits exactly the token a decode step would have, and a
        # request's stream never depends on what OTHER slots proposed
        toks0 = sample_batched(step_keys, last_logits,
                               temperature=temps, top_k=top_ks,
                               top_p=top_ps, vocab_size=cfg.vocab_size,
                               banned=rejects, mask=t0_masks)
        # logprob under the RAW carried logits — the serial convention
        # (_decode_fn); for a residual-resampled t0 this reports the
        # full-distribution logprob (observability only)
        lp0 = jax.nn.log_softmax(last_logits, axis=-1)
        lp0 = jnp.take_along_axis(lp0, toks0[:, None], axis=-1)[:, 0]
        window = jnp.concatenate([toks0[:, None], drafts], axis=1)
        logits, pool = verify_tokens(params, window, pool, cfg,
                                     rope=self._rope,
                                     lengths=lengths,
                                     max_len=self.max_len,
                                     adapters=adapters)
        # logits[:, j] = the model's distribution for the token AFTER
        # window position j — drafts[:, j] claims to be that token
        ctx = logits[:, :k]
        probs, targets = verify_draft_probs(
            ctx, drafts, temperature=temps, top_k=top_ks, top_p=top_ps,
            vocab_size=cfg.vocab_size, mask=draft_masks)

        def row_unifs(rk):
            return jax.vmap(lambda i: jax.random.uniform(
                jax.random.fold_in(rk, i)))(jnp.arange(1, k + 1))

        u = jax.vmap(row_unifs)(step_keys)  # [S, k]
        greedy_rows = (temps == 0.0) | (top_ks == 1)
        accept = jnp.where(greedy_rows[:, None], drafts == targets,
                           u < probs)
        # filler positions (NO_DRAFT = -1: inactive row, empty or
        # short proposal) are never accepted — and never counted as a
        # stochastic rejection below
        accept = accept & (drafts >= 0)
        # grammar gate: rows with a real host guess for t0 only keep
        # their drafts when the device sampled that guess — otherwise
        # the host-walked draft_masks were stepped from the wrong
        # state and nothing downstream of them is trustworthy
        gate_ok = (guess0 < 0) | (toks0 == guess0)
        accept = accept & gate_ok[:, None]
        # capacity clamp: draft j commits at position lengths+1+j and
        # its logits need every window write up to lengths+j in-region
        allow = (lengths[:, None] + 1 + jnp.arange(k)[None, :]
                 <= jnp.int32(self.max_len - 1))
        acc = (accept & allow).astype(jnp.int32)
        a = jnp.sum(jnp.cumprod(acc, axis=1), axis=1)  # [S] in [0, k]
        lp = jax.nn.log_softmax(ctx, axis=-1)
        draft_lp = jnp.take_along_axis(
            lp, drafts[..., None], axis=-1)[..., 0]
        tok_lp = jnp.concatenate([lp0[:, None], draft_lp], axis=1)
        # carried logits = distribution after the LAST committed token
        new_last = jnp.take_along_axis(
            logits, a[:, None, None],
            axis=1)[:, 0].astype(last_logits.dtype)
        # residual carry: only a REAL stochastic rejection at the stop
        # position bans its draft from the next t0 sample — a filler
        # stop, a capacity stop, or an all-accept round carries nothing
        # (and greedy rows' ban is inert by construction: rejection
        # means the banned draft was not the argmax)
        a_idx = jnp.clip(a, 0, k - 1)
        d_stop = jnp.take_along_axis(drafts, a_idx[:, None],
                                     axis=1)[:, 0]
        allow_stop = jnp.take_along_axis(allow, a_idx[:, None],
                                         axis=1)[:, 0]
        # ... and a grammar-gate rejection is NOT a stochastic
        # rejection: banning the stop draft after one would skew the
        # next t0's residual vs the serial masked oracle
        new_rejects = jnp.where(gate_ok & (a < k) & allow_stop
                                & (d_stop >= 0),
                                d_stop,
                                jnp.int32(-1)).astype(jnp.int32)
        new_lengths = jnp.minimum(lengths + 1 + a,
                                  jnp.int32(self.max_len - 1))
        if bkv is not None:
            pool = (pack_block_native(pool, bkv.map) if self._kernel_on
                    else scatter_view(bkv, pool))
        return (pool, new_last, new_rngs, window, tok_lp, a,
                new_lengths, new_rejects)

    def _prefill_fn(self, params, pool, last_logits, rngs, tokens,
                    plens, slots, rng0s, lora, aidxs):
        """Batched prefill: B prompts (same padded bucket) forward in
        ONE call — the weight stream is paid once per batch instead of
        once per request — then each row's KV inserts into its slot.
        Row results are independent (per-row causal attention), so a
        B>1 prefill is the B=1 prefill done B times. Duplicate rows
        (the batch-bucket pads replicate row 0) rewrite the same slot
        with identical values — idempotent by construction.

        With `block_native_attn` the rows land through per-row
        `insert_blocks` (the group's map rows were installed at
        admission; fresh misses, so pfx_blocks = 0) — same written
        bytes, no resolve/scatter bracket.

        `aidxs` [B]: per-ROW adapter bank rows — mixed-adapter
        admissions batch into ONE prefill call (indices are data), so
        adapter diversity never fragments the prefill coalescing."""
        self._prefill_traces += 1
        adapters = (lora, aidxs) if self._adapters_on else None
        bkv = None
        if self._blocks_on and not self._kernel_on:
            bkv, pool = pool, resolve_view(pool)
        B = tokens.shape[0]
        caches = self.pool.make_prefill_caches(B)
        if self.pool.hybrid:
            # a ring takes no padding row (attention.HybridKVCache)
            caches = caches._replace(live_end=plens)
        elif self.pool.conv_layers:
            # a state is left as it stood after each row's own last real
            # token, not after the bucket's padding (attention.ConvKVCache)
            caches = caches._replace(live_rows=plens)
        # the head runs on each row's LAST REAL prompt position alone
        # (bucket pads sit after it and are causally invisible to it)
        logits, caches = lm.model_forward(
            params, tokens, self.cfg, kv_caches=caches,
            rope=self._rope, logits_dtype=jnp.float32,
            adapters=adapters, logits_rows=plens - 1)
        for i in range(B):  # static unroll: B is a trace-time shape
            sub = batch_row(caches, i)
            if self._kernel_on:
                pool = insert_blocks(pool, sub, slots[i], plens[i],
                                     jnp.int32(0))
            else:
                pool = insert_prefill(pool, sub, slots[i], plens[i])
            last_logits = last_logits.at[slots[i]].set(logits[i, 0])
            rngs = rngs.at[slots[i]].set(rng0s[i])
        if bkv is not None:
            pool = scatter_view(bkv, pool)
        return pool, last_logits, rngs

    def _slice_fn(self, params, pool, slot, start):
        """Read `slot`'s region as a batch-1 cache positioned at
        `start` — the prefix-clone read (start = matched prefix
        length; misses start from the shared zero template instead).
        `params` rides along unused so the mesh-aware jit treatment
        applies uniformly (jit drops unused args at lowering)."""
        return slice_slot(pool, slot, start)

    def _slice_blocks_fn(self, params, pool, blocks, start):
        """Block-mode region read: gather an explicit physical-block
        list (a row's map, or a row-less RetainedPrefix's blocks) into
        a batch-1 cache at `start`. Block indices are data — one
        compile serves every source."""
        return slice_blocks(pool, blocks, start)

    def _chunk_fwd_fn(self, params, sub, tokens, last_idx, next_offset,
                      lora, aidx1):
        """Append one [1, s] prompt chunk at `sub`'s current offset
        (generation.prefill_chunk: decode masking generalized to
        q-len > 1). Retraces once per padded chunk length — the same
        bucket set as the monolithic prefill. `aidx1` [1] is the
        pending request's adapter bank row (data — chunked prefills
        under any adapter share the compile)."""
        self._chunk_traces += 1
        adapters = (lora, aidx1) if self._adapters_on else None
        return prefill_chunk(params, tokens, sub, self.cfg,
                             rope=self._rope, last_idx=last_idx,
                             next_offset=next_offset, adapters=adapters)

    def _insert_fn(self, params, pool, last_logits, rngs, sub, slot,
                   plen, last, rng0):
        """Land a completed prefill: the sub-cache's whole region
        writes into `slot` with the first `plen` tokens live (the
        write half of kv_pool.clone_prefix, fused with the slot's
        last-logits/rng activation)."""
        pool = insert_prefill(pool, sub, slot, plen)
        last_logits = last_logits.at[slot].set(last)
        rngs = rngs.at[slot].set(rng0)
        return pool, last_logits, rngs

    def _insert_blocks_fn(self, params, pool, last_logits, rngs, sub,
                          slot, plen, pfx_blocks, last, rng0):
        """Block-mode landing: write the sub through `slot`'s (freshly
        installed) map row, skipping the first `pfx_blocks` ALIASED
        prefix blocks — their content is already in the arena and
        shared with other holders (kv_pool.insert_blocks redirects
        those writes to the trash block)."""
        pool = insert_blocks(pool, sub, slot, plen, pfx_blocks)
        last_logits = last_logits.at[slot].set(last)
        rngs = rngs.at[slot].set(rng0)
        return pool, last_logits, rngs

    @staticmethod
    def _widen_sub(sub, cap: int):
        """Zero-pad a block-truncated batch-1 cache ([L, 1, n*B, ...])
        back to the full region cap — positions past the live tokens
        are garbage the causal mask never reads and appends overwrite
        write-before-read (the bucketed-prefill invariant). int8
        scales pad with 1.0 (a zero scale would NaN a dequantized
        garbage read's softmax). Traced helper: one compile per
        live-block count, bounded by blocks_per_slot."""
        n = sub.k.shape[2]
        pad = ((0, 0), (0, 0), (0, cap - n), (0, 0), (0, 0))
        return sub._replace(
            k=jnp.pad(sub.k, pad), v=jnp.pad(sub.v, pad),
            k_scale=(None if sub.k_scale is None
                     else jnp.pad(sub.k_scale, pad,
                                  constant_values=1.0)),
            v_scale=(None if sub.v_scale is None
                     else jnp.pad(sub.v_scale, pad,
                                  constant_values=1.0)))

    def _handoff_insert_fn(self, params, pool, last_logits, rngs, sub,
                           slot, plen, last, rng0):
        """Disaggregated handoff landing (decode group): `sub` holds
        ONLY the sequence's ceil(plen/B) live blocks, transferred from
        the prefill group — widen to the region cap with zeros and
        land through the slot's freshly-installed map row (pfx 0: a
        disaggregated admission never aliases, its content arrived
        from the other chip group). Fused with the slot activation
        like _insert_blocks_fn."""
        pool = insert_blocks(pool, self._widen_sub(sub, self.pool.cap),
                             slot, plen, jnp.int32(0))
        last_logits = last_logits.at[slot].set(last)
        rngs = rngs.at[slot].set(rng0)
        return pool, last_logits, rngs

    def _pad_sub_pre_fn(self, params, sub, plen):
        """Prefill-group widening of a transferred prefix: the
        decode-side hit sliced down to its live blocks rides over as
        [L, 1, nb*B, ...]; suffix chunks need the full-cap batch-1
        layout at offset `plen`. `params` rides along unused so the
        prefill mesh treatment applies uniformly (jit drops unused
        args at lowering)."""
        sub = self._widen_sub(sub, self.pool.cap)
        return sub._replace(offset=jnp.full_like(sub.offset, plen))

    @staticmethod
    def _truncate_sub(sub, ntok: int):
        """Host-side (eager) slice of a batch-1 cache down to its
        first `ntok` token positions — the only bytes a cross-group
        transfer moves (never a cap region)."""
        return sub._replace(
            k=sub.k[:, :, :ntok], v=sub.v[:, :, :ntok],
            k_scale=(None if sub.k_scale is None
                     else sub.k_scale[:, :, :ntok]),
            v_scale=(None if sub.v_scale is None
                     else sub.v_scale[:, :, :ntok]))

    def _prefill_bucket(self, plen: int) -> int:
        """Pad prompts up to a bucket so the prefill jit cache hits
        across request sizes. ROLLING pools prefill at the exact length:
        pad positions fed through the ring would evict real tokens from
        the W-slot buffer."""
        if self.pool.rolling:
            return plen
        b = max(self.serving.prefill_bucket, 1)
        return min(-(-plen // b) * b, self.max_len)

    @staticmethod
    def _batch_bucket(n: int) -> int:
        """Round a prefill batch up to a power of two so the jit cache
        holds O(log slots) entries per length bucket, not one per
        arrival-burst size."""
        b = 1
        while b < n:
            b *= 2
        return b

    @staticmethod
    def _rng_burn(plen):
        """Splits the SERIAL path spends on a `plen`-token prompt
        before its first generated token: Generator.generate rounds the
        prefill down to a PREFILL_BUCKET multiple and consumes the
        remaining prompt tokens through decode steps, splitting once
        per step. One length or an array of them."""
        return plen - np.maximum(
            (plen // PREFILL_BUCKET) * PREFILL_BUCKET, 1)

    @staticmethod
    def _initial_rng(seed: int, plen: int):
        """Per-request key, advanced past the serial path's in-prompt
        splits (`_rng_burn`) — so a seeded engine request reproduces
        the serial output bit-for-bit from the first generated token.
        Made by ONE compiled call (`_burned_key`), never by an eager
        `jax.random.split` per burned step; the one-row form of
        `_initial_rngs`."""
        # np.int64 is what PRNGKey makes of a Python int seed
        return _burned_key_jit(np.int64(seed),
                               np.int32(ServingEngine._rng_burn(plen)))

    @staticmethod
    def _initial_rngs(seeds: Sequence[int], plens: Sequence[int]):
        """`_initial_rng` for a whole prefill group: keys[B, 2] from
        one compiled call per batch bucket."""
        return _burned_keys_jit(
            np.asarray(seeds, np.int64),
            ServingEngine._rng_burn(np.asarray(plens, np.int32)))

    # ------------------------------------------------------------------
    # engine loop (single thread)
    # ------------------------------------------------------------------
    def _wake(self):
        with self._cond:
            self._cond.notify_all()

    def _heartbeat(self):
        if self._watchdog is not None and self._watchdog.started:
            self._watchdog.heartbeat()

    def _loop(self):
        """Supervisor: run `_session` until clean exit; on a crashed or
        hung iteration, restart it (reset device state, fail only the
        slotted requests, requeue the rest) up to `max_engine_restarts`
        times, then trip the crash-loop circuit breaker."""
        blocks = (f", {self.pool.block_size}-token blocks"
                  if self._blocks_on else "")
        if self._kernel_on:
            blocks += ", block-native attn"
        print_rank_0(
            f"serving engine: {self.num_slots} slots x cap "
            f"{self.pool.cap} ({self.pool.dtype}"
            f"{', rolling' if self.pool.rolling else ''}{blocks}), "
            f"pool {self.pool.nbytes() / 2**20:.1f} MiB, "
            f"queue bound {self.serving.max_queue}")
        while True:
            try:
                if self._session():
                    return
            except Exception as e:  # noqa: BLE001 — supervise, not hang
                msg = repr(e)
                tb = traceback.format_exc()
                if self._restarts >= self._max_restarts:
                    print_rank_0(
                        f"serving engine: loop failed ({msg}); no "
                        f"restarts left\n{tb}")
                    self._trip_breaker(msg)
                    return
                self._restarts += 1
                self._last_restart_t = time.monotonic()
                self.metrics.count("engine_restarts")
                print_rank_0(
                    f"serving engine: loop failed ({msg}); restarting "
                    f"({self._restarts}/{self._max_restarts})\n{tb}")
                try:
                    # suspend the watchdog across the reset: in the
                    # CRASH path (unlike the hang path) it has not
                    # fired/latched, and a slow device-state rebuild
                    # must not trip it mid-restart — it would fail the
                    # very requests the restart is requeuing and leak
                    # _wedged into the fresh session
                    if self._watchdog is not None:
                        with self._watchdog.suspend():
                            self._restart_session(msg)
                    else:
                        self._restart_session(msg)
                except Exception as e2:  # noqa: BLE001
                    self._trip_breaker(
                        f"restart failed: {e2!r} (after {msg})")
                    return

    def _session(self) -> bool:
        """The engine loop proper. Returns True on clean exit (stop /
        drain complete); raises on a crashed or watchdog-flagged
        iteration — the supervisor decides what survives."""
        # what was queued before the loop ran (or went back to the queue
        # at a restart): its groups are known now
        self._hand_groups()
        while True:
            with self._cond:
                if self._nothing_to_do():
                    with span("serve/idle_wait"):
                        while self._nothing_to_do():
                            self._cond.wait(timeout=self._idle_wait)
                            self._heartbeat()  # idleness is not a hang
                            # the brownout ladder must step DOWN on an
                            # idle engine too — after a storm drains,
                            # the level reverts without needing new
                            # traffic to drive loop iterations (the
                            # monotone-revert law)
                            self._evaluate_degrade()
                if self._stop:
                    return True
                if (self._draining and not self._active.any()
                        and not self._prefilling):
                    # drained: queue closed, slots empty, no prefill
                    # in flight (a mid-chunk request is in-flight work
                    # and decodes to completion like a running slot)
                    return True
            if self._wedged:
                raise EngineHungError(
                    "engine iteration exceeded the watchdog deadline "
                    f"({self.serving.engine_step_timeout_s}s); "
                    "in-flight requests were failed by the watchdog")
            with span("serve/iteration", active=int(self._active.sum()),
                      queued=self.scheduler.depth()):
                self._iteration()

    def _nothing_to_do(self) -> bool:
        return (not self._stop and not self._draining
                and not self._wedged
                and self._pending_swap is None
                and self.scheduler.depth() == 0
                and not self._active.any()
                and not self._prefilling)

    def _iteration(self):
        """One pass of the engine loop's body: reap, admit (or apply a
        pending swap), one prefill chunk, one decode window (which may
        admit the next iteration's prefill program while it runs)."""
        early, self._early_program = self._early_program, None
        with span("serve/reap"):
            self._maybe_decay_restarts()
            self._reap_cancelled()
            self._reap_expired()
            # one brownout-ladder evaluation per iteration (each one
            # decode window apart — the dwell counts are calibrated in
            # these units)
            self._evaluate_degrade()
        if self._pending_swap is not None:
            # SWAP BARRIER (docs/serving.md "Live weights"): hold
            # NEW admissions — queued work simply WAITS, nothing is
            # rejected — while in-flight slots and pending prefills
            # run to completion under the CURRENT weights. Once the
            # grid is quiet the swap applies between iterations:
            # pre-swap admissions are pure version N, post-swap
            # admissions pure N+1 (the token-exactness pin).
            if not self._active.any() and not self._prefilling:
                with self._cond:
                    ticket = self._pending_swap
                    if ticket is not None:
                        ticket.taken = True
                        self._pending_swap = None
                if ticket is not None:
                    with span("serve/swap"):
                        self._apply_swap(ticket)
                self._heartbeat()
                return
        else:
            with span("serve/admit") as sp:
                self._preempt_for_priority()
                sp.set_metadata(popped=self._admit())
        # ONE chunk per iteration (Sarathi-Serve): prefill work
        # is interleaved with the decode step below, so running
        # slots keep emitting tokens while a long prompt lands
        if early is None:
            self._advance_prefill()
        else:
            # the program the last window admitted while it ran stands
            # in the chunk's place: a chunk owed by now (what `_admit`
            # just put into `_prefilling`) goes one window later, as it
            # would behind any other prompt's program. The span begins
            # where the device runs that program, between the last
            # window's commit and the next one's, which is where a
            # reader of step periods looks for it
            with span("serve/prefill", early=1, **early):
                pass
        self._heartbeat()  # admit/prefill may compile; decode is
        #                    the op the deadline protects
        if self._active.any():
            with span("serve/step",
                      active=int(self._active.sum())) as sp:
                sp.set_metadata(K=self._step())
        if self._watchdog is not None:
            if not self._watchdog.started:
                # arm only after a full iteration completed — the
                # first one includes the jit compiles, whose
                # duration is unrelated to steady-state health
                self._watchdog.start()
            else:
                self._watchdog.heartbeat()

    def _evaluate_degrade(self):
        """One brownout-ladder evaluation (engine thread only — the
        controller is single-writer; HTTP submit threads read the
        plain-int level lock-free). Transitions count
        `degrade_transitions` and push the `degrade_level` gauge, so
        the ladder's walk is fully reconstructible from /metrics."""
        if self.degrade is None:
            return
        before = self.degrade.level
        after = self.degrade.observe(
            self.scheduler.depth(),
            int(self._active.sum()) + len(self._prefilling),
            self.num_slots)
        if after != before:
            self.metrics.count("degrade_transitions")
            self.metrics.set_degrade_gauge(after)
            print_rank_0(
                f"serving engine: brownout level {before} -> {after} "
                f"(pressure {self.degrade._last_pressure:.2f}, "
                f"queue {self.scheduler.depth()})")

    # ------------------------------------------------------------------
    # supervisor: hang detection, restart, circuit breaker
    # ------------------------------------------------------------------
    def _maybe_decay_restarts(self):
        """Forget consumed restarts after RESTART_DECAY_S of healthy
        operation: a crash LOOP re-crashes within moments, so isolated
        recovered faults spread over a long-lived replica's lifetime
        must not accumulate into a tripped breaker. (The cumulative
        `engine_restarts` metric is unaffected.)"""
        if self._restarts and self._last_restart_t is not None and \
                time.monotonic() - self._last_restart_t \
                > self.RESTART_DECAY_S:
            print_rank_0(
                f"serving engine: {self._restarts} restart(s) aged out "
                f"(> {self.RESTART_DECAY_S:.0f}s healthy); crash-loop "
                "budget reset")
            self._restarts = 0
            self._last_restart_t = None

    def _on_hang(self):
        """Watchdog thread: the engine loop made no progress within the
        deadline. Fail every in-flight future NOW (their device state
        is suspect and the engine thread is stuck — waiting would
        strand them), flag the session wedged, and let the supervisor
        restart the loop when (if) the wedged dispatch returns. Queued
        requests are untouched: they are host-side and will be served
        after the restart (or expire against their deadlines)."""
        self._wedged = True
        msg = (f"engine hung: no decode-loop progress within "
               f"{self.serving.engine_step_timeout_s:.1f}s (watchdog); "
               "request failed, engine restarting")
        print_rank_0("serving " + msg)
        for req in list(self._slot_req):
            if req is not None:
                req.fail(msg)
        for st in list(self._prefilling):
            st.req.fail(msg)
        # pops wedged mid-_admit (e.g. inside a batched group-prefill
        # dispatch) are in neither list above — without this they
        # would strand if the dispatch never returns
        for req in list(self._admitting):
            req.fail(msg)
        self._wake()

    def _trip_breaker(self, msg: str):
        """Crash-loop circuit breaker: more restarts than
        `max_engine_restarts`. The engine goes (and stays) unhealthy —
        every in-flight and queued future resolves with a typed error,
        submits raise EngineUnhealthyError (HTTP 503), `/healthz`
        reports unhealthy."""
        self._broken = (f"circuit breaker open after "
                        f"{self._restarts} restart(s): {msg}")
        print_rank_0(f"serving engine: {self._broken}")
        self._fail_pending_swap(self._broken)
        for req in self._slot_req:
            if req is not None:
                req.fail(self._broken)
        for st in self._prefilling:
            st.req.fail(self._broken)
        for req in self.scheduler.close():
            req.fail(self._broken, kind="unavailable")

    def _restart_session(self, msg: str):
        """Reset after a crashed/hung iteration. The device-side state
        (pool, logits, rng grids — possibly donated into the failed
        call) is rebuilt from scratch; the compiled programs are kept,
        so no retrace. Slotted requests FAIL (their generated stream
        depended on state we can no longer trust); mid-prefill and
        queued requests REQUEUE losslessly (nothing irrecoverable lives
        on device for them — a replay recomputes their KV, and a
        preempted request's resume_rng is host-side). Parked preemption
        buffers are dropped for the same reason; their owners replay.

        HOST state survives deliberately: the scheduler (and with it
        the service-time EWMA — the shed estimate does not cold-start
        on a supervisor restart) and the brownout ladder's level
        (serving/degrade.py) — a replica that wedged UNDER overload
        must not come back at level 0 and re-admit the flood that
        wedged it. Both choices are test-pinned
        (tests/test_resilience.py). A whole-PROCESS replica restart
        does cold-start both: there the EWMA re-learns within one
        sync window of its first completion."""
        for slot, req in enumerate(self._slot_req):
            if req is not None:
                req.fail(f"engine step failed while this request was "
                         f"slotted: {msg}")
        for st in self._prefilling:
            req = st.req
            if req.done():
                continue  # watchdog already failed it
            req.state = RequestState.QUEUED
            self.scheduler.requeue(req)
        self.scheduler.clear_parked()
        self._prefilling = []
        self._early_program = None
        self._behind_window = None
        self._unfetched = [0, 0]
        self._sub0 = None
        self._index = PrefixIndex(self.pool.block_size if self._blocks_on
                                  else max(self.serving.prefill_bucket, 1))
        self.pool = SlotKVPool(self.cfg, self.num_slots, self.max_len,
                               dtype=self.pool.dtype,
                               retained_limit=self.serving.retained_slots,
                               block_size=self.serving.kv_block_size)
        if self.topo is not None:
            self.topo.place_pool(self.pool)
        self.pool.on_reclaim = self._index.remove
        if self._host_tier is not None:
            # the tier itself survives a restart (host RAM is not
            # device state) — only the demotion hook needs rewiring
            # onto the rebuilt pool
            self.pool.on_evict_entry = self._demote_entry
        S, Vp = self.num_slots, self.cfg.padded_vocab_size
        self._last_logits = jnp.zeros((S, Vp), jnp.float32)
        self._rngs = jnp.zeros((S, 2), jnp.uint32)
        self._lengths[:] = 0
        self._active[:] = False
        self._reject[:] = -1
        self._d_reject = self._upload_chained(self._reject)
        # every slotted request failed, so no adapter pin survives; the
        # bank's device arrays DO (they are never donated), so resident
        # adapters stay warm across the restart
        self._adapter_idx[:] = 0
        self._d_adapter_idx = jnp.asarray(self._adapter_idx)
        if self.adapters is not None:
            self.adapters.reset_pins()
        # grammar masks reset with the grid: a requeued structured
        # request keeps its FSM and its advanced fsm_state (both
        # host-side, like resume_rng), so re-activation re-installs
        # the right mask via _set_slot_mask
        self._masks = np.ones((S, Vp), np.bool_)
        self._d_masks = jnp.asarray(self._masks)
        self._mask_state = np.full(S, -1, np.int64)
        self._masks_dirty = False
        self._slot_req = [None] * S
        self._park_knobs(slice(None))
        self._sampling_dirty = True
        self._lengths_dirty = True
        self._kv_dirty = True
        self._bracket_bytes = 0
        self._wedged = False
        if self._watchdog is not None:
            self._watchdog.rearm()

    # ------------------------------------------------------------------
    # priority preemption
    # ------------------------------------------------------------------
    def _preempt_for_priority(self):
        """A queued higher-priority request with NO allocatable slot
        (free list and retained LRU both empty) evicts the
        lowest-priority running slot; ties prefer the youngest victim
        (least sunk cost). At most one victim per waiting iteration —
        the freed slot is consumed by the very next `_admit` pop, so
        preempting deeper would only thrash."""
        if not self._preempt_on:
            return
        if self.pool.free_count() > 0:
            return
        top = self.scheduler.peek_priority()
        if top is None:
            return
        victim, vprio = None, None
        for slot in np.nonzero(self._active)[0]:
            req = self._slot_req[slot]
            if req is None:
                continue
            if (vprio is None or req.priority < vprio
                    or (req.priority == vprio
                        and req.id > self._slot_req[victim].id)):
                victim, vprio = int(slot), req.priority
        if victim is None or vprio >= top:
            return
        self._preempt(victim)

    def _preempt(self, slot: int):
        """Losslessly evict `slot`: park its KV region in a batch-1
        sub-cache OUTSIDE the pool (`slice_slot` — the read half of
        `clone_prefix`; a separate device buffer the grid's idle writes
        can never touch) together with the carried logits row and a
        HOST copy of the PRNG key, then requeue the request. Resume is
        one `insert_prefill` — no re-prefill, token-exact, and the
        decode trace is untouched (slot bookkeeping + two
        already-compiled region copies). The park budget is the slot
        count; beyond it (or after an engine restart) the sub is
        dropped and the victim replays its effective prompt instead —
        still token-exact via the host-side rng."""
        req = self._slot_req[slot]
        plen = int(self._lengths[slot])
        assert plen == len(req.effective_prompt()), (
            plen, len(req.prompt), len(req.generated))
        # host copy FIRST: it survives restarts and the replay fallback
        req.resume_rng = np.asarray(jax.device_get(self._rngs[slot]))
        # the residual carry is committed sampling state (unlike draft
        # proposals, which are droppable): the mirror is exact here —
        # preemption runs at a sync boundary
        req.resume_reject = int(self._reject[slot])
        if self.scheduler.parked_count() < self.num_slots:
            if self._blocks_on:
                sub = self._slice_blk(
                    self._p_dec, self.pool.caches,
                    jnp.asarray(self.pool.map_row(slot), jnp.int32),
                    jnp.int32(plen))
            else:
                sub = self._slice(self._p_dec, self.pool.caches,
                                  jnp.int32(slot), jnp.int32(plen))
            # row-index makes a NEW device buffer — safe across the
            # next decode's donation of self._last_logits
            req.parked = (sub, self._last_logits[slot])
        else:
            req.parked = None  # replay fallback
        req.preemptions += 1
        req.record.preempted += 1
        self.metrics.count("preemptions")
        self._slot_req[slot] = None
        self._active[slot] = False
        self._reject[slot] = -1  # draft state is droppable: a parked
        #                          victim carries only committed tokens
        # the pin frees with the slot; the victim re-ACQUIRES at
        # resume (the bank row may have been recycled meanwhile — the
        # stable adapter_id on the request is what resumes, so the
        # restored stream decodes under the same weights regardless of
        # which row they land in next)
        self._release_adapter(req)
        self._adapter_idx[slot] = 0
        if self._mask_state[slot] >= 0:
            # the mask row frees with the slot; the victim's grammar
            # walk lives on the REQUEST (fsm_state) and re-installs
            # at resume via _set_slot_mask
            self._masks[slot, :] = True
            self._mask_state[slot] = -1
            self._masks_dirty = True
        self._park_knobs(slot)
        self._sampling_dirty = True
        self._kv_dirty = True
        self._lengths_dirty = True
        # the region itself goes back to the free list (its KV lives in
        # the parked sub now, a separate buffer), so the slot parks at
        # position 0 like any hard-freed row — the grid's idle writes
        # land in a region nothing references until the next insert
        # overwrites it whole
        self._index.remove(slot)
        self.pool.release(slot)
        self._lengths[slot] = 0
        req.state = RequestState.QUEUED
        self.scheduler.requeue(req)

    def _admit(self, early: bool = False) -> int:
        """Place what the scheduler pops; returns how many it popped.
        `early` is the call from inside a decode window
        (`_admit_early`): it places ONE prefill program, the requests
        at the queue's head that `_prefill_group` takes as one group,
        and sends the first that is none of them back with all behind
        it, untried, for the next iteration's call."""
        room = self.pool.free_count()
        if early:
            room = min(room, self._prefill_max_batch)
        popped = self.scheduler.pop_ready(room)
        if not popped:
            return 0
        pending = list(popped)
        placed = 0

        def send_back(rest):
            # a requeued request keeps its arrival id, so the order of
            # the next pop is what it would have been
            for rr in rest:
                self.scheduler.requeue(rr)
                pending.remove(rr)
        # expose the not-yet-placed pops to the watchdog: a wedge
        # inside a prefill dispatch below leaves them in neither
        # _slot_req nor _prefilling, and the no-stranded-futures
        # contract covers them too (`pending` is mutated as each
        # request lands, so this alias always holds exactly the
        # unplaced remainder)
        self._admitting = pending
        try:
            groupable: List[GenRequest] = []
            # head-of-line fairness: once a request blocks on a FULL
            # adapter bank, every LATER adapter request this pass
            # requeues untried — otherwise a saturating resident
            # tenant keeps re-pinning its row behind the blocked head
            # and starves it forever. Base requests (no pin) still
            # admit; arrival ids preserve the order across requeues,
            # so the blocked head is served first once a pin frees.
            bank_blocked = False
            for i, r in enumerate(popped):
                if early and r.parked is not None:
                    send_back(popped[i:])  # a resume is no group's
                    break
                if bank_blocked and r.adapter_id is not None:
                    self.scheduler.requeue(r)
                    pending.remove(r)
                    continue
                verdict = self._acquire_adapter(r)
                if verdict != "ok":
                    # "blocked": bank full, requeued until a pin frees;
                    # "failed": typed error already set on the request
                    bank_blocked = bank_blocked or verdict == "blocked"
                    pending.remove(r)
                    continue
                if r.parked is not None:
                    # preemption victim with intact parked KV: resume
                    # with ONE insert — no forward at all
                    self._resume_parked(r)
                    pending.remove(r)
                    placed += 1
                    continue
                # a resumed request prefills its EFFECTIVE prompt
                # (prompt + generated); == prompt when never preempted
                toks = r.effective_prompt()
                src, hit = self._lookup_prefix(toks, r.adapter_ns)
                if r.fanout_leader is not None \
                        and not r.fanout_leader.done() \
                        and not hit \
                        and self._prefix_on and not self.pool.rolling \
                        and r.resume_rng is None:
                    # n-best fan-out: siblings wait for the LEADER's
                    # prompt KV to land in the prefix index, then
                    # admit through the COW-alias hit path — ONE
                    # prefill forward serves the whole fan-out (the
                    # one-prefill pin). Gate on the sibling's OWN
                    # index hit, not leader state: the leader is
                    # RUNNING from admission but indexed only at
                    # activation. No deadlock: a leader terminal in
                    # any way (done()) releases the gate, and
                    # prefixless engines never enter it. Prompts too
                    # short to hit at index granularity re-prefill
                    # standalone once the leader finishes — correct,
                    # just without the saving.
                    self._release_adapter(r)
                    self.scheduler.requeue(r)
                    pending.remove(r)
                    continue
                single = self._single(len(toks), hit,
                                      r.resume_rng is not None)
                if early and (single or (
                        groupable and self._prefill_bucket(len(r.prompt))
                        != self._prefill_bucket(
                            len(groupable[0].prompt)))):
                    # the pending path's, or another program's
                    self._release_adapter(r)
                    send_back(popped[i:])
                    break
                if single:
                    self._start_pending(r, src, hit)
                    pending.remove(r)
                    placed += 1
                else:
                    groupable.append(r)
            groups = AdmissionScheduler.group_by_bucket(
                groupable, lambda rr: self._prefill_bucket(len(rr.prompt)),
                self._prefill_max_batch)
            for padded, reqs in groups:     # all, ahead of the first's wait
                self._hand_group(self._batch_bucket(len(reqs)), padded)
            for padded, reqs in groups:
                stats = dict(n=len(reqs), padded=padded, rid=reqs[0].id)
                # an early program's `serve/prefill` span is the next
                # iteration's (`_iteration`); its dispatch, here, is
                # most of its `serve/admit`
                with span("serve/prefill.early" if early
                          else "serve/prefill", **stats):
                    self._prefill_group(reqs, padded)
                for r in reqs:
                    pending.remove(r)
                placed += len(reqs)
                if early:
                    self._early_program = stats
        except Exception as e:
            # anything not yet admitted is in neither _slot_req /
            # _prefilling nor the scheduler — fail it here or its
            # caller would hang to the request timeout (and its
            # admission-time adapter pin must not leak)
            for r in pending:
                self._release_adapter(r)
                r.fail(repr(e))
            raise
        finally:
            self._admitting = []
            self.metrics.count("admits_total", placed)
            if early:
                self.metrics.count("admits_early", placed)
        return len(popped)

    def _acquire_adapter(self, req: GenRequest) -> str:
        """Resolve req.adapter_id to a pinned bank row (req.bank_idx)
        and its registration-generation namespace (req.adapter_ns).
        Returns "ok", or how the request left this admission pass:
        "blocked" — bank full, REQUEUED (a pin frees when a slot
        finishes; liveness holds because pins only come from
        active/prefilling slots, and _admit stops admitting later
        adapter requests behind a blocked head); "failed" —
        deregistered-since-submit, unloadable source, or RE-REGISTERED
        mid-flight (a preempted/requeued stream must never resume
        under different weights than it started with)."""
        req.bank_idx = 0
        if self.adapters is None or req.adapter_id is None:
            return "ok"
        from megatron_tpu.serving.adapters import (AdapterBankFullError,
                                                   UnknownAdapterError)
        try:
            idx = self.adapters.acquire(req.adapter_id)
        except AdapterBankFullError:
            self.scheduler.requeue(req)
            return "blocked"
        except UnknownAdapterError as e:
            req.fail(str(e))
            return "failed"
        except Exception as e:  # noqa: BLE001 — unloadable source
            req.fail(f"adapter {req.adapter_id!r} failed to load: "
                     f"{e!r}")
            return "failed"
        ns = self.adapters.namespace(req.adapter_id)
        if req.adapter_ns is not None and ns != req.adapter_ns:
            self.adapters.release(idx)
            req.fail(f"adapter {req.adapter_id!r} was re-registered "
                     "while this request was queued or preempted; its "
                     "stream cannot continue under different weights "
                     "— resubmit")
            return "failed"
        req.adapter_ns = ns
        req.bank_idx = idx
        return "ok"

    def _release_adapter(self, req: Optional[GenRequest]):
        """Drop the admission-time pin (slot freed / admission failed).
        Idempotent via bank_idx=0 reset."""
        if req is None or self.adapters is None:
            return
        if req.bank_idx:
            self.adapters.release(int(req.bank_idx))
            req.bank_idx = 0

    def _ns(self, adapter_ns):
        """Prefix/host-tier namespace: (weight generation, adapter
        namespace). The weight generation bumps at every applied hot
        swap, so KV computed under version N is STRUCTURALLY invisible
        to any post-swap lookup — the PR 12 adapter-namespace pattern
        applied to the base weights (belt on top of the swap's eager
        index/tier sweep)."""
        return (self._weight_gen, adapter_ns)

    def _lookup_prefix(self, toks, namespace=None):
        """Longest reusable cached prefix of `toks` COMPUTED UNDER
        `namespace` (the request's adapter id; None = base) and its
        source — an int (running slot) or a RetainedPrefix key. The
        lookup caps the match at len-1: at least one suffix token must
        forward to produce the sampling logits at position plen-1.
        Cross-adapter hits are structurally impossible: the namespace
        is the first node on every indexed path (prefix_index.py).

        ROLLING pools (block mode only — whole-region rolling never
        indexes) add a ring-validity gate: the retained ring holds only
        the LAST W positions of its sequence, so a clone is sound only
        when (a) the new prompt CONTINUES the retained sequence in full
        — matched at the entry's exact length, not the block-floored
        index match — or (b) the source never wrapped (final length <=
        W), where any block-aligned prefix is still resident. Running
        rolling slots are never indexed at all: their ring keeps
        wrapping over the very prefix the index would advertise."""
        if not self._prefix_on:
            return None, 0
        namespace = self._ns(namespace)  # weight-generation isolation
        toks = list(toks)
        src, hit = self._index.lookup(toks, len(toks) - 1,
                                      namespace=namespace)
        if src is None or not hit:
            src, hit = None, 0
        elif self.pool.rolling:
            ent = (None if isinstance(src, (int, np.integer))
                   else self.pool.entry(src))
            if ent is None:
                src, hit = None, 0
            else:
                f = ent.length
                if f <= len(toks) - 1 and toks[:f] == ent.tokens:
                    # full continuation at the EXACT ring length
                    src, hit = src, f
                elif f <= self.pool.cap:
                    pass  # ring never wrapped: any prefix resident
                else:
                    src, hit = None, 0
        # host-RAM tier: a STRICTLY longer demoted match beats the
        # device hit (restoring costs one device_put; at equal length
        # the on-device copy wins)
        if self._host_tier is not None:
            hkey, hhit = self._host_tier.lookup(toks, len(toks) - 1,
                                                namespace=namespace)
            if hkey is not None and hhit > hit:
                return _HostSrc(hkey), hhit
        return src, hit

    def _resume_parked(self, req: GenRequest):
        """Resume a preemption victim whose KV survived in its parked
        sub-cache: allocate a slot and land the whole region with ONE
        `insert_prefill` (plus the saved logits row and rng key) — the
        request continues decoding exactly where it stopped, with zero
        forward work and zero new compiles."""
        sub, last = req.parked
        req.parked = None
        tokens = req.effective_prompt()
        plen = len(tokens)
        blocks = None
        if self._blocks_on:
            got = self.pool.alloc_row(install=False)
            assert got is not None, "popped more requests than free slots"
            slot, blocks = got
        else:
            slot = self.pool.alloc()
            assert slot is not None, "popped more requests than free slots"
        st = None
        try:
            st = _PendingPrefill(req, slot, sub, plen,
                                 jnp.asarray(req.resume_rng),
                                 tokens=tokens, blocks=blocks)
            st.last = last
            # a parked sub was sliced on the decode group and resumes
            # there with one insert — no cross-group handoff
            st.on_decode = True
            self._mark_admitted(req)
            self._activate_pending(st, plen)
        except Exception:
            if blocks is not None and not (st is not None
                                           and st.installed):
                self.pool.drop_blocks(blocks)
            self.pool.release(slot)
            raise

    def _start_pending(self, req: GenRequest, src,
                       prefix_len: int):
        """Reserve a slot and begin a suffix/chunked prefill. On a
        prefix hit the shared region slices out of `src` (a running
        slot or a RetainedPrefix key — one on-device copy in place of
        L forward layers over those tokens); otherwise the sub-cache
        starts empty at offset 0. Block-granular pools additionally
        ALIAS the shared prefix blocks into the new row's map (refs
        taken at alloc, map installed at activation), so the prefix's
        arena blocks are shared, not duplicated — the insert later
        skips them (copy-on-write boundary). A preemption-replay
        request (resume_rng set, parked KV gone) prefills its
        effective prompt and continues the saved PRNG chain —
        token-exact either way."""
        tokens = req.effective_prompt()
        plen = len(tokens)
        host_sub = None
        if prefix_len and isinstance(src, _HostSrc):
            # host-tier restore FIRST (checksum-verified): a corrupt
            # demotion degrades to a plain miss here — the request
            # recomputes its whole prefill, never reads wrong KV
            host_sub = self._restore_host(src.key, prefix_len)
            if host_sub is None:
                src, prefix_len = None, 0
        if prefix_len:
            # matched at lookup — counted even when the allocation
            # below forfeits the hit, so hit_tokens - tokens_saved
            # measures slot-pressure forfeits
            self.metrics.count("prefix_hit_tokens", prefix_len)
            req.record.prefix_hit_tokens += prefix_len
        blocks = None
        pfx_blocks = 0
        device_hit = prefix_len and host_sub is None
        if self._blocks_on:
            alias = []
            roll_src_blocks = None
            disagg_src_blocks = None
            if device_hit and self.pool.rolling:
                # capture BEFORE alloc_row: block pressure may evict
                # the source entry below. Its blocks' content stays
                # valid for this iteration's slice regardless — the
                # arena is functional, the gather reads this dispatch
                # point's version.
                roll_src_blocks = list(self.pool.entry(src).blocks)
            if device_hit and self._disagg:
                # disaggregated hit: the prefix KV rides to the
                # PREFILL group for the suffix chunks, and the handoff
                # later writes the whole sequence back into the new
                # row's own blocks — so the row never aliases (the
                # zero-copy alias would leave the prefix on devices
                # the chunks can't read). Captured before alloc_row
                # for the same eviction-race reason as rolling.
                disagg_src_blocks = self._src_blocks(src)[
                    :prefix_len // self.pool.block_size]
            elif device_hit and not self.pool.rolling:
                pfx_blocks = prefix_len // self.pool.block_size
                alias = self._src_blocks(src)[:pfx_blocks]
            got = self.pool.alloc_row(alias=alias, install=False)
            if got is None and prefix_len:
                # block pressure: forfeit the hit, admit plain
                src, prefix_len, pfx_blocks = None, 0, 0
                host_sub = None
                got = self.pool.alloc_row(install=False)
            assert got is not None, "popped more requests than free slots"
            slot, blocks = got
        else:
            slot = self.pool.alloc(
                exclude=(src,) if device_hit else ())
            if slot is None:
                # the ONLY allocatable slot is the clone source itself:
                # forfeit the hit and reclaim it as a plain slot
                src, prefix_len = None, 0
                host_sub = None
                slot = self.pool.alloc()
            assert slot is not None, "popped more requests than free slots"
        try:
            if prefix_len and host_sub is not None:
                # restored from the host tier: the sub ALREADY holds the
                # prefix KV at offset prefix_len (device_put), so the
                # suffix chunks append to it exactly like a sliced
                # device hit — fresh blocks, no aliasing (pfx_blocks=0:
                # the insert writes the restored prefix into this row's
                # own blocks)
                req.prefix_len = prefix_len
                self.metrics.count("host_tier_hits")
                self.metrics.count("prefill_tokens_saved", prefix_len)
                sub = host_sub
            elif prefix_len:
                if isinstance(src, (int, np.integer)):
                    self.pool.touch(int(src))  # refresh the retained LRU
                else:
                    self.pool.touch_key(src)
                req.prefix_len = prefix_len
                self.metrics.count("prefix_hits")
                self.metrics.count("prefill_tokens_saved", prefix_len)
                if not self._blocks_on:
                    sub = self._slice(self._p_dec, self.pool.caches,
                                      jnp.int32(src),
                                      jnp.int32(prefix_len))
                elif self.pool.rolling:
                    # rolling hit: FULL ring copy out of the retained
                    # entry's blocks (aliasing is unsound on a ring —
                    # the new row's later writes wrap into the early
                    # blocks). The gather reads the arena version of
                    # THIS dispatch point, so later reuse of the
                    # entry's blocks cannot corrupt the copy.
                    sub = self._slice_blk(
                        self._p_dec, self.pool.caches,
                        jnp.asarray(roll_src_blocks, jnp.int32),
                        jnp.int32(prefix_len))
                elif self._disagg:
                    # disaggregated hit: gather ONLY the prefix's live
                    # blocks on the decode group ([L, 1, nb*B, ...]),
                    # move them device-to-device, and widen to the
                    # full-cap batch-1 layout on the prefill group —
                    # the suffix chunks then append exactly like a
                    # same-group hit. Block-granular both ways: a cap
                    # region never crosses the group boundary.
                    sub_t = self._slice_blk(
                        self._p_dec, self.pool.caches,
                        jnp.asarray(disagg_src_blocks, jnp.int32),
                        jnp.int32(prefix_len))
                    sub = self._pad_sub_pre(
                        self._p_pre, self.topo.to_prefill(sub_t),
                        jnp.int32(prefix_len))
                else:
                    # slicing through the new row's OWN block list
                    # reads the aliased prefix content (plus
                    # fresh-block garbage past the offset, which the
                    # causal mask never sees) — the suffix chunks
                    # attend the prefix through this sub
                    sub = self._slice_blk(
                        self._p_dec, self.pool.caches,
                        jnp.asarray(blocks, jnp.int32),
                        jnp.int32(prefix_len))
            else:
                sub = self._zero_sub()      # miss
            if req.resume_rng is not None:
                rng0 = jnp.asarray(req.resume_rng)
            else:
                self._await_program(("key",), (req,))
                rng0 = self._initial_rng(req.seed, plen)
            st = _PendingPrefill(req, slot, sub, prefix_len, rng0,
                                 tokens=tokens, blocks=blocks,
                                 pfx_blocks=pfx_blocks)
            self._mark_admitted(req)
            self._prefilling.append(st)
        except Exception:
            if blocks is not None:
                self.pool.drop_blocks(blocks)  # map never installed
            self.pool.release(slot)
            raise

    def _demote_entry(self, ent):
        """SlotKVPool.on_evict_entry: a retained prefix is dying under
        block pressure (or the retained_limit) — gather its block list
        to host memory so a later hit restores it instead of
        recomputing. Rolling rings never demote (a ring restore is
        only sound as an exact-length continuation). Best-effort: a
        failed demotion loses only the host copy."""
        if self._host_tier is None or self.pool.rolling:
            return
        # size gate BEFORE the device gather: an entry the budget can
        # never hold must not pay a multi-MB device_get on the
        # admission hot path just to be refused
        est = (len(ent.blocks) * self.pool.block_size
               * self.pool.bytes_per_token())
        if est > self._host_tier.budget_bytes:
            return
        arrays = self.pool.gather_blocks_host(ent.blocks)
        if self._host_tier.demote(ent.key, ent.tokens, ent.length,
                                  arrays,
                                  namespace=getattr(ent, "namespace",
                                                    None)):
            self.metrics.count("host_tier_demotions")

    def _restore_host(self, key, plen: int):
        """Checksum-verified host-tier restore: returns the batch-1
        sub-cache holding the demoted prefix at offset `plen`
        (device_put), or None on a checksum miss — the entry is
        dropped and the caller degrades to a plain prefill (a corrupt
        demotion is a MISS, never wrong tokens)."""
        if not self._host_tier.has(key):
            return None  # LRU-evicted since lookup: a plain miss
        ent = self._host_tier.restore(key)
        if ent is None:
            self.metrics.count("host_tier_checksum_misses")
            return None
        nb = -(-plen // self.pool.block_size)
        arrays = {k: v[:, :nb] for k, v in ent.arrays.items()}
        if self._disagg:
            # disaggregated: upload ONLY the live blocks' bytes to the
            # prefill group and widen on-device — the cap-sized zero
            # tail never rides a transfer, the same block-granular
            # discipline as the prefill->decode handoff
            sub_t = self.pool.host_blocks_to_sub(arrays, plen,
                                                 pad_to_cap=False)
            return self._pad_sub_pre(self._p_pre,
                                     self.topo.to_prefill(sub_t),
                                     jnp.int32(plen))
        return self.pool.host_blocks_to_sub(arrays, plen)

    def _src_blocks(self, src) -> List[int]:
        """Physical blocks backing a prefix source: a running slot's
        map row, or a row-less RetainedPrefix's pinned blocks."""
        if isinstance(src, (int, np.integer)):
            return self.pool.map_row(int(src))
        return list(self.pool.entry(src).blocks)

    def _advance_prefill(self):
        """Run ONE prefill chunk for the oldest pending request; when
        its last chunk lands, insert the accumulated KV into the slot
        and activate it. Chunk tokens pad up to the prefill bucket
        (capped so the write can never spill past the region — a
        clamped dynamic_update_slice would silently shift backwards
        over real tokens)."""
        if not self._prefilling:
            return
        st = self._prefilling[0]
        with span("serve/prefill_chunk", rid=st.req.id) as sp:
            sp.set_metadata(tokens=self._prefill_one_chunk(st))

    def _prefill_one_chunk(self, st: _PendingPrefill) -> int:
        """`_advance_prefill`'s dispatch; returns the real tokens
        forwarded."""
        plen = len(st.tokens)
        n, padded = self._chunk_shape(st.pos, plen)
        toks = np.full((1, padded), self.gen.pad_id, np.int32)
        toks[0, :n] = st.tokens[st.pos:st.pos + n]
        aidx1 = (jnp.asarray([st.aidx], jnp.int32) if self._adapters_on
                 else None)
        self._await_program(("chunk", padded), (st.req,))
        if st.pos > 0 and self.cfg.mla:
            self._count_latent_chunk_blocks(st.pos, padded)
        st.sub, st.last = self._chunk_fwd(*self._chunk_args(
            st.sub, jnp.asarray(toks), jnp.int32(n - 1),
            jnp.int32(st.pos + n), aidx1))
        st.pos += n
        st.req.prefill_chunks += 1
        self._note_prefill([st.req.record], padded)
        self.metrics.count("prefill_chunks")
        # REAL tokens forwarded — the cache-on/off A/B seam: prefix
        # hits forward strictly fewer tokens than the cache-off run
        self.metrics.count("prefill_forward_tokens", n)
        if st.pos >= plen:
            self._prefilling.pop(0)
            self._activate_pending(st, plen)
        return n

    def _count_latent_chunk_blocks(self, pos: int, padded: int):
        """`latent_chunk_blocks_read` / `_held`: what the absorbed form of
        a chunk of `padded` rows at offset `pos` reads of the sequence's
        region of latent rows, by models/mla.py's own rule, over its query
        blocks and the MLA layers, against the whole region."""
        layers, t = self.pool.caches.c.shape[0], self.pool.cap
        blk = mla.absorbed_query_block(padded)
        # the last position of each block of queries; rows taken at once
        # (blk 0) read the whole region
        last = (pos + np.arange(blk, padded + 1, blk) - 1 if blk
                else np.array([t - 1]))
        self.metrics.count("latent_chunk_blocks_read", layers * int(
            mla.absorbed_key_blocks(last, t).sum()))
        self.metrics.count("latent_chunk_blocks_held", layers * len(last)
                           * int(mla.absorbed_key_blocks(t - 1, t)))

    def _chunk_shape(self, pos: int, plen: int):
        """(real tokens, padded length) of the chunk that takes a prompt
        of `plen` tokens on from `pos`."""
        n = plen - pos
        if self._chunk is not None:
            n = min(n, self._chunk)
        if self.pool.rolling and pos > 0:
            # rolling prefix-hit suffix: an offset>0 MULTI-token ring
            # write evicts history its own early queries still need
            # within one dispatch (the reason prefill_chunk stays
            # excluded on rolling), but the decode-shaped s=1 append
            # is exact on the ring — so the suffix lands one token per
            # engine iteration, interleaved with decode like any chunk
            n = 1
        # chunk shape bucketing: a FULL chunk is already a fixed shape;
        # only the tail pads up to the prefill bucket (capped at the
        # chunk size so chunking never widens the shape set, and at the
        # region remainder so the padded write can never spill past the
        # slot — a clamped dynamic_update_slice would silently shift
        # backwards over real tokens)
        b = max(self.serving.prefill_bucket, 1)
        if self.pool.rolling:
            # ring prefill is exact-length: pad positions fed through
            # the ring would evict real tokens from the W-slot buffer
            padded = n
        elif self._chunk is not None and n == self._chunk:
            padded = n
        else:
            padded = -(-n // b) * b
            if self._chunk is not None:
                padded = min(padded, max(self._chunk, n))
        if not self.pool.rolling:
            padded = min(padded, self.max_len - pos)
        assert n <= padded, (n, padded, pos)
        return n, padded

    def _activate_pending(self, st: _PendingPrefill, plen: int):
        slot, req = st.slot, st.req
        if self._disagg and not st.on_decode:
            # PREFILL->DECODE HANDOFF (docs/serving.md "Sharded &
            # disaggregated serving"): the finished prefill's KV lives
            # in a batch-1 sub on the prefill group. Move ONLY the
            # sequence's ceil(plen/B) live blocks device-to-device —
            # never the cap region (the handoff_bytes_per_req gauge
            # pins exactly this) — and land them through the decode
            # group's compiled pad+insert program. The carried logits
            # row and rng key ride along (KiB-scale).
            B = self.pool.block_size
            nb_live = -(-plen // B)
            sub_t = self._truncate_sub(st.sub, nb_live * B)
            moved = self.topo.to_decode(sub_t)
            last = jax.device_put(st.last,
                                  self.topo.replicated(
                                      self.topo.decode_mesh))
            rng0 = jax.device_put(st.rng0,
                                  self.topo.replicated(
                                      self.topo.decode_mesh))
            self.pool.install_row(slot, st.blocks)
            st.installed = True
            self._await_program(("handoff", nb_live), (req,))
            out = self._handoff_insert(self._p_dec, self.pool.caches,
                                       self._last_logits, self._rngs,
                                       moved, jnp.int32(slot),
                                       jnp.int32(plen), last, rng0)
            hbytes = nb_live * B * self.pool.bytes_per_token()
            self.metrics.count("handoffs")
            self.metrics.set_handoff_gauge(hbytes)
        else:
            if self._blocks_on:
                # install the row's block map NOW (not at admission):
                # until this moment the row's map pointed at trash, so
                # the K-chained decode dispatches that ran between
                # chunks could never write into the reserved (and
                # possibly aliased) blocks
                self.pool.install_row(slot, st.blocks)
                st.installed = True
            self._await_program(("insert",), (req,))
            insert = self._insert_blk if self._blocks_on else self._insert
            out = insert(*self._insert_args(
                st.sub, jnp.int32(slot), jnp.int32(plen),
                jnp.int32(st.pfx_blocks) if self._blocks_on else None,
                st.last, st.rng0))
        self.pool.caches, self._last_logits, self._rngs = out
        self._lengths[slot] = plen
        self._active[slot] = True
        self._temps[slot] = req.sampling.temperature
        self._top_ks[slot] = req.sampling.top_k
        self._top_ps[slot] = req.sampling.top_p
        # -1 for a fresh request; a preemption resume/replay restores
        # the saved residual carry with the rng chain
        self._reject[slot] = req.resume_reject
        # the slot decodes under the request's adapter bank row
        # (0 = identity/base; pinned since admission)
        self._adapter_idx[slot] = st.aidx
        self._slot_req[slot] = req
        if req.fsm is not None:
            # mask for the request's CURRENT FSM state — 0 when
            # fresh, the saved state on a preemption resume (the
            # grammar walk survives park/requeue with the rng chain)
            self._set_slot_mask(slot, req)
        self._sampling_dirty = True
        self._kv_dirty = True
        self._lengths_dirty = True
        if self._prefix_on and not self.pool.rolling:
            # the slot is now cloneable for its prefilled sequence —
            # the PROMPT for a fresh request, prompt + generated-so-far
            # for a resumed one (extended again at retain time) — in
            # the request's ADAPTER namespace (a different adapter's
            # identical tokens must never hit it).
            # Rolling slots index only at RETAIN time: a running ring
            # keeps wrapping over the very prefix the index would
            # advertise.
            self._index.insert(slot, st.tokens,
                               namespace=self._ns(req.adapter_ns))

    def _drop_pending(self, st: _PendingPrefill, msg: str,
                      kind: str = "error"):
        self._prefilling.remove(st)
        self._release_adapter(st.req)
        if st.blocks is not None:
            # still pending => the map row was never installed, so the
            # reserved/aliased blocks are held only by the pending
            self.pool.drop_blocks(st.blocks)
        self._kv_dirty = True
        self.pool.release(st.slot)
        st.req.fail(msg, kind=kind)  # terminal hook counts the bucket

    def _mark_admitted(self, req: GenRequest):
        """`GenRequest.mark_admitted`, and at a request's FIRST admission
        (a restart-requeued or preempted request re-enters through the
        same paths and is not admitted twice) its row's `t_admit`,
        `early`, `t_device` and what stands ahead of it, the row into
        the ring and `requests_admitted`."""
        first = req.admit_time is None
        req.mark_admitted()  # no-op on a concurrently-failed req
        if not first or req.admit_time is None:
            return
        row = req.record
        row.t_admit = req.admit_time
        if self._behind_window is not None:
            # admitted inside a window (`_admit_early`), which is in
            # front: `_step`, as its fetch returns
            row.early = 1
            self._behind_window.append(row)
        else:
            row.t_device = row.t_admit
        row.ahead_programs += self._unfetched[0]
        row.ahead_rows += self._unfetched[1]
        self._awaiting.append(row)
        self.metrics.record_admitted(row)

    def _awaiting_first(self) -> list:
        """The rows admitted and still without a first token."""
        self._awaiting = [r for r in self._awaiting
                          if r.t_first is None and r.outcome is None]
        return self._awaiting

    def _note_prefill(self, own: list, rows: int):
        """A prefill program of `rows` padded rows was dispatched for the
        requests whose rows are `own`: theirs, and in front of every
        other request that waits for its first token."""
        for row in self._awaiting_first():
            if row not in own:
                row.ahead_programs += 1
                row.ahead_rows += rows
        for row in own:
            if row.t_first is None:
                row.programs += 1
                row.rows += rows
        self._unfetched[0] += 1
        self._unfetched[1] += rows

    def _prefill_group(self, reqs: List[GenRequest], padded: int):
        """One batched prefill for same-bucket admissions. The batch
        dim rounds up to a power of two; pad rows replicate row 0
        (identical re-write of the same slot — harmless)."""
        B_real = len(reqs)
        B = self._batch_bucket(B_real)
        # ahead of the slots: a compile that failed in the pool raises
        # here with nothing allocated
        self._await_program(("keys", B), reqs)
        self._await_program(("prefill", B, padded), reqs)
        if self._blocks_on:
            slots = []
            for _ in reqs:
                # sync=False: pay ONE device-map upload for the whole
                # group (the _prefill dispatch below consumes only the
                # final map state)
                got = self.pool.alloc_row(install=True, sync=False)
                assert got is not None, (
                    "popped more requests than free slots")
                slots.append(got[0])
            self.pool._sync_map()
        else:
            slots = [self.pool.alloc() for _ in reqs]
        plens = [len(r.prompt) for r in reqs]
        toks = np.full((B, padded), self.gen.pad_id, np.int32)
        for i, r in enumerate(reqs):
            toks[i, :plens[i]] = r.prompt
        toks[B_real:] = toks[0]
        plens_a = np.asarray(plens + [plens[0]] * (B - B_real), np.int32)
        slots_a = np.asarray(slots + [slots[0]] * (B - B_real), np.int32)
        seeds = [r.seed for r in reqs]
        rng0s = self._initial_rngs(seeds + [seeds[0]] * (B - B_real),
                                   plens_a)
        aidxs = None
        if self._adapters_on:
            # per-row bank indices (resolved + pinned in _admit):
            # mixed-adapter groups batch into the same compiled call
            rows = [r.bank_idx for r in reqs]
            aidxs = jnp.asarray(rows + [rows[0]] * (B - B_real),
                                jnp.int32)
        self.pool.caches, self._last_logits, self._rngs = self._prefill(
            *self._prefill_args(jnp.asarray(toks), jnp.asarray(plens_a),
                                jnp.asarray(slots_a), rng0s, aidxs))
        if self._blocks_on and not self._kernel_on:
            # the batched-prefill program bracketed with resolve +
            # scatter (block-native lands through insert_blocks
            # instead) — flushed into the gauge at the next window
            self._bracket_bytes += 2 * self._view_bytes
        for slot, plen, req in zip(slots, plens, reqs):
            self._lengths[slot] = plen
            self._active[slot] = True
            self._temps[slot] = req.sampling.temperature
            self._top_ks[slot] = req.sampling.top_k
            self._top_ps[slot] = req.sampling.top_p
            self._reject[slot] = req.resume_reject  # -1 when fresh
            self._adapter_idx[slot] = req.bank_idx
            self._slot_req[slot] = req
            if req.fsm is not None:
                self._set_slot_mask(slot, req)
            # restart-requeued requests re-enter through this path
            # too (the rebuilt PrefixIndex is empty)
            self._mark_admitted(req)
        self._note_prefill([r.record for r in reqs], B * padded)
        self._sampling_dirty = True
        self._kv_dirty = True
        self._lengths_dirty = True
        self.metrics.count("prefill_calls")
        self.metrics.count("prefill_prompts", B_real)
        self.metrics.count("prefill_forward_tokens", int(sum(plens)))
        for slot, req in zip(slots, reqs):
            req.prefill_chunks = 1
            if self._prefix_on and not self.pool.rolling:
                # rolling slots index only at retain time (see
                # _activate_pending)
                self._index.insert(slot, req.prompt,
                                   namespace=self._ns(req.adapter_ns))

    def _reap_cancelled(self):
        for slot in np.nonzero(self._active)[0]:
            req = self._slot_req[slot]
            if req is not None and req.cancelled:
                self._evict(slot, failed="cancelled")
        for st in list(self._prefilling):
            if st.req.cancelled:
                self._drop_pending(st, "cancelled")

    def _reap_expired(self):
        """Effective per-request deadline (request `deadline_s`, else
        ServingConfig.request_deadline_s): evict running slots and drop
        queued/prefilling requests whose wall clock ran out — their
        callers have already timed out; decoding for them starves live
        traffic."""
        now = time.monotonic()
        for slot in np.nonzero(self._active)[0]:
            req = self._slot_req[slot]
            if req is None:
                continue
            ad = req.absolute_deadline(self._deadline_s)
            if ad is not None and now > ad:
                self._evict(
                    slot,
                    failed=(f"deadline exceeded after "
                            f"{now - req.submit_time:.1f}s "
                            f"(deadline {ad - req.submit_time:.1f}s, "
                            f"{len(req.generated)} tokens generated)"),
                    kind="deadline")
        for st in list(self._prefilling):
            ad = st.req.absolute_deadline(self._deadline_s)
            if ad is not None and now > ad:
                self._drop_pending(
                    st,
                    f"deadline exceeded after "
                    f"{now - st.req.submit_time:.1f}s "
                    f"(deadline {ad - st.req.submit_time:.1f}s, "
                    f"{st.pos} prompt tokens prefilled)",
                    kind="deadline")
        # drop_expired fails each victim with kind="deadline" — the
        # terminal hook counts requests_expired per request
        self.scheduler.drop_expired(self._deadline_s, now)

    def _park_knobs(self, slot):
        """A freed row samples unfiltered: its last tenant's top_k /
        top_p would keep sampling._filter_rows' two vocabulary sorts on
        for every step the row stays empty. The disabled values ride the
        `_sampling_dirty` upload every freeing site sets anyway."""
        self._top_ks[slot] = 0
        self._top_ps[slot] = 0.0

    def _evict(self, slot: int, failed: Optional[str] = None,
               kind: str = "error"):
        slot = int(slot)  # callers iterate np.nonzero -> np.int64;
        #                   a numpy slot id must never become an index
        #                   key (isinstance(src, int) gates on it)
        req = self._slot_req[slot]
        self._slot_req[slot] = None
        self._active[slot] = False
        self._reject[slot] = -1  # residual carry dies with the stream
        # the adapter pin frees with the slot: retained KV is plain
        # data and needs no live bank row (the retained entry keeps the
        # adapter NAMESPACE for index correctness, not the weights)
        self._release_adapter(req)
        self._adapter_idx[slot] = 0
        if self._mask_state[slot] >= 0:
            # grammar hygiene: the freed row must sample unmasked —
            # a stale mask would constrain the NEXT tenant's tokens
            self._masks[slot, :] = True
            self._mask_state[slot] = -1
            self._masks_dirty = True
        self._kv_dirty = True
        self._lengths_dirty = True  # device copy re-parks at next step
        self._park_knobs(slot)
        self._sampling_dirty = True
        if failed is None and self._prefix_on and self._blocks_on:
            # block-granular retention: the finished row converts into
            # a ROW-LESS RetainedPrefix pinning only the blocks its
            # final sequence covers — the tail blocks AND the grid row
            # free immediately (this is the slots-per-HBM-byte win:
            # retained capacity is bounded by blocks, not rows). The
            # freed row parks at length 0 with an all-TRASH map, so
            # its idle decode writes land in the trash block — no
            # park-at-final-length dance, and the reason rolling rings
            # can retain at all.
            final = int(self._lengths[slot])
            tokens = req.prompt + req.generated
            ns = self._ns(req.adapter_ns)
            self._index.remove(slot)
            rkey = self.pool.retain_row(slot, final, tokens,
                                        namespace=ns)
            if rkey is not None:
                self._index.insert(rkey, tokens, namespace=ns)
            self._lengths[slot] = 0
        elif failed is None and self._prefix_on:
            # prefix cache: RETAIN the finished slot's KV for reuse
            # instead of freeing it, and index the full sequence the
            # region now holds (prompt + generated — the decode loop
            # wrote every generated token's KV, EOS included, before
            # this eviction). CRITICAL: the slot PARKS AT ITS FINAL
            # LENGTH, not 0 — inactive rows still ride every decode
            # step and write a garbage token at their position, so
            # parking at 0 would clobber the retained prefix's first
            # entry. At >= final length the writes land past every
            # cloneable prefix: a clone is capped at the NEW prompt's
            # len-1 <= max_len-2, while idle writes sit at
            # final..max_len-1 (the decode clamp).
            # index BEFORE retain(): with retained_slots=0 (or any
            # overflow that demotes this very slot) retain() fires
            # on_reclaim -> _index.remove(slot) for the demoted slot —
            # inserting after would resurrect a stale entry over a
            # free-listed slot, and free-list alloc() never reclaims.
            self._index.insert(slot, req.prompt + req.generated,
                               namespace=self._ns(req.adapter_ns))
            self.pool.retain(slot)
        else:
            self._lengths[slot] = 0  # inactive rows park at position 0
            self.pool.release(slot)
            self._index.remove(slot)
        if failed is not None:
            # the terminal-accounting hook classifies the failure
            # (expired / cancelled / failed — "nonfinite" rides the
            # failed bucket, with nonfinite_logit_fails counted at the
            # guard); no per-site counters to keep in sync
            req.fail(failed, kind="error" if kind == "nonfinite"
                     else kind)
            return
        if req.finish():
            # completion metrics ride the terminal hook; only the
            # shed-estimator feed is site-specific (slot service time)
            self.scheduler.observe_service(
                req.finish_time - (req.admit_time or req.submit_time))

    @staticmethod
    def _fetch(tree):
        """ONE device→host transfer for the window's sampled tokens —
        the engine's sync seam (counted as `host_syncs`; wrapped by the
        cadence tests)."""
        return jax.device_get(tree)

    def _set_slot_mask(self, slot: int, req: GenRequest):
        """Write `req`'s CURRENT FSM state's legal-vocab row into the
        host mask grid and flag the upload. Called at activation and
        after every host FSM transition to a NEW state; a self-loop
        transition skips it, so grammars that sit in one state (`a*`)
        upload exactly once — the `mask_uploads` pin. The FSM's vocab
        may be narrower than the padded grid; the padding columns stay
        False (padded vocab ids are never legal)."""
        row = self._masks[slot]
        row[:] = False
        tbl = req.fsm.mask_table[req.fsm_state]
        row[:tbl.shape[0]] = tbl
        self._mask_state[slot] = req.fsm_state
        self._masks_dirty = True

    def _build_round_masks(self, grid, g0, k: int):
        """Host pre-walk for ONE speculative verify round under
        grammar: for each structured slot whose drafter guessed t0
        (g0[slot] >= 0), step its FSM along [g0, d_1..d_k] and emit
        the per-position legal-vocab masks the device verify applies
        (verify_draft_probs). Returns (draft_masks [S, k, Vp] device
        bool, guess0 [S] device int32 — -1 where the round carries no
        usable guess, which makes the device's acceptance gate inert
        for that row).

        Free rows keep all-True masks and guess0 = -1: their drafts
        verify exactly as before (the gate never fires), so mixed
        structured/free traffic shares the one verify trace. An
        FSM-illegal draft (or a guess the FSM rejects outright)
        truncates `grid` IN PLACE from that position — proposing
        tokens the masks already outlaw would only burn verify accept
        probability."""
        S, Vp = self.num_slots, self.cfg.padded_vocab_size
        dm = np.ones((S, k, Vp), np.bool_)
        g0_eff = np.full(S, -1, np.int32)
        for slot in np.nonzero(self._mask_state >= 0)[0]:
            req = self._slot_req[slot]
            if req is None or req.fsm is None:
                continue
            fsm = req.fsm
            g = int(g0[slot])
            if g < 0:
                # no guess → no drafts proposed for this slot either
                # (build_draft_rounds proposes one continuation); the
                # t0 sample still runs under the slot's resident mask
                continue
            g0_eff[slot] = g
            cur = fsm.step(req.fsm_state, g)
            if cur < 0:
                # the guess itself is illegal: the device CANNOT
                # sample it (t0 is masked), so the gate rejects the
                # round's drafts no matter what — drop them now
                grid[slot, :] = -1
                continue
            V = fsm.mask_table.shape[1]
            for j in range(k):
                d = int(grid[slot, j])
                if d < 0:
                    break
                dm[slot, j, :] = False
                dm[slot, j, :V] = fsm.mask_table[cur]
                nxt = fsm.step(cur, d)
                if nxt < 0:
                    # draft leaves the grammar: truncate — positions
                    # past an illegal draft can never commit anyway
                    # (left-to-right acceptance)
                    grid[slot, j:] = -1
                    break
                cur = nxt
        return jnp.asarray(dm), jnp.asarray(g0_eff)

    def _step(self):
        """K chained decode/verify dispatches + ONE host sync +
        bookkeeping.

        With decode_sync_interval=1 this is the classic per-token sync.
        With K>1 the host enqueues K calls back-to-back — each consumes
        the previous call's device outputs, so XLA runs them gap-free —
        and fetches all K token grids in one transfer. The host then
        consumes each slot's tokens in order; a request hitting EOS/max
        at inner step r discards the trailing K-1-r steps (its slot
        burned them as `wasted_decode_steps` — the documented cost of
        the batched sync) and evicts at the boundary. Per-request
        streams are token-exact vs K=1: slot rng/logits/KV chains never
        cross slots or sync boundaries.

        With `speculative_k` each chained step is a draft/verify round
        (`_verify_fn`): the window's draft grids are proposed UPFRONT
        from the host-known committed history (spec_decode.
        build_draft_rounds — later rounds draft under the optimistic
        full-accept alignment; a wrong guess just gets rejected), each
        round commits 1 + accepted tokens per live slot, and accept
        counts + the residual carry chain on device between syncs. A
        round with no real draft from any running slot dispatches the
        cheaper plain decode step instead (`spec_fallback_steps`) —
        which consumes the residual carry too, so fallback never skews
        a stochastic stream.

        Structured rows pin the window to K=1: a grammar row's mask
        for token t+1 depends on token t (host FSM step), so chaining
        plain decode dispatches under a stale mask would commit
        illegal tokens. Speculative verify still commits up to 1+k
        tokens per window — the host pre-walks the draft masks along
        the drafter's guess (spec_decode.build_draft_rounds) — so
        throughput recovery under grammar comes from `speculative_k`,
        not from the sync interval.

        Returns K, the dispatches this window chained."""
        structured_on = bool((self._mask_state >= 0).any())
        K = 1 if structured_on else self._sync_interval
        inj = get_fault_injector()
        if inj is not None:
            # serving fault points (resilience/faults.py): stall the
            # loop (watchdog bait), crash the iteration (supervisor
            # bait), or NaN-poison ONE active slot's carried logits so
            # the non-finite guard catches a REAL poisoned sample
            call = inj.next_serve_step()
            inj.maybe_serve_delay(call)
            inj.check_serve_crash(call)
            # state-corruption faults (chaos-mesh coverage of the
            # checksum gates): flip bytes in a demoted host-tier KV
            # entry / a demoted host adapter copy so the CRC verify
            # paths have REAL corruption to catch — a corrupt demotion
            # must degrade to a miss, never to wrong tokens/weights
            if inj.serve_host_corrupt(call) and \
                    self._host_tier is not None:
                inj.corrupt_host_tier_entry(self._host_tier)
            if inj.serve_adapter_corrupt(call) and \
                    self.adapters is not None:
                inj.corrupt_adapter_host_entry(self.adapters)
            ordinal = inj.serve_nan_slot(call)
            if ordinal is not None:
                act = np.nonzero(self._active)[0]
                if len(act):
                    s = int(act[ordinal % len(act)])
                    self._last_logits = self._last_logits.at[s].set(
                        jnp.nan)
        with span("serve/step.upload"):
            if self._sampling_dirty:
                self._d_temps = jnp.asarray(self._temps)
                self._d_top_ks = jnp.asarray(self._top_ks)
                self._d_top_ps = jnp.asarray(self._top_ps)
                self._filter_live = bool(rows_need_filter(
                    self._temps, self._top_ks, self._top_ps).any())
                self._sampling_dirty = False
                self.metrics.count("sampling_uploads")
            if self._filter_live:
                # the K dispatches below each pay the two vocabulary
                # sorts (a freed slot's knobs were parked: _park_knobs)
                self.metrics.count("sample_filter_steps", K)
            if self._masks_dirty:
                # grammar masks upload ONLY when some slot's FSM state
                # actually changed since the last window (_set_slot_mask /
                # eviction hygiene) — a self-loop state (e.g. `a*`
                # mid-run) re-uses the resident device mask, which is the
                # `mask_uploads` counter pin (tests/test_structured.py)
                self._d_masks = jnp.asarray(self._masks)
                self._masks_dirty = False
                self.metrics.count("mask_uploads")
            if self._lengths_dirty or not self._active.all():
                # churn re-syncs positions from the host truth; partially
                # active grids also re-park idle rows each window (at 0 for
                # hard-freed slots, at their final length for retained
                # ones) so their device-side drift stays bounded by K
                self._d_lengths = self._upload_chained(self._lengths)
                # the residual carry re-uploads with the lengths: the host
                # mirror is exact at boundaries (it rides the window fetch)
                # and churn sites rewrite it before setting the dirty flag
                self._d_reject = self._upload_chained(self._reject)
                # per-slot adapter rows change only on the same churn
                self._d_adapter_idx = jnp.asarray(self._adapter_idx)
                self._lengths_dirty = False
        spec_k = self._spec_k
        if spec_k and self.degrade is not None \
                and self.degrade.spec_disabled():
            # brownout level 1+ (serving/degrade.py): speculative
            # decoding is the first service to go — forcing the
            # window's effective spec_k to 0 makes every round below
            # take the plain _decode path, which is pinned
            # bit-identical to a non-speculative engine (and consumes
            # the residual carry), so running streams switch
            # mid-window without a token changing. No draft building,
            # no spec_rounds/spec_fallback_steps: a degraded window's
            # metrics read exactly like a non-speculative engine's.
            spec_k = 0
        spec_round = [False] * K
        grids = None
        guesses = None
        if spec_k:
            with span("serve/step.draft"):
                # draft proposal (host, once per window): per-slot
                # committed history -> per-round [S, spec_k] grids. Draft
                # state lives only inside this window — droppable by
                # construction. Hand the drafter only the tail it can use
                # (its scan_window, when it declares one): rebuilding the
                # FULL prompt+generated list per slot per window would be
                # O(context) python work on the dispatch thread at long
                # contexts, for tokens the drafter immediately discards.
                win = getattr(self.drafter, "scan_window", None)
                histories: List[Optional[List[int]]] = \
                    [None] * self.num_slots
                for slot in np.nonzero(self._active)[0]:
                    req = self._slot_req[slot]
                    if win is not None and len(req.generated) >= win:
                        histories[slot] = req.generated[-win:]
                    elif win is not None:
                        histories[slot] = (
                            req.prompt[-(win - len(req.generated)):]
                            + req.generated)
                    else:
                        histories[slot] = req.prompt + req.generated
                grids, spec_round, guesses = build_draft_rounds(
                    histories, self.drafter, spec_k, K)
        if self._attend_rows:
            self._count_kv_blocks(spec_round, spec_k)
        # rows prefilled since the last window hold no token yet. Where
        # round 0 is a plain decode round their first token is drawn
        # ahead of it and handed over while it runs; a verify round draws
        # its window sample its own way and keeps them for the commit
        fresh = [] if spec_round[0] else [
            int(s) for s in np.nonzero(self._active)[0]
            if not self._slot_req[s].generated]
        with span("serve/step.dispatch"):
            if fresh:
                # ahead of the decode dispatch, which donates what it reads
                self._await_program(("draw",))
                drawn = _draw_ahead_jit(*self._draw_args(),
                                        vocab_size=self.cfg.vocab_size)
            # adapter bank args: the stacked factor pytree + per-slot rows
            # (None/None with adapters off — the empty-pytree args lower to
            # exactly the pre-adapter graph)
            lora = self.adapters.stacked if self._adapters_on else None
            d_aidx = self._d_adapter_idx if self._adapters_on else None
            tok_steps, lp_steps, acc_steps = [], [], []
            for r in range(K):
                if spec_round[r]:
                    if structured_on:
                        # host pre-walk: step each structured row's FSM
                        # along [guess0, d_1..d_k] into per-position
                        # verify masks (truncates grids[r] in place at
                        # the first illegal draft — do this BEFORE the
                        # grid uploads)
                        d_dm, d_g0 = self._build_round_masks(
                            grids[r], guesses[r], spec_k)
                    else:
                        d_dm, d_g0 = self._d_free_dmask, self._d_no_guess
                    self._await_program(("verify",))
                    out = self._verify(
                        self._p_dec, self.pool.caches,
                        self._last_logits, self._rngs, self._d_lengths,
                        self._d_temps, self._d_top_ks, self._d_top_ps,
                        jnp.asarray(grids[r]), self._d_reject,
                        self._d_masks, d_dm, d_g0, lora, d_aidx)
                    acc_steps.append(out[5])
                    self.metrics.count("spec_rounds")
                else:
                    self._await_program(("decode",))
                    out = self._decode(*self._decode_args())
                    acc_steps.append(None)
                    if spec_k:
                        self.metrics.count("spec_fallback_steps")
                (self.pool.caches, self._last_logits, self._rngs) = out[:3]
                self._d_lengths = out[-2]
                self._d_reject = out[-1]
                tok_steps.append(out[3])
                lp_steps.append(out[4])
        # the rows this window decodes: a prompt admitted while it runs
        # is active by the commit, and none of them
        window = np.nonzero(self._active)[0]
        if self._awaiting:
            # a window between a chunked prompt's first program and the
            # window that draws its first token
            drawing = [self._slot_req[s].record for s in window]
            for row in self._awaiting_first():
                if row.programs and row not in drawing:
                    row.windows_between += 1
        early = {}
        if fresh:
            with span("serve/step.first"):
                # returns when the prefill and the draw are done: the
                # window is queued behind them and the device goes on
                early = self._deliver_first(fresh, *jax.device_get(drawn))
            self._unfetched = [0, 0]    # the draw read what they wrote
        done_programs, done_rows = self._unfetched
        self._behind_window = []
        with span("serve/step.fetch"):
            fetched, admit_error = self._fetch_admitting(
                (tok_steps, lp_steps,
                 [x for x in acc_steps if x is not None], self._d_reject),
                plain=not (structured_on or spec_k))
        # what was dispatched ahead of the window is finished; a program
        # admitted while it ran is not, and has the device from now
        self._unfetched = [self._unfetched[0] - done_programs,
                           self._unfetched[1] - done_rows]
        behind, self._behind_window = self._behind_window, None
        if behind:
            now = time.monotonic()
            for row in behind:
                row.t_device = now
        with span("serve/step.commit") as sp:
            sp.set_metadata(tokens=self._commit(
                fetched, K, spec_round, grids, window, early))
        if admit_error is not None:
            # as from the iteration's own `_admit`, once the window's
            # rows have their tokens
            raise admit_error
        return K

    def _fetch_admitting(self, tree, plain: bool):
        """The window's fetch, and the one place a prompt is admitted
        inside an iteration. The window is dispatched whole by now (its
        dispatch donates what it reads; nothing goes between its
        rounds), so a prefill dispatched here is queued on the device
        behind it and starts as its last program ends. Where that may
        happen the fetch runs on a helper thread and this one waits for
        it and for a submission alike (`scheduler.notify` is `_wake`);
        where it may not, whatever lands, this is the fetch alone.

        `plain`: every round of the window is a plain decode round, no
        row under a grammar (after one, every row's residual carry is
        -1 on the device, which is what a fresh row's is on the host,
        so the commit's mirror of it is right for a row admitted here
        too). A free slot and an empty `_prefilling` cannot come about
        while the window runs; a pending swap, a drain and the
        watchdog's flag are looked at again as a prompt lands
        (`_admit_early`). One attempt a window: one program between two
        windows. Returns (the fetched tree, the exception an early
        admission raised or None)."""
        if not plain or self._disagg or not self.pool.free_count():
            return self._fetch(tree), None
        if self._prefilling:
            # a chunk, a prefix hit or a resume is owed the next
            # iteration's program: a prompt that landed meanwhile waits
            # for the iteration, as it always did
            fetched = self._fetch(tree)
            waiting = self.scheduler.queued()
            if waiting:
                self.metrics.count("early_admit_declined_prefilling")
                for req in waiting:
                    req.record.held += 1
            return fetched, None
        if self._fetcher is None:
            self._fetcher = ThreadPoolExecutor(
                1, thread_name_prefix="serving-fetch")
        fut = self._fetcher.submit(self._fetch, tree)
        fut.add_done_callback(lambda _: self._wake())
        error, untried = None, True
        while True:
            with self._cond:
                while not fut.done() and not (
                        untried and self.scheduler.depth()):
                    self._cond.wait(timeout=self._idle_wait)
            if fut.done():
                return fut.result(), error
            untried = False
            error = self._admit_early()

    def _admit_early(self) -> Optional[Exception]:
        """`_admit` from inside a decode window, for one prefill program
        (`_fetch_admitting`). `_admit` fails what it had popped and
        releases its pins before it raises; the exception is handed
        back for `_step` to raise once the window is committed."""
        if self._pending_swap is not None or self._draining \
                or self._stop or self._wedged:
            return None
        try:
            with span("serve/admit", early=1) as sp:
                sp.set_metadata(popped=self._admit(early=True))
        except Exception as e:  # noqa: BLE001 — raised after the commit
            return e
        finally:
            self._heartbeat()  # the prefill may compile
        return None

    def _append_token(self, req: GenRequest, tok: int, lp: float):
        """Append one token; a request's first also records its TTFT
        and counts it against the TTFT SLO."""
        first = not req.generated
        req.append_token(tok, lp)
        if first:
            req.record.t_first = req.first_token_time
            if self._slo_ttft_s is not None \
                    and req.ttft > self._slo_ttft_s:
                self.metrics.count("slo_ttft_violations")

    def _deliver_first(self, fresh, toks, lps) -> dict:
        """Hand each fresh row's first token to its request ahead of the
        window that commits it. Only what `_commit` would append: a row
        whose log-probability is not finite or whose grammar is at a dead
        end, and every row of a session the watchdog flagged, is left to
        `_commit` whole. Returns {slot: token} of the rows delivered."""
        early = {}
        if self._wedged:
            return early
        for slot in fresh:
            tok, lp = int(toks[slot]), float(lps[slot])
            if tok >= 0 and math.isfinite(lp):
                self._append_token(self._slot_req[slot], tok, lp)
                early[slot] = tok
        self.metrics.count("first_tokens_early", len(early))
        return early

    def _commit(self, fetched, K: int, spec_round, grids, active_slots,
                early) -> int:
        """The host's half of a decode window, after the fetch: append
        each slot's tokens in order, step its FSM, evict what finished,
        set the gauges. `active_slots` are the rows the window decoded
        (a prompt admitted while it ran is active by now and has no
        token in it). A row in `early` has its first token already
        (`_deliver_first`): round 0's token is that token, checked and
        not appended again, and everything else runs on it as on any
        other. Returns the tokens delivered."""
        self.metrics.count("host_syncs")
        if self._wedged:
            # the watchdog flagged THIS iteration while it was in
            # flight and already failed the slotted futures — do not
            # consume results computed on state we no longer trust
            raise EngineHungError(
                "engine iteration exceeded the watchdog deadline "
                "mid-dispatch")
        toks = [np.asarray(t) for t in fetched[0]]   # [S] or [S, k+1]
        tok_lp = [np.asarray(l) for l in fetched[1]]
        accs_flat = iter(fetched[2])
        accs = [np.asarray(next(accs_flat)) if s else None
                for s in spec_round]  # per-round accept counts [S]
        if self._spec_trace is not None:
            # test seam: per-round (window tokens, accept counts) so
            # the exactness pin can REPLAY the verify pipeline serially
            # (accs[r] is None for a fallback decode round)
            for r in range(K):
                self._spec_trace.append((toks[r], accs[r]))
        # host mirror of the residual carry — exact as of this boundary
        self._reject = np.asarray(fetched[3]).astype(np.int32).copy()
        n_active = len(active_slots)
        consumed = np.zeros(K, np.int64)  # tokens delivered per step
        # the host-visible commit moment for this whole sync window —
        # what an SSE consumer's inter-token gap actually measures
        # (per-token timestamps inside a window would be fiction: the
        # K steps land on the host together)
        commit_t = time.monotonic()
        for slot in active_slots:
            req = self._slot_req[slot]
            done = False
            had_tokens = len(req.generated) - (slot in early)
            for r in range(K):
                if done:
                    break
                if accs[r] is not None:
                    # verify round: 1 + accepted committed tokens (the
                    # window sample + the accepted draft prefix); the
                    # k - accepted rejected drafts were never committed
                    # (their KV is overwritten write-before-read).
                    # draft_tokens counts proposals for LIVE rows only;
                    # accepted_tokens counts draft commits actually
                    # DELIVERED (EOS/budget discards don't inflate the
                    # acceptance-rate seam).
                    a = int(accs[r][slot])
                    row_toks = toks[r][slot, :1 + a]
                    row_lps = tok_lp[r][slot, :1 + a]
                    n_drafts = int((grids[r][slot] >= 0).sum())
                    if n_drafts:
                        self.metrics.count("draft_tokens", n_drafts)
                else:
                    row_toks = toks[r][slot:slot + 1]
                    row_lps = tok_lp[r][slot:slot + 1]
                for j in range(len(row_toks)):
                    lp = float(row_lps[j])
                    if not math.isfinite(lp):
                        # per-slot non-finite guard: NaN/inf logits
                        # poison ONE request (numerical blowup,
                        # injected fault), not the engine — fail it,
                        # free the slot, keep every other slot decoding
                        self.metrics.count("nonfinite_logit_fails")
                        if K - 1 - r:
                            self.metrics.count("wasted_decode_steps",
                                               K - 1 - r)
                        self._evict(
                            slot,
                            failed=(f"non-finite logits at position "
                                    f"{int(self._lengths[slot])} "
                                    f"(after {len(req.generated)} "
                                    "tokens); the poisoned request "
                                    "failed, the engine continues"),
                            kind="nonfinite")
                        done = True
                        break
                    tok = int(row_toks[j])
                    if req.fsm is not None and tok < 0:
                        # grammar dead end: EVERY candidate token is
                        # masked out at this state (sample_batched's
                        # all-False sentinel) — the request fails
                        # typed (GrammarDeadEndError → 422), the slot
                        # frees, every other slot keeps decoding
                        self.metrics.count("grammar_dead_ends")
                        if K - 1 - r:
                            self.metrics.count("wasted_decode_steps",
                                               K - 1 - r)
                        self._evict(
                            slot,
                            failed=("grammar dead end: every "
                                    "candidate token is masked out "
                                    "at FSM state "
                                    f"{req.fsm_state} (after "
                                    f"{len(req.generated)} tokens)"),
                            kind="grammar")
                        done = True
                        break
                    if r == 0 and j == 0 and slot in early:
                        if tok != early[slot]:
                            # the step drew another token than the one
                            # already handed over: the stream cannot be
                            # both, so the request fails
                            self.metrics.count("first_token_mismatches")
                            if K - 1:
                                self.metrics.count("wasted_decode_steps",
                                                   K - 1)
                            self._evict(
                                slot,
                                failed=("first token mismatch: "
                                        f"{early[slot]} was delivered "
                                        f"ahead of the step that drew "
                                        f"{tok}"))
                            done = True
                            break
                    else:
                        self._append_token(req, tok, lp)
                    self._lengths[slot] += 1
                    consumed[r] += 1
                    if j > 0:
                        self.metrics.count("accepted_tokens")
                    fsm_done = False
                    if req.fsm is not None:
                        ns = req.fsm.step(req.fsm_state, tok)
                        if ns < 0:
                            # defensive: a masked sample can only be
                            # FSM-legal, so an illegal commit means
                            # host/device mask state diverged — fail
                            # the request, never emit illegal text
                            self.metrics.count("grammar_dead_ends")
                            if K - 1 - r:
                                self.metrics.count(
                                    "wasted_decode_steps", K - 1 - r)
                            self._evict(
                                slot,
                                failed=("grammar violation: token "
                                        f"{tok} is illegal at FSM "
                                        f"state {req.fsm_state}"),
                                kind="grammar")
                            done = True
                            break
                        req.fsm_state = ns
                        # a state with no legal NON-EOS continuation
                        # finishes the request here — eos-less models
                        # (eos_id=None/-1) would otherwise dead-end
                        # on the very next step
                        fsm_done = req.fsm.is_terminal(ns)
                    if (tok == self.gen.eos_id or fsm_done
                            or len(req.generated)
                            >= req.max_new_tokens):
                        if K - 1 - r:
                            self.metrics.count("wasted_decode_steps",
                                               K - 1 - r)
                        self._evict(slot)
                        done = True
                        break
                    if (req.fsm is not None
                            and self._mask_state[slot]
                            != req.fsm_state):
                        # refresh the slot's device mask row for the
                        # NEW state; a self-loop (state unchanged)
                        # skips this — no upload next window
                        self._set_slot_mask(slot, req)
            if self._slo_itl_s is not None \
                    and len(req.generated) > had_tokens:
                # inter-token-latency SLO: one check per slot per
                # window against the gap since the slot's PREVIOUS
                # commit window (the first window's gap is TTFT
                # territory, counted above)
                prev = getattr(req, "_last_commit_t", None)
                if prev is not None \
                        and commit_t - prev > self._slo_itl_s:
                    self.metrics.count("slo_itl_violations")
                req._last_commit_t = commit_t
        self._steps += K
        # attention-path A/B gauges: bytes any resolve/scatter
        # full-pool bracket moved this window, averaged per step.
        # Bracketed block-pool dispatches pay ONE view gather + ONE
        # view scatter each; the block-native kernel (and whole-region
        # pools) pay none — so "kernel on => kv_gather_bytes_per_step
        # == 0" is a host-pinnable assertion (prefill brackets
        # accumulated in _bracket_bytes fold into the same window)
        window_bracket = self._bracket_bytes
        self._bracket_bytes = 0
        if self._blocks_on and not self._kernel_on:
            window_bracket += K * 2 * self._view_bytes
        self.metrics.set_attn_gauges(window_bracket // K,
                                     self._attn_path)
        # chip-group occupancy gauges (disaggregated A/B seam — also
        # meaningful single-group: prefill pending vs slot occupancy)
        self.metrics.set_group_gauges(
            1.0 if self._prefilling else 0.0,
            n_active / max(self.num_slots, 1))
        depth = self.scheduler.depth()
        for k in range(K):
            self.metrics.record_step(n_active, self.num_slots,
                                     int(consumed[k]), depth)
        # KV-pool occupancy/fragmentation gauges (host accounting
        # only — no device sync): blocks in use / pinned by retention,
        # and reserved-minus-live bytes (the fragmentation gauge the
        # block-granular pool exists to shrink). Recomputed only after
        # pool churn — the coverage walk is O(blocks) host python, and
        # a churn-free decode window moves the gauges only through
        # per-slot live lengths (waste drifts a few tokens at most)
        if self._kv_dirty:
            self.metrics.set_kv_gauges(
                *self.pool.kv_gauges(self._lengths))
            if self.adapters is not None:
                self.metrics.set_adapter_gauge(
                    self.adapters.active_count())
            self._kv_dirty = False
        if self._writer is not None and \
                self._steps % self._report_interval < K:
            self.metrics.report(self._writer, self._steps)
        return int(consumed.sum())
