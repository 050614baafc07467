"""The program's host spans: `jax.profiler.TraceAnnotation`s named `mtpu/...`.

A span exists only while a profiler session is on (`--profile` on a training
job, `PUT /admin {"op": "trace"}` on a server, or whoever calls
`jax.profiler.start_trace` round the code). It then lands in the profiler's
trace beside the device's events, on the same clock. With no session it is a
C++ "is anyone tracing" check. The loops have no recorder, flag or option: a
span there runs thousands of times a second and a list of them would grow for
the life of the process.

Two things have a recorder, both on `time.monotonic()`, both always on and
both bounded: the process's start (below), which happens once, and a request
(further down), of which a server sees a few a second where its loop turns
thousands of times.

Start-up, because no profiler session is ever on round it. `phase(name)` is a
span `mtpu/setup/<name>` AND a row `(name, start, end)` on `time.monotonic()`
in the process's start-up record, which `startup_record()` returns. It is for
code that runs once a process: no loop body calls it. `ready()` stamps the
moment the process can do its work (a server as it starts to listen, a
training job as its first step's flush returns), prints one line, and closes
the record: from then on, and past `MAX_PHASES` rows in any case, `phase` is
the span alone (a hot swap's `load` or a re-plan's `engine.programs` shows in
a profile and adds no row; `startup_record()["dropped"]` counts them). What
compiling cost is not here but in `utils/compile_cache.py`'s ledger, which
JAX's own events fill: an engine's programs compile on its compile pool from
the moment the engine knows of them (`serving/engine.py`, "Compile-ahead"),
beside the loop, and no phase goes there. How the loops found their programs
is in the record all the same, as four counters (`PROGRAM_COUNTERS`:
`programs_compiled_ahead`, `programs_awaited` with `programs_awaited_s`,
`programs_compiled_inline`), counted by `ServingEngine._await_program` once a
program and summed over the process's engines; each engine's own are in its
`/metrics`.

| phase | round what |
|---|---|
| `mtpu/setup/mesh` | `parallel.mesh.build_mesh` |
| `mtpu/setup/init_state` | `finetune.init_state`: the train state born sharded (its jit, its run, the wait for it) |
| `mtpu/setup/load` | a checkpoint read and placed (`checkpointing.load_checkpoint`, `serving/weights.py::load_staged`) |
| `mtpu/setup/data` | `finetune.build_data`: index maps built or mapped, the iterators made |
| `mtpu/setup/first_step` | `loop.train` from entry to the return of the first step's flush (trace, lower, compile or load, run), closed at the once-only site that reports memory "after first step" |
| `mtpu/setup/generator` | `Generator.__init__` (the rotary tables: ROADMAP S22) |
| `mtpu/setup/engine` | `ServingEngine.__init__`; children `engine.pool` (the KV pool's allocation) and `engine.programs` (`_compile_programs`, the resident uploads) |

`startup_scalars()` is what `/metrics` and the training writer show of both:
`startup_seconds` (process start to `ready()`, 0 before), `compile_programs`,
`compile_seconds`, `compile_cache_hits`, `compile_cache_misses` (the ledger's
totals now) and `compiles_after_ready`, the programs compiled or loaded since
`ready()`: in a steady state it stays where it is; right after a start it
counts the programs of the shapes the first users brought (compiled beside
the loop; what they waited is `programs_awaited_s`). Beside them
`grad_accum_fused_share`, fixed as `training/train_step.py` traces its step:
of the bytes of the float32 gradient accumulators, the share whose leaf's
gradient leaves the backward pass already added to it (`ops/grad_accum.py`);
the rest is added in a pass of its own once a micro-batch. 1.0 where every
leaf's forward goes through `language_model.loss_fn` or `transformer.py`'s
scans, 0.0 for a step of one micro-batch (which keeps no accumulator) and in
a process that traced no step.

A request's own record: one `RequestRow` a request, where its first token's
time went. The serving engine makes the row with the request, puts it into its
ring where the request is admitted (at its end, for one that never is), fills
it in place from stamps it takes where the work happens, and closes it at the
one place every request ends (`ServingEngine._count_terminal`). The ring is
`ServingMetrics.requests`, `MAX_REQUEST_ROWS` long, and `/metrics`' `ttft_*`,
`queue_wait_*` and `latency_*` percentiles are made from its rows: a live
engine's see a first token when it is drawn. `request_record()` hands out
copies of the rows of the process's last `MAX_ENGINES` engines, a closed
engine's too; each engine takes a small integer as it registers its ring
(`keep_requests`) and its rows carry it. No flag and no option. A request costs
a dozen clock readings and one row, a decode window one clock reading where a
prompt was admitted inside it, a token nothing. All stamps are
`time.monotonic()`'s; one that could not be taken is `None` and the row is
still written.

| field | taken where (`serving/engine.py`) | what |
|---|---|---|
| `engine`, `seq` | `RequestRing.keep` | the engine's integer; the row's ordinal in its ring |
| `rid`, `outcome`, `prompt_tokens`, `generated` | `_count_terminal` (`rid` with the row) | `GenRequest.id`; "completed", "failed", "cancelled" or "expired", `None` while it lives; lengths |
| `t_submit`, `t_first`, `t_finish` | `GenRequest`'s own stamps, copied with the row, in `_append_token` and in `_count_terminal` | |
| `t_admit` | `_mark_admitted`, `GenRequest.mark_admitted`'s stamp: behind the dispatch of a group's program, ahead of a chunked prompt's first chunk | |
| `early` | `_mark_admitted` | 1 if placed while a decode window ran (`_fetch_admitting`, `_admit_early`) |
| `held` | `_fetch_admitting`, where it counts `early_admit_declined_prefilling` | windows that ended with THIS request queued because a chunk, a prefix hit or a resume was owed the next program (the counter counts windows; the row says whose prompt paid) |
| `t_device` | `_step`, as the fetch of the window that ran at `t_admit` returns (`step.fetch`'s end); `t_admit` itself where no window was in flight | the first moment the host knows the device had nothing older than this request's first program in front of it, other prompts' prefill programs apart (`ahead_*`) |
| `programs`, `rows` | `_note_prefill`, from `_prefill_group` and `_prefill_one_chunk` | this request's own prefill programs up to its first token (1, or its chunks) and their padded rows (batch bucket x padded length) |
| `ahead_programs`, `ahead_rows` | `_mark_admitted` and `_note_prefill` | prefill programs of OTHER requests, and their padded rows, that stood between `t_device` and this request's first token: those dispatched and not yet known finished as it was admitted, and those dispatched between its admission and its first token (another prompt's group or chunk, ahead of its own first chunk or between two of them) |
| `windows_between` | `_step` | decode windows dispatched between its first program and the one that draws its first token (0 for an unchunked prompt) |
| `prefix_hit_tokens`, `preempted` | `_start_pending`, `_preempt` | as the engine's counters `prefix_hit_tokens` and `preemptions` count them, for this request |

From a row (`RequestRow.segments()`): queue = `t_admit - t_submit`; behind the
window = `t_device - t_admit`; prefill = `t_first - t_device` (its own
programs, whatever ran between them, the draw, the fetch and the hand-over);
decode = `t_finish - t_first`. The first three tile `[t_submit, t_first]`.
Which benchmark metric reads which field: PERF.md section 3.

A span is a `with` block on the thread that does the work; nesting gives the
parent. Names are constant strings, stats are integers: keyword arguments for
what is known on entry, `set_metadata(...)` on the span for what is known only
on exit (marked + below). `rid` is `GenRequest.id`: the spans of one request
share it.

Serving (`serving/engine.py`, engine thread unless said):

| span | round what | stats |
|---|---|---|
| `mtpu/serve/idle_wait` | the `_cond.wait` loop at the top of `_session`: nothing queued, active or prefilling | |
| `mtpu/serve/iteration` | one pass of the loop's body, `_iteration`; parent of all below but `submit` | `active`, `queued` |
| `mtpu/serve/reap` | `_maybe_decay_restarts`, `_reap_cancelled`, `_reap_expired`, `_evaluate_degrade` | |
| `mtpu/serve/admit` | `_preempt_for_priority` + `_admit` (pop, adapter, prefix lookup, grouping). With `early=1`: `_admit` alone, for a prompt that landed while a decode window ran (`_admit_early`), inside that window's `step.fetch` | `popped`+, `early` |
| `mtpu/serve/prefill` | each `_prefill_group` call (host arrays, the group's sampling keys as one compiled call, `_initial_rngs`, then the dispatch), child of `admit`. With `early=1` it holds nothing: `_iteration` opens it where `_advance_prefill` would run, for the program the window before admitted while it ran. It begins where the device runs that program (behind that window, ahead of the next), so between two `step.commit` starts there is still the span of the prefill that lengthened the interval | `n`, `padded`, `rid` of the first, `early` |
| `mtpu/serve/prefill.early` | the `_prefill_group` call of an early admission, child of `admit` with `early=1`: the host's work and the dispatch, while the device runs the window | `n`, `padded`, `rid` of the first |
| `mtpu/serve/prefill_chunk` | `_advance_prefill` when it dispatches, `_activate_pending` included | `rid`, `tokens`+ |
| `mtpu/serve/swap` | `_apply_swap` | |
| `mtpu/serve/step` | `_step`; parent of the six below | `active`, `K`+ |
| `mtpu/serve/step.upload` | the dirty sampling / mask / lengths / adapter-row uploads | |
| `mtpu/serve/step.draft` | `build_draft_rounds` (only entered with `speculative_k`) | |
| `mtpu/serve/step.dispatch` | the chain of K `_decode` / `_verify` calls, behind the draw of the fresh rows' first tokens (`_draw_ahead`) in a window that has any | |
| `mtpu/serve/step.first` | only in a window with fresh rows whose round 0 is a plain decode round: the fetch of that draw (it returns when the prefill and the draw are done, the window queued behind them) and `_deliver_first`, which hands each token to its request | |
| `mtpu/serve/step.fetch` | `_fetch_admitting`: the host waits for the window's tokens (`self._fetch(...)`, on a helper thread where a prompt may be admitted meanwhile), the device works; parent of an `admit` with `early=1` | |
| `mtpu/serve/step.commit` | `_commit`, everything after the fetch: per-slot token append, FSM, evictions, gauges, writer | `tokens`+ |
| `mtpu/serve/submit` | `submit()`, on the caller's thread | `rid`+ |

Training (`training/loop.py`, main thread):

| span | round what | stats |
|---|---|---|
| `mtpu/train/data_next` | each pull from the iterator with its lift, the in-step pull and the look-ahead pull | |
| `mtpu/train/step` | the `step_fn(...)` dispatch; a `StepTraceAnnotation` | `step_num` |
| `mtpu/train/flush` | the metrics window's `_device_fetch` | |
| `mtpu/train/eval` | `evaluate(...)` | |
| `mtpu/train/save` | `save_fn(...)` | |

On the device (`jax.named_scope`, so in the `op_name` of every HLO instruction
traced under it; models/moe.py, models/attention.py, models/mla.py,
models/hyper_connections.py, models/mamba.py, models/mamba2.py,
models/kda.py, models/gated_delta.py and models/rope.py):

| scope | round what |
|---|---|
| `mtpu/moe/route` | a dropless expert layer's router product, softmax, top-k and aux loss (every dispatch), the sort of the (token, k) rows by expert, the group sizes, the gather of the sorted rows |
| `mtpu/moe/experts` | the weight casts, the two grouped products (ops/grouped_matmul.py) and the activation between them |
| `mtpu/moe/share` | the same where the chip holds a share of the layer's experts (`moe_router_experts` wider than `num_experts`): the products of the held experts' rows alone and the zeroing of the rows behind the last group |
| `mtpu/moe/combine` | the gather back to (token, k) order and the weighted sum of a token's K rows |
| `mtpu/attn/qk_norm` | the RMSNorm over the whole q and the whole k projection (`qk_norm`) |
| `mtpu/attn/window` | a window layer of a stack of two kinds over its ring (`attention.HybridKVCache`): the ring turned into time order, the flash kernel or the scores over ring + chunk, the rows' write over the oldest; a decode step's write and read of a layer of rings |
| `mtpu/attn/full` | a full layer of such a stack over its whole region: the write at the offset, the flash kernel or the scores over the region up to the chunk's end; a decode step's write and read of a layer of regions |
| `mtpu/attn/head_norm` | the RMSNorm over each head's channels of q and of k (`qk_head_norm`) |
| `mtpu/conv/in_proj` | a convolution layer's first product, rows x [h, 3h]: the gates B and C and the input z (`models/short_conv.py`) |
| `mtpu/conv/state` | the read of the layer's state (the kernel's last inputs, a row each slot) ahead of the rows, and the write of the state after the call's last real row, one update in place a layer |
| `mtpu/conv/mix` | B * z, the depthwise taps over [state ; rows] accumulated in float32, times C |
| `mtpu/conv/out_proj` | the layer's second product, rows x [h, h] |
| `mtpu/ssm/in_proj` | a Mamba layer's first product, rows x [h, 2 d_inner]: the scan's input x and the gate z (`models/mamba.py`) |
| `mtpu/ssm/state` | the read of the layer's two states (the depthwise kernel's last inputs, the scan's [d_state, d_inner] float32 matrix; a row each slot) ahead of the rows, and their write after the call's last real row, one update in place a layer each |
| `mtpu/ssm/conv` | the depthwise taps over [state ; rows] accumulated in float32, the bias, SiLU |
| `mtpu/ssm/params` | rows x W_x [d_inner, dt_rank + 2 d_state], the three RMS norms, dt's product with W_dt in float32, softplus, the padding rows' step size set to 0, A = -exp(A_log) |
| `mtpu/ssm/scan` | the recurrence: the kernel `_ssm_selective_scan` for a prefill or a chunk (ops/selective_scan.py; its lane spread of B and C), the one-step update over the pool's layer for a decode step, the `lax.scan` with no cache; y gated by SiLU(z) inside |
| `mtpu/ssm/out_proj` | the layer's second product, rows x [d_inner, h] |
| `mtpu/ssd/in_proj` | a Mamba-2 layer's first product, rows x [h, d_inner + (d_inner + 2 G N) + H]: the gate z, the depthwise kernel's input (x, B, C) and the step sizes (`models/mamba2.py`) |
| `mtpu/ssd/state` | the read of the layer's two states (the depthwise kernel's last inputs over x, B and C; the scan's [heads, head_dim, d_state] float32 matrices; a row each slot) ahead of the rows, and their write after the call's last real row, one update in place a layer each |
| `mtpu/ssd/conv` | the ONE depthwise kernel's taps over [state ; rows] of x, B and C accumulated in float32, the bias, SiLU, the split, softplus of the step sizes in float32, the padding rows' step size set to 0, A = -exp(A_log) |
| `mtpu/ssd/scan` | the recurrence: the kernel `_ssd_chunk_scan` for a prefill or a chunk (ops/ssd_scan.py; four products a chunk a head, the running sums of dt A made outside it), the one-step update over the pool's layer for a decode step, the `einsum` form with no cache |
| `mtpu/ssd/norm` | y gated by SiLU(z), then the RMSNorm over each group's channels, float32 statistics, the learned scale |
| `mtpu/ssd/out_proj` | the layer's second product, rows x [d_inner, h] |
| `mtpu/kda/proj` | a Kimi Delta Attention layer's two first products, rows x [h, 3 H D] (q, k, v) and rows x [h, r + r + H] (the decay's and the output gate's low-rank inputs, beta) (`models/kda.py`) |
| `mtpu/kda/conv` | the read of the layer's two states (the three depthwise kernels' last inputs, the rule's [heads, head_dim, head_dim] float32 matrices; a row each slot), the taps over [state ; rows] of q, k and v accumulated in float32, SiLU, the L2 norm a head of q and k, q's 1 / sqrt(D) |
| `mtpu/kda/gate` | the log-decay a channel, -exp(A_log) softplus(f W_fb + dt_bias) in float32, beta = sigmoid, and the padding rows' decay and beta set to 0 (the rule's step is then the identity) |
| `mtpu/kda/scan` | the gated delta rule: the kernel `_kda_chunk` for a prefill or a chunk (ops/kda_chunk.py; the running sums of the decays made inside it since PR 61: what is outside the Pallas call in the jitted function is beta's transpose and the operands' reshapes), the one-row update over the pool's layer for a decode step, the recurrence with no cache; and the write of both states behind the call's last real row, one update in place a layer each |
| `mtpu/kda/out` | the RMSNorm a head, float32 statistics, ONE learned scale [D]; the output gate sigmoid(z W_gb + b); the layer's last product, rows x [H D, h] |
| `mtpu/gdn/proj` | a Gated DeltaNet layer's two first products, rows x [h, 2 H_k D + 2 H D] (q, k, v and the output gate's z) and rows x [h, 2 H] (beta's and the decay's inputs) (`models/gated_delta.py`) |
| `mtpu/gdn/conv` | the read of the layer's two states (the depthwise kernel's last inputs, the rule's [value heads, D, D] float32 matrices; a row each slot), the taps over [state ; rows] of q, k and v together accumulated in float32, SiLU, the L2 norm a head of q and k, q's 1 / sqrt(D) |
| `mtpu/gdn/gate` | the log-decay a HEAD, -exp(A_log) softplus(a + dt_bias) in float32, beta = sigmoid, and the padding rows' decay and beta set to 0 |
| `mtpu/gdn/scan` | the gated delta rule with one decay a head: the kernel `_gdn_chunk` for a prefill or a chunk (ops/kda_chunk.py, form (d): one K K^T and one Q K^T a chunk a key head on the matrix unit; the running sums of the decays made inside it since PR 61), the one-row update over the pool's layer for a decode step, the recurrence with no cache; and the write of both states behind the call's last real row |
| `mtpu/gdn/out` | the RMSNorm a head, float32 statistics, ONE learned scale [D] (the scale itself, not 1 + w); the output gate SiLU(z); the layer's last product, rows x [H D, h] |
| `mtpu/attn/gate` | the attention's output gate (`cfg.attn_output_gate`): the attention's output times sigmoid of the gate that wq's second half of a head's columns made, float32, ahead of wo (`models/attention.py`) |
| `mtpu/rope/partial` | a rotary over the first `cfg.rotary_dim` channels of a head, the others passed as they are (`models/rope.py`) |
| `mtpu/moe/latent_in` | experts in a latent (`cfg.moe_latent_size`): rows x [h, latent] ahead of the routing's gather (`models/moe.py`) |
| `mtpu/moe/latent_out` | the tokens' weighted sums x [latent, h], behind the combination |
| `mtpu/moe/shared` | the shared experts' MLP, added beside the routed sum (`n_shared_experts`), with their own gate sigmoid(x . w) a token inside it (`moe_shared_expert_gate`) |
| `mtpu/mla/q` | latent attention's query: down-projection, norm, up-projection, the rotary on its rope part |
| `mtpu/mla/latent` | the latent row: down-projection, norm over kv_lora_rank, the rotary on the shared key, the write into the cache |
| `mtpu/mla/attend_expanded` | the expanded form: keys and values of every head from the rows, causal attention from position 0 (training; a prefill at offset 0) |
| `mtpu/mla/attend_absorbed` | the absorbed form: the layer of the cache read by the scores and by the weighted sum, each head's query and output through W_uk and W_uv (decode, verify, continuation chunks) |
| `mtpu/hc/expand` | the model's input put in each of `hc_mult` streams, [b, s, hc_mult x hidden] (models/hyper_connections.py; the MTP block's input too) |
| `mtpu/hc/map` | a sublayer's maps of a token: the product of the streams with phi [hc_mult x hidden, hc_mult^2 + 2 hc_mult] accumulated in float32, the scale by the root mean square over all streams, the two sigmoids, the exponential of the clipped H~_res and the Sinkhorn rounds, the tokens minor |
| `mtpu/hc/pre` | H_pre X: the sublayer's input, one mixed stream |
| `mtpu/hc/post` | H_res X + H_post^T out: the streams behind the sublayer, summed in float32 |
| `mtpu/hc/collapse` | the streams summed ahead of the final norm (and behind the MTP block) |
| `mtpu/rope/yarn` | YaRN's blended frequencies and the cos / sin tables made from them, once, where the tables are made (`models/rope.py::yarn_freqs`) |

Counters of the serving metrics' snapshot that a benchmark reader takes:
`kv_bytes_per_token` and `kv_pool_bytes`, `SlotKVPool.bytes_per_token()` and
`.nbytes()` as the pool counts them, pushed once when the engine builds it
(`serve_kv_bytes_per_token`); beside them `kv_bytes_per_slot`, `kv_ring_bytes`
and `kv_full_bytes` (`.bytes_per_slot()`, `.ring_nbytes()`, `.full_nbytes()`:
what a slot reserves, and the pool's bytes by kind; `serve_kv_bytes_per_slot`)
and `conv_state_bytes` (`.conv_state_nbytes()`: the convolution layers' state
of every slot; a slot's share of it is `serve_state_bytes_per_slot`) and
`ssm_state_bytes` (`.ssm_state_nbytes()`: the scans' float32 matrices of every
slot; a slot's share of it is `serve_ssm_state_bytes_per_slot`), and
`ssd_state_bytes` (`.ssd_state_nbytes()`: the Mamba-2 scans' float32 matrices
a head of every slot; a slot's share of it is
`serve_ssd_state_bytes_per_slot`), and `kda_state_bytes`
(`.kda_state_nbytes()`: the delta rule's float32 matrices a head of every
slot; a slot's share of it is `serve_kda_state_bytes_per_slot`). The device
trace names the chunked rule's kernel calls `%_kda_chunk.N`
(`serve_kda_scan_ms_per_step`, `kda_chunk_roofline_pct`). `gdn_state_bytes`
(`.gdn_state_nbytes()`) is the same for a Gated DeltaNet rule's matrices a
value head (`serve_gdn_state_bytes_per_slot`), whose kernel calls are
`%_gdn_chunk.N` (`serve_gdn_scan_ms_per_step`, `gdn_chunk_roofline_pct`).
`prefill_chunks` counts the chunk programs dispatched. `admits_total`,
`admits_early` and `early_admit_declined_prefilling` (placements by `_admit`,
those made while a decode window ran, and windows that ended with a prompt
queued because a chunk was owed the next prefill program) are in the snapshot
and `/metrics`; no benchmark reader takes them yet. The rows a share's held
experts took (`moe_rows_held` of ISSUE 33) are NOT counted by the program: no
serving program hands a scalar out of the layer loop, and the benchmark counts
them with the reference's router on the window's own tokens (PERF.md section 7).

A TPU v5e's trace names an `XLA Ops` event by the HLO instruction's text, which
does not hold the `op_name` (PERF.md section 7, PR 27): the scopes are in the
trace file's HLO metadata, for a viewer, and the expert kernels are found by
their own name, `%_moe_grouped_matmul.N`.

Which benchmark metric reads which span, and which a row's field: PERF.md
section 3. How an operator reads an idle gap off a trace, and a slow first
token off `PUT /admin {"op": "requests"}`: docs/serving.md "Observability &
drills".
"""
from __future__ import annotations

import collections
import functools
import itertools
import os
import threading
import time
from typing import Deque, Dict, List, Optional

import jax

from megatron_tpu.utils import compile_cache
from megatron_tpu.utils.logging import print_rank_0


def span(name: str, **stats):
    return jax.profiler.TraceAnnotation("mtpu/" + name, **stats)


MAX_PHASES = 64


def _process_start() -> float:
    """The process's start on `time.monotonic()`'s clock: its age by the
    kernel's count (`/proc/self/stat` field 22 against `/proc/uptime`, to
    a hundredth of a second) taken off the clock's reading now, so that
    imports are inside. Where that cannot be read, now: this module's
    import."""
    now = time.monotonic()
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            age = float(f.read().split()[0]) - ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return now
    return now - age if 0.0 <= age < 86400.0 else now


class _StartupRecord:
    """The process's one record of its start (the table above)."""

    def __init__(self):
        self.lock = threading.Lock()
        self.t0 = _process_start()
        self.rows: List[list] = []         # [name, start, end or None]
        self.dropped = 0
        self.ready: Optional[float] = None
        self.programs_at_ready = 0
        self.grad_accum_fused_share = 0.0
        self.programs = dict.fromkeys(PROGRAM_COUNTERS, 0)

    def open(self, name: str) -> Optional[list]:
        with self.lock:
            if self.ready is not None or len(self.rows) >= MAX_PHASES:
                self.dropped += 1
                return None
            row = [name, time.monotonic(), None]
            self.rows.append(row)
            return row


# how a serving engine's loop found each program it reached (the engine
# counts the same in its own metrics; here over the process's engines)
PROGRAM_COUNTERS = ("programs_compiled_ahead", "programs_awaited",
                    "programs_awaited_s", "programs_compiled_inline")

_record = _StartupRecord()


def note_program(counter: str, seconds: float = 0.0) -> None:
    """`ServingEngine._await_program`, once a program: one of
    `PROGRAM_COUNTERS`, with the seconds the loop waited."""
    with _record.lock:
        _record.programs[counter] += 1
        _record.programs["programs_awaited_s"] += seconds


class phase:
    """`with phase("engine"):` or `@phase("engine")` on a function. For
    code that runs once a process (module docstring)."""

    def __init__(self, name: str):
        self.name = name
        self._span = self._row = None

    def __enter__(self):
        self._span = span("setup/" + self.name)
        self._span.__enter__()
        self._row = _record.open(self.name)
        return self

    def __exit__(self, *exc):
        if self._row is not None:
            self._row[2] = time.monotonic()
        self._row = None
        if self._span is not None:
            self._span.__exit__(*exc)
        self._span = None
        return False

    def __call__(self, fn):
        @functools.wraps(fn)
        def inside(*args, **kwargs):
            with phase(self.name):
                return fn(*args, **kwargs)
        return inside


def startup_record() -> Dict[str, object]:
    """`t0` the process's start, `rows` the phases as (name, start, end)
    in the order they began (`end` None while one is open), `ready` the
    stamp or None, `dropped` the phases that added no row; all clock
    readings are `time.monotonic()`'s. And the `PROGRAM_COUNTERS`: of the
    programs the process's serving loops reached, how many they found
    compiled by the engine's pool, waited for (with the seconds) and
    compiled themselves."""
    with _record.lock:
        return {"t0": _record.t0, "ready": _record.ready,
                "rows": [tuple(r) for r in _record.rows],
                "dropped": _record.dropped, **_record.programs}


def ready() -> float:
    """Stamp the moment the process can do its work, once: a second call
    returns the first stamp and prints nothing."""
    with _record.lock:
        if _record.ready is not None:
            return _record.ready
        _record.ready = time.monotonic()
        _record.programs_at_ready = compile_cache.totals()["programs"]
    print_rank_0(ready_line())
    return _record.ready


def ready_line() -> str:
    """`ready in 44.7 s: engine 3.1 (pool 0.4), 14 programs: traced
    6.2 s, lowered 2.9 s, backend 19.8 s, 14 of 14 from the cache (saved
    96 s)`: the outermost phases with their children in brackets, then
    the compile ledger up to the stamp."""
    rec = startup_record()
    t1 = rec["ready"] if rec["ready"] is not None else time.monotonic()
    tops: List[list] = []                  # [name, start, end, children]
    for name, a, b in rec["rows"]:         # in the order they began
        b = t1 if b is None else b
        if tops and tops[-1][1] <= a and b <= tops[-1][2]:
            tops[-1][3].append(
                f"{name.removeprefix(tops[-1][0] + '.')} {b - a:.1f}")
        else:
            tops.append([name, a, b, []])
    parts = [f"{name} {b - a:.1f}" + (f" ({', '.join(kids)})" if kids else "")
             for name, a, b, kids in tops]
    led = compile_cache.until(t1)
    kept = led["hits"] + led["misses"]
    return (f"ready in {t1 - rec['t0']:.1f} s: "
            + (", ".join(parts) + ", " if parts else "")
            + f"{led['programs']} programs: traced {led['trace_s']:.1f} s, "
            f"lowered {led['lower_s']:.1f} s, backend "
            f"{led['backend_s']:.1f} s, {led['hits']} of {kept} from the "
            f"cache (saved {led['saved_s']:.0f} s)")


def note_grad_accum(fused_bytes: int, accumulated_bytes: int) -> None:
    """`train_step`, as it traces a micro-batch's backward pass."""
    with _record.lock:
        _record.grad_accum_fused_share = fused_bytes / accumulated_bytes


def startup_scalars() -> Dict[str, float]:
    """The seven keys of the module docstring, as `/metrics` shows them."""
    led = compile_cache.totals()
    with _record.lock:
        ready_at, at_ready = _record.ready, _record.programs_at_ready
        fused = _record.grad_accum_fused_share
    return {
        "startup_seconds":
            float(ready_at - _record.t0) if ready_at is not None else 0.0,
        "compile_programs": float(led["programs"]),
        "compile_seconds": float(led["trace_s"] + led["lower_s"]
                                 + led["backend_s"]),
        "compile_cache_hits": float(led["hits"]),
        "compile_cache_misses": float(led["misses"]),
        "compiles_after_ready":
            float(led["programs"] - at_ready) if ready_at is not None
            else 0.0,
        "grad_accum_fused_share": fused,
    }


MAX_REQUEST_ROWS = 4096
MAX_ENGINES = 8


class RequestRow:
    """One request's record (the module docstring's table)."""

    __slots__ = ("engine", "seq", "rid", "outcome", "prompt_tokens",
                 "generated", "t_submit", "t_admit", "t_device", "t_first",
                 "t_finish", "early", "held", "programs", "rows",
                 "ahead_programs", "ahead_rows", "windows_between",
                 "prefix_hit_tokens", "preempted")

    def __init__(self, rid: int, t_submit: float):
        self.engine = 0
        self.seq: Optional[int] = None
        self.rid = rid
        self.outcome: Optional[str] = None
        self.prompt_tokens = self.generated = 0
        self.t_submit = t_submit
        self.t_admit: Optional[float] = None
        self.t_device: Optional[float] = None
        self.t_first: Optional[float] = None
        self.t_finish: Optional[float] = None
        self.early = self.held = 0
        self.programs = self.rows = 0
        self.ahead_programs = self.ahead_rows = 0
        self.windows_between = 0
        self.prefix_hit_tokens = self.preempted = 0

    def copy(self) -> "RequestRow":
        out = RequestRow.__new__(RequestRow)
        for k in self.__slots__:
            setattr(out, k, getattr(self, k))
        return out

    def segments(self) -> Dict[str, Optional[float]]:
        """Seconds in the queue, behind the running window, in the
        prefill (its own programs and whatever stood between them and the
        first token) and in decode; `None` where a stamp is missing."""
        def between(a, b):
            return None if a is None or b is None else b - a
        return {"queue_s": between(self.t_submit, self.t_admit),
                "behind_window_s": between(self.t_admit, self.t_device),
                "prefill_s": between(self.t_device, self.t_first),
                "decode_s": between(self.t_first, self.t_finish)}

    def as_dict(self) -> Dict[str, object]:
        return {**{k: getattr(self, k) for k in self.__slots__},
                **self.segments()}


class RequestRing:
    """The newest `maxlen` rows of one engine, in the order they entered."""

    def __init__(self, maxlen: int = MAX_REQUEST_ROWS):
        self.lock = threading.Lock()
        self.rows: Deque[RequestRow] = collections.deque(maxlen=maxlen)
        self.engine = 0
        self.written = 0

    def keep(self, row: RequestRow) -> None:
        """Put the row in, once: a second call changes nothing."""
        with self.lock:
            if row.seq is None:
                row.engine, row.seq = self.engine, self.written
                self.written += 1
                self.rows.append(row)

    def live(self) -> List[RequestRow]:
        """The rows themselves, for a reader in this package that only
        reads."""
        with self.lock:
            return list(self.rows)

    def copies(self) -> List[RequestRow]:
        return [r.copy() for r in self.live()]


_rings_lock = threading.Lock()
_rings: Deque[RequestRing] = collections.deque(maxlen=MAX_ENGINES)
_engines = itertools.count(1)


def keep_requests(ring: RequestRing) -> int:
    """An engine registers its ring as it is built and takes its integer:
    `request_record()` answers from the ring after the engine is closed,
    until `MAX_ENGINES` later engines have pushed it out."""
    with _rings_lock:
        if not ring.engine:              # a ring registers once
            ring.engine = next(_engines)
            _rings.append(ring)
    return ring.engine


def request_record() -> List[RequestRow]:
    """Copies of the rows of the process's engines, oldest engine first and
    within an engine in the order the rows entered its ring. A row of a
    live request is as far as its request has come."""
    with _rings_lock:
        rings = list(_rings)
    return [row for ring in rings for row in ring.copies()]


def step_span(name: str, step: int):
    return jax.profiler.StepTraceAnnotation("mtpu/" + name, step_num=step)


def start_trace(trace_dir: str) -> None:
    """`jax.profiler.start_trace` with the Python tracer off: it records
    every Python call of every thread (543 k events in 5 s of serving,
    PERF.md section 5) and slows the host loop whose gaps the trace is
    read for. The spans above and the device's events are kept."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
