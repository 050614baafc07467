"""The program's host spans: `jax.profiler.TraceAnnotation`s named `mtpu/...`.

A span exists only while a profiler session is on (`--profile` on a training
job, `PUT /admin {"op": "trace"}` on a server, or whoever calls
`jax.profiler.start_trace` round the code). It then lands in the profiler's
trace beside the device's events, on the same clock. With no session it is a
C++ "is anyone tracing" check. There is no recorder, flag or option here.

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
| `mtpu/serve/admit` | `_preempt_for_priority` + `_admit` (pop, adapter, prefix lookup, grouping) | `popped`+ |
| `mtpu/serve/prefill` | each `_prefill_group` call (host arrays, the group's sampling keys as one compiled call, `_initial_rngs`, then the dispatch), child of `admit` | `n`, `padded`, `rid` of the first |
| `mtpu/serve/prefill_chunk` | `_advance_prefill` when it dispatches, `_activate_pending` included | `rid`, `tokens`+ |
| `mtpu/serve/swap` | `_apply_swap` | |
| `mtpu/serve/step` | `_step`; parent of the five below | `active`, `K`+ |
| `mtpu/serve/step.upload` | the dirty sampling / mask / lengths / adapter-row uploads | |
| `mtpu/serve/step.draft` | `build_draft_rounds` (only entered with `speculative_k`) | |
| `mtpu/serve/step.dispatch` | the chain of K `_decode` / `_verify` calls | |
| `mtpu/serve/step.fetch` | `self._fetch(...)`: the host waits, the device works | |
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
traced under it; models/moe.py, models/attention.py and models/mla.py):

| scope | round what |
|---|---|
| `mtpu/moe/route` | a dropless expert layer's router product, softmax, top-k and aux loss (every dispatch), the sort of the (token, k) rows by expert, the group sizes, the gather of the sorted rows |
| `mtpu/moe/experts` | the weight casts, the two grouped products (ops/grouped_matmul.py) and the activation between them |
| `mtpu/moe/share` | the same where the chip holds a share of the layer's experts (`moe_router_experts` wider than `num_experts`): the products of the held experts' rows alone and the zeroing of the rows behind the last group |
| `mtpu/moe/combine` | the gather back to (token, k) order and the weighted sum of a token's K rows |
| `mtpu/attn/qk_norm` | the RMSNorm over the whole q and the whole k projection (`qk_norm`) |
| `mtpu/attn/window` | a window layer of a stack of two kinds over its ring (`attention.HybridKVCache`): the ring turned into time order, the flash kernel or the scores over ring + chunk, the rows' write over the oldest; a decode step's write and read of a layer of rings |
| `mtpu/attn/full` | a full layer of such a stack over its whole region: the write at the offset, the flash kernel or the scores over the region up to the chunk's end; a decode step's write and read of a layer of regions |
| `mtpu/moe/shared` | the shared experts' MLP, added beside the routed sum (`n_shared_experts`) |
| `mtpu/mla/q` | latent attention's query: down-projection, norm, up-projection, the rotary on its rope part |
| `mtpu/mla/latent` | the latent row: down-projection, norm over kv_lora_rank, the rotary on the shared key, the write into the cache |
| `mtpu/mla/attend_expanded` | the expanded form: keys and values of every head from the rows, causal attention from position 0 (training; a prefill at offset 0) |
| `mtpu/mla/attend_absorbed` | the absorbed form: the layer of the cache read by the scores and by the weighted sum, each head's query and output through W_uk and W_uv (decode, verify, continuation chunks) |

Counters of the serving metrics' snapshot that a benchmark reader takes:
`kv_bytes_per_token` and `kv_pool_bytes`, `SlotKVPool.bytes_per_token()` and
`.nbytes()` as the pool counts them, pushed once when the engine builds it
(`serve_kv_bytes_per_token`); beside them `kv_bytes_per_slot`, `kv_ring_bytes`
and `kv_full_bytes` (`.bytes_per_slot()`, `.ring_nbytes()`, `.full_nbytes()`:
what a slot reserves, and the pool's bytes by kind; `serve_kv_bytes_per_slot`).
`prefill_chunks` counts the chunk programs dispatched. The rows a share's held
experts took (`moe_rows_held` of ISSUE 33) are NOT counted by the program: no
serving program hands a scalar out of the layer loop, and the benchmark counts
them with the reference's router on the window's own tokens (PERF.md section 7).

A TPU v5e's trace names an `XLA Ops` event by the HLO instruction's text, which
does not hold the `op_name` (PERF.md section 7, PR 27): the scopes are in the
trace file's HLO metadata, for a viewer, and the expert kernels are found by
their own name, `%_moe_grouped_matmul.N`.

Which benchmark metric reads which span: PERF.md section 3. How an operator
reads an idle gap off a trace: docs/serving.md "Observability & drills".
"""
from __future__ import annotations

import jax


def span(name: str, **stats):
    return jax.profiler.TraceAnnotation("mtpu/" + name, **stats)


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
