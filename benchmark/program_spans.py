"""What the program's own host spans say about a traced window.

The program writes `jax.profiler.TraceAnnotation`s named `mtpu/...` round the
stages of its engine loop and its training loop
(`megatron_tpu/utils/tracing.py` holds the table of names). They land in the
profiler's trace on the device's clock, and `trace.load` keeps them in
`Trace.spans` with the benchmark's own `bench/...` spans: (name, start,
duration), every thread in one list. Three reductions, shared by the readers
under `layer_metrics/`:

- `idle_by_span`: the first device's idle seconds, by what the program was
  doing. The threshold is `Trace.idle_gaps`': a gap between operations counts
  from 50 microseconds. Each idle second goes to the leaf span that covers it,
  where `idle_gaps` gives a whole gap to the one span that overlaps it most:
  between two decode steps the device waits once, through the end of the
  fetch, the commit, the next iteration's reap, admit, uploads and the start of
  the dispatch, and that one gap given whole to the longest stage would name one
  stage and hide the rest.
- `seconds_in` and `count_in`: spans by name.
- `periods`: intervals between consecutive starts of a named span.

A leaf is the innermost span: spans of one thread nest, and of those that
cover an instant the one that began last is the innermost. Across threads
(`mtpu/serve/submit` runs on the caller's) the same rule gives the instant to
the span that began last.

A program without the spans (a parent commit, before they existed) gives
empty lists and `None`s here, never an error. Everything returns `None` unless
the trace is a TPU's: host numbers of a CPU rehearsal are not device metrics.
"""
from __future__ import annotations

import heapq
from typing import Dict, List, Optional, Tuple

from benchmark.trace import Event, Trace, _union

PREFIX = "mtpu/"
MIN_GAP_S = 50e-6                     # Trace.idle_gaps' threshold
UNATTRIBUTED = "unattributed"


def on_tpu(trace: Optional[Trace]) -> bool:
    return trace is not None and trace.kind == "tpu" and bool(trace.ops)


def program_spans(trace: Trace, prefix: str = PREFIX) -> List[Event]:
    return sorted((s for s in trace.spans if s[0].startswith(prefix)),
                  key=lambda s: s[1])


def leaf_pieces(spans: List[Event]) -> List[Tuple[str, float, float]]:
    """(name, start, end) pieces that do not overlap: every instant some
    span covers, under the name of the covering span that began last."""
    spans = sorted(spans, key=lambda s: s[1])
    times = sorted({t for _, s, d in spans for t in (s, s + d)})
    out, alive, i = [], [], 0         # alive: heap of (-start, end, name)
    for t0, t1 in zip(times, times[1:]):
        while i < len(spans) and spans[i][1] <= t0:
            name, s, d = spans[i]
            heapq.heappush(alive, (-s, s + d, name))
            i += 1
        while alive and alive[0][1] <= t0:
            heapq.heappop(alive)
        if alive:
            out.append((alive[0][2], t0, t1))
    return out


def idle_by_span(trace: Optional[Trace], prefix: str = PREFIX,
                 min_s: float = MIN_GAP_S
                 ) -> Optional[Tuple[float, Dict[str, float]]]:
    """(all idle seconds of the first device between its first and last
    operation, {span name: idle seconds under it as a leaf}). Gaps shorter
    than `min_s`, and what no span covers, are under "unattributed", so the
    dictionary's values add up to the total."""
    if not on_tpu(trace):
        return None
    busy = _union(trace.ops[min(trace.ops)])
    gaps = [(e0, s1) for (_, e0), (s1, _) in zip(busy, busy[1:])]
    total = sum(b - a for a, b in gaps)
    pieces = leaf_pieces(program_spans(trace, prefix))
    by: Dict[str, float] = {}
    j = 0
    for a, b in gaps:                 # both lists ascend and do not overlap
        if b - a < min_s:
            continue
        while j < len(pieces) and pieces[j][2] <= a:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][1] < b:
            name, p0, p1 = pieces[k]
            by[name] = by.get(name, 0.0) + min(b, p1) - max(a, p0)
            k += 1
    by[UNATTRIBUTED] = total - sum(by.values())
    return total, by


def idle_ms_per(trace: Optional[Trace], per: str, names=None
                ) -> Optional[float]:
    """Milliseconds the first device sat idle per span `per` that begins
    in the device's window: all its idle time, or with `names` the part
    under those leaf spans. None where the trace holds no span `per`."""
    split = idle_by_span(trace)
    n = count_in(trace, per)
    if split is None or not n:
        return None
    total, by = split
    if names is not None:
        total = sum(by.get(name, 0.0) for name in names)
    return 1e3 * total / n


def count_in(trace: Optional[Trace], name: str) -> Optional[int]:
    """How many spans of that name begin inside the device's window
    (first operation's start to the last one's end on the first device)."""
    if not on_tpu(trace):
        return None
    busy = _union(trace.ops[min(trace.ops)])
    w0, w1 = busy[0][0], busy[-1][1]
    return sum(1 for n, s, _ in trace.spans
               if n == name and w0 <= s <= w1) or None


def seconds_in(trace: Optional[Trace], name: str) -> Optional[float]:
    """Seconds inside every span of that name in the trace."""
    if not on_tpu(trace):
        return None
    spans = [s for s in trace.spans if s[0] == name]
    return sum(d for _, _, d in spans) if spans else None


def periods(trace: Optional[Trace], name: str, split_by=(), drop=()
            ) -> Optional[Tuple[List[float], List[float]]]:
    """Intervals between consecutive starts of the spans `name`, as two
    lists: those with no span of `split_by` beginning inside them, and those
    with one. An interval with a span of `drop` beginning inside it is in
    neither."""
    if not on_tpu(trace):
        return None
    starts = sorted(s for n, s, _ in trace.spans if n == name)
    marks = sorted((s, n in drop) for n, s, _ in trace.spans
                   if n in split_by or n in drop)
    plain, split, j = [], [], 0
    for a, b in zip(starts, starts[1:]):
        while j < len(marks) and marks[j][0] < a:
            j += 1
        inside = []
        while j < len(marks) and marks[j][0] < b:
            inside.append(marks[j][1])
            j += 1
        if not any(inside):
            (split if inside else plain).append(b - a)
    return plain, split


COMMIT = "mtpu/serve/step.commit"
PREFILLS = ("mtpu/serve/prefill", "mtpu/serve/prefill_chunk")
IDLE_WAIT = ("mtpu/serve/idle_wait",)


def serve_step_periods(trace: Optional[Trace]
                       ) -> Optional[Tuple[List[float], List[float]]]:
    """The engine loop's periods: intervals between consecutive starts of
    `mtpu/serve/step.commit`, those with no prefill dispatched inside and
    those with one (`mtpu/serve/prefill` or `prefill_chunk` begins inside).
    Intervals in which the loop went idle (`idle_wait`) are left out."""
    return periods(trace, COMMIT, split_by=PREFILLS, drop=IDLE_WAIT)
