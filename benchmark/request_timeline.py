"""What the program's own record of its requests says of the first token.

The serving engine keeps one row a request (`megatron_tpu/utils/tracing.py`,
`RequestRow`: the table of its fields is there), on `time.monotonic()`, the
clock the drivers' `t_open` and `window_s` are on, and
`tracing.request_record()` hands the rows out after the engine is closed,
which is when `run.py` calls the readers. From a row:

    queue = t_admit - t_submit          behind the window = t_device - t_admit
    prefill = t_first - t_device        (the three tile [t_submit, t_first])

`cut(run)` takes the rows with a first token whose `t_submit` lies in
`[samples["t_open"], samples["t_open"] + samples["window_s"])`: the requests
the driver's time to first token is taken over, as `startup.py` cuts at the
window's opening. The readers under `layer_metrics/serve_first_*.py` reduce it:

| reader | what |
|---|---|
| `behind_window_p50_ms` | median over the cut of `t_device - t_admit`: what a prompt admitted inside a running decode window waits for that window's tokens (0 for a prompt that met none) |
| `behind_prefill_pct` | share of the cut with `ahead_programs > 0` or `held > 0`: first tokens that waited for somebody else's prefill program |
| `own_prefill_p50_ms` | median of `t_first - t_device` over the cut's rows with `programs == 1` and `ahead_programs == 0`: one prefill program, its draw and the hand-over, with nobody in front |
| `host_overhead_pct` | over the rows of ANY phase with `programs == 1` and `ahead_programs == 0` whose prefill segment lies inside the traced part: 100 x (1 - device 0's busy seconds inside the segments / the segments' seconds) |

The first of them also writes one line `requests {...}` on standard error
(`breakdown`): the cut's size beside the driver's `attempted - failed`, and
the three segments' sums beside the sum of `t_first - t_submit`.

The device's clock. A trace's `start_ns` is NOT a clock Python can read: the
profiler takes its session's start off every stamp (`start_ns` of a span
opened 50 ms after `start_trace` returned reads 50.2 ms on the CPU backend
and 103.1 ms on a TPU v5e, where `start_trace` itself lasted 6.6 s and took
its start 52 ms before it returned: PERF.md section 3, PR 54). So `clock_offset`
aligns the two clocks by the `mtpu/serve/submit` spans of the traced part: a
span begins some tens of microseconds before its request's `t_submit` is
stamped inside it, so span starts are the rows' `t_submit` plus one constant.
It tries the constants that put one of the first spans on some row submitted
since the window closed (the drivers start the profiler there), keeps the one
under which most spans lie within `MATCH_S` of a row (arrivals are random, so
a wrong constant matches one span, its own, and another only by chance), and
returns the median difference over those.

A program that keeps no such record (a parent commit) gives `None` from every
reader, never an error; so does a cut with no row. `host_overhead_pct` is
`None` off a TPU. The seconds of the first three are the host's, as
`serve_ttft_p50_ms` is.
"""
from __future__ import annotations

import bisect
import json
import sys
from typing import Dict, List, Optional, Tuple

from benchmark.program_spans import on_tpu
from benchmark.stats import percentile
from benchmark.trace import _union

SUBMIT = "mtpu/serve/submit"
MATCH_S = 2e-3            # a span and its row agree this closely
MIN_SPANS = 2             # one gap between submits is the least to match
ANCHORS = 3               # spans tried as the one that surely has a row


def _record():
    """The program's `request_record`, where it has one."""
    try:
        from megatron_tpu.utils import tracing
    except ImportError:
        return None
    return getattr(tracing, "request_record", None)


def rows_of() -> Optional[list]:
    """Every row the process holds, or None on a program without the
    record."""
    record = _record()
    return None if record is None else list(record())


def cut(run) -> Optional[list]:
    samples = getattr(run, "samples", None) or {}
    rows = rows_of()
    if rows is None or "t_open" not in samples or "window_s" not in samples:
        return None
    t0 = samples["t_open"]
    t1 = t0 + samples["window_s"]
    return [r for r in rows
            if r.t_first is not None and t0 <= r.t_submit < t1]


def _alone(rows: list) -> list:
    """The rows whose first token took one prefill program with no other
    request's in front."""
    return [r for r in rows
            if r.programs == 1 and r.ahead_programs == 0
            and r.t_device is not None and r.t_first is not None]


def behind_window_p50_ms(run) -> Optional[float]:
    rows = cut(run)
    if rows is not None:
        print("requests " + json.dumps(breakdown(run, rows)),
              file=sys.stderr, flush=True)
    xs = [r.t_device - r.t_admit for r in rows or ()
          if r.t_device is not None and r.t_admit is not None]
    return 1e3 * percentile(xs, 50) if xs else None


def behind_prefill_pct(run) -> Optional[float]:
    rows = cut(run)
    if not rows:
        return None
    return 100.0 * sum(1 for r in rows
                       if r.ahead_programs > 0 or r.held > 0) / len(rows)


def own_prefill_p50_ms(run) -> Optional[float]:
    xs = [r.t_first - r.t_device for r in _alone(cut(run) or [])]
    return 1e3 * percentile(xs, 50) if xs else None


def clock_offset(trace, rows: list,
                 after: float = float("-inf")) -> Optional[float]:
    """Seconds to add to a `time.monotonic()` stamp to put it on the
    trace's clock (the module docstring), from the rows submitted at or
    after `after` (a driver starts the profiler as its window closes, so
    no earlier request has a span in the traced part). None where the
    traced part holds fewer than `MIN_SPANS` submit spans or no constant
    puts half of them on rows."""
    starts = sorted(s for n, s, _ in trace.spans if n == SUBMIT)
    subs = sorted(r.t_submit for r in rows if r.t_submit >= after)
    if len(starts) < MIN_SPANS or not subs:
        return None

    def matched(offset: float) -> List[float]:
        diffs = []
        for s in starts:
            i = bisect.bisect_left(subs, s - offset)
            near = [subs[j] for j in (i - 1, i) if 0 <= j < len(subs)]
            t = min(near, key=lambda x: abs(s - offset - x))
            if abs(s - offset - t) <= MATCH_S:
                diffs.append(s - t)
        return diffs

    best: List[float] = []
    for anchor in starts[:ANCHORS]:
        for t in subs:
            diffs = matched(anchor - t)
            if len(diffs) > len(best):
                best = diffs
    if 2 * len(best) < len(starts) or len(best) < MIN_SPANS:
        return None
    return percentile(best, 50)


def _traced_part(trace) -> Optional[Tuple[float, float]]:
    """First event's start to the last one's end, spans and operations."""
    events = [(s, s + d) for _, s, d in trace.spans]
    events += [(s, s + d) for ops in trace.ops.values() for _, s, d in ops]
    if not events:
        return None
    return min(a for a, _ in events), max(b for _, b in events)


def traced_segments(run) -> Optional[Tuple[List[Tuple[float, float]],
                                           List[Tuple[float, float]]]]:
    """(the prefill segments `[t_device, t_first]` on the trace's clock of
    the rows `_alone` keeps that lie inside the traced part, device 0's busy
    intervals), or None off a TPU, without the record or without an
    alignment."""
    trace = getattr(run, "trace", None)
    rows = rows_of()
    if not on_tpu(trace) or rows is None:
        return None
    samples = getattr(run, "samples", None) or {}
    closed = samples.get("t_open", float("-inf")) \
        + samples.get("window_s", 0.0)
    offset = clock_offset(trace, rows, after=closed)
    part = _traced_part(trace)
    if offset is None or part is None:
        return None
    lo, hi = part
    segments = [(r.t_device + offset, r.t_first + offset)
                for r in _alone(rows)]
    segments = [(a, b) for a, b in segments if lo <= a and b <= hi and a < b]
    return segments, _union(trace.ops[min(trace.ops)])


def busy_inside(segment: Tuple[float, float],
                busy: List[Tuple[float, float]]) -> float:
    a, b = segment
    i = bisect.bisect_right(busy, (a, a)) - 1     # may begin before `a`
    total = 0.0
    for s, e in busy[max(i, 0):]:
        if s >= b:
            break
        total += max(0.0, min(b, e) - max(a, s))
    return total


def host_overhead_pct(run) -> Optional[float]:
    both = traced_segments(run)
    if both is None or not both[0]:
        return None
    segments, busy = both
    seconds = sum(b - a for a, b in segments)
    return 100.0 * (1.0 - sum(busy_inside(s, busy) for s in segments)
                    / seconds)


def breakdown(run, rows: list) -> Dict[str, object]:
    """The cut, for a person: how many rows against the driver's count of
    first tokens, the three segments' sums against the sum of `t_first -
    t_submit` (they tile it), and who waited for what."""
    samples = getattr(run, "samples", None) or {}
    whole = [r for r in rows if r.t_admit is not None
             and r.t_device is not None]
    total = sum(r.t_first - r.t_submit for r in whole)
    queue = sum(r.t_admit - r.t_submit for r in whole)
    behind = sum(r.t_device - r.t_admit for r in whole)
    prefill = sum(r.t_first - r.t_device for r in whole)
    driver = None
    if "attempted" in samples and "failed" in samples:
        driver = samples["attempted"] - samples["failed"]
    return {
        "rows": len(rows), "driver_first_tokens": driver,
        "rows_whole": len(whole),
        "first_token_s": total, "queue_s": queue,
        "behind_window_s": behind, "prefill_s": prefill,
        "tiling_error_us": 1e6 * (queue + behind + prefill - total),
        "early": sum(r.early for r in rows),
        "held": sum(1 for r in rows if r.held),
        "ahead": sum(1 for r in rows if r.ahead_programs),
        "chunked": sum(1 for r in rows if r.programs > 1),
        "alone": len(_alone(rows)),
        "engines": sorted({r.engine for r in rows}),
    }
