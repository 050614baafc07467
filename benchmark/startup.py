"""What the program's own start-up record and compile ledger say of `setup_s`.

`setup_s` runs from `run.py`'s first line (`Context.t_process_start`) to the
window's opening, and every driver returns it, so the window opened at
`t_process_start + setup_s` on `time.monotonic()`. The program keeps two
records on that same clock (`megatron_tpu/utils/`):

- `compile_cache.events()`: JAX's own compile events by program,
  `(kind, program, end, seconds)` with kind `trace`, `lower`, `backend`
  (a compile, or the load from the persistent cache in its place), and the
  cache's `request`, `hit`, `miss`, `retrieval`, `saved`;
- `tracing.startup_record()`: the phases `mtpu/setup/<name>` as
  `(name, start, end)`.

`cut(run)` takes both up to the window's opening. The readers under
`layer_metrics/setup_*.py` reduce it:

| reader | what |
|---|---|
| `backend_s` | seconds some backend event covers (union: two threads compiling at once count once) |
| `trace_lower_s` | seconds some outermost trace or some lowering covers: host Python a warm cache does not save |
| `programs` | backend events: programs compiled or loaded |
| `cache_hit_pct` | hits over hits + misses. A miss is a program compiled and WRITTEN to the cache. JAX counts a request for every program, also for those it never stores (compiled in under a second), which can never hit: over requests the six cells' fully warm starts read 13 to 42 (PERF.md section 6, PR 35). 100 is a warm start, 0 a cold one |
| `build_s` | seconds under the phases `mesh`, `init_state`, `load`, `data`, `generator`, `engine` that lie in no trace, lowering or backend event: state made on the device and the host |
| `first_step_s` | seconds under `first_step`, compile or load included |
| `attributed_pct` | share of `setup_s` under some phase or some ledger event |

A program with neither record (a parent commit) makes `cut` return `None`
and every reader with it: never an error. The seconds are the host's, as
`setup_s` is; they are `program_counter`s and `program_span`s, not device
metrics.
"""
from __future__ import annotations

import json
import sys
from typing import Dict, List, NamedTuple, Optional, Tuple

Interval = Tuple[float, float]
BUILD_PHASES = ("mesh", "init_state", "load", "data", "generator", "engine")
FIRST_STEP = "first_step"
_TIMED = ("trace", "lower", "backend")


class Cut(NamedTuple):
    t0: float                     # process start, run.py's
    t1: float                     # the window's opening
    events: list                  # (kind, program, end, seconds), end <= t1
    phases: list                  # (name, start, end) clipped to [t0, t1]


def _records():
    """(compile_cache, tracing) where the program has both records."""
    try:
        from megatron_tpu.utils import compile_cache, tracing
    except ImportError:
        return None
    if not (hasattr(compile_cache, "events")
            and hasattr(tracing, "startup_record")):
        return None
    return compile_cache, tracing


def cut(run) -> Optional[Cut]:
    records = _records()
    setup_s = getattr(run, "end_to_end", {}).get("setup_s")
    if records is None or setup_s is None:
        return None
    compile_cache, tracing = records
    t0 = run.ctx.t_process_start
    t1 = t0 + setup_s
    phases = [(name, max(a, t0), min(t1 if b is None else b, t1))
              for name, a, b in tracing.startup_record()["rows"] if a < t1]
    return Cut(t0, t1, compile_cache.events(upto=t1), phases)


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def seconds(intervals: List[Interval]) -> float:
    return sum(b - a for a, b in union(intervals))


def minus(intervals: List[Interval], holes: List[Interval]) -> List[Interval]:
    """The parts of `intervals` (their union) that no hole covers."""
    out, holes = [], union(holes)
    for a, b in union(intervals):
        for h0, h1 in holes:
            if h1 <= a or h0 >= b:
                continue
            if h0 > a:
                out.append((a, h0))
            a = max(a, h1)
        if a < b:
            out.append((a, b))
    return out


def event_intervals(c: Cut, kinds=_TIMED) -> List[Interval]:
    return [(max(end - s, c.t0), end) for kind, _, end, s in c.events
            if kind in kinds]


def phase_intervals(c: Cut, names=None) -> List[Interval]:
    return [(a, b) for name, a, b in c.phases
            if names is None or name in names]


def count(c: Cut, kind: str) -> int:
    return sum(1 for e in c.events if e[0] == kind)


def backend_s(run) -> Optional[float]:
    c = cut(run)
    return None if c is None else seconds(event_intervals(c, ("backend",)))


def trace_lower_s(run) -> Optional[float]:
    c = cut(run)
    return None if c is None else \
        seconds(event_intervals(c, ("trace", "lower")))


def programs(run) -> Optional[float]:
    c = cut(run)
    return None if c is None else float(count(c, "backend"))


def cache_hit_pct(run) -> Optional[float]:
    c = cut(run)
    if c is None:
        return None
    hits, misses = count(c, "hit"), count(c, "miss")
    return 100.0 * hits / (hits + misses) if hits + misses else None


def build_s(run) -> Optional[float]:
    c = cut(run)
    if c is None or not phase_intervals(c, BUILD_PHASES):
        return None
    return seconds(minus(phase_intervals(c, BUILD_PHASES),
                         event_intervals(c)))


def first_step_s(run) -> Optional[float]:
    c = cut(run)
    if c is None or not phase_intervals(c, (FIRST_STEP,)):
        return None
    return seconds(phase_intervals(c, (FIRST_STEP,)))


def attributed_pct(run) -> Optional[float]:
    c = cut(run)
    if c is None or c.t1 <= c.t0:
        return None
    print("startup " + json.dumps(breakdown(c)), file=sys.stderr, flush=True)
    return 100.0 * seconds(phase_intervals(c) + event_intervals(c)) \
        / (c.t1 - c.t0)


def _r(x: float) -> float:
    return round(x, 2)


def breakdown(c: Cut) -> Dict[str, object]:
    """Where set-up went, for a person: one line on standard error of the
    traced run (`attributed_pct` writes it). `backend_s` to `between_s` add
    up to `setup_s`: compile events by kind (each second once; a backend event
    wins over a lowering, that over a trace), what the phases cover besides,
    and what neither covers, split at the program's first sign of life
    (imports and the backend's own start come before it)."""
    backend = event_intervals(c, ("backend",))
    lower = minus(event_intervals(c, ("lower",)), backend)
    trace = minus(event_intervals(c, ("trace",)), backend + lower)
    compiling = backend + lower + trace
    phases = minus(phase_intervals(c), compiling)
    marks = [a for a, _ in phase_intervals(c) + event_intervals(c)]
    first = min(marks) if marks else c.t1
    rest = minus([(c.t0, c.t1)], compiling + phases)
    by_program: Dict[str, List[float]] = {}
    for kind, program, _, s in c.events:
        if kind in _TIMED:
            row = by_program.setdefault(program, [0, 0.0, 0.0, 0.0])
            row[0] += kind == "backend"
            row[1 + _TIMED.index(kind)] += s
    top = sorted(by_program.items(), key=lambda kv: -sum(kv[1][1:]))[:8]
    return {
        "setup_s": _r(c.t1 - c.t0),
        "backend_s": _r(seconds(backend)), "lower_s": _r(seconds(lower)),
        "trace_s": _r(seconds(trace)), "phases_besides_s": _r(seconds(phases)),
        "before_first_sign_s": _r(first - c.t0),
        "between_s": _r(seconds(minus(rest, [(c.t0, first)]))),
        "phases": [(n, _r(a - c.t0), _r(b - a)) for n, a, b in c.phases],
        "programs": count(c, "backend"), "requests": count(c, "request"),
        "hits": count(c, "hit"), "misses": count(c, "miss"),
        "saved_s": _r(sum(e[3] for e in c.events if e[0] == "saved")),
        "retrieval_s": _r(sum(e[3] for e in c.events if e[0] == "retrieval")),
        "events": len(c.events),
        "top_programs_n_trace_lower_backend":
            [(p, n, _r(t), _r(lo), _r(b)) for p, (n, t, lo, b) in top],
    }
