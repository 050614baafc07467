"""Where JAX's persistent compilation cache lives, and what compiling cost.

Every entry point calls `ensure_compile_cache()` before its first
compile. Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself
and nothing here sets another directory. Where it is not, the cache
goes to `<checkout>/.jax_cache` (git-ignored): the directory's path is
part of every entry's key, so it is a fixed path — never one built from
a temporary name, a pid or the time — and a second run of the same
command from the same checkout finds what the first compiled.

The same call registers, once a process, the compile ledger: listeners
for JAX's own monitoring events, which fire where the compile happens.

| event | what JAX times | kind here |
|---|---|---|
| `/jax/core/compile/jaxpr_trace_duration` | a function traced to a jaxpr (Python) | `trace` |
| `/jax/core/compile/jaxpr_to_mlir_module_duration` | the jaxpr lowered to an MLIR module (Python) | `lower` |
| `/jax/core/compile/backend_compile_duration` | the backend's compile, or the load from the persistent cache in its place | `backend` |
| `/jax/compilation_cache/compile_requests_use_cache` | a program asked of the persistent cache | `request` |
| `/jax/compilation_cache/cache_hits` | ... and found there | `hit` |
| `/jax/compilation_cache/cache_misses` | ... compiled and WRITTEN there (JAX stores none that compiled in under a second, so requests = hits + misses + programs too quick to keep) | `miss` |
| `/jax/compilation_cache/cache_retrieval_time_sec` | seconds reading a hit | `retrieval` |
| `/jax/compilation_cache/compile_time_saved_sec` | the compile seconds stored with the entry, less the retrieval | `saved` |

An event is `(kind, program, end, seconds)`: `end` is `time.monotonic()`
as the listener is called, which is as the timed work ends, so the work
ran over `[end - seconds, end]`. `program` is JAX's `fun_name` without
its `jit(...)`: `_decode_fn`, `_prefill_fn`, `_chunk_fwd_fn`,
`train_step`, and `multiply` for an eager product. Traces nest (tracing
`_decode_fn` traces every jitted function it calls, and lowering it
traces the lowering rules' helpers): only the outermost is kept, told by
JAX's matching start-of-event scalars. A hit or a miss
carries no name and fires inside its program's backend event, on the
compiling thread: it is given that program's.

`ledger()` reduces the events to plain numbers, `until(t)` and
`since(t)` those that ended up to and after a clock reading. There is
no event on a call that compiles nothing, so a steady loop pays nothing.
"""
from __future__ import annotations

import os
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional, Tuple

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval",
    "/jax/compilation_cache/compile_time_saved_sec": "saved",
}
_COUNTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "request",
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
# A process that compiles for ever (a shape that never repeats) must not
# grow this list for ever: past the cap an event is counted and dropped.
# A benchmark cell's whole start makes 160 to 190 (my chip runs, PR 35).
MAX_EVENTS = 65536

Event = Tuple[str, str, float, float]      # kind, program, end, seconds

_SECONDS = {"trace": "trace_s", "lower": "lower_s", "backend": "backend_s",
            "retrieval": "retrieval_s", "saved": "saved_s"}
_NUMBERS = {"trace": "traces", "backend": "programs", "request": "requests",
            "hit": "hits", "miss": "misses"}


def _blank() -> Dict[str, float]:
    return {**{k: 0 for k in _NUMBERS.values()},
            **{k: 0.0 for k in _SECONDS.values()}}


def _add(row: Dict[str, float], kind: str, seconds: float) -> None:
    if kind in _NUMBERS:
        row[_NUMBERS[kind]] += 1
    if kind in _SECONDS:
        row[_SECONDS[kind]] += seconds


_lock = threading.Lock()
_events: List[Event] = []
_totals = _blank()          # of every event, the dropped ones too
_dropped = 0
# per thread: .depth traces and lowerings open; .traced, .lowering and
# .compiling the program last traced, being lowered, in the backend
_local = threading.local()
_registered = False


def ensure_compile_cache() -> None:
    _register()
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))


def _register() -> None:
    global _registered
    with _lock:
        if _registered:
            return
        _registered = True
    from jax import monitoring
    monitoring.register_scalar_listener(_on_scalar)
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_count)


_UNNAMED = ("", "<unknown>", "<unnamed function>")


def _program(fun_name: Optional[str], fallback: Optional[str]) -> str:
    """`jit(_decode_fn)` -> `_decode_fn`. JAX names the lowering and the
    compile of a `functools.partial` `jit(<unknown>)` (the train step):
    those take `fallback`, the name of the trace they follow."""
    name = fun_name or ""
    if name.endswith(")") and "(" in name:
        name = name[name.index("(") + 1:-1]
    return (fallback or "<unknown>") if name in _UNNAMED else name


def _append(kind: str, program: str, seconds: float) -> None:
    global _dropped
    event = (kind, program, time.monotonic(), seconds)
    with _lock:
        _add(_totals, kind, seconds)
        if len(_events) < MAX_EVENTS:
            _events.append(event)
        else:
            _dropped += 1


def _on_scalar(event: str, value, **kwargs) -> None:
    """JAX sends an event's start as a scalar under the event's name. A
    trace or a lowering opens a level: what is traced inside either (a
    lowering rule traces its helpers: some 300 `add`s and `bitwise_xor`s
    in a program that draws random numbers) is not outermost. Names are
    settled here, at the start: by a lowering's end such a helper's
    trace is the thread's last."""
    kind = _DURATIONS.get(event)
    if kind in ("trace", "lower"):
        _local.depth = getattr(_local, "depth", 0) + 1
    if kind == "lower":
        _local.lowering = _program(kwargs.get("fun_name"),
                                   getattr(_local, "traced", None))
    elif kind == "backend":
        _local.compiling = _program(kwargs.get("fun_name"),
                                    getattr(_local, "lowering", None))


def _on_duration(event: str, seconds: float, **kwargs) -> None:
    kind = _DURATIONS.get(event)
    if kind is None:
        return
    if kind in ("trace", "lower"):
        _local.depth = depth = max(getattr(_local, "depth", 1) - 1, 0)
        if depth:                          # inside another: not outermost
            return
    if kind == "trace":
        name = _local.traced = kwargs.get("fun_name") or "<unknown>"
    elif kind == "lower":
        name = getattr(_local, "lowering", None) or "<unknown>"
    else:                                  # backend; retrieval, saved in it
        name = _compiling()
    _append(kind, name, seconds)


def _on_count(event: str, **kwargs) -> None:
    kind = _COUNTS.get(event)
    if kind is not None:
        _append(kind, _compiling(), 0.0)


def _compiling() -> str:
    """The program whose backend event a cache event fires inside."""
    return getattr(_local, "compiling", None) or "<unknown>"


def totals() -> Dict[str, float]:
    """The ledger's totals since the process began, with no list walked:
    what a `/metrics` scrape reads."""
    with _lock:
        return dict(_totals, events_dropped=_dropped)


def events(after: Optional[float] = None,
           upto: Optional[float] = None) -> List[Event]:
    """The events that ended after `after` and no later than `upto`."""
    with _lock:
        out = list(_events)
    return [e for e in out
            if (after is None or e[2] > after)
            and (upto is None or e[2] <= upto)]


def ledger(after: Optional[float] = None,
           upto: Optional[float] = None) -> Dict[str, object]:
    """Totals, and `by_program` the same by program: `programs` (backend
    events: compiled, or loaded from the cache), `traces`, `requests`,
    `hits`, `misses`, and the seconds `trace_s`, `lower_s`, `backend_s`,
    `retrieval_s`, `saved_s`. `events_dropped` is what the cap dropped,
    at any time."""
    total, by = _blank(), {}
    for kind, program, _, seconds in events(after, upto):
        _add(total, kind, seconds)
        _add(by.setdefault(program, _blank()), kind, seconds)
    with _lock:
        total["events_dropped"] = _dropped
    total["by_program"] = by
    return total


def since(t: float) -> Dict[str, object]:
    """The ledger of what ended after the clock read `t`."""
    return ledger(after=t)


def until(t: float) -> Dict[str, object]:
    """The ledger of what had ended when the clock read `t`."""
    return ledger(upto=t)
