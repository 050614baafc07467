"""What a driver is given, and what it gives back."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional


@dataclasses.dataclass
class Context:
    root: str                    # the checkout
    cell: dict                   # the entry of BENCHMARK.json's workloads
    config: dict                 # benchmark/configs/<config>.json
    traffic: dict                # benchmark/traffic/<traffic>.json
    seed: int
    seconds: float               # length of the measured window
    trace: bool
    devices: list
    peaks: Optional[dict]        # this device kind's row of peaks.json
    compiles: Any                # run.CompileCounter
    t_process_start: float

    def setup_seconds(self, t_window_open: float) -> float:
        return t_window_open - self.t_process_start


@dataclasses.dataclass
class Run:
    correct: bool
    attempted: int               # steps or requests of the window
    failed: int
    end_to_end: Dict[str, float]
    samples: Dict[str, Any]      # raw material for the per-layer readers
    checks: Dict[str, Any]       # what `correct` was decided from
    window_s: float
    ctx: Context
    trace: Optional[Any] = None  # benchmark.trace.Trace of the traced part


def start_profiler(trace_dir: str):
    """`jax.profiler.start_trace` without the Python tracer: it records
    every Python call of every thread (543 k events in 5 s of serving, PR
    24), which slows the very host loop whose gaps the trace is read for.
    `TraceAnnotation`s and the device's events are kept."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def span(name: str):
    """One of the benchmark's own host spans: a
    `jax.profiler.TraceAnnotation` named `bench/<name>`, so that it lands in
    the profiler's trace on the device's clock and `Trace.idle_gaps` can
    say what the host was doing while the device waited."""
    import jax
    return jax.profiler.TraceAnnotation("bench/" + name)
