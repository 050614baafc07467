"""Layer: engine, decode side. Milliseconds per decode window the first
device sat idle while the engine thread was in `mtpu/serve/step.commit`: the
host's bookkeeping after the fetch (token append, FSM, evictions, gauges)."""
from benchmark.program_spans import idle_ms_per

SPANS = ("mtpu/serve/step.commit",)


def read(run):
    return idle_ms_per(run.trace, "mtpu/serve/step", SPANS)
