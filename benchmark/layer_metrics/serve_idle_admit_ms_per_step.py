"""Layer: scheduler and engine, prefill side. Milliseconds per decode
window the first device sat idle while the engine thread was reaping,
admitting, or building and dispatching a prefill (`mtpu/serve/reap`, `admit`,
`prefill`, `prefill_chunk` as leaves)."""
from benchmark.program_spans import idle_ms_per

SPANS = ("mtpu/serve/reap", "mtpu/serve/admit", "mtpu/serve/prefill",
         "mtpu/serve/prefill_chunk")


def read(run):
    return idle_ms_per(run.trace, "mtpu/serve/step", SPANS)
