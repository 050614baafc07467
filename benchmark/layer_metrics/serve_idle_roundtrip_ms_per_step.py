"""Layer: engine, decode side. Milliseconds per decode window the first
device sat idle under `mtpu/serve/step.upload`, `step.draft`, `step.dispatch`
and `step.fetch`: what the host round trip of every token costs the chip
(ROADMAP S5). Under `step.fetch` the device is idle only after its last
operation: the transfer of the sampled tokens and the host's wake-up."""
from benchmark.program_spans import idle_ms_per

SPANS = ("mtpu/serve/step.upload", "mtpu/serve/step.draft",
         "mtpu/serve/step.dispatch", "mtpu/serve/step.fetch")


def read(run):
    return idle_ms_per(run.trace, "mtpu/serve/step", SPANS)
