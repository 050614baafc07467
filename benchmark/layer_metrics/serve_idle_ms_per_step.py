"""Layer: engine loop. Milliseconds the first device sat idle per decode
window: its idle seconds between the traced window's first and last
operation, over the `mtpu/serve/step` spans that begin in that window. The
metrics beside this one (`serve_idle_*`) say under which of the program's
spans the device waited."""
from benchmark.program_spans import idle_ms_per


def read(run):
    return idle_ms_per(run.trace, "mtpu/serve/step")
