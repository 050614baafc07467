"""Layer: kernels. Device time on the first device of the scalar-decay
chunked delta rule's kernel calls (`%_gdn_chunk.N`: `megatron_tpu/ops/
kda_chunk.py`, form (d)), per `mtpu/serve/step` span of the traced window:
what a prefill's and a chunk's Gated DeltaNet scans cost an engine
iteration. `None` where the trace is not a TPU's, the program has no such
kernel (a parent commit, a model without such a layer) or the window has no
step."""
from benchmark.gdn_roofline import kernel_events
from benchmark.program_spans import count_in


def read(run):
    events = kernel_events(run.trace)
    if not events:
        return None
    steps = count_in(run.trace, "mtpu/serve/step")
    if not steps:
        return None
    return 1e3 * sum(d for _, d in events) / steps
