"""Layer: kernels. Device time on the first device of the chunked scan's
kernel calls (`%_ssd_chunk_scan.N`: `megatron_tpu/ops/ssd_scan.py`, form
(a)), per `mtpu/serve/step` span of the traced window: what a prefill's and
a chunk's Mamba-2 scans cost an engine iteration. `None` where the trace is
not a TPU's, the program has no such kernel (a parent commit, a model
without a Mamba-2 layer) or the window has no step."""
from benchmark.program_spans import count_in
from benchmark.ssd_roofline import kernel_events


def read(run):
    events = kernel_events(run.trace)
    if not events:
        return None
    steps = count_in(run.trace, "mtpu/serve/step")
    if not steps:
        return None
    return 1e3 * sum(d for _, d in events) / steps
