"""Layer: kernels. Device time of the expert products (the
`_moe_grouped_matmul*` Pallas kernels, `benchmark/moe_roofline.py`) on the
first device per `mtpu/serve/step` span of the traced window. Prefill and
decode programs are in it together: the trace cannot split them yet
(PERF.md section 7)."""
from benchmark.moe_roofline import kernel_events
from benchmark.program_spans import count_in


def read(run):
    events = kernel_events(run.trace)
    steps = count_in(run.trace, "mtpu/serve/step") if events else None
    if not steps:
        return None
    return 1e3 * sum(d for _, d in events) / steps
