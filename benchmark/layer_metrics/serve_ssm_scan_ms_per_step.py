"""Layer: kernels. Device time on the first device of the selective scan's
kernel calls (`%_ssm_selective_scan.N`: `megatron_tpu/ops/
selective_scan.py`, form (b); in a served chunk the `kCustom` fusion of the
call with the write of its state into the stacked cache, which carries the
kernel's name), per `mtpu/serve/step` span of the traced window: what a prefill's and a chunk's 26 scans cost an engine iteration.
`None` where the trace is not a TPU's, the program has no such kernel (a
parent commit, a model without a scan) or the window has no step."""
from benchmark.program_spans import count_in
from benchmark.ssm_roofline import kernel_events


def read(run):
    events = kernel_events(run.trace)
    if not events:
        return None
    steps = count_in(run.trace, "mtpu/serve/step")
    if not steps:
        return None
    return 1e3 * sum(d for _, d in events) / steps
