"""Layer: engine, prefill side. Over the requests of any phase whose row
(`megatron_tpu/utils/tracing.py::RequestRow`) has `programs == 1` and
`ahead_programs == 0` and whose prefill segment `[t_device, t_first]` lies
inside the traced part: 100 x (1 - device 0's busy seconds inside the
segments / the segments' seconds), the share of a lone first token's prefill
segment in which the device waited for the host. The rows' clock is put on
the trace's by the `mtpu/serve/submit` spans
(`benchmark/request_timeline.py::clock_offset`). `None` off a TPU and where
the program keeps no record (a parent commit)."""
from benchmark import request_timeline


def read(run):
    return request_timeline.host_overhead_pct(run)
