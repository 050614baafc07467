"""Layer: engine, prefill side. Median of `t_first - t_device` over the
requests submitted in the window whose row
(`megatron_tpu/utils/tracing.py::RequestRow`) has `programs == 1` and
`ahead_programs == 0`: one prefill program, its draw and the hand-over, as a
request feels them with nobody in front. `None` where the program keeps no
record (a parent commit)."""
from benchmark import request_timeline


def read(run):
    return request_timeline.own_prefill_p50_ms(run)
