"""Layer: engine, decode side. Median, over the requests submitted in the
window that got a first token, of `t_device - t_admit` from the program's own
record of its requests (`megatron_tpu/utils/tracing.py::RequestRow`): what a
prompt admitted inside a running decode window waits for that window's
tokens, 0 for a prompt that met no window. Also writes the cut's `requests
{...}` line on standard error. `None` where the program keeps no record (a
parent commit)."""
from benchmark import request_timeline


def read(run):
    return request_timeline.behind_window_p50_ms(run)
