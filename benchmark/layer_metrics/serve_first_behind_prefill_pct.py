"""Layer: scheduler. Share of the requests submitted in the window that got a
first token whose row (`megatron_tpu/utils/tracing.py::RequestRow`) has
`ahead_programs > 0` or `held > 0`: first tokens that waited for somebody
else's prefill program, dispatched in front of theirs or owed the one program
between two windows. `None` where the program keeps no record (a parent
commit)."""
from benchmark import request_timeline


def read(run):
    return request_timeline.behind_prefill_pct(run)
