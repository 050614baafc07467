"""Layer: engine loop. The tracing's own coverage: the share of the first
device's idle seconds that lie under some `mtpu/serve/...` leaf span,
`idle_wait` included. The rest is gaps under 50 microseconds and time no span
of the program covers."""
from benchmark import program_spans as ps


def read(run):
    split = ps.idle_by_span(run.trace, prefix="mtpu/serve/")
    if split is None:
        return None
    total, by = split
    if not total or len(by) == 1:        # no idle, or no span of the program
        return None
    return 100.0 * (total - by[ps.UNATTRIBUTED]) / total
