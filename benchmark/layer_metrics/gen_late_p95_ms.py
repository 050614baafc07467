"""Layer: load generator. 95th percentile of how late a request was
submitted against the time it was due: a starved generator must not be read
as a fast server."""
from benchmark.stats import percentile


def read(run):
    xs = run.samples.get("gen_late_s")
    return 1e3 * percentile(xs, 95) if xs else None
