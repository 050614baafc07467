"""Layer: scheduler. 95th percentile of `admit_time - submit_time` over the
window's completed requests."""
from benchmark.stats import percentile


def read(run):
    xs = run.samples.get("queue_wait_s")
    return 1e3 * percentile(xs, 95) if xs else None
