"""Layer: engine, prefill side. Median of `first_token_time - admit_time`
over the window's completed requests."""
from benchmark.stats import percentile


def read(run):
    xs = run.samples.get("admit_to_first_s")
    return 1e3 * percentile(xs, 50) if xs else None
