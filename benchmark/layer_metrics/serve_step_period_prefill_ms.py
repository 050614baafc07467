"""Layer: engine, prefill side. Median interval between consecutive starts
of `mtpu/serve/step.commit` over the intervals in which a prefill was
dispatched (`mtpu/serve/prefill` or `prefill_chunk` begins inside): how long
a landing prompt stalls every running request."""
from benchmark.program_spans import serve_step_periods
from benchmark.stats import percentile


def read(run):
    both = serve_step_periods(run.trace)
    if both is None or not both[1]:
        return None
    return 1e3 * percentile(both[1], 50)
