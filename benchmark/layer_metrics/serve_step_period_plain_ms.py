"""Layer: engine, decode side. Median interval between consecutive starts of
`mtpu/serve/step.commit` over the intervals in which no prefill was
dispatched (no `mtpu/serve/prefill` or `prefill_chunk` span begins inside):
the period of a decode window when nothing lands. Intervals in which the loop
went idle (`idle_wait`) are left out."""
from benchmark.program_spans import serve_step_periods
from benchmark.stats import percentile


def read(run):
    both = serve_step_periods(run.trace)
    if both is None or not both[0]:
        return None
    return 1e3 * percentile(both[0], 50)
