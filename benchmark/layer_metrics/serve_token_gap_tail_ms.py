"""Layer: engine, decode side. The tail of the gap between a running
request's tokens, as a streaming client sees it. With `decode_sync_interval`
1 and no speculation every running request gets one token per engine
iteration, at `mtpu/serve/step.commit`, so the gaps are the intervals between
consecutive commit starts, with and without a prefill between them. Of those
n intervals (about 65 in a 5 s trace) this is the one with exactly ten longer
than it: the highest percentile that has ten samples beyond it, in
`stats.percentile`'s terms the 100 (n - 11)/(n - 1)-th, 84.4 for n = 65.
`None` under 21 intervals, and where the traffic file's `serving` sets
`decode_sync_interval` or `speculative_k`: a commit then delivers several
tokens at once."""
from benchmark.program_spans import serve_step_periods

BEYOND = 10


def read(run):
    serving = run.ctx.traffic.get("serving", {})
    if serving.get("decode_sync_interval", 1) != 1 \
            or serving.get("speculative_k", 0):
        return None
    both = serve_step_periods(run.trace)
    if both is None:
        return None
    gaps = sorted(both[0] + both[1])
    if len(gaps) < 2 * BEYOND + 1:
        return None
    return 1e3 * gaps[-1 - BEYOND]
