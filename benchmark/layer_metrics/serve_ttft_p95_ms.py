"""Layer: scheduler. The tail of time to first token: 95th percentile over
the requests due in the window, counted from when each was due, a failed
request at the length of the whole run. It is a per-layer metric and not an
end-to-end one because over 198 requests it spreads by 6-10 % between runs of
the same code (PERF.md section 6), more than half of the widest bound a metric
may have; the median beside it is what a PR is judged on."""
from benchmark.stats import percentile, with_failures


def read(run):
    xs = run.samples.get("ttft_s")
    if not xs:
        return None
    return 1e3 * percentile(with_failures(xs, run.samples["run_s"]), 95)
