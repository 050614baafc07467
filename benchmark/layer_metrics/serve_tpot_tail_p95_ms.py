"""Layer: engine, decode side. The tail of time per further token: 95th
percentile over the requests that finished in the window of (finish - first
token) / (tokens - 1), a failed request at the length of the whole run. It
was the end-to-end `serve_tpot_p95_ms` until the driver's first check read it
spreading by 10-11 % of its median between seeds in `falcon-7b.serve-chat`
(PERF.md section 6), more than any bound a metric may have can carry; so it is
recorded here and not judged."""
from benchmark.stats import percentile, with_failures


def read(run):
    xs = run.samples.get("tpot_s")
    if not xs:
        return None
    return 1e3 * percentile(with_failures(xs, run.samples["run_s"]), 95)
