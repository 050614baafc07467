"""Layer: data. Milliseconds a step waited for its batch: host clock round
the loop's pulls from its prefetching iterator, over the window."""


def read(run):
    s = run.samples
    if "data_wait_s" not in s or not s.get("steps"):
        return None
    return 1e3 * s["data_wait_s"] / s["steps"]
