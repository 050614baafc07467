"""Layer: sharding. Device time of the collective operations (all-gather,
all-reduce, reduce-scatter, all-to-all, collective-permute) on the first
device, per traced step. Whether compute hides them is not in this number
(PERF.md §7)."""
from benchmark.trace import is_collective


def read(run):
    t, steps = run.trace, run.samples.get("traced_steps")
    if t is None or t.kind != "tpu" or not steps:
        return None
    return 1e3 * t.seconds_where(is_collective) / steps
