"""Layer: data. Milliseconds per traced step inside `mtpu/train/data_next`:
the loop's pull from its iterator with the lift to the device, as the loop
itself brackets it. `data_wait_ms_per_step` is the benchmark's clock round
the pull alone."""
from benchmark import program_spans as ps


def read(run):
    seconds = ps.seconds_in(run.trace, "mtpu/train/data_next")
    steps = run.samples.get("traced_steps")
    if seconds is None or not steps:
        return None
    return 1e3 * seconds / steps
