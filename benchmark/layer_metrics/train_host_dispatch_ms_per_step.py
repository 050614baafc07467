"""Layer: training loop. Milliseconds per traced step inside
`mtpu/train/step`: how long the host takes to enqueue one step. The loop runs
ahead of the device while this is well under `train_step_ms`; as it nears it,
the host is in the way."""
from benchmark import program_spans as ps


def read(run):
    seconds = ps.seconds_in(run.trace, "mtpu/train/step")
    steps = run.samples.get("traced_steps")
    if seconds is None or not steps:
        return None
    return 1e3 * seconds / steps
