"""Layer: kernels. Device time of the Pallas kernels (`custom-call`s whose
target is `tpu_custom_call`: the flash-attention forward kernel and its two
backward kernels) on the first device, per traced step."""
from benchmark.trace import is_pallas_kernel


def read(run):
    t, steps = run.trace, run.samples.get("traced_steps")
    if t is None or t.kind != "tpu" or not steps:
        return None
    seconds = t.seconds_where(is_pallas_kernel)
    return 1e3 * seconds / steps if seconds > 0 else None
