"""Layer: compile cache. Seconds of `setup_s` inside some outermost
`jaxpr_trace_duration` or some `jaxpr_to_mlir_module_duration` event of the
program's compile ledger before the window opened: tracing to jaxprs and
lowering to MLIR, host Python that a warm cache does not save. `None` where
the program keeps no ledger (a parent commit)."""
from benchmark import startup


def read(run):
    return startup.trace_lower_s(run)
