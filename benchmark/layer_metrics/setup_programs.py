"""Layer: compile cache. Programs compiled or loaded before the window
opened, by the program's compile ledger (its backend events): every prefill
bucket, the decode or train step, the reference check's, the driver's own,
and each eager operation's. `None` where the program keeps no ledger (a
parent commit)."""
from benchmark import startup


def read(run):
    return startup.programs(run)
