"""Layer: start-up. Seconds of `setup_s` under the program's phase
`mtpu/setup/first_step`: `loop.train` from its entry through the train step's
first dispatch to the return of its flush, compile or load included. Training
cells alone. `None` where the program keeps no record (a parent commit)."""
from benchmark import startup


def read(run):
    return startup.first_step_s(run)
