"""Layer: start-up. The share of `setup_s` that lies under some phase of the
program's start-up record or inside some trace, lowering or backend event of
its compile ledger. The rest is imports, the backend's own start, and what the
driver does between the program's calls (the corpus it writes, the reference
check's run, the warm requests' queueing). Also writes the whole breakdown as
one line on standard error (`benchmark/startup.py::breakdown`). `None` where
the program keeps neither record (a parent commit)."""
from benchmark import startup


def read(run):
    return startup.attributed_pct(run)
