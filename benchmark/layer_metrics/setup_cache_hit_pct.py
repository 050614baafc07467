"""Layer: compile cache. Of the programs the persistent cache answered or
stored before the window opened (`cache_hits` + `cache_misses` of the
program's compile ledger), the share it answered: 100 is a warm start, 0 a
cold one, and two `setup_s` readings may be compared only where this reads
the same. Programs compiled in under a second are neither (JAX stores none),
so over `compile_requests_use_cache` a warm start would not read 100:
`benchmark/startup.py`. `None` where nothing was kept or asked for, and where
the program keeps no ledger (a parent commit)."""
from benchmark import startup


def read(run):
    return startup.cache_hit_pct(run)
