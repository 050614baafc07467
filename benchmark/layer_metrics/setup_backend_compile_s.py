"""Layer: compile cache. Seconds of `setup_s` inside some
`/jax/core/compile/backend_compile_duration` event of the program's compile
ledger (`megatron_tpu/utils/compile_cache.py`) that ended before the window
opened: the backend compiling, or loading from the persistent cache in its
place. `None` where the program keeps no ledger (a parent commit)."""
from benchmark import startup


def read(run):
    return startup.backend_s(run)
