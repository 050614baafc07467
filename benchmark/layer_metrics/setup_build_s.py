"""Layer: start-up. Seconds of `setup_s` under the program's phases
`mtpu/setup/mesh`, `init_state`, `load`, `data`, `generator` and `engine`
(`megatron_tpu/utils/tracing.py`'s start-up record) that lie in no trace,
lowering or backend event of the compile ledger: state made on the device and
the host. `None` where the program keeps no record (a parent commit)."""
from benchmark import startup


def read(run):
    return startup.build_s(run)
