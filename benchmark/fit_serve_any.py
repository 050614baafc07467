"""`fit.py`'s serving rehearsal for a cell whose mix names a driver built on
`serve_open_loop` (`serve_open_loop_<model>`: `fit.py` itself takes that one
name and no other): the engine's decode program and its largest prefill
program compiled for a described `v5e:2x2` in the sandbox, the compiler's
memory count of each printed (`fit.report`).

    JAX_PLATFORMS=cpu python benchmark/fit_serve_any.py \
        --workload lfm2-8b-a1b.serve-chat-2k [--slots N] [--hlo DIR]

`--slots` tries another grid than the mix's; `--hlo DIR` also writes each
program's compiled text there (`<program>.txt`), to look for a copy of the
pool or of the state that should be an update in place. A rehearsal tool
like `fit.py`: nothing runs.
"""
from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--slots", type=int, default=None)
    p.add_argument("--hlo", default=None)
    args = p.parse_args()
    sys.path.insert(0, ROOT)
    from benchmark import fit, run as bench_run
    _, _, config, mix = bench_run.load_cell(args.workload)
    if not mix["driver"].startswith("serve_open_loop"):
        raise SystemExit(f"not a serving mix: driver {mix['driver']!r}")
    if args.slots is not None:
        mix = dict(mix, serving=dict(mix["serving"], num_slots=args.slots))

    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    if args.hlo:
        os.makedirs(args.hlo, exist_ok=True)
        report = fit.report

        def report_and_keep(name, compiled):
            with open(os.path.join(args.hlo, name + ".txt"), "w") as f:
                f.write(compiled.as_text())
            return report(name, compiled)
        fit.report = report_and_keep
    fit.fit_serve(config, mix, None, topo)


if __name__ == "__main__":
    main()
