"""Run by hand: `JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`.
These tests are outside tier-1's `tests/`."""
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TINY_E2E = [
    {"name": "train_tokens_per_s_per_chip", "unit": "tokens/s/chip",
     "better": "higher", "bound": 0.1, "source": "host_clock",
     "workloads": ["tiny.train", "tiny.train-tp4"]},
    {"name": "serve_ttft_p50_ms", "unit": "ms", "better": "lower",
     "bound": 0.1, "source": "host_clock", "workloads": ["tiny.serve"]},
    {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
     "source": "host_clock"}]


@pytest.fixture
def bench_copy(tmp_path):
    """A copy of `benchmark/` with the tiny configurations and mixes added
    as files, and a BENCHMARK.json of tiny cells beside it: what a later PR
    does to add a cell, and the only way these tests reach a CPU-sized run."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for kind in ("configs", "traffic"):
        for f in os.listdir(os.path.join(HERE, "tiny", kind)):
            shutil.copy(os.path.join(HERE, "tiny", kind, f),
                        root / "benchmark" / kind / f)
    real = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    per_layer = []
    for m in real["per_layer"]:
        m = dict(m)
        m["workloads"] = (["tiny.serve"] if m["moves"].startswith("serve")
                          else ["tiny.train", "tiny.train-tp4"])
        per_layer.append(m)
    spec = dict(real, end_to_end=TINY_E2E, per_layer=per_layer, configs=[
        {"name": "falcon-tiny", "source": "rehearsal", "reduced": [],
         "file": "benchmark/configs/falcon-tiny.json", "why": "rehearsal"},
        {"name": "falcon-tiny-tp4", "source": "rehearsal", "reduced": [],
         "file": "benchmark/configs/falcon-tiny-tp4.json", "why": "rehearsal"}],
        workloads=[
        {"name": "tiny.train", "config": "falcon-tiny",
         "traffic": "tiny-pretrain", "chips": 1, "why": "rehearsal"},
        {"name": "tiny.serve", "config": "falcon-tiny",
         "traffic": "tiny-chat", "chips": 1, "why": "rehearsal"},
        {"name": "tiny.train-tp4", "config": "falcon-tiny-tp4",
         "traffic": "tiny-pretrain", "chips": 4, "why": "rehearsal"}])
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def run_cell(root, workload, trace, seconds=2, seed=3000000019, devices=1,
             require_tpu=False):
    """The command of BENCHMARK.json in a child, with the TPU requirement
    relaxed by this test alone (`main(require_tpu=False)`)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               JAX_COMPILATION_CACHE_DIR=str(root / ".jax_cache"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    code = ("import sys; sys.path.insert(0, %r); "
            "from benchmark import run; "
            "sys.exit(run.main(sys.argv[1:], require_tpu=%r))"
            % (str(root), require_tpu))
    p = subprocess.run(
        [sys.executable, "-c", code, "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    return p
