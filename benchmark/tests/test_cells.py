"""Each kind of cell end to end on the CPU at tiny size: the last line of
standard output parses and carries the contract's keys."""
import json

import pytest

from conftest import run_cell

E2E = {"tiny.train": {"train_tokens_per_s_per_chip", "setup_s"},
       "tiny.train-tp4": {"train_tokens_per_s_per_chip", "setup_s"},
       "tiny.serve": {"serve_ttft_p50_ms", "setup_s"}}
LAYER = {"tiny.train": {"data_wait_ms_per_step", "train_step_ms"},
         "tiny.train-tp4": {"data_wait_ms_per_step", "train_step_ms"},
         "tiny.serve": {"serve_ttft_p95_ms", "serve_queue_wait_p95_ms",
                        "serve_admit_to_first_p50_ms",
                        "serve_tokens_per_decode_step",
                        "serve_tpot_tail_p95_ms", "serve_tpot_mean_ms",
                        "gen_late_p95_ms"}}


@pytest.mark.parametrize("workload,devices", [
    ("tiny.train", 1), ("tiny.serve", 1), ("tiny.train-tp4", 4)])
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_last_line(bench_copy, workload, devices, trace):
    p = run_cell(bench_copy, workload, trace, devices=devices)
    assert p.returncode == 0, p.stderr[-4000:]
    lines = p.stdout.strip().splitlines()
    assert len(lines) == 1, "nothing but the result goes to standard output"
    res = json.loads(lines[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(res)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"       # never a device number
    names = set(res["metrics"])
    if trace:
        assert LAYER[workload] <= names, names
        assert "breakdown" in res and res["device"]["busy_s"] > 0
        assert res["device"]["window_s"] >= res["device"]["busy_s"]
    else:
        assert names == E2E[workload], names
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"]
