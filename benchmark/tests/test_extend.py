"""A later PR adds a driver, a per-layer metric and a cell as files and
entries, and edits no file that is there; and a run that finds no TPU says
nothing."""
import json

from conftest import run_cell

DRIVER = '''"""A driver of a later PR: the training job, one more sample."""
from benchmark.by_name import load_module


def run(ctx):
    result = load_module("drivers", "train_job").run(ctx)
    result.samples["added_by_later_pr"] = 42.0
    return result
'''
READER = '''def read(run):
    return run.samples.get("added_by_later_pr")
'''


def test_add_driver_metric_mix_and_cell_as_files(bench_copy):
    b = bench_copy / "benchmark"
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    (b / "drivers" / "later_job.py").write_text(DRIVER)
    (b / "layer_metrics" / "later_metric.py").write_text(READER)
    mix = json.loads((b / "traffic" / "tiny-pretrain.json").read_text())
    mix["driver"] = "later_job"
    (b / "traffic" / "later-mix.json").write_text(json.dumps(mix))
    spec = json.loads((bench_copy / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tiny.later", "config": "falcon-tiny",
                              "traffic": "later-mix", "chips": 1,
                              "why": "added as data"})
    spec["per_layer"].append({
        "name": "later_metric", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "data", "workloads":
        ["tiny.later"], "moves": "train_tokens_per_s_per_chip"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "tiny.train" in m.get("workloads", []):
            m["workloads"].append("tiny.later")
    (bench_copy / "BENCHMARK.json").write_text(json.dumps(spec))
    p = run_cell(bench_copy, "tiny.later", 1)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["metrics"]["later_metric"] == {"value": 42.0, "unit": "count"}
    assert "train_step_ms" in res["metrics"]
    assert all(p.read_bytes() == data for p, data in before.items())


def test_no_tpu_no_result(bench_copy):
    p = run_cell(bench_copy, "tiny.train", 0, require_tpu=True)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
