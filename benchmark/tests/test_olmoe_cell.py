"""The OLMoE serving cell's own files at tiny size on the CPU: the driver
`serve_open_loop_olmoe` end to end (its check against
`reference/olmoe.py` included), added to the rehearsal's copy as a cell the
way a PR adds one; and that the real tree differs from the commit this cell
was added on by additions only."""
import json
import os
import subprocess

import pytest

from conftest import REPO, run_cell

CELL = "tiny.serve-olmoe"
PARENT = "58b1158edeb51ffa179b6dd9d5b1fe4ddaa7ffcc"      # PR 26


def add_cell(root):
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "olmoe-tiny", "source": "rehearsal", "reduced": [],
        "file": "benchmark/configs/olmoe-tiny.json", "why": "rehearsal"})
    spec["workloads"].append({
        "name": CELL, "config": "olmoe-tiny", "traffic": "tiny-chat-olmoe",
        "chips": 1, "why": "rehearsal"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "tiny.serve" in m.get("workloads", []):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


@pytest.mark.parametrize("trace", [0, 1])
def test_olmoe_cell_last_line(bench_copy, trace):
    add_cell(bench_copy)
    p = run_cell(bench_copy, CELL, trace)
    assert p.returncode == 0, p.stderr[-4000:]
    lines = p.stdout.strip().splitlines()
    assert len(lines) == 1, "nothing but the result goes to standard output"
    res = json.loads(lines[-1])
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"       # never a device number
    chk = res["checks"]
    # bf16 activations against the float32 reference, at tiny widths: a few
    # 1e-3 (a dropped token or renormalised gates: 1e-1)
    assert chk["logprob_positions"] == 8
    assert chk["logprob_max_abs_diff"] < 2e-2
    assert chk["logprob_positions_over_0_05"] == 0
    assert len(chk["expert_load_max_over_mean"]) == 2          # layers
    # 8 experts, 2 a token: a drawn router gives up to 4; the spread ones
    # the driver sets up stay near 1 on 47 tokens
    assert all(1.0 <= x <= 2.5 for x in chk["expert_load_max_over_mean"])
    load = chk["expert_load_window"]       # the window's own prompts
    assert load["prompts"] >= 1 and load["tokens"] == 47 * load["prompts"]
    assert all(1.0 <= x <= 2.5 for x in load["max_over_mean"])
    assert load["experts_without_a_token"] == [0, 0]
    assert all(6.0 <= x <= 8.0 for x in load["groups_hit_per_decode_step"])
    names = set(res["metrics"])
    if trace:
        assert {"serve_ttft_p95_ms", "serve_tokens_per_decode_step"} <= names
        # the kernel's metrics are a TPU trace's: nothing on the CPU
        assert not {"serve_moe_experts_ms_per_step",
                    "moe_grouped_matmul_roofline_pct"} & names
    else:
        assert names == {"serve_ttft_p50_ms", "setup_s"}, names


def test_real_tree_differs_from_its_parent_by_additions_only():
    def git(*args):
        return subprocess.run(["git", *args], cwd=REPO, capture_output=True,
                              text=True)
    if git("cat-file", "-e", PARENT + "^{commit}").returncode != 0:
        pytest.skip("no git history here (a chip machine's copy)")
    status = git("diff", "--name-status", PARENT, "--", "benchmark").stdout
    changed = [line for line in status.splitlines()
               if line and not line.startswith("A")]
    assert changed == [], changed
    old = json.loads(git("show", PARENT + ":BENCHMARK.json").stdout)
    new = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    assert new["command"] == old["command"]
    assert new["run_seconds"] == old["run_seconds"]
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len(new[key]) >= len(old[key])
        for was, now in zip(old[key], new[key]):       # new entries are last
            grown = dict(now)
            if "workloads" in was:                     # names appended only
                n = len(was["workloads"])
                assert now["workloads"][:n] == was["workloads"]
                grown["workloads"] = was["workloads"]
            assert grown == was, (key, was["name"])
