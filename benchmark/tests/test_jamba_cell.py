"""The Jamba serving cell's own files at tiny size on the CPU: the driver
`serve_open_loop_jamba` end to end, chunked prefill on (its check against
`reference/jamba.py` included: two chunks of 32, then 13 rows in a bucket of
16, 8 tokens decoded through pool and state), and the readers PR 47 brought
that need no TPU, added to the rehearsal's copy as a cell the way a PR adds
one; and that the real tree differs from the commit this cell was added on
by additions only."""
import json
import subprocess

import pytest

from conftest import REPO, run_cell

CELL = "tiny.serve-jamba"
PARENT = "c3441042a456cc67e8aa3c178e4b046ac4c0ab5d"      # PR 45


def add_cell(root):
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "jamba-tiny", "source": "rehearsal", "reduced": [],
        "file": "benchmark/configs/jamba-tiny.json", "why": "rehearsal"})
    spec["workloads"].append({
        "name": CELL, "config": "jamba-tiny",
        "traffic": "tiny-longdoc-jamba", "chips": 1, "why": "rehearsal"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "tiny.serve" in m.get("workloads", []):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


@pytest.mark.parametrize("trace", [0, 1])
def test_jamba_cell_last_line(bench_copy, trace):
    add_cell(bench_copy)
    p = run_cell(bench_copy, CELL, trace)
    assert p.returncode == 0, p.stderr[-4000:]
    lines = p.stdout.strip().splitlines()
    assert len(lines) == 1, "nothing but the result goes to standard output"
    res = json.loads(lines[-1])
    chk = res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"       # never a device number
    # bf16 activations over float32 weights against the float32 reference,
    # at tiny widths: a few 1e-2 (the limits are the published widths')
    assert chk["logprob_positions"] == 8
    assert chk["logprob_max_abs_diff"] < 0.1
    assert chk["prefill_chunks"] == 3               # 32 + 32 + 13 of 77
    # 2 attention layers of k and v of one head of 16, bf16; 26 states of
    # 16 x 128 float32 and 3 x 128 bf16; 8 slots of 128
    assert chk["kv_bytes_per_token"] == 2 * 2 * 16 * 2
    assert chk["ssm_state_bytes"] == 8 * 26 * 16 * 128 * 4
    assert chk["conv_state_bytes"] == 8 * 26 * 3 * 128 * 2
    assert chk["kv_bytes_per_slot"] == 128 * 128 + 26 * (8192 + 768)
    names = set(res["metrics"])
    if trace:
        assert {"serve_ttft_p95_ms", "serve_tokens_per_decode_step",
                "serve_kv_bytes_per_token", "serve_state_bytes_per_slot",
                "serve_ssm_state_bytes_per_slot",
                "serve_prefill_chunks_per_prompt"} <= names
        assert res["metrics"]["serve_ssm_state_bytes_per_slot"]["value"] \
            == 26 * 8192
        assert res["metrics"]["serve_state_bytes_per_slot"]["value"] \
            == 26 * 768
        assert res["metrics"]["serve_kv_bytes_per_token"]["value"] == 128
        assert 1.0 <= res["metrics"]["serve_prefill_chunks_per_prompt"][
            "value"] < 2.5
        # a TPU trace's: nothing on the CPU
        assert not {"serve_ssm_scan_ms_per_step", "ssm_scan_roofline_pct",
                    "serve_ssm_state_ms_per_step",
                    "serve_ssm_conv_state_ms_per_step",
                    "serve_ssm_kv_attend_ms_per_step"} & names
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
    new = json.load(open(f"{REPO}/BENCHMARK.json"))
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
    # every entry this PR adds lists its cells (ISSUE 47, PR 46's refusal)
    for m in new["per_layer"][len(old["per_layer"]):]:
        assert m["workloads"] == ["jamba2-3b.serve-longdoc-32k"], m
