"""The Qwen3-Next serving cell's own files at tiny size on the CPU: the
driver `serve_open_loop_qwen3_next` end to end, chunked prefill on with
bucket = chunk (its check against `reference/qwen3_next.py` included: two
chunks of 32, then 13 rows in the bucket of 32, 8 tokens decoded through
pool and state, and a second request that ends 6 rows behind a chunk's
start, each slot's matrices, depthwise inputs and last keys against the
reference's; the window's expert load under a share of 4 of 8 experts), and
the readers PR 60 brought that need no TPU, added to the rehearsal's copy as
a cell the way a PR adds one; and that the real tree differs from the commit
this cell was added on by additions only."""
import json
import subprocess

import pytest

from conftest import REPO, run_cell

CELL = "tiny.serve-qwen3-next"
PARENT = "bc8a185dff35c75b265f8ae1437950613e9e91e0"      # PR 59
REAL = "qwen3-next-80b-a3b.serve-longdoc-32k"


def add_cell(root):
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "qwen3-next-tiny", "source": "rehearsal", "reduced": [],
        "file": "benchmark/configs/qwen3-next-tiny.json",
        "why": "rehearsal"})
    spec["workloads"].append({
        "name": CELL, "config": "qwen3-next-tiny",
        "traffic": "tiny-longdoc-qwen3-next", "chips": 1,
        "why": "rehearsal"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "tiny.serve" in m.get("workloads", []):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


@pytest.mark.parametrize("trace", [0, 1])
def test_qwen3_next_cell_last_line(bench_copy, trace):
    add_cell(bench_copy)
    p = run_cell(bench_copy, CELL, trace)
    assert p.returncode == 0, p.stderr[-4000:]
    lines = p.stdout.strip().splitlines()
    assert len(lines) == 1, "nothing but the result goes to standard output"
    res = json.loads(lines[-1])
    chk = res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"       # never a device number
    # bf16 activations and weights against the float32 reference, at tiny
    # widths: a few 1e-2 (the limits are the published widths')
    assert chk["logprob_positions"] == 8
    assert chk["logprob_max_abs_diff"] < 0.2
    assert chk["prefill_chunks"] == 3               # 32 + 32 + 13 of 77
    carry = chk["carry"]
    assert carry["prompt"] == 70 and carry["prefill_chunks"] == 3
    assert carry["logprob_positions"] == 8
    assert carry["logprob_max_abs_diff"] < 0.2
    print("state", {k: (chk[k], carry[k]) for k in
                    ("state_rel_err", "inputs_rel_err", "keys_rel_err")})
    # the first linear layer's rows carry no other layer's rounding
    assert chk["state_first_layer_rel_err"] \
        == chk["state_rel_err_by_layer"][0] <= chk["state_rel_err"]
    assert len(chk["state_rel_err_by_layer"]) == 6
    for part in ("state_rel_err", "inputs_rel_err", "keys_rel_err"):
        # (a routing flip of one decoded row moves a tiny model's state by
        # several parts in a hundred; 4 of 8 experts of width 32)
        assert max(chk[part], carry[part]) < 0.2, part
    assert {chk["state_rows_ahead"], carry["state_rows_ahead"]} <= {0, 1}
    # 2 attention layers of 2 kv heads of 16, keys and values, bf16; 6
    # states of 4 x 16 x 16 float32 and 3 x 128 bf16; 8 slots of 128
    assert chk["kv_bytes_per_token"] == 2 * 2 * 32 * 2
    assert chk["gdn_state_bytes"] == chk["state_bytes_as_stated"] \
        == 8 * 6 * 4 * 16 * 16 * 4
    assert chk["conv_state_bytes"] == 8 * 6 * 3 * 128 * 2
    load = chk["expert_load_window"]
    assert load["prompts"] >= 1 and len(load["held_row_share"]) == 8
    assert all(0.2 < s < 0.8 for s in load["held_row_share"])
    names = set(res["metrics"])
    if trace:
        assert {"serve_ttft_p95_ms", "serve_tokens_per_decode_step",
                "serve_kv_bytes_per_token", "serve_state_bytes_per_slot",
                "serve_gdn_state_bytes_per_slot",
                "serve_prefill_chunks_per_prompt"} <= names
        assert res["metrics"]["serve_gdn_state_bytes_per_slot"]["value"] \
            == 6 * 4096
        assert res["metrics"]["serve_state_bytes_per_slot"]["value"] \
            == 6 * 768
        assert res["metrics"]["serve_kv_bytes_per_token"]["value"] == 256
        # a TPU trace's: nothing on the CPU
        assert not {"serve_gdn_scan_ms_per_step", "gdn_chunk_roofline_pct",
                    "serve_gdn_state_ms_per_step",
                    "serve_gdn_conv_ms_per_step",
                    "serve_gdn_kv_attend_ms_per_step",
                    "serve_kda_scan_ms_per_step",
                    "moe_share_roofline_pct"} & names
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
                assert set(now["workloads"][n:]) <= {REAL}
                grown["workloads"] = was["workloads"]
            assert grown == was, (key, was["name"])
    assert [c["name"] for c in new["workloads"][len(old["workloads"]):]] \
        == [REAL]
    assert len(new["configs"]) == len(old["configs"]) + 1
    # every entry this PR adds lists its cell
    for m in new["per_layer"][len(old["per_layer"]):]:
        assert m["workloads"] == [REAL], m
