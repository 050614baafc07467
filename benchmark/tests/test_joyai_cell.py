"""The JoyAI serving cell's own files at tiny size on the CPU: the driver
`serve_open_loop_joyai` end to end (its check against `reference/joyai.py`
included) and the two readers PR 31 brought, added to the rehearsal's copy as
a cell the way a PR adds one; and that the real tree differs from the commit
this cell was added on by additions only."""
import json
import subprocess

import pytest

from conftest import REPO, run_cell

CELL = "tiny.serve-joyai"
PARENT = "280f7aa07cb37deb896eb3373fe9323bc791e010"      # PR 30


def add_cell(root):
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "joyai-tiny", "source": "rehearsal", "reduced": [],
        "file": "benchmark/configs/joyai-tiny.json", "why": "rehearsal"})
    spec["workloads"].append({
        "name": CELL, "config": "joyai-tiny",
        "traffic": "tiny-longdoc-joyai", "chips": 1, "why": "rehearsal"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "tiny.serve" in m.get("workloads", []):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


@pytest.mark.parametrize("trace", [0, 1])
def test_joyai_cell_last_line(bench_copy, trace):
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
    # bf16 activations over float32 weights against the float32 reference,
    # at tiny widths: a few 1e-3
    assert chk["logprob_positions"] == 8
    assert chk["logprob_max_abs_diff"] < 2e-2
    assert chk["logprob_positions_over_0_05"] == 0
    # a latent row of 32 + 8 values, bf16, 4 layers; 8 slots of 128
    assert chk["kv_bytes_per_token"] == 40 * 2 * 4
    assert chk["kv_pool_bytes"] == 40 * 2 * 4 * 8 * 128
    assert len(chk["expert_load_max_over_mean"]) == 3       # expert layers
    assert all(1.0 <= x <= 2.5 for x in chk["expert_load_max_over_mean"])
    load = chk["expert_load_window"]       # the window's own prompts
    assert load["prompts"] >= 1 and load["tokens"] == 47 * load["prompts"]
    assert all(5.0 <= x <= 8.0 for x in load["groups_hit_per_decode_step"])
    names = set(res["metrics"])
    if trace:
        assert {"serve_ttft_p95_ms", "serve_tokens_per_decode_step",
                "serve_kv_bytes_per_token"} <= names
        assert res["metrics"]["serve_kv_bytes_per_token"]["value"] == 320
        # a TPU trace's: nothing on the CPU
        assert not {"serve_latent_attend_ms_per_step",
                    "serve_moe_experts_ms_per_step",
                    "moe_stacked_bank_roofline_pct"} & names
    else:
        assert names == {"serve_ttft_p50_ms", "setup_s"}, names


def test_latent_reader_counts_the_pools_shape():
    """`serve_latent_attend_ms_per_step` on a hand-built trace: operations
    that hold an array of the pool's shape, with or without the layers'
    axis, and no other."""
    import types
    from benchmark.by_name import load_module
    from benchmark.trace import Trace
    ops = [("%fusion.1 = f32[8,4,1,128]{3,2,1,0} fusion(bf16[8,1,4,40]{3,2,1,0} "
            "%q, bf16[4,8,128,40]{3,2,1,0} %pool)", 0.0, 0.010),
           ("%scatter.2 = bf16[4,8,128,40]{3,2,1,0} scatter(bf16[4,8,128,40]"
            "{3,2,1,0} %pool, s32[8,1,3]{2,1,0} %i, bf16[8,1,40]{2,1,0} %new)",
            0.010, 0.002),
           ("%fusion.3 = bf16[8,4,32]{2,1,0} fusion(bf16[8,40,128]{2,1,0} "
            "%layer)", 0.012, 0.004),
           ("%fusion.4 = bf16[8,128,48]{2,1,0} fusion(bf16[8,64]{1,0} %x)",
            0.016, 0.050)]
    spans = [("mtpu/serve/step", 0.001, 0.03), ("mtpu/serve/step", 0.04, 0.02)]
    trace = Trace(kind="tpu", window_s=0.066, ops={0: ops}, spans=spans)
    ctx = types.SimpleNamespace(
        config={"kv_lora_rank": 32, "qk_rope_head_dim": 8,
                "num_hidden_layers": 4},
        traffic={"serving": {"num_slots": 8, "max_len": 128}})
    read = load_module("layer_metrics", "serve_latent_attend_ms_per_step").read
    run = types.SimpleNamespace(ctx=ctx, trace=trace)
    assert read(run) == pytest.approx(1e3 * 0.016 / 2)
    # a program without latent attention, and the CPU's trace: nothing
    ctx.config = {"num_hidden_layers": 4}
    assert read(run) is None
    ctx.config = {"kv_lora_rank": 32, "qk_rope_head_dim": 8,
                  "num_hidden_layers": 4}
    run.trace = Trace(kind="host-xla", window_s=1.0, ops={0: ops}, spans=spans)
    assert read(run) is None


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
