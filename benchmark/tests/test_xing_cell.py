"""The Xing4.0 serving cell's own files at tiny size on the CPU: the driver
`serve_open_loop_xing` end to end, chunked prefill over the latent pool on
(its check against `reference/xing4.py` included: a chunk of 32 in the
expanded form, 13 rows in a bucket of 16 in the absorbed form, 8 tokens
decoded), and the reader PR 41 brought that needs no TPU, added to the
rehearsal's copy as a cell the way a PR adds one."""
import json

import pytest

from conftest import run_cell

CELL = "tiny.serve-xing"


def add_cell(root):
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "xing-tiny", "source": "rehearsal", "reduced": [],
        "file": "benchmark/configs/xing-tiny.json", "why": "rehearsal"})
    spec["workloads"].append({
        "name": CELL, "config": "xing-tiny",
        "traffic": "tiny-mixed-xing", "chips": 1, "why": "rehearsal"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "tiny.serve" in m.get("workloads", []):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


@pytest.mark.parametrize("trace", [0, 1])
def test_xing_cell_last_line(bench_copy, trace):
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
    assert chk["logprob_max_abs_diff"] < 3e-2
    assert chk["prefill_chunks"] == 2           # 32 + 13 of 45
    # a latent row of 32 + 16 values, bf16, 5 layers; 8 slots of 128
    assert chk["kv_bytes_per_token"] == 48 * 2 * 5
    assert chk["kv_pool_bytes"] == 48 * 2 * 5 * 8 * 128
    assert len(chk["expert_load_max_over_mean"]) == 3       # expert layers
    # the drawn maps do work: H_res between the identity and uniform, moving
    # from token to token, doubly stochastic within what 20 rounds leave
    maps = chk["hc_maps"]
    assert 0.35 < maps["h_res_row_max_mean"] < 0.9, maps
    assert maps["h_res_row_max_std_over_tokens"] > 0.02
    assert 0.05 < maps["h_pre_std"] and 0.1 < maps["h_post_std"]
    assert maps["h_res_row_sum_max_err"] < 1e-5
    assert maps["h_res_column_sum_max_err"] < 0.1   # what 20 rounds leave
    names = set(res["metrics"])
    if trace:
        assert {"serve_ttft_p95_ms", "serve_tokens_per_decode_step",
                "serve_kv_bytes_per_token",
                "serve_prefill_chunks_per_prompt"} <= names
        assert res["metrics"]["serve_kv_bytes_per_token"]["value"] == 480
        # the mix's longest prompts take three chunks, most take one
        assert 1.0 <= res["metrics"]["serve_prefill_chunks_per_prompt"][
            "value"] < 2.0
        # a TPU trace's: nothing on the CPU
        assert not {"serve_hc_map_ms_per_step", "serve_hc_mix_ms_per_step",
                    "serve_latent_attend_ms_per_step"} & names
    else:
        assert names == {"serve_ttft_p50_ms", "setup_s"}, names
