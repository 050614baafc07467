"""The command-a-plus serving cell's own files at tiny size on the CPU: the
driver `serve_open_loop_command_a` end to end (its warm-up of the chunk
programs and its check against `reference/command_a_plus.py` included) and
the readers PR 33 brought, added to the rehearsal's copy as a cell the way a
PR adds one; and that the real tree differs from the commit this cell was
added on by additions only."""
import json
import subprocess

import pytest

from conftest import REPO, run_cell

CELL = "tiny.serve-command-a"
PARENT = "7d3c31ed8357ac84aa3f4a46e43a391fbfe20514"      # PR 32


def add_cell(root):
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "command-a-tiny", "source": "rehearsal", "reduced": [],
        "file": "benchmark/configs/command-a-tiny.json", "why": "rehearsal"})
    spec["workloads"].append({
        "name": CELL, "config": "command-a-tiny",
        "traffic": "tiny-longdoc-command-a", "chips": 1, "why": "rehearsal"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "tiny.serve" in m.get("workloads", []):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


@pytest.mark.parametrize("trace", [0, 1])
def test_command_a_cell_last_line(bench_copy, trace):
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
    assert chk["prefill_chunks"] == 2           # 41 tokens: 24 + 17 in 24
    # 6 prompt lengths warmed: 8, 16, 24 one shot, 24 + 8, + 16, + 24
    assert chk["warm_requests"] == 6
    # 3 rings of 16 rows + 1 region of 128; k and v of 2 heads x 16, bf16
    row = 2 * 2 * 16 * 2
    assert chk["kv_bytes_per_slot"] == (3 * 16 + 128) * row
    assert chk["kv_ring_bytes"] == 8 * 3 * 16 * row
    assert chk["kv_full_bytes"] == 8 * 128 * row
    assert chk["kv_pool_bytes"] == chk["kv_ring_bytes"] + chk["kv_full_bytes"]
    load = chk["expert_load_window"]       # the window's own prompts
    assert load["prompts"] >= 1 and load["tokens"] == 48 * load["prompts"]
    # 4 of 8 experts held: about half of the (token, choice) rows
    assert all(0.3 <= x <= 0.7 for x in load["held_row_share"])
    assert all(0 < x <= 4.0 for x in load["groups_hit_per_decode_step"])
    assert all(0 < x <= 16.0 for x in load["held_rows_per_decode_step"])
    names = set(res["metrics"])
    if trace:
        assert {"serve_ttft_p95_ms", "serve_tokens_per_decode_step",
                "serve_kv_bytes_per_slot"} <= names
        assert res["metrics"]["serve_kv_bytes_per_slot"]["value"] == 176 * row
        # a TPU trace's: nothing on the CPU
        assert not {"serve_window_attend_ms_per_step",
                    "serve_full_attend_ms_per_step",
                    "moe_share_roofline_pct",
                    "serve_moe_experts_ms_per_step"} & names
    else:
        assert names == {"serve_ttft_p50_ms", "setup_s"}, names


# A ring fault planted in the rehearsal's copy alone: a driver that runs the
# real one over a program whose `_hybrid_update_attend` forgets `live_end`,
# so that a chunk's padding rows are written into the rings (the check's
# second chunk is 17 tokens in a bucket of 24: seven padding rows land on the
# rows of positions 25 to 31, inside the window of every decoded token).
FAULTY_DRIVER = '''
import jax.numpy as jnp
from benchmark.by_name import load_module
from megatron_tpu.models import attention

_real = load_module("drivers", "serve_open_loop_command_a")
_sound = attention._hybrid_update_attend


def _padding_into_rings(q, k, v, cache, *args, **kw):
    out, new = _sound(q, k, v, cache._replace(live_end=jnp.int32(
        attention.HybridKVCache.NO_PADDING)), *args, **kw)
    return out, new._replace(live_end=cache.live_end)


def run(ctx):
    attention._hybrid_update_attend = _padding_into_rings
    return _real.run(ctx)
'''


def test_padding_written_into_a_ring_is_read_by_the_check(bench_copy):
    """The harness's own comparison on the planted fault, beside the sound
    program on the same seed. At tiny widths in float32 weights the sound
    engine reads 0.002 in the mean and the fault 0.026, twelve times that and over the
    rehearsal's own limit; what the same fault reads at the published
    widths is in PERF.md section 6, PR 33 (far less: drawn weights attend
    almost evenly over 4,096 rows)."""
    add_cell(bench_copy)
    sound = json.loads(run_cell(bench_copy, CELL, 0).stdout.strip()
                       .splitlines()[-1])["checks"]
    (bench_copy / "benchmark" / "drivers"
     / "serve_open_loop_command_a_faulty.py").write_text(FAULTY_DRIVER)
    mix = bench_copy / "benchmark" / "traffic" / "tiny-longdoc-command-a.json"
    mix.write_text(json.dumps(dict(json.loads(mix.read_text()),
                                   driver="serve_open_loop_command_a_faulty")))
    p = run_cell(bench_copy, CELL, 0)
    assert p.returncode == 0, p.stderr[-4000:]
    faulty = json.loads(p.stdout.strip().splitlines()[-1])["checks"]
    print("sound", sound["logprob_mean_abs_diff"],
          sound["logprob_max_abs_diff"], "faulty",
          faulty["logprob_mean_abs_diff"], faulty["logprob_max_abs_diff"])
    assert sound["logprob_max_abs_diff"] < 2e-2
    assert faulty["logprob_max_abs_diff"] > 2e-2
    assert faulty["logprob_mean_abs_diff"] \
        > 5 * sound["logprob_mean_abs_diff"]


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
