"""The LFM2 serving cell's own files at tiny size on the CPU: the driver
`serve_open_loop_lfm2` end to end (its check against `reference/lfm2_moe.py`
included) and the readers PR 37 brought, added to the rehearsal's copy as a
cell the way a PR adds one; the planted padding fault read by the harness's
own comparison; and that the real tree differs from the commit this cell was
added on by additions only."""
import json
import subprocess

import pytest

from conftest import REPO, run_cell

CELL = "tiny.serve-lfm2"
PARENT = "0ea8c1ec0cbf5e8c376f5be1ab5b1a4287416650"      # PR 36


def add_cell(root):
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "lfm2-tiny", "source": "rehearsal", "reduced": [],
        "file": "benchmark/configs/lfm2-tiny.json", "why": "rehearsal"})
    spec["workloads"].append({
        "name": CELL, "config": "lfm2-tiny", "traffic": "tiny-chat-lfm2",
        "chips": 1, "why": "rehearsal"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "tiny.serve" in m.get("workloads", []):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


@pytest.mark.parametrize("trace", [0, 1])
def test_lfm2_cell_last_line(bench_copy, trace):
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
    # bf16 activations and weights against the float32 reference, matrices
    # drawn at the published widths' gain: a few 1e-2
    assert chk["logprob_positions"] == 8
    assert chk["logprob_max_abs_diff"] < 0.1
    # 4 padded lengths (16 to 64) x (a prefill of two and one of one)
    assert chk["warm_requests"] == 12
    # 3 attention layers of k and v of 2 heads x 8, bf16; 10 states of 2 x 64
    assert chk["kv_bytes_per_token"] == 3 * 2 * 2 * 8 * 2
    assert chk["conv_state_bytes"] == 8 * 10 * 2 * 64 * 2
    assert chk["kv_bytes_per_slot"] == 128 * 192 + 10 * 2 * 64 * 2
    assert chk["kv_pool_bytes"] == 8 * chk["kv_bytes_per_slot"]
    load = chk["expert_load_window"]       # the window's own prompts
    assert load["prompts"] >= 1 and load["tokens"] == 28 * load["prompts"]
    assert len(load["groups_hit_per_decode_step"]) == 12
    names = set(res["metrics"])
    if trace:
        assert {"serve_ttft_p95_ms", "serve_tokens_per_decode_step",
                "serve_kv_bytes_per_token",
                "serve_state_bytes_per_slot"} <= names
        assert res["metrics"]["serve_state_bytes_per_slot"]["value"] == 2560
        assert res["metrics"]["serve_kv_bytes_per_token"]["value"] == 192
        # a TPU trace's: nothing on the CPU
        assert not {"serve_conv_mix_ms_per_step",
                    "serve_conv_state_ms_per_step",
                    "serve_kv_attend_ms_per_step",
                    "serve_moe_experts_ms_per_step"} & names
    else:
        assert names == {"serve_ttft_p50_ms", "setup_s"}, names


# The padding fault planted in the rehearsal's copy alone: a driver that
# runs the real one over a program whose convolution layers forget
# `live_rows`, so that a prefill leaves the state behind its bucket's padding
# (the check's 21 tokens lie in a bucket of 32: the state is the one after
# eleven rows of token 0, not after row 20).
FAULTY_DRIVER = '''
import jax.numpy as jnp
from benchmark.by_name import load_module
from megatron_tpu.models import attention, short_conv

_real = load_module("drivers", "serve_open_loop_lfm2")
_sound = short_conv.short_conv_apply


def _state_at_the_buckets_end(params, x, cfg, *, kv_cache=None,
                              kind_layer=None):
    if kv_cache is None:
        return _sound(params, x, cfg)
    out, new = _sound(params, x, cfg, kind_layer=kind_layer,
                      kv_cache=kv_cache._replace(live_rows=jnp.int32(
                          attention.ConvKVCache.NO_PADDING)))
    return out, new._replace(live_rows=kv_cache.live_rows)


def run(ctx):
    short_conv.short_conv_apply = _state_at_the_buckets_end
    return _real.run(ctx)
'''


def test_a_state_taken_behind_the_padding_is_read_by_the_check(bench_copy):
    """The harness's own comparison on the planted fault, beside the sound
    program on the same seed: the first two decoded positions read the
    state the prefill left. What the same fault reads at the published
    widths is in PERF.md section 6, PR 37 (`state_fault_at_width.py`)."""
    add_cell(bench_copy)
    sound = json.loads(run_cell(bench_copy, CELL, 0).stdout.strip()
                       .splitlines()[-1])["checks"]
    (bench_copy / "benchmark" / "drivers"
     / "serve_open_loop_lfm2_faulty.py").write_text(FAULTY_DRIVER)
    mix = bench_copy / "benchmark" / "traffic" / "tiny-chat-lfm2.json"
    mix.write_text(json.dumps(dict(json.loads(mix.read_text()),
                                   driver="serve_open_loop_lfm2_faulty")))
    p = run_cell(bench_copy, CELL, 0)
    assert p.returncode == 0, p.stderr[-4000:]
    faulty = json.loads(p.stdout.strip().splitlines()[-1])["checks"]
    print("sound", sound["logprob_mean_abs_diff"],
          sound["logprob_first_two_max_abs_diff"], "faulty",
          faulty["logprob_mean_abs_diff"],
          faulty["logprob_first_two_max_abs_diff"])
    assert sound["logprob_max_abs_diff"] < 0.1
    assert faulty["logprob_first_two_max_abs_diff"] > 0.2
    assert faulty["logprob_first_two_max_abs_diff"] \
        > 5 * sound["logprob_first_two_max_abs_diff"]


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
