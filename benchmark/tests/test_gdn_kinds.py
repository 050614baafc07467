"""`gdn_kinds.py`'s patterns on hand-made event texts, and the readers of
the pool's Gated DeltaNet state, depthwise inputs and folded keys and values
on a hand-made trace and hand-made samples; the new configuration and mix as
files."""
import json
import os
import types

import pytest

from benchmark import gdn_kinds
from benchmark.by_name import load_module
from benchmark.tests.test_gdn_roofline import gdn_call
from benchmark.trace import Trace

GDN_HERE = os.path.dirname(os.path.abspath(__file__))
GDN_BENCH = os.path.dirname(GDN_HERE)
GDN_CFG = json.load(open(os.path.join(
    GDN_BENCH, "configs", "qwen3-next-80b-a3b-8l.json")))
GDN_MIX = json.load(open(os.path.join(
    GDN_BENCH, "traffic", "longdoc-30k-chunk4k-open-loop.json")))
GDN_SERVING = GDN_MIX["serving"]
GDN_CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
GDN_CELL = "qwen3-next-80b-a3b.serve-longdoc-32k"


def test_the_qwen3_next_configuration_holds_the_sources_keys():
    assert set(GDN_CFG["reduced"]) == {"num_hidden_layers", "num_experts",
                                       "vocab_size"}
    assert (GDN_CFG["num_hidden_layers"], GDN_CFG["num_experts"],
            GDN_CFG["vocab_size"]) == (8, 128, 37984)
    assert GDN_CFG["published"]["num_hidden_layers"] == 48
    assert GDN_CFG["published"]["num_experts"] == 512
    assert GDN_CFG["published"]["vocab_size"] == 151936
    assert GDN_CFG["num_routed_experts_published"] == 512
    # every width is the published one
    assert (GDN_CFG["hidden_size"], GDN_CFG["linear_num_key_heads"],
            GDN_CFG["linear_num_value_heads"], GDN_CFG["linear_key_head_dim"],
            GDN_CFG["linear_value_head_dim"],
            GDN_CFG["linear_conv_kernel_dim"], GDN_CFG["head_dim"],
            GDN_CFG["num_attention_heads"], GDN_CFG["num_key_value_heads"],
            GDN_CFG["partial_rotary_factor"],
            GDN_CFG["moe_intermediate_size"],
            GDN_CFG["shared_expert_intermediate_size"],
            GDN_CFG["num_experts_per_tok"], GDN_CFG["norm_topk_prob"],
            GDN_CFG["full_attention_interval"]) == \
        (2048, 16, 32, 128, 128, 4, 256, 16, 2, 0.25, 512, 512, 10, True, 4)
    assert GDN_CFG["layers_held"] == {
        "linear_attention": [0, 1, 2, 4, 5, 6], "full_attention": [3, 7]}
    assert GDN_CFG["cli"][:2] == ["--model", "qwen3-next"]
    assert {"gdn_state", "gdn_conv_state", "gdn_norms", "initialiser",
            "norm_scales", "gates", "rotary", "embedding",
            "mtp"} <= set(GDN_CFG["assumed"])
    assert "24 TPU v5e chips" in GDN_CFG["deployment"]
    if os.path.exists(GDN_CATALOG):
        row = next(json.loads(line) for line in open(GDN_CATALOG)
                   if "Qwen3-Next-80B-A3B-Instruct" in line)
        assert GDN_CFG["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in GDN_CFG["reduced"]:
                assert GDN_CFG[key] == value, key


def test_the_mix_is_issue_60s():
    assert GDN_MIX["driver"] == "serve_open_loop_qwen3_next"
    assert GDN_MIX["prompt"] == {"median": 5120, "sigma": 0.7, "min": 1024,
                                 "max": 30720}
    assert GDN_MIX["output"] == {"median": 96, "sigma": 0.7, "min": 16,
                                 "max": 384}
    assert GDN_MIX["prompt_plus_output_max"] == 32640
    assert (GDN_MIX["ramp_s"], GDN_MIX["trace_s"]) == (15, 5)
    assert GDN_MIX["check"] == {"prompt": 9000, "output": 32}
    assert GDN_MIX["check_carry"] == {"prompt": 8250, "output": 32}
    assert (GDN_SERVING["max_len"], GDN_SERVING["prefill_chunk"],
            GDN_SERVING["prefill_bucket"],
            GDN_SERVING["prefill_max_batch"]) == (32768, 4096, 4096, 1)
    assert GDN_SERVING["num_slots"] in (32, 24, 16)
    assert {"rate_rps", "num_slots"} <= set(GDN_MIX["assumed"])
    spec = json.load(open(os.path.join(os.path.dirname(GDN_BENCH),
                                       "BENCHMARK.json")))
    cell = next(w for w in spec["workloads"] if w["name"] == GDN_CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("qwen3-next-80b-a3b-8l", "longdoc-30k-chunk4k-open-loop", 1)
    mine = [m["name"] for m in spec["per_layer"]
            if m.get("workloads") == [GDN_CELL]]
    assert sorted(mine) == sorted([
        "serve_gdn_scan_ms_per_step", "gdn_chunk_roofline_pct",
        "serve_gdn_state_ms_per_step", "serve_gdn_conv_ms_per_step",
        "serve_gdn_state_bytes_per_slot", "serve_gdn_kv_attend_ms_per_step"])


def test_patterns_take_the_pools_arrays_and_nothing_else():
    found = gdn_kinds.patterns(GDN_CFG, GDN_SERVING)
    state, conv, kv = found["state"], found["conv"], found["kv"]
    for text in ("f32[6,32,32,128,128]{4,3,2,1,0}", "f32[32,32,128,128]",
                 "f32[1,32,128,128]", "f32[6,1,32,128,128]",
                 "f32[1,32,32,128,128]"):
        assert state.search(text), text
    for text in ("bf16[6,32,32,128,128]", "f32[6,32,16,128,128]",
                 "f32[32,32,128,64]", "f32[5,32,32,128,128]",
                 # Kimi's state a slot looks the same a layer; its stack does
                 # not where the depth differs, and its cell has no such cfg
                 "f32[7,32,32,128,128]"):
        assert not state.search(text), text
    for text in ("bf16[6,32,3,8192]", "bf16[32,3,8192]", "bf16[1,3,8192]",
                 "bf16[6,1,3,8192]"):
        assert conv.search(text), text
    for text in ("bf16[6,32,3,12288]", "bf16[6,32,4,8192]",
                 "bf16[1,4096,8192]"):
        assert not conv.search(text), text
    for text in ("bf16[2,32,32768,512]", "bf16[32,32768,512]",
                 "bf16[1,32768,512]", "bf16[2,1,32768,512]"):
        assert kv.search(text), text
    for text in ("bf16[2,32,576,32768]", "bf16[1,4096,512]",
                 "bf16[32,32768,1024]", "bf16[128,2048,1024]"):
        assert not kv.search(text), text
    assert gdn_kinds.patterns({"num_hidden_layers": 8}, GDN_SERVING) is None


def test_gdn_kinds_readers_on_a_hand_built_trace():
    flash = ('%_flash_attention_offset.3 = (bf16[1,16,4096,256]{3,2,1,0}, '
             'f32[1,16,4096,128]{3,2,1,0}) custom-call(s32[2]{0} %o, '
             'bf16[1,16,4096,256]{3,2,1,0} %q, bf16[1,32768,512]{2,1,0} %k, '
             'bf16[1,32768,512]{2,1,0} %v), '
             'custom_call_target="tpu_custom_call"')
    elsewhere = flash.replace("bf16[1,32768,512]{2,1,0}",
                              "bf16[1,2,32768,256]{3,2,1,0}")
    ops = [
        (gdn_call(n=1), 0.00, 0.004),                 # the scan's, not state
        ("%fusion.1 = f32[6,32,32,128,128]{4,3,2,1,0} fusion("
         "f32[6,32,32,128,128]{4,3,2,1,0} %p), kind=kLoop", 0.01, 0.002),
        ("%fusion.2 = bf16[6,32,3,8192]{3,2,1,0} fusion("
         "bf16[6,32,3,8192]{3,2,1,0} %c), kind=kLoop", 0.02, 0.001),
        ("%fusion.3 = f32[32,16,1,32768]{3,2,1,0} fusion("
         "bf16[2,32,32768,512]{3,2,1,0} %k), kind=kOutput", 0.03, 0.003),
        (flash, 0.04, 0.005),
        ("%fusion.4 = bf16[1,4096,2048]{2,1,0} fusion()", 0.05, 0.5)]
    spans = [("mtpu/serve/step", 0.0, 0.3), ("mtpu/serve/step", 0.4, 0.2)]
    ctx = types.SimpleNamespace(peaks=None, config=GDN_CFG, traffic=GDN_MIX)
    run = types.SimpleNamespace(
        ctx=ctx, samples={"gdn_state_bytes_per_slot": 12582912}, checks={},
        trace=Trace(kind="tpu", window_s=0.7, ops={0: ops}, spans=spans))
    read = lambda name: load_module("layer_metrics", name).read(run)  # noqa: E731
    assert read("serve_gdn_state_ms_per_step") == pytest.approx(1.0)
    assert read("serve_gdn_conv_ms_per_step") == pytest.approx(0.5)
    assert read("serve_gdn_kv_attend_ms_per_step") == pytest.approx(4.0)
    assert read("serve_gdn_state_bytes_per_slot") == 12582912
    assert gdn_kinds.is_flash_at_offset(flash)
    assert gdn_kinds.is_flash_at_offset(elsewhere)    # by its name alone
    # nothing, and no error: a parent's program, another configuration, a CPU
    for name in ("serve_gdn_state_ms_per_step", "serve_gdn_conv_ms_per_step",
                 "serve_gdn_kv_attend_ms_per_step"):
        run.trace = Trace(kind="tpu", window_s=0.7, ops={0: ops[-1:]},
                          spans=spans)
        assert read(name) is None
        run.trace = Trace(kind="cpu", window_s=0.7, ops={0: ops}, spans=spans)
        assert read(name) is None
    run.trace = Trace(kind="tpu", window_s=0.7, ops={0: ops}, spans=spans)
    run.ctx = types.SimpleNamespace(peaks=None, config={"num_hidden_layers": 8},
                                    traffic=GDN_MIX)
    assert read("serve_gdn_state_ms_per_step") is None
    run.samples = {}
    assert read("serve_gdn_state_bytes_per_slot") is None
