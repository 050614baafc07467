"""`kda_kinds.py`'s patterns on hand-made event texts, and the readers of
the pool's delta-rule state and depthwise inputs on a hand-made trace and
hand-made samples; the new configuration and mix as files."""
import json
import os
import types

import pytest

from benchmark import kda_kinds
from benchmark.by_name import load_module
from benchmark.trace import Trace

KDA_HERE = os.path.dirname(os.path.abspath(__file__))
KDA_BENCH = os.path.dirname(KDA_HERE)
KDA_CFG = json.load(open(os.path.join(
    KDA_BENCH, "configs", "kimi-linear-48b-a3b-8l.json")))
KDA_MIX = json.load(open(os.path.join(
    KDA_BENCH, "traffic", "longdoc-32k-chunk4k-open-loop.json")))
KDA_SERVING = KDA_MIX["serving"]
KDA_CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_kimi_linear_configuration_holds_the_sources_keys():
    assert set(KDA_CFG["reduced"]) == {"num_hidden_layers", "num_experts",
                                       "vocab_size"}
    assert (KDA_CFG["num_hidden_layers"], KDA_CFG["num_experts"],
            KDA_CFG["vocab_size"]) == (8, 64, 40960)
    assert KDA_CFG["published"]["num_hidden_layers"] == 27
    assert KDA_CFG["published"]["num_experts"] == 256
    assert KDA_CFG["published"]["vocab_size"] == 163840
    assert KDA_CFG["num_routed_experts_published"] == 256
    # every width is the published one
    group = KDA_CFG["linear_attn_config"]
    assert (KDA_CFG["hidden_size"], group["num_heads"], group["head_dim"],
            group["short_conv_kernel_size"], KDA_CFG["kv_lora_rank"],
            KDA_CFG["qk_nope_head_dim"], KDA_CFG["qk_rope_head_dim"],
            KDA_CFG["v_head_dim"], KDA_CFG["num_attention_heads"],
            KDA_CFG["moe_intermediate_size"], KDA_CFG["intermediate_size"],
            KDA_CFG["num_experts_per_token"], KDA_CFG["num_experts_per_tok"],
            KDA_CFG["routed_scaling_factor"], KDA_CFG["q_lora_rank"],
            KDA_CFG["mla_use_nope"]) == \
        (2304, 32, 128, 4, 512, 128, 64, 128, 32, 1024, 9216, 8, 8, 2.446,
         None, True)
    assert KDA_CFG["linear_attn_layers_held"] == {
        "kda_layers": [1, 2, 3, 5, 6, 7], "full_attn_layers": [4, 8]}
    assert KDA_CFG["cli"][:2] == ["--model", "kimi-linear"]
    assert {"kda_gate_rank", "kda_output_gate_bias", "kda_norms", "kda_state",
            "kda_conv_state", "choosing_bias", "initialiser",
            "embedding"} <= set(KDA_CFG["assumed"])
    assert "16 TPU v5e chips" in KDA_CFG["deployment"]
    if os.path.exists(KDA_CATALOG):
        row = next(json.loads(line) for line in open(KDA_CATALOG)
                   if "Kimi-Linear-48B-A3B-Instruct" in line)
        assert KDA_CFG["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in KDA_CFG["reduced"]:
                assert KDA_CFG[key] == value, key


def test_the_kimi_linear_mix_holds_the_issues_parameters():
    # ISSUE 58's letter but for the rate (0.6 of the knee, under its third
    # way out) and its second way out (prompts to 16,384:
    # `assumed.prompt_max` gives both sweeps)
    assert KDA_MIX["prompt"] == {"median": 6144, "sigma": 0.7, "min": 1024,
                                 "max": 16384}
    assert KDA_MIX["output"] == {"median": 96, "sigma": 0.7, "min": 16,
                                 "max": 384}
    assert (KDA_SERVING["num_slots"], KDA_SERVING["max_len"],
            KDA_SERVING["prefill_chunk"], KDA_SERVING["prefill_bucket"],
            KDA_SERVING["prefill_max_batch"]) == (32, 32768, 4096, 4096, 1)
    assert (KDA_MIX["ramp_s"], KDA_MIX["trace_s"], KDA_MIX["temperature"]) \
        == (15, 5, 1.0)
    # two whole chunks and 808 rows; 58 rows behind a chunk's start
    assert KDA_MIX["check"] == {"prompt": 9000, "output": 32}
    assert KDA_MIX["check_carry"] == {"prompt": 8250, "output": 32}
    assert 9000 - 2 * 4096 == 808 and 8250 - 2 * 4096 == 58
    assert KDA_MIX["driver"] == "serve_open_loop_kimi_linear"
    # 0.6 of the knee 1.0: one step under ISSUE 58's third and last way out,
    # at which six seeds still spread 8.4 % (`assumed.steadiness`)
    assert KDA_MIX["rate_rps"] == 0.6
    assert {"rate_rps", "prompt_max", "steadiness"} <= set(KDA_MIX["assumed"])


def test_shapes_of_the_kda_state_and_its_depthwise_inputs():
    found = kda_kinds.patterns(KDA_CFG, KDA_SERVING)
    kinds = {
        "state": ["f32[6,32,32,128,128]{4,3,2,1,0}",
                  "f32[32,32,128,128]{3,2,1,0}",
                  "f32[6,1,32,128,128]{4,3,2,1,0}",
                  "f32[1,32,128,128]{3,2,1,0}",
                  "f32[1,32,32,128,128]{4,3,2,1,0}"],
        "conv": ["bf16[6,32,3,12288]{3,2,1,0}", "bf16[32,3,12288]{2,1,0}",
                 "bf16[6,1,3,12288]{3,2,1,0}", "bf16[1,3,12288]{2,1,0}"],
        "latent": ["bf16[2,32,576,32768]{3,2,1,0}",   # the pool's rows
                   "bf16[2,1,576,32768]{3,2,1,0}",    # a chunk's own cache
                   "bf16[32,576,32768]{2,1,0}", "bf16[1,576,32768]{2,1,0}",
                   "bf16[1,32,576,32768]{3,2,1,0}",
                   "bf16[32768,256,32]{2,1,0}",       # a chunk's weights
                   "bf16[32,32768,32]{2,1,0}"]}       # a decode step's
    other = ["bf16[6,32,32,128,128]{4,3,2,1,0}",   # not float32: no state
             "f32[32,128,128]{2,1,0}",             # a sequence's, no batch
             "bf16[7,64,2304,2048]{3,2,1,0}",      # the experts' banks
             "bf16[448,2304,2048]{2,1,0}", "bf16[64,1024,2304]{2,1,0}",
             "f32[32,256]{1,0}", "f32[4096,256]{1,0}",   # the router's rows
             "bf16[5,32,576,16384]{3,2,1,0}",      # JoyAI's pool
             "bf16[8,32,576,32768]{3,2,1,0}",      # a row a layer: not held
             "bf16[576,32768]{1,0}", "bf16[4096,32,576]{2,1,0}",
             "f32[32,256,32768]{2,1,0}",
             "bf16[1,4096,12288]{2,1,0}", "bf16[5,4,12288]{2,1,0}",
             "f32[1,4096,4096]{2,1,0}", "f32[32,40960]{1,0}"]
    for kind, texts in kinds.items():
        for text in texts:
            assert found[kind].search(text), (kind, text)
            for off in set(kinds) - {kind}:
                assert not found[off].search(text), (off, text)
    for text in other:
        for kind in kinds:
            assert not found[kind].search(text), (kind, text)
    # a configuration with no KDA layer: nothing to read
    assert kda_kinds.patterns({"num_hidden_layers": 4}, KDA_SERVING) is None
    for name in ("joyai-llm-flash-5l.json",
                 "nemotron-3-super-120b-a12b-11l.json"):
        cfg = json.load(open(os.path.join(KDA_BENCH, "configs", name)))
        assert kda_kinds.patterns(cfg, KDA_SERVING) is None


def test_kda_kinds_readers_on_a_hand_built_trace():
    scan = ("%_kda_chunk.2 = (bf16[1,4096,4096]{2,1,0}, "
            "f32[1,32,128,128]{3,2,1,0}) custom-call(bf16[1,4096,4096]{2,1,0}"
            ' %q, f32[1,32,128,128]{3,2,1,0} %h0), custom_call_target='
            '"tpu_custom_call"')
    ops = [("%fusion.675 = f32[6,32,32,128,128]{4,3,2,1,0} fusion("
            "f32[6,32,32,128,128]{4,3,2,1,0} %pool, bf16[32,32,128]{2,1,0} "
            "%k)", 0.0, 0.004),
           (scan, 0.01, 0.002),               # the kernel's own: not state
           ("%fusion.4 = bf16[32,40960]{1,0} fusion(bf16[32,2304]{1,0} %x)",
            0.04, 0.5),
           ("%fusion.12 = bf16[6,32,3,12288]{3,2,1,0} fusion("
            "bf16[6,32,3,12288]{3,2,1,0} %pool, bf16[32,12288]{1,0} %row)",
            0.61, 0.0007),
           ("%fusion.1232 = (f32[32,256]{1,0}, bf16[32768,256,32]{2,1,0}) "
            "fusion(bf16[256,32,576]{2,1,0} %q, bf16[2,1,576,32768]{3,2,1,0} "
            "%cache)", 0.62, 0.011),
           ("%bitcast_reduce_fusion = (f32[32,32]{1,0}, "
            "bf16[32,32768,32]{2,1,0}) fusion(bf16[32,32,576]{2,1,0} %q, "
            "bf16[2,32,576,32768]{3,2,1,0} %pool)", 0.64, 0.005)]
    spans = [("mtpu/serve/step", 0.0, 0.3), ("mtpu/serve/step", 0.4, 0.2)]
    ctx = types.SimpleNamespace(peaks=None, config=KDA_CFG, traffic=KDA_MIX)
    run = types.SimpleNamespace(
        ctx=ctx, samples={"kda_state_bytes_per_slot": 12582912}, checks={},
        trace=Trace(kind="tpu", window_s=0.7, ops={0: ops}, spans=spans))
    read = lambda name: load_module("layer_metrics", name).read(run)  # noqa: E731
    assert read("serve_kda_state_ms_per_step") == pytest.approx(2.0)
    assert read("serve_kda_conv_ms_per_step") == pytest.approx(0.35)
    assert read("serve_kda_latent_attend_ms_per_step") == pytest.approx(8.0)
    assert read("serve_kda_state_bytes_per_slot") == 12582912
    # the parent commit's program holds none of it: nothing, and no error
    run.trace = Trace(kind="tpu", window_s=0.7, ops={0: ops[2:3]},
                      spans=spans)
    run.samples = {}
    for name in ("serve_kda_state_ms_per_step", "serve_kda_conv_ms_per_step",
                 "serve_kda_latent_attend_ms_per_step",
                 "serve_kda_state_bytes_per_slot"):
        assert read(name) is None
