"""`ssd_kinds.py`'s patterns on hand-made event texts, and the readers of
the pool's Mamba-2 state and of the latent's projections on a hand-made
trace and hand-made samples; the new configuration and mix as files."""
import json
import os
import types

import pytest

from benchmark import ssd_kinds
from benchmark.by_name import load_module
from benchmark.trace import Trace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CFG = json.load(open(os.path.join(
    BENCH, "configs", "nemotron-3-super-120b-a12b-11l.json")))
MIX = json.load(open(os.path.join(
    BENCH, "traffic", "agent-8k-chunked-open-loop.json")))
SERVING = MIX["serving"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_nemotron_configuration_holds_the_sources_keys():
    assert set(CFG["reduced"]) == {"num_hidden_layers", "n_routed_experts",
                                   "vocab_size"}
    assert (CFG["num_hidden_layers"], CFG["n_routed_experts"],
            CFG["num_experts"], CFG["vocab_size"]) == (11, 128, 128, 32768)
    assert CFG["published"] == {
        "num_hidden_layers": 88, "n_routed_experts": 512, "num_experts": 512,
        "vocab_size": 131072, "params": "120B-A12B"}
    assert CFG["hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    assert len(CFG["hybrid_override_pattern"]) == 88
    # every width is the published one
    assert (CFG["hidden_size"], CFG["mamba_num_heads"], CFG["mamba_head_dim"],
            CFG["n_groups"], CFG["ssm_state_size"], CFG["conv_kernel"],
            CFG["chunk_size"], CFG["moe_latent_size"],
            CFG["moe_intermediate_size"],
            CFG["moe_shared_expert_intermediate_size"],
            CFG["num_experts_per_tok"], CFG["num_attention_heads"],
            CFG["num_key_value_heads"], CFG["head_dim"]) == \
        (4096, 128, 64, 8, 128, 4, 128, 1024, 2688, 5376, 22, 32, 2, 128)
    assert CFG["cli"][:2] == ["--model", "nemotron-3-super"]
    assert {"positions", "ssm_state", "conv_state", "initialiser",
            "choosing_bias", "embedding"} <= set(CFG["assumed"])
    assert "32 chips" in CFG["deployment"] and "4 chips" in CFG["deployment"]
    if os.path.exists(CATALOG):
        row = next(json.loads(line) for line in open(CATALOG)
                   if "Nemotron-3-Super-120B" in line)
        assert CFG["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in CFG["reduced"]:
                assert CFG[key] == value, key


def test_the_mix_holds_the_issues_parameters():
    assert MIX["prompt"] == {"median": 1024, "sigma": 0.9, "min": 128,
                             "max": 7680}
    assert MIX["output"] == {"median": 192, "sigma": 0.7, "min": 16,
                             "max": 768}
    assert MIX["prompt_plus_output_max"] == 8064
    assert (SERVING["num_slots"], SERVING["max_len"],
            SERVING["prefill_chunk"], SERVING["prefill_bucket"],
            SERVING["prefill_max_batch"]) == (64, 8192, 2048, 512, 1)
    assert MIX["check"] == {"prompt": 5000, "output": 32}
    # the second checked request ends 54 rows behind a chunk's start
    assert MIX["check_carry"] == {"prompt": 4150, "output": 32}
    assert MIX["driver"] == "serve_open_loop_nemotron"
    assert isinstance(MIX["rate_rps"], float)


def test_shapes_of_the_state_and_the_rest_of_the_pool():
    found = ssd_kinds.patterns(CFG, SERVING)
    kinds = {
        "state": ["f32[5,64,128,64,128]{4,3,2,1,0}",
                  "f32[64,128,64,128]{3,2,1,0}",
                  "f32[5,1,128,64,128]{4,3,2,1,0}",
                  "f32[1,128,64,128]{3,2,1,0}",
                  "f32[1,64,128,64,128]{4,3,2,1,0}"],
        "conv": ["bf16[5,64,3,10240]{3,2,1,0}", "bf16[64,3,10240]{2,1,0}",
                 "bf16[5,1,3,10240]{3,2,1,0}", "bf16[1,3,10240]{2,1,0}"],
        "kv": ["bf16[1,64,8192,256]{3,2,1,0}", "bf16[64,8192,256]{2,1,0}"],
        "latent": ["bf16[5,4096,1024]{2,1,0}", "bf16[5,1024,4096]{2,1,0}"]}
    other = ["bf16[5,64,128,64,128]{4,3,2,1,0}",   # not float32: no state
             "f32[1,16,128,128]{3,2,1,0}",         # the chunks' decays
             "f32[128,64,128]{2,1,0}", "bf16[1,2048,8192]{2,1,0}",
             "bf16[1,2048,10240]{2,1,0}", "bf16[5,4,10240]{2,1,0}",
             "bf16[1,1024,4096]{2,1,0}",           # a bucket's rows
             "bf16[1024,4096]{1,0}",               # ... flattened
             "bf16[5,4096,18560]{2,1,0}", "bf16[5,128,1024,2688]{3,2,1,0}",
             "bf16[640,1024,2688]{2,1,0}", "f32[64,32768]{1,0}"]
    for kind, texts in kinds.items():
        for text in texts:
            assert found[kind].search(text), (kind, text)
            for off in set(kinds) - {kind}:
                assert not found[off].search(text), (off, text)
    for text in other:
        for kind in kinds:
            assert not found[kind].search(text), (kind, text)
    # a configuration with no Mamba-2 layer: nothing to read
    assert ssd_kinds.patterns({"num_hidden_layers": 4}, SERVING) is None
    jamba = json.load(open(os.path.join(BENCH, "configs",
                                        "jamba2-3b-28l.json")))
    assert ssd_kinds.patterns(jamba, SERVING) is None


def _run(trace, samples=None):
    ctx = types.SimpleNamespace(peaks=None, config=CFG, traffic=MIX)
    return types.SimpleNamespace(ctx=ctx, trace=trace, samples=samples or {},
                                 checks={})


def test_ssd_kinds_readers_on_a_hand_built_trace():
    scan = ("%_ssd_chunk_scan.2 = (bf16[1,2048,8192]{2,1,0}, "
            "f32[1,128,64,128]{3,2,1,0}) custom-call(bf16[1,2048,8192]{2,1,0}"
            ' %x, f32[1,128,64,128]{3,2,1,0} %h0), custom_call_target='
            '"tpu_custom_call"')
    ops = [("%fusion.675 = f32[5,64,128,64,128]{4,3,2,1,0} fusion("
            "f32[5,64,128,64,128]{4,3,2,1,0} %pool, bf16[64,128,64]{2,1,0} "
            "%x)", 0.0, 0.004),
           (scan, 0.01, 0.002),               # the kernel's own: not state
           ("%fusion.9 = bf16[64,1024]{1,0} fusion(bf16[64,4096]{1,0} %u, "
            "bf16[5,4096,1024]{2,1,0} %w)", 0.02, 0.0005),
           ("%fusion.10 = bf16[64,4096]{1,0} fusion(bf16[64,1024]{1,0} %y, "
            "bf16[5,1024,4096]{2,1,0} %w)", 0.03, 0.0015),
           ("%fusion.4 = bf16[64,32768]{1,0} fusion(bf16[64,4096]{1,0} %x)",
            0.04, 0.5),
           ("%fusion.11 = bf16[64,32,128]{2,1,0} fusion(bf16[64,32,128]"
            "{2,1,0} %q, bf16[1,64,8192,256]{3,2,1,0} %k)", 0.6, 0.003),
           ("%fusion.12 = bf16[5,64,3,10240]{3,2,1,0} fusion("
            "bf16[5,64,3,10240]{3,2,1,0} %pool, bf16[64,10240]{1,0} %row)",
            0.61, 0.0007)]
    spans = [("mtpu/serve/step", 0.0, 0.3), ("mtpu/serve/step", 0.4, 0.2)]
    run = _run(Trace(kind="tpu", window_s=0.7, ops={0: ops}, spans=spans),
               {"ssd_state_bytes_per_slot": 20971520})
    read = lambda name: load_module("layer_metrics", name).read(run)  # noqa: E731
    assert read("serve_ssd_state_ms_per_step") == pytest.approx(2.0)
    assert read("serve_moe_latent_ms_per_step") == pytest.approx(1.0)
    assert read("serve_ssd_state_bytes_per_slot") == 20971520
    assert read("serve_ssd_kv_attend_ms_per_step") == pytest.approx(1.5)
    assert read("serve_ssd_conv_state_ms_per_step") == pytest.approx(0.35)
