"""`ssm_kinds.py`'s patterns on hand-made event texts, and the readers of
the pool's selective-scan state, depthwise inputs and keys and values on a
hand-made trace and hand-made samples."""
import json
import os
import types

import pytest

from benchmark import ssm_kinds
from benchmark.by_name import load_module
from benchmark.trace import Trace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CFG = json.load(open(os.path.join(BENCH, "configs", "jamba2-3b-28l.json")))
SERVING = json.load(open(os.path.join(
    BENCH, "traffic", "longdoc-32k-chunked-open-loop.json")))["serving"]


def test_the_configuration_holds_the_sources_keys():
    assert CFG["reduced"] == {} and CFG["num_hidden_layers"] == 28
    assert (CFG["hidden_size"], CFG["mamba_expand"], CFG["mamba_d_state"],
            CFG["mamba_dt_rank"], CFG["mamba_d_conv"]) == (2560, 2, 16, 160, 4)
    assert (CFG["attn_layer_period"], CFG["attn_layer_offset"]) == (14, 7)
    assert CFG["cli"] == ["--model", "jamba2-3b"]
    assert {"head_dim", "layer_order", "initialiser", "ssm_state",
            "conv_state", "embedding"} <= set(CFG["assumed"])


def test_shapes_of_the_state():
    found = ssm_kinds.patterns(CFG, SERVING)
    state = ["f32[26,32,16,5120]{3,2,1,0}", "f32[32,16,5120]{2,1,0}",
             "f32[1,32,16,5120]{3,2,1,0}", "f32[26,1,16,5120]{3,2,1,0}",
             "f32[1,16,5120]{2,1,0}"]
    other = ["bf16[26,32,3,5120]{3,2,1,0}",      # the depthwise inputs
             "f32[16,5120]{1,0}",                # A, transposed
             "f32[26,16,5120]{2,1,0}",           # A_log stacked over layers
             "bf16[26,32,16,5120]{3,2,1,0}",     # not float32: not the state
             "f32[1,2048,5120]{2,1,0}", "f32[1,2048,16,128]{3,2,1,0}",
             "bf16[2,32,32768,128]{3,2,1,0}", "f32[32,65536]{1,0}"]
    for text in state:
        assert found["state"].search(text), text
    for text in other:
        assert not found["state"].search(text), text
    # a configuration with no Mamba layer: nothing to read
    assert ssm_kinds.patterns({"num_hidden_layers": 4}, SERVING) is None
    lfm2 = json.load(open(os.path.join(BENCH, "configs",
                                       "lfm2-8b-a1b-13l.json")))
    assert ssm_kinds.patterns(lfm2, SERVING) is None


def test_shapes_of_the_depthwise_inputs_and_of_keys_and_values():
    found = ssm_kinds.patterns(CFG, SERVING)
    kinds = {
        "conv": ["bf16[26,32,3,5120]{3,2,1,0}", "bf16[32,3,5120]{2,1,0}",
                 "bf16[1,32,3,5120]{3,2,1,0}", "bf16[26,1,3,5120]{3,2,1,0}",
                 "bf16[1,3,5120]{2,1,0}"],
        "kv": ["bf16[2,32,32768,128]{3,2,1,0}", "bf16[32,32768,128]{2,1,0}",
               "bf16[1,32,32768,128]{3,2,1,0}"]}
    other = ["f32[26,32,16,5120]{3,2,1,0}",      # the scan's state
             "bf16[26,4,5120]{2,1,0}",           # the taps
             "bf16[1,2048,5120]{2,1,0}",         # a chunk's rows
             "bf16[32,32768,20]{1,2,0}",         # a decode step's scores
             "pred[32,32768]{1,0}",
             "bf16[2,1,2048,128]{3,2,1,0}",      # a chunk's own keys
             "bf16[26,2560,2,8192]{3,2,1,0}", "f32[32,65536]{1,0}"]
    for kind, texts in kinds.items():
        for text in texts:
            assert found[kind].search(text), (kind, text)
            assert not any(found[k].search(text) for k in found if k != kind)
    for text in other:
        for kind in kinds:
            assert not found[kind].search(text), (kind, text)


@pytest.mark.parametrize("name,kind_ms", [
    ("serve_ssm_conv_state_ms_per_step", 0.003),
    ("serve_ssm_kv_attend_ms_per_step", 0.040)])
def test_pool_readers_on_a_hand_built_trace(name, kind_ms):
    ops = [("%fusion.1 = bf16[26,32,3,5120]{3,2,1,0} fusion(bf16[26,32,3,"
            "5120]{3,2,1,0} %conv, bf16[32,3,5120]{2,1,0} %new)", 0.0, 0.003),
           ("%fusion.2 = bf16[32,32768,20]{1,2,0} fusion(bf16[32,20,128]"
            "{2,0,1} %q, bf16[2,32,32768,128]{3,2,1,0} %k)", 0.01, 0.030),
           ("%fusion.3 = bf16[32,20,128]{2,0,1} fusion(bf16[32,32768,20]"
            "{1,2,0} %p, bf16[32,32768,128]{2,1,0} %v)", 0.05, 0.010),
           ("%fusion.4 = bf16[32,65536]{1,0} fusion(bf16[32,2560]{1,0} %x)",
            0.1, 0.5)]
    spans = [("mtpu/serve/step", 0.001, 0.3), ("mtpu/serve/step", 0.4, 0.2)]
    trace = Trace(kind="tpu", window_s=0.7, ops={0: ops}, spans=spans)
    ctx = types.SimpleNamespace(config=CFG, traffic={"serving": SERVING})
    run = types.SimpleNamespace(ctx=ctx, trace=trace, samples={})
    read = load_module("layer_metrics", name).read
    assert read(run) == pytest.approx(1e3 * kind_ms / 2)
    # a program with no such array (a parent commit), a CPU's trace, another
    # configuration's cell: nothing, never an error
    run.trace = Trace(kind="tpu", window_s=0.7, ops={0: ops[3:]}, spans=spans)
    assert read(run) is None
    run.trace = Trace(kind="host-xla", window_s=1.0, ops={0: ops},
                      spans=spans)
    assert read(run) is None
    run.trace, run.ctx.config = trace, json.load(open(os.path.join(
        BENCH, "configs", "lfm2-8b-a1b-13l.json")))
    assert read(run) is None


def test_readers_on_a_hand_built_trace():
    scan = ("%_ssm_selective_scan.3 = (bf16[1,2048,5120]{2,1,0}, "
            "f32[1,16,5120]{2,1,0}) custom-call(bf16[1,2048,5120]{2,1,0} %x, "
            "f32[1,16,5120]{2,1,0} %h0), "
            'custom_call_target="tpu_custom_call"')
    ops = [("%fusion.1 = f32[26,32,16,5120]{3,2,1,0} fusion(f32[26,32,16,"
            "5120]{3,2,1,0} %ssm, f32[32,16,5120]{2,1,0} %new)", 0.0, 0.004),
           ("%fusion.2 = f32[26,1,16,5120]{3,2,1,0} fusion(f32[26,1,16,5120]"
            "{3,2,1,0} %sub, f32[1,16,5120]{2,1,0} %h)", 0.01, 0.002),
           (scan, 0.02, 0.050),           # the kernel's own call: not here
           ("%fusion.4 = bf16[32,65536]{1,0} fusion(bf16[32,2560]{1,0} %x)",
            0.1, 0.5)]
    spans = [("mtpu/serve/step", 0.001, 0.3), ("mtpu/serve/step", 0.4, 0.2)]
    trace = Trace(kind="tpu", window_s=0.7, ops={0: ops}, spans=spans)
    ctx = types.SimpleNamespace(config=CFG, traffic={"serving": SERVING})
    run = types.SimpleNamespace(ctx=ctx, trace=trace, samples={})
    read = load_module("layer_metrics", "serve_ssm_state_ms_per_step").read
    assert read(run) == pytest.approx(1e3 * 0.006 / 2)
    run.trace = Trace(kind="host-xla", window_s=1.0, ops={0: ops},
                      spans=spans)
    assert read(run) is None
    # another configuration's cell: nothing, never an error
    run.trace, run.ctx.config = trace, json.load(open(os.path.join(
        BENCH, "configs", "falcon-7b-11l.json")))
    assert read(run) is None


def test_state_bytes_a_slot_is_the_drivers_copy():
    read = load_module("layer_metrics", "serve_ssm_state_bytes_per_slot").read
    run = types.SimpleNamespace(samples={"ssm_state_bytes_per_slot": 8519680})
    assert read(run) == 8519680
    assert read(types.SimpleNamespace(samples={})) is None
    assert read(types.SimpleNamespace(
        samples={"ssm_state_bytes_per_slot": None})) is None
