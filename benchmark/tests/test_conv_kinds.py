"""`conv_kinds.py`'s patterns on hand-made event texts, and the four readers
PR 37 brought on a hand-made trace and hand-made samples."""
import json
import os
import types

import pytest

from benchmark import conv_kinds
from benchmark.by_name import load_module
from benchmark.trace import Trace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CFG = json.load(open(os.path.join(BENCH, "configs", "lfm2-8b-a1b-13l.json")))
SERVING = json.load(open(os.path.join(
    BENCH, "traffic", "chat-2k-open-loop.json")))["serving"]


def test_shapes_of_the_three_kinds():
    found = conv_kinds.patterns(CFG, SERVING)
    mix = ["bf16[128,1,6144]{2,1,0}", "bf16[2,1536,6144]{2,1,0}",
           "bf16[2048,6144]{1,0}", "bf16[9,2048,6144]{2,1,0}",
           "bf16[3072,6144]{1,0}"]
    state = ["bf16[10,128,2,2048]{3,1,2,0}", "bf16[128,2,2048]{2,1,0}",
             "bf16[1,128,2,2048]{3,2,1,0}", "bf16[10,1,2,2048]{3,2,1,0}",
             "bf16[10,2,2,2048]{3,2,1,0}"]
    kv = ["bf16[3,128,2048,512]{3,2,1,0}", "bf16[128,2048,512]{2,1,0}",
          "bf16[1,128,2048,512]{3,2,1,0}"]
    other = ["bf16[6144,2048]{1,0}",         # 6,144 expert ROWS of a prefill
             "s32[6144]{0}",                 # its router's sort
             "bf16[128,3,2048]{2,1,0}",      # [state ; a] of a decode step
             "bf16[32,2048,3584]{2,1,0}", "bf16[128,65536]{1,0}",
             "bf16[3,1,2048,512]{3,2,1,0}",  # a prefill's own keys
             "bf16[128,2048]{1,0}"]
    for kind, texts in (("mix", mix), ("state", state), ("kv", kv)):
        for text in texts:
            hits = {k for k, pat in found.items() if pat.search(text)}
            assert hits == {kind}, (text, hits)
    for text in other:
        assert not any(pat.search(text) for pat in found.values()), text
    # a configuration with no convolution layer: nothing to read
    assert conv_kinds.patterns({"num_hidden_layers": 4}, SERVING) is None
    assert conv_kinds.patterns(
        {"num_hidden_layers": 2, "layer_types": ["full_attention"] * 2},
        SERVING) is None


def test_the_cut_counts_its_own_layers():
    """`layer_types` is the source's, 24 entries; the 13 layers kept are
    named beside it and are what the shapes are made from."""
    assert len(CFG["layer_types"]) == 24 and CFG["num_hidden_layers"] == 13
    kept = CFG["layers_kept"]["their_layer_types"]
    assert [CFG["layer_types"][i]
            for i in CFG["layers_kept"]["published_layers"]] == kept
    assert kept.count("conv") == 10 and kept.count("full_attention") == 3
    assert CFG["cli"][CFG["cli"].index("--layer_types") + 1] == ",".join(kept)


def test_readers_on_a_hand_built_trace():
    ops = [("%fusion.1 = bf16[128,1,6144]{2,1,0} fusion(bf16[128,1,2048]"
            "{2,1,0} %x, bf16[9,2048,6144]{2,1,0} %w)", 0.0, 0.010),
           ("%fusion.2 = bf16[10,128,2,2048]{3,1,2,0} fusion(bf16[10,128,2,"
            "2048]{3,1,2,0} %conv, bf16[128,2,2048]{2,1,0} %new)", 0.011,
            0.002),
           ("%fusion.3 = f32[128,32,1,2048]{3,2,1,0} fusion(bf16[3,128,2048,"
            "512]{3,2,1,0} %pool_k)", 0.014, 0.030),
           ("%fusion.4 = bf16[128,2048]{1,0} fusion(bf16[128,2048]{1,0} %x)",
            0.045, 0.500)]
    spans = [("mtpu/serve/step", 0.001, 0.3), ("mtpu/serve/step", 0.4, 0.2)]
    trace = Trace(kind="tpu", window_s=0.7, ops={0: ops}, spans=spans)
    ctx = types.SimpleNamespace(config=CFG, traffic={"serving": SERVING})
    run = types.SimpleNamespace(ctx=ctx, trace=trace, samples={})
    want = {"serve_conv_mix_ms_per_step": 0.010,
            "serve_conv_state_ms_per_step": 0.002,
            "serve_kv_attend_ms_per_step": 0.030}
    for name, seconds in want.items():
        assert load_module("layer_metrics", name).read(run) == \
            pytest.approx(1e3 * seconds / 2), name
    run.trace = Trace(kind="host-xla", window_s=1.0, ops={0: ops},
                      spans=spans)
    for name in want:
        assert load_module("layer_metrics", name).read(run) is None
    # another configuration's cell: nothing, never an error
    falcon = json.load(open(os.path.join(BENCH, "configs",
                                         "falcon-7b-11l.json")))
    run.trace, run.ctx.config = trace, falcon
    for name in want:
        assert load_module("layer_metrics", name).read(run) is None


def test_state_bytes_a_slot_is_the_drivers_copy():
    read = load_module("layer_metrics", "serve_state_bytes_per_slot").read
    run = types.SimpleNamespace(samples={"state_bytes_per_slot": 81920})
    assert read(run) == 81920
    assert read(types.SimpleNamespace(samples={})) is None
    assert read(types.SimpleNamespace(
        samples={"state_bytes_per_slot": None})) is None
