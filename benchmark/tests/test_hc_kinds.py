"""`hc_kinds.py`'s patterns on hand-made event texts (the shapes are the ones
the chip's compiler gave the cell's decode, prefill and chunk programs, PR
41), and the three readers PR 41 brought on a hand-made trace and hand-made
samples."""
import json
import os
import types

import pytest

from benchmark import hc_kinds
from benchmark.by_name import load_module
from benchmark.trace import Trace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CFG = json.load(open(os.path.join(BENCH, "configs",
                                  "xing4.0-29b-a4b-6l.json")))
SERVING = json.load(open(os.path.join(
    BENCH, "traffic", "mixed-16k-chunked-open-loop.json")))["serving"]


def test_hc_shapes_of_the_two_kinds():
    found = hc_kinds.patterns(CFG, SERVING)
    maps = ["bf16[14336,24]{0,1:T(8,128)(2,1)}", "bf16[5,14336,24]{2,1,0}",
            "bf16[1,14336,24]{1,2,0}",
            # a decode step's planes: 24 slots
            "f32[24,24,1]{1,0,2:T(8,128)}", "f32[4,24]{1,0:T(4,128)S(1)}",
            "f32[4,4,24,1]{2,3,1,0:T(1,128)}", "f32[16,24,1]{1,0,2}",
            "f32[4,24,1]{1,0,2:T(4,128)}", "f32[1,4,24,1]{3,2,1,0}",
            "f32[4,1,24,1]{3,2,1,0}",
            # a chunk's: 4,096 rows, and a tail bucket's
            "f32[4096,24]{0,1:T(8,128)}", "f32[4,4096]{1,0:T(4,128)}",
            "f32[4,4,1,4096]{3,1,0,2:T(4,128)}", "f32[16,1,2048]{2,1,0}",
            "f32[24,1,2048]{2,1,0}", "f32[1,4,1,4096]{3,2,1,0}"]
    mixes = ["bf16[1,4096,14336]{1,2,0:T(8,128)(2,1)}",
             "bf16[4096,14336]{0,1:T(8,128)(2,1)}", "bf16[24,14336]{1,0}",
             "f32[1,4096,14336]{1,2,0:T(8,128)}", "bf16[24,1,14336]{2,1,0}",
             "bf16[1,2048,4,3584]{3,2,1,0}"]
    other = ["bf16[24,1,3584]{2,1,0}",        # a stream's width
             "bf16[1,4096,3584]{2,1,0}", "f32[4096,3584]{1,0}",
             "bf16[24,4096]{1,0}",            # 24 slots x 32 heads of 128
             "bf16[24,1,4096]{2,1,0}",
             "bf16[24,1,1024]{2,1,0}",        # a decode step's shared expert
             "s32[6,24]{1,0}",                # the pool's offsets
             "f32[24,131072]{1,0}", "s32[4096,4]{1,0}",   # top 4 of 64
             "f32[24]{0}", "f32[4096]{0:T(1024)}",
             "bf16[6,24,576,16384]{3,2,1,0}", "bf16[3584,9216]{1,0}",
             "bf16[64,3584,2048]{2,1,0}", "f32[24,64]{1,0}"]
    for kind, texts in (("map", maps), ("mix", mixes), (None, other)):
        for text in texts:
            assert hc_kinds.kind_of(found, text) == kind, (text, kind)
    # the rows are the mix's own padded lengths: the cell's bucket of 1,024
    # makes planes of 1,024 and 3,072 rows, which are maps here and nothing
    # under a bucket of 2,048
    coarse = hc_kinds.patterns(CFG, dict(SERVING, prefill_bucket=2048))
    for text in ("f32[24,1,1024]{2,1,0}", "f32[1,4,1,3072]{3,2,1,0}"):
        assert hc_kinds.kind_of(found, text) == "map", text
        assert hc_kinds.kind_of(coarse, text) is None, text
    # an operation that holds both is the map's: the product reads the streams
    assert hc_kinds.kind_of(
        found, "%f = f32[4096,24]{0,1} fusion(bf16[1,4096,14336]{1,2,0} %x, "
        "bf16[14336,24]{0,1} %phi)") == "map"
    # a configuration with one stream: nothing to read
    assert hc_kinds.patterns({"hidden_size": 2048}, SERVING) is None
    assert hc_kinds.patterns({"hidden_size": 2048, "hc_mult": 1},
                             SERVING) is None


def test_hc_readers_on_a_hand_built_trace():
    ops = [("%fusion.1 = f32[4096,24]{0,1} fusion(bf16[1,4096,14336]{1,2,0} "
            "%x, bf16[14336,24]{0,1} %phi)", 0.0, 0.010),
           ("%divide_reduce_fusion.2 = f32[4,4096]{1,0} fusion(f32[4,4096]"
            "{1,0} %m, f32[4,4096]{1,0} %sum)", 0.011, 0.002),
           ("%fusion.3 = bf16[4096,14336]{0,1} fusion(bf16[4096,14336]{0,1} "
            "%x, bf16[4096,3584]{1,0} %out, f32[4,4,1,4096]{3,1,0,2} %res)",
            0.014, 0.030),
           ("%fusion.4 = bf16[1,4096,3584]{2,1,0} fusion(bf16[1,4096,14336]"
            "{1,2,0} %x)", 0.045, 0.020),
           ("%fusion.5 = bf16[4096,3584]{1,0} fusion(bf16[4096,3584]{1,0} %x)",
            0.070, 0.500)]
    spans = [("mtpu/serve/step", 0.001, 0.3), ("mtpu/serve/step", 0.4, 0.2)]
    trace = Trace(kind="tpu", window_s=0.7, ops={0: ops}, spans=spans)
    ctx = types.SimpleNamespace(config=CFG, traffic={"serving": SERVING})
    run = types.SimpleNamespace(ctx=ctx, trace=trace, samples={})
    # the post mix holds H_res's plane beside the streams: the map's pattern
    # comes first, so an operation that fuses a mix WITH a round is the map's
    want = {"serve_hc_map_ms_per_step": 0.010 + 0.002 + 0.030,
            "serve_hc_mix_ms_per_step": 0.020}
    for name, seconds in want.items():
        assert load_module("layer_metrics", name).read(run) == \
            pytest.approx(1e3 * seconds / 2), name
    run.trace = Trace(kind="host-xla", window_s=1.0, ops={0: ops},
                      spans=spans)
    for name in want:
        assert load_module("layer_metrics", name).read(run) is None
    # another configuration's cell: nothing, never an error
    joyai = json.load(open(os.path.join(BENCH, "configs",
                                        "joyai-llm-flash-5l.json")))
    run.trace, run.ctx.config = trace, joyai
    for name in want:
        assert load_module("layer_metrics", name).read(run) is None


def test_chunks_a_prompt_is_the_engines_own_count():
    read = load_module("layer_metrics", "serve_prefill_chunks_per_prompt").read
    run = types.SimpleNamespace(samples={"window_engine_counters": {
        "prefill_chunks": 30, "prefill_prompts": 40,
        "requests_admitted": 50}})
    assert read(run) == pytest.approx(1.4)
    assert read(types.SimpleNamespace(samples={})) is None
    assert read(types.SimpleNamespace(samples={"window_engine_counters": {
        "prefill_chunks": 0, "prefill_prompts": 0,
        "requests_admitted": 0}})) is None


@pytest.mark.parametrize("moved, positions, match", [
    ({}, 32, True),                                   # a sound run
    ({i: 0.06 for i in range(9)}, 32, True),          # nine flips: at the limit
    ({i: 0.06 for i in range(10)}, 32, False),        # the count decides
    ({3: 0.81}, 32, False),                           # one position too far
    ({i: 0.045 for i in range(32)} | {0: 0.79, 1: 0.79}, 32, False),  # the mean
    ({}, 33, False)])                                 # a request cut short
def test_the_check_s_rule_on_made_up_log_probabilities(moved, positions,
                                                       match):
    """`serve_open_loop_xing.verdict`: what the cell's `correct` and
    `hc_fault_at_width.py`'s control both go through."""
    import numpy as np
    driver = load_module("drivers", "serve_open_loop_xing")
    ref = np.linspace(-4.0, -0.5, 32)
    got = ref.copy()
    for i, by in moved.items():
        got[i] += by
    v = driver.verdict(got, ref, positions)
    assert v["logprobs_match_reference"] is match, v
    assert v["logprob_positions"] == 32
    assert v["logprob_positions_over_0_05"] == sum(
        by > driver.OVER for by in moved.values())
    assert (v["logprob_tolerance_positions_over_0_05"],
            v["logprob_tolerance_max"], v["logprob_tolerance_mean"]) == (
                driver.TOL_POSITIONS_OVER, driver.TOL_LOGPROB_MAX,
                driver.TOL_LOGPROB_MEAN)
