"""`benchmark/layer_metrics/serve_weight_copy_ms_per_step.py` on hand-made
traces: the self time of every operation that WRITES an array of a whole
weight's shape (a stack, a layer of it, the embedding table), per
`mtpu/serve/step` span. A product that reads the float32 stack where it lies
names the stack among its operands and is not one of them."""
import json
import os
import types

import pytest

from benchmark.by_name import load_module
from benchmark.trace import Trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
L, H, F, V, SLOTS = 11, 4544, 18176, 65024, 64
T = "{2,1,0:T(8,128)}"
OTHER = (f"%fusion.1 = bf16[{SLOTS},{H}]{{1,0}} fusion(bf16[{SLOTS},{H}]"
         "{1,0} %p), kind=kLoop")
# the parent's decode step: a whole stack cast, lifted out of the layer loop
STACK_CAST = (f"%convert.45 = bf16[{L},{F},{H}]{{2,1,0:T(8,128)(2,1)}} "
              f"convert(f32[{L},{F},{H}]{T} %params__transformer__mlp__w2)")
# ... and the product that reads a layer of the copy
PRODUCT_OF_COPY = (f"%fusion.310 = bf16[{SLOTS},{H}]{{1,0}} fusion(bf16["
                   f"{SLOTS},{F}]{{1,0}} %h, bf16[{L},{F},{H}]{T} "
                   "%convert.45, s32[] %i), kind=kOutput, calls=%fused_dot")
# the change's: the product reads the float32 stack in place
PRODUCT_IN_PLACE = (f"%fusion.302 = bf16[{SLOTS},{H}]{{1,0}} fusion(bf16["
                    f"{SLOTS},{F}]{{1,0}} %h, f32[{L},{F},{H}]{T} %w2, "
                    "s32[] %i), kind=kOutput, calls=%fused_dot")
TABLE_COPY = (f"%copy.69 = bf16[{V},{H}]{{1,0:T(8,128)(2,1)}} copy(f32[{V},"
              f"{H}]{{1,0:T(8,128)}} %params__embedding__word_embeddings)")
LAYER_SLICE = (f"%constant_dynamic-slice_fusion.5 = f32[1,{H},{H}]{T} fusion("
               f"f32[{L},{H},{H}]{T} %wq, s32[] %i), kind=kLoop")
LAYER_COPY = (f"%copy.111 = f32[1,{H},{H}]{{1,2,0:T(8,128)}} copy(f32[1,{H},"
              f"{H}]{T} %constant_dynamic-slice_fusion.5)")
LAYER_2D = (f"%fusion.313 = bf16[{H},{H}]{{1,0}} fusion(f32[1,{H},{H}]"
            "{1,2,0} %copy.111), kind=kLoop")
KV_SLICE = (f"%copy.114 = f32[1,{H},128]{{1,2,0}} copy(f32[1,{H},128]{T} %s)")
LOOP = (f"%while.1 = (s32[], bf16[{SLOTS},1,{H}], f32[{L},{H},{H}], f32[{L},"
        f"{H},{F}]) while((s32[], bf16[{SLOTS},1,{H}], f32[{L},{H},{H}], "
        f"f32[{L},{H},{F}]) %tuple.77), condition=%c, body=%b")


def reader():
    return load_module("layer_metrics", "serve_weight_copy_ms_per_step")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "falcon-7b-11l.json")) as f:
        return json.load(f)


def run_with(events, config, kind="tpu", steps=2, serving=True):
    events = [*events, (OTHER, 0.0, 0.9)]
    spans = [("mtpu/serve/step", 0.1 + 0.4 * i, 0.3) for i in range(steps)]
    trace = Trace(kind=kind, window_s=1.0, ops={0: events}, spans=spans)
    traffic = {"serving": {"num_slots": SLOTS}} if serving else {"cli": []}
    ctx = types.SimpleNamespace(peaks=None, config=config, traffic=traffic)
    return types.SimpleNamespace(trace=trace, ctx=ctx, samples={}, checks={})


def test_the_shapes_are_the_configurations(config):
    assert reader().weight_shapes(config) == {
        (L, H, H), (L, H, 128), (L, H, F), (L, F, H), (V, H)}


@pytest.mark.parametrize("name,text,counted", [
    ("stack_cast", STACK_CAST, True),
    ("table_copy", TABLE_COPY, True),
    ("layer_slice", LAYER_SLICE, True),
    ("layer_copy_in_another_order", LAYER_COPY, True),
    ("layer_as_a_matrix", LAYER_2D, True),
    ("kv_projection_slice", KV_SLICE, True),
    ("product_of_the_copy", PRODUCT_OF_COPY, False),
    ("product_in_place", PRODUCT_IN_PLACE, False),
    ("activations", OTHER, False),
])
def test_what_counts_as_a_copy_of_a_weight(config, name, text, counted):
    got = reader().read(run_with([(text, 0.2, 4e-3)], config))
    assert got == pytest.approx(4.0 / 2 if counted else 0.0)


def test_a_parent_that_casts_and_a_change_that_does_not(config):
    parent = [(STACK_CAST, 0.20, 7.2e-3), (PRODUCT_OF_COPY, 0.21, 2.1e-3),
              (TABLE_COPY, 0.22, 2.3e-3), (LAYER_SLICE, 0.23, 0.2e-3),
              (LAYER_COPY, 0.24, 0.2e-3)]
    change = [(PRODUCT_IN_PLACE, 0.21, 9.0e-3), (TABLE_COPY, 0.22, 2.3e-3)]
    assert reader().read(run_with(parent, config)) == pytest.approx(9.9 / 2)
    assert reader().read(run_with(change, config)) == pytest.approx(2.3 / 2)


def test_the_layer_loop_has_no_time_of_its_own(config):
    events = [(LOOP, 0.2, 30e-3), (PRODUCT_IN_PLACE, 0.2001, 29e-3)]
    assert reader().read(run_with(events, config)) == 0.0


def test_zero_where_steps_ran_and_nothing_was_copied(config):
    got = reader().read(run_with([(PRODUCT_IN_PLACE, 0.2, 9e-3)], config))
    assert got == 0.0 and got is not None


@pytest.mark.parametrize("why,kwargs", [
    ("not_a_tpu", {"kind": "host-xla"}), ("no_step_span", {"steps": 0}),
    ("not_a_serving_cell", {"serving": False})])
def test_nothing_where_there_is_nothing_to_read(config, why, kwargs):
    assert reader().read(
        run_with([(STACK_CAST, 0.2, 7e-3)], config, **kwargs)) is None


def test_nothing_without_a_trace(config):
    assert reader().read(types.SimpleNamespace(
        trace=None, samples={}, checks={},
        ctx=types.SimpleNamespace(
            config=config, traffic={"serving": {"num_slots": 8}}))) is None
