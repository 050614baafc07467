"""`benchmark/layer_metrics/serve_vocab_ms_per_step.py` on hand-made traces:
the self time of every operation that holds the decode grid's logits,
slots x padded vocabulary, per `mtpu/serve/step` span."""
import types

import pytest

from benchmark.by_name import load_module
from benchmark.trace import Trace

SLOTS, VOCAB = 32, 129280
GRID = f"f32[{SLOTS},{VOCAB}]{{1,0:T(8,128)}}"
SORT = (f"%sort.54 = {GRID} sort({GRID} %fusion.12), dimensions={{1}}, "
        "is_stable=true, to_apply=%compare")
HEAD = (f"%fusion.88 = {GRID} fusion(bf16[{SLOTS},2048]{{1,0}} %h, "
        f"bf16[2048,{VOCAB}]{{1,0}} %w), kind=kOutput, calls=%fused_dot")
ARGMAX = (f"%fusion.3 = s32[{SLOTS}]{{0}} fusion({GRID} %masked), "
          "kind=kInput, calls=%fused_argmax")
OTHER = (f"%fusion.1 = bf16[{SLOTS},2048]{{1,0}} fusion(bf16[{SLOTS},2048]"
         "{1,0} %p), kind=kLoop")
EMBED = (f"%convert.5 = bf16[{VOCAB},2048]{{1,0}} convert(f32[{VOCAB},2048]"
         "{1,0} %table)")


def reader():
    return load_module("layer_metrics", "serve_vocab_ms_per_step")


def run_with(events, kind="tpu", steps=2, config=None, serving=True):
    # an operation that is none of the grid's, so that the device's window
    # holds the step spans
    events = [*events, (OTHER, 0.0, 0.9)]
    spans = [("mtpu/serve/step", 0.1 + 0.4 * i, 0.3) for i in range(steps)]
    trace = Trace(kind=kind, window_s=1.0, ops={0: events}, spans=spans)
    traffic = {"serving": {"num_slots": SLOTS, "max_len": 16384}}
    ctx = types.SimpleNamespace(
        peaks=None, config={"vocab_size": VOCAB} if config is None else config,
        traffic=traffic if serving else {"cli": []})
    return types.SimpleNamespace(trace=trace, ctx=ctx, samples={}, checks={})


def test_a_parent_that_sorts_and_a_change_that_does_not():
    rest = [(HEAD, 0.20, 2.0e-3), (ARGMAX, 0.21, 0.5e-3),
            (EMBED, 0.95, 4e-3)]
    sorts = [(SORT, 0.30, 5.0e-3), (SORT.replace("sort.54", "sort.57"),
                                    0.31, 5.0e-3)]
    assert reader().read(run_with(rest + sorts)) == pytest.approx(12.5 / 2)
    assert reader().read(run_with(rest)) == pytest.approx(2.5 / 2)


def test_a_conditional_has_no_time_of_its_own():
    """The guarded sorts run inside a `conditional`: the container's span
    is not counted beside its body's."""
    cond = (f"%conditional.7 = {GRID} conditional(pred[] %any, {GRID} %x, "
            f"{GRID} %x), true_computation=%filters, "
            "false_computation=%as_it_is")
    events = [(cond, 0.30, 10.2e-3), (SORT, 0.3001, 5.0e-3),
              (SORT.replace("sort.54", "sort.57"), 0.3052, 5.0e-3)]
    assert reader().read(run_with(events)) == pytest.approx(10.0 / 2)
    skipped = [(cond, 0.30, 40e-6),
               (f"%copy.2 = {GRID} copy({GRID} %x)", 0.30001, 30e-6)]
    assert reader().read(run_with(skipped)) == pytest.approx(0.030 / 2)


@pytest.mark.parametrize("vocab,cli,padded", [
    (129280, [], 129280), (65024, ["--model", "falcon-7b"], 65024),
    (50280, [], 50304), (96, ["--make_vocab_size_divisible_by", "32"], 96),
    (83, ["--make_vocab_size_divisible_by", "32", "--bf16"], 96)])
def test_the_vocabulary_as_the_program_pads_it(vocab, cli, padded):
    r = reader()
    assert r.padded_vocab({"vocab_size": vocab, "cli": cli}) == padded
    op = (f"%fusion.9 = f32[{SLOTS},{padded}]{{1,0}} fusion(f32[{SLOTS},"
          f"{padded}]{{1,0}} %x), kind=kLoop")
    run = run_with([(op, 0.2, 3e-3)], config={"vocab_size": vocab,
                                              "cli": cli})
    assert r.read(run) == pytest.approx(3.0 / 2)


def test_nothing_where_there_is_nothing_to_read():
    read = reader().read
    assert read(run_with([(EMBED, 0.95, 4e-3)])) is None
    assert read(run_with([(HEAD, 0.2, 2e-3)], kind="host-xla")) is None
    assert read(run_with([(HEAD, 0.2, 2e-3)], steps=0)) is None
    assert read(run_with([(HEAD, 0.2, 2e-3)], config={})) is None
    assert read(run_with([(HEAD, 0.2, 2e-3)], serving=False)) is None
    assert read(types.SimpleNamespace(
        trace=None, samples={}, checks={},
        ctx=types.SimpleNamespace(config={"vocab_size": VOCAB},
                                  traffic={"serving": {"num_slots": 8}}))
                ) is None
