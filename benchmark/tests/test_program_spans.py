"""The readers of the program's own spans (`benchmark/program_spans.py` and
the ten `layer_metrics/` files that use it) against traces written by hand,
each value worked out on paper. Times below are milliseconds."""
import types

import pytest

from benchmark import program_spans as ps
from benchmark.by_name import load_module
from benchmark.trace import Trace

MS = 1e-3
S = "mtpu/serve/"


def ev(name, t0, t1):
    return (name, t0 * MS, (t1 - t0) * MS)


def step(t0, t1, upload, dispatch, fetch, commit):
    """A `step` span and its four children, each (start, end)."""
    return [ev(S + "step", t0, t1), ev(S + "step.upload", *upload),
            ev(S + "step.dispatch", *dispatch), ev(S + "step.fetch", *fetch),
            ev(S + "step.commit", *commit)]


# Device 0 runs six operations and waits four times:
#   10-20 (10), 30-30.02 (0.02: under the 50 us threshold), 40-52 (12),
#   70-80 (10): 32.02 ms of idle in all.
OPS = {0: [ev("%decode.1", 0, 10), ev("%decode.2", 20, 30),
           ev("%decode.3", 30.02, 40), ev("%prefill.1", 52, 60),
           ev("%decode.4", 60, 70), ev("%decode.5", 80, 90)],
       1: [ev("%other", 0, 90)]}
# What the engine thread was doing (and, 74-74.5, a caller's thread):
SPANS = (
    [ev(S + "iteration", 0, 13)]
    + step(0.5, 13, (0.5, 1), (1, 2), (2, 11), (11, 13))
    + [ev(S + "iteration", 13, 33), ev(S + "reap", 13.5, 14),
       ev(S + "admit", 14, 15)]
    + step(15, 33, (15, 16), (16, 19), (19, 31), (31, 33))
    + [ev(S + "iteration", 33, 43), ev(S + "reap", 33, 33.5),
       ev(S + "admit", 33.5, 34)]
    + step(34, 43, (34, 34.5), (34.5, 35), (35, 41), (41, 43))
    + [ev(S + "iteration", 43, 73), ev(S + "reap", 43, 44),
       ev(S + "admit", 44, 53), ev(S + "prefill", 45, 51)]
    + step(53, 73, (53, 54), (54, 55), (55, 71), (71, 73))
    + [ev(S + "idle_wait", 73, 77), ev(S + "submit", 74, 74.5),
       ev("bench/submit", 74, 74.6), ev(S + "iteration", 78, 95)]
    + step(78, 95, (78, 78), (78, 80), (80, 91), (91, 95)))
# Idle seconds by leaf span, gap by gap:
#   10-20: fetch 10-11, commit 11-13, iteration (its own time) 13-13.5,
#          reap 13.5-14, admit 14-15, upload 15-16, dispatch 16-19,
#          fetch 19-20
#   40-52: fetch 40-41, commit 41-43, reap 43-44, admit 44-45 and 51-52,
#          prefill 45-51
#   70-80: fetch 70-71, commit 71-73, idle_wait 73-74 and 74.5-77,
#          submit 74-74.5, nothing 77-78, dispatch 78-80
BY_SPAN = {"step.fetch": 4, "step.commit": 6, "iteration": 0.5, "reap": 1.5,
           "admit": 3, "prefill": 6, "step.upload": 1, "step.dispatch": 5,
           "idle_wait": 3.5, "submit": 0.5}
IDLE = 32.02
UNATTRIBUTED = 1.02                      # 0.02 under the threshold, 77-78
STEPS = 5                                # step spans that begin in 0-90


def run_of(kind="tpu", spans=SPANS, ops=OPS, serving=None, samples=None):
    trace = None
    if kind is not None:
        trace = Trace(kind=kind, window_s=90 * MS, ops=ops,
                      spans=list(spans))
    traffic = {"serving": serving or {"num_slots": 64}}
    return types.SimpleNamespace(
        trace=trace, samples=samples or {},
        ctx=types.SimpleNamespace(traffic=traffic))


def read(metric, run):
    return load_module("layer_metrics", metric).read(run)


def test_the_paper_values_add_up():
    assert sum(BY_SPAN.values()) + UNATTRIBUTED == pytest.approx(IDLE)


def test_idle_by_span_splits_every_gap_among_its_leaf_spans():
    total, by = ps.idle_by_span(run_of().trace)
    assert total == pytest.approx(IDLE * MS)
    want = {S + k: v * MS for k, v in BY_SPAN.items()}
    want[ps.UNATTRIBUTED] = UNATTRIBUTED * MS
    # `ev` adds a start and a duration: a child's end and its sibling's
    # start may differ in the last bit, and the sliver goes to the parent
    slivers = {k for k, v in by.items() if v < 1e-12}
    assert slivers <= {S + "step"}
    assert set(by) - slivers == set(want)
    for k, v in want.items():
        assert by[k] == pytest.approx(v), k
    assert sum(by.values()) == pytest.approx(total)


def test_leaf_pieces_do_not_overlap_and_prefer_the_span_begun_last():
    pieces = ps.leaf_pieces([ev("a", 0, 10), ev("b", 2, 4), ev("c", 3, 3.5),
                             ev("other-thread", 9, 12)])
    flat = [(n, round(a / MS, 6), round(b / MS, 6)) for n, a, b in pieces]
    assert flat == [("a", 0, 2), ("b", 2, 3), ("c", 3, 3.5), ("b", 3.5, 4),
                    ("a", 4, 9), ("other-thread", 9, 10),
                    ("other-thread", 10, 12)]


@pytest.mark.parametrize("metric,want", [
    ("serve_idle_ms_per_step", IDLE / STEPS),
    ("serve_idle_attributed_pct", 100 * (IDLE - UNATTRIBUTED) / IDLE),
    ("serve_idle_commit_ms_per_step", 6 / STEPS),
    ("serve_idle_admit_ms_per_step", (1.5 + 3 + 6) / STEPS),
    ("serve_idle_roundtrip_ms_per_step", (1 + 5 + 4) / STEPS),
    # commits begin at 11, 31, 41, 71, 91: intervals 20, 10, 30, 20; the
    # third holds the prefill (45), the fourth the idle wait (73)
    ("serve_step_period_plain_ms", 15.0),
    ("serve_step_period_prefill_ms", 30.0),
])
def test_serving_reader_against_paper(metric, want):
    assert read(metric, run_of()) == pytest.approx(want)
    assert read(metric, run_of(kind="host-xla")) is None
    assert read(metric, run_of(kind=None)) is None
    # a program without the spans (the parent): nothing to read, no error
    assert read(metric, run_of(spans=[ev("bench/submit", 74, 74.6)])) is None


def test_idle_parts_add_up_to_the_whole():
    r = run_of()
    parts = sum(read(m, r) for m in (
        "serve_idle_commit_ms_per_step", "serve_idle_admit_ms_per_step",
        "serve_idle_roundtrip_ms_per_step"))
    other = (BY_SPAN["iteration"] + BY_SPAN["idle_wait"] + BY_SPAN["submit"]
             + UNATTRIBUTED) / STEPS
    assert parts + other == pytest.approx(read("serve_idle_ms_per_step", r))


def test_token_gap_tail_is_the_sample_with_ten_beyond_it():
    # 25 commits whose 24 intervals are 1, 2, ... 24 ms in a shuffled order
    order = [7, 24, 1, 13, 19, 2, 22, 8, 14, 3, 20, 9, 15, 4, 23, 10, 16, 5,
             21, 11, 17, 6, 18, 12]
    t, spans = 0.0, [ev(S + "step.commit", 0, 0.5)]
    for gap in order:
        t += gap
        spans.append(ev(S + "step.commit", t, t + 0.5))
    ops = {0: [ev("%decode", 0, t + 1)]}
    metric = "serve_token_gap_tail_ms"
    assert read(metric, run_of(spans=spans, ops=ops)) == pytest.approx(14.0)
    # too few intervals for a tail; and a commit that delivers several
    # tokens at once is no token gap
    assert read(metric, run_of(spans=spans[:21], ops=ops)) is None
    assert read(metric, run_of(spans=spans[:22], ops=ops)) == \
        pytest.approx(sorted(order[:21])[-11])
    for serving in ({"decode_sync_interval": 4}, {"speculative_k": 2}):
        assert read(metric, run_of(spans=spans, ops=ops,
                                   serving=serving)) is None
    assert read(metric, run_of(kind="host-xla", spans=spans, ops=ops)) is None
    assert read(metric, run_of(kind=None)) is None
    assert read(metric, run_of()) is None            # four intervals


@pytest.mark.parametrize("metric,want", [
    ("train_data_next_ms_per_step", 0.2),
    ("train_host_dispatch_ms_per_step", 1.5)])
def test_training_reader_against_paper(metric, want):
    spans = [ev("bench/flush", 0, 1)]
    for i in range(3):
        spans += [ev("mtpu/train/step", 10 * i + 1, 10 * i + 2.5),
                  ev("mtpu/train/data_next", 10 * i + 3, 10 * i + 3.2),
                  ev("bench/data_next", 10 * i + 3, 10 * i + 3.1)]
    ops = {0: [ev("%step", 2, 31)]}
    samples = {"traced_steps": 3}
    assert read(metric, run_of(spans=spans, ops=ops, samples=samples)) == \
        pytest.approx(want)
    assert read(metric, run_of(kind="host-xla", spans=spans, ops=ops,
                               samples=samples)) is None
    assert read(metric, run_of(kind=None, samples=samples)) is None
    assert read(metric, run_of(spans=spans[:1], ops=ops,
                               samples=samples)) is None
    assert read(metric, run_of(spans=spans, ops=ops)) is None   # no steps
