"""The readers of the program's own record of its requests
(`benchmark/request_timeline.py` and the four
`layer_metrics/serve_first_*.py`): against a record and a trace written by
hand, each value worked out on paper; against a program that keeps no record
(a parent commit); and, by hand, in the tiny CPU cell, where the additions
change no file the benchmark had."""
import json
import os
import subprocess
import types

import pytest

from benchmark import request_timeline as rt
from benchmark.by_name import load_module
from benchmark.trace import Trace

__all__ = [                      # what tier-1 collects: no child runs
    "record",
    "test_the_cut_ends_at_the_windows_two_ends",
    "test_the_four_reductions_on_a_record_written_by_hand",
    "test_the_three_segments_tile_the_first_token",
    "test_clock_offset_finds_the_shift_through_jitter_and_strangers",
    "test_clock_offset_answers_none_where_nothing_aligns",
    "test_busy_inside_clips_at_both_ends",
    "test_a_program_without_the_record_reads_none",
    "test_off_a_tpu_the_device_reader_reads_none",
    "test_an_empty_cut_reads_none"]

READERS = ("serve_first_behind_window_p50_ms",
           "serve_first_behind_prefill_pct",
           "serve_first_own_prefill_p50_ms",
           "serve_first_host_overhead_pct")
MS = 1e-3
T_OPEN, WINDOW_S = 1000.0, 45.0
SESSION = 1046.5            # `time.monotonic()` as the profiler's session began


def row(t_submit, queue=1.0, behind=0.0, prefill=None, programs=1, ahead=0,
        held=0, rid=0):
    """A row from its segments in milliseconds; `prefill` None: no first
    token."""
    t_admit = t_submit + queue * MS
    t_device = t_admit + behind * MS
    return types.SimpleNamespace(
        engine=1, rid=rid, t_submit=t_submit, t_admit=t_admit,
        t_device=t_device,
        t_first=None if prefill is None else t_device + prefill * MS,
        early=int(behind > 0), held=held, programs=programs,
        ahead_programs=ahead)


# The window is [1000, 1045). In the cut: B (at the opening), C, D, E, H.
#   behind the window, ms: B 8, C 4, D 0, E 0, H 2       -> median 2
#   behind a prefill: D (two programs ahead), H (held)   -> 2 of 5 = 40 %
#   alone (one program, nobody ahead): B 30, C 20, H 40  -> median 30 ms
# Out of it: A (ramp), F (no first token), G (at the close).
# The tail, any phase, traced from 1046.5 on the rows' clock:
#   T0 began before the session; T1 and T2 alone and inside; T3 behind
#   another's program; T4's segment ends after the last traced event.
WINDOW_ROWS = {
    "A": row(999.9, prefill=30), "B": row(1000.0, 2, 8, 30),
    "C": row(1010.0, 1, 4, 20),
    "D": row(1020.0, 100, 0, 200, ahead=2, held=1),
    "E": row(1030.0, 1, 0, 499, programs=3),
    "F": row(1044.999), "G": row(1045.0, prefill=30),
    "H": row(1040.0, 50, 2, 40, held=1)}
TAIL_ROWS = {
    "T0": row(1046.2, 100, 0, 300), "T1": row(1047.0, 1, 0, 30),
    "T2": row(1048.2, 1, 10, 40), "T3": row(1049.0, 1, 0, 60, ahead=1),
    "T4": row(1051.4, 1, 0, 30)}
ROWS = list(WINDOW_ROWS.values()) + list(TAIL_ROWS.values())
# device 0 on the trace's clock, seconds: T1's segment [0.501, 0.531] holds
# 15 + 6 = 21 ms of work, T2's [1.711, 1.751] 9 + 11 = 20 ms: 41 of 70 ms
BUSY = [(0.0, 0.4), (0.505, 0.520), (0.525, 0.6), (1.70, 1.72), (1.74, 1.80),
        (4.0, 4.92)]
OVERHEAD_PCT = 100.0 * (1.0 - 41.0 / 70.0)


def a_trace(shift=0.0, jitter=(0.0,), kind="tpu", names=("T1", "T2", "T3",
                                                         "T4")):
    """Submit spans of the tail's requests on the trace's clock (`shift`
    later than it is, each begun `jitter` seconds before its row's stamp)
    and device 0's operations."""
    spans = [(rt.SUBMIT,
              TAIL_ROWS[n].t_submit - SESSION + shift
              - jitter[i % len(jitter)], 50e-6)
             for i, n in enumerate(names)]
    spans.append(("mtpu/serve/iteration", 0.0 + shift, 4.9))
    ops = {0: [("%op", a + shift, b - a) for a, b in BUSY],
           1: [("%other", 0.0 + shift, 4.0)]}      # never read for work
    return Trace(kind=kind, window_s=4.92, ops=ops, spans=spans)


def a_run(rows=ROWS, trace=None):
    return types.SimpleNamespace(
        samples={"t_open": T_OPEN, "window_s": WINDOW_S, "attempted": 6,
                 "failed": 1},
        trace=trace, ctx=None)


@pytest.fixture
def record(monkeypatch):
    monkeypatch.setattr(rt, "_record", lambda: lambda: list(ROWS))


def read(name, run):
    return load_module("layer_metrics", name).read(run)


def test_the_cut_ends_at_the_windows_two_ends(record):
    got = rt.cut(a_run())
    want = [WINDOW_ROWS[k] for k in "BCDEH"]
    assert sorted(r.t_submit for r in got) \
        == sorted(r.t_submit for r in want)
    # a run without the window's marks cuts nothing
    bare = types.SimpleNamespace(samples={}, trace=None)
    assert rt.cut(bare) is None


def test_the_four_reductions_on_a_record_written_by_hand(record, capfd):
    run = a_run(trace=a_trace())
    assert read("serve_first_behind_window_p50_ms", run) \
        == pytest.approx(2.0)
    assert read("serve_first_behind_prefill_pct", run) == pytest.approx(40.0)
    assert read("serve_first_own_prefill_p50_ms", run) == pytest.approx(30.0)
    assert read("serve_first_host_overhead_pct", run) \
        == pytest.approx(OVERHEAD_PCT)
    # the first reader's line for a person
    line = [ln for ln in capfd.readouterr().err.splitlines()
            if ln.startswith("requests ")]
    assert len(line) == 1
    said = json.loads(line[0][len("requests "):])
    assert (said["rows"], said["driver_first_tokens"]) == (5, 5)
    assert (said["early"], said["held"], said["ahead"], said["chunked"],
            said["alone"]) == (3, 2, 1, 1, 3)


def test_the_three_segments_tile_the_first_token(record):
    said = rt.breakdown(a_run(), rt.cut(a_run()))
    assert said["queue_s"] == pytest.approx(0.154)
    assert said["behind_window_s"] == pytest.approx(0.014)
    assert said["prefill_s"] == pytest.approx(0.789)
    assert said["first_token_s"] == pytest.approx(0.957)
    assert abs(said["tiling_error_us"]) < 1.0


def test_clock_offset_finds_the_shift_through_jitter_and_strangers(record):
    # spans begin 20 to 400 us before their rows' stamps, and one span is a
    # request no row answers for (refused at submit: it reached no engine)
    trace = a_trace(shift=0.25, jitter=(20e-6, 400e-6, 60e-6, 90e-6))
    trace.spans.append((rt.SUBMIT, 3.3333, 40e-6))
    got = rt.clock_offset(trace, ROWS, after=T_OPEN + WINDOW_S)
    assert got == pytest.approx(0.25 - SESSION, abs=400e-6)
    # every row of the record, the window's too: the same constant
    assert rt.clock_offset(trace, ROWS) == pytest.approx(got)
    # and the device reader reads the shifted trace as the plain one
    assert rt.host_overhead_pct(a_run(trace=a_trace(shift=0.25))) \
        == pytest.approx(OVERHEAD_PCT)


def test_clock_offset_answers_none_where_nothing_aligns(record):
    one = a_trace(names=("T2",))
    assert rt.clock_offset(one, ROWS) is None          # one span: no gap
    assert rt.host_overhead_pct(a_run(trace=one)) is None
    strangers = a_trace()
    strangers.spans[:] = [(rt.SUBMIT, s, 50e-6)
                          for s in (0.1, 0.77, 2.05, 3.9)]
    assert rt.clock_offset(strangers, ROWS) is None    # no row fits two
    assert rt.clock_offset(a_trace(), []) is None


def test_busy_inside_clips_at_both_ends():
    busy = [(0.0, 1.0), (2.0, 3.0), (5.0, 6.0)]
    assert rt.busy_inside((0.5, 2.5), busy) == pytest.approx(1.0)
    assert rt.busy_inside((1.0, 2.0), busy) == 0.0
    assert rt.busy_inside((2.2, 2.4), busy) == pytest.approx(0.2)
    assert rt.busy_inside((-1.0, 9.0), busy) == pytest.approx(3.0)


def test_a_program_without_the_record_reads_none(monkeypatch):
    monkeypatch.setattr(rt, "_record", lambda: None)
    run = a_run(trace=a_trace())
    assert rt.cut(run) is None and rt.rows_of() is None
    for name in READERS:
        assert read(name, run) is None, name


def test_off_a_tpu_the_device_reader_reads_none(record):
    for trace in (None, a_trace(kind="host-xla")):
        run = a_run(trace=trace)
        assert read("serve_first_host_overhead_pct", run) is None
        assert read("serve_first_own_prefill_p50_ms", run) \
            == pytest.approx(30.0)


def test_an_empty_cut_reads_none(monkeypatch):
    monkeypatch.setattr(rt, "_record", lambda: lambda: [])
    run = a_run(trace=a_trace())
    for name in READERS:
        assert read(name, run) is None, name


# ---------------------------------------------------------------------
# by hand: the tiny cell, and that the additions are additions
# ---------------------------------------------------------------------
ADDED = ["request_timeline.py", "tests/test_request_timeline.py"] + [
    f"layer_metrics/{name}.py" for name in READERS]


def test_the_additions_change_no_file_the_benchmark_had(bench_copy):
    """In the manner of `test_extend.py`: take the additions out of a copy,
    which leaves what the benchmark had; put them back as files and
    entries; the tiny serving cell then reports the three host-clock
    metrics and every file that was there is as it was."""
    from conftest import run_cell
    b = bench_copy / "benchmark"
    spec = json.loads((bench_copy / "BENCHMARK.json").read_text())
    mine = [m for m in spec["per_layer"] if m["name"] in READERS]
    assert [m["name"] for m in spec["per_layer"][-4:]] == list(READERS)
    added = {name: (b / name).read_bytes() for name in ADDED}
    for name in ADDED:
        (b / name).unlink()
    spec["per_layer"] = [m for m in spec["per_layer"] if m not in mine]
    (bench_copy / "BENCHMARK.json").write_text(json.dumps(spec))
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}

    p = run_cell(bench_copy, "tiny.serve", 1)
    assert p.returncode == 0, p.stderr[-3000:]
    had = json.loads(p.stdout.strip().splitlines()[-1])["metrics"]
    assert not set(READERS) & set(had)

    for name, data in added.items():
        (b / name).write_bytes(data)
    spec["per_layer"] += mine
    (bench_copy / "BENCHMARK.json").write_text(json.dumps(spec))
    p = run_cell(bench_copy, "tiny.serve", 1)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(had) <= set(res["metrics"])
    assert set(READERS[:3]) <= set(res["metrics"])
    assert READERS[3] not in res["metrics"]           # no TPU, no number
    said = [ln for ln in p.stderr.splitlines() if ln.startswith("requests ")]
    said = json.loads(said[-1][len("requests "):])
    assert abs(said["rows"] - (res["attempted"] - res["failed"])) <= 1
    assert abs(said["tiling_error_us"]) < 1.0
    assert all(p.read_bytes() == data for p, data in before.items())


def test_against_the_parent_commit_only_files_are_added():
    """Where the checkout is a git repository that holds the parent: under
    `benchmark/` no file it had is edited or gone, and `BENCHMARK.json` is
    the parent's with four `per_layer` entries behind its last."""
    from conftest import REPO
    parent = "9c10826e7266882b19fedc8a4b3391d8cef7cda7"

    def git(*args):
        return subprocess.run(["git", *args], cwd=REPO, capture_output=True,
                              text=True)
    p = git("diff", "--name-status", parent, "--", "benchmark")
    if p.returncode != 0:
        pytest.skip("no git history here")
    changed = dict(reversed(ln.split("\t")) for ln in p.stdout.splitlines())
    assert set(changed.values()) <= {"A"}, changed
    assert set(changed) <= {os.path.join("benchmark", n) for n in ADDED}
    had = json.loads(git("show", parent + ":BENCHMARK.json").stdout)
    has = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    assert [m["name"] for m in has["per_layer"][-4:]] == list(READERS)
    has["per_layer"] = has["per_layer"][:-4]
    assert has == had
