"""The readers of the program's start-up record and compile ledger
(`benchmark/startup.py` and the seven `layer_metrics/setup_*.py`): against a
record written by hand, each value worked out on paper; against a program
that keeps neither record (a parent commit); and in the tiny CPU cells."""
import json
import types

import pytest

from benchmark import startup
from benchmark.by_name import load_module
from conftest import run_cell

READERS = ("setup_backend_compile_s", "setup_trace_lower_s",
           "setup_programs", "setup_cache_hit_pct", "setup_build_s",
           "setup_first_step_s", "setup_attributed_pct")

# The process starts at 100 and the window opens at 160: setup_s = 60.
#   phases: mesh 110-112; init_state 112-120; data 120-121;
#           first_step 121-150; engine 151-155 (so both kinds are read);
#           load 158-170 is cut at the window's opening, 160; a phase that
#           begins after it, 161, is not there at all.
#   events (end, seconds): `<lambda>` (init_state's jit) traced 113-114,
#           lowered 114-115, compiled 115-118; `train_step` traced 122-130
#           (on another thread `eager` is lowered 129-131 and compiled
#           131.1-131.2), lowered 131-134, so that trace and lowering cover
#           122-134 between them, loaded from the cache 134-136; `late` compiled 159-163 ends
#           after the opening and is left out.
#   cache: 3 requests, 1 hit (train_step, saved 40 s, read in 1.5 s), 1 miss
#           written (`<lambda>`), `eager` too quick to keep.
EVENTS = [
    ("trace", "<lambda>", 114.0, 1.0), ("lower", "<lambda>", 115.0, 1.0),
    ("request", "<lambda>", 115.1, 0.0), ("miss", "<lambda>", 117.9, 0.0),
    ("backend", "<lambda>", 118.0, 3.0),
    ("trace", "train_step", 130.0, 8.0), ("lower", "eager", 131.0, 2.0),
    ("request", "eager", 131.1, 0.0), ("backend", "eager", 131.2, 0.1),
    ("lower", "train_step", 134.0, 3.0),
    ("request", "train_step", 134.1, 0.0), ("hit", "train_step", 135.6, 0.0),
    ("saved", "train_step", 135.6, 40.0),
    ("retrieval", "train_step", 135.6, 1.5),
    ("backend", "train_step", 136.0, 2.0),
    ("backend", "late", 163.0, 4.0)]
ROWS = [("mesh", 110.0, 112.0), ("init_state", 112.0, 120.0),
        ("data", 120.0, 121.0), ("first_step", 121.0, 150.0),
        ("engine", 151.0, 155.0), ("load", 158.0, 170.0),
        ("generator", 161.0, None)]


def program(events=EVENTS, rows=ROWS):
    cc = types.SimpleNamespace(events=lambda upto=None: [
        e for e in events if upto is None or e[2] <= upto])
    tr = types.SimpleNamespace(startup_record=lambda: {
        "t0": 99.0, "ready": None, "rows": list(rows), "dropped": 0})
    return cc, tr


def a_run(setup_s=60.0):
    return types.SimpleNamespace(
        ctx=types.SimpleNamespace(t_process_start=100.0),
        end_to_end={"setup_s": setup_s}, samples={}, trace=None)


def read(name, run):
    return load_module("layer_metrics", name).read(run)


def test_readers_on_a_record_written_by_hand(monkeypatch, capfd):
    monkeypatch.setattr(startup, "_records", program)
    run = a_run()
    # backend: 115-118, 131.1-131.2, 134-136
    assert read("setup_backend_compile_s", run) == pytest.approx(5.1)
    # trace and lowering: 113-115, 122-134
    assert read("setup_trace_lower_s", run) == pytest.approx(14.0)
    assert read("setup_programs", run) == 3.0
    assert read("setup_cache_hit_pct", run) == pytest.approx(50.0)
    # mesh 2 + init_state 8 + data 1 + engine 4 + load 2 (cut at 160), less
    # the five seconds of init_state's trace, lowering and compile
    assert read("setup_build_s", run) == pytest.approx(12.0)
    assert read("setup_first_step_s", run) == pytest.approx(29.0)
    # 110-150, 151-155, 158-160 of 60
    assert read("setup_attributed_pct", run) == pytest.approx(100 * 46 / 60)
    line = [ln for ln in capfd.readouterr().err.splitlines()
            if ln.startswith("startup ")]
    assert len(line) == 1
    b = json.loads(line[0][len("startup "):])
    parts = ("backend_s", "lower_s", "trace_s", "phases_besides_s",
             "before_first_sign_s", "between_s")
    assert sum(b[k] for k in parts) == pytest.approx(b["setup_s"]) == 60.0
    assert (b["backend_s"], b["before_first_sign_s"]) == (5.1, 10.0)
    # the lowering 129-131 wins over the trace it overlaps, and loses the
    # 0.1 s under `eager`'s backend event to that
    assert (b["lower_s"], b["trace_s"]) == (5.9, 8.0)
    assert (b["phases_besides_s"], b["between_s"]) == (27.0, 4.0)
    assert (b["hits"], b["misses"], b["requests"]) == (1, 1, 3)
    assert b["saved_s"] == 40.0 and b["programs"] == 3


def test_sums_stay_inside_setup_s(monkeypatch):
    monkeypatch.setattr(startup, "_records", program)
    for setup_s in (5.0, 16.0, 31.1, 60.0, 500.0):
        run = a_run(setup_s)
        assert (read("setup_backend_compile_s", run)
                + read("setup_trace_lower_s", run)) <= setup_s
        assert 0.0 <= read("setup_attributed_pct", run) <= 100.0


def test_nothing_kept_means_no_hit_share(monkeypatch):
    quick = [e for e in EVENTS if e[0] not in ("hit", "miss")]
    monkeypatch.setattr(startup, "_records", lambda: program(events=quick))
    assert read("setup_cache_hit_pct", a_run()) is None
    assert read("setup_programs", a_run()) == 3.0


@pytest.mark.parametrize("name", READERS)
def test_a_program_with_no_records_reads_none(monkeypatch, name):
    """A parent commit's `compile_cache` has `ensure_compile_cache` alone
    and its `tracing` no `startup_record`: the real `_records` says so."""
    import megatron_tpu.utils.compile_cache as cc
    import megatron_tpu.utils.tracing as tr
    monkeypatch.delattr(cc, "events")
    assert startup._records() is None
    assert read(name, a_run()) is None
    monkeypatch.undo()
    monkeypatch.delattr(tr, "startup_record")
    assert read(name, a_run()) is None


@pytest.mark.parametrize("name", READERS)
def test_a_run_with_no_setup_s_reads_none(name):
    assert read(name, types.SimpleNamespace(end_to_end={})) is None


@pytest.mark.parametrize("workload", ["tiny.train", "tiny.serve"])
def test_tiny_cells_print_numbers_that_add_up(bench_copy, workload):
    spec = json.loads((bench_copy / "BENCHMARK.json").read_text())
    for m in spec["per_layer"]:          # the copy files them by `moves`
        if m["moves"] == "setup_s" and m["name"] != "setup_first_step_s":
            m["workloads"] = ["tiny.train", "tiny.serve", "tiny.train-tp4"]
    (bench_copy / "BENCHMARK.json").write_text(json.dumps(spec))
    untraced = run_cell(bench_copy, workload, 0)
    assert untraced.returncode == 0, untraced.stderr[-3000:]
    assert not [k for k in json.loads(untraced.stdout)["metrics"]
                if k.startswith("setup_") and k != "setup_s"]
    p = run_cell(bench_copy, workload, 1)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    m = {k: v["value"] for k, v in res["metrics"].items()}
    want = set(READERS) - {"setup_cache_hit_pct"}     # see below
    if workload == "tiny.serve":
        want -= {"setup_first_step_s"}
        assert "setup_first_step_s" not in m
    assert want <= set(m), sorted(m)
    line, = [ln for ln in p.stderr.splitlines() if ln.startswith("startup ")]
    b = json.loads(line[len("startup "):])
    setup_s = b["setup_s"]
    assert m["setup_backend_compile_s"] + m["setup_trace_lower_s"] <= setup_s
    assert 0 < m["setup_attributed_pct"] <= 100
    assert m["setup_programs"] == b["programs"] >= 3
    assert 0 < m["setup_build_s"] < setup_s
    if workload == "tiny.train":
        assert 0 < m["setup_first_step_s"] < setup_s
        assert {"init_state", "data", "first_step"} <= {
            n for n, _, _ in b["phases"]}
        assert any(prog == "train_step"
                   for prog, *_ in b["top_programs_n_trace_lower_backend"])
    else:
        assert [n for n, _, _ in b["phases"]] == [
            "generator", "engine", "engine.pool", "engine.programs"]
    assert len(b["phases"]) < 64
    # of the programs that took the CPU a second to compile (JAX keeps no
    # other) the second run found in the copy's cache what the first wrote;
    # where there was none, the metric is left out
    kept = b["hits"] + b["misses"]
    if kept:
        assert m["setup_cache_hit_pct"] == pytest.approx(
            100.0 * b["hits"] / kept)
    else:
        assert "setup_cache_hit_pct" not in m
