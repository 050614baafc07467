"""Compile-ahead (serving/engine.py: `_compile_ahead`, `_await_program`,
`_hand_programs`, `_hand_group`, `_hand_groups`): a served program is compiled on the
engine's small pool from the moment its request is queued, and the loop,
when it reaches the program, finds it compiled or waits for that one
future.

The contracts:
- every program the loop reaches was compiled once, by the pool
  (`programs_compiled_inline` 0, one `backend` event a program in the
  compile ledger, the engine's own trace counters at one a shape), and the
  tokens are those of the same engine with no pool;
- the set of programs is the one the loop alone would have compiled for
  the same queue: a group's batch bucket is read off the queue as the loop
  starts and off each pop, nothing is guessed;
- a `lower()` that raises in the pool fails the requests that wait for it,
  where the loop's own compile would have (the supervisor restarts the
  session), and the next request of that shape is served;
- two `submit()`s of one new shape from two threads start one compile;
- a wait ends at the request's deadline, and the loop compiles itself;
- on a mesh, with the prefix cache on and over a pipeline's stages the
  loop compiles as it always did.

Each test bounds its own waits (`TIMEOUT_S`): none can hang the suite.
"""
import sys
import threading
import time

import jax
import pytest

from megatron_tpu.config import ModelConfig, ServingConfig
from megatron_tpu.inference import Generator
from megatron_tpu.models import language_model as lm
from megatron_tpu.serving import SamplingOptions, ServingEngine
from megatron_tpu.serving import engine as engine_mod
from megatron_tpu.utils import compile_cache, tracing

TIMEOUT_S = 240.0
DRAWN = SamplingOptions(temperature=1.0)
# four padded lengths under a bucket of 8: 8, 16, 24, 40
LENGTHS = (5, 13, 20, 37)
OWN = ("_decode_fn", "_prefill_fn", "_chunk_fwd_fn", "_insert_fn")


@pytest.fixture(scope="module")
def model():
    compile_cache.ensure_compile_cache()    # every entry point's first call
    cfg = ModelConfig(num_layers=2, hidden_size=64, num_attention_heads=4,
                      num_kv_heads=2, vocab_size=96, seq_length=64,
                      make_vocab_size_divisible_by=32,
                      compute_dtype="float32").derived()
    return cfg, lm.model_init(jax.random.PRNGKey(0), cfg)


@pytest.fixture
def record(monkeypatch):
    rec = tracing._StartupRecord()
    monkeypatch.setattr(tracing, "_record", rec)
    return rec


@pytest.fixture(autouse=True)
def ledger(monkeypatch):
    """A ledger with room: the process's own may be at its cap behind the
    test files this worker ran before (tests/test_startup_record.py)."""
    monkeypatch.setattr(compile_cache, "_events", [])
    monkeypatch.setattr(compile_cache, "_totals", compile_cache._blank())
    monkeypatch.setattr(compile_cache, "_dropped", 0)


def quiet():
    """Wait until no other engine's pool still compiles (one closed with
    its decode step under way, say): its events would land in this test's
    cut of the ledger under this test's names. An idle thread of a pool
    stands in `_worker`, at the queue."""
    def busy():
        frames = sys._current_frames()
        return [t for t in threading.enumerate()
                if t.name.startswith("serving-compile")
                and t.ident in frames
                and frames[t.ident].f_code.co_name != "_worker"]
    end = time.monotonic() + 60.0
    while busy() and time.monotonic() < end:
        time.sleep(0.05)


def engine(model, threads, monkeypatch, **serving):
    """A fresh generator an engine: its jits are its own, so nothing one
    engine compiled answers another's programs in memory."""
    cfg, params = model
    monkeypatch.setattr(engine_mod, "COMPILE_THREADS", threads)
    gen = Generator(params, cfg, eos_id=-1, pad_id=0)
    kw = dict(num_slots=4, max_queue=32, max_len=64, prefill_bucket=8)
    return ServingEngine(gen, ServingConfig(**{**kw, **serving}),
                         start=False)


def serve(eng, lengths, n_new=4, **kw):
    reqs = [eng.submit(list(range(1, n + 1)), n_new, DRAWN, seed=i, **kw)
            for i, n in enumerate(lengths)]
    eng._thread.start()
    return [r.result(timeout=TIMEOUT_S)[0] for r in reqs]


def own_programs(t0):
    by = {}
    for kind, program, _, _ in compile_cache.events(after=t0):
        if kind == "backend" and program in OWN:
            by[program] = by.get(program, 0) + 1
    return by


CASES = {
    # serving, lengths queued, the engine's own programs by name
    "one_a_bucket": ({}, LENGTHS, {"_decode_fn": 1, "_prefill_fn": 4}),
    # three of one length under prefill_max_batch 2: a group of two and
    # one of one, the benchmark's warm-up
    "groups": ({"prefill_max_batch": 2}, (5, 5, 5, 13, 13, 13),
               {"_decode_fn": 1, "_prefill_fn": 4}),
    # two of one bucket queued whole: ONE program of two rows, not a
    # second of one row
    "pair": ({}, (10, 15), {"_decode_fn": 1, "_prefill_fn": 1}),
    # chunks of 16: 5 and 13 in one shot; 20 = 16 + a tail in the bucket
    # of 8, 37 = 16 + 16 + a tail in the bucket of 8; one landing
    "chunked": ({"prefill_chunk": 16}, LENGTHS,
                {"_decode_fn": 1, "_prefill_fn": 2, "_chunk_fwd_fn": 2,
                 "_insert_fn": 1}),
    "chunked_blocks": ({"prefill_chunk": 16, "kv_block_size": 8}, LENGTHS,
                       {"_decode_fn": 1, "_prefill_fn": 2,
                        "_chunk_fwd_fn": 2}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_every_program_once_by_the_pool_and_the_same_tokens(
        model, monkeypatch, record, case):
    serving, lengths, programs = CASES[case]
    quiet()
    t0 = time.monotonic()
    eng = engine(model, 3, monkeypatch, **serving)
    try:
        tokens = serve(eng, lengths)
        snap = eng.metrics.snapshot()
        traces = (eng._decode_traces, eng._prefill_traces,
                  eng._chunk_traces)
    finally:
        eng.close()
    by = own_programs(t0)
    assert {k: by.get(k, 0) for k in programs} == programs
    assert traces == (1, programs["_prefill_fn"],
                      programs.get("_chunk_fwd_fn", 0))
    assert snap["programs_compiled_inline"] == 0
    reached = snap["programs_compiled_ahead"] + snap["programs_awaited"]
    # the engine's own, the draw, and the keys' programs (module-level
    # jits: another test's engine may have compiled them already, the
    # loop reaches them all the same)
    assert reached >= sum(programs.values()) + 2
    assert (snap["programs_awaited_s"] > 0) == (snap["programs_awaited"] > 0)
    rec = tracing.startup_record()
    assert {k: rec[k] for k in tracing.PROGRAM_COUNTERS} \
        == {k: snap[k] for k in tracing.PROGRAM_COUNTERS}

    # the same engine with no pool: the loop compiles each at its first
    # call, the same set, and draws the same tokens
    t1 = time.monotonic()
    ref = engine(model, 0, monkeypatch, **serving)
    try:
        assert ref._compiler is None
        assert serve(ref, lengths) == tokens
        ref_snap = ref.metrics.snapshot()
    finally:
        ref.close()
    by_ref = own_programs(t1)
    assert {k: by_ref.get(k, 0) for k in programs} == programs
    assert ref_snap["programs_compiled_ahead"] == 0
    assert ref_snap["programs_awaited"] == 0
    assert ref_snap["programs_compiled_inline"] == reached


def test_the_events_keep_their_program_names_on_the_pools_threads(
        model, monkeypatch):
    """Every kind of event of a program the pool compiled carries the
    program's name: the ledger settles names per thread."""
    quiet()
    t0 = time.monotonic()
    eng = engine(model, 2, monkeypatch)
    try:
        serve(eng, (5, 13))
    finally:
        eng.close()
    kinds = {}
    for kind, program, _, _ in compile_cache.events(after=t0):
        kinds.setdefault(program, set()).add(kind)
    for program in ("_decode_fn", "_prefill_fn"):
        assert {"trace", "lower", "backend"} <= kinds[program]


class Boom:
    """A jitted program whose `lower` raises while `armed`."""

    def __init__(self, program):
        self.program, self.armed, self.lowered = program, True, 0

    def lower(self, *args, **kwargs):
        self.lowered += 1
        if self.armed:
            raise RuntimeError("lowering went boom")
        return self.program.lower(*args, **kwargs)

    def __call__(self, *args):
        return self.program(*args)


def test_a_lower_that_raises_in_the_pool_fails_its_requests_only(
        model, monkeypatch):
    eng = engine(model, 2, monkeypatch)
    try:
        boom = eng._prefill = Boom(eng._prefill)
        doomed = eng.submit(list(range(1, 6)), 4, DRAWN, seed=0)
        eng._thread.start()
        with pytest.raises(Exception, match="lowering went boom"):
            doomed.result(timeout=TIMEOUT_S)
        deadline = time.monotonic() + TIMEOUT_S
        while eng.metrics.snapshot()["engine_restarts"] < 1:
            assert time.monotonic() < deadline
            time.sleep(0.01)
        assert eng.health()["healthy"]
        # the key was forgotten: the next request of that shape hands the
        # program over again, and is served
        boom.armed = False
        again = eng.submit(list(range(1, 6)), 4, DRAWN, seed=0)
        other = eng.submit(list(range(1, 14)), 4, DRAWN, seed=1)
        tokens = [r.result(timeout=TIMEOUT_S)[0] for r in (again, other)]
        snap = eng.metrics.snapshot()
        assert snap["engine_restarts"] == 1
        assert snap["requests_failed"] == 1
        assert snap["requests_completed"] == 2
        assert boom.lowered == 3
    finally:
        eng.close()
    ref = engine(model, 0, monkeypatch)
    try:
        assert serve(ref, (5, 13)) == tokens
    finally:
        ref.close()


def test_two_submits_of_one_new_bucket_start_one_compile(model,
                                                         monkeypatch):
    eng = engine(model, 3, monkeypatch, prefill_max_batch=1)
    try:
        eng._thread.start()
        eng.submit([1, 2], 2, DRAWN).result(timeout=TIMEOUT_S)   # warm
        counted = eng._prefill = Boom(eng._prefill)
        counted.armed = False
        gate, reqs = threading.Barrier(2), [None, None]

        def submit(i):
            gate.wait(timeout=TIMEOUT_S)
            reqs[i] = eng.submit(list(range(1, 14)), 3, DRAWN, seed=i)

        threads = [threading.Thread(target=submit, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=TIMEOUT_S)
        for r in reqs:
            r.result(timeout=TIMEOUT_S)
        assert counted.lowered == 1
        assert eng._prefill_traces == 2         # the warm one and this
        assert eng.metrics.snapshot()["programs_compiled_inline"] == 0
    finally:
        eng.close()


def test_the_wait_ends_at_the_requests_deadline(model, monkeypatch):
    """A compile slower than the request's deadline: the loop stops
    waiting there and goes on as it would have without a pool (its own
    call compiles), so nothing hangs on a pool that does not answer."""
    eng = engine(model, 2, monkeypatch)
    release = threading.Event()
    try:
        program = eng._prefill

        class Slow(Boom):
            def lower(self, *args, **kwargs):
                release.wait(timeout=TIMEOUT_S)
                return program.lower(*args, **kwargs)

        eng._prefill = Slow(program)
        req = eng.submit(list(range(1, 6)), 2, DRAWN, deadline_s=0.5)
        eng._thread.start()
        try:
            req.result(timeout=TIMEOUT_S)
        except Exception:   # noqa: BLE001 — expired, or served just in time
            pass
        snap = eng.metrics.snapshot()
        assert snap["programs_compiled_inline"] == 1
        assert 0.2 < snap["programs_awaited_s"] < 30.0
        assert snap["engine_restarts"] == 0
    finally:
        release.set()
        eng.close()


@pytest.mark.parametrize("case", ["prefix_cache", "threads_over_cores"])
def test_what_the_pool_is_not_handed_and_how_large_it_is(
        model, monkeypatch, case):
    if case == "threads_over_cores":
        monkeypatch.setattr(engine_mod.os, "cpu_count", lambda: 2)
        eng = engine(model, 64, monkeypatch)
        try:
            assert eng._compiler._max_workers == 2
        finally:
            eng.close()
        return
    # a hit decides path and shapes at admission: nothing of a request is
    # handed over at submit(), the window's two programs are, and a pop's
    # groups (misses all) as they are formed
    kw = dict(enable_prefix_cache=True, prefill_chunk=16)
    eng = engine(model, 3, monkeypatch, **kw)
    ref = engine(model, 0, monkeypatch, **kw)
    try:
        reqs = [eng.submit(list(range(1, n + 1)), 4, DRAWN, seed=i)
                for i, n in enumerate((5, 20))]
        assert set(eng._programs) == {("decode",), ("draw",)}
        eng._thread.start()
        tokens = [r.result(timeout=TIMEOUT_S)[0] for r in reqs]
        snap = eng.metrics.snapshot()
        # the key, two chunk programs and the landing of the 20 tokens
        assert snap["programs_compiled_inline"] == 4
        # decode, the draw, the 5 tokens' group and its keys
        assert snap["programs_compiled_ahead"] + snap["programs_awaited"] \
            == 4
        assert serve(ref, (5, 20)) == tokens
    finally:
        eng.close()
        ref.close()


def test_the_counters_are_in_the_schema_from_the_first_scrape(
        model, monkeypatch, record):
    eng = engine(model, 1, monkeypatch)
    try:
        snap = eng.metrics.snapshot()
        rec = tracing.startup_record()
        for key in tracing.PROGRAM_COUNTERS:
            assert snap[key] == 0.0 and rec[key] == 0
    finally:
        eng.close()
