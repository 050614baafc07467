"""Start-up read from inside the program: the compile ledger of
`megatron_tpu/utils/compile_cache.py` (JAX's own monitoring events, by
program) and the start-up record of `megatron_tpu/utils/tracing.py` (phases
on `time.monotonic()`, the `ready()` stamp, the six `/metrics` keys). No
assertion here is on a CPU timing's size: only signs, counts, names, order.
"""
import functools
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from megatron_tpu.config import (DataConfig, MegatronConfig, ModelConfig,
                                 OptimizerConfig, ServingConfig,
                                 TrainingConfig)
from megatron_tpu.inference.generation import Generator
from megatron_tpu.models import language_model as lm
from megatron_tpu.serving import (EngineRouter, SamplingOptions,
                                  ServingEngine, ServingMetrics,
                                  check_schema)
from megatron_tpu.training import loop as loop_mod
from megatron_tpu.utils import compile_cache, tracing
from megatron_tpu.utils.compile_cache import ensure_compile_cache

KEYS = ("startup_seconds", "compile_programs", "compile_seconds",
        "compile_cache_hits", "compile_cache_misses", "compiles_after_ready",
        "grad_accum_fused_share")


@pytest.fixture(autouse=True)
def listening(monkeypatch):
    ensure_compile_cache()       # what every entry point calls first
    # a ledger with room: the process's own may be at its cap (`MAX_EVENTS`,
    # where an event is counted and dropped) behind the test files this
    # worker ran before, and these tests read single events (PR 52: five of
    # them read `0 - 0` in a worker that had compiled 65,536 events' worth)
    monkeypatch.setattr(compile_cache, "_events", [])
    monkeypatch.setattr(compile_cache, "_totals", compile_cache._blank())
    monkeypatch.setattr(compile_cache, "_dropped", 0)


@pytest.fixture
def record(monkeypatch):
    """A start-up record of this test's own: the process's may long be
    closed (another test's server called `ready()`) or full."""
    rec = tracing._StartupRecord()
    monkeypatch.setattr(tracing, "_record", rec)
    return rec


def row(name):
    return compile_cache.ledger()["by_program"].get(
        name, compile_cache._blank())


# ---------------------------------------------------------------------
# the compile ledger
# ---------------------------------------------------------------------
def test_first_call_adds_one_program_and_the_second_nothing():
    def ledger_probe_fn(x):
        return jnp.tanh(x) @ x

    f = jax.jit(ledger_probe_fn)
    before, t0 = row("ledger_probe_fn"), time.monotonic()
    f(jnp.ones((8, 8))).block_until_ready()
    t1 = time.monotonic()
    after = row("ledger_probe_fn")
    assert after["programs"] - before["programs"] == 1
    assert after["traces"] - before["traces"] == 1
    for k in ("trace_s", "lower_s", "backend_s"):
        assert after[k] > before[k], k
    f(jnp.ones((8, 8))).block_until_ready()
    assert row("ledger_probe_fn") == after
    # every event lies on time.monotonic(), inside the call that made it
    mine = [e for e in compile_cache.events(after=t0, upto=t1)
            if e[1] == "ledger_probe_fn"]
    assert {e[0] for e in mine} >= {"trace", "lower", "backend"}
    assert all(t0 <= end - s and end <= t1 for _, _, end, s in mine)
    # the cuts take it apart at a clock reading
    assert "ledger_probe_fn" not in compile_cache.until(t0)["by_program"] \
        or compile_cache.until(t0)["by_program"]["ledger_probe_fn"] == before
    assert compile_cache.since(t0)["by_program"]["ledger_probe_fn"][
        "programs"] == 1
    assert "ledger_probe_fn" not in compile_cache.since(t1)["by_program"]


def test_nested_traces_count_once_and_a_partial_keeps_its_name():
    """Tracing a function traces every jitted function it calls (`tanh`,
    `matmul`): only the outermost is a row's trace. JAX names a partial's
    lowering and compile `jit(<unknown>)`: they join the trace's row."""
    def ledger_partial_fn(x, k):
        return jnp.tanh(x) @ x * k

    t0 = time.monotonic()
    jax.jit(functools.partial(ledger_partial_fn, k=3))(jnp.ones((6, 6)))
    cut = compile_cache.since(t0)
    r = cut["by_program"]["ledger_partial_fn"]
    assert (r["traces"], r["programs"]) == (1, 1)
    assert r["lower_s"] > 0 and r["backend_s"] > 0
    assert "<unknown>" not in cut["by_program"]
    assert cut["traces"] == cut["programs"]     # no inner trace got a row


def test_a_lowering_rules_helpers_are_not_outermost_traces():
    """Lowering a program that draws random numbers traces the threefry
    rule's helpers (hundreds of `add` and `bitwise_xor`) after the
    program's own trace has ended: they are inside the lowering, and the
    lowering and the compile still take the program's name, a lambda's
    too."""
    t0 = time.monotonic()
    jax.jit(lambda k: jax.random.normal(k, (5, 7)) * 2)(
        jax.random.PRNGKey(3)).block_until_ready()
    cut = compile_cache.since(t0)
    r = cut["by_program"]["<lambda>"]
    assert (r["traces"], r["programs"]) == (1, 1)
    assert r["lower_s"] > 0 and r["backend_s"] > 0
    assert cut["traces"] == cut["programs"], {
        k: (v["traces"], v["programs"]) for k, v in cut["by_program"].items()}


def test_totals_are_the_ledger_with_no_cut():
    jnp.ones((3, 5)).sum().block_until_ready()
    led, tot = compile_cache.ledger(), compile_cache.totals()
    for k in ("programs", "traces", "requests", "hits", "misses"):
        assert tot[k] == led[k], k
    assert tot["backend_s"] == pytest.approx(led["backend_s"])
    assert tot["events_dropped"] == 0


def test_a_second_compile_from_the_persistent_cache_counts_a_hit(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cc.reset_cache()
    try:
        def ledger_cached_fn(x):
            return jnp.cos(x) @ x + 7

        jax.jit(ledger_cached_fn)(jnp.ones((9, 9))).block_until_ready()
        first = dict(row("ledger_cached_fn"))
        if not first["requests"] or not first["misses"]:
            pytest.skip("this backend wrote no entry to the persistent "
                        f"compile cache: {first}")
        assert first["hits"] == 0
        jax.clear_caches()       # as a second process starts: nothing held
        jax.jit(ledger_cached_fn)(jnp.ones((9, 9))).block_until_ready()
        second = row("ledger_cached_fn")
        assert second["hits"] == 1 and second["misses"] == first["misses"]
        assert second["programs"] == first["programs"] + 1
        assert second["retrieval_s"] > 0
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        cc.reset_cache()


# ---------------------------------------------------------------------
# the start-up record
# ---------------------------------------------------------------------
def test_phase_nests_on_the_monotonic_clock(record):
    t0 = time.monotonic()
    with tracing.phase("engine"):
        with tracing.phase("engine.pool"):
            time.sleep(0.002)
    t1 = time.monotonic()
    rec = tracing.startup_record()
    (outer, a0, a1), (inner, b0, b1) = rec["rows"]
    assert (outer, inner) == ("engine", "engine.pool")
    assert t0 <= a0 <= b0 < b1 <= a1 <= t1
    assert rec["ready"] is None and rec["dropped"] == 0
    assert rec["t0"] <= t0


def test_phase_decorates_a_function(record):
    @tracing.phase("data")
    def build(x, *, y):
        """doc"""
        assert tracing.startup_record()["rows"][-1][2] is None   # open
        return x + y

    assert build(1, y=2) == 3 and build.__name__ == "build"
    assert build.__doc__ == "doc"
    assert build(2, y=2) == 4
    assert [r[0] for r in tracing.startup_record()["rows"]] == ["data"] * 2


def test_phase_is_a_span_inside_a_session_and_nothing_outside(
        record, tmp_path):
    with tracing.phase("mesh"):
        pass                                      # no session: no error
    tracing.start_trace(str(tmp_path))
    try:
        with tracing.phase("load"):
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    names = {e.name for p in ProfileData.from_file(files[0]).planes
             if p.name == "/host:CPU" for ln in p.lines for e in ln.events}
    assert "mtpu/setup/load" in names and "mtpu/setup/mesh" not in names


def test_ready_is_idempotent_and_closes_the_record(record, caplog):
    with tracing.phase("generator"):
        pass
    assert tracing.startup_scalars()["startup_seconds"] == 0.0
    with caplog.at_level("INFO", logger="megatron_tpu"):
        first = tracing.ready()
        again = tracing.ready()
    assert first == again == tracing.startup_record()["ready"]
    lines = [r.message for r in caplog.records if "ready in" in r.message]
    assert len(lines) == 1
    assert "generator" in lines[0] and "programs: traced" in lines[0]
    assert "from the cache (saved" in lines[0]
    with tracing.phase("load"):                   # a later hot swap
        pass
    rec = tracing.startup_record()
    assert [r[0] for r in rec["rows"]] == ["generator"]
    assert rec["dropped"] == 1
    s = tracing.startup_scalars()
    assert s["startup_seconds"] == pytest.approx(first - rec["t0"])
    assert s["startup_seconds"] > 0


def test_the_record_is_capped(record):
    for _ in range(tracing.MAX_PHASES + 5):
        with tracing.phase("data"):
            pass
    rec = tracing.startup_record()
    assert len(rec["rows"]) == tracing.MAX_PHASES and rec["dropped"] == 5


def test_the_process_start_is_before_this_module_ran():
    assert tracing._process_start() < time.monotonic()
    assert time.monotonic() - tracing._process_start() < 86400


# ---------------------------------------------------------------------
# an engine's start, and the recompile alarm
# ---------------------------------------------------------------------
def tiny_generator():
    cfg = ModelConfig(num_layers=2, hidden_size=64, num_attention_heads=4,
                      num_kv_heads=2, vocab_size=96, seq_length=64,
                      make_vocab_size_divisible_by=32,
                      compute_dtype="float32").derived()
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    return Generator(params, cfg, eos_id=-1, pad_id=0)


def test_an_engines_whole_start_and_compiles_after_ready(record, caplog):
    gen = tiny_generator()
    eng = ServingEngine(gen, ServingConfig(num_slots=2, max_queue=8,
                                           max_len=64, prefill_bucket=8))
    try:
        def serve(n_prompt, seed):
            req = eng.submit(list(range(1, n_prompt + 1)), 4,
                             SamplingOptions(temperature=1.0), seed=seed)
            return req.result(timeout=120)

        rows = tracing.startup_record()["rows"]
        names = [r[0] for r in rows]
        assert names == ["generator", "engine", "engine.pool",
                         "engine.programs"]
        assert all(end is not None for _, _, end in rows)
        (_, e0, e1), pool, programs = rows[1], rows[2], rows[3]
        assert e0 <= pool[1] <= pool[2] <= programs[1] <= programs[2] <= e1
        serve(5, 0)
        serve(5, 1)                  # the same shapes again: all warm now
        assert len(tracing.startup_record()["rows"]) < tracing.MAX_PHASES
        t_ready = tracing.ready()
        snap = eng.metrics.snapshot()
        assert snap["compiles_after_ready"] == 0.0
        assert snap["startup_seconds"] > 0
        assert snap["compile_programs"] >= 2     # decode and one prefill
        serve(5, 2)                  # a shape already warmed
        assert eng.metrics.snapshot()["compiles_after_ready"] == 0.0
        before = row("_prefill_fn")["programs"]
        serve(13, 3)                 # a new prefill bucket (16 rows)
        took = compile_cache.since(t_ready)
        assert row("_prefill_fn")["programs"] == before + 1
        assert took["by_program"]["_prefill_fn"]["programs"] == 1
        assert took["programs"] >= 1
        assert eng.metrics.snapshot()["compiles_after_ready"] \
            == float(took["programs"])
        # nothing the loop did added a row: the record is start-up's
        assert [r[0] for r in tracing.startup_record()["rows"]] == names
    finally:
        with caplog.at_level("INFO", logger="megatron_tpu"):
            eng.drain(timeout=60)
        eng.close()
    drained = [r.message for r in caplog.records if "drained" in r.message]
    assert len(drained) == 1
    # the engine's own counters, then the ledger's rows, in one place
    assert "program traces: decode=1 prefill=2 chunk=0 verify=0" \
        in drained[0]
    assert "_decode_fn " in drained[0] and "_prefill_fn " in drained[0]


@pytest.mark.parametrize("key", KEYS)
def test_metrics_hold_the_key_on_the_first_scrape(key):
    snap = ServingMetrics().snapshot()
    assert key in snap and isinstance(snap[key], float)
    check_schema(snap)


def test_a_fleet_scrape_shows_the_process_once():
    class Fake:
        def __init__(self):
            self.metrics, self.max_len = ServingMetrics(), 64

    agg = EngineRouter([Fake(), Fake()]).aggregate_snapshot()
    one = ServingMetrics().snapshot()
    for key in KEYS:             # the process's numbers, not twice them
        assert agg[key] == one[key], key
    check_schema(agg, router=True)


# ---------------------------------------------------------------------
# a training job's start
# ---------------------------------------------------------------------
class Scalars:
    def __init__(self):
        self.seen = []

    def add_scalar(self, tag, value, step):
        self.seen.append((tag, float(value), int(step)))

    def add_text(self, *a, **k):
        pass

    def flush(self):
        pass


def test_a_training_jobs_whole_start(record, monkeypatch, caplog):
    model = ModelConfig(num_layers=2, hidden_size=32, num_attention_heads=2,
                        vocab_size=64, seq_length=16).derived()
    cfg = MegatronConfig(
        model=model, optimizer=OptimizerConfig(lr=1e-3),
        training=TrainingConfig(micro_batch_size=1, global_batch_size=2,
                                train_iters=4, log_interval=2),
        data=DataConfig(num_workers=0)).validate(n_devices=1)

    def batches():
        i = 0
        while True:
            tokens = jax.random.randint(jax.random.PRNGKey(i), (2, 1, 17),
                                        0, 64)
            yield {"tokens": np.asarray(tokens),
                   "loss_mask": np.ones((2, 1, 16), np.float32)}
            i += 1

    writer = Scalars()
    monkeypatch.setattr(loop_mod, "make_writer", lambda *a, **k: writer)
    flushes = []
    real = loop_mod._device_fetch

    def fetch(tree):
        out = real(tree)
        flushes.append(time.monotonic())
        return out

    monkeypatch.setattr(loop_mod, "_device_fetch", fetch)
    with caplog.at_level("INFO", logger="megatron_tpu"):
        loop_mod.train(cfg, batches())
    rec = tracing.startup_record()
    assert [r[0] for r in rec["rows"]] == ["first_step"]
    (_, a, b), = rec["rows"]
    # closed as the first step's flush returned, and ready() right there
    assert a < flushes[0] <= b <= rec["ready"] <= flushes[1]
    assert len([r for r in caplog.records if "ready in" in r.message]) == 1
    startup = [(t, s) for t, _, s in writer.seen if t.startswith("startup/")]
    assert startup == [(f"startup/{k}", 1) for k in KEYS]
    assert len(rec["rows"]) < tracing.MAX_PHASES


def test_a_loop_that_never_flushed_still_closes_its_phase(record):
    model = ModelConfig(num_layers=1, hidden_size=32, num_attention_heads=2,
                        vocab_size=64, seq_length=16).derived()
    cfg = MegatronConfig(
        model=model, optimizer=OptimizerConfig(lr=1e-3),
        training=TrainingConfig(micro_batch_size=1, global_batch_size=2,
                                train_iters=2, log_interval=2),
        data=DataConfig(num_workers=0)).validate(n_devices=1)
    with pytest.raises(StopIteration):           # no batch at all
        loop_mod.train(cfg, iter(()))
    (name, a, b), = tracing.startup_record()["rows"]
    assert name == "first_step" and b is not None and b >= a
    assert tracing.startup_record()["ready"] is None
