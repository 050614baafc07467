"""The program's host spans (`megatron_tpu/utils/tracing.py`): inside a
profiler session the engine loop and the training loop write the spans the
tables in that docstring name, nested as the tables say; outside one they
change nothing. No assertion here is on a CPU timing: only names, counts,
nesting and order.
"""
import glob
import os
import threading
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from megatron_tpu.config import (DataConfig, MegatronConfig, ModelConfig,
                                 OptimizerConfig, ServingConfig,
                                 TrainingConfig)
from megatron_tpu.inference.generation import Generator
from megatron_tpu.models import language_model as lm
from megatron_tpu.serving import SamplingOptions, ServingEngine
from megatron_tpu.training import loop as loop_mod
from megatron_tpu.utils import tracing
from megatron_tpu.utils.tracing import start_trace

SERVE = "mtpu/serve/"
TRAIN = "mtpu/train/"
PROMPTS = [[5, 17, 3, 42], [7, 8, 9], [11, 12, 13, 14, 15]]
NEW_TOKENS = 6


def host_events(trace_dir):
    """[(line index, name, start ns, end ns, stats)] of the `mtpu/...`
    spans on the host plane, and every other host event's name. A line is
    a thread; its name is the process's, so lines are told apart by index."""
    files = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    assert len(files) == 1, files
    spans, others = [], set()
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("mtpu/"):
                    spans.append((i, e.name, e.start_ns,
                                  e.start_ns + e.duration_ns,
                                  dict(e.stats)))
                else:
                    others.add(e.name)
    return spans, others


class traced:
    """A profiler session with the Python tracer off round a block."""

    def __init__(self, trace_dir):
        self.dir = str(trace_dir)

    def __enter__(self):
        start_trace(self.dir)
        return self.dir

    def __exit__(self, *exc):
        jax.profiler.stop_trace()


# ---------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny_gen():
    cfg = ModelConfig(num_layers=2, hidden_size=64, num_attention_heads=4,
                      num_kv_heads=2, vocab_size=96, seq_length=64,
                      make_vocab_size_divisible_by=32,
                      compute_dtype="float32").derived()
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    return Generator(params, cfg, eos_id=-1, pad_id=0)


def serve_three(gen, **serving):
    """Three seeded requests through a fresh engine: one alone, then,
    once the loop has gone idle, two together. Returns their tokens."""
    eng = ServingEngine(gen, ServingConfig(num_slots=3, max_queue=8,
                                           max_len=64, **serving))
    try:
        def submit(i):
            return eng.submit(PROMPTS[i], NEW_TOKENS,
                              SamplingOptions(temperature=1.0), seed=i)
        first = submit(0)
        out = [first.result(timeout=120)[0]]
        deadline = time.monotonic() + 30
        while (eng._active.any() or eng.scheduler.depth()) \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.05)         # the loop is in its idle wait by now
        rest = [submit(1), submit(2)]
        out += [r.result(timeout=120)[0] for r in rest]
        return out, [first.id] + [r.id for r in rest]
    finally:
        eng.close()


@pytest.fixture(scope="module")
def plain_run(tiny_gen, tmp_path_factory):
    """The default engine under a session: its spans and its tokens."""
    serve_three(tiny_gen)                        # compile outside the trace
    with traced(tmp_path_factory.mktemp("serve-plain")) as d:
        tokens, rids = serve_three(tiny_gen)
    spans, others = host_events(d)
    return {"spans": spans, "others": others, "tokens": tokens,
            "rids": rids}


def names(spans):
    return {n for _, n, *_ in spans}


def test_plain_engine_writes_every_span_its_path_runs(plain_run):
    want = {SERVE + n for n in (
        "idle_wait", "iteration", "reap", "admit", "prefill", "step",
        "step.upload", "step.dispatch", "step.first", "step.fetch",
        "step.commit", "submit")}
    assert want <= names(plain_run["spans"])
    # every span the engine loop writes is a row of the module's table
    assert all(f"| `{n}` |" in tracing.__doc__
               for n in names(plain_run["spans"]) if n.startswith(SERVE))
    # what this path never enters is not there
    assert not {SERVE + "prefill_chunk", SERVE + "step.draft",
                SERVE + "swap"} & names(plain_run["spans"])


def test_chunked_speculative_engine_writes_chunk_and_draft_spans(
        tiny_gen, tmp_path):
    kw = dict(prefill_chunk=2, speculative_k=2)
    serve_three(tiny_gen, **kw)
    with traced(tmp_path) as d:
        serve_three(tiny_gen, **kw)
    spans, _ = host_events(d)
    assert {SERVE + "prefill_chunk", SERVE + "step.draft"} <= names(spans)
    chunks = [s for s in spans if s[1] == SERVE + "prefill_chunk"]
    # prompts of 4, 3 and 5 tokens in chunks of 2: 2 + 2 + 3 dispatches
    assert len(chunks) == 7
    assert sum(s[4]["tokens"] for s in chunks) == 12
    assert all(s[4]["tokens"] in (1, 2) and "rid" in s[4] for s in chunks)


def test_stats_are_integers_under_their_names(plain_run):
    by = {}
    for _, n, _, _, stats in plain_run["spans"]:
        by.setdefault(n, []).append(stats)
    rids = plain_run["rids"]
    assert sorted(s["rid"] for s in by[SERVE + "submit"]) == sorted(rids)
    assert {s["rid"] for s in by[SERVE + "prefill"]} <= set(rids)
    assert sum(s["n"] for s in by[SERVE + "prefill"]) == 3
    assert all(s["padded"] >= 3 for s in by[SERVE + "prefill"])
    assert sum(s["popped"] for s in by[SERVE + "admit"]) == 3
    assert all({"active", "queued"} <= set(s) for s in by[SERVE + "iteration"])
    assert all(s["K"] == 1 and 1 <= s["active"] <= 3
               for s in by[SERVE + "step"])
    # the first token comes from the prefill, the rest from decode steps
    assert sum(s["tokens"] for s in by[SERVE + "step.commit"]) == \
        3 * NEW_TOKENS


def test_step_and_prefill_spans_lie_inside_an_iteration_on_its_line(
        plain_run):
    spans = plain_run["spans"]
    iterations = [s for s in spans if s[1] == SERVE + "iteration"]
    lines = {s[0] for s in iterations}
    assert len(lines) == 1                      # one engine thread
    inside = [s for s in spans
              if s[1].startswith(SERVE + "step") or s[1] in (
                  SERVE + "prefill", SERVE + "admit", SERVE + "reap")]
    assert inside
    for line, name, t0, t1, _ in inside:
        assert line in lines, name
        assert any(i0 <= t0 and t1 <= i1 for _, _, i0, i1, _ in iterations), \
            name
    # prefill is admit's child, the step.* spans are step's
    for parent, kids in ((SERVE + "admit", (SERVE + "prefill",)),
                         (SERVE + "step", tuple(
                             SERVE + "step." + k for k in (
                                 "upload", "dispatch", "first", "fetch",
                                 "commit")))):
        ps = [s for s in spans if s[1] == parent]
        for _, name, t0, t1, _ in (s for s in spans if s[1] in kids):
            assert any(p0 <= t0 and t1 <= p1 for _, _, p0, p1, _ in ps), name
    # submit is on the caller's thread, idle_wait on the engine's
    assert {s[0] for s in spans if s[1] == SERVE + "submit"}.isdisjoint(lines)
    assert {s[0] for s in spans if s[1] == SERVE + "idle_wait"} == lines


def test_dispatch_fetch_commit_are_in_order_in_every_step(plain_run):
    spans = plain_run["spans"]
    steps = [s for s in spans if s[1] == SERVE + "step"]
    assert steps
    older = [SERVE + "step." + k for k in (
        "upload", "dispatch", "fetch", "commit")]
    with_first = 0
    for _, _, s0, s1, _ in steps:
        kids = sorted((t0, n) for _, n, t0, t1, _ in spans
                      if n.startswith(SERVE + "step.")
                      and s0 <= t0 and t1 <= s1)
        got = [n for _, n in kids]
        # a window that follows a prefill hands the first tokens over
        # between its dispatch and its fetch; the others are as they were
        if SERVE + "step.first" in got:
            with_first += 1
            assert got.index(SERVE + "step.first") == 2
            got.remove(SERVE + "step.first")
        assert got == older
    prefills = sum(1 for s in spans if s[1] == SERVE + "prefill")
    assert 1 <= with_first <= prefills
    assert with_first == sum(1 for s in spans
                             if s[1] == SERVE + "step.first")


def test_tokens_are_the_same_with_and_without_a_session(tiny_gen, plain_run):
    untraced, _ = serve_three(tiny_gen)
    assert untraced == plain_run["tokens"]
    assert all(len(t) == len(p) + NEW_TOKENS
               for t, p in zip(untraced, PROMPTS))


def test_session_holds_no_python_call_events(plain_run):
    # the Python tracer names its events `$file:line function`
    assert not [n for n in plain_run["others"] if n.startswith("$")]


# ---------------------------------------------------------------------
# the operator's capture: PUT /admin {"op": "trace"}
# ---------------------------------------------------------------------
class FakeTokenizer:
    vocab_size = 96
    eod = 0
    bos = 1

    def tokenize(self, text):
        return [2 + (ord(c) % 90) for c in text][:16]

    def detokenize(self, ids):
        return " ".join(str(i) for i in ids)


def test_admin_trace_captures_one_session_at_a_time(tiny_gen, tmp_path):
    from megatron_tpu.inference.server import MegatronServer
    srv = MegatronServer(tiny_gen, FakeTokenizer(),
                         serving=ServingConfig(num_slots=2, max_queue=8,
                                               max_len=64))
    try:
        for bad in ({"op": "trace"}, {"op": "trace", "dir": str(tmp_path),
                                      "seconds": "soon"},
                    {"op": "trace", "dir": str(tmp_path), "seconds": 0}):
            assert srv.handle_admin(bad)[0] == 400
        srv.engine.submit(PROMPTS[0], 2).result(timeout=120)   # compile
        replies = {}

        def capture(name, seconds):
            replies[name] = srv.handle_admin(
                {"op": "trace", "seconds": seconds, "dir": str(tmp_path)})

        first = threading.Thread(target=capture, args=("first", 1.0))
        first.start()
        deadline = time.monotonic() + 30
        while not srv._trace_lock.locked() and time.monotonic() < deadline:
            time.sleep(0.01)
        capture("second", 0.1)             # while the first one runs
        srv.engine.submit(PROMPTS[1], 3).result(timeout=120)
        first.join(timeout=120)
        assert not first.is_alive()
        assert replies["second"][0] == 409
        assert replies["first"] == (200, {"dir": str(tmp_path),
                                          "seconds": 1.0})
        spans, others = host_events(tmp_path)
        assert {SERVE + "submit", SERVE + "iteration",
                SERVE + "step.fetch"} <= names(spans)
        assert not [n for n in others if n.startswith("$")]
        # the cap: a request for an hour is a request for TRACE_MAX_S
        assert MegatronServer.TRACE_MAX_S == 30.0
    finally:
        srv.close()


# ---------------------------------------------------------------------
# training
# ---------------------------------------------------------------------
def train_cfg(train_iters=6, **training):
    model = ModelConfig(num_layers=2, hidden_size=32,
                        num_attention_heads=2, vocab_size=64,
                        seq_length=16).derived()
    return MegatronConfig(
        model=model, optimizer=OptimizerConfig(lr=1e-3),
        training=TrainingConfig(micro_batch_size=1, global_batch_size=2,
                                train_iters=train_iters, log_interval=3,
                                **training),
        data=DataConfig(num_workers=0)).validate(n_devices=1)


def batches():
    i = 0
    while True:
        tokens = jax.random.randint(jax.random.PRNGKey(i), (2, 1, 17), 0, 64)
        yield {"tokens": np.asarray(tokens),
               "loss_mask": np.ones((2, 1, 16), np.float32)}
        i += 1


def run_training(monkeypatch, cfg, **kw):
    """`loop.train` on the tiny model; returns the flushed losses, in
    order, and the number of `_device_fetch` calls."""
    fetched = []
    real = loop_mod._device_fetch

    def fetch(tree):
        out = real(tree)
        fetched.append(out)
        return out

    monkeypatch.setattr(loop_mod, "_device_fetch", fetch)
    loop_mod.train(cfg, batches(), **kw)
    monkeypatch.setattr(loop_mod, "_device_fetch", real)
    losses = [float(m["lm_loss"]) for out in fetched
              if isinstance(out, list) and out and isinstance(out[0], dict)
              for m in out]
    return losses, len(fetched)


def test_training_loop_writes_its_spans_once_per_iteration(
        monkeypatch, tmp_path):
    saves = []
    cfg = train_cfg(train_iters=6, eval_interval=6, eval_iters=1,
                    save_interval=6)
    kw = dict(valid_iterator=batches(),
              save_fn=lambda state, it, consumed: saves.append(it))
    run_training(monkeypatch, train_cfg(train_iters=1))        # compile
    with traced(tmp_path) as d:
        traced_losses, _ = run_training(monkeypatch, cfg, **kw)
    spans, others = host_events(d)
    by = {}
    for s in spans:
        by.setdefault(s[1], []).append(s)
    assert {TRAIN + n for n in ("data_next", "step", "flush", "eval",
                                "save")} <= set(by)
    steps = sorted(by[TRAIN + "step"], key=lambda s: s[2])
    assert [s[4]["step_num"] for s in steps] == list(range(6))
    assert len(by[TRAIN + "data_next"]) == 6
    # flushes: after the first step, at each log boundary (3, 6)
    assert len(by[TRAIN + "flush"]) == 3
    assert len(by[TRAIN + "eval"]) == 1 and len(by[TRAIN + "save"]) == 1
    assert saves == [6]
    assert len({s[0] for s in spans}) == 1          # all on the main thread
    assert not [n for n in others if n.startswith("$")]
    # and the loop computes what it computes with no session
    saves.clear()
    plain_losses, _ = run_training(monkeypatch, cfg, **dict(
        kw, valid_iterator=batches()))
    assert plain_losses == traced_losses and len(plain_losses) == 6


def test_profile_traces_the_asynchronous_loop(monkeypatch, tmp_path):
    """`--profile` no longer implies `sync_metrics`: the loop under the
    profiler fetches once per metrics window like the loop without it,
    and the trace holds one `mtpu/train/step` per traced iteration."""
    plain_losses, plain_fetches = run_training(
        monkeypatch, train_cfg(train_iters=9))
    _, sync_fetches = run_training(
        monkeypatch, train_cfg(train_iters=9, sync_metrics=True))
    cfg = train_cfg(train_iters=9, profile=True, profile_step_start=2,
                    profile_step_end=5, profile_dir=str(tmp_path))
    assert cfg.training.sync_metrics is False
    losses, fetches = run_training(monkeypatch, cfg)
    assert fetches == plain_fetches < sync_fetches == 9
    assert losses == plain_losses
    spans, others = host_events(tmp_path)
    steps = sorted(s[4]["step_num"] for s in spans if s[1] == TRAIN + "step")
    assert steps == [2, 3, 4, 5]
    assert not [n for n in others if n.startswith("$")]
