"""A request's own record (`megatron_tpu/utils/tracing.py::RequestRow`): one
row a request and exactly one, filled where the engine does the work, closed
where the request ends, kept in the metrics' ring and handed out by
`tracing.request_record()`, a closed engine's too.

Engines are driven by hand where the schedule matters (`start=False`,
`_iteration()` on the test's thread, a prompt "lands while the window runs"
by being submitted from inside the `_fetch` seam, as in
tests/test_early_admit.py). No assertion is on a CPU timing's size: only
order, counts and sums.
"""
import json
import time

import jax
import numpy as np
import pytest

from megatron_tpu.config import ModelConfig, ServingConfig
from megatron_tpu.inference import Generator
from megatron_tpu.models import language_model as lm
from megatron_tpu.serving import (AdmissionError, SamplingOptions,
                                  ServingEngine, ServingMetrics)
from megatron_tpu.serving.metrics import _percentile
from megatron_tpu.utils import tracing
from tests.test_early_admit import Driven as _Driven

GREEDY = SamplingOptions(temperature=0.0)
P4, Q3, R5 = [5, 17, 3, 42], [7, 8, 9], [11, 12, 13, 14, 15]
OUTCOMES = ("completed", "failed", "cancelled", "expired")
TAKEN_S = 60.0


@pytest.fixture(scope="module")
def gen():
    cfg = ModelConfig(num_layers=2, hidden_size=64, num_attention_heads=4,
                      num_kv_heads=2, vocab_size=96, seq_length=64,
                      make_vocab_size_divisible_by=32,
                      compute_dtype="float32").derived()
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    return Generator(params, cfg, eos_id=-1, pad_id=0)


def rows_of(eng):
    """{rid: [rows]} of one engine, from the process's record."""
    out = {}
    for row in tracing.request_record():
        if row.engine == eng.engine_id:
            out.setdefault(row.rid, []).append(row)
    return out


def the_row(eng, req):
    (row,) = rows_of(eng)[req.id]
    return row


class Driven(_Driven):
    """tests/test_early_admit.py's hand-driven engine (its `_fetch` seam
    runs `during[k]` inside the k-th window's fetch, before the tokens are
    fetched), which also notes when each fetch returned."""

    def __init__(self, gen, **serving):
        super().__init__(gen, **serving)
        self.fetched_at = []
        seam = self.eng._fetch

        def fetch(tree):
            out = seam(tree)
            self.fetched_at.append(time.monotonic())
            return out
        self.eng._fetch = fetch

    def run(self, reqs, n=None, limit=120):
        super().run(reqs, len(reqs) if n is None else n, limit)


# ---------------------------------------------------------------------
# one row a request, whatever its end
# ---------------------------------------------------------------------
@pytest.fixture(scope="module")
def ended(gen):
    """One engine that saw each of the four ends `_count_terminal` sees,
    a request that wanted no token, and one refused at submit."""
    d = Driven(gen, num_slots=2, max_queue=8)
    eng = d.eng
    reqs = {"completed": eng.submit(P4, 5, GREEDY),
            "no_tokens": eng.submit(Q3, 0, GREEDY),
            "cancelled": eng.submit(R5, 5, GREEDY),
            "expired": eng.submit(Q3, 5, GREEDY, deadline_s=0.001)}
    eng.cancel(reqs["cancelled"])
    with pytest.raises(AdmissionError):
        eng.submit(list(range(1, 80)), 5, GREEDY)      # never fits
    time.sleep(0.01)
    d.run([reqs["completed"], reqs["expired"]])
    reqs["failed"] = eng.submit(R5, 5, GREEDY)         # queued at the close
    eng.close()
    return {"eng": eng, "reqs": reqs, "snap": eng.metrics.snapshot(),
            "rows": rows_of(eng)}


@pytest.mark.parametrize("outcome", OUTCOMES)
def test_each_end_closes_exactly_one_row(ended, outcome):
    req = ended["reqs"][outcome]
    (row,) = ended["rows"][req.id]
    assert row.outcome == outcome
    assert row.engine == ended["eng"].engine_id and row.seq is not None
    assert row.prompt_tokens == len(req.prompt)
    assert row.generated == len(req.generated)
    assert (row.t_submit, row.t_finish) == (req.submit_time, req.finish_time)
    if outcome == "completed":
        assert row.generated == 5 and row.programs == 1
        assert row.t_first == req.first_token_time
    else:
        # never admitted: the stamps that could not be taken are None and
        # the row is written all the same, at the request's end
        assert row.t_admit is row.t_device is row.t_first is None
        assert row.programs == row.generated == 0
        assert row.segments()["queue_s"] is None


def test_a_request_for_no_token_is_admitted_and_ends_at_once(ended):
    req = ended["reqs"]["no_tokens"]
    (row,) = ended["rows"][req.id]
    assert row.outcome == "completed" and row.t_first is None
    assert row.t_admit == row.t_device == req.admit_time
    assert row.programs == 0


def test_rows_written_are_the_requests_the_engine_took_in(ended):
    """Beside serving/invariants.py's conservation law: a request refused
    at submit is counted there and never reaches the engine, so it gets
    no row; every other one gets exactly one."""
    snap, eng = ended["snap"], ended["eng"]
    assert snap["requests_rejected"] == 1
    assert eng.metrics.requests.written \
        == snap["requests_received"] - snap["requests_rejected"] == 5
    assert eng.metrics.requests.written == sum(
        snap["requests_" + o] for o in OUTCOMES)
    assert sorted(len(v) for v in ended["rows"].values()) == [1] * 5
    assert sorted(r[0].seq for r in ended["rows"].values()) == list(range(5))


def test_a_closed_engine_still_answers_and_hands_out_copies(ended):
    req = ended["reqs"]["completed"]
    row = the_row(ended["eng"], req)
    row.outcome, row.t_first = "tampered", -1.0
    again = the_row(ended["eng"], req)
    assert again.outcome == "completed"
    assert again.t_first == req.first_token_time
    assert again.as_dict()["rid"] == req.id
    json.dumps(again.as_dict())                       # plain numbers


# ---------------------------------------------------------------------
# the stamps and the segments
# ---------------------------------------------------------------------
def test_stamps_are_in_order_and_three_segments_tile_the_first_token(gen):
    eng = ServingEngine(gen, ServingConfig(num_slots=2, max_queue=16,
                                           max_len=64))
    try:
        reqs = [eng.submit(p, 6, SamplingOptions(temperature=1.0), seed=i)
                for i, p in enumerate([P4, Q3, R5, P4, Q3])]
        for r in reqs:
            r.result(timeout=120)
    finally:
        eng.close()
    for req in reqs:
        row = the_row(eng, req)
        assert row.outcome == "completed" and row.generated == 6
        assert row.t_submit <= row.t_admit <= row.t_device \
            <= row.t_first <= row.t_finish
        seg = row.segments()
        assert seg["queue_s"] + seg["behind_window_s"] + seg["prefill_s"] \
            == pytest.approx(row.t_first - row.t_submit, abs=1e-6)
        assert seg["decode_s"] == row.t_finish - row.t_first
        assert row.programs == 1 and row.rows >= row.prompt_tokens
        assert row.windows_between == 0


def test_a_prompt_admitted_inside_a_window_waits_for_that_windows_fetch(gen):
    d = Driven(gen)
    try:
        reqs = [d.eng.submit(P4, 9, GREEDY)]

        def lands():
            reqs.append(d.eng.submit(Q3, 4, GREEDY))
            assert d.slotted(reqs[-1], TAKEN_S)
        d.during[2] = lands
        d.run(reqs, 2)
    finally:
        d.eng.close()
    first, early = (the_row(d.eng, r) for r in reqs)
    assert (first.early, early.early) == (0, 1)
    assert first.t_device == first.t_admit        # nothing was in flight
    # admitted while window 2 ran; the device is its own when that
    # window's fetch has returned
    assert early.t_admit < d.fetched_at[1] <= early.t_device < early.t_first
    assert early.segments()["behind_window_s"] > 0
    assert (early.programs, early.ahead_programs, early.windows_between) \
        == (1, 0, 0)
    assert d.eng.metrics.snapshot()["admits_early"] == 1


def test_a_prompt_behind_anothers_chunks_and_one_the_rule_held_back(gen):
    """Two long prompts and `prefill_chunk`: the second's chunks wait for
    the first's, one a window. A short prompt that lands while a chunk is
    owed the next program is held back by the one-program rule."""
    d = Driven(gen, prefill_chunk=8)
    rs = np.random.RandomState(3)
    try:
        reqs = [d.eng.submit(P4, 16, GREEDY)]
        d.eng._iteration()                             # window 1
        first = d.eng.submit(rs.randint(1, 96, 20).tolist(), 4, GREEDY)
        second = d.eng.submit(rs.randint(1, 96, 18).tolist(), 4, GREEDY)
        reqs += [first, second]

        def lands():
            assert d.eng._prefilling              # a chunk is owed
            reqs.append(d.eng.submit(Q3, 4, GREEDY))
        d.during[2] = lands
        d.run(reqs, 4)
    finally:
        d.eng.close()
    row1, row2, short = (the_row(d.eng, r) for r in reqs[1:])
    # a chunked prompt's programs are its chunks, a window between two
    assert row1.programs == first.prefill_chunks == 3
    assert row2.programs == second.prefill_chunks == 3
    assert row1.rows == row2.rows == 3 * 8
    assert row1.windows_between >= row1.programs - 1
    # the second waited while the first's chunks ran
    assert row2.ahead_programs >= row1.programs
    assert row2.ahead_rows >= row1.rows
    assert row2.t_first > row1.t_first
    # the short prompt: held back once, then placed by the iteration,
    # with the long prompts' chunks between it and its first token
    assert (short.held, short.early) == (1, 0)
    assert row1.held == row2.held == 0
    snap = d.eng.metrics.snapshot()
    assert snap["early_admit_declined_prefilling"] == short.held
    assert short.programs == 1
    assert snap["prefill_chunks"] == row1.programs + row2.programs


def test_prefix_hit_tokens_are_the_counters_by_request(gen):
    d = Driven(gen, enable_prefix_cache=True, prefill_bucket=8)
    prompt = list(range(1, 20))
    try:
        a = d.eng.submit(prompt, 3, GREEDY)
        d.run([a])
        b = d.eng.submit(prompt, 3, GREEDY)
        d.run([b])
    finally:
        d.eng.close()
    row_a, row_b = the_row(d.eng, a), the_row(d.eng, b)
    assert row_a.prefix_hit_tokens == 0 and row_b.prefix_hit_tokens > 0
    assert d.eng.metrics.snapshot()["prefix_hit_tokens"] \
        == row_b.prefix_hit_tokens == b.prefix_len


# ---------------------------------------------------------------------
# the ring and the process's record
# ---------------------------------------------------------------------
def test_the_ring_never_exceeds_its_length(gen):
    eng = ServingEngine(gen, ServingConfig(num_slots=4, max_queue=16,
                                           max_len=64),
                        metrics=ServingMetrics(max_samples=4))
    try:
        for p in (P4, Q3, R5) * 2:
            eng.submit(p, 2, GREEDY).result(timeout=120)
    finally:
        eng.close()
    ring = eng.metrics.requests
    assert ring.written == 6 and len(ring.rows) == 4
    kept = rows_of(eng)
    assert sorted(r[0].seq for r in kept.values()) == [2, 3, 4, 5]
    assert all(r[0].outcome == "completed" for r in kept.values())
    # the percentiles read what the ring holds
    assert eng.metrics.snapshot()["ttft_p50_ms"] > 0
    assert tracing.RequestRing().rows.maxlen == tracing.MAX_REQUEST_ROWS \
        == 4096


def test_the_process_keeps_the_last_few_engines_rings(monkeypatch):
    monkeypatch.setattr(tracing, "_rings",
                        type(tracing._rings)(maxlen=tracing.MAX_ENGINES))
    rings = [tracing.RequestRing() for _ in range(tracing.MAX_ENGINES + 1)]
    ids = [tracing.keep_requests(ring) for ring in rings]
    assert ids == sorted(set(ids)) and all(i > 0 for i in ids)
    assert tracing.keep_requests(rings[-1]) == ids[-1]      # registers once
    for ring in rings:
        row = tracing.RequestRow(7, 1.0)
        ring.keep(row)
        ring.keep(row)                                      # and a row once
        assert ring.written == 1
    assert [r.engine for r in tracing.request_record()] == ids[1:]


# ---------------------------------------------------------------------
# /metrics reads the same, and the operator's op
# ---------------------------------------------------------------------
def test_metrics_percentiles_read_as_the_three_deques_did(gen):
    """The parent kept three lists of samples: `first_token_time -
    submit_time` at each first token, `admit_time - submit_time` at each
    first admission, `finish_time - submit_time` at each completion, and
    read nearest-rank percentiles off them. The same off the rows, on a
    scripted sequence with a cancellation in it."""
    d = Driven(gen, num_slots=2)
    try:
        reqs = [d.eng.submit(p, n, GREEDY)
                for p, n in ((P4, 8), (Q3, 3), (R5, 12), (P4, 2), (Q3, 5))]
        for _ in range(4):
            d.eng._iteration()
        d.eng.cancel(reqs[2])                 # running: a first token, no end
        d.run(reqs)
        snap = d.eng.metrics.snapshot()
    finally:
        d.eng.close()
    assert reqs[2].error is not None and reqs[2].first_token_time is not None
    ttft = sorted(r.first_token_time - r.submit_time for r in reqs)
    qwait = sorted(r.admit_time - r.submit_time for r in reqs)
    lat = sorted(r.finish_time - r.submit_time for r in reqs
                 if r.error is None)
    assert len(lat) == 4
    want = {"ttft_p50_ms": (ttft, 0.50), "ttft_p95_ms": (ttft, 0.95),
            "queue_wait_p50_ms": (qwait, 0.50),
            "queue_wait_p95_ms": (qwait, 0.95),
            "queue_wait_p99_ms": (qwait, 0.99),
            "latency_p50_ms": (lat, 0.50), "latency_p95_ms": (lat, 0.95)}
    for key, (xs, q) in want.items():
        assert snap[key] == _percentile(xs, q) * 1e3, key
    assert snap["requests_admitted"] == 5
    # and a fresh registry reads zeros under the same keys
    fresh = ServingMetrics().snapshot()
    assert all(fresh[key] == 0.0 for key in want)


class FakeTokenizer:
    vocab_size = 96
    eod = 0
    bos = 1

    def tokenize(self, text):
        return [2 + (ord(c) % 90) for c in text][:16]

    def detokenize(self, ids):
        return " ".join(str(i) for i in ids)


def test_admin_requests_answers_the_newest_rows_slowest_first(gen):
    from megatron_tpu.inference.server import MegatronServer
    srv = MegatronServer(gen, FakeTokenizer(),
                         serving=ServingConfig(num_slots=2, max_queue=8,
                                               max_len=64))
    try:
        for bad in ({"op": "requests", "n": 0},
                    {"op": "requests", "n": "many"}):
            assert srv.handle_admin(bad)[0] == 400
        reqs = [srv.engine.submit(p, 3, GREEDY) for p in (P4, Q3, R5)]
        for r in reqs:
            r.result(timeout=120)
        t0 = time.monotonic()
        code, reply = srv.handle_admin({"op": "requests", "n": 2})
        assert code == 200 and reply["now"] >= t0
        rows = json.loads(json.dumps(reply))["requests"]
        assert len(rows) == 2
        # the newest two by submission, whatever else the process served
        assert {r["rid"] for r in rows} == {reqs[1].id, reqs[2].id}
        firsts = [r["t_first"] - r["t_submit"] for r in rows]
        assert firsts == sorted(firsts, reverse=True)
        for r in rows:
            assert r["outcome"] == "completed" and r["generated"] == 3
            assert r["queue_s"] + r["behind_window_s"] + r["prefill_s"] \
                == pytest.approx(r["t_first"] - r["t_submit"], abs=1e-6)
        assert len(srv.handle_admin({"op": "requests"})[1]["requests"]) >= 3
    finally:
        srv.close()


def test_the_docstring_names_every_field_of_the_row():
    for field in tracing.RequestRow.__slots__:
        assert f"`{field}`" in tracing.__doc__, field
