"""A prompt that lands while a decode window runs (serving/engine.py
`_fetch_admitting`, `_admit_early`).

The contracts:
- it is in a slot, its prefill dispatched, before the window's fetch
  returns, and its tokens and log-probabilities are what the ordinary
  path (admitted by the next iteration) gives the same request in the
  same slot under the same seed;
- it is the iteration's ONE prefill program: never beside a chunk, never
  two in a window, and the iteration after it skips `_advance_prefill`
  once;
- the order of admissions, early and ordinary together, is `pop_ready`'s;
- a pending swap, a drain, a flagged session, a verify window and a
  window with a grammar row each leave it to the iteration;
- an exception in the early dispatch fails what was popped and nothing
  else, releases the adapter pins, and is raised once the window that was
  running is committed;
- `admits_early`, `admits_total`, `early_admit_declined_prefilling` count
  what happened.

Every engine here is driven by hand (`start=False`, `_iteration()` on the
test's thread) so the schedule of arrivals is fixed: a prompt "lands while
the window runs" by being submitted from inside the `_fetch` seam.
"""
import sys
import threading
import time

import jax
import numpy as np
import pytest

from megatron_tpu.config import ModelConfig, ServingConfig
from megatron_tpu.inference import Generator, SamplingParams
from megatron_tpu.models import language_model as lm
from megatron_tpu.serving import (RequestState, SamplingOptions,
                                  ServingEngine)
from megatron_tpu.serving import engine as engine_mod

GREEDY = SamplingOptions(temperature=0.0)
DRAWN = SamplingOptions(temperature=0.9, top_k=5)
P4, Q3, R5 = [5, 17, 3, 42], [7, 8, 9], [11, 12, 13, 14, 15]
NOT_TAKEN_S = 0.15      # how long a hook waits to see nothing happen
TAKEN_S = 60.0          # and at most for what should (it may compile)


@pytest.fixture(scope="module")
def gen():
    cfg = ModelConfig(num_layers=2, hidden_size=64, num_attention_heads=4,
                      num_kv_heads=2, vocab_size=96, seq_length=64,
                      make_vocab_size_divisible_by=32,
                      compute_dtype="float32").derived()
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    return Generator(params, cfg, eos_id=-1, pad_id=0)


def serial(gen, prompt, n):
    """The serial path's greedy continuation of `prompt`."""
    toks, lens, _ = gen.generate(
        [prompt], n, sampling=SamplingParams(temperature=0.0))
    return toks[0, len(prompt):lens[0]].tolist()


class Driven:
    """A hand-driven engine whose `_fetch` seam runs `during[k]` inside
    the k-th window's fetch, before the tokens are fetched. `early=False`
    is the ordinary path on the same schedule: the early admission never
    takes anything."""

    def __init__(self, gen, early=True, **serving):
        serving = dict(dict(num_slots=4, max_queue=16, max_len=64),
                       **serving)
        self.eng = ServingEngine(gen, ServingConfig(**serving), start=False)
        if not early:
            self.eng._admit_early = lambda: None
        self.windows = 0
        self.during = {}
        self.slots = {}       # request id -> the slot it was seen in
        real = self.eng._fetch

        def fetch(tree):
            self.windows += 1
            hook = self.during.pop(self.windows, None)
            if hook is not None:
                hook()
            return real(tree)
        self.eng._fetch = fetch

    def slotted(self, req, timeout):
        """Whether `req` reaches a slot within `timeout` seconds."""
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout:
            if req in self.eng._slot_req:
                return True
            time.sleep(0.001)
        return req in self.eng._slot_req

    def run(self, reqs, n, limit=80):
        """Iterate until `reqs` holds `n` requests and all are done."""
        for _ in range(limit):
            self.eng._iteration()
            for slot, r in enumerate(self.eng._slot_req):
                if r is not None:
                    self.slots.setdefault(r.id, slot)
            if len(reqs) == n and all(r.done() for r in reqs):
                return
        raise AssertionError("the engine did not finish")

    def snap(self):
        return self.eng.metrics.snapshot()


class Spans:
    """Stands in for `tracing.span` in the engine's module: the names and
    stats of the spans in the order they begin."""

    def __init__(self, monkeypatch):
        self.rows = []
        monkeypatch.setattr(engine_mod, "span", self)

    def __call__(self, name, **stats):
        row = (name, dict(stats))
        if name != "serve/submit":          # the caller's thread
            self.rows.append(row)
        return _Span(row)

    def between(self, name):
        """The rows split at every begin of `name`."""
        parts, cur = [], []
        for row in self.rows:
            if row[0] == name:
                parts.append(cur)
                cur = []
            cur.append(row)
        return parts + [cur]


class _Span:
    def __init__(self, row):
        self.row = row

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **stats):
        self.row[1].update(stats)


# ---------------------------------------------------------------------
# (a) in a slot before the fetch returns; the ordinary path's tokens
# ---------------------------------------------------------------------
@pytest.mark.parametrize("serving", [
    {}, dict(decode_sync_interval=2), dict(kv_block_size=16),
    dict(prefill_chunk=8)], ids=["plain", "sync2", "blocks", "chunked"])
def test_admitted_before_the_fetch_returns_with_the_ordinary_tokens(
        gen, serving):
    arms = {}
    for early in (True, False):
        d = Driven(gen, early=early, **serving)
        try:
            seen, reqs = {}, [d.eng.submit(P4, 9, DRAWN, seed=1)]

            def lands():
                with d.eng._cond:       # both are queued as it wakes
                    reqs.append(d.eng.submit(Q3, 6, DRAWN, seed=4))
                    reqs.append(d.eng.submit(R5, 5, GREEDY, seed=0))
                seen["slotted"] = [
                    d.slotted(r, TAKEN_S if early else NOT_TAKEN_S)
                    for r in reqs[1:]]
                seen["prefill_calls"] = d.snap()["prefill_calls"]
                seen["states"] = [r.state for r in reqs[1:]]
            d.during[2] = lands
            d.run(reqs, 3)
            arms[early] = (d, reqs, dict(seen))
        finally:
            d.eng.close()
    (d, reqs, seen), (ref_d, ref_reqs, ref_seen) = arms[True], arms[False]
    # in a slot, RUNNING, its prefill dispatched, while the seam held the
    # window's tokens back
    assert seen == {"slotted": [True] * 2, "prefill_calls": 2,
                    "states": [RequestState.RUNNING] * 2}
    assert ref_seen == {"slotted": [False] * 2, "prefill_calls": 1,
                        "states": [RequestState.QUEUED] * 2}
    for r, ref in zip(reqs, ref_reqs):
        assert r.error is None and ref.error is None
        assert r.generated == ref.generated
        assert r.gen_logprobs == ref.gen_logprobs       # bit for bit
        assert d.slots[r.id] == ref_d.slots[ref.id]
    snap, ref_snap = d.snap(), ref_d.snap()
    assert snap["first_token_mismatches"] == 0
    assert snap["first_tokens_early"] == 3
    # both went early as ONE program, the group `_admit` makes of them
    assert (snap["admits_early"], snap["admits_total"]) == (2, 3)
    assert snap["prefill_calls"] == ref_snap["prefill_calls"] == 2
    assert (ref_snap["admits_early"], ref_snap["admits_total"]) == (0, 3)
    for name in ("tokens_generated", "decode_steps", "host_syncs",
                 "requests_completed", "prefill_prompts"):
        assert snap[name] == ref_snap[name], name


def test_a_started_engine_admits_inside_the_window(gen):
    """The engine's own thread, a blocked fetch, a caller's submit."""
    eng = ServingEngine(gen, ServingConfig(num_slots=3, max_queue=16,
                                           max_len=64))
    try:
        real, held = eng._fetch, []

        def fetch(tree):
            if held and held[0] is None:
                held[0] = eng.submit(Q3, 4, GREEDY, seed=0)
                t0 = time.monotonic()
                while (held[0] not in eng._slot_req
                       and time.monotonic() - t0 < TAKEN_S):
                    time.sleep(0.001)
                held.append(held[0] in eng._slot_req)
            return real(tree)
        eng._fetch = fetch
        first = eng.submit(P4, 12, GREEDY, seed=0)
        assert first.wait_token(1, timeout=300)
        held.append(None)
        first.result(timeout=300)
        held[0].result(timeout=300)
        assert held[1] is True
        assert held[0].generated == serial(gen, Q3, 4)
        assert eng.metrics.snapshot()["admits_early"] >= 1
    finally:
        eng.close()


def test_many_callers_under_a_short_switch_interval(gen):
    """More submitting threads than cores against the engine's own thread
    and its fetcher: every stream is the serial path's, whichever way it
    was admitted, and every admission is counted once."""
    prompts = [[5 + i, 17, 3 + (i % 7), 42][:2 + i % 3] for i in range(24)]
    want = {tuple(p): serial(gen, p, 5) for p in prompts}
    eng = ServingEngine(gen, ServingConfig(num_slots=4, max_queue=64,
                                           max_len=64))
    interval, got, errors = sys.getswitchinterval(), {}, []

    def caller(k):
        try:
            for p in prompts[k::8]:
                req = eng.submit(p, 5, GREEDY, seed=0)
                req.result(timeout=300)
                got[req.id] = (tuple(p), req.generated)
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(repr(e))
    try:
        eng.generate(P4, 2, GREEDY)               # the programs compile
        sys.setswitchinterval(1e-5)
        threads = [threading.Thread(target=caller, args=(k,))
                   for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        assert not any(t.is_alive() for t in threads) and not errors, errors
        snap = eng.metrics.snapshot()
    finally:
        sys.setswitchinterval(interval)
        eng.close()
    assert len(got) == len(prompts)
    for prompt, tokens in got.values():
        assert tokens == want[prompt]
    assert snap["first_token_mismatches"] == 0
    assert snap["admits_total"] == snap["requests_admitted"] == 25
    assert 1 <= snap["admits_early"] <= 24


# ---------------------------------------------------------------------
# (b) one prefill program between two windows
# ---------------------------------------------------------------------
def test_one_early_admission_a_window(gen):
    d = Driven(gen)
    try:
        seen, reqs = {}, [d.eng.submit(P4, 9, GREEDY, seed=0)]

        def lands():
            reqs.append(d.eng.submit(Q3, 4, GREEDY, seed=0))
            seen["first"] = d.slotted(reqs[-1], TAKEN_S)
            reqs.append(d.eng.submit(R5, 4, GREEDY, seed=0))
            seen["second"] = d.slotted(reqs[-1], NOT_TAKEN_S)
        d.during[2] = lands
        d.run(reqs, 3)
        assert seen == {"first": True, "second": False}
        snap = d.snap()
        assert (snap["admits_early"], snap["admits_total"]) == (1, 3)
        for r in reqs:
            assert r.generated == serial(gen, r.prompt, r.max_new_tokens)
    finally:
        d.eng.close()


def test_a_pending_chunk_keeps_the_prompt_for_the_iteration(gen):
    d = Driven(gen, prefill_chunk=8)
    try:
        rs = np.random.RandomState(3)
        long_prompt = rs.randint(1, 96, 20).tolist()      # three chunks
        seen, reqs = {}, [d.eng.submit(P4, 12, GREEDY, seed=0)]

        def lands():
            seen["owed"] = len(d.eng._prefilling)
            reqs.append(d.eng.submit(Q3, 4, GREEDY, seed=0))
            seen["slotted"] = d.slotted(reqs[-1], NOT_TAKEN_S)
        d.eng._iteration()                                # window 1
        reqs.append(d.eng.submit(long_prompt, 4, GREEDY, seed=0))
        d.during[2] = lands
        d.run(reqs, 3)
        assert seen == {"owed": 1, "slotted": False}
        snap = d.snap()
        assert snap["early_admit_declined_prefilling"] == 1
        assert snap["admits_early"] == 0 and snap["admits_total"] == 3
        assert reqs[-1].generated == serial(gen, Q3, 4)
    finally:
        d.eng.close()


@pytest.mark.parametrize("chunk", [8, None], ids=["chunked", "unchunked"])
def test_no_window_has_an_early_program_beside_a_chunk(gen, chunk,
                                                       monkeypatch):
    """Long and short prompts landing in every window of a run: between
    two windows' dispatches the device is given at most one chunk or one
    early program, never both, and between two `step.commit` starts no
    early `serve/prefill` lies beside a `prefill_chunk`."""
    spans = Spans(monkeypatch)
    d = Driven(gen, num_slots=6, prefill_chunk=chunk, prefill_max_batch=2)
    try:
        rs = np.random.RandomState(7)
        lengths = [4, 20, 3, 5, 33, 4, 18, 6, 3, 20, 5, 4]
        reqs = [d.eng.submit(P4, 24, GREEDY, seed=0)]

        def lander(n):
            def lands():
                reqs.append(d.eng.submit(rs.randint(1, 96, n).tolist(), 6,
                                         DRAWN, seed=n))
                d.slotted(reqs[-1], 0.05)
            return lands
        for k, n in enumerate(lengths):
            d.during[k + 1] = lander(n)
        d.run(reqs, 1 + len(lengths), limit=200)
    finally:
        d.eng.close()
    assert all(r.error is None for r in reqs)
    snap = d.snap()
    n_early = snap["admits_early"]
    assert n_early >= (1 if chunk else 3)
    assert snap["first_token_mismatches"] == 0
    if chunk is None:
        assert snap["early_admit_declined_prefilling"] == 0
    else:
        assert snap["early_admit_declined_prefilling"] >= 1
    early_markers = 0
    for part in spans.between("serve/step.commit"):
        names = [n for n, _ in part]
        early = [s for n, s in part
                 if n == "serve/prefill" and s.get("early")]
        early_markers += len(early)
        assert len(early) <= 1
        assert not (early and "serve/prefill_chunk" in names), part
    assert early_markers == n_early == sum(
        1 for n, _ in spans.rows if n == "serve/prefill.early")
    # the device's own order: what is dispatched between two windows
    for part in spans.between("serve/step.dispatch"):
        names = [n for n, _ in part]
        assert (names.count("serve/prefill.early")
                + names.count("serve/prefill_chunk")) <= 1, part
    # the early dispatch is inside its window's step, after the dispatch
    for i, (name, stats) in enumerate(spans.rows):
        if name == "serve/prefill.early":
            assert spans.rows[i - 1][0] == "serve/admit"
            assert spans.rows[i - 1][1]["early"] == 1
            assert spans.rows[i - 2][0] == "serve/step.fetch"


# ---------------------------------------------------------------------
# (c) the order of admissions is pop_ready's
# ---------------------------------------------------------------------
def test_order_across_early_and_ordinary_admissions_is_pop_readys(gen):
    orders = {}
    for early in (True, False):
        d = Driven(gen, early=early, num_slots=2, prefill_max_batch=1,
                   priority_levels=3)
        try:
            seen, names = {}, ["first"]
            reqs = [d.eng.submit(P4, 3, GREEDY, seed=0)]

            def lands():
                # one slot is free: what is taken now is the queue's head
                with d.eng._cond:
                    for name, prompt, kw in (
                            ("low", Q3, dict(priority=0)),
                            ("soon", R5, dict(priority=1, deadline_s=50.0)),
                            ("high", P4, dict(priority=2)),
                            ("later", Q3, dict(priority=1,
                                               deadline_s=500.0))):
                        names.append(name)
                        reqs.append(d.eng.submit(prompt, 2, GREEDY, **kw))
                seen["high"] = d.slotted(reqs[3],
                                         TAKEN_S if early else NOT_TAKEN_S)
            d.during[2] = lands
            d.run(reqs, 5)
            assert all(r.error is None for r in reqs)
            order = sorted(range(5), key=lambda i: reqs[i].admit_time)
            orders[early] = (seen["high"], [names[i] for i in order])
        finally:
            d.eng.close()
    want = ["first", "high", "soon", "later", "low"]
    assert orders == {True: (True, want), False: (False, want)}


# ---------------------------------------------------------------------
# (d) what declines it
# ---------------------------------------------------------------------
def _swap(eng):
    eng._pending_swap = engine_mod._SwapTicket(None)
    return lambda: setattr(eng, "_pending_swap", None)


def _drain(eng):
    eng._draining = True
    return lambda: setattr(eng, "_draining", False)


def _wedge(eng):
    eng._wedged = True
    return lambda: setattr(eng, "_wedged", False)


@pytest.mark.parametrize("case", ["swap", "drain", "wedged", "verify",
                                  "grammar", "no_slot", "not_declined"])
def test_what_declines_an_early_admission(gen, case):
    serving, first_kw, flag = {}, {}, None
    prompt = P4
    if case == "verify":
        serving = dict(speculative_k=2)
        prompt = [5, 6, 7, 5, 6, 7, 5, 6]       # drafts from its repeats
    elif case == "grammar":
        first_kw = dict(response_format={"type": "regex",
                                         "pattern": "[0-9]{2,30}"})
    elif case == "no_slot":
        serving = dict(num_slots=1)
    else:
        flag = {"swap": _swap, "drain": _drain, "wedged": _wedge}.get(case)
    d = Driven(gen, **serving)
    try:
        seen = {}
        reqs = [d.eng.submit(prompt, 10, GREEDY, seed=0, **first_kw)]

        def lands():
            # the request is queued before the flag is up (`submit`
            # refuses a draining engine), the engine wakes for it after
            with d.eng._cond:
                reqs.append(d.eng.submit(Q3, 3, GREEDY, seed=0))
                lower = flag(d.eng) if flag else None
            seen["slotted"] = d.slotted(reqs[-1], NOT_TAKEN_S)
            if lower:
                lower()
        d.during[2] = lands
        d.run(reqs, 2)
        first, late = reqs[0], reqs[1:]
        assert seen == {"slotted": case == "not_declined"}
        snap = d.snap()
        assert snap["admits_early"] == (case == "not_declined")
        assert snap["admits_total"] == 2
        assert snap["early_admit_declined_prefilling"] == 0
        if case == "verify":
            assert snap["spec_rounds"] >= 1
        assert late[0].generated == serial(gen, Q3, 3)
        assert first.error is None
    finally:
        d.eng.close()


def test_a_prompt_longer_than_the_chunk_goes_back_as_it_came(gen):
    """Nothing owed, a slot free, but the queue's head is no group's:
    it is popped and sent back, and the iteration places it."""
    d = Driven(gen, prefill_chunk=8)
    try:
        seen, reqs = {}, [d.eng.submit(P4, 10, GREEDY, seed=0)]
        long_prompt = np.random.RandomState(5).randint(1, 96, 20).tolist()

        def lands():
            with d.eng._cond:
                reqs.append(d.eng.submit(long_prompt, 3, GREEDY, seed=0))
                reqs.append(d.eng.submit(Q3, 3, GREEDY, seed=0))
            seen["slotted"] = [d.slotted(r, NOT_TAKEN_S) for r in reqs[1:]]
            seen["depth"] = d.eng.scheduler.depth()
        d.during[2] = lands
        d.run(reqs, 3)
        late = reqs[1:]
        assert seen == {"slotted": [False, False], "depth": 2}
        assert late[0].admit_time < late[1].admit_time
        assert all(r.error is None for r in late)
        assert d.snap()["admits_early"] == 0
    finally:
        d.eng.close()


# ---------------------------------------------------------------------
# (e) an exception in the early dispatch
# ---------------------------------------------------------------------
def test_a_failed_early_dispatch_fails_what_it_popped_and_no_more(gen):
    from megatron_tpu.serving.adapters import random_adapter_factors
    d = Driven(gen, prefill_max_batch=1, adapter_slots=2, adapter_rank=4)
    eng = d.eng
    try:
        eng.register_adapter("tenant", rank=4, alpha=8.0,
                             factors=random_adapter_factors(gen.cfg, 4, 11))
        seen, late = {}, []
        real = eng._prefill_group

        def failing(reqs, padded):
            if late and reqs[0] is late[0]:
                seen["pins_inside"] = int(eng.adapters._pins.sum())
                raise RuntimeError("planted: the early dispatch")
            return real(reqs, padded)
        eng._prefill_group = failing

        def lands():
            with eng._cond:
                late.append(eng.submit(Q3, 3, GREEDY, seed=0,
                                       adapter_id="tenant"))
                late.append(eng.submit(R5, 3, GREEDY, seed=0))
            t0 = time.monotonic()
            while not late[0].done() and time.monotonic() - t0 < TAKEN_S:
                time.sleep(0.001)
            seen["failed_inside"] = late[0].done()
        d.during[2] = lands
        first = eng.submit(P4, 10, GREEDY, seed=0)
        eng._iteration()
        tokens = len(first.generated)
        # raised as the iteration's own `_admit` would raise it, once the
        # window that was running has given its rows their tokens
        with pytest.raises(RuntimeError, match="planted"):
            eng._iteration()
        assert len(first.generated) == tokens + 1
        assert seen == {"pins_inside": 1, "failed_inside": True}
        assert "planted" in late[0].error
        assert int(eng.adapters._pins.sum()) == 0         # pin released
        assert eng._admitting == []
        assert late[1].state is RequestState.QUEUED       # never popped
        assert eng._early_program is None
        snap = d.snap()
        assert snap["admits_early"] == 0 and snap["admits_total"] == 1
        # the engine goes on: the other request is served
        eng._prefill_group = real
        d.run([first, late[1]], 2)
        assert first.error is None and late[1].error is None
    finally:
        eng.close()


def test_a_hang_inside_an_early_dispatch_strands_no_future(gen):
    """The watchdog's `_admitting` alias covers the early `_admit`."""
    d = Driven(gen, engine_step_timeout_s=60.0)
    eng = d.eng
    try:
        seen, late = {}, []
        real = eng._prefill_group

        def wedging(reqs, padded):
            if late and reqs[0] is late[0]:
                seen["admitting"] = list(eng._admitting)
                eng._on_hang()                  # as the watchdog's thread
            return real(reqs, padded)
        eng._prefill_group = wedging

        def lands():
            late.append(eng.submit(Q3, 3, GREEDY, seed=0))
            t0 = time.monotonic()
            while not late[0].done() and time.monotonic() - t0 < TAKEN_S:
                time.sleep(0.001)
        d.during[2] = lands
        first = eng.submit(P4, 10, GREEDY, seed=0)
        eng._iteration()
        with pytest.raises(engine_mod.EngineHungError):
            eng._iteration()
        assert seen["admitting"] == [late[0]]
        assert (first.done(), late[0].done()) == (True, True)
        assert "engine hung" in first.error and "engine hung" in late[0].error
    finally:
        eng.close()
