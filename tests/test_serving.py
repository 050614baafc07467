"""Continuous-batching engine tests (megatron_tpu/serving).

The load-bearing contracts:
- a seeded engine request reproduces the serial
  `Generator.generate`/`generate_and_post_process` output
  token-for-token (the engine is a scheduling change, not a semantics
  change);
- requests INTERLEAVE: a later-arriving short request finishes while an
  earlier long one is still decoding;
- the decode step compiles exactly ONCE regardless of request count,
  lengths, or sampling params (static slot-grid shapes);
- backpressure: bounded queue overflow rejects (429 at the HTTP layer),
  oversize requests fail admission (400).
"""
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.config import ModelConfig, ServingConfig
from megatron_tpu.inference import Generator, SamplingParams
from megatron_tpu.models import language_model as lm
from megatron_tpu.serving import (AdmissionError, GenRequest, PrefixIndex,
                                  QueueFullError, RequestState,
                                  SamplingOptions, ServiceUnavailableError,
                                  ServingEngine, ServingMetrics, SlotKVPool,
                                  clone_prefix)


def tiny_cfg(**overrides):
    base = dict(num_layers=2, hidden_size=64, num_attention_heads=4,
                num_kv_heads=2, vocab_size=96, seq_length=64,
                make_vocab_size_divisible_by=32, compute_dtype="float32")
    base.update(overrides)
    return ModelConfig(**base).derived()


@pytest.fixture(scope="module")
def tiny_model():
    cfg = tiny_cfg()
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    return params, cfg


@pytest.fixture(scope="module")
def engine(tiny_model):
    params, cfg = tiny_model
    gen = Generator(params, cfg, eos_id=0, pad_id=0)
    eng = ServingEngine(gen, ServingConfig(num_slots=3, max_queue=32,
                                           max_len=64))
    yield gen, eng
    eng.close()


PROMPTS = [[5, 17, 3, 42], [7, 8, 9], [11, 12, 13, 14, 15],
           [21, 22], [31, 32, 33], [41, 42, 43, 44],
           [51, 52, 53, 54, 55, 56, 57]]


class TestEngineMatchesSerial:
    """Acceptance: >= 6 concurrent requests through a 2-4-slot engine on
    CPU match the serial path exactly, interleave, and share ONE decode
    compile."""

    def test_seeded_outputs_equal_serial_and_single_compile(self, engine):
        gen, eng = engine
        arms = (
            # (sampling, seeds) — greedy AND seeded-sampled requests mix
            # in the same grid (per-slot sampling params)
            (SamplingOptions(temperature=0.0), range(len(PROMPTS))),
            (SamplingOptions(temperature=0.9, top_k=5),
             range(100, 100 + len(PROMPTS))),
            (SamplingOptions(temperature=1.1, top_p=0.8),
             range(200, 200 + len(PROMPTS))),
        )
        for sampling, seeds in arms:
            # submit ALL before collecting: requests decode concurrently
            reqs = [eng.submit(p, 8, sampling, seed=s)
                    for p, s in zip(PROMPTS, seeds)]
            sp = SamplingParams(temperature=sampling.temperature,
                                top_k=sampling.top_k, top_p=sampling.top_p)
            for p, s, r in zip(PROMPTS, seeds, reqs):
                toks, lps = r.result(timeout=300)
                want_toks, want_lens, _ = gen.generate(
                    [p], 8, sampling=sp, seed=s)
                want = want_toks[0, :want_lens[0]].tolist()
                assert toks == want, (p, s, toks, want)
                assert len(lps) == len(toks) - len(p)
        # one trace total across 21 mixed requests — no per-request
        # retrace (the acceptance criterion)
        assert eng._decode_traces == 1

    def test_later_short_request_finishes_before_earlier_long(self,
                                                              engine):
        gen, eng = engine
        long_req = eng.submit([5, 6, 7], 40,
                              SamplingOptions(temperature=0.8), seed=1)
        time.sleep(0.01)
        short_req = eng.submit([9, 10], 3,
                               SamplingOptions(temperature=0.8), seed=2)
        short_req.result(timeout=300)
        long_req.result(timeout=300)
        # premise: the long request really is long (no early EOS with
        # these seeds on this model)
        assert len(long_req.generated) == 40
        assert len(short_req.generated) <= 3
        assert short_req.submit_time > long_req.submit_time
        assert short_req.finish_time < long_req.finish_time, (
            "continuous batching must let the later short request "
            "finish while the long one is still decoding")

    def test_queue_overflow_drains_in_fifo_order(self, engine):
        """More requests than slots+queue slots process fine when
        submitted under the bound; results stay request-accurate."""
        gen, eng = engine
        reqs = [eng.submit(p, 4, SamplingOptions(temperature=0.0), seed=0)
                for p in PROMPTS * 2]  # 14 requests through 3 slots
        outs = [r.result(timeout=300)[0] for r in reqs]
        for p, toks in zip(PROMPTS * 2, outs):
            want_toks, want_lens, _ = gen.generate(
                [p], 4, sampling=SamplingParams(temperature=0.0))
            assert toks == want_toks[0, :want_lens[0]].tolist()

    def test_concurrent_submitters(self, engine):
        """Submissions from many threads (the HTTP handler pattern)."""
        gen, eng = engine
        results = {}
        lock = threading.Lock()

        def worker(i):
            toks, _ = eng.generate(PROMPTS[i % len(PROMPTS)], 5,
                                   SamplingOptions(temperature=0.0),
                                   seed=0, timeout=300)
            with lock:
                results[i] = toks

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert len(results) == 8
        for i, toks in results.items():
            p = PROMPTS[i % len(PROMPTS)]
            want_toks, want_lens, _ = gen.generate(
                [p], 5, sampling=SamplingParams(temperature=0.0))
            assert toks == want_toks[0, :want_lens[0]].tolist()

    def test_max_new_tokens_zero_returns_prompt(self, engine):
        gen, eng = engine
        toks, lps = eng.generate([5, 6, 7], 0, timeout=60)
        assert toks == [5, 6, 7] and lps == []


class TestBackpressure:
    def test_queue_full_rejects(self, tiny_model):
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        # start=False: nothing drains, so the bound is deterministic
        eng = ServingEngine(gen, ServingConfig(num_slots=1, max_queue=2,
                                               max_len=64), start=False)
        eng.submit([1, 2], 4)
        eng.submit([3, 4], 4)
        with pytest.raises(QueueFullError):
            eng.submit([5, 6], 4)
        assert eng.metrics.snapshot()["requests_rejected"] == 1

    def test_close_on_never_started_engine(self, tiny_model):
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        with ServingEngine(gen, ServingConfig(num_slots=1, max_queue=2,
                                              max_len=32),
                           start=False) as eng:
            req = eng.submit([1, 2], 4)
        # close() failed the queued backlog instead of crashing on the
        # never-started thread
        assert req.done()
        with pytest.raises(RuntimeError):
            req.result(timeout=1)

    def test_oversize_request_rejected(self, tiny_model):
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        eng = ServingEngine(gen, ServingConfig(num_slots=1, max_queue=2,
                                               max_len=32), start=False)
        with pytest.raises(AdmissionError):
            eng.submit(list(range(1, 30)), 8)  # 29 + 8 > 32
        # the zero-decode short-circuit must apply the SAME admission
        # check (engine and serial routes must agree on 400)
        with pytest.raises(AdmissionError):
            eng.submit(list(range(1, 40)), 0)  # 39 > 32
        # and an admissible zero-decode request keeps counters balanced
        eng.submit([1, 2, 3], 0)
        snap = eng.metrics.snapshot()
        assert snap["requests_admitted"] == snap["requests_completed"] == 1

    def test_cancel_queued_request(self, tiny_model):
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        eng = ServingEngine(gen, ServingConfig(num_slots=1, max_queue=4,
                                               max_len=64), start=False)
        r1 = eng.submit([1, 2], 4)
        r2 = eng.submit([3, 4], 4)
        eng.cancel(r2)
        assert r2.done()
        with pytest.raises(RuntimeError, match="cancelled"):
            r2.result(timeout=1)
        assert not r1.done()
        assert eng.scheduler.depth() == 1

    def test_cancel_running_request_frees_slot(self, engine):
        """A RUNNING request flagged for cancellation is evicted at the
        next decode step; its slot serves later traffic."""
        gen, eng = engine
        long_req = eng.submit([5, 6, 7], 4096 // 70,
                              SamplingOptions(temperature=0.8), seed=1)
        # long enough to still be decoding when cancel lands; if it
        # already finished, the cancel is a no-op and the test is moot
        eng.cancel(long_req)
        try:
            toks, _ = long_req.result(timeout=60)
            # raced completion (legal): must have decoded to the end
            assert len(long_req.generated) > 0
        except RuntimeError as e:
            assert "cancelled" in str(e)
        # the grid still serves fresh requests afterwards
        toks, _ = eng.generate([9, 10], 3,
                               SamplingOptions(temperature=0.0),
                               timeout=300)
        want_toks, want_lens, _ = gen.generate(
            [[9, 10]], 3, sampling=SamplingParams(temperature=0.0))
        assert toks == want_toks[0, :want_lens[0]].tolist()

    def test_failed_payload_cancels_orphans(self, tiny_model):
        """HTTP layer: when one row of a multi-prompt payload times out
        (or fails), the siblings must be cancelled rather than left
        decoding for a response nobody will read."""
        from megatron_tpu.inference.server import MegatronServer
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        srv = MegatronServer(gen, FakeTokenizer(),
                             serving=ServingConfig(num_slots=1,
                                                   max_queue=4,
                                                   max_len=64),
                             request_timeout=0.05)
        srv.engine.close()
        # NON-RUNNING engine: results never arrive -> the tiny request
        # timeout fires deterministically during the drain
        srv.engine = ServingEngine(
            gen, ServingConfig(num_slots=1, max_queue=4, max_len=64),
            start=False)
        status, body = srv.handle({"prompts": ["a", "b", "c"],
                                   "tokens_to_generate": 2})
        assert status == 500
        # every orphaned row was cancelled out of the queue
        assert srv.engine.scheduler.depth() == 0


class TestSlotKVPool:
    def test_alloc_release_cycle(self, tiny_model):
        _, cfg = tiny_model
        pool = SlotKVPool(cfg, 3, 64)
        assert pool.caches.k.shape == (2, 3, 64, 2, 16)
        assert pool.caches.offset.shape == (2, 3)
        slots = [pool.alloc() for _ in range(3)]
        assert sorted(slots) == [0, 1, 2] and pool.free_count() == 0
        pool.release(1)
        assert pool.alloc() == 1
        with pytest.raises(AssertionError):
            pool.release(0)
            pool.release(0)

    def test_int8_pool_has_scales(self, tiny_model):
        _, cfg = tiny_model
        pool = SlotKVPool(cfg, 2, 64, dtype=jnp.int8)
        assert pool.caches.k.dtype == jnp.int8
        assert pool.caches.k_scale is not None
        assert pool.nbytes() > 0

    def test_slot_nbytes_matches_real_pool(self, tiny_model):
        from megatron_tpu.serving.kv_pool import fit_num_slots, slot_nbytes
        _, cfg = tiny_model
        for dtype in (jnp.bfloat16, jnp.int8):
            pool = SlotKVPool(cfg, 3, 64, dtype=dtype)
            assert slot_nbytes(cfg, 64, dtype) * 3 == pool.nbytes()
        # CPU backend exposes no memory stats -> requested unchanged
        assert fit_num_slots(cfg, 64, requested=8) == 8

    @pytest.mark.parametrize("case", ["fits", "weights_pending",
                                      "sharded", "tpu_without_stats"])
    def test_fit_num_slots_budget(self, tiny_model, monkeypatch, case):
        """The pool's budget is what the device has left AFTER the
        weights still staged on the host; each tp shard holds 1/shards
        of both; a TPU that reports no stats is an error, not 8 slots."""
        import types

        import jax
        from megatron_tpu.serving.kv_pool import fit_num_slots, slot_nbytes
        _, cfg = tiny_model
        slot = slot_nbytes(cfg, 64)
        stats = {"bytes_limit": 100 * slot, "bytes_in_use": 0}
        dev = types.SimpleNamespace(
            platform="tpu", device_kind="fake",
            memory_stats=lambda: None if case == "tpu_without_stats"
            else stats)
        monkeypatch.setattr(jax, "local_devices", lambda: [dev])
        if case == "fits":
            assert fit_num_slots(cfg, 64, requested=8) == 8
            assert fit_num_slots(cfg, 64, requested=200) == 80  # headroom
        elif case == "weights_pending":
            assert fit_num_slots(cfg, 64, requested=200,
                                 pending_bytes=90 * slot) == 8
        elif case == "sharded":
            # 4 shards: a quarter of the weights and of every slot each
            assert fit_num_slots(cfg, 64, requested=500, shards=4,
                                 pending_bytes=200 * slot) == 160
        else:
            with pytest.raises(RuntimeError, match="no memory stats"):
                fit_num_slots(cfg, 64, requested=8)

    def test_rolling_pool_caps_to_window(self):
        cfg = tiny_cfg(sliding_window=16, attention_impl="flash",
                       seq_length=64, max_position_embeddings=64)
        pool = SlotKVPool(cfg, 2, 64)
        assert pool.cap == 16 and pool.rolling
        # prefill caches must share the rolling layout
        pc = pool.make_prefill_caches(1)
        assert pc.k.shape[2] == 16


class TestEngineKvVariants:
    """The pool reuses init_kv_caches' int8 and sliding-window modes;
    the engine must stay token-exact against the serial path on both."""

    def test_int8_pool_matches_serial_int8(self, tiny_model):
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0,
                        kv_cache_dtype=jnp.int8)
        with ServingEngine(gen, ServingConfig(num_slots=2, max_queue=16,
                                              max_len=64)) as eng:
            reqs = [eng.submit(p, 6, SamplingOptions(temperature=0.0),
                               seed=0) for p in PROMPTS[:4]]
            for p, r in zip(PROMPTS[:4], reqs):
                toks, _ = r.result(timeout=300)
                want_toks, want_lens, _ = gen.generate(
                    [p], 6, sampling=SamplingParams(temperature=0.0))
                assert toks == want_toks[0, :want_lens[0]].tolist()

    @pytest.mark.slow  # flash prefill + rolling decode compile-heavy
    def test_rolling_pool_matches_serial_rolling(self):
        cfg = tiny_cfg(sliding_window=16, attention_impl="flash",
                       seq_length=128, max_position_embeddings=128,
                       vocab_size=96)
        params = lm.model_init(jax.random.PRNGKey(0), cfg)
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        rs = np.random.RandomState(0)
        prompts = [rs.randint(1, 96, n).tolist() for n in (6, 10, 20)]
        with ServingEngine(gen, ServingConfig(num_slots=2, max_queue=8,
                                              max_len=64)) as eng:
            # 24 new tokens crosses the W=16 rolling boundary per slot
            reqs = [eng.submit(p, 24, SamplingOptions(temperature=0.0),
                               seed=0) for p in prompts]
            for p, r in zip(prompts, reqs):
                toks, _ = r.result(timeout=300)
                want_toks, want_lens, _ = gen.generate(
                    [p], 24, sampling=SamplingParams(temperature=0.0))
                assert toks == want_toks[0, :want_lens[0]].tolist(), p


class TestServingMetrics:
    def test_snapshot_and_percentiles(self):
        from megatron_tpu.utils.tracing import RequestRow
        m = ServingMetrics()
        for rid, t in enumerate((0.1, 0.2, 0.3, 0.4)):
            row = RequestRow(rid, 0.0)
            row.t_admit, row.t_first = 0.05, t
            m.record_admitted(row)
        m.record_completed(8)
        m.record_step(2, 4, 2, 1)
        snap = m.snapshot()
        assert snap["requests_completed"] == 1
        assert snap["tokens_generated"] == 8
        assert snap["slot_occupancy"] == 0.5
        assert snap["queue_depth"] == 1
        assert 100 <= snap["ttft_p50_ms"] <= 300
        assert snap["ttft_p95_ms"] >= snap["ttft_p50_ms"]

    def test_report_goes_through_writer(self):
        m = ServingMetrics()
        m.record_step(1, 2, 1, 0)
        seen = {}

        class Rec:
            def add_scalar(self, tag, v, step):
                seen[tag] = v

            def flush(self):
                pass

        m.report(Rec(), step=7)
        assert "serving/decode_steps" in seen
        assert "serving/tokens_per_s" in seen

    def test_engine_reports_through_writer(self, tiny_model):
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        seen = []

        class Rec:
            def add_scalar(self, tag, v, step):
                seen.append(tag)

            def flush(self):
                pass

        with ServingEngine(gen, ServingConfig(num_slots=2, max_queue=8,
                                              max_len=64),
                           writer=Rec(), report_interval=2) as eng:
            eng.generate([5, 6, 7], 6, SamplingOptions(temperature=0.0),
                         timeout=300)
        assert any(t.startswith("serving/") for t in seen)


class TestServingConfig:
    def test_validate_bounds(self):
        cfg = tiny_cfg()
        ServingConfig(num_slots=4, max_len=64).validate(cfg)
        with pytest.raises(AssertionError):
            ServingConfig(max_len=1024).validate(cfg)  # > max positions
        with pytest.raises(AssertionError):
            ServingConfig(num_slots=0).validate(cfg)
        with pytest.raises(AssertionError):
            ServingConfig(kv_dtype="fp8").validate(cfg)

    def test_from_dict_roundtrip(self):
        from megatron_tpu.config import MegatronConfig
        mc = MegatronConfig.from_dict(
            {"serving": {"num_slots": 5, "kv_dtype": "int8"}})
        assert mc.serving.num_slots == 5
        assert mc.serving.kv_dtype == "int8"


class FakeTokenizer:
    vocab_size = 96
    eod = 0
    bos = 1

    def tokenize(self, text):
        return [2 + (ord(c) % 90) for c in text][:16]

    def detokenize(self, ids):
        return " ".join(str(i) for i in ids)


class TestServerStatusCodes:
    """Satellite: validation failures must come back 400 (both
    backends), queue overflow 429, success 200 — not the reference's
    200 + {"message": ...}."""

    @pytest.fixture(scope="class")
    def server(self, tiny_model):
        from megatron_tpu.inference.server import MegatronServer
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        srv = MegatronServer(gen, FakeTokenizer(),
                             serving=ServingConfig(num_slots=2,
                                                   max_queue=16,
                                                   max_len=64))
        yield srv
        srv.close()

    @pytest.mark.parametrize("payload,frag", [
        ({}, "prompts argument required"),
        ({"prompts": []}, "non-empty list"),
        ({"prompts": "hi"}, "non-empty list"),
        ({"prompts": [""]}, "non-empty strings"),
        ({"prompts": ["x"] * 129, "tokens_to_generate": 1},
         "Maximum number of prompts"),
        ({"prompts": ["hi"], "tokens_to_generate": -1}, ">= 0"),
        ({"prompts": ["hi"], "tokens_to_generate": "lots"}, "integer"),
        ({"prompts": ["hi"], "temperature": [1]}, "temperature"),
        ({"prompts": ["hi"], "top_k": {}}, "top_k"),
        ({"prompts": ["hi"], "random_seed": "abc"}, "random_seed"),
        ({"prompts": ["a", "b"], "beam_width": 2}, "only one prompt"),
    ])
    def test_invalid_payloads_are_400(self, server, payload, frag):
        status, body = server.handle(payload)
        assert status == 400, (payload, body)
        assert frag in body["message"]

    def test_beam_oversize_prompt_is_400(self, server):
        """The beam route must apply the same length admission — RoPE
        positions past the table would silently clamp, not error."""
        status, body = server.handle(
            {"prompts": ["abcdefghijklmnop"], "tokens_to_generate": 60,
             "beam_width": 2})
        assert status == 400
        assert "max_position_embeddings" in body["message"]

    def test_valid_payload_is_200(self, server):
        status, body = server.handle({"prompts": ["hello"],
                                      "tokens_to_generate": 3,
                                      "temperature": 0.0})
        assert status == 200 and len(body["text"]) == 1

    def test_engine_matches_serial_through_server(self, server):
        """Server-level acceptance: the engine route and the serial
        fallback route return identical text for the same seed."""
        payload = {"prompts": ["hello world"], "tokens_to_generate": 6,
                   "temperature": 0.8, "top_k": 4, "random_seed": 11}
        s1, engine_out = server.handle(payload)
        s2, serial_out = server.handle({**payload, "serial": True})
        assert s1 == s2 == 200
        assert engine_out["text"] == serial_out["text"]
        assert engine_out["segments"] == serial_out["segments"]

    def test_queue_full_of_other_traffic_is_429(self, tiny_model):
        """429 fires when the queue is full of OTHER traffic before the
        payload placed a single row (a payload merely LARGER than the
        queue drains its own rows in waves instead — see
        test_payload_larger_than_queue_succeeds)."""
        from megatron_tpu.inference.server import MegatronServer
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        srv = MegatronServer(gen, FakeTokenizer(),
                             serving=ServingConfig(num_slots=1,
                                                   max_queue=1,
                                                   max_len=64))
        # swap in a NON-RUNNING engine so the bound is deterministic
        srv.engine.close()
        srv.engine = ServingEngine(
            gen, ServingConfig(num_slots=1, max_queue=1, max_len=64),
            start=False)
        srv.engine.submit([1, 2], 2)  # other traffic fills the queue
        status, body = srv.handle({"prompts": ["a"],
                                   "tokens_to_generate": 2})
        assert status == 429
        assert "queue full" in body["message"]

    def test_payload_larger_than_queue_succeeds(self, tiny_model):
        """The reference's contract allows 128 prompts per payload; the
        engine route must serve a payload bigger than slots + queue by
        draining its own completed rows, not 429."""
        from megatron_tpu.inference.server import MegatronServer
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        srv = MegatronServer(gen, FakeTokenizer(),
                             serving=ServingConfig(num_slots=2,
                                                   max_queue=2,
                                                   max_len=64))
        try:
            status, body = srv.handle({"prompts": ["p%d" % i
                                                   for i in range(9)],
                                       "tokens_to_generate": 2,
                                       "temperature": 0.0})
            assert status == 200, body
            assert len(body["text"]) == 9
        finally:
            srv.close()

    def test_oversize_prompt_is_400(self, server):
        status, body = server.handle(
            {"prompts": ["abcdefghijklmnop"],  # 16 tokens
             "tokens_to_generate": 60})  # 16 + 60 > max_len 64
        assert status == 400
        assert "max_len" in body["message"]
        # the SERIAL route must agree: its length ValueError maps to
        # 400 too (Generator raises on prompt + new > max positions)
        status, body = server.handle(
            {"prompts": ["abcdefghijklmnop"], "tokens_to_generate": 60,
             "serial": True})
        assert status == 400

    def test_stdlib_backend_emits_statuses(self, server):
        """The raw http.server path must carry the same statuses."""
        import json as _json
        import socket
        import urllib.error
        import urllib.request
        sock = socket.socket()
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
        sock.close()
        t = threading.Thread(target=server._run_stdlib,
                             args=("127.0.0.1", port), daemon=True)
        t.start()

        def put(payload):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/api",
                data=_json.dumps(payload).encode(), method="PUT",
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=60) as resp:
                    return resp.status, _json.loads(resp.read())
            except urllib.error.HTTPError as e:
                return e.code, _json.loads(e.read())

        for _ in range(50):
            try:
                status, body = put({"prompts": ["hi"],
                                    "tokens_to_generate": 2,
                                    "temperature": 0.0})
                break
            except (ConnectionError, urllib.error.URLError):
                time.sleep(0.2)
        else:
            pytest.fail("server never became reachable")
        assert status == 200 and "text" in body
        status, body = put({})
        assert status == 400
        assert body["message"] == "prompts argument required"
        # GET /metrics exposes the engine snapshot
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=60) as resp:
            snap = _json.loads(resp.read())
        assert snap["requests_completed"] >= 1


class TestDecodeSyncCadence:
    """Acceptance for the K-step dispatch window: decode_sync_interval=K
    is token-exact vs K=1 for seeded requests, performs 1/K host syncs
    per decode step, still compiles the decode exactly once, and only
    re-uploads the per-slot sampling state on slot churn."""

    def _collect(self, tiny_model, K):
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        with ServingEngine(gen, ServingConfig(
                num_slots=3, max_queue=32, max_len=64,
                decode_sync_interval=K)) as eng:
            reqs = [eng.submit(p, 8,
                               SamplingOptions(temperature=0.9, top_k=5),
                               seed=100 + i)
                    for i, p in enumerate(PROMPTS)]
            outs = [r.result(timeout=300)[0] for r in reqs]
            assert eng._decode_traces == 1
            return outs, eng.metrics.snapshot()

    def test_k_step_window_token_exact_at_one_over_k_syncs(self,
                                                           tiny_model):
        outs1, snap1 = self._collect(tiny_model, 1)
        outs3, snap3 = self._collect(tiny_model, 3)
        # token-exact: per-slot rng/logits/KV chains are independent of
        # the sync cadence
        assert outs1 == outs3
        # 1/K syncs per decode step, windows always complete
        assert snap1["host_syncs"] == snap1["decode_steps"]
        assert snap3["decode_steps"] % 3 == 0
        assert snap3["host_syncs"] == snap3["decode_steps"] / 3
        assert snap3["host_syncs_per_step"] == pytest.approx(1 / 3)

    def test_sampling_uploads_only_on_slot_churn(self, tiny_model):
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        with ServingEngine(gen, ServingConfig(num_slots=2, max_queue=8,
                                              max_len=64)) as eng:
            toks, _ = eng.generate([5, 17, 3], 24,
                                   SamplingOptions(temperature=0.8),
                                   seed=9)
            snap = eng.metrics.snapshot()
        # one long-running request: ~24 decode steps but the sampling
        # knobs upload only on admission (+ the engine's initial dirty
        # state), NOT once per step as before
        assert snap["decode_steps"] >= 20
        assert snap["sampling_uploads"] <= 3

    def test_batched_prefill_coalesces_same_bucket_admissions(
            self, tiny_model):
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        eng = ServingEngine(gen, ServingConfig(num_slots=3, max_queue=32,
                                               max_len=64),
                            start=False)
        try:
            # queue a burst BEFORE the loop starts so the first pop
            # sees all of them: 3 free slots, same 16-token bucket ->
            # ONE batched prefill call for the first three
            reqs = [eng.submit(p, 4, SamplingOptions(temperature=0.0),
                               seed=0) for p in PROMPTS[:4]]
            eng._thread.start()
            outs = [r.result(timeout=300)[0] for r in reqs]
            snap = eng.metrics.snapshot()
        finally:
            eng.close()
        assert snap["prefill_calls"] <= 2  # 3 coalesced + 1 straggler
        assert snap["prefill_prompts"] == 4
        assert snap["prompts_per_prefill"] >= 2
        # batching is a scheduling change, not a semantics change
        for p, toks in zip(PROMPTS[:4], outs):
            want_toks, want_lens, _ = gen.generate(
                [p], 4, sampling=SamplingParams(temperature=0.0))
            assert toks == want_toks[0, :want_lens[0]].tolist()


class TestPrefixIndex:
    """Host-side radix index: bucket-aligned longest match, recency
    tie-break, tolerant removal with tail pruning."""

    def test_longest_aligned_match(self):
        idx = PrefixIndex(4)
        idx.insert(0, list(range(12)))
        # uncapped: the whole 3-block sequence matches
        assert idx.lookup(list(range(12))) == (0, 12)
        # capped at 11 (the engine's len(prompt)-1): 2 blocks
        assert idx.lookup(list(range(12)), max_tokens=11) == (0, 8)
        # diverging after the first block matches exactly one block
        assert idx.lookup(list(range(4)) + [99, 98, 97, 96]) == (0, 4)
        # diverging inside the first block matches nothing
        assert idx.lookup([99] + list(range(1, 12))) == (None, 0)

    def test_most_recent_wins_remove_prunes(self):
        idx = PrefixIndex(2)
        idx.insert(1, [1, 2, 3, 4])
        idx.insert(2, [1, 2, 3, 4])
        assert idx.lookup([1, 2, 3, 4])[0] == 2  # warmest KV wins
        idx.remove(2)
        assert idx.lookup([1, 2, 3, 4]) == (1, 4)
        idx.remove(1)
        idx.remove(1)  # removal is tolerant (on_reclaim may repeat)
        assert idx.lookup([1, 2, 3, 4]) == (None, 0)
        assert len(idx) == 0 and not idx._root.children  # pruned

    def test_reinsert_replaces_path(self):
        idx = PrefixIndex(2)
        idx.insert(3, [1, 2, 3, 4])
        idx.insert(3, [5, 6, 7, 8])  # retain-time extension/replace
        assert idx.lookup([1, 2, 3, 4]) == (None, 0)
        assert idx.lookup([5, 6, 7, 8]) == (3, 4)

    def test_sub_block_sequences_not_indexed(self):
        idx = PrefixIndex(8)
        idx.insert(0, [1, 2, 3])  # shorter than one block
        assert idx.lookup([1, 2, 3, 4, 5, 6, 7, 8]) == (None, 0)


class TestRetainedPool:
    """Lazy slot eviction: finished slots keep their KV on an LRU
    retained list; admission reclaims them only when it must."""

    def test_retain_lru_and_lazy_reclaim(self, tiny_model):
        _, cfg = tiny_model
        pool = SlotKVPool(cfg, 3, 64)
        reclaimed = []
        pool.on_reclaim = reclaimed.append
        a, b, c = pool.alloc(), pool.alloc(), pool.alloc()
        pool.retain(a)
        pool.retain(b)
        assert pool.free_count() == 2 and pool.retained_count() == 2
        pool.touch(a)  # a is now most recently used
        assert pool.alloc() == b and reclaimed == [b]  # LRU goes first
        # `exclude` protects the clone source of the same admission
        assert pool.alloc(exclude=(a,)) is None
        assert pool.alloc() == a and reclaimed == [b, a]
        pool.release(c)
        assert pool.alloc() == c  # free list beats retained

    def test_retained_limit_demotes_oldest(self, tiny_model):
        _, cfg = tiny_model
        pool = SlotKVPool(cfg, 3, 64, retained_limit=1)
        reclaimed = []
        pool.on_reclaim = reclaimed.append
        a, b, _ = pool.alloc(), pool.alloc(), pool.alloc()
        pool.retain(a)
        pool.retain(b)
        assert reclaimed == [a] and pool.retained_count() == 1
        assert pool.alloc() == a  # demoted to the free list

    def test_clone_prefix_copies_verbatim(self, tiny_model):
        """The prefix-hit primitive copies k/v (and int8 scales)
        bit-identically and leaves the source untouched."""
        _, cfg = tiny_model
        rs = np.random.RandomState(0)

        def rnd(x):
            if x is None:
                return None
            if x.dtype == jnp.int8:
                return jnp.asarray(
                    rs.randint(-127, 128, x.shape), jnp.int8)
            return jnp.asarray(rs.randn(*x.shape), x.dtype)

        for dtype in (jnp.bfloat16, jnp.int8):
            pool = SlotKVPool(cfg, 2, 32, dtype=dtype)
            caches = pool.caches._replace(
                k=rnd(pool.caches.k), v=rnd(pool.caches.v),
                k_scale=rnd(pool.caches.k_scale),
                v_scale=rnd(pool.caches.v_scale))
            out = clone_prefix(caches, 0, 1, 5)
            for name in ("k", "v", "k_scale", "v_scale"):
                src = getattr(caches, name)
                if src is None:
                    continue
                got = np.asarray(getattr(out, name))
                # dst region == src region (whole cap, verbatim) and
                # the source region is untouched
                np.testing.assert_array_equal(
                    got[:, 1], np.asarray(src)[:, 0], err_msg=name)
                np.testing.assert_array_equal(
                    got[:, 0], np.asarray(src)[:, 0], err_msg=name)
            off = np.asarray(out.offset)
            assert (off[:, 1] == 5).all() and (off[:, 0] == 0).all()


class TestPrefixCacheEngine:
    """Tentpole acceptance: seeded generation is token-exact with the
    prefix cache on vs off (bf16 AND int8 pools), and a shared-prefix
    workload forwards strictly fewer prefill tokens with the cache on
    (counted through the prefill_forward_tokens seam, not wall-clock)."""

    SHARED = list(range(5, 21))  # one full 16-token bucket

    def _jobs(self):
        return [(self.SHARED + [70 + i, 80 + i], 300 + i)
                for i in range(4)]

    def _run(self, gen, serving):
        outs = []
        with ServingEngine(gen, serving) as eng:
            for p, s in self._jobs():  # sequential => deterministic hits
                outs.append(eng.generate(
                    p, 8, SamplingOptions(temperature=0.9, top_k=5),
                    seed=s, timeout=300)[0])
            snap = eng.metrics.snapshot()
        return outs, snap

    @pytest.mark.parametrize("kv_dtype", [None, "int8"])
    def test_token_exact_on_vs_off_and_tokens_saved(self, tiny_model,
                                                    kv_dtype):
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0,
                        kv_cache_dtype=(jnp.int8 if kv_dtype else
                                        jnp.bfloat16))
        base = dict(num_slots=3, max_queue=16, max_len=64)
        off_outs, off_snap = self._run(gen, ServingConfig(**base))
        on_outs, on_snap = self._run(
            gen, ServingConfig(enable_prefix_cache=True, **base))
        assert on_outs == off_outs  # bit-exact cache on vs off
        for (p, s), toks in zip(self._jobs(), on_outs):  # ... and serial
            want_toks, want_lens, _ = gen.generate(
                [p], 8, sampling=SamplingParams(temperature=0.9,
                                                top_k=5), seed=s)
            assert toks == want_toks[0, :want_lens[0]].tolist(), (p, s)
        # every request after the first hits the 16-token bucket prefix
        assert on_snap["prefix_hits"] == 3
        assert on_snap["prefix_hit_tokens"] == 48
        assert on_snap["prefill_tokens_saved"] == 48
        assert off_snap["prefill_tokens_saved"] == 0
        # the seam: strictly fewer REAL tokens through prefill forwards
        assert (on_snap["prefill_forward_tokens"]
                == off_snap["prefill_forward_tokens"] - 48 > 0)

    def test_hit_on_running_slot(self, tiny_model):
        """A prompt sharing a prefix with a STILL-DECODING request
        clones from the running slot; both stay token-exact."""
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        with ServingEngine(gen, ServingConfig(
                num_slots=3, max_queue=16, max_len=64,
                enable_prefix_cache=True)) as eng:
            long_req = eng.submit(self.SHARED + [90], 24,
                                  SamplingOptions(temperature=0.8),
                                  seed=7)
            while not long_req.generated and not long_req.done():
                time.sleep(0.005)
            short = eng.submit(self.SHARED + [91, 92], 6,
                               SamplingOptions(temperature=0.8), seed=8)
            short_toks, _ = short.result(timeout=300)
            long_toks, _ = long_req.result(timeout=300)
            snap = eng.metrics.snapshot()
        assert snap["prefix_hits"] >= 1 and short.prefix_len == 16
        for p, s, got in (((self.SHARED + [90]), 7, long_toks),
                          ((self.SHARED + [91, 92]), 8, short_toks)):
            want_toks, want_lens, _ = gen.generate(
                [p], 24 if s == 7 else 6,
                sampling=SamplingParams(temperature=0.8), seed=s)
            assert got == want_toks[0, :want_lens[0]].tolist(), (p, s)

    def test_retained_slots_reclaimed_under_pressure(self, tiny_model):
        """More distinct prompts than slots: retained slots are lazily
        reclaimed for fresh admissions and everything stays exact."""
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        prompts = [[10 * i + j for j in range(1, 7)] for i in range(1, 7)]
        with ServingEngine(gen, ServingConfig(
                num_slots=2, max_queue=16, max_len=64,
                enable_prefix_cache=True, retained_slots=1)) as eng:
            reqs = [eng.submit(p, 4, SamplingOptions(temperature=0.0),
                               seed=0) for p in prompts]
            outs = [r.result(timeout=300)[0] for r in reqs]
        for p, toks in zip(prompts, outs):
            want_toks, want_lens, _ = gen.generate(
                [p], 4, sampling=SamplingParams(temperature=0.0))
            assert toks == want_toks[0, :want_lens[0]].tolist(), p

    def test_forfeited_hit_counts_hit_tokens_not_saved(self,
                                                       tiny_model):
        """With 1 slot the clone source is the only allocatable slot:
        the hit is forfeited (the slot is reclaimed as a plain slot) —
        counted in prefix_hit_tokens but NOT prefill_tokens_saved, and
        output stays exact."""
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        p2 = self.SHARED + [71, 81]
        with ServingEngine(gen, ServingConfig(
                num_slots=1, max_queue=8, max_len=64,
                enable_prefix_cache=True)) as eng:
            eng.generate(self.SHARED + [70, 80], 4,
                         SamplingOptions(temperature=0.0), seed=0,
                         timeout=300)
            toks, _ = eng.generate(p2, 4,
                                   SamplingOptions(temperature=0.0),
                                   seed=0, timeout=300)
            snap = eng.metrics.snapshot()
        assert snap["prefix_hit_tokens"] == 16  # matched at lookup
        assert snap["prefill_tokens_saved"] == 0  # ...but forfeited
        assert snap["prefix_hits"] == 0
        want_toks, want_lens, _ = gen.generate(
            [p2], 4, sampling=SamplingParams(temperature=0.0))
        assert toks == want_toks[0, :want_lens[0]].tolist()

    def test_retained_slots_zero_no_stale_index(self, tiny_model):
        """retained_slots=0: retain() demotes the finishing slot itself
        straight to the free list, and the index entry must die WITH it
        (retain fires on_reclaim for the demoted slot; free-list alloc
        never does). An entry inserted after retain() would outlive the
        demotion: an immediate repeat of the same prompt would 'hit' a
        free-listed slot — a phantom clone source the pool no longer
        guards (exclude= only protects the retained scan) — and inflate
        the hit metrics. With nothing ever retained, every request must
        be a miss."""
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        pa = self.SHARED + [70, 80]
        pb = [50 - i for i in range(18)]  # different 16-token bucket
        with ServingEngine(gen, ServingConfig(
                num_slots=1, max_queue=8, max_len=64,
                enable_prefix_cache=True, retained_slots=0)) as eng:
            # pa twice back-to-back: the repeat would hit a stale entry
            # (no intervening admission cleans it); then pb reuses the
            # slot; then pa again after the reuse.
            jobs = (pa, pa, pb, pa)
            outs = [eng.generate(p, 4, SamplingOptions(temperature=0.0),
                                 seed=0, timeout=300)[0] for p in jobs]
            snap = eng.metrics.snapshot()
        assert outs[0] == outs[1] == outs[3]  # repeats bit-identical
        for p, toks in zip(jobs, outs):
            want_toks, want_lens, _ = gen.generate(
                [p], 4, sampling=SamplingParams(temperature=0.0))
            assert toks == want_toks[0, :want_lens[0]].tolist(), p
        # nothing retained and nothing running at each admission: every
        # lookup must miss (a stale entry shows up as hits > 0 here)
        assert snap["prefix_hits"] == 0
        assert snap["prefix_hit_tokens"] == 0
        assert snap["prefill_tokens_saved"] == 0

    def test_flash_int8_pool_supported_token_exact(self):
        """The old flash-int8 exclusion is ERASED: quantized caches
        skip the offset-0 flash prefill shortcut (attention_apply), so
        every cached int8 forward — prefill, chunk, prefix suffix —
        reads the same dequantized cache through the same dot path and
        the token-exact cache-on/off contract holds structurally."""
        cfg = tiny_cfg(attention_impl="flash")
        # validates clean now (was an AssertionError before the block
        # refactor)
        ServingConfig(max_len=64, kv_dtype="int8",
                      enable_prefix_cache=True,
                      prefill_chunk=8).validate(cfg)
        params = lm.model_init(jax.random.PRNGKey(0), cfg)
        gen = Generator(params, cfg, eos_id=0, pad_id=0,
                        kv_cache_dtype=jnp.int8)
        shared = list(range(2, 34))
        wave1 = [shared + [40 + i, 50 + i] for i in range(3)]
        wave2 = [shared + [70 + i] for i in range(2)]

        def run(prefix):
            with ServingEngine(gen, ServingConfig(
                    num_slots=3, max_len=64, kv_dtype="int8",
                    enable_prefix_cache=prefix,
                    prefill_chunk=8 if prefix else None)) as eng:
                outs = []
                for wave in (wave1, wave2):  # wave 1 retains, 2 hits
                    reqs = [eng.submit(p, 4,
                                       SamplingOptions(temperature=0.8,
                                                       top_k=5),
                                       seed=i)
                            for i, p in enumerate(wave)]
                    outs += [r.result(timeout=300)[0] for r in reqs]
                snap = eng.metrics.snapshot()
            return outs, snap

        off, _ = run(False)
        on, snap = run(True)
        assert on == off, "flash-int8 prefix cache diverged"
        assert snap["prefix_hits"] >= 1
        assert snap["prefill_tokens_saved"] > 0

    def test_rolling_pool_requires_blocks(self):
        """Rolling retention/preemption needs the block-granular pool
        (a whole-region ring row's idle writes wrap into live
        content); chunked prefill stays excluded on rolling with OR
        without blocks. All four combinations pinned."""
        cfg = tiny_cfg(sliding_window=32, attention_impl="flash",
                       seq_length=64, max_position_embeddings=64)
        with pytest.raises(AssertionError, match="kv_block_size"):
            ServingConfig(max_len=64,
                          enable_prefix_cache=True).validate(cfg)
        with pytest.raises(AssertionError, match="ROLLING"):
            ServingConfig(max_len=64, prefill_chunk=8).validate(cfg)
        with pytest.raises(AssertionError, match="ROLLING"):
            ServingConfig(max_len=64, kv_block_size=16,
                          prefill_chunk=8).validate(cfg)
        # blocks lift the prefix-cache and preemption exclusions
        ServingConfig(max_len=64, kv_block_size=16,
                      enable_prefix_cache=True).validate(cfg)
        ServingConfig(max_len=64, kv_block_size=16, preemption=True,
                      priority_levels=2).validate(cfg)
        # non-rolling models validate fine
        ServingConfig(max_len=64, enable_prefix_cache=True,
                      prefill_chunk=8).validate(tiny_cfg())
        # the engine enforces it even without validate()
        params = lm.model_init(jax.random.PRNGKey(0), cfg)
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        with pytest.raises(AssertionError, match="kv_block_size"):
            ServingEngine(gen, ServingConfig(
                max_len=64, enable_prefix_cache=True), start=False)


class TestChunkedPrefill:
    """Chunked prefill is a scheduling change, not a semantics change:
    multi-chunk prompts are token-exact vs the monolithic prefill, and
    decode steps for running slots interleave between chunks."""

    def _long_prompts(self):
        rs = np.random.RandomState(3)
        return [rs.randint(1, 96, n).tolist() for n in (20, 33, 48)]

    def test_chunked_token_exact_vs_unchunked_and_serial(self,
                                                         tiny_model):
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        base = dict(num_slots=3, max_queue=16, max_len=64)
        outs = {}
        for chunk in (None, 8):
            with ServingEngine(gen, ServingConfig(
                    prefill_chunk=chunk, **base)) as eng:
                reqs = [eng.submit(p, 8,
                                   SamplingOptions(temperature=0.9,
                                                   top_k=5),
                                   seed=50 + i)
                        for i, p in enumerate(self._long_prompts())]
                outs[chunk] = [r.result(timeout=300)[0] for r in reqs]
                if chunk:
                    snap = eng.metrics.snapshot()
                    assert snap["prefill_chunks"] >= 3 + 5 + 6
                    chunks = [r.prefill_chunks for r in reqs]
                    assert chunks == [3, 5, 6]  # ceil(plen / 8)
        assert outs[8] == outs[None]
        for p, s, toks in zip(self._long_prompts(), (50, 51, 52),
                              outs[8]):
            want_toks, want_lens, _ = gen.generate(
                [p], 8, sampling=SamplingParams(temperature=0.9,
                                                top_k=5), seed=s)
            assert toks == want_toks[0, :want_lens[0]].tolist(), (p, s)

    def test_uniform_chunks_compile_once(self, tiny_model):
        """Full chunks are a fixed shape: two multi-chunk prompts share
        ONE chunk-forward trace (the tail pads to the same shape when
        prefill_chunk <= prefill_bucket)."""
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        with ServingEngine(gen, ServingConfig(
                num_slots=2, max_queue=8, max_len=64,
                prefill_chunk=8)) as eng:
            for i, p in enumerate(self._long_prompts()[:2]):
                eng.generate(p, 4, SamplingOptions(temperature=0.0),
                             seed=i, timeout=300)
            assert eng._chunk_traces == 1
            assert eng._decode_traces == 1

    def test_decode_interleaves_between_chunks(self, tiny_model):
        """The no-full-prompt-stall pin: while a long prompt prefills
        chunk by chunk, the already-running slot keeps taking decode
        steps between chunks."""
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        with ServingEngine(gen, ServingConfig(
                num_slots=2, max_queue=8, max_len=64,
                prefill_chunk=8)) as eng:
            events = []
            d, c = eng._decode, eng._chunk_fwd

            def rec_decode(*a):
                events.append("d")
                return d(*a)

            def rec_chunk(*a):
                events.append("c")
                return c(*a)

            eng._decode, eng._chunk_fwd = rec_decode, rec_chunk
            running = eng.submit([3, 4], 40,
                                 SamplingOptions(temperature=0.8),
                                 seed=1)
            while not running.generated and not running.done():
                time.sleep(0.005)
            long_req = eng.submit(list(range(1, 41)), 4,
                                  SamplingOptions(temperature=0.8),
                                  seed=2)  # 40 tokens -> 5 chunks
            long_req.result(timeout=300)
            running.result(timeout=300)
        chunk_idx = [i for i, e in enumerate(events) if e == "c"]
        assert len(chunk_idx) >= 5
        assert "d" in events[chunk_idx[0]:chunk_idx[-1]], (
            "chunks ran back-to-back — the long prompt stalled the "
            f"running request's decode: {events}")

    def test_cancel_mid_chunk_releases_slot(self, tiny_model):
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        eng = ServingEngine(gen, ServingConfig(
            num_slots=1, max_queue=4, max_len=64, prefill_chunk=4),
            start=False)
        try:
            r = eng.submit(list(range(1, 13)), 4)  # 12 tokens, 3 chunks
            eng._admit()
            assert len(eng._prefilling) == 1
            assert eng.pool.free_count() == 0  # slot reserved
            eng._advance_prefill()  # one chunk lands, 2 remain
            assert eng._prefilling and eng._prefilling[0].pos == 4
            r.cancel()
            eng._reap_cancelled()
            assert r.done() and not eng._prefilling
            assert eng.pool.free_count() == 1
            with pytest.raises(RuntimeError, match="cancelled"):
                r.result(timeout=1)
        finally:
            eng.close()


class TestPrefillBucketBoundaries:
    """Satellite: prompt lengths straddling the prefill bucket
    (bucket-1 / bucket / bucket+1) and a pow-2 batch-bucket pad row
    stay token-exact vs serial generation."""

    def test_bucket_edges_token_exact(self, engine):
        gen, eng = engine
        rs = np.random.RandomState(7)
        bucket = eng.serving.prefill_bucket
        for n in (bucket - 1, bucket, bucket + 1):
            p = rs.randint(1, 96, n).tolist()
            toks, _ = eng.generate(
                p, 6, SamplingOptions(temperature=0.9, top_k=5),
                seed=n, timeout=300)
            want_toks, want_lens, _ = gen.generate(
                [p], 6, sampling=SamplingParams(temperature=0.9,
                                                top_k=5), seed=n)
            assert toks == want_toks[0, :want_lens[0]].tolist(), n

    def test_batch_bucket_pad_row(self, tiny_model):
        """3 same-bucket admissions batch-bucket to a pow-2 B=4 with a
        replicated pad row — one prefill call, request-exact rows."""
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        eng = ServingEngine(gen, ServingConfig(num_slots=3, max_queue=8,
                                               max_len=64),
                            start=False)
        try:
            reqs = [eng.submit(p, 4, SamplingOptions(temperature=0.0),
                               seed=0) for p in PROMPTS[:3]]
            eng._thread.start()
            outs = [r.result(timeout=300)[0] for r in reqs]
            snap = eng.metrics.snapshot()
        finally:
            eng.close()
        assert snap["prefill_calls"] == 1  # one coalesced B=4 call
        assert snap["prefill_prompts"] == 3
        for p, toks in zip(PROMPTS[:3], outs):
            want_toks, want_lens, _ = gen.generate(
                [p], 4, sampling=SamplingParams(temperature=0.0))
            assert toks == want_toks[0, :want_lens[0]].tolist(), p


class TestDrainResolvesQueued:
    """Satellite: drain() must RESOLVE requests that were admitted to
    the scheduler but never given a slot — terminal 503, not a hung
    future."""

    def test_drain_fails_queued_as_503(self, tiny_model):
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        eng = ServingEngine(gen, ServingConfig(num_slots=1, max_queue=8,
                                               max_len=64), start=False)
        r1 = eng.submit([1, 2], 4)
        r2 = eng.submit([3, 4], 4)
        assert eng.drain(timeout=5)  # nothing in flight -> immediate
        for r in (r1, r2):
            assert r.done(), "queued request left hanging by drain()"
            with pytest.raises(ServiceUnavailableError):
                r.result(timeout=1)
        eng.close()

    def test_drain_completes_running_fails_queued(self, tiny_model):
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        eng = ServingEngine(gen, ServingConfig(num_slots=1, max_queue=8,
                                               max_len=64))
        running = eng.submit([5, 6, 7], 30,
                             SamplingOptions(temperature=0.8), seed=1)
        while running.state is not RequestState.RUNNING \
                and not running.done():
            time.sleep(0.005)
        queued = eng.submit([8, 9], 4)  # 1 slot busy -> stays queued
        assert eng.drain(timeout=120)
        toks, _ = running.result(timeout=1)  # decoded to completion
        assert len(running.generated) > 0
        assert queued.done()
        with pytest.raises(ServiceUnavailableError):
            queued.result(timeout=1)
        eng.close()

    def test_drain_completes_mid_chunk_request(self, tiny_model):
        """A request mid-chunked-prefill is in-flight work: drain waits
        for it instead of hanging or dropping it."""
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        eng = ServingEngine(gen, ServingConfig(num_slots=2, max_queue=8,
                                               max_len=64,
                                               prefill_chunk=8))
        r = eng.submit(list(range(1, 41)), 4,
                       SamplingOptions(temperature=0.0), seed=1)
        while r.state is not RequestState.RUNNING and not r.done():
            time.sleep(0.002)
        assert eng.drain(timeout=120)
        toks, _ = r.result(timeout=1)
        want_toks, want_lens, _ = gen.generate(
            [list(range(1, 41))], 4,
            sampling=SamplingParams(temperature=0.0))
        assert toks == want_toks[0, :want_lens[0]].tolist()
        eng.close()


class TestMetricsHardening:
    """Satellite: a /metrics scrape before the first request must not
    raise — empty sample windows are total."""

    def test_empty_snapshot_total_and_jsonable(self):
        import json
        snap = ServingMetrics().snapshot()
        json.dumps(snap)  # scrape-able as-is
        assert snap["requests_completed"] == 0.0
        assert snap["tokens_generated"] == 0.0
        assert snap["prefill_tokens_saved"] == 0.0
        assert snap["prefix_hits"] == 0.0
        assert snap["ttft_p50_ms"] == 0.0
        assert snap["tokens_per_s"] == 0.0
        assert snap["slot_occupancy"] == 0.0

    def test_percentile_degenerate_inputs(self):
        from megatron_tpu.serving.metrics import _percentile
        assert _percentile([], 0.5) == 0.0
        assert _percentile([], 0.0) == 0.0
        assert _percentile([1.0], 2.0) == 1.0   # q clamped high
        assert _percentile([1.0, 2.0], -0.5) == 1.0  # q clamped low

    def test_fresh_server_metrics_scrape(self, tiny_model):
        import json
        from megatron_tpu.inference.server import MegatronServer
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        srv = MegatronServer(gen, FakeTokenizer(),
                             serving=ServingConfig(num_slots=1,
                                                   max_queue=2,
                                                   max_len=32))
        try:
            snap = json.loads(json.dumps(srv.engine.metrics.snapshot()))
            assert snap["requests_received"] == 0.0
        finally:
            srv.close()


class TestSeeding:
    def test_explicit_seed_deterministic_unseeded_entropic(self,
                                                           tiny_model):
        from megatron_tpu.inference.server import MegatronServer
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        srv = MegatronServer(gen, FakeTokenizer(),
                             serving=ServingConfig(serial_fallback=True))
        assert srv._seed_for({"random_seed": 5}) == 5
        assert srv._seed_for({"random_seed": 5}) == 5
        # entropy-mixed: two unseeded requests differ (collision odds
        # 2^-31), and a FRESH server (process restart stand-in) does not
        # replay the old counter-only 0, 1, 2, ... sequence
        a, b = srv._seed_for({}), srv._seed_for({})
        assert a != b
        srv2 = MegatronServer(gen, FakeTokenizer(),
                              serving=ServingConfig(serial_fallback=True))
        assert (srv2._seed_for({}), srv2._seed_for({})) != (a, b)


def _serial_chain(seed, burn):
    """The chain admission ran eagerly before it became one device
    call: the reference the group function is held to."""
    key = jax.random.PRNGKey(seed)
    for _ in range(burn):
        key = jax.random.split(key)[0]
    return np.asarray(key)


class TestAdmissionKeys:
    """A group's initial sampling keys come from ONE compiled call
    (`ServingEngine._initial_rngs`), bit-identical to the serial chain
    `PRNGKey(seed)` -> `split(...)[0]` x burn, with the burn count as
    data: one compile per batch bucket, none per burn count, and no
    eager `jax.random` dispatch between admission and prefill."""

    # 3000000019 does not fit 32 signed bits: PRNGKey wraps it, and so
    # must the group function
    @pytest.mark.parametrize("seed", [0, 7, 3000000019])
    @pytest.mark.parametrize("burn", range(16))
    def test_group_rows_equal_serial_chain(self, seed, burn):
        plen = 16 + burn  # one full PREFILL_BUCKET, then `burn` steps
        assert ServingEngine._rng_burn(plen) == burn
        want = _serial_chain(seed, burn)
        # a neighbour row with another seed and another burn count
        o_seed, o_plen = seed + 1, 16 + (burn + 5) % 16
        o_want = _serial_chain(o_seed, ServingEngine._rng_burn(o_plen))
        one = np.asarray(ServingEngine._initial_rngs([seed], [plen]))
        two = np.asarray(ServingEngine._initial_rngs([seed, o_seed],
                                                     [plen, o_plen]))
        # the batch-bucket pad row replicates row 0
        pad = np.asarray(ServingEngine._initial_rngs([seed, seed],
                                                     [plen, plen]))
        assert one.shape == (1, 2) and one.dtype == want.dtype
        np.testing.assert_array_equal(one[0], want)
        np.testing.assert_array_equal(two, np.stack([want, o_want]))
        np.testing.assert_array_equal(pad, np.stack([want, want]))
        np.testing.assert_array_equal(
            np.asarray(ServingEngine._initial_rng(seed, plen)), want)
        if 0 < burn < 15:  # under one bucket all but the first token burn
            np.testing.assert_array_equal(
                np.asarray(ServingEngine._initial_rng(seed, burn + 1)),
                want)

    def test_one_compile_per_batch_bucket_and_no_eager_split(
            self, tiny_model, monkeypatch):
        from megatron_tpu.serving import engine as engine_mod
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        sampling = SamplingOptions(temperature=0.9, top_k=5)
        # lengths 17..31 share the padded length 32 and differ in burn
        # count; the waves admit as groups of 2, 1, 2 and 1
        waves = [(17, 20), (25,), (18, 31), (29,)]
        rs = np.random.RandomState(11)
        prompts = {n: rs.randint(1, 96, n).tolist()
                   for wave in waves for n in wave}
        real_split = jax.random.split

        def traced_split_only(key, *a, **kw):
            assert isinstance(key, jax.core.Tracer), (
                "eager jax.random.split on the engine thread")
            return real_split(key, *a, **kw)

        engine_mod._burned_keys_jit.clear_cache()
        outs = {}
        eng = ServingEngine(gen, ServingConfig(
            num_slots=2, max_queue=8, max_len=64, prefill_max_batch=2))
        try:
            # undone before the serial oracle below splits eagerly
            with monkeypatch.context() as m:
                m.setattr(jax.random, "split", traced_split_only)
                for wave in waves:
                    with eng._cond:  # the loop sees the wave whole
                        reqs = [eng.submit(prompts[n], 4, sampling,
                                           seed=n) for n in wave]
                    for n, r in zip(wave, reqs):
                        outs[n] = r.result(timeout=300)[0]
            snap = eng.metrics.snapshot()
        finally:
            eng.close()
        assert snap["engine_restarts"] == 0
        assert snap["prefill_calls"] == len(waves)
        assert snap["prefill_prompts"] == len(prompts)
        # batch buckets 2 and 1: six burn counts compiled nothing more
        assert engine_mod._burned_keys_jit._cache_size() == 2
        for n, toks in outs.items():
            want_toks, want_lens, _ = gen.generate(
                [prompts[n]], 4, sampling=SamplingParams(
                    temperature=0.9, top_k=5), seed=n)
            assert toks == want_toks[0, :want_lens[0]].tolist(), n


class TestSLOAdmission:
    """SLO-aware admission (scheduler units): the queue orders by
    (priority desc, deadline asc, arrival), early shedding fails fast
    with a retryable error + backoff hint, and requeue (the preemption
    re-admission path) bypasses the bound and keeps arrival order."""

    def _sched(self, **kw):
        from megatron_tpu.serving.scheduler import AdmissionScheduler
        base = dict(max_queue=16, max_total_len=64, num_slots=2)
        base.update(kw)
        return AdmissionScheduler(**base)

    def _req(self, priority=0, deadline_s=None, plen=2):
        return GenRequest(list(range(1, plen + 1)), 4,
                          priority=priority, deadline_s=deadline_s)

    def test_priority_then_edf_then_fifo(self):
        s = self._sched()
        r_low = self._req(priority=0)
        r_hi_late = self._req(priority=1, deadline_s=50.0)
        r_hi_soon = self._req(priority=1, deadline_s=1.0)
        r_low_soon = self._req(priority=0, deadline_s=0.5)
        for r in (r_low, r_hi_late, r_hi_soon, r_low_soon):
            s.submit(r)
        got = s.pop_ready(10)
        # priority first; EDF within a level; deadline-less last (FIFO)
        assert got == [r_hi_soon, r_hi_late, r_low_soon, r_low]
        assert s.peek_priority() is None

    def test_peek_priority_skips_cancelled(self):
        s = self._sched()
        hi, low = self._req(priority=3), self._req(priority=1)
        s.submit(hi), s.submit(low)
        assert s.peek_priority() == 3
        hi.cancel()
        assert s.peek_priority() == 1

    def test_shed_requires_service_sample_then_sheds(self):
        from megatron_tpu.serving import OverloadShedError
        s = self._sched(shed_on_overload=True, num_slots=1)
        s.active_fn = lambda: 1
        # never sheds blind: no completion observed yet
        s.submit(self._req(deadline_s=0.001))
        s.observe_service(10.0)  # one slow completion observed
        with pytest.raises(OverloadShedError) as ei:
            s.submit(self._req(deadline_s=0.1))
        assert ei.value.retry_after >= 1
        assert ei.value.queue_depth == 1
        # a deadline the estimate can meet is still admitted
        s.submit(self._req(deadline_s=3600.0))
        assert s.depth() == 2

    def test_queue_full_carries_backoff_hint(self):
        s = self._sched(max_queue=2)
        s.submit(self._req()), s.submit(self._req())
        with pytest.raises(QueueFullError) as ei:
            s.submit(self._req())
        assert ei.value.queue_depth == 2
        assert ei.value.retry_after >= 1

    def test_requeue_bypasses_bound_and_keeps_arrival_order(self):
        s = self._sched(max_queue=2)
        victim = self._req()     # earliest arrival id
        later = self._req()
        s.submit(later), s.submit(self._req())  # queue now full
        assert s.requeue(victim)  # a victim is never bounced
        assert s.depth() == 3
        # same priority class: the requeued victim's ORIGINAL arrival
        # id puts it ahead of later arrivals
        assert s.pop_ready(1) == [victim]

    def test_requeue_on_closed_scheduler_fails_503(self):
        s = self._sched()
        s.close()
        r = self._req()
        assert not s.requeue(r)
        with pytest.raises(ServiceUnavailableError):
            r.result(timeout=1)

    def test_drop_expired_per_request_deadline_overrides_default(self):
        s = self._sched()
        tight = self._req(deadline_s=0.001)
        slack = self._req(deadline_s=60.0)
        inherit = self._req()  # inherits the default passed to drop
        for r in (tight, slack, inherit):
            s.submit(r)
        expired = s.drop_expired(30.0, time.monotonic() + 1.0)
        assert expired == [tight]
        assert s.depth() == 2
        with pytest.raises(Exception, match="deadline"):
            tight.result(timeout=1)

    def test_clear_parked_drops_device_refs(self):
        s = self._sched()
        r = self._req()
        r.parked = ("sub", "logits")
        s.submit(r)
        assert s.parked_count() == 1
        assert s.clear_parked() == 1
        assert r.parked is None and s.parked_count() == 0

    def test_new_overload_counters_in_fresh_snapshot(self):
        snap = ServingMetrics().snapshot()
        for key in ("requests_shed", "preemptions", "engine_restarts",
                    "nonfinite_logit_fails"):
            assert snap[key] == 0
        for key in ("queue_wait_p95_ms", "queue_wait_p99_ms",
                    "host_syncs_per_step", "prompts_per_prefill"):
            assert snap[key] == 0.0


class TestPreemption:
    """Tentpole acceptance: a request preempted mid-decode and resumed
    from its retained (parked) KV emits the IDENTICAL token sequence as
    an un-preempted run — bf16 and int8 pools — and the decode step
    compiles exactly once across the preemption."""

    def _engine(self, gen, **kw):
        base = dict(num_slots=1, max_queue=16, max_len=64,
                    priority_levels=2, preemption=True)
        base.update(kw)
        return ServingEngine(gen, ServingConfig(**base))

    @pytest.mark.parametrize("kv_dtype", [None, "int8"])
    def test_preempted_resume_token_exact_single_compile(self, tiny_model,
                                                         kv_dtype):
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0,
                        kv_cache_dtype=(jnp.int8 if kv_dtype else
                                        jnp.bfloat16))
        prompt, n, seed = [5, 17, 3, 42], 16, 9
        sampling = SamplingOptions(temperature=0.9, top_k=5)
        with self._engine(gen) as eng:
            victim = eng.submit(prompt, n, sampling, seed=seed,
                                priority=0)
            # let it get properly mid-decode before the preemptor lands
            t0 = time.monotonic()
            while len(victim.generated) < 2 and not victim.done():
                time.sleep(0.002)
                assert time.monotonic() - t0 < 60
            hp = eng.submit([7, 8, 9], 4, sampling, seed=11, priority=1)
            hp_toks, _ = hp.result(timeout=300)
            toks, _ = victim.result(timeout=300)
            assert victim.preemptions >= 1  # it actually happened
            snap = eng.metrics.snapshot()
            assert snap["preemptions"] >= 1
            assert eng._decode_traces == 1  # preemption = bookkeeping
        want_toks, want_lens, _ = gen.generate(
            [prompt], n, sampling=SamplingParams(temperature=0.9,
                                                 top_k=5), seed=seed)
        assert toks == want_toks[0, :want_lens[0]].tolist()
        want_hp, hp_lens, _ = gen.generate(
            [[7, 8, 9]], 4, sampling=SamplingParams(temperature=0.9,
                                                    top_k=5), seed=11)
        assert hp_toks == want_hp[0, :hp_lens[0]].tolist()

    def test_replay_fallback_token_exact_after_parked_drop(self,
                                                           tiny_model):
        """When the parked KV is dropped (engine restart / park
        budget), the victim replays its effective prompt through
        prefill — still token-exact: the host-side PRNG copy carries
        the decode chain across the gap."""
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        prompt, n, seed = [5, 17, 3, 42], 12, 13
        sampling = SamplingOptions(temperature=0.9, top_k=5)
        with self._engine(gen) as eng:
            victim = eng.submit(prompt, n, sampling, seed=seed,
                                priority=0)
            t0 = time.monotonic()
            while len(victim.generated) < 2 and not victim.done():
                time.sleep(0.002)
                assert time.monotonic() - t0 < 60
            hp = eng.submit([7, 8, 9], 8, sampling, seed=11, priority=1)
            # wait for the preemption, then drop the parked device refs
            # (the engine-restart path) while the victim is queued
            while victim.preemptions == 0 and not victim.done():
                time.sleep(0.002)
                assert time.monotonic() - t0 < 60
            dropped = eng.scheduler.clear_parked()
            hp.result(timeout=300)
            toks, _ = victim.result(timeout=300)
            assert victim.preemptions >= 1
            assert dropped >= 1  # the fallback actually exercised
        want_toks, want_lens, _ = gen.generate(
            [prompt], n, sampling=SamplingParams(temperature=0.9,
                                                 top_k=5), seed=seed)
        assert toks == want_toks[0, :want_lens[0]].tolist()

    def test_preemption_prefers_lowest_priority_youngest(self,
                                                         tiny_model):
        """With two running slots, the LOWEST-priority (tie: youngest)
        one is evicted; an equal-or-higher-priority arrival never
        preempts."""
        params, cfg = tiny_model
        # eos_id=-1: no early EOS, so both victims keep decoding until
        # max_new — the preemption window is deterministic, not a race
        # against sampling luck
        gen = Generator(params, cfg, eos_id=-1, pad_id=0)
        sampling = SamplingOptions(temperature=0.8)
        with self._engine(gen, num_slots=2, priority_levels=3) as eng:
            mid = eng.submit([5, 6, 7], 48, sampling, seed=1, priority=1)
            low = eng.submit([8, 9], 48, sampling, seed=2, priority=0)
            t0 = time.monotonic()
            while (len(mid.generated) < 1 or len(low.generated) < 1):
                time.sleep(0.002)
                assert time.monotonic() - t0 < 60
            # same priority as `low`: must NOT preempt (it queues);
            # progress-based wait — several iterations pass untouched
            peer = eng.submit([1, 2], 2, sampling, seed=3, priority=0)
            mark = len(low.generated)
            while len(low.generated) < mark + 3 and not low.done():
                time.sleep(0.002)
                assert time.monotonic() - t0 < 60
            assert low.preemptions == 0 and mid.preemptions == 0
            hi = eng.submit([3, 4], 2, sampling, seed=4, priority=2)
            for r in (hi, peer, mid, low):
                r.result(timeout=300)
            assert low.preemptions >= 1  # lowest priority was the victim
            assert mid.preemptions == 0


class TestDeadlineMidChunkedPrefill:
    """Satellite: a request whose deadline expires while MID-chunked-
    prefill (the PR 5 pendings path) resolves 504 and its sub-cache
    slot is reclaimed — interleaved with live decode that keeps
    running."""

    def test_expiry_mid_chunk_resolves_504_and_reclaims(self,
                                                        tiny_model):
        from megatron_tpu.serving import DeadlineExceededError
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        eng = ServingEngine(gen, ServingConfig(
            num_slots=2, max_queue=8, max_len=64, prefill_chunk=4),
            start=False)
        try:
            live = eng.submit([3, 4], 6, SamplingOptions(temperature=0.9,
                                                         top_k=5),
                              seed=1)
            # 12 tokens -> 3 chunks; a deadline that expires mid-chunk
            slow = eng.submit(list(range(1, 13)), 4,
                              SamplingOptions(temperature=0.0),
                              deadline_s=0.05)
            eng._admit()
            assert len(eng._prefilling) == 1
            assert eng.pool.free_count() == 0
            eng._advance_prefill()          # chunk 1 of 3 lands
            assert eng._prefilling[0].pos == 4
            eng._step()                     # live decode interleaves
            assert len(live.generated) == 1
            time.sleep(0.08)                # the deadline passes
            eng._reap_expired()
            assert slow.done() and not eng._prefilling
            with pytest.raises(DeadlineExceededError):
                slow.result(timeout=1)
            assert eng.pool.free_count() == 1  # sub-cache slot reclaimed
            assert eng.metrics.snapshot()["requests_expired"] == 1
            # the live request decodes on to completion, token-exact
            while not live.done():
                eng._reap_expired()
                eng._step()
            toks, _ = live.result(timeout=1)
        finally:
            eng.close()
        want, lens, _ = gen.generate(
            [[3, 4]], 6, sampling=SamplingParams(temperature=0.9,
                                                 top_k=5), seed=1)
        assert toks == want[0, :lens[0]].tolist()


class TestEngineSupervisor:
    """Supervisor contracts (chaos tier): a crashed step restarts the
    loop and fails only what it must; a crash loop trips the breaker;
    a wedged iteration is detected by the watchdog and recovered; a
    NaN-poisoned slot fails one REQUEST, not the engine."""

    pytestmark = pytest.mark.chaos

    def test_step_crash_restarts_and_serves_queued(self, tiny_model):
        from megatron_tpu.resilience import (FaultInjector,
                                             use_fault_injector)
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        sampling = SamplingOptions(temperature=0.9, top_k=5)
        with ServingEngine(gen, ServingConfig(
                num_slots=1, max_queue=8, max_len=64,
                max_engine_restarts=2)) as eng:
            eng.generate([9, 9], 2, sampling, seed=0)  # warm compiles
            with use_fault_injector(FaultInjector(
                    serve_crash_calls={1})):
                victim = eng.submit([1, 2, 3], 6, sampling, seed=1)
                queued = eng.submit([4, 5], 4, sampling, seed=2)
                with pytest.raises(RuntimeError, match="engine step"):
                    victim.result(timeout=120)
                toks, _ = queued.result(timeout=120)
            snap = eng.metrics.snapshot()
            health = eng.health()
            assert snap["engine_restarts"] == 1
            assert health["healthy"] and health["state"] == "running"
        # the queued survivor is served token-exact after the restart
        want, lens, _ = gen.generate(
            [[4, 5]], 4, sampling=SamplingParams(temperature=0.9,
                                                 top_k=5), seed=2)
        assert toks == want[0, :lens[0]].tolist()

    def test_crash_loop_trips_breaker_and_503s(self, tiny_model):
        from megatron_tpu.resilience import (FaultInjector,
                                             use_fault_injector)
        from megatron_tpu.serving import EngineUnhealthyError
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        sampling = SamplingOptions(temperature=0.8)
        eng = ServingEngine(gen, ServingConfig(
            num_slots=1, max_queue=8, max_len=64,
            max_engine_restarts=0))
        try:
            eng.generate([9, 9], 2, sampling, seed=0)
            with use_fault_injector(FaultInjector(
                    serve_crash_calls=set(range(1, 32)))):
                slotted = eng.submit([1, 2], 4, sampling, seed=1)
                queued = eng.submit([3, 4], 4, sampling, seed=2)
                with pytest.raises(RuntimeError):
                    slotted.result(timeout=120)
                # queued work resolves 503 (typed, retryable) — never
                # stranded
                with pytest.raises(ServiceUnavailableError):
                    queued.result(timeout=120)
            health = eng.health()
            assert health["circuit_breaker_open"]
            assert not health["healthy"]
            assert health["state"] == "unhealthy"
            assert eng.metrics.snapshot()["engine_restarts"] == 0
            with pytest.raises(EngineUnhealthyError):
                eng.submit([5], 2, sampling, seed=3)
        finally:
            eng.close()

    def test_hung_iteration_watchdog_restart(self, tiny_model):
        from megatron_tpu.resilience import (FaultInjector,
                                             use_fault_injector)
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        sampling = SamplingOptions(temperature=0.8)
        with ServingEngine(gen, ServingConfig(
                num_slots=1, max_queue=8, max_len=64,
                engine_step_timeout_s=0.6, max_engine_restarts=2)) as eng:
            # warmup completes an iteration -> watchdog armed
            eng.generate([9, 9], 2, sampling, seed=0)
            with use_fault_injector(FaultInjector(
                    serve_delay_calls={1: 1.5})):
                victim = eng.submit([1, 2], 8, sampling, seed=1)
                t0 = time.monotonic()
                with pytest.raises(RuntimeError, match="hung"):
                    victim.result(timeout=120)
                detect_s = time.monotonic() - t0
                # failed by the watchdog DURING the stall, not after it
                assert detect_s < 1.5
                # the supervisor restarts once the stalled dispatch
                # returns; fresh work completes
                probe = eng.submit([3, 4], 2, sampling, seed=2)
                probe.result(timeout=120)
            snap = eng.metrics.snapshot()
            health = eng.health()
            assert snap["engine_restarts"] >= 1
            assert health["healthy"] and health["state"] == "running"

    def test_nonfinite_guard_fails_only_poisoned_slot(self, tiny_model):
        from megatron_tpu.resilience import (FaultInjector,
                                             use_fault_injector)
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        sampling = SamplingOptions(temperature=0.9, top_k=5)
        eng = ServingEngine(gen, ServingConfig(
            num_slots=2, max_queue=8, max_len=64), start=False)
        try:
            ok_req = eng.submit([5, 17, 3], 5, sampling, seed=1)
            poisoned = eng.submit([7, 8, 9], 5, sampling, seed=2)
            eng._admit()  # one batched prefill: slots 0 and 1
            with use_fault_injector(FaultInjector(
                    serve_nan_calls={2: 1})):  # step 2, active slot 1
                eng._step()  # both decode token 1
                assert len(poisoned.generated) == 1
                eng._step()  # slot 1's carried logits poisoned
            assert poisoned.done()
            with pytest.raises(RuntimeError, match="non-finite"):
                poisoned.result(timeout=1)
            assert eng.pool.free_count() == 1  # poisoned slot reclaimed
            assert not ok_req.done()  # the grid keeps decoding
            while not ok_req.done():
                eng._step()
            toks, _ = ok_req.result(timeout=1)
            snap = eng.metrics.snapshot()
            assert snap["nonfinite_logit_fails"] == 1
            assert snap["engine_restarts"] == 0  # request died, not engine
        finally:
            eng.close()
        want, lens, _ = gen.generate(
            [[5, 17, 3]], 5, sampling=SamplingParams(temperature=0.9,
                                                     top_k=5), seed=1)
        assert toks == want[0, :lens[0]].tolist()


class TestOverloadServerEndpoints:
    """Satellite: 429/503 responses carry Retry-After + queue depth;
    /healthz is the separate liveness probe; SLO payload fields
    validate and pass through."""

    @pytest.fixture(scope="class")
    def server(self, tiny_model):
        from megatron_tpu.inference.server import MegatronServer
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        srv = MegatronServer(gen, FakeTokenizer(),
                             serving=ServingConfig(num_slots=2,
                                                   max_queue=16,
                                                   max_len=64))
        yield srv
        srv.close()

    def test_healthz_healthy(self, server):
        status, body = server.healthz()
        assert status == 200
        assert body["healthy"] and body["state"] == "running"
        for key in ("circuit_breaker_open", "engine_restarts",
                    "active_slots", "queue_depth", "num_slots"):
            assert key in body

    def test_healthz_serial_mode(self, tiny_model):
        from megatron_tpu.inference.server import MegatronServer
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        srv = MegatronServer(gen, FakeTokenizer(),
                             serving=ServingConfig(serial_fallback=True))
        assert srv.healthz() == (200, {"healthy": True,
                                       "serving": "serial"})

    def test_429_carries_retry_after_and_queue_depth(self, tiny_model):
        from megatron_tpu.inference.server import MegatronServer
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        srv = MegatronServer(gen, FakeTokenizer(),
                             serving=ServingConfig(num_slots=1,
                                                   max_queue=1,
                                                   max_len=64))
        srv.engine.close()
        srv.engine = ServingEngine(
            gen, ServingConfig(num_slots=1, max_queue=1, max_len=64),
            start=False)
        try:
            srv.engine.submit([1, 2], 2)  # other traffic fills the queue
            status, body = srv.handle({"prompts": ["a"],
                                       "tokens_to_generate": 2})
            assert status == 429
            assert body["retry_after"] >= 1
            assert body["queue_depth"] == 1
            assert MegatronServer.response_headers(body) == {
                "Retry-After": str(body["retry_after"])}
        finally:
            srv.close()

    def test_unhealthy_engine_is_503_and_healthz_reports(self, tiny_model):
        from megatron_tpu.inference.server import MegatronServer
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        srv = MegatronServer(gen, FakeTokenizer(),
                             serving=ServingConfig(num_slots=1,
                                                   max_queue=4,
                                                   max_len=64))
        try:
            # breaker-open stand-in (the supervisor sets this after
            # max_engine_restarts — see TestEngineSupervisor)
            srv.engine._broken = "circuit breaker open after 2 restarts"
            status, body = srv.handle({"prompts": ["a"],
                                       "tokens_to_generate": 2})
            assert status == 503
            assert "circuit breaker" in body["message"]
            assert body["retry_after"] >= 1 and "queue_depth" in body
            hstatus, hbody = srv.healthz()
            assert hstatus == 503
            assert hbody["circuit_breaker_open"]
            assert not hbody["healthy"]
        finally:
            srv.engine._broken = None
            srv.close()

    def test_bad_slo_fields_are_400(self, server):
        for payload, frag in (
                ({"prompts": ["x"], "priority": []}, "priority"),
                ({"prompts": ["x"], "deadline_s": "soon"}, "deadline_s")):
            status, body = server.handle(payload)
            assert status == 400
            assert frag in body["message"]

    def test_slo_fields_pass_through(self, server):
        status, body = server.handle({"prompts": ["hi"],
                                      "tokens_to_generate": 2,
                                      "priority": 1,
                                      "deadline_s": 120.0})
        assert status == 200 and len(body["text"]) == 1

    def test_stdlib_healthz_endpoint(self, server):
        import json as _json
        import socket
        import urllib.request
        sock = socket.socket()
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
        sock.close()
        t = threading.Thread(target=server._run_stdlib,
                             args=("127.0.0.1", port), daemon=True)
        t.start()
        deadline = time.monotonic() + 10
        while True:
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/healthz",
                        timeout=5) as resp:
                    assert resp.status == 200
                    body = _json.loads(resp.read())
                break
            except OSError:
                assert time.monotonic() < deadline
                time.sleep(0.05)
        assert body["healthy"] and body["state"] == "running"

    def test_healthz_503_while_draining(self, tiny_model):
        """A draining replica rejects every new request — readiness
        must pull it out of rotation, not keep reporting 200."""
        from megatron_tpu.inference.server import MegatronServer
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        srv = MegatronServer(gen, FakeTokenizer(),
                             serving=ServingConfig(num_slots=1,
                                                   max_queue=4,
                                                   max_len=64))
        try:
            assert srv.healthz()[0] == 200
            assert srv.engine.drain(timeout=60)
            status, body = srv.healthz()
            assert status == 503
            assert body["state"] == "draining"
        finally:
            srv.close()

    def test_submit_after_close_is_typed_503(self, tiny_model):
        """The submit-vs-close race window (breaker trip / drain
        closing the queue between the engine's flag checks and the
        enqueue) resolves as a typed, retryable 503 — never a bare
        RuntimeError the HTTP layer would 500."""
        from megatron_tpu.serving import EngineUnhealthyError
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        eng = ServingEngine(gen, ServingConfig(num_slots=1, max_queue=4,
                                               max_len=64), start=False)
        try:
            eng.scheduler.close()  # the race, made deterministic
            with pytest.raises(EngineUnhealthyError):
                eng.scheduler.submit(GenRequest([1, 2], 2))
        finally:
            eng.close()

    def test_preemption_requires_priority_levels(self, tiny_model):
        """preemption with a single priority class is silently inert
        (every request clamps to 0) — rejected loudly at validate()
        AND by the engine constructor."""
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        with pytest.raises(AssertionError, match="priority_levels"):
            ServingConfig(preemption=True).validate(cfg)
        with pytest.raises(AssertionError, match="priority_levels"):
            ServingEngine(gen, ServingConfig(num_slots=1, max_len=64,
                                             preemption=True),
                          start=False)

    def test_nonfinite_or_nonpositive_deadline_is_400(self, server):
        """json.loads parses NaN/Infinity: a NaN deadline would be
        unreapable AND poison the scheduler's EDF sort key — rejected
        at the boundary, and GenRequest guards direct callers."""
        for bad in (float("nan"), float("inf"), 0.0, -1.0):
            status, body = server.handle({"prompts": ["x"],
                                          "tokens_to_generate": 1,
                                          "deadline_s": bad})
            assert status == 400, bad
            assert "deadline_s" in body["message"]
        with pytest.raises(AssertionError, match="deadline_s"):
            GenRequest([1, 2], 2, deadline_s=float("nan"))

    def test_restart_budget_decays_after_healthy_period(self, tiny_model):
        """Isolated recovered faults spread over a long-lived replica
        must not accumulate into a tripped breaker — consumed restarts
        age out after RESTART_DECAY_S of healthy operation."""
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        eng = ServingEngine(gen, ServingConfig(num_slots=1, max_len=64),
                            start=False)
        try:
            eng._restarts, eng._last_restart_t = 2, time.monotonic()
            eng._maybe_decay_restarts()
            assert eng._restarts == 2  # recent: still counts
            eng._last_restart_t = (time.monotonic()
                                   - eng.RESTART_DECAY_S - 1.0)
            eng._maybe_decay_restarts()
            assert eng._restarts == 0 and eng._last_restart_t is None
        finally:
            eng.close()

    def test_watchdog_covers_mid_admit_pops(self, tiny_model):
        """A wedge INSIDE a batched group-prefill dispatch leaves its
        requests in neither _slot_req nor _prefilling — _on_hang must
        still fail them (no stranded futures), via the _admitting
        alias."""
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        eng = ServingEngine(gen, ServingConfig(
            num_slots=2, max_queue=8, max_len=64,
            engine_step_timeout_s=30.0), start=False)
        try:
            r = eng.submit([1, 2, 3], 4)
            orig, seen = eng._prefill, {}

            def wedged(*a):
                # the watchdog fires while this dispatch is in flight
                eng._on_hang()
                seen["resolved_during_wedge"] = r.done()
                return orig(*a)

            eng._prefill = wedged
            eng._admit()
            assert seen["resolved_during_wedge"] is True
            with pytest.raises(RuntimeError, match="hung"):
                r.result(timeout=1)
            assert eng._admitting == []  # cleared after the pass
        finally:
            eng.close()

    def test_requeued_group_admission_records_wait_once(self,
                                                        tiny_model):
        """A restart-requeued request re-entering through the batched
        group path must not push a second queue-wait sample (the
        first-admission guard _start_pending/_resume_parked already
        have)."""
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        eng = ServingEngine(gen, ServingConfig(num_slots=1, max_len=64),
                            start=False)
        try:
            r = eng.submit([1, 2], 2)
            r.mark_admitted()  # a pre-restart admission already happened
            before = eng.metrics.requests.written
            eng._admit()       # groupable path (no chunk, no hit)
            assert eng._slot_req[0] is r  # it WAS re-admitted
            assert eng.metrics.requests.written == before  # no resample
        finally:
            eng.close()


class TestSpeculativeDecode:
    """--speculative_k acceptance (ISSUE 8): greedy output is
    token-exact vs the non-speculative engine AND the serial path for
    bf16 and int8 pools; the decode+verify pair compiles exactly once
    per k; stochastic rows are distribution-correct rejection sampling
    whose accepted prefixes replay bit-exact against a serial (batch-1)
    recomputation of the verify logits; the verify window clamps at
    capacity; and draft state is droppable (preemption composes)."""

    def _serial(self, gen, prompt, n, sampling, seed):
        sp = SamplingParams(temperature=sampling.temperature,
                            top_k=sampling.top_k, top_p=sampling.top_p)
        t, l, _ = gen.generate([prompt], n, sampling=sp, seed=seed)
        return t[0, :l[0]].tolist()

    # prompts with repeated n-grams so the self-drafting matcher has
    # something to look up (plus plain ones riding the same grid)
    SPEC_PROMPTS = [[5, 6, 7, 5, 6, 7, 5, 6], [9, 2, 9, 2, 9, 2],
                    [11, 12, 13, 14], [3, 4]]

    @pytest.mark.parametrize("kv_dtype", [None, "int8"])
    def test_greedy_token_exact_vs_nonspec_and_serial(self, tiny_model,
                                                      kv_dtype):
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0,
                        kv_cache_dtype=(jnp.int8 if kv_dtype
                                        else jnp.bfloat16))
        sampling = SamplingOptions(temperature=0.0)
        outs = {}
        for k in (0, 4):
            with ServingEngine(gen, ServingConfig(
                    num_slots=3, max_queue=32, max_len=64,
                    speculative_k=k)) as eng:
                reqs = [eng.submit(p, 16, sampling, seed=0)
                        for p in self.SPEC_PROMPTS]
                outs[k] = [r.result(timeout=300)[0] for r in reqs]
                if k:
                    snap = eng.metrics.snapshot()
                    assert snap["spec_rounds"] >= 1
                    assert snap["draft_tokens"] >= 1
                    # the drafter actually pays off on repetitive rows
                    assert snap["accepted_tokens"] >= 1
                    # single-compile pin: the decode+verify PAIR
                    assert eng._decode_traces == 1
                    assert eng._verify_traces == 1
        assert outs[4] == outs[0]
        for p, toks in zip(self.SPEC_PROMPTS, outs[4]):
            assert toks == self._serial(gen, p, 16, sampling, 0), p

    def test_composes_with_decode_sync_interval(self, tiny_model):
        """K-chained verify rounds: accept counts and the residual
        carry stay on device between syncs — greedy output identical
        at K=1 and K=3, and still identical to serial."""
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        sampling = SamplingOptions(temperature=0.0)
        outs = {}
        for K in (1, 3):
            with ServingEngine(gen, ServingConfig(
                    num_slots=3, max_queue=32, max_len=64,
                    speculative_k=2, decode_sync_interval=K)) as eng:
                reqs = [eng.submit(p, 12, sampling, seed=0)
                        for p in self.SPEC_PROMPTS]
                outs[K] = [r.result(timeout=300)[0] for r in reqs]
                assert eng._verify_traces <= 1
        assert outs[3] == outs[1]
        for p, toks in zip(self.SPEC_PROMPTS, outs[1]):
            assert toks == self._serial(gen, p, 12, sampling, 0), p

    @pytest.mark.parametrize("plen", [27, 28, 30, 31])
    def test_capacity_boundary_clamps_verify_window(self, tiny_model,
                                                    plen):
        """Slots at length cap-k-1 .. cap-1: the verify window must
        clamp so nothing writes past max_len-1, accepted counts stop at
        the region edge, and the output fills the budget token-exactly
        (same clamp the K-chained decode uses for idle rows)."""
        params, cfg = tiny_model
        # eos_id=-1: rows decode all the way to the capacity boundary
        gen = Generator(params, cfg, eos_id=-1, pad_id=0)
        max_len, k = 32, 4
        prompt = [(i % 90) + 1 for i in range(plen)]
        # repetitive tail so drafts really are proposed near the edge
        prompt[-6:] = [7, 8, 7, 8, 7, 8]
        n = max_len - plen  # fills the slot region exactly
        sampling = SamplingOptions(temperature=0.0)
        with ServingEngine(gen, ServingConfig(
                num_slots=2, max_queue=8, max_len=max_len,
                speculative_k=k, decode_sync_interval=2)) as eng:
            # a second, shorter row rides the same grid (idle/finishing
            # rows cross the window boundary while row 0 clamps)
            r0 = eng.submit(prompt, n, sampling, seed=0)
            r1 = eng.submit([5, 6, 5, 6], 3, sampling, seed=0)
            toks0, _ = r0.result(timeout=300)
            r1.result(timeout=300)
        assert len(toks0) == max_len  # filled to capacity, not past
        assert toks0 == self._serial(gen, prompt, n, sampling, 0)

    def test_stochastic_stream_independent_of_grid(self, tiny_model):
        """A request's sampled stream depends only on its own seed,
        drafts, and accepts — never on what OTHER slots proposed: a
        1-slot engine (serial verify) and a 4-slot engine (grid-batched
        verify) emit identical tokens. The two verifies are different
        XLA programs (batch 1 against batch 4) and may round a float32
        log-probability differently in its last place, so the logprobs
        are compared to 1e-5 and not bit for bit."""
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        sampling = SamplingOptions(temperature=0.9, top_k=5)

        def run(slots, serially):
            outs = []
            with ServingEngine(gen, ServingConfig(
                    num_slots=slots, max_queue=32, max_len=64,
                    speculative_k=3)) as eng:
                if serially:
                    for i, p in enumerate(self.SPEC_PROMPTS):
                        outs.append(eng.submit(
                            p, 10, sampling,
                            seed=100 + i).result(timeout=300))
                else:
                    reqs = [eng.submit(p, 10, sampling, seed=100 + i)
                            for i, p in enumerate(self.SPEC_PROMPTS)]
                    outs = [r.result(timeout=300) for r in reqs]
            return outs

        one = run(1, True)
        grid = run(4, False)
        assert [toks for toks, _ in one] == [toks for toks, _ in grid]
        for (_, lps_one), (_, lps_grid) in zip(one, grid):
            assert len(lps_one) == len(lps_grid)
            np.testing.assert_allclose(lps_one, lps_grid, rtol=0, atol=1e-5)

    def test_accepted_prefix_bitexact_vs_serial_verify_replay(
            self, tiny_model):
        """The stochastic pin: replay the engine's recorded rounds
        through a SERIAL batch-1 recomputation of the verify pipeline —
        same prefill shapes, same split/fold key schedule, same
        processed-probability acceptance — and require bit-exact
        agreement on every sampled token and accept count."""
        from megatron_tpu.inference.generation import (init_kv_caches,
                                                       verify_tokens)
        from megatron_tpu.inference.sampling import (sample_batched,
                                                     verify_draft_probs)
        from megatron_tpu.models import language_model as lm2
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        prompt, n, seed, k, max_len = [5, 6, 7, 5, 6, 7, 5], 10, 7, 3, 64
        sampling = SamplingOptions(temperature=0.9, top_k=5)
        with ServingEngine(gen, ServingConfig(
                num_slots=1, max_queue=8, max_len=max_len,
                speculative_k=k), start=False) as eng:
            eng._spec_trace = []
            eng._thread.start()
            req = eng.submit(prompt, n, sampling, seed=seed)
            toks, _ = req.result(timeout=300)
            trace = list(eng._spec_trace)
        assert any(acc is not None for _, acc in trace), (
            "no verify round ran — the pin tested nothing")

        # --- serial replay -------------------------------------------
        plen = len(prompt)
        padded = -(-plen // 16) * 16  # the engine's prefill bucket
        arr = np.full((1, padded), 0, np.int32)
        arr[0, :plen] = prompt
        caches = init_kv_caches(cfg, 1, max_len, dtype=jnp.bfloat16)
        logits, caches = lm2.model_forward(
            params, jnp.asarray(arr), cfg, kv_caches=caches,
            rope=gen.rope, logits_dtype=jnp.float32)
        carried = logits[0, plen - 1]
        rng = ServingEngine._initial_rng(seed, plen)
        temps = jnp.asarray([sampling.temperature], jnp.float32)
        tks = jnp.asarray([sampling.top_k], jnp.int32)
        tps = jnp.asarray([sampling.top_p], jnp.float32)
        length, reject, committed = plen, -1, list(prompt)
        for w_toks, acc in trace:
            rng, step = jax.random.split(rng)
            t0 = sample_batched(
                step[None], carried[None], temperature=temps,
                top_k=tks, top_p=tps, vocab_size=cfg.vocab_size,
                banned=jnp.asarray([reject], jnp.int32))
            w = np.atleast_2d(np.asarray(w_toks))  # [1, 1] or [1, k+1]
            assert int(t0[0]) == int(w[0, 0]), "t0 diverged"
            logits, caches = verify_tokens(
                params, jnp.asarray(w), caches, cfg, rope=gen.rope,
                lengths=jnp.asarray([length], jnp.int32),
                max_len=max_len)
            if acc is None:  # fallback decode round
                committed.append(int(w[0, 0]))
                carried, length, reject = logits[0, 0], length + 1, -1
                continue
            drafts = w[:, 1:].astype(np.int32)
            probs, _ = verify_draft_probs(
                logits[:, :k], jnp.asarray(drafts), temperature=temps,
                top_k=tks, top_p=tps, vocab_size=cfg.vocab_size)
            u = np.asarray([float(jax.random.uniform(
                jax.random.fold_in(step, i))) for i in range(1, k + 1)])
            allow = (length + 1 + np.arange(k)) <= max_len - 1
            ok = (u < np.asarray(probs)[0]) & (drafts[0] >= 0) & allow
            a = 0
            while a < k and ok[a]:
                a += 1
            assert a == int(np.asarray(acc)[0]), "accept count diverged"
            committed.extend(int(t) for t in w[0, :1 + a])
            carried = logits[0, a]
            reject = (int(drafts[0, a])
                      if a < k and allow[a] and drafts[0, a] >= 0
                      else -1)
            length += 1 + a
        # the request's tokens are exactly the replay's committed
        # prefix (the last round may overshoot EOS/budget)
        assert toks == committed[:len(toks)]

    def test_spec_with_preemption_token_exact(self, tiny_model):
        """Draft state is droppable: a greedy request preempted
        mid-stream under --speculative_k resumes token-exact (only
        committed tokens park; drafts re-propose from history)."""
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=-1, pad_id=0)
        sampling = SamplingOptions(temperature=0.0)
        prompt, n = [5, 6, 7, 5, 6, 7], 24
        with ServingEngine(gen, ServingConfig(
                num_slots=1, max_queue=16, max_len=64,
                priority_levels=2, preemption=True,
                speculative_k=3)) as eng:
            victim = eng.submit(prompt, n, sampling, seed=1, priority=0)
            t0 = time.monotonic()
            while len(victim.generated) < 2 and not victim.done():
                time.sleep(0.002)
                assert time.monotonic() - t0 < 60
            hp = eng.submit([9, 2, 9, 2], 4, sampling, seed=2,
                            priority=1)
            hp_toks, _ = hp.result(timeout=300)
            toks, _ = victim.result(timeout=300)
            assert victim.preemptions >= 1
            assert eng._decode_traces == 1
            assert eng._verify_traces <= 1
        assert toks == self._serial(gen, prompt, n, sampling, 1)
        assert hp_toks == self._serial(gen, [9, 2, 9, 2], 4, sampling,
                                       2)

    def test_empty_drafter_falls_back_bit_identical_to_nonspec(
            self, tiny_model):
        """A drafter with nothing to propose must cost nothing but the
        fallback counter: the spec engine's stream — greedy AND
        stochastic — is bit-identical to the non-speculative engine's
        (the plain decode step consumes the same split keys and the
        banned<0 path is bit-exact)."""
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)

        class NoDraft:
            def propose(self, tokens, n):
                return []

        sampling = SamplingOptions(temperature=1.1, top_p=0.8)
        outs = {}
        for spec in (0, 4):
            with ServingEngine(
                    gen, ServingConfig(num_slots=2, max_queue=16,
                                       max_len=64, speculative_k=spec),
                    drafter=NoDraft() if spec else None) as eng:
                reqs = [eng.submit(p, 8, sampling, seed=200 + i)
                        for i, p in enumerate(self.SPEC_PROMPTS)]
                outs[spec] = [r.result(timeout=300) for r in reqs]
                if spec:
                    snap = eng.metrics.snapshot()
                    assert snap["spec_fallback_steps"] >= 1
                    assert snap["spec_rounds"] == 0
                    assert eng._verify_traces == 0
        assert outs[4] == outs[0]

    def test_validate_rejects_rolling_keeps_flash_int8(self):
        """Speculative decoding stays excluded on ROLLING pools (with
        or without kv_block_size — a rejected draft's ring write
        already evicted the position the rewind would need), but the
        old flash-int8 exclusion is erased (the int8 prefill takes the
        cached dot path, so verify windows read the same values)."""
        cfg_roll = tiny_cfg(sliding_window=16, attention_impl="flash",
                            seq_length=64)
        with pytest.raises(AssertionError, match="ROLLING"):
            ServingConfig(speculative_k=4).validate(cfg_roll)
        with pytest.raises(AssertionError, match="ROLLING"):
            ServingConfig(speculative_k=4,
                          kv_block_size=8).validate(cfg_roll)
        cfg_flash = tiny_cfg(attention_impl="flash")
        ServingConfig(speculative_k=4,
                      kv_dtype="int8").validate(cfg_flash)
        # engine re-assert on the RESOLVED pool layout, even without
        # validate()
        params = lm.model_init(jax.random.PRNGKey(0), cfg_roll)
        gen = Generator(params, cfg_roll, eos_id=0, pad_id=0)
        with pytest.raises(AssertionError, match="speculative_k"):
            ServingEngine(gen, ServingConfig(num_slots=2, max_len=64,
                                             speculative_k=4),
                          start=False)

    def test_spec_counters_in_base_schema(self):
        snap = ServingMetrics().snapshot()
        for key in ("spec_rounds", "draft_tokens", "accepted_tokens",
                    "spec_fallback_steps"):
            assert snap[key] == 0.0  # present before any traffic

    def test_ngram_drafter_and_grid_builder(self):
        from megatron_tpu.serving.spec_decode import (NO_DRAFT,
                                                      NGramDrafter,
                                                      build_draft_rounds)
        d = NGramDrafter(max_ngram=3)
        # trailing [7, 8] matched at the earlier occurrence -> proposes
        # its continuation
        assert d.propose([1, 7, 8, 9, 4, 7, 8], 2) == [9, 4]
        # longest n-gram wins over a shorter, more recent match
        assert d.propose([1, 2, 3, 9, 5, 1, 2, 3], 1) == [9]
        assert d.propose([1, 2, 3], 2) == []  # no earlier occurrence
        assert d.propose([4], 2) == []        # history too short
        grids, any_real, guesses = build_draft_rounds(
            [[1, 7, 8, 9, 4, 7, 8], None], d, k=2, rounds=2)
        assert len(grids) == 2 and grids[0].shape == (2, 2)
        assert grids[0][0].tolist() == [4, 7]  # C[1:3] of [9,4,7,8,...]
        assert (grids[0][1] == NO_DRAFT).all()  # inactive row = filler
        assert any_real[0] is True
        # the host-known t0 guess the drafts were proposed after (C[0])
        # — grammar rows pre-walk their FSM along [guess, d1..dk]
        assert guesses[0].tolist() == [9, NO_DRAFT]


class TestBlockPoolUnits:
    """SlotKVPool block-mode accounting: refcounted free blocks,
    aliasing, row-less retention, trash map, gauges, and the pinned
    whole-region alloc order (the deque satellite)."""

    def test_whole_region_alloc_order_pinned(self, tiny_model):
        """Free slots come back FIFO in release order; exhausting the
        free list reclaims retained slots OLDEST-first (with exclude
        honored). This order is load-bearing for the prefix cache's
        LRU semantics — pin it."""
        _, cfg = tiny_model
        pool = SlotKVPool(cfg, 4, 32)
        assert [pool.alloc() for _ in range(4)] == [0, 1, 2, 3]
        pool.release(2)
        pool.release(0)
        assert pool.alloc() == 2 and pool.alloc() == 0  # FIFO
        pool.retain(3)
        pool.retain(1)
        reclaimed = []
        pool.on_reclaim = reclaimed.append
        assert pool.alloc(exclude=(3,)) == 1  # oldest outside exclude
        assert pool.alloc() == 3
        assert reclaimed == [1, 3]
        assert pool.alloc() is None

    def test_block_pool_refcounts_and_retention(self, tiny_model):
        _, cfg = tiny_model
        pool = SlotKVPool(cfg, 3, 32, block_size=8)  # 4 blocks/slot
        assert pool.blocks_enabled and pool.blocks_per_slot == 4
        assert pool.total_blocks == 13 and pool.TRASH == 12
        # a fresh row owns 4 blocks; its map installs eagerly
        s0, b0 = pool.alloc_row()
        assert sorted(b0) == list(range(4))
        assert list(pool._map[s0]) == b0
        # retention pins only the covered blocks (11 tokens -> 2) and
        # frees the row + tail immediately
        key = pool.retain_row(s0, 11, list(range(11)))
        assert key is not None and pool.entry(key).length == 11
        assert len(pool.entry(key).blocks) == 2
        assert len(pool._free_blocks) == 10  # 8 untouched + 2 tail
        assert (pool._map[s0] == pool.TRASH).all()
        assert pool.free_count() == 3
        # aliasing: a new row reuses a retained prefix block; only 3
        # fresh blocks leave the free pool
        alias = pool.entry(key).blocks[:1]
        s2, b2 = pool.alloc_row(alias=alias, install=False)
        assert b2[:1] == alias and pool._rc[alias[0]] == 2
        assert len(pool._free_blocks) == 7
        # the map stays on TRASH until install (idle-write protection)
        assert (pool._map[s2] == pool.TRASH).all()
        pool.install_row(s2, b2)
        assert list(pool._map[s2]) == b2
        # evicting the retained entry keeps the aliased block alive
        # (the row's ref) while its exclusive block frees
        reclaimed = []
        pool.on_reclaim = reclaimed.append
        pool._evict_retained()
        assert reclaimed == [key]
        assert pool._rc[alias[0]] == 1
        assert len(pool._free_blocks) == 8
        pool.release_row(s2)
        assert pool._rc[alias[0]] == 0
        assert len(pool._free_blocks) == 12

    def test_block_pressure_evicts_retained_lru(self, tiny_model):
        _, cfg = tiny_model
        pool = SlotKVPool(cfg, 2, 32, block_size=8)
        s0, _ = pool.alloc_row()
        k0 = pool.retain_row(s0, 8, list(range(8)))   # pins 1 block
        s1, _ = pool.alloc_row()
        k1 = pool.retain_row(s1, 8, list(range(8)))   # pins 1 block
        reclaimed = []
        pool.on_reclaim = reclaimed.append
        # 6 free blocks; two fresh rows need 8 -> oldest entry evicts
        pool.alloc_row()
        pool.alloc_row()
        assert reclaimed == [k0, k1]  # LRU order under pressure

    def test_free_count_reclaims_chained_retained_blocks(self,
                                                         tiny_model):
        """Liveness: multi-turn chains retain entries that ALIAS each
        other's blocks (rc >= 2 with no row holding them). free_count
        must count those as reclaimable — pop_ready(free_count()) is
        the only trigger that ever evicts retained entries, so
        undercounting would starve admission permanently even though
        evicting the chain frees a whole row."""
        _, cfg = tiny_model
        pool = SlotKVPool(cfg, 1, 32, block_size=8)  # 4 blocks, 1 row
        s0, _ = pool.alloc_row()
        k1 = pool.retain_row(s0, 16, list(range(16)))  # pins 2 blocks
        # turn 2 aliases turn 1's blocks and retains a longer chain
        alias = pool.entry(k1).blocks[:2]
        s1, b1 = pool.alloc_row(alias=alias)
        pool.retain_row(s1, 24, list(range(24)))  # pins alias + 1
        # every real block is now referenced ONLY by retained entries
        # (two of them at rc=2); nothing is exclusively-retained, yet
        # evicting the chain frees the whole row
        assert len(pool._free_blocks) == 1
        assert pool.free_count() == 1
        got = pool.alloc_row()  # must evict the chain and succeed
        assert got is not None

    def test_retained_limit_caps_entries(self, tiny_model):
        _, cfg = tiny_model
        pool = SlotKVPool(cfg, 3, 32, block_size=8, retained_limit=1)
        reclaimed = []
        pool.on_reclaim = reclaimed.append
        s0, _ = pool.alloc_row()
        k0 = pool.retain_row(s0, 8, list(range(8)))
        s1, _ = pool.alloc_row()
        pool.retain_row(s1, 8, list(range(8)))
        assert reclaimed == [k0] and pool.retained_count() == 1
        # limit 0: nothing retains, the row just frees
        pool0 = SlotKVPool(cfg, 2, 32, block_size=8, retained_limit=0)
        s, _ = pool0.alloc_row()
        assert pool0.retain_row(s, 8, list(range(8))) is None
        assert pool0.retained_count() == 0 and pool0.free_count() == 2

    def test_slot_nbytes_matches_block_pool(self, tiny_model):
        from megatron_tpu.serving.kv_pool import slot_nbytes
        _, cfg = tiny_model
        pool = SlotKVPool(cfg, 3, 64, block_size=16)
        per_slot = slot_nbytes(cfg, 64, block_size=16)
        # arena = slots * per-slot bytes + one trash block
        assert pool.nbytes() == 3 * per_slot + per_slot // 4
        # int8 pools include scale bytes
        pool8 = SlotKVPool(cfg, 2, 64, dtype=jnp.int8, block_size=16)
        per8 = slot_nbytes(cfg, 64, dtype=jnp.int8, block_size=16)
        assert pool8.nbytes() == 2 * per8 + per8 // 4

    def test_kv_gauges_modes(self, tiny_model):
        import numpy as np
        _, cfg = tiny_model
        bpt = SlotKVPool(cfg, 2, 32).bytes_per_token()
        # whole-region: reserved = used regions * cap
        pool = SlotKVPool(cfg, 2, 32)
        pool.alloc()
        used, ret, wasted = pool.kv_gauges(np.array([10, 0]))
        assert (used, ret) == (1, 0)
        assert wasted == (32 - 10) * bpt
        # blocks: reserved = allocated blocks * B; retention waste only
        # spans the entry's last partial block
        poolb = SlotKVPool(cfg, 2, 32, block_size=8)
        s0, _ = poolb.alloc_row()
        poolb.retain_row(s0, 11, list(range(11)))
        used, ret, wasted = poolb.kv_gauges(np.array([0, 0]))
        assert (used, ret) == (2, 2)
        assert wasted == (16 - 11) * bpt

    def test_validate_block_size_constraints(self):
        cfg = tiny_cfg()
        ServingConfig(max_len=64, kv_block_size=16).validate(cfg)
        with pytest.raises(AssertionError, match="divide"):
            ServingConfig(max_len=64, kv_block_size=24).validate(cfg)
        with pytest.raises(AssertionError, match="prefill_bucket"):
            ServingConfig(max_len=64, kv_block_size=8,
                          enable_prefix_cache=True).validate(cfg)
        # block_size >= cap degrades to whole-region mode
        pool = SlotKVPool(cfg, 2, 32, block_size=64)
        assert not pool.blocks_enabled

    def test_kv_gauges_in_metrics_schema(self):
        snap = ServingMetrics().snapshot()
        for key in ("kv_blocks_used", "kv_blocks_retained",
                    "kv_bytes_wasted"):
            assert snap[key] == 0.0  # present before any traffic

    @pytest.mark.parametrize("kv_dtype", [jnp.bfloat16, jnp.int8])
    def test_view_roundtrip_identity_with_duplicates(self, tiny_model,
                                                     kv_dtype):
        """Property pin for the determinism argument that
        kv_pool.scatter_view's docstring until now asserted only in
        prose: scatter_view(resolve_view(x)) == x BIT-EXACTLY, map
        duplicates included — the shared TRASH block (every idle row's
        whole map) and prefix blocks aliased into several slots. The
        gather reads a duplicated block identically into every view
        row that maps it, so the unordered scatter writes identical
        values back — the round trip can never lose or mix content.
        Random arena payloads, random alias structure, k/v AND int8
        scales, offsets ride through untouched."""
        from megatron_tpu.serving.kv_pool import (resolve_view,
                                                  scatter_view)
        _, cfg = tiny_model
        rs = np.random.RandomState(0)
        pool = SlotKVPool(cfg, 4, 32, dtype=kv_dtype, block_size=8)
        a = pool.caches.arena
        shape, dt = a.k.shape, a.k.dtype

        def payload():
            if dt == jnp.int8:
                return jnp.asarray(
                    rs.randint(-127, 127, shape), jnp.int8)
            return jnp.asarray(rs.randn(*shape), dt)

        arena = a._replace(
            k=payload(), v=payload(),
            offset=jnp.asarray(rs.randint(0, 32, a.offset.shape),
                               jnp.int32),
            k_scale=(None if a.k_scale is None else jnp.asarray(
                rs.rand(*a.k_scale.shape), jnp.float32)),
            v_scale=(None if a.v_scale is None else jnp.asarray(
                rs.rand(*a.v_scale.shape), jnp.float32)))
        # map with every duplicate flavor: slot 0 fully on TRASH
        # (idle), slots 1/2 aliasing a shared 2-block prefix, slot 3
        # partially trash + one block aliased THREE ways
        T = pool.TRASH
        bmap = np.array([[T, T, T, T],
                         [0, 1, 2, 3],
                         [0, 1, 4, 5],
                         [0, T, 6, 7]], np.int32)
        bkv = pool.caches._replace(arena=arena,
                                   map=jnp.asarray(bmap))
        out = scatter_view(bkv, resolve_view(bkv))
        for name in ("k", "v", "offset", "k_scale", "v_scale"):
            want = getattr(bkv.arena, name)
            got = getattr(out.arena, name)
            if want is None:
                assert got is None
                continue
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want),
                                          err_msg=name)
        np.testing.assert_array_equal(np.asarray(out.map), bmap)


@pytest.fixture(scope="module")
def block_model():
    cfg = tiny_cfg(seq_length=96, max_position_embeddings=96)
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    return params, cfg


class TestBlockPoolEngine:
    """The block-on-vs-off bit-exactness contract: the map resolve is
    pure data movement, so EVERY path — plain decode, prefix-hit,
    chunked prefill, preemption-resume, speculative — produces
    bit-identical seeded outputs with kv_block_size set vs not, for
    bf16 AND int8 pools, while decode + verify keep compiling exactly
    once. These extend the existing exactness pins (same workloads,
    same serial ground truth) to the block pool."""

    def _outs(self, gen, serving, prompts, n=8,
              sampling=SamplingOptions(temperature=0.9, top_k=5),
              trace_check=None, second_wave=None):
        with ServingEngine(gen, serving) as eng:
            reqs = [eng.submit(p, n, sampling, seed=i)
                    for i, p in enumerate(prompts)]
            outs = [r.result(timeout=300)[0] for r in reqs]
            if second_wave is not None:
                rr = [eng.submit(p, n, sampling, seed=100 + i)
                      for i, p in enumerate(second_wave)]
                outs += [r.result(timeout=300)[0] for r in rr]
            snap = eng.metrics.snapshot()
            if trace_check is not None:
                trace_check(eng)
        return outs, snap

    @pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
    def test_plain_decode_bit_identical_and_single_compile(
            self, block_model, kv_dtype):
        params, cfg = block_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)

        def pin(eng):
            assert eng._decode_traces == 1

        off, _ = self._outs(gen, ServingConfig(
            num_slots=3, max_len=96, kv_dtype=kv_dtype), PROMPTS)
        on, _ = self._outs(gen, ServingConfig(
            num_slots=3, max_len=96, kv_dtype=kv_dtype,
            kv_block_size=16), PROMPTS, trace_check=pin)
        assert on == off
        # and the serial ground truth still holds through blocks
        sp = SamplingParams(temperature=0.9, top_k=5)
        want, lens, _ = gen.generate([PROMPTS[0]], 8, sampling=sp, seed=0)
        assert on[0] == want[0, :lens[0]].tolist()

    @pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
    def test_prefix_and_chunked_bit_identical(self, block_model,
                                              kv_dtype):
        params, cfg = block_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        shared = list(range(2, 36))
        prompts = [shared + [40 + i, 50 + i, 60 + i] for i in range(6)]
        base, _ = self._outs(gen, ServingConfig(
            num_slots=3, max_len=96, kv_dtype=kv_dtype), prompts, n=6)
        for chunk in (None, 16):
            on, snap = self._outs(gen, ServingConfig(
                num_slots=3, max_len=96, kv_dtype=kv_dtype,
                kv_block_size=16, enable_prefix_cache=True,
                prefill_chunk=chunk), prompts, n=6)
            assert on == base, f"diverged with chunk={chunk}"
            assert snap["prefix_hits"] >= 1
            assert snap["prefill_tokens_saved"] > 0

    def test_retained_capacity_exceeds_slots(self, block_model):
        """THE capacity win: retained prefixes pin blocks, not grid
        rows (or whole cap regions), so far more sessions stay
        cloneable than the pool has slots. Five 1-block chat sessions
        through a 3-slot pool, turns submitted serially: whole-region
        retention LRU-thrashes (a retained sequence costs a full
        96-token region, at most num_slots survive, and every turn-2
        miss evicts another session) while the block pool keeps all
        five 16-token prefixes resident — every turn 2 hits. A prompt
        is a whole block by itself, so a session retains one whatever
        the drawn model emits (an early EOS included)."""
        params, cfg = block_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        greedy = SamplingOptions(temperature=0.0)
        prompts = [[10 + i] * 16 for i in range(5)]

        def run(block):
            turn2 = []
            with ServingEngine(gen, ServingConfig(
                    num_slots=3, max_len=96, kv_block_size=block,
                    enable_prefix_cache=True)) as eng:
                turn1 = [eng.generate(p, 4, greedy, seed=i)[0]
                         for i, p in enumerate(prompts)]  # serial
                retained_after_t1 = eng.pool.retained_count()
                for i, hist in enumerate(turn1):
                    turn2.append(eng.generate(hist + [88], 4, greedy,
                                              seed=100 + i)[0])
                snap = eng.metrics.snapshot()
            return turn1 + turn2, retained_after_t1, snap

        off, ret_off, snap_off = run(None)
        on, ret_on, snap_on = run(16)
        assert on == off  # hit-path outputs stay bit-identical
        # whole-region retention is bounded by the slot count; blocks
        # keep every session
        assert ret_off <= 3
        assert ret_on == len(prompts)
        # ...and turn 2 converts that into hits: all 5 for blocks,
        # none for whole-region (LRU thrash)
        assert snap_off["prefix_hits"] == 0
        assert snap_on["prefix_hits"] == len(prompts)
        assert snap_on["kv_blocks_retained"] > 0

    def test_burst_hits_on_recycled_running_slots(self, block_model):
        """Regression: slot ids flow through np.nonzero (np.int64) into
        evictions, the free-row deque, and eventually the prefix index
        as RUNNING-slot keys — which the hit path must still recognize
        as slots, not retained-prefix keys (a np.int64 once fell
        through `isinstance(src, int)` and crashed the engine loop
        with pool.entry(np.int64) == None under concurrent
        shared-prefix bursts). Drive chained retention + mixed bursts
        and require every request served with ZERO engine restarts."""
        params, cfg = block_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        greedy = SamplingOptions(temperature=0.0)
        rs = np.random.RandomState(0)
        with ServingEngine(gen, ServingConfig(
                num_slots=4, max_len=96, kv_block_size=16,
                enable_prefix_cache=True, max_queue=64)) as eng:
            hist = [h % 90 + 2 for h in range(40)]
            for _ in range(3):  # multi-turn chain retention
                hist = eng.generate(hist, 6, greedy, seed=1,
                                    timeout=300)[0] + [30]
            for _ in range(4):  # concurrent mixed bursts
                reqs = [eng.submit(
                    (hist[:rs.randint(5, len(hist))] if i % 2 else
                     rs.randint(2, 90, rs.randint(4, 40)).tolist()),
                    8, greedy, seed=i) for i in range(10)]
                for r in reqs:
                    r.result(timeout=300)
            snap = eng.metrics.snapshot()
        assert snap["engine_restarts"] == 0
        assert snap["requests_completed"] >= 43
        assert snap["prefix_hits"] >= 1

    @pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
    def test_preemption_resume_bit_identical(self, block_model,
                                             kv_dtype):
        params, cfg = block_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)

        def run(block):
            serving = ServingConfig(
                num_slots=1, max_len=96, kv_dtype=kv_dtype,
                kv_block_size=block, priority_levels=2, preemption=True)
            with ServingEngine(gen, serving) as eng:
                low = eng.submit([5, 6, 7, 8], 24,
                                 SamplingOptions(temperature=0.8,
                                                 top_k=5), seed=1,
                                 priority=0)
                t0 = time.monotonic()
                while len(low.generated) < 2 and not low.done():
                    time.sleep(0.002)
                    assert time.monotonic() - t0 < 60
                hi = eng.submit([50, 51], 4,
                                SamplingOptions(temperature=0.0),
                                seed=2, priority=1)
                hi_out = hi.result(timeout=300)[0]
                low_out = low.result(timeout=300)[0]
                pre = eng.metrics.snapshot()["preemptions"]
            return low_out, hi_out, pre

        l_off, h_off, p_off = run(None)
        l_on, h_on, p_on = run(16)
        assert p_on >= 1, "premise: preemption fired in the block arm"
        assert (l_on, h_on) == (l_off, h_off)

    @pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
    def test_speculative_bit_identical_and_single_verify_compile(
            self, block_model, kv_dtype):
        params, cfg = block_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        prompts = [[5, 17, 3, 42, 9, 9, 5, 17], [7, 8, 9, 7, 8, 9, 7],
                   [11, 12, 13, 11, 12]]

        def pin(eng):
            assert eng._decode_traces == 1
            assert eng._verify_traces == 1

        for temp in (0.0, 0.8):
            sampling = SamplingOptions(temperature=temp)
            off, s_off = self._outs(gen, ServingConfig(
                num_slots=3, max_len=96, kv_dtype=kv_dtype,
                speculative_k=4), prompts, n=10, sampling=sampling)
            on, s_on = self._outs(gen, ServingConfig(
                num_slots=3, max_len=96, kv_dtype=kv_dtype,
                speculative_k=4, kv_block_size=16), prompts, n=10,
                sampling=sampling, trace_check=pin)
            assert on == off, f"spec diverged at temperature={temp}"
            assert s_on["accepted_tokens"] == s_off["accepted_tokens"]
        # greedy spec ALSO matches the non-speculative engine (the
        # existing pin, extended through blocks)
        nospec, _ = self._outs(gen, ServingConfig(
            num_slots=3, max_len=96, kv_dtype=kv_dtype), prompts,
            n=10, sampling=SamplingOptions(temperature=0.0))
        spec, _ = self._outs(gen, ServingConfig(
            num_slots=3, max_len=96, kv_dtype=kv_dtype,
            kv_block_size=16, speculative_k=4), prompts, n=10,
            sampling=SamplingOptions(temperature=0.0))
        assert spec == nospec


class TestBlockNativeAttn:
    """--block_native_attn: the Pallas block-map kernel replaces the
    resolve_view/scatter_view bracket on the decode / verify /
    batched-prefill hot path. The contract, pinned per ISSUE 11's
    acceptance bar: seeded outputs stay token-exact kernel-on vs off
    (bf16 AND int8 pools) across plain decode, prefix-hit, chunked
    prefill, preemption-resume, and speculative verify; decode +
    verify keep ONE compile each; and with the kernel on the hot path
    performs ZERO full-pool brackets — kv_gather_bytes_per_step == 0,
    asserted on the metrics seam (a CPU-pinnable claim, not an
    on-chip one)."""

    _outs = TestBlockPoolEngine._outs

    @pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
    def test_plain_decode_token_exact_zero_gather(self, block_model,
                                                  kv_dtype):
        params, cfg = block_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)

        def pin(eng):
            assert eng._decode_traces == 1
            assert eng._kernel_on

        off, s_off = self._outs(gen, ServingConfig(
            num_slots=3, max_len=96, kv_dtype=kv_dtype,
            kv_block_size=16), PROMPTS)
        on, s_on = self._outs(gen, ServingConfig(
            num_slots=3, max_len=96, kv_dtype=kv_dtype,
            kv_block_size=16, block_native_attn=True), PROMPTS,
            trace_check=pin)
        assert on == off
        # THE merge gate: kernel on => zero resolve/scatter bracket
        # bytes on the decode path; kernel off pays the full-view
        # gather + scatter every step
        assert s_on["kv_gather_bytes_per_step"] == 0.0
        assert s_off["kv_gather_bytes_per_step"] > 0.0
        assert s_on["kv_attn_path"] == 2.0
        assert s_off["kv_attn_path"] == 1.0
        # serial ground truth holds through the kernel too
        sp = SamplingParams(temperature=0.9, top_k=5)
        want, lens, _ = gen.generate([PROMPTS[0]], 8, sampling=sp,
                                     seed=0)
        assert on[0] == want[0, :lens[0]].tolist()

    @pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
    def test_prefix_and_chunked_token_exact(self, block_model,
                                            kv_dtype):
        params, cfg = block_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        shared = list(range(2, 36))
        prompts = [shared + [40 + i, 50 + i, 60 + i] for i in range(6)]
        base, _ = self._outs(gen, ServingConfig(
            num_slots=3, max_len=96, kv_dtype=kv_dtype), prompts, n=6)
        for chunk in (None, 16):
            on, snap = self._outs(gen, ServingConfig(
                num_slots=3, max_len=96, kv_dtype=kv_dtype,
                kv_block_size=16, enable_prefix_cache=True,
                prefill_chunk=chunk, block_native_attn=True),
                prompts, n=6)
            assert on == base, f"diverged with chunk={chunk}"
            assert snap["prefix_hits"] >= 1
            # prefix hits + chunked prefill route through slice_blk /
            # insert_blk (never bracketed) — the hot path stays clean
            assert snap["kv_gather_bytes_per_step"] == 0.0

    @pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
    def test_preemption_resume_token_exact(self, block_model,
                                           kv_dtype):
        params, cfg = block_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)

        def run(kernel):
            serving = ServingConfig(
                num_slots=1, max_len=96, kv_dtype=kv_dtype,
                kv_block_size=16, priority_levels=2, preemption=True,
                block_native_attn=kernel)
            with ServingEngine(gen, serving) as eng:
                low = eng.submit([5, 6, 7, 8], 24,
                                 SamplingOptions(temperature=0.8,
                                                 top_k=5), seed=1,
                                 priority=0)
                t0 = time.monotonic()
                while len(low.generated) < 2 and not low.done():
                    time.sleep(0.002)
                    assert time.monotonic() - t0 < 60
                hi = eng.submit([50, 51], 4,
                                SamplingOptions(temperature=0.0),
                                seed=2, priority=1)
                hi_out = hi.result(timeout=300)[0]
                low_out = low.result(timeout=300)[0]
                snap = eng.metrics.snapshot()
            return low_out, hi_out, snap

        l_off, h_off, s_off = run(False)
        l_on, h_on, s_on = run(True)
        assert s_on["preemptions"] >= 1, "premise: preemption fired"
        assert (l_on, h_on) == (l_off, h_off)
        assert s_on["kv_gather_bytes_per_step"] == 0.0

    @pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
    def test_speculative_token_exact_single_verify_compile(
            self, block_model, kv_dtype):
        params, cfg = block_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        prompts = [[5, 17, 3, 42, 9, 9, 5, 17], [7, 8, 9, 7, 8, 9, 7],
                   [11, 12, 13, 11, 12]]

        def pin(eng):
            assert eng._decode_traces == 1
            assert eng._verify_traces == 1

        for temp in (0.0, 0.8):
            sampling = SamplingOptions(temperature=temp)
            off, s_off = self._outs(gen, ServingConfig(
                num_slots=3, max_len=96, kv_dtype=kv_dtype,
                speculative_k=4, kv_block_size=16), prompts, n=10,
                sampling=sampling)
            on, s_on = self._outs(gen, ServingConfig(
                num_slots=3, max_len=96, kv_dtype=kv_dtype,
                speculative_k=4, kv_block_size=16,
                block_native_attn=True), prompts, n=10,
                sampling=sampling, trace_check=pin)
            assert on == off, f"spec diverged at temperature={temp}"
            assert s_on["accepted_tokens"] == s_off["accepted_tokens"]
            # the verify grid is the same kernel (w = k+1): still no
            # bracket anywhere on the hot path
            assert s_on["kv_gather_bytes_per_step"] == 0.0
            assert s_on["spec_rounds"] >= 1

    def test_auto_off_without_blocks(self, block_model):
        """block_native_attn without kv_block_size is INERT (there is
        no arena to index): the engine builds the plain whole-region
        programs, bit-identical to the flagless engine."""
        params, cfg = block_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        base, _ = self._outs(gen, ServingConfig(
            num_slots=3, max_len=96), PROMPTS, n=6)

        def pin(eng):
            assert not eng._kernel_on

        on, snap = self._outs(gen, ServingConfig(
            num_slots=3, max_len=96, block_native_attn=True), PROMPTS,
            n=6, trace_check=pin)
        assert on == base
        assert snap["kv_attn_path"] == 0.0
        assert snap["kv_gather_bytes_per_step"] == 0.0

    def test_validate_rejects_sliding_window(self):
        """The kernel has no window-band mask: EVERY sliding-window
        model is rejected — the rolling (flash) layout AND the
        non-rolling dot layout, whose full-cap pool would silently
        need a banded mask the kernel doesn't apply (without this the
        engine crash-loops at serve time on the kernel's own
        assert)."""
        for impl in ("flash", "dot"):
            cfg = tiny_cfg(sliding_window=32, attention_impl=impl,
                           seq_length=96, max_position_embeddings=96)
            with pytest.raises(AssertionError, match="sliding-window"):
                ServingConfig(max_len=96, kv_block_size=16,
                              block_native_attn=True).validate(cfg)
            # the engine constructor re-asserts for validate-less
            # construction (the crash-loop repro path)
            params = lm.model_init(jax.random.PRNGKey(0), cfg)
            gen = Generator(params, cfg, eos_id=0, pad_id=0)
            with pytest.raises(AssertionError, match="sliding-window"):
                ServingEngine(gen, ServingConfig(
                    max_len=96, kv_block_size=16,
                    block_native_attn=True), start=False)
        # windowless configs pass
        ServingConfig(max_len=96, kv_block_size=16,
                      block_native_attn=True).validate(tiny_cfg(
                          seq_length=96, max_position_embeddings=96))

    def test_attn_gauges_in_metrics_schema(self):
        snap = ServingMetrics().snapshot()
        for key in ("kv_gather_bytes_per_step", "kv_attn_path"):
            assert snap[key] == 0.0  # present before any traffic


@pytest.fixture(scope="module")
def rolling_model():
    cfg = tiny_cfg(sliding_window=32, attention_impl="flash",
                   seq_length=96, max_position_embeddings=96)
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    return params, cfg


class TestRollingBlocks:
    """The rolling exclusions, erased (clone, preempt) or narrowed
    (speculative) by the block pool — the clone/preempt/speculative
    exactness suite the refactor's acceptance demands."""

    def _serve(self, gen, serving, waves, timeout=300):
        outs = []
        with ServingEngine(gen, serving) as eng:
            for wave in waves:
                reqs = [eng.submit(p, n, s, seed=seed)
                        for (p, n, s, seed) in wave]
                outs.append([r.result(timeout=timeout)[0]
                             for r in reqs])
            snap = eng.metrics.snapshot()
        return outs, snap

    def test_plain_rolling_blocks_bit_identical(self, rolling_model):
        params, cfg = rolling_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        wave = [([5 + i, 6 + i, 7 + i], 8,
                 SamplingOptions(temperature=0.7, top_k=5), i)
                for i in range(4)]
        off, _ = self._serve(gen, ServingConfig(num_slots=2,
                                                max_len=96), [wave])
        on, _ = self._serve(gen, ServingConfig(
            num_slots=2, max_len=96, kv_block_size=16), [wave])
        assert on == off

    @pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
    def test_rolling_clone_cache_on_vs_off(self, rolling_model,
                                           kv_dtype):
        """Multi-turn continuation on a ROLLING pool: turn 2 extends
        turn 1's full sequence, so the retained ring (wrapped at
        f > W for the long session, unwrapped for the short one) is
        cloned at its exact length and only the new turn forwards —
        token-matching the cache-off engine, which re-prefills the
        whole conversation."""
        params, cfg = rolling_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        sampling = SamplingOptions(temperature=0.7, top_k=5)
        turn1 = [(list(range(2, 22)), 10, sampling, 0),     # f=30 <= W
                 (list(range(3, 33)), 10, sampling, 1)]     # f=40 > W
        base, _ = self._serve(gen, ServingConfig(
            num_slots=2, max_len=96, kv_dtype=kv_dtype,
            kv_block_size=16), [turn1])
        turn2 = [(base[0][0] + [40, 41], 8, sampling, 100),
                 (base[0][1] + [42, 43, 44], 8, sampling, 101)]

        def run(prefix):
            return self._serve(gen, ServingConfig(
                num_slots=2, max_len=96, kv_dtype=kv_dtype,
                kv_block_size=16, enable_prefix_cache=prefix),
                [turn1, turn2])

        off, s_off = run(False)
        on, s_on = run(True)
        assert on == off
        assert s_on["prefix_hits"] == 2
        # the WRAPPED source's clone saved its whole 40-token history
        assert s_on["prefill_tokens_saved"] == 30 + 40
        assert s_on["prefill_forward_tokens"] \
            < s_off["prefill_forward_tokens"]

    def test_rolling_partial_hit_only_when_unwrapped(self,
                                                     rolling_model):
        """A PARTIAL prefix hit (not a full continuation) is sound
        only while the source ring never wrapped (f <= W): positions
        below f-W are gone from a wrapped ring. Pin both sides: the
        unwrapped source serves a shared-prefix sibling; the wrapped
        source does not."""
        params, cfg = rolling_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        greedy = SamplingOptions(temperature=0.0)
        shared = list(range(2, 18))  # one 16-token block

        def run(first_len, prefix):
            turn1 = [(shared + list(range(60, 60 + first_len)), 10,
                      greedy, 0)]
            sibling = [(shared + [70, 71, 72], 6, greedy, 100)]
            return self._serve(gen, ServingConfig(
                num_slots=2, max_len=96, kv_block_size=16,
                enable_prefix_cache=prefix), [turn1, sibling])

        # unwrapped source (16+4+10 = 30 <= 32): sibling hits
        off, _ = run(4, False)
        on, snap = run(4, True)
        assert on == off
        assert snap["prefix_hits"] == 1
        # wrapped source (16+10+10 = 36 > 32): the shared block is no
        # longer resident — the engine must NOT clone it
        _, snap_w = run(10, True)
        assert snap_w["prefix_hits"] == 0

    @pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
    def test_rolling_preemption_token_exact(self, rolling_model,
                                            kv_dtype):
        params, cfg = rolling_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)

        def run(preempt):
            serving = ServingConfig(
                num_slots=1, max_len=96, kv_dtype=kv_dtype,
                kv_block_size=16, priority_levels=2,
                preemption=preempt)
            with ServingEngine(gen, serving) as eng:
                # prompt 38 > W=32: the ring has wrapped before the
                # preemption lands
                low = eng.submit(list(range(2, 40)), 30,
                                 SamplingOptions(temperature=0.8,
                                                 top_k=5), seed=1,
                                 priority=0)
                t0 = time.monotonic()
                while len(low.generated) < 2 and not low.done():
                    time.sleep(0.002)
                    assert time.monotonic() - t0 < 60
                hi = eng.submit([50, 51, 52], 5,
                                SamplingOptions(temperature=0.0),
                                seed=2, priority=1)
                hi_out = hi.result(timeout=300)[0]
                low_out = low.result(timeout=300)[0]
                pre = eng.metrics.snapshot()["preemptions"]
            return low_out, hi_out, pre

        l_on, h_on, p_on = run(True)
        l_off, h_off, _ = run(False)
        assert p_on >= 1, "premise: preemption fired"
        assert (l_on, h_on) == (l_off, h_off)

    def test_rolling_replay_fallback_token_exact(self, rolling_model):
        """Parked refs dropped (park budget 0 via a full parking lot is
        hard to stage deterministically — instead drop them directly):
        the victim replays prompt+generated through the offset-0 flash
        prefill, exact on the ring because the replay writes the same
        positions the original stream wrote."""
        params, cfg = rolling_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)

        def run(drop_parked):
            serving = ServingConfig(
                num_slots=1, max_len=96, kv_block_size=16,
                priority_levels=2, preemption=True)
            with ServingEngine(gen, serving) as eng:
                low = eng.submit(list(range(2, 40)), 26,
                                 SamplingOptions(temperature=0.8,
                                                 top_k=5), seed=1,
                                 priority=0)
                t0 = time.monotonic()
                while len(low.generated) < 2 and not low.done():
                    time.sleep(0.002)
                    assert time.monotonic() - t0 < 60
                hi = eng.submit([50, 51, 52], 5,
                                SamplingOptions(temperature=0.0),
                                seed=2, priority=1)
                if drop_parked:
                    # between preemption and resume, drop the parked
                    # device refs (the engine-restart / park-budget
                    # path) — same seam the contiguous-pool replay
                    # test uses
                    t0 = time.monotonic()
                    while low.preemptions == 0 and not low.done():
                        time.sleep(0.002)
                        assert time.monotonic() - t0 < 60
                    dropped = eng.scheduler.clear_parked()
                else:
                    dropped = 0
                hi.result(timeout=300)
                low_out = low.result(timeout=300)[0]
                pre = eng.metrics.snapshot()["preemptions"]
            return low_out, pre, dropped

        # the drop races the engine loop: if `hi` finished and the
        # victim resumed from its park before clear_parked ran,
        # nothing was dropped and the replay path never exercised —
        # that run proves nothing either way (the output is exact
        # regardless), so retry the stage a few times instead of
        # flaking under suite-wide CPU contention
        for _ in range(4):
            replay, p1, dropped = run(True)
            if dropped >= 1:
                break
        parked, p2, _ = run(False)
        assert p1 >= 1 and p2 >= 1 and dropped >= 1
        assert replay == parked

    def test_block_size_equal_window_stays_block_mode(self,
                                                      rolling_model):
        """Regression: kv_block_size == W passes validate (block_size
        >= cap is the documented whole-region degrade) but on a
        ROLLING pool block mode is what retention needs — the pool
        must clamp to one block per slot, NOT silently coerce to
        whole-region and crash the engine's rolling-requires-blocks
        assertion."""
        params, cfg = rolling_model  # W = 32
        serving = ServingConfig(num_slots=2, max_len=96,
                                kv_block_size=32,
                                enable_prefix_cache=True)
        serving.validate(cfg)
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        with ServingEngine(gen, serving) as eng:
            assert eng.pool.blocks_enabled
            assert eng.pool.blocks_per_slot == 1
            # f = 30 + 10 = 40 > W: the ring wraps, and the sequence
            # spans >= one 32-token index block so the continuation
            # is findable (shorter-than-a-block sequences can't index
            # — the granularity floor, same as any block size)
            toks, _ = eng.generate(list(range(3, 33)), 10,
                                   SamplingOptions(temperature=0.0),
                                   seed=0, timeout=300)
            toks2, _ = eng.generate(toks + [40, 41], 4,
                                    SamplingOptions(temperature=0.0),
                                    seed=1, timeout=300)
            snap = eng.metrics.snapshot()
        assert snap["prefix_hits"] >= 1
        # non-rolling pools keep the whole-region degrade
        pool = SlotKVPool(tiny_cfg(), 2, 32, block_size=64)
        assert not pool.blocks_enabled

    def test_rolling_speculative_still_excluded(self, rolling_model):
        """The ONE remaining rolling exclusion, pinned with its
        reason: a rejected draft's ring write evicted the position it
        displaced — no rewind can restore it, blocks or not."""
        params, cfg = rolling_model
        with pytest.raises(AssertionError, match="ROLLING"):
            ServingConfig(max_len=96, kv_block_size=16,
                          speculative_k=4).validate(cfg)
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        with pytest.raises(AssertionError, match="speculative_k"):
            ServingEngine(gen, ServingConfig(
                num_slots=2, max_len=96, kv_block_size=16,
                speculative_k=4), start=False)


class TestFrontDoorContracts:
    """Satellites: the health() routing-signal schema is pinned (the
    router contract can't drift), the new front-door counters sit in
    the fixed /metrics schema, and the degenerate config — one
    replica, no streaming, no host tier — builds the bare engine."""

    HEALTH_KEYS = (
        "healthy", "state", "accepting", "loop_alive",
        "circuit_breaker_open", "engine_restarts", "max_engine_restarts",
        "active_slots", "prefilling", "num_slots",
        # the routing signals the router consumes:
        "queue_depth", "free_slots", "kv_blocks_retained",
        "service_time_ewma_ms",
    )

    def test_health_schema_pinned(self, engine):
        gen, eng = engine
        h = eng.health()
        for key in self.HEALTH_KEYS:
            assert key in h, f"health() lost routing signal {key!r}"
        assert isinstance(h["free_slots"], int)
        assert isinstance(h["kv_blocks_retained"], int)
        assert isinstance(h["service_time_ewma_ms"], float)
        # after at least one completion the EWMA must be live (>0) —
        # the router's least-loaded signal feeds off it
        eng.generate([3, 1, 4], 2, SamplingOptions(temperature=0.0),
                     seed=0)
        assert eng.health()["service_time_ewma_ms"] > 0.0

    def test_front_door_counters_in_base_schema(self):
        snap = ServingMetrics().snapshot()
        for key in ("router_failovers", "router_retries",
                    "host_tier_hits", "host_tier_demotions",
                    "host_tier_checksum_misses", "stream_reconnects",
                    # the remote-transport taxonomy (serving/remote.py)
                    # lives in the SAME fixed schema — a fleet scrape
                    # needs no new keys to alert on
                    "router_remote_timeouts", "router_remote_retries",
                    "router_probe_failures"):
            assert snap[key] == 0.0, key
        # fleet health is an always-present gauge, 0 on a fresh
        # registry (no router has pushed replica states yet)
        assert snap["fleet_replicas_up"] == 0.0

    def test_default_config_builds_plain_engine(self, tiny_model):
        """num_replicas=1 + host_kv_bytes=0 + no streaming client is
        the PR 9 engine exactly: no router object exists at all."""
        from megatron_tpu.inference.server import MegatronServer
        from megatron_tpu.serving.router import EngineRouter
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        srv = MegatronServer(gen, FakeTokenizer(),
                             serving=ServingConfig(num_slots=2,
                                                   max_queue=8,
                                                   max_len=64))
        try:
            assert isinstance(srv.engine, ServingEngine)
            assert not isinstance(srv.engine, EngineRouter)
            assert srv.engine._host_tier is None
            status, body = srv.handle({"prompts": ["hi"],
                                       "tokens_to_generate": 2,
                                       "random_seed": 3})
            assert status == 200 and len(body["text"]) == 1
        finally:
            srv.close()

    def test_validate_front_door_knobs(self):
        with pytest.raises(AssertionError, match="host_kv_bytes"):
            ServingConfig(host_kv_bytes=1 << 20).validate()
        with pytest.raises(AssertionError, match="host_kv_bytes"):
            ServingConfig(host_kv_bytes=1 << 20,
                          enable_prefix_cache=True).validate()
        with pytest.raises(AssertionError):
            ServingConfig(num_replicas=0).validate()
        with pytest.raises(AssertionError, match="serial_fallback"):
            ServingConfig(num_replicas=2, serial_fallback=True).validate()
        # the legal combination validates
        ServingConfig(num_replicas=2, enable_prefix_cache=True,
                      kv_block_size=16, host_kv_bytes=1 << 20,
                      max_len=64).validate()


class TestRouter:
    """Tentpole (a): prefix-affinity routing, health-driven failover
    with token-exact requeue-and-retry, half-open recovery, and the
    degraded-vs-down /healthz distinction."""

    def _router(self, tiny_model, **kw):
        from megatron_tpu.serving.router import EngineRouter
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        sc = ServingConfig(num_slots=2, max_queue=32, max_len=64,
                           enable_prefix_cache=True, kv_block_size=16,
                           **kw).validate(cfg)
        engines = [ServingEngine(gen, sc) for _ in range(2)]
        return EngineRouter(engines, max_retries=2,
                            heartbeat_timeout_s=3.0,
                            probe_backoff_s=0.05), engines, gen

    def test_routed_outputs_match_serial(self, tiny_model):
        router, engines, gen = self._router(tiny_model)
        try:
            s = SamplingOptions(temperature=0.9, top_k=5)
            reqs = [(router.submit([5 + i, 2, 7], 6, s, seed=i), i)
                    for i in range(6)]
            for r, i in reqs:
                toks, lps = r.result(timeout=300)
                want, lens, _ = gen.generate(
                    [[5 + i, 2, 7]], 6,
                    sampling=SamplingParams(temperature=0.9, top_k=5),
                    seed=i)
                assert toks == want[0, :lens[0]].tolist()
                assert len(lps) == len(toks) - 3
            # both replicas actually served (least-loaded spreads a
            # 6-request burst over 2x2 slots)
            used = sum(1 for e in engines
                       if e.metrics.snapshot()["requests_received"] > 0)
            assert used == 2
        finally:
            router.close()

    def test_prefix_affinity_prefers_warm_replica(self, tiny_model):
        router, engines, gen = self._router(tiny_model)
        try:
            prefix = list(range(2, 20))  # covers one 16-token block
            s = SamplingOptions(temperature=0.0)
            engines[1].generate(prefix, 4, s, seed=0)  # warm ONLY 1
            assert engines[1].prefix_peek(prefix + [50, 51]) >= 16
            assert engines[0].prefix_peek(prefix + [50, 51]) == 0
            with router._lock:
                rep, canary = router._pick_locked(prefix + [50, 51])
            assert rep.idx == 1 and not canary
            # and a request actually lands there with a prefix hit
            r = router.submit(prefix + [50, 51], 4, s, seed=1)
            toks, _ = r.result(timeout=120)
            assert r.replica.idx == 1
            assert engines[1].metrics.snapshot()["prefix_hits"] >= 1
            want, lens, _ = gen.generate(
                [prefix + [50, 51]], 4,
                sampling=SamplingParams(temperature=0.0))
            assert toks == want[0, :lens[0]].tolist()
        finally:
            router.close()

    def test_replica_kill_mid_decode_failover_token_exact(self,
                                                          tiny_model):
        """Acceptance: killing a replica mid-traffic loses ZERO
        accepted requests — every future resolves, every completion
        (requeued-and-retried included) token-exact vs serial, and
        /healthz reports DEGRADED (ready), not down."""
        router, engines, gen = self._router(tiny_model)
        try:
            s = SamplingOptions(temperature=0.0)
            for e in engines:  # warm both (compiles)
                e.generate([3, 1, 4], 2, s, seed=0)
            reqs = [(router.submit([9 + i, 3, 5], 8, s, seed=i), i)
                    for i in range(6)]
            deadline = time.monotonic() + 30
            while (engines[0].health()["active_slots"] == 0
                   and time.monotonic() < deadline):
                time.sleep(0.002)
            engines[0].close()  # the kill
            for r, i in reqs:
                toks, _ = r.result(timeout=300)  # no stranded futures
                want, lens, _ = gen.generate(
                    [[9 + i, 3, 5]], 8,
                    sampling=SamplingParams(temperature=0.0))
                assert toks == want[0, :lens[0]].tolist(), i
            h = router.health()
            assert h["state"] == "degraded" and h["healthy"]
            snap = router.aggregate_snapshot()
            assert snap["router_failovers"] >= 1
            assert snap["router_retries"] >= 1
            # retried attempts preserved their original arrival id
            for r, _ in reqs:
                assert r.inner.id == r.arrival_id
        finally:
            router.close()

    def test_all_replicas_down_is_typed_503(self, tiny_model):
        router, engines, _ = self._router(tiny_model)
        try:
            for e in engines:
                e.close()
            with pytest.raises(ServiceUnavailableError,
                               match="replicas are down"):
                router.submit([1, 2], 2)
            h = router.health()
            assert h["state"] == "down" and not h["healthy"]
        finally:
            router.close()

    def test_half_open_canary_recovery(self, tiny_model):
        router, engines, _ = self._router(tiny_model)
        try:
            s = SamplingOptions(temperature=0.0)
            for e in engines:
                e.generate([3, 1, 4], 2, s, seed=0)
            rep0 = router.replicas[0]
            with router._lock:
                rep0.state = "down"  # ejected (simulated); engine fine
                rep0.down_until = 0.0
            # next refresh sees a healthy snapshot -> PROBING; the
            # first submit becomes its canary and promotes it
            r = router.submit([4, 5, 6], 2, s, seed=1)
            canary_rep = r.replica
            r.result(timeout=120)
            # pump the canary verdict (result() settled it)
            assert canary_rep.canary is None
            if canary_rep is rep0:
                assert rep0.state == "up"
            else:  # probing replica was picked first by contract
                pytest.fail("probing replica must receive the canary")
        finally:
            router.close()


class TestSSEStreaming:
    """Tentpole (b): SSE token streams with monotonic ids, resume via
    Last-Event-ID (no duplicated or missing tokens), and clean typed
    terminal error events."""

    @pytest.fixture(scope="class")
    def sse_server(self, tiny_model):
        from megatron_tpu.inference.server import MegatronServer
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        srv = MegatronServer(gen, FakeTokenizer(),
                             serving=ServingConfig(num_slots=2,
                                                   max_queue=16,
                                                   max_len=64))
        yield srv
        srv.close()

    @staticmethod
    def _frames(body):
        import json as _json
        frames = []
        for block in "".join(body).strip().split("\n\n"):
            f = {}
            for line in block.split("\n"):
                k, _, v = line.partition(": ")
                f.setdefault(k, v)
            f["data"] = _json.loads(f["data"])
            frames.append(f)
        return frames

    def test_stream_matches_completed_future(self, sse_server):
        payload = {"prompts": ["hello"], "tokens_to_generate": 8,
                   "temperature": 0.0, "random_seed": 7}
        status, body = sse_server.handle(dict(payload, stream=True))
        assert status == 200
        frames = self._frames(body)
        assert frames[0]["event"] == "start"
        assert frames[-1]["event"] == "done"
        toks = [f["data"]["token"] for f in frames
                if f.get("event") == "token"]
        ids = [int(f["id"]) for f in frames if f.get("event") == "token"]
        assert ids == list(range(len(toks)))  # monotonic token index
        status2, body2 = sse_server.handle(payload)
        ref = body2["segments"][0]
        # EOS may end the stream before the 8 tokens asked for; the stream
        # is all of what the completed future holds past the prompt
        assert 1 <= len(toks) <= 8
        assert toks == ref[len(ref) - len(toks):]
        assert len(ref) - len(toks) == len(FakeTokenizer().tokenize("hello"))

    def test_reconnect_resumes_exactly(self, sse_server):
        status, body = sse_server.handle(
            {"prompts": ["resume me"], "tokens_to_generate": 8,
             "temperature": 0.0, "random_seed": 11, "stream": True})
        frames = self._frames(body)
        sid = frames[0]["data"]["stream_id"]
        toks = [f["data"]["token"] for f in frames
                if f.get("event") == "token"]
        # client "dropped" after event id 2; reconnect with the header
        status3, body3 = sse_server.handle(
            {"stream": True, "stream_id": sid},
            headers={"Last-Event-ID": "2"})
        assert status3 == 200
        frames3 = self._frames(body3)
        assert frames3[0]["data"]["resumed"] is True
        ids3 = [int(f["id"]) for f in frames3
                if f.get("event") == "token"]
        toks3 = [f["data"]["token"] for f in frames3
                 if f.get("event") == "token"]
        assert ids3 == list(range(3, len(toks)))  # no dup, no gap
        assert toks3 == toks[3:]
        assert frames3[-1]["event"] == "done"
        assert sse_server.metrics_snapshot()["stream_reconnects"] >= 1

    def test_unknown_stream_id_404_and_bad_payloads_400(self,
                                                        sse_server):
        s, b = sse_server.handle({"stream": True, "stream_id": "nope"})
        assert s == 404 and "stream_id" in b["message"]
        s, b = sse_server.handle({"prompts": ["a", "b"], "stream": True})
        assert s == 400
        s, b = sse_server.handle({"prompts": ["a"], "beam_width": 2,
                                  "stream": True})
        assert s == 400

    def test_serial_fallback_stream_is_400(self, tiny_model):
        from megatron_tpu.inference.server import MegatronServer
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        srv = MegatronServer(gen, FakeTokenizer(),
                             serving=ServingConfig(serial_fallback=True))
        s, b = srv.handle({"prompts": ["x"], "stream": True})
        assert s == 400 and "engine" in b["message"]

    def test_failed_request_yields_terminal_error_event(self,
                                                        sse_server):
        """A mid-stream failure surfaces as a CLEAN typed error event —
        never a silent hang. Driven with a deadline expiry (504)."""
        status, body = sse_server.handle(
            {"prompts": ["doomed"], "tokens_to_generate": 48,
             "temperature": 0.0, "random_seed": 13,
             "deadline_s": 0.02, "stream": True})
        assert status == 200  # stream opened; failure is in-band
        frames = self._frames(body)
        assert frames[-1]["event"] == "error"
        assert frames[-1]["data"]["status"] == 504
        assert "committed" in frames[-1]["data"]

    def test_stdlib_sse_end_to_end(self, sse_server):
        """Real HTTP: PUT a streaming payload through the stdlib
        transport and read the text/event-stream response."""
        import socket
        import urllib.request
        sock = socket.socket()
        sock.bind(("127.0.0.1", 0))
        port = sock.getsockname()[1]
        sock.close()
        t = threading.Thread(target=sse_server._run_stdlib,
                             args=("127.0.0.1", port), daemon=True)
        t.start()
        payload = json.dumps({"prompts": ["net"],
                              "tokens_to_generate": 4,
                              "temperature": 0.0, "random_seed": 5,
                              "stream": True}).encode()
        deadline = time.monotonic() + 15
        while True:
            try:
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/api", data=payload,
                    method="PUT",
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=30) as resp:
                    assert resp.status == 200
                    ctype = resp.headers.get("Content-Type")
                    text = resp.read().decode()
                break
            except OSError:
                assert time.monotonic() < deadline
                time.sleep(0.05)
        assert ctype == "text/event-stream"
        frames = self._frames([text])
        assert frames[0]["event"] == "start"
        assert frames[-1]["event"] == "done"
        assert sum(1 for f in frames if f.get("event") == "token") == 4


class TestHostKVTier:
    """Tentpole (c): retained-prefix block lists demote to host RAM on
    eviction, restore via device_put on a later hit (token-exact), a
    corrupt demotion is a checksum MISS (never wrong tokens), and
    host_kv_bytes=0 is bit-identical to the tier-less engine."""

    PREFIX = list(range(2, 20))  # 18 tokens: one full 16-token block

    def _engine(self, tiny_model, host_bytes, retained=1):
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        sc = ServingConfig(num_slots=2, max_queue=32, max_len=64,
                           enable_prefix_cache=True, kv_block_size=16,
                           retained_slots=retained,
                           host_kv_bytes=host_bytes).validate(cfg)
        return ServingEngine(gen, sc), gen

    def _churn(self, eng, s, seeds=(40, 50)):
        """Finish filler requests so retained-entry pressure evicts
        (and, with the tier on, demotes) earlier prefixes."""
        for base in seeds:
            eng.generate([base, base + 1, base + 2], 2, s, seed=0)

    def test_unit_budget_lru_and_checksum(self):
        import numpy as np
        from megatron_tpu.serving import HostKVTier
        tier = HostKVTier(budget_bytes=3000, granularity=4)
        mk = lambda seed: {"k": np.full((2, 1, 4, 2, 8), seed,
                                        np.float32),
                           "v": np.full((2, 1, 4, 2, 8), seed,
                                        np.float32)}
        assert tier.demote("a", list(range(8)), 5, mk(1))
        assert tier.demote("b", list(range(100, 108)), 5, mk(2))
        # each entry is 2*512 floats = 1024B... two fit, third evicts
        # the LRU ("a")
        assert tier.demote("c", list(range(200, 208)), 5, mk(3))
        assert not tier.has("a") and tier.has("b") and tier.has("c")
        key, hit = tier.lookup(list(range(100, 108)), 7)
        assert key == "b" and hit == 4  # block-aligned, capped
        assert tier.restore("b") is not None
        # corrupt "c": restore drops it and returns None
        tier._entries["c"].arrays["k"].flat[0] = 99.0
        assert tier.restore("c") is None
        assert not tier.has("c")
        # oversized entry refuses cleanly
        big = {"k": np.zeros((2, 1, 64, 2, 64), np.float32),
               "v": np.zeros((2, 1, 64, 2, 64), np.float32)}
        assert not tier.demote("huge", list(range(8)), 5, big)
        # same-sequence demotion REPLACES (demote/restore/retain
        # cycles of one hot prompt must not duplicate), and the byte
        # accounting stays exact through the replacement
        before = tier.bytes_used
        assert tier.demote("b2", list(range(100, 108)), 5, mk(4))
        assert not tier.has("b") and tier.has("b2")
        assert tier.bytes_used == before

    def test_demote_restore_token_exact(self, tiny_model):
        eng, gen = self._engine(tiny_model, host_bytes=1 << 22)
        try:
            s = SamplingOptions(temperature=0.0)
            eng.generate(self.PREFIX, 6, s, seed=0)
            self._churn(eng, s)  # evicts the prefix -> demotes
            snap = eng.metrics.snapshot()
            assert snap["host_tier_demotions"] >= 1
            assert len(eng._host_tier) >= 1
            p2 = self.PREFIX + [90, 91]
            toks, _ = eng.generate(p2, 6, s, seed=2)
            snap = eng.metrics.snapshot()
            assert snap["host_tier_hits"] >= 1
            assert snap["host_tier_checksum_misses"] == 0
            want, lens, _ = gen.generate(
                [p2], 6, sampling=SamplingParams(temperature=0.0))
            assert toks == want[0, :lens[0]].tolist()
        finally:
            eng.close()

    def test_corrupt_demotion_is_miss_never_wrong_tokens(self,
                                                         tiny_model):
        eng, gen = self._engine(tiny_model, host_bytes=1 << 22)
        try:
            s = SamplingOptions(temperature=0.0)
            eng.generate(self.PREFIX, 6, s, seed=0)
            self._churn(eng, s)
            tier = eng._host_tier
            for ent in tier._entries.values():
                if ent.length >= 16:
                    ent.arrays["k"].view("uint8").flat[0] ^= 0xFF
            p2 = self.PREFIX + [90, 91]
            toks, _ = eng.generate(p2, 6, s, seed=2)
            snap = eng.metrics.snapshot()
            assert snap["host_tier_checksum_misses"] >= 1
            assert snap["host_tier_hits"] == 0
            want, lens, _ = gen.generate(
                [p2], 6, sampling=SamplingParams(temperature=0.0))
            assert toks == want[0, :lens[0]].tolist()
        finally:
            eng.close()

    def test_tier_off_is_bit_identical_baseline(self, tiny_model):
        """host_kv_bytes=0: no tier object, zero host counters, and
        the same seeded workload produces identical tokens."""
        outs = {}
        for host_bytes in (1 << 22, 0):
            eng, gen = self._engine(tiny_model, host_bytes=host_bytes)
            try:
                s = SamplingOptions(temperature=0.0)
                stream = []
                stream.append(eng.generate(self.PREFIX, 6, s,
                                           seed=0)[0])
                self._churn(eng, s)
                stream.append(eng.generate(self.PREFIX + [90, 91], 6,
                                           s, seed=2)[0])
                outs[host_bytes] = stream
                snap = eng.metrics.snapshot()
                if host_bytes == 0:
                    assert eng._host_tier is None
                    assert snap["host_tier_demotions"] == 0
                    assert snap["host_tier_hits"] == 0
            finally:
                eng.close()
        assert outs[0] == outs[1 << 22]


class TestFirstTokenAhead:
    """A freshly prefilled request's first token is drawn ahead of the
    decode window that commits it and handed over before that window's
    fetch. It is the token the window draws again: streams and
    log-probabilities are those of an engine that hands nothing over
    ahead (the commit appends every token, as before), and of the serial
    path. Engines here are driven by hand, one `_iteration` a call, so
    that both arms batch alike."""

    GREEDY = SamplingOptions(temperature=0.0)
    DRAWN = SamplingOptions(temperature=0.9, top_k=5)
    DIGITS = {"type": "regex", "pattern": "[0-9]{2,6}"}
    P4, Q4 = [5, 17, 3, 42], [7, 8, 9, 10]

    @staticmethod
    def _drive(eng, reqs, until=lambda r: r.done()):
        for _ in range(400):
            if all(until(r) for r in reqs):
                return
            eng._iteration()
        raise AssertionError("the engine made no end of it")

    def _together(self, jobs):
        def run(eng):
            reqs = [eng.submit(p, n, s, seed=seed, **kw)
                    for p, n, s, seed, kw in jobs]
            self._drive(eng, reqs)
            return reqs
        return run

    def _in_turn(self, jobs):
        def run(eng):
            reqs = []
            for p, n, s, seed, kw in jobs:
                reqs.append(eng.submit(p, n, s, seed=seed, **kw))
                self._drive(eng, reqs)
            return reqs
        return run

    def _poisoned(self, jobs):
        def run(eng):
            from megatron_tpu.resilience import (FaultInjector,
                                                 use_fault_injector)
            # the first step's second active row carries NaN logits
            with use_fault_injector(FaultInjector(serve_nan_calls={1: 1})):
                return self._together(jobs)(eng)
        return run

    def _dead_ended(self, eng):
        """Two grammar rows in one prefill; the second one's mask is
        emptied under it before the first window: the sentinel row."""
        reqs = [eng.submit(self.P4, 6, SamplingOptions(temperature=1.0),
                           seed=seed, response_format=self.DIGITS)
                for seed in (3, 4)]
        eng._admit()
        eng._masks[eng._slot_req.index(reqs[1]), :] = False
        eng._masks_dirty = True
        self._drive(eng, reqs)
        return reqs

    def _preempted(self, eng):
        victim = eng.submit(self.P4, 10, self.DRAWN, seed=9, priority=0)
        self._drive(eng, [victim], until=lambda r: len(r.generated) >= 2)
        hp = eng.submit(self.Q4[:3], 4, self.DRAWN, seed=11, priority=1)
        self._drive(eng, [victim, hp])
        assert victim.preemptions == 1
        return [victim, hp]

    def _cases(self, gen):
        """name -> (gen, ServingConfig fields, scenario, first tokens
        expected ahead, counters expected, {request index: error})."""
        G, D = self.GREEDY, self.DRAWN
        long = np.random.RandomState(3)
        p20, p33 = (long.randint(1, 96, n).tolist() for n in (20, 33))
        shared = list(range(5, 21))
        first = int(gen.generate([self.P4], 1, sampling=SamplingParams(
            temperature=0.0))[0][0, len(self.P4)])
        ends_at_once = Generator(gen.params, gen.cfg, eos_id=first,
                                 pad_id=0)
        two = [(self.P4, 6, G, 0, {}), (self.Q4, 6, D, 11, {})]
        return {
            "batch_of_two": (gen, {}, self._together(two), 2,
                             {"prefill_calls": 1}, {}),
            "chunked_last_chunk": (
                gen, dict(prefill_chunk=8),
                self._together([(p20, 6, D, 50, {}), (p33, 6, G, 0, {})]),
                2, {"prefill_chunks": 3 + 5}, {}),
            "prefix_hit": (
                gen, dict(enable_prefix_cache=True),
                self._in_turn([(shared + [70, 80], 6, D, 300, {}),
                               (shared + [71, 81], 6, D, 301, {})]),
                2, {"prefix_hits": 1}, {}),
            "sync_interval_2": (gen, dict(decode_sync_interval=2),
                                self._together(two), 2, {}, {}),
            "one_token": (
                gen, {}, self._together([(self.P4, 1, G, 0, {}),
                                         (self.Q4, 1, D, 11, {})]),
                2, {"requests_completed": 2}, {}),
            "eos_first": (ends_at_once, {},
                          self._together([(self.P4, 6, G, 0, {})]), 1,
                          {"requests_completed": 1}, {}),
            "grammar_and_dead_end": (
                gen, {}, self._dead_ended, 1,
                {"grammar_dead_ends": 1, "structured_requests": 2},
                {1: "dead end"}),
            "nonfinite_row": (gen, {}, self._poisoned(two), 1,
                              {"nonfinite_logit_fails": 1},
                              {1: "non-finite"}),
            # three activations, two of them fresh
            "preemption_resume": (
                gen, dict(num_slots=1, priority_levels=2,
                          preemption=True),
                self._preempted, 2, {"preemptions": 1}, {}),
            # every row drafts from its repeats: the first window is a
            # verify round, which draws its own way
            "speculative_verify_first": (
                gen, dict(speculative_k=2),
                self._together([([5, 6, 7, 5, 6, 7, 5, 6], 8, G, 0, {}),
                                ([9, 2, 9, 2, 9, 2], 8, G, 0, {})]),
                0, {"spec_rounds": 2}, {}),
            # nothing to draft from: the window falls back to a plain
            # decode round
            "speculative_fallback": (
                gen, dict(speculative_k=2),
                self._together([(self.P4, 1, D, 11, {})]), 1,
                {"spec_rounds": 0, "spec_fallback_steps": 1}, {}),
        }

    @pytest.mark.parametrize("case", [
        "batch_of_two", "chunked_last_chunk", "prefix_hit",
        "sync_interval_2", "one_token", "eos_first",
        "grammar_and_dead_end", "nonfinite_row", "preemption_resume",
        "speculative_verify_first", "speculative_fallback"])
    def test_first_token_is_the_windows_own_and_leaves_before_it(
            self, tiny_model, case):
        params, cfg = tiny_model
        gen, serving, scenario, n_ahead, counters, errors = self._cases(
            Generator(params, cfg, eos_id=-1, pad_id=0))[case]
        serving = dict(dict(num_slots=3, max_queue=16, max_len=64),
                       **serving)
        arms = {}
        for ahead in (True, False):
            eng = ServingEngine(gen, ServingConfig(**serving), start=False)
            handed = []       # (request, its tokens, clock) at each commit
            try:
                if ahead:
                    commit = eng._commit

                    def spy(*args, eng=eng, commit=commit):
                        handed.extend(
                            (eng._slot_req[s], list(
                                eng._slot_req[s].generated),
                             time.monotonic()) for s in args[-1])
                        return commit(*args)
                    eng._commit = spy
                else:
                    eng._deliver_first = lambda fresh, toks, lps: {}
                reqs = scenario(eng)
                arms[ahead] = (reqs, eng.metrics.snapshot())
            finally:
                eng.close()
            if not ahead:
                continue
            # in the requests' hands before their window's commit began
            assert len(handed) == n_ahead
            for req, tokens, t_commit in handed:
                assert tokens == req.generated[:1] and len(tokens) == 1
                assert req.first_token_time <= t_commit
        (reqs, snap), (ref_reqs, ref_snap) = arms[True], arms[False]
        assert snap["first_tokens_early"] == n_ahead
        assert ref_snap["first_tokens_early"] == 0
        assert snap["first_token_mismatches"] == 0
        for name, want in counters.items():
            assert snap[name] == want, name
        for name in ("tokens_generated", "decode_steps", "host_syncs",
                     "requests_completed", "requests_failed",
                     "spec_rounds", "spec_fallback_steps",
                     "accepted_tokens", *counters):
            assert snap[name] == ref_snap[name], name
        for i, (req, ref) in enumerate(zip(reqs, ref_reqs)):
            assert req.generated == ref.generated, i
            assert req.gen_logprobs == ref.gen_logprobs, i
            assert len(req.generated) <= req.max_new_tokens
            if i in errors:
                assert errors[i] in str(req.error), req.error
                assert not req.generated     # left to the commit, whole
                continue
            assert req.error is None and req.generated
            if req.fsm is not None:
                continue                     # the serial path has no grammar
            s = req.sampling
            want, lens, _ = gen.generate(
                [req.prompt], req.max_new_tokens,
                sampling=SamplingParams(temperature=s.temperature,
                                        top_k=s.top_k, top_p=s.top_p),
                seed=req.seed)
            assert req.prompt + req.generated == \
                want[0, :lens[0]].tolist(), i

    def test_a_window_that_draws_another_token_fails_that_request_alone(
            self, tiny_model):
        """The guard behind the hand-over: were the window ever to draw
        another token than the one already in the request's hands, the
        stream could not be both, so that request fails and is counted;
        its neighbour goes on."""
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=-1, pad_id=0)
        eng = ServingEngine(gen, ServingConfig(num_slots=3, max_queue=16,
                                               max_len=64), start=False)
        try:
            deliver = eng._deliver_first

            def other_token(fresh, toks, lps):
                early = deliver(fresh, toks, lps)
                first = min(early)
                early[first] = (early[first] + 1) % cfg.vocab_size
                return early
            eng._deliver_first = other_token
            bad, good = self._together(
                [(self.P4, 6, self.GREEDY, 0, {}),
                 (self.Q4, 6, self.DRAWN, 11, {})])(eng)
            snap = eng.metrics.snapshot()
        finally:
            eng.close()
        assert "first token mismatch" in str(bad.error)
        assert len(bad.generated) == 1
        assert good.error is None and len(good.generated) == 6
        assert snap["first_token_mismatches"] == 1
        assert snap["first_tokens_early"] == 2
        assert snap["requests_failed"] == 1
