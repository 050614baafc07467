"""Brownout ladder tests (megatron_tpu/serving/degrade).

The load-bearing contracts:
- the controller walks ONE rung per transition, needs `dwell_up`
  consecutive over-threshold evaluations to rise and `dwell_down`
  under-the-hysteresis-edge evaluations to fall, and always walks back
  to level 0 on a quiet engine (a brownout is a mode, not a ratchet);
- level 1 disables speculation for the affected windows and the output
  stays token-exact vs the plain decode path (degradation changes
  LATENCY, never tokens);
- level 2 rewrites new admissions' effective config (fan-out collapsed,
  max_new_tokens capped) BEFORE any accounting, so conservation and the
  serial oracle both see the request the engine actually ran;
- levels 3/4 shed at submit with a typed 429 carrying a >= 1s
  Retry-After hint;
- `degrade_ladder=0` builds NO controller — the engine is bit-identical
  to the pre-ladder engine (the regression pin);
- the 5 new /metrics keys are present-at-0 on a fresh scrape, and every
  always-present engine gauge has a router aggregation rule (the PR 13
  silent-zero lesson, pinned structurally this time).
"""
import math
import time

import jax
import pytest

from megatron_tpu.config import ModelConfig, ServingConfig
from megatron_tpu.inference import Generator, SamplingParams
from megatron_tpu.models import language_model as lm
from megatron_tpu.serving import (AdmissionError, SamplingOptions,
                                  ServingEngine, ServingMetrics)
from megatron_tpu.serving import metrics as metrics_mod
from megatron_tpu.serving import router as router_mod
from megatron_tpu.serving.degrade import (DEFAULT_RAISE_AT,
                                          DegradeController,
                                          LEVEL_CAP_WORK,
                                          LEVEL_FULL_SERVICE,
                                          LEVEL_NO_SPEC,
                                          LEVEL_SHED_ALL,
                                          LEVEL_SHED_LOW_PRIORITY,
                                          MAX_LEVEL)
from megatron_tpu.serving.scheduler import (AdmissionScheduler,
                                            OverloadShedError)


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


GREEDY = SamplingOptions(temperature=0.0)

# dwell_down so large the ladder NEVER steps down within a test: level
# forced by the test stays put while the idle engine loop keeps
# evaluating (the single-writer contract makes the direct set legal
# only because these tests hold the level still)
HOLD = dict(degrade_ladder=4, degrade_dwell_down=10**9)


def _serial(gen, prompt, n, seed=0):
    t, lens, _ = gen.generate([list(prompt)], n,
                              sampling=SamplingParams(temperature=0.0),
                              seed=seed)
    return t[0, :lens[0]].tolist()


# ---------------------------------------------------------------------
# controller unit laws (no engine)
# ---------------------------------------------------------------------
class TestDegradeController:
    def test_full_ladder_walk_one_rung_per_transition(self):
        c = DegradeController(max_level=4, raise_at=(0.5, 1.0, 2.0, 4.0),
                              dwell_up=2, dwell_down=4)
        levels = [c.observe(queue_depth=16, active_slots=2, num_slots=2)
                  for _ in range(8)]
        # pressure 8.0 clears every rung: one rung per dwell_up window
        assert levels == [0, 1, 1, 2, 2, 3, 3, 4]
        down = [c.observe(queue_depth=0, active_slots=0, num_slots=2)
                for _ in range(16)]
        assert down == [4, 4, 4, 3, 3, 3, 3, 2,
                        2, 2, 2, 1, 1, 1, 1, 0]
        assert c.transitions == 8
        assert c.level == LEVEL_FULL_SERVICE

    def test_dwell_counters_reset_on_interruption(self):
        c = DegradeController(max_level=2, raise_at=(1.0, 2.0),
                              dwell_up=3, dwell_down=2)
        # 2 hot evals < dwell_up, then one cool one: no transition, and
        # the up-counter starts over
        for _ in range(2):
            assert c.observe(8, 2, 2) == 0
        assert c.observe(0, 0, 2) == 0
        for _ in range(2):
            assert c.observe(8, 2, 2) == 0
        assert c.observe(8, 2, 2) == 1

    def test_hysteresis_band_holds_level(self):
        c = DegradeController(max_level=1, raise_at=(1.0,),
                              hysteresis=0.4, dwell_up=1, dwell_down=1)
        assert c.observe(4, 2, 2) == 1           # pressure 2.0 >= 1.0
        # pressure 0.5: below the raise edge (1.0) but above the lower
        # edge (0.4) — the band exists precisely so this holds forever
        held = [c.observe(1, 2, 2) for _ in range(10)]
        assert held == [1] * 10, "inside the hysteresis band must hold"
        assert c.observe(0, 0, 2) == 0           # pressure 0 < 0.4: falls

    def test_pressure_formula(self):
        # queue depth normalized by slots, damped by slot busyness: a
        # deep queue on an IDLE engine is startup, not overload
        assert DegradeController.pressure(8, 0, 2) == 0.0
        assert DegradeController.pressure(8, 1, 2) == pytest.approx(2.0)
        assert DegradeController.pressure(8, 2, 2) == pytest.approx(4.0)
        assert DegradeController.pressure(0, 2, 2) == 0.0

    def test_effect_predicates_nest(self):
        c = DegradeController(max_level=4)
        for lvl, spec_off, cap, shed_low, shed_all in (
                (LEVEL_FULL_SERVICE, False, False, False, False),
                (LEVEL_NO_SPEC, True, False, False, False),
                (LEVEL_CAP_WORK, True, True, False, False),
                (LEVEL_SHED_LOW_PRIORITY, True, True, True, False),
                (LEVEL_SHED_ALL, True, True, True, True)):
            c.level = lvl
            assert c.spec_disabled() is spec_off
            assert c.cap_work() is cap
            assert c.shed_priority(0, priority_levels=2) is shed_low
            assert c.shed_priority(1, priority_levels=2) is shed_all
            # single-class engines have no "lowest class": level 3 is a
            # no-op there, the ladder effectively goes 2 -> 4
            assert c.shed_priority(0, priority_levels=1) is shed_all

    def test_constructor_validation(self):
        with pytest.raises(AssertionError):
            DegradeController(max_level=0)
        with pytest.raises(AssertionError):
            DegradeController(max_level=2, raise_at=(1.0,))
        with pytest.raises(AssertionError):
            DegradeController(max_level=2, raise_at=(2.0, 1.0))
        with pytest.raises(AssertionError):
            DegradeController(max_level=1, hysteresis=1.0)
        with pytest.raises(AssertionError):
            DegradeController(max_level=1, dwell_up=0)

    def test_from_config(self):
        assert DegradeController.from_config(ServingConfig()) is None
        c = DegradeController.from_config(ServingConfig(
            degrade_ladder=3, degrade_raise_at=(1.0, 2.0, 3.0),
            degrade_hysteresis=0.4, degrade_dwell_up=5,
            degrade_dwell_down=7))
        assert c is not None and c.max_level == 3
        assert c.raise_at == (1.0, 2.0, 3.0)
        assert (c.hysteresis, c.dwell_up, c.dwell_down) == (0.4, 5, 7)
        d = DegradeController.from_config(ServingConfig(degrade_ladder=2))
        assert d.raise_at == DEFAULT_RAISE_AT[:2]
        assert MAX_LEVEL == 4


# ---------------------------------------------------------------------
# engine-level rung effects
# ---------------------------------------------------------------------
class TestEngineDegrade:
    def test_ladder_off_builds_no_controller(self, tiny_model):
        """The regression pin: degrade_ladder=0 (the default) must run
        the EXACT pre-ladder submit/step paths — no controller object,
        level 0 in health, serial-exact output."""
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        with ServingEngine(gen, ServingConfig(
                num_slots=2, max_queue=8, max_len=64)) as eng:
            assert eng.degrade is None
            h = eng.health()
            assert h["degrade_level"] == 0 and h["degrade"] is None
            toks, _ = eng.submit([5, 17, 3], 8, GREEDY,
                                 seed=0).result(timeout=300)
            assert toks == _serial(gen, [5, 17, 3], 8)
            snap = eng.metrics.snapshot()
            assert snap["degrade_transitions"] == 0.0
            assert snap["degrade_level"] == 0.0

    def test_level1_spec_off_token_exact_and_reversible(self, tiny_model):
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)

        class AlwaysDraft:
            """Proposes at every step, whatever the history: with it, no
            speculative round at level 0 can only mean that the ladder
            left drafting switched off."""

            def propose(self, tokens, n):
                return [tokens[-1]] * n

        with ServingEngine(gen, ServingConfig(
                num_slots=2, max_queue=8, max_len=64,
                speculative_k=3, **HOLD), drafter=AlwaysDraft()) as eng:
            eng.degrade.level = LEVEL_NO_SPEC
            reqs = [eng.submit(p, 12, GREEDY, seed=0)
                    for p in ([5, 17, 3, 42], [7, 8, 9])]
            outs = [r.result(timeout=300)[0] for r in reqs]
            snap = eng.metrics.snapshot()
            # degraded windows take the PLAIN decode path: the spec
            # counters must read like a non-speculative engine
            assert snap["spec_rounds"] == 0.0
            assert snap["draft_tokens"] == 0.0
            for p, toks in zip(([5, 17, 3, 42], [7, 8, 9]), outs):
                assert toks == _serial(gen, p, 12), p
            # recovery: back at level 0 the drafter resumes — same
            # tokens, spec counters moving again
            eng.degrade.level = LEVEL_FULL_SERVICE
            toks, _ = eng.submit([5, 17, 3, 42], 12, GREEDY,
                                 seed=0).result(timeout=300)
            assert toks == _serial(gen, [5, 17, 3, 42], 12)
            assert eng.metrics.snapshot()["spec_rounds"] >= 1.0

    def test_level2_caps_effective_config(self, tiny_model):
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=-1, pad_id=0)
        with ServingEngine(gen, ServingConfig(
                num_slots=2, max_queue=8, max_len=64,
                degrade_max_new_tokens=4, **HOLD)) as eng:
            eng.degrade.level = LEVEL_CAP_WORK
            r = eng.submit([5, 17, 3], 16, GREEDY, seed=0)
            toks, _ = r.result(timeout=300)
            # the REQUEST carries the effective budget (accounting and
            # oracle key off it), and the output is exactly the serial
            # run of that effective config — shorter, never different
            assert r.max_new_tokens == 4
            assert toks == _serial(gen, [5, 17, 3], 4)
            # fan-out collapses to n: best_of=2 admits as a plain
            # single-sample request (no children)
            r2 = eng.submit([7, 8, 9], 16, GREEDY, seed=0,
                            n=1, best_of=2)
            toks2, _ = r2.result(timeout=300)
            assert getattr(r2, "children", None) is None
            assert toks2 == _serial(gen, [7, 8, 9], 4)
            # original-shape admission errors still fire on the
            # ORIGINAL values: a malformed request is a 400, not a
            # silently-degraded admit
            with pytest.raises(AdmissionError):
                eng.submit([1, 2], 4, GREEDY, n=3, best_of=2)

    def test_level3_sheds_lowest_class_only(self, tiny_model):
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        with ServingEngine(gen, ServingConfig(
                num_slots=2, max_queue=8, max_len=64,
                priority_levels=2, **HOLD)) as eng:
            eng.degrade.level = LEVEL_SHED_LOW_PRIORITY
            with pytest.raises(OverloadShedError) as ei:
                eng.submit([1, 2, 3], 4, GREEDY, priority=0)
            assert ei.value.retry_after >= 1
            # the paying class still gets served
            toks, _ = eng.submit([5, 17, 3], 8, GREEDY, seed=0,
                                 priority=1).result(timeout=300)
            assert toks == _serial(gen, [5, 17, 3], 8)
            snap = eng.metrics.snapshot()
            assert snap["requests_shed"] >= 1.0
            assert snap["requests_rejected"] >= 1.0

    def test_level4_sheds_everything(self, tiny_model):
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        with ServingEngine(gen, ServingConfig(
                num_slots=2, max_queue=8, max_len=64,
                priority_levels=2, **HOLD)) as eng:
            eng.degrade.level = LEVEL_SHED_ALL
            for prio in (0, 1):
                with pytest.raises(OverloadShedError):
                    eng.submit([1, 2, 3], 4, GREEDY, priority=prio)

    def test_engine_walks_ladder_up_and_back_under_real_load(
            self, tiny_model):
        """No forced levels: a burst beyond the slot grid raises the
        level through the engine's own evaluations; the drained engine
        walks it back to 0 (the monotone-revert law, in miniature)."""
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=-1, pad_id=0)
        with ServingEngine(gen, ServingConfig(
                num_slots=2, max_queue=32, max_len=64,
                degrade_ladder=4, degrade_raise_at=(0.25, 0.5, 1.0, 2.0),
                degrade_dwell_up=1, degrade_dwell_down=2)) as eng:
            eng.generate([9, 9], 2, GREEDY, seed=0)   # warm compiles
            reqs = [eng.submit([1 + i, 2, 3], 24, GREEDY, seed=0)
                    for i in range(10)]
            peak = 0
            while any(not r.done() for r in reqs):
                peak = max(peak, eng.health()["degrade_level"])
                time.sleep(0.002)
            for r in reqs:
                r.result(timeout=300)
            assert peak >= 1, "10-deep backlog on 2 slots never degraded"
            deadline = time.monotonic() + 30.0
            while (eng.health()["degrade_level"]
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert eng.health()["degrade_level"] == 0
            snap = eng.metrics.snapshot()
            assert snap["degrade_transitions"] >= 2.0
            assert snap["degrade_level"] == 0.0

    def test_health_payload_carries_ladder_state(self, tiny_model):
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        with ServingEngine(gen, ServingConfig(
                num_slots=2, max_queue=8, max_len=64, **HOLD)) as eng:
            eng.degrade.level = 2
            h = eng.health()
            assert h["degrade_level"] == 2
            d = h["degrade"]
            assert set(d) >= {"level", "max_level", "pressure",
                              "transitions"}
            assert d["level"] == 2 and d["max_level"] == 4


# ---------------------------------------------------------------------
# SLO accounting (engine-side counters; the harness-side laws live in
# serving/invariants.py and tools/chaos_storm.py)
# ---------------------------------------------------------------------
class TestSLOAccounting:
    def test_violation_counters_and_goodput(self, tiny_model):
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=-1, pad_id=0)
        # sub-microsecond SLOs: every completion violates both, and a
        # TTFT-late completion contributes ZERO goodput
        with ServingEngine(gen, ServingConfig(
                num_slots=2, max_queue=8, max_len=64,
                slo_ttft_ms=1e-4, slo_itl_p99_ms=1e-4)) as eng:
            reqs = [eng.submit([5 + i, 17, 3], 8, GREEDY, seed=0)
                    for i in range(3)]
            for r in reqs:
                r.result(timeout=300)
            snap = eng.metrics.snapshot()
            assert snap["slo_ttft_violations"] >= 3.0
            assert snap["slo_itl_violations"] >= 1.0
            assert snap["tokens_generated"] >= 24.0
            assert snap["goodput_tokens"] == 0.0

    def test_no_slo_configured_counts_everything_good(self, tiny_model):
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=-1, pad_id=0)
        with ServingEngine(gen, ServingConfig(
                num_slots=2, max_queue=8, max_len=64)) as eng:
            eng.submit([5, 17, 3], 8, GREEDY, seed=0).result(timeout=300)
            snap = eng.metrics.snapshot()
            assert snap["slo_ttft_violations"] == 0.0
            assert snap["goodput_tokens"] == snap["tokens_generated"] > 0


# ---------------------------------------------------------------------
# /metrics schema + router aggregation coverage (the PR 13 lesson)
# ---------------------------------------------------------------------
class _FakeEngine:
    """metrics + max_len are all aggregate_snapshot touches."""

    def __init__(self):
        self.metrics = ServingMetrics()
        self.max_len = 64


class TestMetricsSchema:
    NEW_KEYS = ("degrade_transitions", "degrade_level",
                "slo_ttft_violations", "slo_itl_violations",
                "goodput_tokens")

    def test_new_keys_present_at_zero_on_fresh_scrape(self):
        snap = ServingMetrics().snapshot()
        for key in self.NEW_KEYS:
            assert snap[key] == 0.0, key

    def test_degrade_gauge_setter_round_trips(self):
        m = ServingMetrics()
        m.set_degrade_gauge(3)
        assert m.snapshot()["degrade_level"] == 3.0

    def test_goodput_accounting(self):
        m = ServingMetrics()
        m.record_completed(10)                  # no SLO verdict
        m.record_completed(10, good_tokens=0)   # TTFT-late
        m.record_completed(10, good_tokens=10)
        assert m.snapshot()["goodput_tokens"] == 20.0

    def test_every_base_gauge_has_an_aggregation_rule(self):
        """Structural pin: an engine gauge added to _BASE_GAUGES
        without a router aggregation rule (sum / max / router-owned)
        silently reads 0 on fleet scrapes — the exact regression
        kv_gather_bytes_per_step shipped with in PR 13."""
        handled = (set(router_mod._SUM_GAUGES)
                   | set(router_mod._MAX_GAUGES)
                   | {"weight_version", "fleet_replicas_up"})
        missing = [g for g in metrics_mod._BASE_GAUGES
                   if g not in handled]
        assert not missing, (
            f"gauges with NO aggregation rule (add to _SUM_GAUGES or "
            f"_MAX_GAUGES in serving/router.py): {missing}")

    def test_nonzero_gauges_survive_aggregation(self):
        """Behavioral twin of the structural pin: set every base gauge
        nonzero on one replica and require the fleet scrape to carry a
        nonzero reading for each (sum and max both preserve > 0)."""
        from megatron_tpu.serving import EngineRouter
        eng_a, eng_b = _FakeEngine(), _FakeEngine()
        for i, g in enumerate(metrics_mod._BASE_GAUGES):
            # both replicas: weight_version aggregates as the fleet
            # MIN, so a zeroed sibling would legitimately floor it
            setattr(eng_a.metrics, g, float(i + 1))
            setattr(eng_b.metrics, g, float(i + 1))
        router = EngineRouter([eng_a, eng_b])
        agg = router.aggregate_snapshot()
        for g in metrics_mod._BASE_GAUGES:
            assert agg.get(g, 0.0) > 0.0, (
                f"nonzero engine gauge {g!r} zeroed by aggregation")

    def test_router_reports_most_degraded_replica(self):
        from megatron_tpu.serving import EngineRouter
        eng_a, eng_b = _FakeEngine(), _FakeEngine()
        eng_a.metrics.set_degrade_gauge(1)
        eng_b.metrics.set_degrade_gauge(3)
        agg = EngineRouter([eng_a, eng_b]).aggregate_snapshot()
        assert agg["degrade_level"] == 3.0


# ---------------------------------------------------------------------
# Retry-After >= 1s, pinned at BOTH layers (the herd clamp)
# ---------------------------------------------------------------------
class TestRetryAfterFloor:
    def test_scheduler_hint_never_below_one_second(self):
        sched = AdmissionScheduler(max_queue=4, max_total_len=64,
                                   num_slots=2)
        assert sched.retry_after_hint() == 1   # no EWMA yet: floor
        sched.observe_service(0.01)            # sub-second estimate
        assert sched.retry_after_hint() == 1
        for _ in range(50):
            sched.observe_service(500.0)       # absurd estimate: capped
        assert sched.retry_after_hint() <= 60

    def test_server_backoff_body_ceils_float_hints(self):
        from megatron_tpu.inference.server import MegatronServer
        body = MegatronServer._backoff_body(None, "shed",
                                            retry_after=0.5,
                                            queue_depth=3)
        # int(0.5) == 0 was the bug: a zero hint tells every shed
        # client to retry NOW, and response_headers drops falsy values
        # so the Retry-After header vanished entirely
        assert body["retry_after"] == 1
        assert MegatronServer.response_headers(body) == {
            "Retry-After": "1"}
        assert MegatronServer._backoff_body(
            None, "m", retry_after=None, queue_depth=0)["retry_after"] == 1
        assert MegatronServer._backoff_body(
            None, "m", retry_after=2.3, queue_depth=0)["retry_after"] == 3
        assert math.ceil(0.5) == 1  # the clamp's arithmetic, spelled out


# ---------------------------------------------------------------------
# cold start + restart survival
# ---------------------------------------------------------------------
class TestColdStartAndRestart:
    def test_shed_estimate_cold_start_relearns_in_one_completion(
            self, tiny_model):
        """A restarted PROCESS starts with _service_ewma=None: it must
        never shed blind, and one completed request re-arms the
        estimate (one sync window, not a long calibration)."""
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        with ServingEngine(gen, ServingConfig(
                num_slots=2, max_queue=8, max_len=64,
                shed_on_overload=True)) as eng:
            assert eng.scheduler.service_time_ewma() == 0.0
            # cold estimator + tight deadline: admits (never shed blind)
            r = eng.submit([5, 17, 3], 4, GREEDY, seed=0,
                           deadline_s=120.0)
            r.result(timeout=300)
            assert eng.scheduler.service_time_ewma() > 0.0

    def test_degrade_level_and_ewma_survive_engine_restart(
            self, tiny_model):
        """_restart_session rebuilds DEVICE state only: the brownout
        level and the shed estimator are host state and deliberately
        survive — a replica that crashed under overload must not come
        back at level 0 and re-admit the same storm."""
        from megatron_tpu.resilience import (FaultInjector,
                                             use_fault_injector)
        params, cfg = tiny_model
        gen = Generator(params, cfg, eos_id=0, pad_id=0)
        with ServingEngine(gen, ServingConfig(
                num_slots=1, max_queue=8, max_len=64,
                max_engine_restarts=2, **HOLD)) as eng:
            eng.generate([9, 9], 2, GREEDY, seed=0)  # warm compiles
            ewma_before = eng.scheduler.service_time_ewma()
            assert ewma_before > 0.0
            eng.degrade.level = 3
            with use_fault_injector(FaultInjector(serve_crash_calls={1})):
                victim = eng.submit([1, 2, 3], 4,
                                    SamplingOptions(temperature=0.9),
                                    seed=1, priority=1)
                with pytest.raises(RuntimeError):
                    victim.result(timeout=120)
            deadline = time.monotonic() + 30.0
            while (eng.metrics.snapshot()["engine_restarts"] < 1
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert eng.metrics.snapshot()["engine_restarts"] == 1
            assert eng.degrade.level == 3, (
                "brownout level must survive a session restart")
            assert eng.scheduler.service_time_ewma() == pytest.approx(
                ewma_before), "shed estimator must survive a restart"


# ---------------------------------------------------------------------
# CLI / config plumbing
# ---------------------------------------------------------------------
class TestConfigValidation:
    def test_ladder_bounds(self):
        tiny = tiny_cfg()
        ServingConfig(degrade_ladder=4).validate(tiny)
        with pytest.raises(AssertionError):
            ServingConfig(degrade_ladder=5).validate(tiny)
        with pytest.raises(AssertionError):
            ServingConfig(degrade_raise_at=(1.0,)).validate(tiny)
        with pytest.raises(AssertionError):
            ServingConfig(degrade_ladder=2,
                          degrade_raise_at=(2.0, 1.0)).validate(tiny)
        with pytest.raises(AssertionError):
            ServingConfig(degrade_ladder=1,
                          degrade_hysteresis=1.5).validate(tiny)
        with pytest.raises(AssertionError):
            ServingConfig(slo_ttft_ms=-1.0).validate(tiny)

    def test_cli_flags_parse_and_default_off(self):
        import inspect

        from megatron_tpu import arguments
        args = arguments.build_parser().parse_args(
            ["--degrade_ladder", "3", "--slo_ttft_ms", "250",
             "--slo_itl_p99_ms", "80"])
        assert args.degrade_ladder == 3
        assert args.slo_ttft_ms == 250.0
        assert args.slo_itl_p99_ms == 80.0
        defaults = arguments.build_parser().parse_args([])
        assert defaults.degrade_ladder == 0
        assert defaults.slo_ttft_ms is None
        # the flags actually FLOW into ServingConfig (config_from_args
        # builds it field-by-field; a flag parsed but dropped there is
        # the classic wiring regression)
        src = inspect.getsource(arguments.config_from_args)
        for field in ("degrade_ladder", "slo_ttft_ms", "slo_itl_p99_ms"):
            assert f"{field}=args.{field}" in src, field
