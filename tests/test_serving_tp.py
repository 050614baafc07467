"""Sharded + disaggregated serving (serving/topology.py; docs/serving.md
"Sharded & disaggregated serving").

Acceptance pins, on the 8-virtual-device CPU mesh (conftest.py forces
`--xla_force_host_platform_device_count=8` — the same trick the
pipeline tests use, so tp=2 and 2-group disaggregation are
CPU-pinnable):

- tp=2 is TOKEN-EXACT vs tp=1 for bf16 AND int8 pools across plain
  decode, prefix-hit, chunked prefill, preemption-resume, speculative
  verify, and mixed-adapter rows — decode + verify still ONE compile
  each;
- serving_tp=1 builds NO topology at all (the engine takes the
  pre-topology code paths — bit-identical to today by construction);
- the disaggregated prefill->decode handoff moves ONLY the sequence's
  live physical blocks (handoff_bytes_per_req == ceil(plen/B) * B *
  bytes_per_token, never a cap region), and the single-chip
  chunk-interleave fallback stays bit-identical with the knob off.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.config import ModelConfig, ServingConfig
from megatron_tpu.inference import Generator, SamplingParams
from megatron_tpu.models import language_model as lm
from megatron_tpu.serving import ServingEngine, build_topology, \
    devices_per_engine
from megatron_tpu.serving.adapters import random_adapter_factors
from megatron_tpu.serving.request import SamplingOptions


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


def _gen(tiny_model, kv_dtype=None, eos_id=0):
    params, cfg = tiny_model
    return Generator(params, cfg, eos_id=eos_id, pad_id=0,
                     kv_cache_dtype=(jnp.int8 if kv_dtype == "int8"
                                     else jnp.bfloat16))


# the kitchen-sink config: every engine feature the tp=2 exactness
# criterion names, in ONE engine so the tp=1-vs-2 comparison pays two
# engine compiles per dtype arm instead of twelve
def _sink_cfg(tp, **overrides):
    base = dict(num_slots=3, max_queue=32, max_len=64, kv_block_size=16,
                enable_prefix_cache=True, prefill_chunk=8,
                speculative_k=2, priority_levels=2, preemption=True,
                adapter_slots=2, adapter_rank=4, serving_tp=tp)
    base.update(overrides)
    return ServingConfig(**base)


def _iterate(eng, until):
    """Run the engine loop's body on this thread until `until()` holds."""
    for _ in range(2000):
        if until():
            return
        eng._iteration()
    raise AssertionError("not reached in 2000 iterations")


def _drive(eng, reqs):
    """`_iterate` until `reqs` are done; returns their token lists."""
    _iterate(eng, lambda: all(r.done() for r in reqs))
    return [r.result(timeout=0)[0] for r in reqs]


def _drive_sink(gen, serving, cfg):
    """One workload exercising every named scenario; returns the
    ordered token lists plus the engine's compile/metric evidence.
    The engine has no thread of its own (`start=False`): the test runs
    its iterations, so which requests share a step, and that three
    low-priority rows are live when the high-priority one lands, does
    not depend on timing. `gen` must not be able to emit EOS, or the
    drawn model can end a low-priority row at its first token."""
    eng = ServingEngine(gen, serving.validate(cfg), start=False)
    try:
        for aid in ("tenant-a", "tenant-b"):
            eng.register_adapter(
                aid, factors=random_adapter_factors(cfg, 4, seed=hash(aid)
                                                    % 1000),
                rank=4, alpha=8.0)
        greedy = SamplingOptions(temperature=0.0)
        sampled = SamplingOptions(temperature=0.9, top_k=5)
        outs = []
        # (1) plain decode, greedy + sampled; the repetitive prompt
        # gives the n-gram drafter real acceptances (spec verify)
        shared = [5, 17, 3, 42, 6, 7, 9, 2, 4, 8, 1, 3, 5, 7, 9, 11]
        r_plain = [eng.submit(shared + [61, 62, 63, 64], 8, greedy,
                              seed=0),
                   eng.submit([7, 8, 7, 8, 7, 8, 7], 10, greedy, seed=1),
                   eng.submit([11, 12, 13], 6, sampled, seed=2)]
        outs += _drive(eng, r_plain)
        # (2) prefix hit: a new prompt sharing the served one's first
        # (block-aligned) 16 tokens clones the retained KV
        outs += _drive(eng, [eng.submit(shared + [71, 72], 8, greedy,
                                        seed=5)])
        # (3) chunked prefill: prompt longer than prefill_chunk=8
        outs += _drive(eng, [eng.submit(list(range(2, 25)), 6, greedy,
                                        seed=3)])
        # (4) mixed-adapter rows decoding concurrently
        r_mix = [eng.submit([21, 22, 23], 6, greedy, seed=4,
                            adapter_id="tenant-a"),
                 eng.submit([21, 22, 23], 6, greedy, seed=4,
                            adapter_id="tenant-b"),
                 eng.submit([21, 22, 23], 6, greedy, seed=4)]
        outs += _drive(eng, r_mix)
        # (5) preemption-resume: fill every slot with low-priority
        # work, then land a high-priority request (lossless park)
        lows = [eng.submit([31 + i, 32, 33], 24, sampled, seed=10 + i,
                           priority=0) for i in range(3)]
        _iterate(eng, lambda: all(r.generated for r in lows))
        assert not any(r.done() for r in lows)  # three live rows
        hi = eng.submit([41, 42], 4, greedy, seed=20, priority=1)
        outs += _drive(eng, [hi])
        outs += _drive(eng, lows)
        snap = eng.metrics.snapshot()
        evidence = dict(
            decode_traces=eng._decode_traces,
            verify_traces=eng._verify_traces,
            prefix_hits=snap["prefix_hits"],
            accepted=snap["accepted_tokens"],
            preemptions=snap["preemptions"],
            topo=eng.topo,
        )
        return outs, evidence
    finally:
        eng.close()


class TestTPShardedEngine:
    """Tentpole acceptance (a): the tp=2 engine is a PLACEMENT change,
    not a semantics change."""

    @pytest.mark.parametrize("kv_dtype", [None, "int8"])
    def test_tp2_token_exact_all_scenarios(self, tiny_model, kv_dtype):
        params, cfg = tiny_model
        gen = _gen(tiny_model, kv_dtype, eos_id=-1)
        base, ev1 = _drive_sink(gen, _sink_cfg(1, kv_dtype=kv_dtype),
                                cfg)
        tp2, ev2 = _drive_sink(gen, _sink_cfg(2, kv_dtype=kv_dtype),
                               cfg)
        assert base == tp2
        # decode + verify still one compile each on the sharded mesh
        assert ev2["decode_traces"] == 1 and ev2["verify_traces"] == 1
        # the scenarios actually happened (both arms)
        for ev in (ev1, ev2):
            assert ev["prefix_hits"] >= 1
            assert ev["accepted"] >= 1
            assert ev["preemptions"] >= 1
        # and the tp=1 arm really was the topology-free engine
        assert ev1["topo"] is None and ev2["topo"] is not None
        assert ev2["topo"].tp == 2

    def test_tp2_block_native_kernel_token_exact(self, tiny_model):
        """The Pallas block-native kernel under shard_map on the
        head-sharded arena: token-exact vs the tp=1 kernel engine,
        decode/verify one compile each."""
        params, cfg = tiny_model
        gen = _gen(tiny_model)
        sv = dict(num_slots=3, max_len=64, kv_block_size=16,
                  enable_prefix_cache=True, speculative_k=2,
                  block_native_attn=True)
        outs = {}
        for tp in (1, 2):
            eng = ServingEngine(gen, ServingConfig(
                serving_tp=tp, **sv).validate(cfg))
            try:
                reqs = [eng.submit([5, 17, 3, 42], 8,
                                   SamplingOptions(temperature=0.0),
                                   seed=0),
                        eng.submit([7, 8, 7, 8, 7, 8], 8,
                                   SamplingOptions(temperature=0.0),
                                   seed=1)]
                outs[tp] = [r.result(timeout=300)[0] for r in reqs]
                assert eng._decode_traces == 1
                snap = eng.metrics.snapshot()
                # kernel stays the zero-bracket path under shard_map
                assert snap["kv_attn_path"] == 2
                assert snap["kv_gather_bytes_per_step"] == 0
            finally:
                eng.close()
        assert outs[1] == outs[2]

    def test_tp1_builds_no_topology(self, tiny_model):
        """serving_tp=1 without disaggregation is the bit-identical
        default: no topology object, params/jits are the generator's
        own — the pre-topology code paths, by construction."""
        params, cfg = tiny_model
        gen = _gen(tiny_model)
        eng = ServingEngine(gen, ServingConfig(num_slots=2, max_len=64),
                            start=False)
        try:
            assert eng.topo is None
            assert eng._p_dec is gen.params and eng._p_pre is gen.params
        finally:
            eng.close()
        assert build_topology(ServingConfig()) is None

    def test_validate_rejections(self, tiny_model):
        params, cfg = tiny_model
        # head counts must divide: nkv=2 rejects tp=4... 4 % 4 == 0 for
        # nq but nkv=2 % 4 != 0
        with pytest.raises(AssertionError, match="head count"):
            ServingConfig(serving_tp=4).validate(cfg)
        with pytest.raises(AssertionError, match="serial"):
            ServingConfig(serving_tp=2,
                          serial_fallback=True).validate(cfg)
        # disaggregation needs the block pool (the handoff unit)
        with pytest.raises(AssertionError, match="kv_block_size"):
            ServingConfig(disaggregate_prefill=True).validate(cfg)
        # rolling pools have no defined block handoff
        roll = tiny_cfg(sliding_window=32, attention_impl="flash")
        with pytest.raises(AssertionError, match="ROLLING"):
            ServingConfig(disaggregate_prefill=True, kv_block_size=16,
                          max_len=64).validate(roll)

    def test_devices_per_engine(self):
        assert devices_per_engine(ServingConfig()) == 1
        assert devices_per_engine(ServingConfig(serving_tp=2)) == 2
        assert devices_per_engine(ServingConfig(
            serving_tp=2, disaggregate_prefill=True,
            kv_block_size=16)) == 4


class TestDisaggregatedServing:
    """Tentpole acceptance (b): prefill and decode on separate chip
    groups, the handoff block-granular, the fallback untouched."""

    def _serve(self, gen, cfg, prompts_and_n, **sv):
        eng = ServingEngine(gen, ServingConfig(
            num_slots=3, max_queue=32, max_len=64,
            kv_block_size=16, **sv).validate(cfg))
        try:
            reqs = [eng.submit(p, n, SamplingOptions(temperature=0.0),
                               seed=i)
                    for i, (p, n) in enumerate(prompts_and_n)]
            outs = [r.result(timeout=300)[0] for r in reqs]
            snap = eng.metrics.snapshot()
            return outs, snap, eng.topo
        finally:
            eng.close()

    @pytest.mark.parametrize("kv_dtype", [None, "int8"])
    def test_handoff_moves_only_live_blocks(self, tiny_model, kv_dtype):
        """handoff_bytes_per_req == ceil(plen/B) * B * bytes_per_token
        — the sequence's physical blocks, NEVER a cap-region copy —
        and outputs are token-exact vs the single-group fallback."""
        params, cfg = tiny_model
        gen = _gen(tiny_model, kv_dtype)
        jobs = [([5, 17, 3, 42], 6), (list(range(2, 22)), 6)]
        base, snap0, topo0 = self._serve(gen, cfg, jobs,
                                         kv_dtype=kv_dtype)
        # the knob-off engine is the pre-disaggregation code: no
        # topology, no handoffs
        assert topo0 is None and snap0["handoffs"] == 0
        assert snap0["handoff_bytes_per_req"] == 0
        dis, snap1, topo1 = self._serve(gen, cfg, jobs,
                                        kv_dtype=kv_dtype,
                                        disaggregate_prefill=True)
        assert base == dis
        assert topo1 is not None and topo1.disaggregated
        assert snap1["handoffs"] == len(jobs)
        # the LAST admission was the 20-token prompt: 2 live 16-token
        # blocks crossed the group boundary, not the 64-token region
        from megatron_tpu.serving.kv_pool import SlotKVPool
        pool = SlotKVPool(cfg, 1, 64,
                          dtype=(jnp.int8 if kv_dtype else jnp.bfloat16),
                          block_size=16)
        plen = len(jobs[-1][0])
        want = -(-plen // 16) * 16 * pool.bytes_per_token()
        assert snap1["handoff_bytes_per_req"] == want
        cap_bytes = 64 * pool.bytes_per_token()
        assert want < cap_bytes  # strictly less than a cap region

    def test_disagg_prefix_hit_preempt_token_exact(self, tiny_model):
        """Prefix hits (blocks ride decode->prefill for the suffix
        chunks), preemption-resume (parked subs stay on the decode
        group), and adapters (the bank's prefill-mesh mirror feeds the
        chunk forward) all compose with disaggregation, token-exact."""
        params, cfg = tiny_model
        gen = _gen(tiny_model)
        sv = dict(enable_prefix_cache=True, prefill_chunk=8,
                  priority_levels=2, preemption=True,
                  adapter_slots=1, adapter_rank=4)
        base = {}
        for dis in (False, True):
            eng = ServingEngine(gen, ServingConfig(
                num_slots=2, max_queue=32, max_len=64, kv_block_size=16,
                disaggregate_prefill=dis, **sv).validate(cfg))
            try:
                eng.register_adapter(
                    "tenant-a",
                    factors=random_adapter_factors(cfg, 4, seed=7),
                    rank=4, alpha=8.0)
                greedy = SamplingOptions(temperature=0.0)
                outs = [eng.submit([5, 17, 3, 42, 6, 7, 9, 2, 4, 8, 1,
                                    3, 5, 7, 9, 11, 2, 4], 6, greedy,
                                   seed=0).result(timeout=300)[0]]
                outs.append(eng.submit(
                    [21, 22, 23], 6, greedy, seed=4,
                    adapter_id="tenant-a").result(timeout=300)[0])
                # same prompt again: block-aligned prefix hit
                outs.append(eng.submit(
                    [5, 17, 3, 42, 6, 7, 9, 2, 4, 8, 1, 3, 5, 7, 9, 11,
                     30, 31], 6, greedy, seed=1).result(timeout=300)[0])
                lows = [eng.submit([31 + i, 32], 24,
                                   SamplingOptions(temperature=0.9,
                                                   top_k=5),
                                   seed=10 + i, priority=0)
                        for i in range(2)]
                t0 = time.monotonic()
                while any(len(r.generated) < 1 for r in lows):
                    time.sleep(0.002)
                    assert time.monotonic() - t0 < 120
                hi = eng.submit([41, 42], 4, greedy, seed=20,
                                priority=1)
                outs.append(hi.result(timeout=300)[0])
                outs += [r.result(timeout=300)[0] for r in lows]
                snap = eng.metrics.snapshot()
                assert snap["prefix_hits"] >= 1
                assert snap["preemptions"] >= 1
                base[dis] = outs
            finally:
                eng.close()
        assert base[False] == base[True]

    @pytest.mark.slow
    def test_disagg_tp2_four_device_groups(self, tiny_model):
        """tp=2 decode group + tp=2 prefill group (4 devices): the
        full topology, token-exact vs single-group tp=1."""
        params, cfg = tiny_model
        gen = _gen(tiny_model)
        jobs = [([5, 17, 3, 42], 8), (list(range(2, 20)), 6)]
        base, _, _ = self._serve(gen, cfg, jobs)
        dis, snap, topo = self._serve(gen, cfg, jobs, serving_tp=2,
                                      disaggregate_prefill=True,
                                      enable_prefix_cache=True)
        assert base == dis
        assert topo.tp == 2 and topo.disaggregated
        assert len(topo.devices) == 4
        assert snap["handoffs"] == len(jobs)

    def test_group_gauges_present_and_move(self, tiny_model):
        """prefill_group_busy / decode_group_busy are always-present
        schema (0.0 on a fresh scrape) and reflect occupancy after
        traffic."""
        from megatron_tpu.serving.metrics import ServingMetrics
        fresh = ServingMetrics().snapshot()
        for k in ("handoffs", "handoff_bytes_per_req",
                  "prefill_group_busy", "decode_group_busy"):
            assert k in fresh and fresh[k] == 0.0
        params, cfg = tiny_model
        gen = _gen(tiny_model)
        _, snap, _ = self._serve(gen, cfg, [([5, 6, 7], 6)],
                                 disaggregate_prefill=True)
        assert snap["decode_group_busy"] > 0.0

    def test_disagg_host_tier_restore_token_exact(self, tiny_model):
        """A host-tier restore on a disaggregated engine uploads ONLY
        the demoted entry's live blocks to the prefill group (widened
        on-device) and stays token-exact vs the single-group engine."""
        params, cfg = tiny_model
        gen = _gen(tiny_model)
        prefix = list(range(2, 20))  # > one 16-token block
        outs = {}
        for dis in (False, True):
            eng = ServingEngine(gen, ServingConfig(
                num_slots=2, max_queue=32, max_len=64, kv_block_size=16,
                enable_prefix_cache=True, retained_slots=1,
                host_kv_bytes=1 << 22,
                disaggregate_prefill=dis).validate(cfg))
            try:
                greedy = SamplingOptions(temperature=0.0)
                run = [eng.submit(prefix, 6, greedy,
                                  seed=0).result(timeout=300)[0]]
                # churn retained entries: the prefix demotes to host
                for f in ([40, 41, 42], [50, 51, 52], [60, 61, 62]):
                    eng.submit(f, 2, greedy, seed=0).result(timeout=300)
                run.append(eng.submit(prefix + [90, 91], 6, greedy,
                                      seed=1).result(timeout=300)[0])
                snap = eng.metrics.snapshot()
                assert snap["host_tier_demotions"] >= 1
                assert snap["host_tier_hits"] >= 1
                outs[dis] = run
            finally:
                eng.close()
        assert outs[False] == outs[True]

    def test_router_aggregate_carries_disagg_gauges(self):
        """The router's aggregate /metrics must surface the handoff /
        group-busy gauges (max across replicas) and SUM the handoffs
        counter — a fleet scrape that silently zeroed them would hide
        the disaggregation seam (caught by the e2e HTTP drive)."""
        from megatron_tpu.serving import EngineRouter
        from megatron_tpu.serving.metrics import ServingMetrics

        class StubEngine:
            max_len = 64

            def __init__(self, handoff, busy):
                self.metrics = ServingMetrics()
                self.metrics.count("handoffs", 2)
                self.metrics.set_handoff_gauge(handoff)
                self.metrics.set_group_gauges(busy, busy)

        router = EngineRouter([StubEngine(4096, 0.5),
                               StubEngine(8192, 1.0)])
        agg = router.aggregate_snapshot()
        assert agg["handoffs"] == 4.0
        assert agg["handoff_bytes_per_req"] == 8192.0
        assert agg["prefill_group_busy"] == 1.0
        assert agg["decode_group_busy"] == 1.0
