"""Deterministic chaos-mesh conformance: seeded fault schedules +
system-wide invariants over the full serving matrix.

The scripted drills (chaos_serve / chaos_router / chaos_upgrade) each
walk ONE hand-written scenario. This tool is the FoundationDB-style
complement: from a single ``--seed`` it

1. **samples a serving config** from the capability matrix — pool
   layout (whole-region / block / block-native kernel), prefix cache +
   chunked prefill + host tier, speculative decoding, adapters,
   priorities/preemption/shedding, serving_tp, disaggregation with
   per-phase widths (prefill_tp / decode_tp — asymmetric splits
   included), pipeline-sharded decode (serving_pp stage chains,
   optionally wave-interleaved), replicas, int8 KV, rolling
   sliding-window models —
   driving the REAL
   ``ServingConfig.validate()`` as the rejection filter, so illegal
   combinations (rolling x speculative, kernel x sliding-window, ...)
   are exercised as LOUD-rejection cases (recorded per run), never
   silently skipped;
2. **generates a randomized workload** — shared prefixes, priorities,
   hopeless deadlines, adapter mix, seeded stochastic sampling, a
   streaming consumer, mid-flight cancels, grammar-constrained
   requests (seeded draws from a bounded/cyclic regex + json_schema
   pool, checked by the grammar-validity law AND token-exact vs a
   quiet single-slot oracle engine), and n=2 COW fan-out requests
   (each sample independently seed-checked);
3. **interleaves a randomized fault schedule** — engine-step faults
   drawn from the extended `FaultInjector` (serve_delay / serve_crash /
   serve_nan / serve_host_corrupt / serve_adapter_corrupt) plus
   harness actions (queue-overload burst, replica kill, live-weight
   swap, torn/corrupt publish) — then
4. **checks the system-wide invariants** (serving/invariants.py)
   during and after the storm: request conservation, typed terminals
   (zero stranded futures), token-exactness of every COMPLETED request
   vs a serial oracle keyed by its (seed, sampling, adapter,
   weight-version), KV-block accounting + namespace isolation, metrics
   schema stability, and healthz consistency.

A failing run prints the one-line repro (``--seed S [--require ...]``)
with the violated laws. ``--minutes N`` soak mode walks seeds until
the budget expires; ``--smoke`` runs a small fixed seed set covering
adapters, disaggregation, a live-weight swap, the brownout
degradation ladder, and a pipeline-sharded stage chain (bench extras
+ the slow-tier test run it);
``--inject_violation`` deliberately drops a
terminal transition after a green run to prove the checker is not
vacuous (test-pinned).

  JAX_PLATFORMS=cpu python tools/chaos_mesh.py --seed 7
  JAX_PLATFORMS=cpu python tools/chaos_mesh.py --smoke [--out FILE]
  JAX_PLATFORMS=cpu python tools/chaos_mesh.py --minutes 10
"""
from __future__ import annotations

import argparse
import json
import os
import random
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from megatron_tpu.utils.compile_cache import ensure_compile_cache
from tools import chaos_common as cc

N_DEVICES = 4  # forced host platform: disagg/tp configs need 2x2

# smoke seed set: each (seed, require) pair is a full repro line; the
# `require` tokens bias the sampler toward a matrix corner so the
# fixed smoke always covers adapters, disaggregation, a live-weight
# swap, structured output, and n-best fan-out regardless of what the
# bare seed would draw
SMOKE_SEEDS = [(7, ("adapters",)), (11, ("disagg",)), (23, ("swap",)),
               (31, ("structured",)), (43, ("fanout",)),
               (53, ("phases",)),  # asymmetric per-phase disagg split
               (61, ("degrade",)),  # brownout ladder + SLO accounting
               (71, ("pp",))]  # pipeline-sharded (layer-staged) decode

# the seeded grammar pool: every entry compiles against the tiny
# model's vocab-128 identity token table (token i <-> chr(i)), so
# masked decoding emits literal ASCII. `bounded` entries have an
# acyclic DFA — the workload gives them max_new_tokens >= the longest
# path, which arms the law-7 PARSE check (final_text_valid), not just
# per-token legality; the cyclic entry keeps unbounded-grammar
# coverage (validity-only).
GRAMMAR_POOL = [
    {"type": "regex", "pattern": "(ab|ba){2,3}"},
    {"type": "regex", "pattern": "[0-9]{2,5}"},
    {"type": "regex", "pattern": "(foo|bar|quux)"},
    {"type": "regex", "pattern": "a[bc]*d"},  # cyclic: validity-only
    {"type": "json_schema",
     "schema": {"type": "integer", "minimum": 0, "maxDigits": 3}},
]


# ---------------------------------------------------------------------
# 1. seeded config sampling (validate() as the rejection filter)
# ---------------------------------------------------------------------
def sample_config(rng: random.Random, require=()):
    """Sample (model_kwargs, serving_kwargs, rejections) — resampling
    through ServingConfig.validate() until a LEGAL point of the
    capability matrix comes up; every rejection is recorded (matrix
    exclusions exercised loudly, not skipped). What rejects is
    validate()'s alone to say: its rules between options, and the table
    of model kind x feature in serving/capabilities.py. The fault
    schedule is sampled separately (build_fault_injector /
    build_actions)."""
    from megatron_tpu.config import ServingConfig
    rejections = []
    for _ in range(200):
        rolling = rng.random() < 0.15 and "disagg" not in require \
            and "tp" not in require and "phases" not in require \
            and "pp" not in require
        model_kwargs = dict(compute="float32", num_kv_heads=2)
        if rolling:
            model_kwargs.update(sliding_window=64,
                                attention_impl="flash")
        blocks = rng.random() < 0.7
        kw = dict(
            num_slots=2, max_queue=64, max_len=128,
            max_engine_restarts=2,
            kv_block_size=16 if blocks else None,
            block_native_attn=blocks and rng.random() < 0.5,
            enable_prefix_cache=rng.random() < 0.6,
            prefill_chunk=8 if rng.random() < 0.3 else None,
            retained_slots=rng.choice([None, None, 1]),
            speculative_k=4 if rng.random() < 0.35 else 0,
            adapter_slots=2 if rng.random() < 0.35 else 0,
            kv_dtype="int8" if rng.random() < 0.2 else None,
            shed_on_overload=rng.random() < 0.5,
            serving_tp=2 if rng.random() < 0.2 else 1,
            disaggregate_prefill=rng.random() < 0.25,
            num_replicas=2 if rng.random() < 0.4 else 1,
        )
        # per-phase widths (serving/topology.py): disaggregated configs
        # draw independent prefill_tp/decode_tp — asymmetric splits are
        # the point. A small slice deliberately draws ILL-FORMED
        # corners (validate()'s width rules), which must come back as
        # LOUD rejections, never silent coercion.
        if kw["disaggregate_prefill"] and rng.random() < 0.35:
            kw["prefill_tp"] = rng.choice([1, 2])
            kw["decode_tp"] = rng.choice([1, 2])
        elif rng.random() < 0.08:
            kw["prefill_tp"] = rng.choice([2, 3])
        # pipeline-sharded serving axis (serving/topology.py
        # "Pipeline-sharded serving"): a slice draws a 2-stage
        # layer-staged decode chain, half of it wave-interleaved. The
        # draw deliberately lands on ILLEGAL pairings too (validate()'s
        # serving_pp rules) — all must come back as LOUD rejections,
        # never silent coercion.
        if rng.random() < 0.2:
            kw["serving_pp"] = 2
            if rng.random() < 0.5:
                kw["pp_waves"] = 2
        if rng.random() < 0.5:
            kw.update(priority_levels=2,
                      preemption=rng.random() < 0.7)
        # brownout ladder + SLO accounting axis (serving/degrade.py):
        # degraded admissions stay oracle-exact because the
        # token-exact law keys off the request's EFFECTIVE
        # max_new_tokens, not the spec it was submitted with
        if rng.random() < 0.3:
            kw.update(degrade_ladder=rng.choice([2, 4]),
                      degrade_max_new_tokens=6)
        if rng.random() < 0.25:
            kw.update(slo_ttft_ms=30_000.0, slo_itl_p99_ms=30_000.0)
        if rng.random() < 0.35:
            kw["engine_step_timeout_s"] = 2.0
        if kw["enable_prefix_cache"] and kw["kv_block_size"] \
                and rng.random() < 0.4:
            kw["host_kv_bytes"] = 1 << 22
        # require biases (part of the repro line): force the matrix
        # corner the caller wants covered
        if "adapters" in require:
            kw["adapter_slots"] = 2
        if "disagg" in require:
            kw.update(disaggregate_prefill=True, kv_block_size=16)
        if "router" in require:
            kw["num_replicas"] = 2
        if "tp" in require:
            kw["serving_tp"] = 2
        if "phases" in require:
            # asymmetric per-phase disagg split (1 prefill chip : 2
            # decode chips — fits the 4-device budget with slack)
            kw.update(disaggregate_prefill=True, kv_block_size=16,
                      serving_tp=1, prefill_tp=1, decode_tp=2,
                      num_replicas=1)
        if "degrade" in require:
            # full brownout ladder with hair-trigger raise edges and
            # minimal dwell so the mesh storm actually walks it under
            # a 2-slot engine, plus live SLO accounting
            kw.update(degrade_ladder=4,
                      degrade_raise_at=(0.25, 0.5, 1.0, 2.0),
                      degrade_dwell_up=1, degrade_dwell_down=2,
                      degrade_max_new_tokens=6,
                      shed_on_overload=True, priority_levels=2,
                      slo_ttft_ms=30_000.0, slo_itl_p99_ms=30_000.0)
        if "pp" in require:
            # layer-staged decode chain (2 stages x width 1) with the
            # second wave interleaved on the slot grid. validate()'s
            # serving_pp rules would reject the other draws, so pin
            # the legal corner; the bare engine keeps fan-out
            # admissible, exercising COW forks over the staged pool
            kw.update(serving_pp=2, decode_tp=1, pp_waves=2,
                      kv_block_size=16, block_native_attn=False,
                      disaggregate_prefill=False, speculative_k=0,
                      serving_tp=1, num_replicas=1)
            kw.pop("prefill_tp", None)
            kw.pop("host_kv_bytes", None)
        if "fanout" in require:
            # fan-out aggregates are engine-level (the router's retry
            # pump refuses best_of > 1 typed) — pin a bare engine so
            # the required n=2 specs actually admit
            kw["num_replicas"] = 1
        # resource clamp (not a matrix exclusion): N_DEVICES virtual
        # devices must fit num_replicas x devices_per_engine — the
        # same per-phase arithmetic serving/topology.devices_per_engine
        # resolves (decode width + prefill width when disaggregated)
        ptp = kw.get("prefill_tp") or kw["serving_tp"]
        dtp = kw.get("decode_tp") or kw["serving_tp"]
        per = dtp * kw.get("serving_pp", 1) \
            + (ptp if kw["disaggregate_prefill"] else 0)
        if per * kw["num_replicas"] > N_DEVICES:
            kw["num_replicas"] = 1
        if per > N_DEVICES:
            kw["serving_tp"] = 1
            kw.pop("prefill_tp", None)
            kw.pop("decode_tp", None)
            kw.pop("serving_pp", None)
            kw.pop("pp_waves", None)
        model = cc.tiny_model_cfg(**model_kwargs)
        try:
            ServingConfig(**kw).validate(model)
        except AssertionError as e:
            rejections.append({
                "kwargs": {k: v for k, v in kw.items() if v},
                "rolling": rolling,
                "rejected": str(e).splitlines()[0][:160],
            })
            continue
        return model_kwargs, kw, rejections
    raise RuntimeError(
        f"sample_config: 200 consecutive validate() rejections "
        f"(sampler/matrix drift?): last={rejections[-1]}")


# ---------------------------------------------------------------------
# 2. seeded workload
# ---------------------------------------------------------------------
def build_workload(rng: random.Random, serving_kw: dict,
                   n_requests: int, new_tokens: int, require=()):
    """Randomized request specs: shared prefixes, priorities, hopeless
    deadlines, adapter mix, seeded stochastic sampling (greedy-only
    when speculative — stochastic spec rows are distribution-correct,
    not serial-bit-reproducing), grammar-constrained requests from
    GRAMMAR_POOL, and n=2 fan-out requests (bare engines only — the
    router refuses best_of > 1 typed). The grammar draw rides the SAME
    seeded rng stream as everything else, so the ``--seed`` repro line
    regenerates the exact grammars too. Returns (specs, cancel_idx,
    stream_idx)."""
    from megatron_tpu.serving import SamplingOptions
    from megatron_tpu.serving.structured import compile_response_format
    prefixes = [[rng.randrange(2, 120) for _ in range(rng.choice([16, 20]))]
                for _ in range(2)]
    adapters = ([None, "tenant-0", "tenant-1"]
                if serving_kw.get("adapter_slots") else [None])
    fanout_ok = serving_kw.get("num_replicas", 1) == 1
    specs = []
    for i in range(n_requests):
        if rng.random() < 0.4:
            prompt = list(rng.choice(prefixes)) + \
                [rng.randrange(2, 120) for _ in range(rng.randrange(1, 5))]
        else:
            prompt = [rng.randrange(2, 120)
                      for _ in range(rng.randrange(3, 20))]
        if serving_kw.get("speculative_k") or rng.random() < 0.6:
            sampling = SamplingOptions(temperature=0.0)
        else:
            sampling = SamplingOptions(temperature=0.8, top_k=5)
        specs.append(dict(
            prompt=prompt,
            max_new_tokens=rng.randrange(3, new_tokens + 1),
            sampling=sampling,
            seed=rng.randrange(1 << 20),
            priority=(rng.randrange(2)
                      if serving_kw.get("priority_levels", 1) > 1 else 0),
            deadline_s=(0.001 if rng.random() < 0.12 else None),
            adapter_id=rng.choice(adapters),
        ))
        # structured axis: grammar-constrained decode under the storm
        # (law 7 checks FSM legality + parse; the quiet-engine oracle
        # pins the masked stream token-exact)
        if rng.random() < 0.25 or ("structured" in require and i == 1):
            rf = rng.choice(GRAMMAR_POOL)
            fsm = compile_response_format(rf, 128)
            specs[i]["response_format"] = rf
            specs[i]["deadline_s"] = None  # completed streams feed law 7
            if fsm.max_path_len is not None:
                # bounded grammar: budget covers the longest path, so
                # the sweep's PARSE check arms (not just legality)
                specs[i]["max_new_tokens"] = fsm.max_path_len
        # fan-out axis: n=2 COW samples off one prefill (num_slots=2
        # caps best_of at 2 here); composes with structured draws
        if fanout_ok and (rng.random() < 0.2
                          or ("fanout" in require and i == 1)):
            specs[i]["n"] = 2
            specs[i]["best_of"] = 2
        # at least one deadline-less greedy request so the storm
        # always has an oracle-checkable completion
        if i == 0:
            specs[0]["deadline_s"] = None
            specs[0]["sampling"] = SamplingOptions(temperature=0.0)
    cancel_idx = rng.randrange(n_requests) if rng.random() < 0.6 else None
    stream_idx = rng.randrange(n_requests)
    return specs, cancel_idx, stream_idx


def build_fault_injector(rng: random.Random, serving_kw: dict):
    """Seeded engine-step fault schedule over the EXTENDED FaultInjector
    kinds (docs/resilience.md 'Chaos conformance' has the grammar)."""
    from megatron_tpu.resilience import FaultInjector
    kinds = []
    kw = dict(serve_delay_calls={}, serve_crash_calls=set(),
              serve_nan_calls={}, serve_host_corrupt_calls=set(),
              serve_adapter_corrupt_calls=set())
    if rng.random() < 0.5:
        kw["serve_crash_calls"].add(rng.randrange(4, 12))
        kinds.append("serve_crash")
    if rng.random() < 0.5:
        kw["serve_nan_calls"][rng.randrange(3, 10)] = rng.randrange(2)
        kinds.append("serve_nan")
    if rng.random() < 0.35:
        stall = (3.0 if serving_kw.get("engine_step_timeout_s")
                 else 0.3)  # past-watchdog wedge vs plain stall
        kw["serve_delay_calls"][rng.randrange(3, 10)] = stall
        kinds.append("serve_delay")
    if serving_kw.get("host_kv_bytes"):
        kw["serve_host_corrupt_calls"].add(rng.randrange(5, 20))
        kinds.append("serve_host_corrupt")
    if serving_kw.get("adapter_slots") and rng.random() < 0.5:
        kw["serve_adapter_corrupt_calls"].add(rng.randrange(5, 20))
        kinds.append("serve_adapter_corrupt")
    return FaultInjector(**kw), kinds


def build_actions(rng: random.Random, serving_kw: dict, require=()):
    """Harness-level fault actions (the kinds an injector fault point
    cannot reach): overload burst, replica kill, live-weight swap,
    torn (corrupt) publish."""
    actions = []
    if rng.random() < 0.7:
        actions.append("burst")
    if serving_kw.get("num_replicas", 1) > 1 and rng.random() < 0.5:
        actions.append("kill_replica")
    do_swap = "swap" in require or rng.random() < 0.3
    if do_swap:
        if rng.random() < 0.5:
            actions.append("swap_corrupt")  # refused BEFORE the good one
        actions.append("swap_good")
    rng.shuffle(actions)
    return actions


# ---------------------------------------------------------------------
# 3+4. the storm + invariant sweeps
# ---------------------------------------------------------------------
def _build_target(model_kwargs: dict, serving_kw: dict):
    """(target, engines, gen) — a bare engine or an EngineRouter fleet,
    devices sliced per replica when the topology needs them."""
    import jax

    from megatron_tpu.config import ServingConfig
    from megatron_tpu.serving import EngineRouter, ServingEngine
    model = cc.tiny_model_cfg(**model_kwargs)
    gen = cc.tiny_generator(model, seed=0)
    serving = ServingConfig(**serving_kw).validate(model)
    n_rep = serving_kw.get("num_replicas", 1)
    # per-replica window size under the RESOLVED per-phase topology
    # (decode_tp + prefill_tp when disaggregated — the same arithmetic
    # inference/server.py slices with)
    from megatron_tpu.serving.topology import devices_per_engine
    per = devices_per_engine(serving)
    devs = jax.devices()
    if per > 1:
        engines = [ServingEngine(gen, serving,
                                 devices=devs[i * per:(i + 1) * per])
                   for i in range(n_rep)]
    else:
        engines = [ServingEngine(gen, serving) for _ in range(n_rep)]
    if n_rep > 1:
        return (EngineRouter(engines, max_retries=2,
                             heartbeat_timeout_s=2.0,
                             probe_backoff_s=0.2),
                engines, gen)
    return engines[0], engines, gen


def _make_oracles(gen, model_kwargs: dict, serving_kw: dict,
                  adapters: dict, gen_v2=None, aux=None):
    """Per-weight-version oracle fns for invariants.check_token_exact:
    each maps a completed request -> the serial ground truth for its
    (prompt, n, seed, sampling) under its adapter's MERGED weights.
    Int8 pools get int8-kv serial generators (matched cache numerics).
    Grammar-constrained requests route to a lazily-built QUIET oracle
    engine instead (single slot, no faults, no speculation): the
    serial Generator has no mask seam, but a calm engine walking the
    same seeded chain is the ground truth the stormed engine must
    match. Engines built here are appended to `aux` for the caller to
    close."""
    import jax.numpy as jnp

    from megatron_tpu.inference.generation import (Generator,
                                                   SamplingParams)
    kv_dtype = (jnp.int8 if serving_kw.get("kv_dtype") == "int8"
                else jnp.bfloat16)
    rank, alpha = 4, 8.0
    aux = aux if aux is not None else []

    def _mk(base_gen):
        cache = {}

        def _gen_for(adapter_id):
            if adapter_id not in cache:
                if adapter_id is None:
                    params = base_gen.params
                else:
                    from megatron_tpu.training.lora import merge_lora
                    params = merge_lora(base_gen.params,
                                        adapters[adapter_id],
                                        base_gen.cfg, rank, alpha)
                cache[adapter_id] = Generator(params, base_gen.cfg,
                                              eos_id=-1, pad_id=0,
                                              kv_cache_dtype=kv_dtype)
            return cache[adapter_id]

        quiet = []

        def _quiet_engine():
            if not quiet:
                from megatron_tpu.config import ServingConfig
                from megatron_tpu.serving import ServingEngine
                skw = dict(num_slots=1, max_queue=64,
                           max_len=serving_kw.get("max_len", 128))
                if serving_kw.get("kv_dtype"):
                    skw["kv_dtype"] = serving_kw["kv_dtype"]
                if serving_kw.get("adapter_slots"):
                    skw["adapter_slots"] = serving_kw["adapter_slots"]
                eng = ServingEngine(
                    base_gen,
                    ServingConfig(**skw).validate(base_gen.cfg))
                for aid, factors in sorted(adapters.items()):
                    eng.register_adapter(aid, factors=factors,
                                         rank=rank, alpha=alpha)
                aux.append(eng)
                quiet.append(eng)
            return quiet[0]

        want_cache = {}

        def want(req):
            sp = req.sampling if hasattr(req, "sampling") \
                else req.spec["sampling"]
            seed = req.seed if hasattr(req, "seed") else req.spec["seed"]
            n = (req.max_new_tokens if hasattr(req, "max_new_tokens")
                 else req.spec["max_new_tokens"])
            aid = getattr(req, "adapter_id", None)
            if aid is None and hasattr(req, "spec"):
                aid = req.spec.get("adapter_id")
            rf = getattr(req, "response_format", None)
            if rf is None and hasattr(req, "spec"):
                rf = req.spec.get("response_format")
            key = (aid, tuple(req.prompt), n, seed,
                   (sp.temperature, sp.top_k, sp.top_p),
                   json.dumps(rf, sort_keys=True) if rf else None)
            if key not in want_cache:
                if rf is not None:
                    r2 = _quiet_engine().submit(
                        list(req.prompt), n, sp, seed=seed,
                        adapter_id=aid, response_format=rf)
                    # result() is prompt + generated, same shape the
                    # token-exact law compares against
                    toks, _ = r2.result(timeout=120.0)
                    want_cache[key] = list(toks)
                else:
                    t, lens, _ = _gen_for(aid).generate(
                        [list(req.prompt)], n,
                        sampling=SamplingParams(
                            temperature=sp.temperature,
                            top_k=sp.top_k, top_p=sp.top_p),
                        seed=seed)
                    want_cache[key] = t[0, :lens[0]].tolist()
            return want_cache[key]

        return want

    oracles = [_mk(gen)]
    if gen_v2 is not None:
        oracles.append(_mk(gen_v2))
    return oracles


def run_one(seed: int, require=(), n_requests: int = 12,
            new_tokens: int = 10, inject_violation: bool = False) -> dict:
    """One seeded conformance run. Returns the record; record["ok"] is
    the verdict and record["repro"] the one-line reproduction."""
    from megatron_tpu.resilience import use_fault_injector
    from megatron_tpu.serving import SamplingOptions

    rng = random.Random(seed)
    t0 = time.monotonic()
    # the FULL repro line: the rng stream's consumption depends on the
    # workload-size knobs too, so a repro without them replays a
    # different storm (and likely comes back green)
    repro = (f"python tools/chaos_mesh.py --seed {seed}"
             + (f" --require {','.join(require)}" if require else "")
             + f" --requests {n_requests} --new_tokens {new_tokens}")
    model_kwargs, serving_kw, rejections = sample_config(rng, require)
    specs, cancel_idx, stream_idx = build_workload(
        rng, serving_kw, n_requests, new_tokens, require=require)
    injector, fault_kinds = build_fault_injector(rng, serving_kw)
    actions = build_actions(rng, serving_kw, require)

    target, engines, gen = _build_target(model_kwargs, serving_kw)
    model = gen.cfg
    adapters = {}
    if serving_kw.get("adapter_slots"):
        adapters = cc.make_adapters(model, 2, rank=4)
        for aid, factors in sorted(adapters.items()):
            target.register_adapter(aid, factors=factors, rank=4,
                                    alpha=8.0)
    gen_v2 = root = d2 = None
    if "swap_good" in actions or "swap_corrupt" in actions:
        gen_v2 = cc.tiny_generator(model, seed=1)
        root = tempfile.mkdtemp(prefix="chaos_mesh_")
        d2 = cc.publish_checkpoint(root, model, gen_v2.params, 2)

    greedy = SamplingOptions(temperature=0.0)
    record = {
        "seed": seed, "require": list(require), "repro": repro,
        "config": {k: v for k, v in serving_kw.items() if v},
        "model": {k: v for k, v in model_kwargs.items()
                  if k != "compute"},
        "validate_rejections": len(rejections),
        "rejection_kinds": [r["rejected"] for r in rejections],
        "fault_kinds": fault_kinds, "actions": actions,
        # the seeded structured/fan-out draw (grammars regenerate from
        # the --seed repro line; recorded for log-line readability)
        "grammars": sorted({json.dumps(s["response_format"],
                                       sort_keys=True)
                            for s in specs if s.get("response_format")}),
        "fanout_specs": sum(1 for s in specs if s.get("best_of", 1) > 1),
    }
    reqs: list = []
    action_log = []
    stream_seen: list = []
    violations: list = []
    aux_engines: list = []  # quiet oracle engines (closed in finally)
    try:
        # warmup: compiles + the shed estimator's first sample, BEFORE
        # the injector arms (the fault schedule indexes steady steps)
        for eng in engines:
            eng.generate([3, 1, 4], 2, greedy, seed=0)
        with use_fault_injector(injector):
            for i, spec in enumerate(specs):
                try:
                    r = target.submit(**spec)
                    reqs.append(r)
                    if i == stream_idx:
                        # fan-out aggregates have no token stream of
                        # their own — follow sample 0, like the SSE
                        # layer's sample-major generator does
                        watch = (getattr(r, "children", None) or [r])[0]
                        threading.Thread(
                            target=_stream_watch,
                            args=(watch, stream_seen), daemon=True).start()
                    if i == cancel_idx:
                        time.sleep(0.01)
                        target.cancel(r)
                except Exception as e:  # noqa: BLE001 — typed rejections
                    action_log.append(
                        ("submit_rejected", type(e).__name__))
                time.sleep(0.005)
            for act in actions:
                time.sleep(0.05)
                action_log.append(
                    (act, _run_action(act, target, engines, rng, specs,
                                      reqs, d2, greedy)))
            # mid-storm LIGHT sweep: race-safe laws only
            mid = cc.invariant_sweep(target, strict=False)
            violations.extend(mid["violations"])
            # ride out the storm WITH the injector active (scheduled
            # step faults must be able to land mid-decode, not only
            # during the brief submission window); outcomes are
            # classified by the strict sweep below
            for r in reqs:
                try:
                    r.result(timeout=120.0)
                except Exception:  # noqa: BLE001 — typed-checked below
                    pass
        # post-storm STRICT sweep: resolve every future (typed
        # terminals / zero stranded), full accounting, oracle
        # exactness at every admitted weight version
        oracles = _make_oracles(gen, model_kwargs, serving_kw,
                                adapters, gen_v2=gen_v2,
                                aux=aux_engines)
        final = cc.invariant_sweep(target, reqs=reqs, oracles=oracles,
                                   strict=True, timeout=120.0)
        violations.extend(final["violations"])
        record["outcomes"] = final.get("outcomes", {})
        record["token_exact"] = final.get("token_exact", {})
        record["laws_checked"] = final.get("laws_checked", [])
        if inject_violation:
            # drop a terminal transition (the checker-not-vacuous pin):
            # the strict conservation law must now fail and report the
            # seed repro. Tamper verdicts stay SEPARATE from the real
            # storm's — an injected run must not mask a genuine
            # violation as "caught as intended"
            engines[0].metrics._counters["requests_completed"] -= 1
            tampered = cc.invariant_sweep(target, strict=True)
            record["injected_violation_caught"] = not tampered["ok"]
            record["injected_sweep_violations"] = (
                tampered["violations"]
                or ["[inject] tampered counter NOT caught — checker "
                    "is vacuous"])
    finally:
        try:
            target.close()
        except Exception:  # noqa: BLE001
            pass
        for eng in aux_engines:
            try:
                eng.close()
            except Exception:  # noqa: BLE001
                pass
    record.update({
        "faults_fired": [f"{k}:{d}" for k, d in injector.fired],
        "action_log": action_log,
        "stream_tokens_seen": len(stream_seen),
        "violations": violations,
        "wall_s": round(time.monotonic() - t0, 1),
        # an injected run still FAILS on genuine storm violations —
        # only the deliberately-tampered sweep's catch flips to "good"
        "ok": (not violations
               and (not inject_violation
                    or bool(record.get("injected_violation_caught")))),
    })
    if not record["ok"]:
        print(f"chaos_mesh: INVARIANT VIOLATION — repro: {repro}",
              file=sys.stderr)
        for v in violations:
            print(f"chaos_mesh:   {v}", file=sys.stderr)
    return record


def _stream_watch(req, seen: list):
    """Streaming consumer: follows tokens via wait_token the way the
    SSE layer does (exercises the per-token condition path under
    chaos); the committed stream it sees must be a prefix of the final
    result, which the oracle sweep already pins."""
    i = 0
    while req.wait_token(i, timeout=60.0):
        gen = list(req.generated)
        if len(gen) <= i:
            break  # terminal
        seen.append(gen[i])
        i += 1


def _run_action(act: str, target, engines, rng, specs, reqs, d2,
                greedy) -> str:
    """Execute one harness-level fault action; returns a short verdict
    string for the record (typed failures are EXPECTED outcomes)."""
    if act == "burst":
        n = 0
        for _ in range(6):
            spec = dict(rng.choice(specs))
            spec["seed"] = rng.randrange(1 << 20)
            try:
                reqs.append(target.submit(**spec))
                n += 1
            except Exception:  # noqa: BLE001 — 429/503 are the point
                pass
        return f"submitted {n}/6"
    if act == "kill_replica":
        engines[0].close()  # in-process analogue of an OOM-killed pod
        return "replica 0 closed"
    if act == "swap_corrupt":
        import glob
        import shutil
        # torn publish: corrupt a COPY so the later good swap still
        # has an intact checkpoint to apply
        bad = d2 + "_torn"
        if not os.path.isdir(bad):
            shutil.copytree(d2, bad)
            cc.corrupt_payload(bad)
        try:
            if hasattr(target, "rolling_upgrade"):
                target.rolling_upgrade(bad, swap_timeout_s=60)
            else:
                target.swap_weights(bad, timeout=60)
            return "corrupt swap APPLIED (gate failed!)"
        except Exception as e:  # noqa: BLE001 — typed refusal expected
            return f"refused typed: {type(e).__name__}"
    if act == "swap_good":
        try:
            if hasattr(target, "rolling_upgrade"):
                v = target.rolling_upgrade(d2, swap_timeout_s=60)
            else:
                v = target.swap_weights(d2, timeout=60)
            return f"swapped to {v.label}"
        except Exception as e:  # noqa: BLE001 — e.g. killed replica
            return f"not applied: {type(e).__name__}"
    return "unknown action"


# ---------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------
def run_smoke(n_requests: int, new_tokens: int) -> dict:
    runs = [run_one(seed, require, n_requests=n_requests,
                    new_tokens=new_tokens)
            for seed, require in SMOKE_SEEDS]
    ok = all(r["ok"] for r in runs)
    return {
        "metric": "chaos_mesh_configs_green",
        "value": sum(1 for r in runs if r["ok"]),
        "unit": (f"seeded configs with every invariant green "
                 f"(of {len(runs)}: adapters/disagg/live-swap/"
                 f"structured/fanout/asymmetric-phases/degrade "
                 f"corners)"),
        "vs_baseline": None,
        "completed": ok,
        "seed": SMOKE_SEEDS[0][0],
        "seeds": [list(s) for s in SMOKE_SEEDS],
        "runs": runs,
        "wall_s": round(sum(r["wall_s"] for r in runs), 1),
    }


def run_soak(minutes: float, start_seed: int, n_requests: int,
             new_tokens: int, require=()) -> dict:
    """Walk seeds until the budget expires; stop at the first
    violation (its repro line is the product). `require` biases every
    sampled config (and rides each run's repro line) — soaking a
    specific matrix corner."""
    deadline = time.monotonic() + minutes * 60.0
    runs, seed = [], start_seed
    first_bad = None
    while time.monotonic() < deadline:
        r = run_one(seed, require, n_requests=n_requests,
                    new_tokens=new_tokens)
        runs.append({k: r[k] for k in ("seed", "ok", "wall_s",
                                       "violations", "repro")})
        if not r["ok"]:
            first_bad = r
            break
        seed += 1
    ok = first_bad is None
    return {
        "metric": "chaos_mesh_soak_seeds_green",
        "value": sum(1 for r in runs if r["ok"]),
        "unit": (f"seeds green in {minutes:.1f} min soak "
                 f"(start --seed {start_seed}"
                 + (f", require {','.join(require)}" if require else "")
                 + ")"),
        "vs_baseline": None,
        "completed": ok,
        "seed": start_seed,
        "runs": runs,
        "first_violation": first_bad,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=None,
                    help="run ONE seeded conformance storm (the repro "
                         "knob: config + workload + fault schedule all "
                         "derive from it)")
    ap.add_argument("--require", type=str, default="",
                    help="comma-separated sampler biases (part of the "
                         "repro line): adapters, disagg, router, tp, "
                         "phases, swap, structured, fanout, degrade")
    ap.add_argument("--smoke", action="store_true",
                    help="fixed seed set for bench extras / CI: >= 6 "
                         "distinct configs covering adapters, "
                         "disaggregation, a live-weight swap, "
                         "structured output, n-best fan-out, and an "
                         "asymmetric per-phase (prefill_tp!=decode_tp) "
                         "disagg split")
    ap.add_argument("--minutes", type=float, default=None,
                    help="soak mode: walk seeds until the wall-clock "
                         "budget expires; stop at the first violation")
    ap.add_argument("--requests", type=int, default=12,
                    help="workload size per seed")
    ap.add_argument("--new_tokens", type=int, default=10,
                    help="max decode length per request")
    ap.add_argument("--inject_violation", action="store_true",
                    help="after the run, deliberately drop a terminal "
                         "transition and REQUIRE the checker to catch "
                         "it (exit 0 iff caught) — the checker-not-"
                         "vacuous pin")
    ap.add_argument("--out", type=str, default=None,
                    help="also write the JSON record here")
    args = ap.parse_args(argv)

    cc.force_host_devices(N_DEVICES)
    ensure_compile_cache()
    require = tuple(t for t in args.require.split(",") if t)

    if args.minutes is not None:
        record = run_soak(args.minutes, args.seed or 0, args.requests,
                          args.new_tokens, require=require)
    elif args.smoke:
        record = run_smoke(args.requests, args.new_tokens)
    else:
        seed = args.seed if args.seed is not None else 0
        one = run_one(seed, require, n_requests=args.requests,
                      new_tokens=args.new_tokens,
                      inject_violation=args.inject_violation)
        record = {
            "metric": "chaos_mesh_invariants_green",
            "value": 1.0 if one["ok"] else 0.0,
            "unit": "seeded config x workload x fault schedule, all "
                    "system invariants",
            "vs_baseline": None,
            "completed": one["ok"],
            **one,
        }
    cc.emit_record(record, args.out, seed=record.get("seed", 0))
    return 0 if record["completed"] else 1


if __name__ == "__main__":
    sys.exit(main())
