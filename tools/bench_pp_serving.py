"""Pipeline-sharded serving A/B: layer-staged decode vs the mono engine.

`--serving_pp S` (serving/topology.py "Pipeline-sharded serving")
splits the decode group into S layer-stage sub-meshes so a model whose
stacked layers exceed one chip group's HBM still serves — each stage
holds num_layers/S layers plus its slice of the per-layer KV arena,
and decode becomes a staged program chain with ONE [num_slots, hidden]
activation device_put per boundary. The cost is the pipeline bubble
(S-1)/(W+S-1), amortised by `--pp_waves W` interleaved waves on the
slot grid. This bench drives the SAME seeded staggered workload
(bench_disagg's arrivals) through three arms on one host:

- mono    — serving_pp=1 (the un-staged engine; the byte-identical
  baseline every staged arm must reproduce);
- pp2_w1  — serving_pp=2, pp_waves=1 (bubble 1/2);
- pp2_w2  — serving_pp=2, pp_waves=2 (bubble 1/3: wave B decodes while
  wave A's activation crosses the stage boundary).

Every arm runs greedy and MUST agree token-for-token (staging is a
placement change, not a semantics change — the assert is the point).
The record reports TTFT p50, inter-token p99, and decode tok/s per arm
plus each staged arm's `pp_stage_bubble` / `pp_activation_bytes_per_step`
gauge readings. On CPU the wall-clocks are harness smoke; ON CHIP the
pp2/mono decode tok/s ratio vs the analytic bubble — and whether W=2
claws back the gap — is the record: PERF_NOTES queue item 13.

  python tools/bench_pp_serving.py [--smoke] [--requests N] [--out FILE]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from megatron_tpu.utils.compile_cache import ensure_compile_cache
from tools import chaos_common as cc

# the staged arms need serving_pp=2 chips; force the 2-virtual-device
# CPU host (no-op when the caller already set flags or the platform is
# a real chip)
N_DEVICES = 2

# the four always-present staged-serving gauges (serving/metrics.py) —
# read from the engine snapshot, not recomputed, so a gauge-wiring
# regression fails the bench rather than hiding behind arithmetic
PP_GAUGES = ("serving_pp", "pp_waves", "pp_stage_bubble",
             "pp_activation_bytes_per_step")


def _run_pp_arm(gen, prompts, args, **sv_overrides) -> dict:
    """bench_disagg._run_serving_arm plus the staged-topology gauges.

    Same seeded workload, same watcher threads, same percentile
    treatment — the mono row must be comparable side by side with
    bench_disagg/bench_phase_topology records.
    """
    from megatron_tpu.config import ServingConfig
    from megatron_tpu.serving import SamplingOptions, ServingEngine
    from tools.bench_disagg import _percentile, _watch_tokens

    serving = ServingConfig(
        num_slots=args.slots, max_queue=max(len(prompts), 64),
        kv_block_size=args.block, prefill_chunk=args.chunk,
        **sv_overrides).validate(gen.cfg)
    sampling = SamplingOptions(temperature=0.0)  # greedy: arms must agree
    with ServingEngine(gen, serving) as eng:
        eng.generate(prompts[0], 2, sampling, seed=0)  # warm compiles
        snap0 = eng.metrics.snapshot()
        t0 = time.monotonic()
        reqs, watchers = [], []
        for i, p in enumerate(prompts):
            r = eng.submit(p, args.new, sampling, seed=i)
            times = []
            th = threading.Thread(target=_watch_tokens,
                                  args=(r, args.new, times), daemon=True)
            th.start()
            reqs.append(r)
            watchers.append((th, times))
            time.sleep(args.stagger_ms / 1e3)
        outs = [r.result(timeout=600)[0] for r in reqs]
        for th, _ in watchers:
            th.join(timeout=60)
        wall = time.monotonic() - t0
        snap = eng.metrics.snapshot()
    inter = []
    for _, times in watchers:
        inter += [b - a for a, b in zip(times, times[1:])]
    toks = int(snap["tokens_generated"] - snap0["tokens_generated"])
    r = {
        "outputs": outs,  # popped before emit; arms must agree
        "ttft_p50_ms": round(snap["ttft_p50_ms"], 2),
        "inter_token_p99_ms": round(_percentile(inter, 0.99) * 1e3, 2),
        "decode_tok_s": round(toks / max(wall, 1e-9), 1),
        "tokens_generated": toks,
        "wall_s": round(wall, 3),
    }
    for g in PP_GAUGES:
        r[g] = round(float(snap[g]), 4)
    return r


def main(argv=None):
    cc.force_host_devices(N_DEVICES)
    ensure_compile_cache()
    p = argparse.ArgumentParser("bench_pp_serving", description=__doc__)
    p.add_argument("--out", default="/tmp/bench_pp_serving.log")
    p.add_argument("--smoke", action="store_true",
                   help="tiny shapes for the CPU harness smoke")
    p.add_argument("--requests", type=int, default=12)
    p.add_argument("--prompt", type=int, default=96)
    p.add_argument("--new", type=int, default=32)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--block", type=int, default=16)
    p.add_argument("--chunk", type=int, default=32)
    p.add_argument("--stagger_ms", type=float, default=20.0)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--hidden", type=int, default=128)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--seq", type=int, default=256)
    args = p.parse_args(argv)
    if args.smoke:
        args.requests, args.prompt, args.new = 4, 40, 8
        args.slots, args.chunk, args.stagger_ms = 2, 16, 5.0
    assert args.layers % 2 == 0, "staged arms split layers across 2 stages"
    assert args.slots % 2 == 0, "the W=2 arm needs pp_waves | num_slots"

    import jax

    from tools.bench_disagg import _build

    gen, prompts = _build(args)
    ndev = len(jax.devices())

    record = {
        "bench": "pp_serving",
        "device": getattr(jax.devices()[0], "device_kind",
                          jax.devices()[0].platform),
        "devices": ndev,
        "requests": args.requests,
        "prompt": args.prompt,
        "new_tokens": args.new,
        "greedy_arms_token_exact": True,  # asserts below
    }
    out_path = args.out

    if ndev < 2:
        record["skipped"] = f"{ndev} device(s) < 2 (no staged arm fits)"
        line = json.dumps(record)
        print(line, flush=True)
        with open(out_path, "w") as f:
            f.write(line + "\n")
        return 0

    # ARMS: (name, serving overrides) — the only variable is the stage
    # depth / wave count, on ONE decode width
    arms = [("mono", {}),
            ("pp2_w1", dict(serving_pp=2, decode_tp=1)),
            ("pp2_w2", dict(serving_pp=2, decode_tp=1, pp_waves=2))]

    base_out = None
    for name, sv in arms:
        r = _run_pp_arm(gen, prompts, args, **sv)
        outs = r.pop("outputs")
        if base_out is None:
            base_out = outs
        else:
            assert outs == base_out, (
                f"{name} ({sv}) diverged from the mono arm: the staged "
                "decode chain is UNSOUND")
        # the gauge pins: bubble = (S-1)/(W+S-1), and the mono arm must
        # read all-zero (the schema keys exist, the plane is off)
        pp = int(sv.get("serving_pp", 1))
        waves = int(sv.get("pp_waves", 1))
        if pp > 1:
            want = (pp - 1) / (waves + pp - 1)
            assert abs(r["pp_stage_bubble"] - round(want, 4)) < 1e-9, (
                name, r["pp_stage_bubble"], want)
            assert r["pp_activation_bytes_per_step"] > 0, name
            assert (r["serving_pp"], r["pp_waves"]) == (pp, waves), name
        else:
            assert all(r[g] == 0.0 for g in PP_GAUGES), (name, r)
        record[name] = r

    # on chip the staged tax and the wave claw-back are the record
    mono = record["mono"]
    for name in ("pp2_w1", "pp2_w2"):
        record[name]["tok_s_vs_mono_x"] = round(
            record[name]["decode_tok_s"]
            / max(mono["decode_tok_s"], 1e-9), 2)

    line = json.dumps(record)
    print(line, flush=True)
    with open(out_path, "w") as f:
        f.write(line + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
