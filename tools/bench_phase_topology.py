"""Symmetric-vs-asymmetric per-phase serving topology A/B.

Disaggregation (tools/bench_disagg.py, PERF_NOTES item 10) split
prefill and decode onto separate chip groups but kept both groups the
SAME width (`serving_tp` each side). The phases have opposite
rooflines — prefill is compute-bound, decode is HBM-bound — so the
optimal tp width differs per phase, and `prefill_tp` / `decode_tp`
(serving/topology.py "Per-phase parallelism") make the two mesh widths
independent knobs. This bench drives the SAME seeded staggered mixed
workload (long-prompt arrivals landing while earlier requests decode)
through three disaggregated arms on one device budget:

- symmetric   — prefill_tp=1, decode_tp=1 (the PR-13 layout: 2 chips);
- decode-heavy — prefill_tp=1, decode_tp=2 (3 chips: the decode-bound
  split the placement optimizer picks under high decode duty);
- prefill-heavy — prefill_tp=2, decode_tp=1 (3 chips: the TTFT-bound
  split under prompt floods).

Every arm runs greedy and MUST agree token-for-token (a per-phase
width change is a placement change, not a semantics change — the
assert is the point; the P!=D handoff reshards the kv-head axis inside
the one device_put, and the pinned `handoff_bytes_per_req` ==
ceil(plen/B) * block bytes shows no extra copy appeared). The record
reports TTFT p50, inter-token p99, and decode tok/s per arm plus each
arm's resolved topology gauges. On CPU the wall-clocks are harness
smoke; ON CHIP the decode-heavy/symmetric ITL ratio and the
prefill-heavy TTFT ratio are the record — PERF_NOTES queue item 12.

  python tools/bench_phase_topology.py [--smoke] [--requests N]
                                       [--out FILE]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from megatron_tpu.utils.compile_cache import ensure_compile_cache
from tools import chaos_common as cc

# the asymmetric arms need decode_tp + prefill_tp = 3 chips; force the
# 4-virtual-device CPU host the serving-tp tests run on (no-op when the
# caller already set flags or the platform is a real chip)
N_DEVICES = 4


def main(argv=None):
    cc.force_host_devices(N_DEVICES)
    ensure_compile_cache()
    p = argparse.ArgumentParser("bench_phase_topology",
                                description=__doc__)
    p.add_argument("--out", default="/tmp/bench_phase_topology.log")
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

    import jax

    # the workload/engine helpers are bench_disagg's (same seeded
    # prompts, same watcher threads, same percentile treatment — the
    # two records must be comparable side by side)
    from tools.bench_disagg import _build, _run_serving_arm
    from megatron_tpu.serving.kv_pool import SlotKVPool

    gen, prompts = _build(args)
    ndev = len(jax.devices())

    record = {
        "bench": "phase_topology",
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
        record["skipped"] = f"{ndev} device(s) < 2 (no disagg arm fits)"
        line = json.dumps(record)
        print(line, flush=True)
        with open(out_path, "w") as f:
            f.write(line + "\n")
        return 0

    # ARMS: (name, prefill_tp, decode_tp) — all disaggregated, so the
    # only variable is the per-phase split
    arms = [("symmetric", 1, 1)]
    if ndev >= 3:
        arms += [("decode_heavy", 1, 2), ("prefill_heavy", 2, 1)]
    else:
        record["asymmetric"] = {"skipped":
                                f"{ndev} device(s) < 3 (1+2 split)"}

    # the handoff moves ceil(plen/B) live blocks regardless of the
    # widths — a P!=D arm resharding inside the device_put must NOT
    # change the byte count (bytes_per_token is layout-independent)
    pool = SlotKVPool(gen.cfg, 1, gen.cfg.max_position_embeddings,
                      block_size=args.block)
    want_bytes = (-(-args.prompt // args.block) * args.block
                  * pool.bytes_per_token())

    base_out = None
    for name, ptp, dtp in arms:
        r = _run_serving_arm(gen, prompts, args,
                             disaggregate_prefill=True,
                             prefill_tp=ptp, decode_tp=dtp)
        outs = r.pop("outputs")
        if base_out is None:
            base_out = outs
        else:
            assert outs == base_out, (
                f"{name} (prefill_tp={ptp}, decode_tp={dtp}) diverged "
                "from the symmetric arm: the per-phase topology is "
                "UNSOUND")
        assert r["handoffs"] == args.requests, (name, r["handoffs"])
        assert r["handoff_bytes_per_req"] == want_bytes, (
            name, r["handoff_bytes_per_req"], want_bytes)
        r["prefill_tp"], r["decode_tp"] = ptp, dtp
        record[name] = r

    if "decode_heavy" in record:
        sym = record["symmetric"]
        record["decode_heavy"]["itl_p99_vs_symmetric_x"] = round(
            sym["inter_token_p99_ms"]
            / max(record["decode_heavy"]["inter_token_p99_ms"], 1e-9), 2)
        record["prefill_heavy"]["ttft_vs_symmetric_x"] = round(
            sym["ttft_p50_ms"]
            / max(record["prefill_heavy"]["ttft_p50_ms"], 1e-9), 2)

    line = json.dumps(record)
    print(line, flush=True)
    with open(out_path, "w") as f:
        f.write(line + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
