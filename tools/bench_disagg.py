"""Interleave-vs-disaggregated serving A/B + a serving-tp decode arm.

A single-group engine interleaves chunked prefill with decode on one
chip (group): every admitted prompt steals decode iterations, so
running requests' inter-token latency spikes whenever traffic arrives —
and decode steals prefill FLOPs, so TTFT stretches under decode load.
Disaggregation (`--disaggregate_prefill`, serving/topology.py;
DistServe, PAPERS.md) moves the batch-1 prefill onto its own chip
group and hands finished KV to the decode group as a device-to-device
copy of the sequence's live blocks, so the two phases stop fighting.

This bench drives the SAME seeded mixed workload (staggered long-prompt
arrivals landing while earlier requests decode) through:

- interleave: single-group chunked-prefill engine (the fallback mode);
- disaggregated: same config + `disaggregate_prefill=True` (skipped
  with a note when the backend has < 2 devices).

Both arms run greedy and MUST agree token-for-token (disaggregation is
a placement change, not a semantics change — the assert is the point),
and the record reports the phase-interference numbers: TTFT p50,
inter-token p99 (per-token arrival timestamps via wait_token), decode
tok/s, and the handoff accounting (`handoff_bytes_per_req` ==
ceil(plen/B) * block bytes — the never-a-cap-region pin, asserted).

A second arm pair measures `--serving_tp`: tp=1 vs tp=2 decode tok/s
at matched workload (token-agreement asserted; skipped below 2
devices). On CPU every wall-clock here is a harness smoke; ON CHIP the
TTFT/ITL split and the tp scaling are the record — PERF_NOTES queue
item 10.

  python tools/bench_disagg.py [--smoke] [--requests N] [--out FILE]
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


def _build(args):
    import jax
    import numpy as np

    from megatron_tpu.config import ModelConfig
    from megatron_tpu.inference.generation import Generator
    from megatron_tpu.models import language_model as lm

    cfg = ModelConfig(
        num_layers=args.layers, hidden_size=args.hidden,
        num_attention_heads=args.heads,
        num_kv_heads=max(args.heads // 2, 1), vocab_size=args.vocab,
        seq_length=args.seq, max_position_embeddings=args.seq,
        make_vocab_size_divisible_by=64,
        compute_dtype="bfloat16").derived()
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    # eos_id=-1: no early EOS — every arm measures the same token volume
    gen = Generator(params, cfg, eos_id=-1, pad_id=0)
    rs = np.random.RandomState(0)
    prompts = [rs.randint(1, args.vocab, args.prompt).tolist()
               for _ in range(args.requests)]
    return gen, prompts


def _watch_tokens(req, n_new, times):
    """Record each token index's arrival wall-clock (the inter-token
    latency seam a streaming client actually observes)."""
    for i in range(n_new):
        if not req.wait_token(i, timeout=600):
            break
        times.append(time.monotonic())


def _percentile(vals, q):
    vals = sorted(vals)
    if not vals:
        return 0.0
    return vals[min(len(vals) - 1, max(0, int(q * len(vals))))]


def _run_serving_arm(gen, prompts, args, **sv_overrides) -> dict:
    from megatron_tpu.config import ServingConfig
    from megatron_tpu.serving import SamplingOptions, ServingEngine

    serving = ServingConfig(
        num_slots=args.slots, max_queue=max(len(prompts), 64),
        kv_block_size=args.block, prefill_chunk=args.chunk,
        **sv_overrides).validate(gen.cfg)
    sampling = SamplingOptions(temperature=0.0)  # greedy: arms must agree
    with ServingEngine(gen, serving) as eng:
        eng.generate(prompts[0], 2, sampling, seed=0)  # warm compiles
        snap0 = eng.metrics.snapshot()
        t0 = time.monotonic()
        reqs, watchers, itl_times = [], [], []
        for i, p in enumerate(prompts):
            r = eng.submit(p, args.new, sampling, seed=i)
            times = []
            th = threading.Thread(target=_watch_tokens,
                                  args=(r, args.new, times), daemon=True)
            th.start()
            reqs.append(r)
            watchers.append((th, times))
            # staggered arrivals: later prompts' prefills land WHILE
            # earlier requests decode — the interference the A/B is for
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
    return {
        "outputs": outs,  # popped before emit; arms must agree
        "ttft_p50_ms": round(snap["ttft_p50_ms"], 2),
        "inter_token_p99_ms": round(_percentile(inter, 0.99) * 1e3, 2),
        "decode_tok_s": round(toks / max(wall, 1e-9), 1),
        "tokens_generated": toks,
        "handoffs": int(snap["handoffs"] - snap0["handoffs"]),
        "handoff_bytes_per_req": int(snap["handoff_bytes_per_req"]),
        "wall_s": round(wall, 3),
    }


def main(argv=None):
    ensure_compile_cache()
    p = argparse.ArgumentParser("bench_disagg", description=__doc__)
    p.add_argument("--out", default="/tmp/bench_disagg.log")
    p.add_argument("--smoke", action="store_true",
                   help="tiny shapes for the CPU harness smoke")
    p.add_argument("--requests", type=int, default=12)
    p.add_argument("--prompt", type=int, default=96)
    p.add_argument("--new", type=int, default=32)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--block", type=int, default=16)
    p.add_argument("--chunk", type=int, default=32)
    p.add_argument("--stagger_ms", type=float, default=20.0)
    p.add_argument("--tp", type=int, default=2,
                   help="sharded-decode arm width (tp=1 baseline always)")
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
    from megatron_tpu.serving.kv_pool import SlotKVPool

    gen, prompts = _build(args)
    ndev = len(jax.devices())

    interleave = _run_serving_arm(gen, prompts, args)
    base_out = interleave.pop("outputs")
    assert interleave["handoffs"] == 0  # the fallback never hands off

    record = {
        "bench": "disagg_serving",
        "device": getattr(jax.devices()[0], "device_kind",
                          jax.devices()[0].platform),
        "devices": ndev,
        "requests": args.requests,
        "prompt": args.prompt,
        "new_tokens": args.new,
        "greedy_arms_token_exact": True,  # asserts below
        "interleave": interleave,
    }

    if ndev >= 2:
        dis = _run_serving_arm(gen, prompts, args,
                               disaggregate_prefill=True)
        assert dis.pop("outputs") == base_out, (
            "disaggregated arm diverged from the interleave fallback: "
            "the handoff is UNSOUND")
        # the handoff moved ceil(plen/B) live blocks, never a region
        pool = SlotKVPool(gen.cfg, 1, gen.cfg.max_position_embeddings,
                          block_size=args.block)
        want = (-(-args.prompt // args.block) * args.block
                * pool.bytes_per_token())
        assert dis["handoff_bytes_per_req"] == want, (
            dis["handoff_bytes_per_req"], want)
        assert dis["handoffs"] == args.requests
        dis["ttft_speedup_x"] = round(
            interleave["ttft_p50_ms"] / max(dis["ttft_p50_ms"], 1e-9), 2)
        dis["itl_p99_speedup_x"] = round(
            interleave["inter_token_p99_ms"]
            / max(dis["inter_token_p99_ms"], 1e-9), 2)
        record["disaggregated"] = dis
    else:
        record["disaggregated"] = {"skipped":
                                   f"{ndev} device(s) < 2 groups"}

    # serving-tp decode arm: tp=1 vs tp=N plain decode throughput.
    # Gate on the REAL validate (head counts AND padded vocab must
    # divide tp): an unsupported combination records a skip instead of
    # aborting the bench after the arms above already ran.
    tp_supported = ndev >= args.tp and args.tp > 1
    if tp_supported:
        from megatron_tpu.config import ServingConfig
        try:
            ServingConfig(num_slots=args.slots,
                          kv_block_size=args.block,
                          serving_tp=args.tp).validate(gen.cfg)
        except AssertionError as e:
            tp_supported = False
            record["tp_arms"] = {"skipped": f"validate: {e}"}
    if tp_supported:
        # the tp=1 side IS the interleave arm (identical config +
        # workload) — reuse its numbers and outputs instead of paying
        # a third engine build/compile/sweep
        tpn = _run_serving_arm(gen, prompts, args, serving_tp=args.tp)
        assert tpn.pop("outputs") == base_out, (
            f"serving_tp={args.tp} arm diverged: the sharded decode "
            "is UNSOUND")
        record["tp_arms"] = {
            "tp1_decode_tok_s": interleave["decode_tok_s"],
            f"tp{args.tp}_decode_tok_s": tpn["decode_tok_s"],
            "tp_speedup_x": round(
                tpn["decode_tok_s"]
                / max(interleave["decode_tok_s"], 1e-9), 2),
        }
    elif "tp_arms" not in record:
        record["tp_arms"] = {"skipped":
                             f"{ndev} device(s), tp={args.tp}"}

    line = json.dumps(record)
    print(line, flush=True)
    with open(args.out, "w") as f:
        f.write(line + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
