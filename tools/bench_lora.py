"""Multi-tenant LoRA serving A/B micro-bench on the serving engine.

Drives the SAME seeded decode-heavy workload through three arms:

- base:    no adapters (adapter_slots=0 — the pre-adapter engine);
- one:     every request under ONE adapter;
- mixed-8: requests round-robin across 8 distinct adapters in the same
           slot grid (the multi-tenant case — one batched gather +
           two rank-r matmuls per projection, still one decode trace).

Every arm runs greedy and EVERY ROW is pinned token-exact against its
own adapter's serial oracle — a plain Generator whose base weights have
that adapter's A·B·(alpha/rank) merged in (training/lora.py
merge_lora); the assert is the point of the A/B: batching
heterogeneous adapters is a scheduling change, not a semantics change.
Per arm it reports tok/s and the adapter-gather bytes each decode step
moves (slots x the per-row A/B factor slices — the Punica-style
gather's HBM cost, which the on-chip run judges against the base
decode's weight stream). On CPU the wall-clock is a harness smoke; ON
CHIP the gather-bytes ratio and the tok/s deltas transfer.

Emits ONE BENCH-style JSON record on stdout (and to --out); runs in
the bench.py extras chain (--smoke).

  python tools/bench_lora.py [--requests N] [--new N] [--adapters N]
                             [--rank R] [--smoke] [--out FILE]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from megatron_tpu.utils.compile_cache import ensure_compile_cache


def _build(args):
    import jax
    import numpy as np

    from megatron_tpu.config import ModelConfig
    from megatron_tpu.inference.generation import Generator
    from megatron_tpu.models import language_model as lm
    from megatron_tpu.serving.adapters import random_adapter_factors

    cfg = ModelConfig(
        num_layers=args.layers, hidden_size=args.hidden,
        num_attention_heads=args.heads,
        num_kv_heads=max(args.heads // 2, 1), vocab_size=args.vocab,
        seq_length=args.seq, max_position_embeddings=args.seq,
        make_vocab_size_divisible_by=64,
        # fp32 activations: every row is pinned vs a MERGED-weights
        # oracle, and factored-vs-merged only agrees token-for-token
        # when the ~1e-7 associativity drift is not amplified by bf16
        # rounding (the chaos drills' block-native precedent)
        compute_dtype="float32").derived()
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    gen = Generator(params, cfg, eos_id=-1, pad_id=0)
    rs = np.random.RandomState(0)
    prompts = [rs.randint(1, args.vocab, args.prompt).tolist()
               for _ in range(args.requests)]
    adapters = {f"tenant-{a}": random_adapter_factors(cfg, args.rank,
                                                      100 + a)
                for a in range(args.adapters)}
    return cfg, params, gen, prompts, adapters


def _oracle_outputs(cfg, params, prompts, new, adapters, assignment,
                    rank, alpha):
    """Per-request expected tokens: each request's own adapter's
    merged-weights serial Generator (None = base)."""
    import jax.numpy as jnp  # noqa: F401 — jax initialized by caller

    from megatron_tpu.inference.generation import (Generator,
                                                   SamplingParams)
    from megatron_tpu.training.lora import merge_lora

    oracles = {}
    want = []
    for p, aid in zip(prompts, assignment):
        if aid not in oracles:
            merged = (params if aid is None else
                      merge_lora(params, adapters[aid], cfg, rank, alpha))
            oracles[aid] = Generator(merged, cfg, eos_id=-1, pad_id=0)
        t, lens, _ = oracles[aid].generate(
            [p], new, sampling=SamplingParams(temperature=0.0))
        want.append(t[0, :lens[0]].tolist())
    return want


def _run_arm(gen, prompts, assignment, adapters, args, label) -> dict:
    from megatron_tpu.config import ServingConfig
    from megatron_tpu.serving import SamplingOptions, ServingEngine

    n_adapters = len({a for a in assignment if a is not None})
    serving = ServingConfig(
        num_slots=args.slots, max_queue=max(len(prompts), 64),
        adapter_slots=max(n_adapters, 1) if n_adapters else 0,
        adapter_rank=args.rank).validate(gen.cfg)
    sampling = SamplingOptions(temperature=0.0)
    with ServingEngine(gen, serving) as eng:
        for aid in sorted({a for a in assignment if a is not None}):
            eng.register_adapter(aid, factors=adapters[aid],
                                 rank=args.rank, alpha=args.alpha)
        eng.generate(prompts[0], 2, sampling, seed=0)  # warmup compile
        snap0 = eng.metrics.snapshot()
        t0 = time.monotonic()
        reqs = [eng.submit(p, args.new, sampling, seed=i, adapter_id=a)
                for i, (p, a) in enumerate(zip(prompts, assignment))]
        outs = [r.result(timeout=600)[0] for r in reqs]
        wall = time.monotonic() - t0
        snap = eng.metrics.snapshot()
        decode_traces = eng._decode_traces
    toks = int(snap["tokens_generated"] - snap0["tokens_generated"])
    return {
        "arm": label,
        "adapters": n_adapters,
        "outputs": outs,  # popped before emit after the exactness pin
        "tokens_generated": toks,
        "adapter_loads": int(snap["adapter_loads"]),
        "active_adapters": int(snap["active_adapters"]),
        "decode_traces": int(decode_traces),
        "tok_s": round(toks / max(wall, 1e-9), 1),
        "wall_s": round(wall, 3),
    }


def main(argv=None):
    ensure_compile_cache()
    p = argparse.ArgumentParser("bench_lora", description=__doc__)
    p.add_argument("--out", default="/tmp/bench_lora.log")
    p.add_argument("--smoke", action="store_true",
                   help="tiny fixed scenario for bench extras / CI")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--prompt", type=int, default=16)
    p.add_argument("--new", type=int, default=32)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--adapters", type=int, default=8)
    p.add_argument("--rank", type=int, default=4)
    p.add_argument("--alpha", type=float, default=8.0)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--hidden", type=int, default=128)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--seq", type=int, default=256)
    args = p.parse_args(argv)
    if args.smoke:
        args.requests, args.new, args.adapters = 6, 8, 3
        args.hidden, args.vocab, args.seq = 64, 128, 128
        args.prompt, args.slots = 8, 2

    import jax
    from megatron_tpu.serving.adapters import (adapter_bank_nbytes,
                                               adapter_factor_shapes)

    cfg, params, gen, prompts, adapters = _build(args)
    ids = sorted(adapters)
    arms_spec = [
        ("base", [None] * len(prompts)),
        ("one_adapter", [ids[0]] * len(prompts)),
        (f"mixed_{len(ids)}",
         [ids[i % len(ids)] for i in range(len(prompts))]),
    ]
    arms = []
    exact = True
    for label, assignment in arms_spec:
        arm = _run_arm(gen, prompts, assignment, adapters, args, label)
        want = _oracle_outputs(cfg, params, prompts, args.new, adapters,
                               assignment, args.rank, args.alpha)
        outs = arm.pop("outputs")
        if outs != want:
            exact = False
            print(f"bench_lora: arm {label} diverged from its "
                  "merged-weights oracles", file=sys.stderr)
        arms.append(arm)
    assert exact, ("per-row token agreement vs merged-weights serial "
                   "oracles FAILED: batched adapter serving is UNSOUND")

    # adapter-gather traffic per decode step: every slot pulls its
    # row's A/B factor slices (all 8 factors, all layers) — the
    # Punica-style gather the on-chip number is judged by
    import numpy as np
    per_row = sum(int(np.prod(s)) for s in
                  adapter_factor_shapes(cfg, args.rank).values()) * 4
    dev = jax.devices()[0]
    record = {
        "bench": "lora_adapters",
        "device": getattr(dev, "device_kind", dev.platform),
        "requests": args.requests,
        "new_tokens": args.new,
        "rank": args.rank,
        "alpha": args.alpha,
        "rows_token_exact_vs_merged_oracle": True,  # asserted above
        "one_decode_compile_per_arm": all(
            a["decode_traces"] == 1 for a in arms),
        "adapter_gather_bytes_per_step": per_row * args.slots,
        "bank_nbytes": adapter_bank_nbytes(cfg, len(ids), args.rank),
        "arms": arms,
        "mixed_vs_base_tok_s_x": round(
            arms[2]["tok_s"] / max(arms[0]["tok_s"], 1e-9), 3),
    }
    line = json.dumps(record)
    print(line, flush=True)
    with open(args.out, "w") as f:
        f.write(line + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
