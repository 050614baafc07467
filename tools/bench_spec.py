"""Speculative-decoding A/B micro-bench on the serving engine.

Steady-state decode is HBM-bandwidth-bound: every step streams all
params plus the KV slice to commit ONE token per slot
(tools/bench_decode.py prints that roofline). `--speculative_k`
(serving/engine.py) verifies k self-drafted tokens per slot in one
[slots, k+1]-token forward, so the per-weight-stream commit rate rises
toward 1 + k * acceptance_rate. This bench drives the SAME seeded
decode-heavy workload through:

- baseline: speculative_k=0 (the plain one-token decode step);
- one arm per k in --ks (default 2,4,8).

All arms run greedy (temperature=0) and MUST agree token-for-token
with the baseline — speculation is a scheduling change, not a
semantics change; the assert is the point of the A/B. Per arm it
reports acceptance rate (accepted/draft — the engine's counter seam),
committed tokens per verify round, accepted-tok/s, and the speedup vs
baseline, next to the bench_decode-style HBM roofline so the numbers
are judged against the hardware: on the memory-bound path the ideal
speedup IS tokens-per-round, discounted by the verify window's extra
FLOPs (negligible until k+1 approaches the arithmetic-intensity
knee). On CPU the wall-clock is a harness smoke; ON CHIP the
acceptance rate and tokens/round transfer directly.

Emits ONE BENCH-style JSON record on stdout (and to --out), like the
other bench tools; runs in the bench.py extras chain.

  python tools/bench_spec.py [--requests N] [--new N] [--slots N]
                             [--ks 2,4,8] [--out FILE]
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

    cfg = ModelConfig(
        num_layers=args.layers, hidden_size=args.hidden,
        num_attention_heads=args.heads,
        num_kv_heads=max(args.heads // 2, 1), vocab_size=args.vocab,
        seq_length=args.seq, max_position_embeddings=args.seq,
        make_vocab_size_divisible_by=64,
        compute_dtype="bfloat16").derived()
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    # eos_id=-1: no early EOS — every request decodes exactly --new
    # tokens, so the arms measure the same token volume
    gen = Generator(params, cfg, eos_id=-1, pad_id=0)
    rs = np.random.RandomState(0)
    prompts = []
    for i in range(args.requests):
        # decode-heavy shape with a repetitive motif (the serving
        # traffic self-drafting pays off on: code, templates,
        # multi-turn chat) plus a unique head so the prefix index
        # never collapses the workload
        motif = rs.randint(1, args.vocab, rs.randint(2, 5)).tolist()
        head = rs.randint(1, args.vocab, 4).tolist()
        p = (head + motif * 6)[:args.prompt]
        prompts.append(p)
    return gen, prompts


def _run_arm(gen, prompts, args, k: int) -> dict:
    from megatron_tpu.config import ServingConfig
    from megatron_tpu.serving import SamplingOptions, ServingEngine

    serving = ServingConfig(num_slots=args.slots,
                            max_queue=max(len(prompts), 64),
                            speculative_k=k)
    sampling = SamplingOptions(temperature=0.0)  # greedy: arms must agree
    with ServingEngine(gen, serving) as eng:
        # warmup: compile the prefill bucket + the decode/verify pair
        eng.generate(prompts[0], 2, sampling, seed=0)
        snap0 = eng.metrics.snapshot()  # counters exclude the warmup
        t0 = time.monotonic()
        reqs = [eng.submit(p, args.new, sampling, seed=i)
                for i, p in enumerate(prompts)]
        outs = [r.result(timeout=600)[0] for r in reqs]
        wall = time.monotonic() - t0
        snap = eng.metrics.snapshot()

    def delta(key):
        return int(snap[key] - snap0[key])

    drafts = delta("draft_tokens")
    accepted = delta("accepted_tokens")
    rounds = delta("spec_rounds")
    toks = delta("tokens_generated")
    return {
        "speculative_k": k,
        "outputs": outs,  # popped before emit; arms must agree
        "tokens_generated": toks,
        "spec_rounds": rounds,
        "spec_fallback_steps": delta("spec_fallback_steps"),
        "draft_tokens": drafts,
        "accepted_tokens": accepted,
        "acceptance_rate": round(accepted / drafts, 3) if drafts else 0.0,
        # committed tokens per slot per weight-stream on verify rounds:
        # 1 (the t0 sample) + k * acceptance — the number the
        # memory-bound roofline scales by (plain decode commits 1)
        "tokens_per_round": (1.0 if k == 0 or not drafts else
                             round(1 + k * accepted / drafts, 3)),
        "accepted_tok_s": round(toks / max(wall, 1e-9), 1),
        "wall_s": round(wall, 3),
    }


def main(argv=None):
    ensure_compile_cache()
    p = argparse.ArgumentParser("bench_spec", description=__doc__)
    p.add_argument("--out", default="/tmp/bench_spec.log")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--prompt", type=int, default=16)
    p.add_argument("--new", type=int, default=48,
                   help="decode-heavy: tokens generated per request")
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--ks", type=str, default="2,4,8",
                   help="comma-separated speculative_k arms (0 = the "
                        "baseline, always run)")
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--hidden", type=int, default=128)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--seq", type=int, default=256)
    args = p.parse_args(argv)

    import jax
    gen, prompts = _build(args)
    base = _run_arm(gen, prompts, args, 0)
    base_out = base.pop("outputs")
    arms = []
    for k in [int(x) for x in args.ks.split(",") if x.strip()]:
        arm = _run_arm(gen, prompts, args, k)
        # speculation must be a scheduling change, not a semantics
        # change — greedy arms replay the baseline token-for-token
        assert arm.pop("outputs") == base_out, (
            f"k={k} arm diverged from baseline: speculative decode "
            "is UNSOUND")
        arm["speedup_x"] = round(arm["accepted_tok_s"]
                                 / max(base["accepted_tok_s"], 1e-9), 2)
        arms.append(arm)

    # bench_decode-style roofline context: bytes streamed per decode
    # step (all params + the mean-context KV slice) -> the ideal
    # one-token rate speculation multiplies by tokens_per_round
    from tools.bench_decode import _HBM_BW
    dev = jax.devices()[0]
    kind = getattr(dev, "device_kind", dev.platform)
    bw = next((v for kk, v in _HBM_BW.items()
               if kind.lower().startswith(kk.lower())), None)
    n_params = sum(x.size for x in jax.tree.leaves(gen.params))
    ctx = args.prompt + args.new / 2
    # geometry from the config actually built (not re-derived from raw
    # CLI args, which would silently drift if _build's formula changes)
    cfg = gen.cfg
    cache_bytes = (2 * cfg.num_layers * args.slots * ctx
                   * cfg.num_kv_heads * cfg.kv_channels * 2)
    step_bytes = n_params * 2 + cache_bytes
    roofline = {
        "step_bytes": int(step_bytes),
        "ideal_tok_s": (round(args.slots * bw / step_bytes, 1)
                        if bw else None),
        "note": ("ideal accepted-tok/s ~= ideal_tok_s * "
                 "tokens_per_round on the memory-bound path"),
    }

    record = {
        "bench": "speculative_decode",
        "device": kind,
        "requests": args.requests,
        "new_tokens": args.new,
        "greedy_arms_token_exact": True,  # the asserts above
        "baseline": base,
        "arms": arms,
        "best_speedup_x": max((a["speedup_x"] for a in arms),
                              default=1.0),
        "best_acceptance_rate": max((a["acceptance_rate"]
                                     for a in arms), default=0.0),
        "roofline": roofline,
    }
    line = json.dumps(record)
    print(line, flush=True)
    with open(args.out, "w") as f:
        f.write(line + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
