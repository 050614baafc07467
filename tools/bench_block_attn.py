"""Gather/scatter-bracket vs block-NATIVE attention A/B on the engine.

With `--kv_block_size B` every decode/verify dispatch used to bracket
its body with kv_pool.resolve_view/scatter_view: a full
[L, S, cap, nkv, hd] gather of the pool into a contiguous view plus a
scatter back, PER STEP — O(pool bytes) of HBM traffic spent relocating
KV the attention then streams again. `--block_native_attn`
(ops/block_attention_pallas.py) deletes the bracket: the Pallas kernel
reads the arena through the block map, and the step's KV append
scatters only the touched block. This bench drives the SAME seeded
greedy decode-heavy workload through both arms at every requested
block size x pool dtype:

- bracket arm: kv_block_size=B, block_native_attn off;
- kernel arm:  kv_block_size=B, block_native_attn on.

Arms MUST agree token-for-token — the kernel is a data-path change,
not a semantics change; the assert is the point of the A/B. Per combo
it reports decode tok/s, the speedup, and the bracket's measured
gather bytes/step (the engine's kv_gather_bytes_per_step gauge —
pinned 0 for the kernel arm) next to the ideal step bytes, so the
number is judged against what the hardware moves anyway: the bracket
arm pays (2 x view bytes) / step of PURE OVERHEAD on top of the
attention's own KV stream, and the kernel arm's win approaches that
ratio on the HBM-bound decode path. On CPU (pallas interpret mode)
the wall-clock is a harness smoke; ON CHIP the bytes ratio transfers
directly — PERF_NOTES queues that run.

Emits ONE BENCH-style JSON record on stdout (and to --out); runs in
the bench.py extras chain with --smoke.

  python tools/bench_block_attn.py [--blocks 16,64,256]
         [--dtypes bfloat16,int8] [--requests N] [--new N] [--smoke]
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
        compute_dtype=args.compute_dtype).derived()
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    # eos_id=-1: no early EOS — every request decodes exactly --new
    # tokens, so both arms measure the same token volume
    gen = Generator(params, cfg, eos_id=-1, pad_id=0)
    rs = np.random.RandomState(0)
    prompts = [rs.randint(1, args.vocab, args.prompt).tolist()
               for _ in range(args.requests)]
    return gen, prompts


def _run_arm(gen, prompts, args, block: int, dtype: str,
             kernel: bool) -> dict:
    from megatron_tpu.config import ServingConfig
    from megatron_tpu.serving import SamplingOptions, ServingEngine

    serving = ServingConfig(num_slots=args.slots,
                            max_queue=max(len(prompts), 64),
                            max_len=args.max_len, kv_dtype=dtype,
                            kv_block_size=block,
                            block_native_attn=kernel)
    sampling = SamplingOptions(temperature=0.0)  # greedy: arms agree
    with ServingEngine(gen, serving) as eng:
        assert eng._kernel_on == kernel, (
            "arm premise broken: block size >= cap degraded the pool "
            "to whole-region — shrink --blocks or grow --max_len")
        eng.generate(prompts[0], 2, sampling, seed=0)  # warmup/compile
        snap0 = eng.metrics.snapshot()
        t0 = time.monotonic()
        reqs = [eng.submit(p, args.new, sampling, seed=i)
                for i, p in enumerate(prompts)]
        outs = [r.result(timeout=600)[0] for r in reqs]
        wall = time.monotonic() - t0
        snap = eng.metrics.snapshot()
    toks = int(snap["tokens_generated"] - snap0["tokens_generated"])
    return {
        "attn_path": "block_native" if kernel else "gather_scatter",
        "outputs": outs,  # popped before emit; arms must agree
        "tokens_generated": toks,
        "decode_steps": int(snap["decode_steps"]
                            - snap0["decode_steps"]),
        # the A/B seam itself: bytes the resolve/scatter bracket moved
        # per decode step (gauge; 0 pinned for the kernel arm)
        "kv_gather_bytes_per_step": int(
            snap["kv_gather_bytes_per_step"]),
        "kv_attn_path": int(snap["kv_attn_path"]),
        "tok_s": round(toks / max(wall, 1e-9), 1),
        "wall_s": round(wall, 3),
    }


def main(argv=None):
    ensure_compile_cache()
    p = argparse.ArgumentParser("bench_block_attn", description=__doc__)
    p.add_argument("--out", default="/tmp/bench_block_attn.log")
    p.add_argument("--smoke", action="store_true",
                   help="one tiny combo (B=16, bf16) — the CI / "
                        "bench-extras harness check")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--prompt", type=int, default=16)
    p.add_argument("--new", type=int, default=32,
                   help="decode-heavy: tokens generated per request")
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--max_len", type=int, default=512,
                   help="slot capacity; every --blocks entry must "
                        "divide it STRICTLY (B == cap degrades to "
                        "whole-region and is no A/B at all)")
    p.add_argument("--blocks", type=str, default="16,64,256",
                   help="comma-separated kv_block_size arms")
    p.add_argument("--dtypes", type=str, default="bfloat16,int8",
                   help="comma-separated pool dtypes")
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--hidden", type=int, default=128)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--seq", type=int, default=None,
                   help="max_position_embeddings (default: max_len)")
    p.add_argument("--compute_dtype", type=str, default="float32",
                   help="activation dtype (float32 keeps the CPU "
                        "interpret-mode A/B numerically tight)")
    args = p.parse_args(argv)
    if args.smoke:
        args.blocks, args.dtypes = "16", "bfloat16"
        args.requests, args.new, args.max_len = 4, 8, 128
        args.hidden, args.vocab = 64, 128
    if args.seq is None:
        args.seq = args.max_len

    import jax
    gen, prompts = _build(args)
    combos = []
    for dtype in [d for d in args.dtypes.split(",") if d.strip()]:
        for block in [int(b) for b in args.blocks.split(",")
                      if b.strip()]:
            if block >= args.max_len:
                print(f"bench_block_attn: skipping B={block} >= cap "
                      f"{args.max_len} (whole-region degrade, no A/B)",
                      file=sys.stderr)
                continue
            bracket = _run_arm(gen, prompts, args, block, dtype, False)
            kernel = _run_arm(gen, prompts, args, block, dtype, True)
            # the kernel is a data-path change, not a semantics
            # change — greedy arms must replay each other exactly
            assert kernel.pop("outputs") == bracket.pop("outputs"), (
                f"B={block} dtype={dtype}: block-native arm diverged "
                "from the gather/scatter arm — the kernel is UNSOUND")
            assert kernel["kv_gather_bytes_per_step"] == 0, (
                "kernel arm still paid a resolve/scatter bracket")
            assert bracket["kv_gather_bytes_per_step"] > 0
            combos.append({
                "kv_block_size": block,
                "kv_dtype": dtype,
                "bracket": bracket,
                "kernel": kernel,
                "speedup_x": round(kernel["tok_s"]
                                   / max(bracket["tok_s"], 1e-9), 2),
                # the pure-overhead traffic the kernel deletes, as a
                # fraction of the bracket arm's whole KV view — the
                # on-chip win this ratio bounds
                "bracket_overhead_bytes_per_step":
                    bracket["kv_gather_bytes_per_step"],
            })

    dev = jax.devices()[0]
    record = {
        "bench": "block_native_attn",
        "device": getattr(dev, "device_kind", dev.platform),
        "requests": args.requests,
        "new_tokens": args.new,
        "max_len": args.max_len,
        "greedy_arms_token_exact": True,  # the asserts above
        "combos": combos,
        "best_speedup_x": max((c["speedup_x"] for c in combos),
                              default=1.0),
        "note": ("CPU wall-clock is a harness smoke (pallas interpret "
                 "mode); the bytes ratio is the on-chip claim — "
                 "PERF_NOTES queues the real-chip run"),
    }
    line = json.dumps(record)
    print(line, flush=True)
    with open(args.out, "w") as f:
        f.write(line + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
