"""Host-sync cadence + dispatch-gap micro-bench.

The async-dispatch layer (training/loop.py metrics window, serving
decode_sync_interval) exists to take host round-trips off the device's
critical path. This tool measures exactly that, before/after style:

- TRAINING arm: the same tiny train run twice — --sync_metrics
  semantics (fetch every step) vs the async window — counting host
  syncs through the loop's `_device_fetch` seam and timing steady-state
  ms/step. On CPU the times are only a harness smoke (the cpu backend
  keeps a one-step dispatch barrier — see loop.py overlap_dispatch);
  ON CHIP the delta between the two arms IS the dispatch gap the
  per-step fetch was costing.
- SERVING arm: the continuous-batching engine at decode_sync_interval
  1 vs K on the same seeded burst — host syncs/token (must be 1/K) and
  aggregate tok/s.

Emits ONE BENCH-style JSON record on stdout (and to --out), like the
other bench tools; runs in the bench.py extras chain.

  python tools/bench_sync.py [--iters N] [--log_interval N]
                             [--requests N] [--new N] [--sync_k K]
                             [--out FILE]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from megatron_tpu.utils.compile_cache import ensure_compile_cache


def _bench_training(args) -> dict:
    import dataclasses

    import jax
    import numpy as np

    from megatron_tpu.config import (DataConfig, MegatronConfig,
                                     ModelConfig, OptimizerConfig,
                                     TrainingConfig)
    from megatron_tpu.training import loop as loop_mod

    model = ModelConfig(
        num_layers=args.layers, hidden_size=args.hidden,
        num_attention_heads=args.heads, vocab_size=args.vocab,
        seq_length=args.seq, compute_dtype="bfloat16").derived()

    def cfg_for(sync: bool) -> MegatronConfig:
        return MegatronConfig(
            model=model,
            optimizer=OptimizerConfig(lr=1e-4),
            training=TrainingConfig(
                micro_batch_size=args.micro_batch,
                global_batch_size=args.micro_batch * 2,
                train_iters=args.iters, log_interval=args.log_interval,
                sync_metrics=sync),
            data=DataConfig(num_workers=0),
        ).validate(n_devices=1)

    rs = np.random.RandomState(0)

    def batches():
        while True:
            yield {"tokens": rs.randint(
                0, args.vocab,
                (2, args.micro_batch, args.seq + 1)).astype(np.int32),
                "loss_mask": np.ones(
                    (2, args.micro_batch, args.seq), np.float32)}

    def run(sync: bool) -> dict:
        calls = [0]
        real = loop_mod._device_fetch

        def counting(tree):
            calls[0] += 1
            return real(tree)

        loop_mod._device_fetch = counting
        try:
            t0 = time.perf_counter()
            loop_mod.train(cfg_for(sync), batches(),
                           rng=jax.random.PRNGKey(0))
            wall = time.perf_counter() - t0
        finally:
            loop_mod._device_fetch = real
        return {"host_syncs": calls[0],
                "host_syncs_per_step": round(calls[0] / args.iters, 4),
                "ms_per_step": round(wall * 1e3 / args.iters, 3)}

    sync = run(True)     # also absorbs the shared jit compile
    async_ = run(False)
    return {"sync": sync, "async": async_,
            "sync_reduction_x": round(
                sync["host_syncs"] / max(async_["host_syncs"], 1), 1)}


def _bench_serving(args) -> dict:
    import jax
    import numpy as np

    from megatron_tpu.config import ModelConfig, ServingConfig
    from megatron_tpu.inference.generation import Generator
    from megatron_tpu.models import language_model as lm
    from megatron_tpu.serving import SamplingOptions, ServingEngine

    cfg = ModelConfig(
        num_layers=args.layers, hidden_size=args.hidden,
        num_attention_heads=args.heads,
        num_kv_heads=max(args.heads // 2, 1), vocab_size=args.vocab,
        seq_length=args.seq, max_position_embeddings=args.seq,
        make_vocab_size_divisible_by=64,
        compute_dtype="bfloat16").derived()
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    gen = Generator(params, cfg, eos_id=0, pad_id=0)
    rs = np.random.RandomState(0)
    prompts = [rs.randint(1, cfg.vocab_size, 24).tolist()
               for _ in range(args.requests)]

    def run(K: int) -> dict:
        serving = ServingConfig(num_slots=args.slots,
                                max_queue=max(args.requests, 64),
                                decode_sync_interval=K)
        with ServingEngine(gen, serving) as eng:
            # warmup compiles (prefill buckets + the one decode trace)
            eng.generate(prompts[0], 2, SamplingOptions(temperature=1.0),
                         seed=0)
            t0 = time.monotonic()
            reqs = [eng.submit(p, args.new,
                               SamplingOptions(temperature=1.0),
                               seed=i) for i, p in enumerate(prompts)]
            for r in reqs:
                r.result(timeout=600)
            wall = time.monotonic() - t0
            snap = eng.metrics.snapshot()
        toks = snap["tokens_generated"]
        return {"decode_sync_interval": K,
                "tokens": int(toks),
                "decode_steps": int(snap["decode_steps"]),
                "host_syncs": int(snap["host_syncs"]),
                "syncs_per_step": round(snap["host_syncs"]
                                        / max(snap["decode_steps"], 1),
                                        4),  # == 1/K by construction
                "syncs_per_token": round(snap["host_syncs"]
                                         / max(toks, 1), 4),
                "wasted_decode_steps": int(
                    snap.get("wasted_decode_steps", 0)),
                "prompts_per_prefill": round(
                    snap.get("prompts_per_prefill", 1.0), 2),
                "tokens_per_s": round(toks / max(wall, 1e-9), 1)}

    base = run(1)
    k = run(args.sync_k)
    return {"k1": base, "k": k,
            "sync_reduction_x": round(
                base["syncs_per_token"]
                / max(k["syncs_per_token"], 1e-9), 1)}


def main(argv=None):
    ensure_compile_cache()
    p = argparse.ArgumentParser("bench_sync", description=__doc__)
    p.add_argument("--out", default="/tmp/bench_sync.log")
    p.add_argument("--iters", type=int, default=24)
    p.add_argument("--log_interval", type=int, default=8)
    p.add_argument("--micro_batch", type=int, default=2)
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--new", type=int, default=24)
    p.add_argument("--sync_k", type=int, default=4,
                   help="decode_sync_interval for the K arm")
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--hidden", type=int, default=128)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--seq", type=int, default=128)
    args = p.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    record = {
        "bench": "sync_cadence",
        "device": getattr(dev, "device_kind", dev.platform),
        "training": _bench_training(args),
        "serving": _bench_serving(args),
    }
    line = json.dumps(record)
    print(line, flush=True)
    with open(args.out, "w") as f:
        f.write(line + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
