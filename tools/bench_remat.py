"""Remat-policy A/B at the headline bench config, on one chip.

bench.py's headline 0.74B config runs `recompute_granularity="full"`.
Full remat recomputes the whole forward during the backward (~4/3x the counted
FLOPs) — if "none" (or selective) fits the v5e's 16 GB alongside fp32
Adam state (~11.8 GB at 0.74B), the step should shed most of that
recompute and the headline tokens/s rises accordingly.

Each arm is attempted independently; OOM / compile-helper failures are
caught and reported per arm, so one bad policy can't mask the others.
If an arm wins on-chip, promote it to bench.py's attempt list.

  python tools/bench_remat.py [--out FILE] [--iters N] [--smoke]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from megatron_tpu.utils.compile_cache import ensure_compile_cache


def main(argv=None):
    ensure_compile_cache()
    p = argparse.ArgumentParser("bench_remat", description=__doc__)
    p.add_argument("--out", default="/tmp/bench_remat.log")
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--smoke", action="store_true",
                   help="tiny shapes: exercises every arm in seconds "
                        "(CPU CI smoke; timings meaningless)")
    args = p.parse_args(argv)
    # the timing loop reads the warmup loop's m; and 0 iters would emit
    # tok_s=0, silently dropped from the best-arm report
    args.warmup = max(args.warmup, 1)
    args.iters = max(args.iters, 1)

    import jax
    import jax.numpy as jnp

    from megatron_tpu.config import (MegatronConfig, OptimizerConfig,
                                     TrainingConfig, llama2_config)
    from megatron_tpu.training import init_train_state, make_train_step

    log = open(args.out, "w", buffering=1)

    def emit(line):
        print(line, flush=True)
        log.write(line + "\n")

    emit("bench_remat: probing backend...")
    dev = jax.devices()[0]
    emit(f"device: {dev.platform} {getattr(dev, 'device_kind', '?')}")

    if args.smoke:
        shape = dict(num_layers=2, hidden_size=64, num_attention_heads=4,
                     num_kv_heads=4, ffn_hidden_size=128, vocab_size=128,
                     seq_length=64)
        micro_bs, n_micro = 1, 1
    else:
        # the bench.py headline 0.74B shape
        shape = dict(num_layers=12, hidden_size=2048,
                     num_attention_heads=16, num_kv_heads=16,
                     ffn_hidden_size=5504, vocab_size=32000,
                     seq_length=2048)
        micro_bs, n_micro = 2, 4

    results = {}
    # the int8 arm measures the quantized-GEMM training path at the same
    # shape: forward GEMMs on the int8 datapath (~2x bf16 MXU peak),
    # backward in bf16 — an upper bound of ~1.3x if matmul-bound
    for remat, qg in (("none", "none"), ("selective", "none"),
                      ("full", "none"), ("full", "int8")):
        arm = remat if qg == "none" else f"{remat}+int8"
        model = llama2_config("tiny", compute_dtype="bfloat16",
                              attention_impl="flash", quantized_gemm=qg,
                              recompute_granularity=remat, **shape)
        cfg = MegatronConfig(
            model=model,
            optimizer=OptimizerConfig(lr=1e-4, clip_grad=1.0),
            training=TrainingConfig(micro_batch_size=micro_bs,
                                    global_batch_size=micro_bs * n_micro,
                                    train_iters=args.iters),
        ).validate(n_devices=1)
        try:
            rng = jax.random.PRNGKey(0)
            state = init_train_state(rng, cfg)
            step = make_train_step(cfg)
            seq = cfg.model.seq_length
            tokens = jax.random.randint(
                jax.random.PRNGKey(1), (n_micro, micro_bs, seq + 1), 0,
                cfg.model.vocab_size, dtype=jnp.int32)
            batch = {"tokens": tokens,
                     "loss_mask": jnp.ones((n_micro, micro_bs, seq),
                                           jnp.float32)}
            t_compile = time.perf_counter()
            for i in range(args.warmup):
                state, m = step(state, batch, jax.random.fold_in(rng, i))
            jax.block_until_ready(m["lm_loss"])
            t0 = time.perf_counter()
            for i in range(args.iters):
                state, m = step(state, batch,
                                jax.random.fold_in(rng, args.warmup + i))
            jax.block_until_ready(m["lm_loss"])
            dt = time.perf_counter() - t0
            tok_s = n_micro * micro_bs * seq * args.iters / dt
            results[arm] = tok_s
            emit(f"remat={arm:9s}: {tok_s:9.1f} tok/s "
                 f"(warmup+compile {t0 - t_compile:.1f}s, "
                 f"loss {float(m['lm_loss']):.3f})")
        except Exception as e:
            results[arm] = None
            emit(f"remat={arm:9s}: FAILED {type(e).__name__}: "
                 f"{str(e)[:200]}")
        finally:
            # the failed arm's state pins HBM via live references —
            # drop before the next arm initializes
            state = step = batch = m = None

    ok = {k: v for k, v in results.items() if v}
    if ok:
        best = max(ok, key=ok.get)
        emit(f"best: remat={best} at {ok[best]:.1f} tok/s"
             + (f" ({ok[best] / ok['full'] - 1:+.1%} vs full)"
                if ok.get("full") else ""))
    emit("done")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
