"""Throughput on a Llama-2-7B-architecture slice (32k default; any seq).

A full 7B cannot fit one v5e chip (fp32 params + Adam moments + grads =
16 bytes/param = ~112 GB), so this measures the largest TRUE-7B-WIDTH
slice that fits: h=4096, 32 heads, ffn=11008, vocab 32000, Pallas flash
attention, full remat, fp32 Adam — only num_layers shrinks (largest-first
ladder). The per-layer math (attention block sizes, MLP shapes, flash
tiles, remat behavior) is therefore exactly the 7B kernel path at the
requested sequence length.

Two BASELINE rows ride this tool:
- BASELINE config 5 (7B long-context 32k): default --seq_length 32768,
  RoPE scaling 8.0 (applied automatically for seq > 8192).
- BASELINE configs 1-2 (7B at training shapes): --seq_length 4096 —
  the VERDICT r3 item-3 measurement slice.

Beyond the per-slice tokens/s it measures the TWO largest feasible layer
counts, fits step_time(L) = a + b*L (b = per-layer time, a = the fixed
embedding/head/optimizer overhead), and emits an EXTRAPOLATED full-model
(32-layer) step time and tokens/s/chip — clearly labeled as an
extrapolation from a width-true slice, not a measured full-7B step.

Writes to --out (default /tmp/bench_32k.log) as well as stdout.

  python tools/bench_32k.py [--out FILE] [--iters N] [--seq_length N]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from megatron_tpu.utils.compile_cache import ensure_compile_cache

# bf16 peak FLOP/s (same table as bench.py detect_peak, abridged)
_V5E_PEAK = 197e12
_A100_BASELINE_TOKS = 890.0  # ref: docs/guide/getting_started.md:200-201


def main(argv=None):
    ensure_compile_cache()
    p = argparse.ArgumentParser("bench_32k", description=__doc__)
    p.add_argument("--out", default="/tmp/bench_32k.log")
    p.add_argument("--iters", type=int, default=5)
    p.add_argument("--warmup", type=int, default=2)  # min 1 (compile step)
    p.add_argument("--seq_length", type=int, default=32768)
    p.add_argument("--extrapolate_layers", type=int, default=32,
                   help="full-model layer count for the a+b*L fit")
    # width overrides exist ONLY for cheap CPU smoke tests of the
    # ladder/fit/emit logic; the 7B-width slice is the default
    p.add_argument("--hidden", type=int, default=4096)
    p.add_argument("--ffn", type=int, default=11008)
    p.add_argument("--heads", type=int, default=32)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from megatron_tpu.config import (MegatronConfig, OptimizerConfig,
                                     TrainingConfig, llama2_config)
    from megatron_tpu.training import init_train_state, make_train_step

    log = open(args.out, "w", buffering=1)

    def emit(line):
        print(line, flush=True)
        log.write(line + "\n")

    dev = jax.devices()[0]
    emit(f"device: {dev.platform} {getattr(dev, 'device_kind', '?')}")
    seq = args.seq_length
    seq_tag = f"{seq // 1024}k" if seq >= 1024 else str(seq)
    warmup = max(args.warmup, 1)  # the timing loop reads the warmup's `m`
    iters = max(args.iters, 1)

    last_err = None
    measured = []  # (layers, step_seconds)
    for layers in (4, 3, 2):
        model = llama2_config(
            "tiny", num_layers=layers, hidden_size=args.hidden,
            num_attention_heads=args.heads, num_kv_heads=args.heads,
            ffn_hidden_size=args.ffn,
            vocab_size=32000, seq_length=seq,
            # long-context runs use the scaled-RoPE recipe; training-shape
            # slices (BASELINE configs 1-2, seq <= 8k) use standard RoPE
            rope_scaling_factor=8.0 if seq > 8192 else 1.0,
            compute_dtype="bfloat16", attention_impl="flash",
            recompute_granularity="full")
        cfg = MegatronConfig(
            model=model,
            optimizer=OptimizerConfig(lr=1e-4, clip_grad=1.0),
            training=TrainingConfig(micro_batch_size=1,
                                    global_batch_size=1, train_iters=1),
        ).validate(n_devices=1)
        state = step = batch = m = tokens = None
        try:
            emit(f"trying {layers} layers x h4096 x seq {seq} ...")
            rng = jax.random.PRNGKey(0)
            state = init_train_state(rng, cfg)
            step = make_train_step(cfg)
            tokens = jax.random.randint(
                jax.random.PRNGKey(1), (1, 1, seq + 1), 0, 32000,
                dtype=jnp.int32)
            batch = {"tokens": tokens,
                     "loss_mask": jnp.ones((1, 1, seq), jnp.float32)}
            for i in range(warmup):
                state, m = step(state, batch, jax.random.fold_in(rng, i))
            jax.block_until_ready(m["lm_loss"])
            t0 = time.perf_counter()
            for i in range(iters):
                state, m = step(state, batch,
                                jax.random.fold_in(rng, 100 + i))
            jax.block_until_ready(m["lm_loss"])
            dt = (time.perf_counter() - t0) / iters
            n_params = sum(x.size for x in jax.tree.leaves(state.params))
            tok_s = seq / dt
            stats = None
            try:
                stats = dev.memory_stats()
            except Exception:
                pass
            record = {
                "metric": f"{seq_tag}_slice_train_tokens_per_sec_per_chip",
                "value": round(tok_s, 1),
                "layers": layers,
                "hidden": args.hidden,
                "seq": seq,
                "params_b": round(n_params / 1e9, 3),
                "step_ms": round(dt * 1e3, 1),
                "loss": float(m["lm_loss"]),
                "device_kind": getattr(dev, "device_kind", "?"),
                "peak_bytes": (stats or {}).get("peak_bytes_in_use"),
            }
            emit(json.dumps(record))
            measured.append((layers, dt))
            if len(measured) == 2:
                break  # two points fix the a + b*L fit
        except Exception as e:  # OOM / lowering failure: try fewer layers
            last_err = f"{type(e).__name__}: {str(e)[:400]}"
            emit(f"  failed: {last_err}")
        finally:
            # drop the attempt's live buffers (fp32 params + Adam moments)
            # BEFORE the next attempt allocates, or it OOMs on top of them
            state = step = batch = m = tokens = None  # noqa: F841
            import gc
            gc.collect()

    if not measured:
        emit(f"bench_32k: all layer counts failed; last: {last_err}")
        return 1

    # --- full-model extrapolation from the width-true slice ---
    LF = args.extrapolate_layers
    if len(measured) >= 2:
        (l1, t1), (l2, t2) = measured[:2]
        per_layer = (t1 - t2) / (l1 - l2)
        overhead = t1 - per_layer * l1
        fit = f"fit over L={l1},{l2}"
    else:
        (l1, t1) = measured[0]
        per_layer, overhead = t1 / l1, 0.0
        fit = f"single point L={l1} (overhead folded into per-layer)"
    t_full = overhead + per_layer * LF
    tok_s_full = seq / t_full
    flops_per_tok = 6 * 6.74e9  # fwd+bwd dense FLOPs at true 7B params
    record = {
        "metric": f"extrapolated_7b_{seq_tag}_tokens_per_sec_per_chip",
        "value": round(tok_s_full, 1),
        "note": (f"EXTRAPOLATED to {LF} layers from a width-true slice "
                 f"({fit}) — not a measured full-7B step"),
        "per_layer_ms": round(per_layer * 1e3, 2),
        "overhead_ms": round(overhead * 1e3, 2),
        "seq": seq,
        "mfu_at_v5e_peak": round(tok_s_full * flops_per_tok / _V5E_PEAK, 4),
        "vs_a100_baseline_toks": round(tok_s_full / _A100_BASELINE_TOKS, 3),
        "device_kind": getattr(dev, "device_kind", "?"),
    }
    emit(json.dumps(record))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
