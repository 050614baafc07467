"""Serving throughput: prefill + per-token decode on the current chip.

The training benches (bench.py, bench_32k.py) cover the MXU-bound
training path; this measures the OTHER serving-critical numbers the
reference's text_generation_server lives on (ref:
megatron/text_generation/generation.py:89-285):

- prefill latency (the flash-prefill path, offset-0 Pallas kernel) and
- steady-state decode tokens/s (the KV-cache lax.scan loop — HBM
  bandwidth-bound: every step streams all params + the cache).

Model: a llama-architecture preset sized to leave room for the KV cache
(bf16 params for serving — no optimizer state). The decode roofline is
printed next to the measurement: tok/s_ideal = HBM_BW / bytes(params +
cache slice), so the number is judged against the hardware, not vibes.

  python tools/bench_decode.py [--out FILE] [--batch N] [--prompt N]
                               [--new N] [--layers N] [--hidden N]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from megatron_tpu.utils.compile_cache import ensure_compile_cache

# HBM bandwidth by device kind (public spec sheets), bytes/s
_HBM_BW = {
    "TPU v5 lite": 819e9,
    "TPU v5e": 819e9,
    "TPU v4": 1228e9,
    "TPU v5": 2765e9,
    "TPU v5p": 2765e9,
    "TPU v6": 1640e9,
    "cpu": None,
}


def main(argv=None):
    ensure_compile_cache()
    p = argparse.ArgumentParser("bench_decode", description=__doc__)
    p.add_argument("--out", default="/tmp/bench_decode.log")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--prompt", type=int, default=512)
    p.add_argument("--new", type=int, default=128)
    p.add_argument("--layers", type=int, default=12)
    p.add_argument("--hidden", type=int, default=2048)
    p.add_argument("--heads", type=int, default=16)
    p.add_argument("--ffn", type=int, default=5504)
    p.add_argument("--vocab", type=int, default=32000)
    p.add_argument("--int8_weights", action="store_true",
                   help="ALSO measure with int8-resident transformer "
                        "weights (ops/quantized.quantize_weights) — the "
                        "weight stream halves, so the bandwidth-bound "
                        "decode should speed up toward its new roofline")
    p.add_argument("--int8_kv", action="store_true",
                   help="ALSO measure with the int8 KV cache "
                        "(Generator kv_cache_dtype=jnp.int8) — halves "
                        "the cache stream, the dominant term at long "
                        "context; with --int8_weights a combined arm "
                        "runs too")
    p.add_argument("--sliding_window", type=int, default=None,
                   help="banded attention + ROLLING W-slot cache: decode "
                        "streams O(W) cache bytes instead of O(context)")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from megatron_tpu.config import llama2_config
    from megatron_tpu.inference.generation import Generator
    from megatron_tpu.models import language_model as lm

    log = open(args.out, "w", buffering=1)

    def emit(line):
        print(line, flush=True)
        log.write(line + "\n")

    dev = jax.devices()[0]
    kind = getattr(dev, "device_kind", dev.platform)
    emit(f"device: {dev.platform} {kind}")

    cfg = llama2_config(
        "tiny", num_layers=args.layers, hidden_size=args.hidden,
        num_attention_heads=args.heads, num_kv_heads=args.heads,
        ffn_hidden_size=args.ffn, vocab_size=args.vocab,
        seq_length=args.prompt + args.new, compute_dtype="bfloat16",
        attention_impl="flash", sliding_window=args.sliding_window)

    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    # serving layout: bf16 params (the reference serves fp16 — Float16Module)
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    n_params = sum(p.size for p in jax.tree.leaves(params))
    # mirror Generator.generate's 64-bucketing: the cache rolls only when
    # the window is smaller than the bucketed max_len (init_kv_caches)
    bucketed = -(-(args.prompt + args.new) // 64) * 64
    rolls = (args.sliding_window is not None
             and args.sliding_window < bucketed)
    sw = ("" if args.sliding_window is None else
          f" sliding_window={args.sliding_window}"
          + (" (rolling cache)" if rolls else " (band only: window >= "
             "context, cache stays full-length)"))
    emit(f"model: {n_params/1e9:.3f}B params, L={args.layers} "
         f"h={args.hidden}{sw}")

    rng_prompts = np.random.RandomState(0)
    prompts = [list(rng_prompts.randint(0, args.vocab, args.prompt))
               for _ in range(args.batch)]
    new_toks = args.batch * args.new
    iters = 3
    bw = next((v for k, v in _HBM_BW.items()
               if kind.lower().startswith(k.lower())), None)
    # per-decode-step HBM streams: all params + the cache slice for the
    # mean context length (+ the int8 cache's fp32 scales, 1/hd of it);
    # a rolling window caps the streamed context at W slots
    ctx = args.prompt + args.new / 2
    if args.sliding_window is not None:
        ctx = min(ctx, args.sliding_window)
    bf16_cache = (2 * args.layers * args.batch * ctx * args.heads *
                  (args.hidden // args.heads) * 2)
    int8_cache = bf16_cache / 2 * (1 + 4 / (args.hidden // args.heads))
    bf16_params = n_params * 2

    from megatron_tpu.ops.quantized import quantize_weights
    state = {"params": params, "pq": None, "pq_bytes": 0}
    del params

    def make_params(int8_w):
        if not int8_w:
            return state["params"]
        if state["pq"] is None:
            state["pq"] = quantize_weights(state["params"])
            state["pq_bytes"] = sum(x.nbytes
                                    for x in jax.tree.leaves(state["pq"]))
            # the fp originals are no longer needed by any later arm
            # (bf16-param arms run first) — drop them so quantized arms
            # at 7B-class shapes don't hold both trees in HBM
            state["params"] = None
        return state["pq"]

    # bf16-param arms FIRST: once a quantized arm runs, the fp tree is
    # freed and unquantized arms would be impossible
    arms = [("bf16", False, False)]
    if args.int8_kv:
        arms.append(("int8kv", False, True))
    if args.int8_weights:
        arms.append(("int8", True, False))
    if args.int8_weights and args.int8_kv:
        arms.append(("int8w+kv", True, True))

    base_tok_s = None
    for name, int8_w, int8_kv in arms:
        # one generator at a time: two resident at 7B-class shapes would
        # OOM a v5e, and leftover HBM pressure skews a bandwidth bench
        gen = Generator(make_params(int8_w), cfg, eos_id=-1,
                        kv_cache_dtype=jnp.int8 if int8_kv
                        else jnp.bfloat16)
        t0 = time.perf_counter()
        gen.generate(prompts, max_new_tokens=args.new, seed=1)
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        for i in range(iters):
            gen.generate(prompts, max_new_tokens=args.new, seed=2 + i)
        dt = (time.perf_counter() - t0) / iters
        gen = None
        tok_s = new_toks / dt
        vs = ""
        if base_tok_s is None:
            base_tok_s = tok_s
        else:
            vs = f" ({tok_s/base_tok_s:.2f}x vs bf16)"
        if int8_w:
            vs += (f" [param bytes {bf16_params/1e9:.2f} GB -> "
                   f"{state['pq_bytes']/1e9:.2f} GB]")
        label = "generate" if name == "bf16" else f"{name} generate"
        emit(f"{label}(batch={args.batch}, prompt={args.prompt}, "
             f"new={args.new}): {dt*1e3:.1f} ms/call -> {tok_s:.0f} "
             f"new-tok/s ({tok_s/args.batch:.1f} tok/s/seq, compile "
             f"{compile_s:.1f}s){vs}")
        if bw:
            step_bytes = ((state["pq_bytes"] if int8_w else bf16_params)
                          + (int8_cache if int8_kv else bf16_cache))
            ideal = step_bytes / bw
            emit(f"  {name} roofline: {step_bytes/1e9:.2f} GB/step @ "
                 f"{bw/1e9:.0f} GB/s -> ideal {args.batch/ideal:.0f} "
                 f"new-tok/s (measured/ideal = "
                 f"{tok_s * ideal / args.batch:.2f})")
    emit("note: per-batch-step sampling + done-mask bookkeeping ride the "
         "same jit; prefill is amortized over the call, not subtracted")


if __name__ == "__main__":
    main()
