"""Measure the 1F1B uniform-head overhead claim on one chip.

The 1F1B schedule runs the (final-norm + LM head + vocab cross-entropy)
forward AND backward on EVERY pipeline stage, masked to zero off the
last stage — the price of a branch-free uniform SPMD program
(parallel/pipeline.py:536-540 estimates ≈2hV/(Lc·12h²) ≈ 5% FLOPs at
7B/pp8). VERDICT r3 weak #6 asks for a measurement, not an estimate.

A single chip measures it directly: time (a) one transformer layer
fwd+bwd and (b) the head fwd+bwd (final norm → [b,s,h]×[h,V] logits →
CE mean), both jitted at true 7B width (h=4096, 32 heads, ffn 11008,
V=32000) using the SAME model code the schedule runs (stack_apply /
head_logits / cross_entropy_loss). The pp-schedule overhead is then

    overhead(pp, L) = (pp-1) * t_head / (L * t_layer + pp * t_head)

(per microbatch tick each of the pp stages runs the head once; exactly
one of those is useful work, the other pp-1 are the uniform-program
tax). Reported at the BASELINE configs' (pp, L) points. Both arms are
plain vjps — the schedule's recompute-full factor multiplies layer and
head alike, so it divides out of the ratio.

Writes to --out as well as stdout (same convention as the other bench
tools).

  python tools/bench_head.py [--out FILE] [--iters N] [--seq N]
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
    p = argparse.ArgumentParser("bench_head", description=__doc__)
    p.add_argument("--out", default="/tmp/bench_head.log")
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--seq", type=int, default=4096)
    p.add_argument("--micro_bs", type=int, default=1)
    p.add_argument("--hidden", type=int, default=4096)
    p.add_argument("--ffn", type=int, default=11008)
    p.add_argument("--heads", type=int, default=32)
    p.add_argument("--vocab", type=int, default=32000)
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from megatron_tpu.config import llama2_config
    from megatron_tpu.models import language_model as lm
    from megatron_tpu.models import transformer as tfm
    from megatron_tpu.ops.cross_entropy import cross_entropy_loss

    log = open(args.out, "w", buffering=1)

    def emit(line):
        print(line, flush=True)
        log.write(line + "\n")

    dev = jax.devices()[0]
    emit(f"device: {dev.platform} {getattr(dev, 'device_kind', '?')}")

    # recompute "none": BOTH arms must be plain vjps for the ratio to be
    # schedule-faithful (the 1F1B schedule checkpoints layer chunks AND
    # the head alike — pipeline.py:457-462 — so the recompute factor
    # multiplies both and divides out; leaving "full" here would remat
    # only the layer arm and understate the head share)
    cfg = llama2_config(
        "tiny", num_layers=1, hidden_size=args.hidden,
        num_attention_heads=args.heads, num_kv_heads=args.heads,
        ffn_hidden_size=args.ffn, vocab_size=args.vocab,
        seq_length=args.seq, compute_dtype="bfloat16",
        attention_impl="flash", recompute_granularity="none")

    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    rope = lm.make_rope(cfg)
    b, s, h = args.micro_bs, args.seq, args.hidden

    x = jax.random.normal(jax.random.PRNGKey(1), (b, s, h), jnp.float32)
    labels = jax.random.randint(jax.random.PRNGKey(2), (b, s), 0,
                                args.vocab, dtype=jnp.int32)

    def timeit(fn, *a):
        jax.block_until_ready(fn(*a))  # compile
        t0 = time.perf_counter()
        out = None
        for _ in range(args.iters):
            out = fn(*a)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.iters * 1e3  # ms

    # (a) one transformer layer, fwd+bwd wrt (stack params, x) — the
    # pipeline chunk's per-layer unit of work
    def layer_loss(sp, xin):
        out, _, _ = tfm.stack_apply(sp, xin.astype(jnp.bfloat16), cfg,
                                 rope_cos=rope.cos if rope else None,
                                 rope_sin=rope.sin if rope else None,
                                 deterministic=True)
        return jnp.sum(out.astype(jnp.float32))

    t_layer = timeit(jax.jit(jax.value_and_grad(layer_loss, argnums=(0, 1))),
                     params["transformer"], x)

    # (b) the head, fwd+bwd wrt (params, x) — exactly the per-stage
    # per-tick head_loss work the 1F1B schedule masks off non-last stages
    def head_loss(pr, xin):
        logits = lm.head_logits(pr, xin.astype(jnp.bfloat16), cfg)
        losses = cross_entropy_loss(logits, labels,
                                    vocab_size=cfg.vocab_size)
        return jnp.mean(losses)

    # head_logits reads ONLY final_norm + lm_head (untied preset); the
    # stack AND the word embedding must stay out of the grad target or
    # value_and_grad materializes zero-grads for them every timed
    # iteration (~0.5 GB of spurious HBM writes at 7B width)
    head_params = {k: v for k, v in params.items()
                   if k in ("final_norm", "lm_head")}
    assert "lm_head" in head_params, "preset unexpectedly tied"

    def head_arm(hp, xin):
        return jax.value_and_grad(
            lambda hp2, x2: head_loss(dict(hp2, transformer=None), x2),
            argnums=(0, 1))(hp, xin)

    t_head = timeit(jax.jit(head_arm), head_params, x)

    emit(f"7B-width @ seq {s}, micro_bs {b}:")
    emit(f"  t_layer fwd+bwd = {t_layer:.2f} ms")
    emit(f"  t_head  fwd+bwd = {t_head:.2f} ms  "
         f"(ratio head/layer = {t_head / t_layer:.3f})")
    for pp, L in [(2, 32), (4, 32), (8, 32), (4, 80), (8, 80), (16, 80)]:
        ov = (pp - 1) * t_head / (L * t_layer + pp * t_head)
        emit(f"  pp={pp:2d} L={L:2d}: uniform-head overhead = {ov:.1%}")
    # head = 2hV flops/token (one [h,V] GEMM at 2 flops/MAC); layer =
    # ~24h^2 (12h^2 params x 2 flops/MAC, attention-score flops excluded
    # like bench.py's MFU model) -> share = V/(V+12h)
    analytic = args.vocab / (args.vocab + 12 * args.hidden)
    emit("(overhead = (pp-1)*t_head / (L*t_layer + pp*t_head); analytic "
         f"FLOP share of head vs one layer V/(V+12h) = {analytic:.1%},"
         f" measured share = {t_head / (t_head + t_layer):.1%})")


if __name__ == "__main__":
    main()
