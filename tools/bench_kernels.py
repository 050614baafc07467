"""Kernel microbenchmarks on the current accelerator.

One command for the on-chip A/B numbers PERF_NOTES.md tracks: Pallas vs
XLA for the fused norms and for flash attention, at transformer shapes.
Writes human-readable lines to --out (default /tmp/kernel_bench.log) as
well as stdout.

  python tools/bench_kernels.py [--out FILE] [--iters N]
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
    p = argparse.ArgumentParser("bench_kernels", description=__doc__)
    p.add_argument("--out", default="/tmp/kernel_bench.log")
    p.add_argument("--iters", type=int, default=50)
    p.add_argument("--smoke", action="store_true",
                   help="tiny shapes: exercises every arm end-to-end in "
                        "seconds (CPU CI smoke; timings meaningless)")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from megatron_tpu.models.norms import layernorm, rmsnorm
    from megatron_tpu.ops.flash_attention import _blockwise_attention
    # direct kernel import: an ImportError must FAIL the pallas arm, not
    # silently time the XLA fallback under a 'pallas' label
    from megatron_tpu.ops.flash_attention_pallas import \
        pallas_flash_attention
    from megatron_tpu.ops.fused_norms import (pallas_layernorm,
                                              pallas_rmsnorm)

    log = open(args.out, "w", buffering=1)

    def emit(line):
        print(line, flush=True)
        log.write(line + "\n")

    # header BEFORE jax.devices(): a 0-byte log is indistinguishable
    # from "never started"
    emit("bench_kernels: probing backend...")
    dev = jax.devices()[0]
    emit(f"device: {dev.platform} {getattr(dev, 'device_kind', '?')}")
    # off-TPU the raw kernels can only run interpreted; smoke mode opts in
    interp = args.smoke and dev.platform != "tpu"


    def timeit(fn, *a):
        jax.block_until_ready(fn(*a))  # compile
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = fn(*a)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.iters * 1e6  # us

    norm_shapes = [(4, 2048, 2048), (2, 4096, 4096), (8, 1024, 8192)]
    flash_shapes = [(2, 2048, 16, 128), (1, 8192, 8, 128),
                    (1, 32768, 4, 128)]
    if args.smoke:
        norm_shapes = [(2, 128, 256)]
        flash_shapes = [(1, 256, 2, 64)]

    # --- norms: pallas vs xla-fused jnp, fwd and vjp ---
    for (b, s, h) in norm_shapes:
        x = jax.random.normal(jax.random.PRNGKey(0), (b, s, h),
                              jnp.bfloat16)
        scale = jnp.ones((h,), jnp.bfloat16)
        bias = jnp.zeros((h,), jnp.bfloat16)
        dy = jax.random.normal(jax.random.PRNGKey(1), (b, s, h),
                               jnp.bfloat16)
        gb_fwd = 2 * x.size * 2 / 1e9   # x read + y write, bf16
        gb_vjp = 3 * x.size * 2 / 1e9   # x + dy reads, dx write

        pairs = [
            ("rms fwd", gb_fwd,
             jax.jit(lambda x, s: rmsnorm({"scale": s}, x)),
             jax.jit(lambda x, s: pallas_rmsnorm(x, s, interpret=interp)), (x, scale)),
            ("ln  fwd", gb_fwd,
             jax.jit(lambda x, s, b2: layernorm({"scale": s, "bias": b2},
                                                x)),
             jax.jit(lambda x, s, b2: pallas_layernorm(
                 x, s, b2, interpret=interp)),
             (x, scale, bias)),
            ("rms vjp", gb_vjp,
             jax.jit(jax.grad(lambda x, s: jnp.sum(
                 rmsnorm({"scale": s}, x).astype(jnp.float32)
                 * dy.astype(jnp.float32)), argnums=(0, 1))),
             jax.jit(jax.grad(lambda x, s: jnp.sum(
                 pallas_rmsnorm(x, s, interpret=interp).astype(jnp.float32)
                 * dy.astype(jnp.float32)), argnums=(0, 1))), (x, scale)),
        ]
        for name, gb, f_xla, f_pal, fargs in pairs:
            try:
                t_x = timeit(f_xla, *fargs)
                t_p = timeit(f_pal, *fargs)
                emit(f"{name} [{b},{s},{h}] bf16: xla {t_x:8.1f}us "
                     f"({gb / (t_x * 1e-6):5.0f} GB/s) | pallas "
                     f"{t_p:8.1f}us ({gb / (t_p * 1e-6):5.0f} GB/s)")
            except Exception as e:
                emit(f"{name} [{b},{s},{h}] FAILED: "
                     f"{type(e).__name__}: {str(e)[:160]}")

    # --- quantized GEMM: int8 datapath vs bf16, fwd (ops/quantized.py —
    # the TE-fp8-counterpart path; v5e int8 peak is ~2x bf16) ---
    from megatron_tpu.ops.quantized import int8_matmul
    gemm_shapes = [(8192, 4096, 11008), (4096, 4096, 4096),
                   (2048, 8192, 8192)]
    if args.smoke:
        gemm_shapes = [(64, 128, 256)]
    for (m, k, n) in gemm_shapes:
        x = jax.random.normal(jax.random.PRNGKey(4), (m, k), jnp.bfloat16)
        w = jax.random.normal(jax.random.PRNGKey(5), (k, n), jnp.bfloat16)
        fl = 2 * m * k * n
        try:
            t_b = timeit(jax.jit(lambda x, w: x @ w), x, w)
            t_q = timeit(jax.jit(int8_matmul), x, w)
            emit(f"gemm [{m}x{k}x{n}]: bf16 {t_b:9.1f}us "
                 f"({fl / (t_b * 1e-6) / 1e12:5.1f} TF/s) | int8(+quant) "
                 f"{t_q:9.1f}us ({fl / (t_q * 1e-6) / 1e12:5.1f} TOP/s)")
        except Exception as e:
            emit(f"gemm [{m}x{k}x{n}] FAILED: "
                 f"{type(e).__name__}: {str(e)[:160]}")

    # --- flash attention: pallas kernel vs xla blockwise, fwd ---
    for (b, s, n, d) in flash_shapes:
        q = jax.random.normal(jax.random.PRNGKey(2), (b, s, n, d),
                              jnp.bfloat16)
        try:
            t_p = timeit(jax.jit(lambda q: pallas_flash_attention(
                q, q, q, True, None, interpret=interp)), q)
            t_x = timeit(jax.jit(lambda q: _blockwise_attention(
                q, q, q, causal=True, scale=None, block_kv=512)), q)
            fl = 4 * b * n * s * s * d / 2  # causal matmul flops
            emit(f"flash fwd [{b},{s},{n},{d}] bf16: pallas {t_p:9.1f}us "
                 f"({fl / (t_p * 1e-6) / 1e12:5.1f} TF/s) | xla-block "
                 f"{t_x:9.1f}us ({fl / (t_x * 1e-6) / 1e12:5.1f} TF/s)")
        except Exception as e:
            emit(f"flash [{b},{s},{n},{d}] FAILED: "
                 f"{type(e).__name__}: {str(e)[:160]}")
    emit("done")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
