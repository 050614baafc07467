"""By hand, ON THE CHIP (through the builder's chip tool): the scan kernel
alone, at the Jamba cell's chunk shape (one sequence, 2,048 rows, 5,120
channels, a state of 16, bfloat16 rows).

    python benchmark/tests/ssm_kernel_bench.py [--seed n] [--calls n]

One JSON line: the kernel against the `lax.scan` form on the same arguments
(largest |difference| of y and of the state, 512 rows), the kernel's
milliseconds a call for a few blocks of channels and of rows (the module's
`BLOCK_CHANNELS` x `BLOCK_ROWS` first; a block the compiler refuses reads
"refused: ..."), and the `lax.scan` form's milliseconds for scale. Host
clock round `--calls` dispatches of fresh outputs, so a call's own dispatch
is in the number: PERF.md's figures for the kernel alone come from here, the
cell's `serve_ssm_scan_ms_per_step` and `ssm_scan_roofline_pct` from the
device trace.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402

from megatron_tpu.ops import selective_scan as ss      # noqa: E402

D_INNER, D_STATE = 5120, 16
BLOCKS = ((ss.BLOCK_CHANNELS, ss.BLOCK_ROWS), (256, 128), (1024, 128),
          (512, 256))


def draw(key, rows):
    ks = jax.random.split(key, 6)
    shape = (1, rows, D_INNER)
    x = jax.random.normal(ks[0], shape, jnp.float32).astype(jnp.bfloat16)
    z = jax.random.normal(ks[1], shape, jnp.float32).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(ks[2], shape) - 3)
    a_t = -jnp.broadcast_to(
        jnp.arange(1, D_STATE + 1, dtype=jnp.float32)[:, None],
        (D_STATE, D_INNER))
    b = jax.random.normal(ks[3], (1, rows, D_STATE))
    c = jax.random.normal(ks[4], (1, rows, D_STATE))
    h0 = jax.random.normal(ks[5], (1, D_STATE, D_INNER))
    return x, dt, a_t, b, c, jnp.ones((D_INNER,)), z, h0


def ms_a_call(fn, args, calls):
    jax.block_until_ready(fn(*args))            # compiles
    start = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) * 1e3 / calls


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=10)
    opts = ap.parse_args()
    out = {"device": jax.devices()[0].device_kind}
    key = jax.random.PRNGKey(opts.seed % (2 ** 31))

    args = draw(key, 512)
    y0, h0 = jax.jit(ss._scan_xla)(*args)
    y1, h1 = ss.selective_scan(*args)
    f32 = jnp.float32
    out["kernel_vs_xla_y"] = float(
        jnp.abs(y0.astype(f32) - y1.astype(f32)).max())
    out["kernel_vs_xla_h"] = float(jnp.abs(h0 - h1).max())
    out["y_abs_max"] = float(jnp.abs(y0.astype(f32)).max())

    args = draw(jax.random.fold_in(key, 1), 2048)
    for cb, tb in BLOCKS:
        ss.BLOCK_CHANNELS, ss.BLOCK_ROWS = cb, tb
        jax.clear_caches()
        try:
            out[f"ms_cb{cb}_tb{tb}"] = ms_a_call(
                ss.selective_scan, args, opts.calls)
        except Exception as e:          # a block the compiler refuses
            out[f"ms_cb{cb}_tb{tb}"] = "refused: " + str(
                e).splitlines()[0][:200]
    ss.BLOCK_CHANNELS, ss.BLOCK_ROWS = BLOCKS[0]
    out["ms_xla_form"] = ms_a_call(jax.jit(ss._scan_xla), args, 1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
