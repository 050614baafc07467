"""By hand, ON THE CHIP (through the builder's chip tool): the two chunk
kernels of the delta rule alone (`megatron_tpu/ops/kda_chunk.py`), at the
shapes their benchmark cells run (one sequence, 4,096 rows, 32 heads of 128
key and value channels, bfloat16 rows, float32 log-decays and state; the
scalar-decay form with 16 key heads) and at 512 rows.

    python tools/delta_rule_kernel_bench.py [--seed n] [--calls n]
        [--module path/to/kda_chunk.py] [--rows 4096,512] [--interpret]

One JSON line. For each form and row count: `ms_call`, the whole jitted
function on the host's clock round `--calls` dispatches; from a device trace
of the same calls `ms_kernel`, the Pallas call alone, and `ms_outside`, what
else of the jitted function ran on the device (running sums made by XLA, a
transpose, a pad); `ms_cumsum_xla`, `jnp.cumsum` over the log-decays in
chunks of 64 alone, the form the kernel's caller used up to PR 60;
`ms_recurrence`, the rule row by row (`kda_recurrent` / `gdn_recurrent`) for
scale; `compile_s`, one 4,096-row call lowered and compiled with no cache.
`errors`: at 4,096 rows, for each decay of `tests/test_kda.py::DECAYS`, the
largest |difference| of o and of the state between the kernel and the
recurrence in float32 on the same drawn rows (bfloat16 rows: the budget a
faster kernel is held to; the recurrence reads the same rows as float32),
and `errors_f32` the same for a float32 call of the last row count (the
kernel is exact there, 1e-6).

`--module` loads the kernels from another file (a copy of the parent
commit's), so that one script reads both sides; the recurrence is always
this checkout's. PERF.md's figures for the kernels alone come from here, the
cells' `kda_chunk_roofline_pct` / `gdn_chunk_roofline_pct` from their traces.
"""
from __future__ import annotations

import argparse
import functools
import importlib.util
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402

from benchmark import trace as trace_mod               # noqa: E402
from megatron_tpu.ops import kda_chunk as here         # noqa: E402

HEADS, KEY_HEADS, D = 32, 16, 128
DECAYS = {"typical": {}, "near_0": dict(scale=1e-3),
          "minus_8": dict(const=-8.0), "mixed_to_minus_40": dict(scale=20.0)}
F32 = jnp.float32


def draw(key, form, rows, dtype=jnp.bfloat16, scale=1.0, const=None):
    """`tests/test_kda.py::_rows` at the cells' widths: k of unit length, q
    of length 1 / sqrt(d), g <= 0 a channel ("kda") or a head ("gdn")."""
    ks = jax.random.split(key, 6)
    hk = HEADS if form == "kda" else KEY_HEADS
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(ks[0], (1, rows, hk, D))) / D ** 0.5
    k = unit(jax.random.normal(ks[1], (1, rows, hk, D)))
    v = jax.random.normal(ks[2], (1, rows, HEADS, D))
    by_g = (1, rows, HEADS, D) if form == "kda" else (1, rows, HEADS)
    g = -scale * jax.nn.softplus(jax.random.normal(ks[3], by_g))
    if const is not None:
        g = jnp.full_like(g, const)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, rows, HEADS)))
    h0 = jax.random.normal(ks[5], (1, HEADS, D, D))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta, h0)


def ms_a_call(fn, args, calls):
    jax.block_until_ready(fn(*args))            # compiles
    start = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - start) * 1e3 / calls


def device_ms(fn, args, calls, kernel):
    """(the Pallas call, every other operation) in device milliseconds a
    call, from a trace of `calls` calls; (None, None) off a TPU."""
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(calls):
                out = fn(*args)
            jax.block_until_ready(out)
        path = trace_mod.find_xplane(d)
        tr = trace_mod.load(path) if path else None
    if tr is None or tr.kind != "tpu":
        return None, None
    own = lambda text: (kernel in trace_mod.parse_op(text)[0]     # noqa
                        and trace_mod.is_pallas_kernel(text))
    return (tr.seconds_where(own) * 1e3 / calls,
            tr.seconds_where(lambda text: not own(text)) * 1e3 / calls)


def largest(a, b):
    return float(jnp.abs(a.astype(F32) - b.astype(F32)).max())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--module", default=None)
    ap.add_argument("--rows", default="4096,512")
    ap.add_argument("--interpret", action="store_true",
                    help="the rehearsal off the chip, at a few rows")
    opts = ap.parse_args()
    jax.config.update("jax_enable_compilation_cache", False)
    mod = here
    if opts.module:
        spec = importlib.util.spec_from_file_location("kda_chunk_other",
                                                      opts.module)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    out = {"device": jax.devices()[0].device_kind,
           "module": opts.module or here.__file__, "seed": opts.seed}
    key = jax.random.PRNGKey(opts.seed % (2 ** 31))
    forms = {"kda": (mod._kda_chunk, here.kda_recurrent, "_kda_chunk"),
             "gdn": (mod._gdn_chunk, here.gdn_recurrent, "_gdn_chunk")}
    rows_list = [int(r) for r in opts.rows.split(",")]
    for form, (kernel, recurrent, name) in forms.items():
        if opts.interpret:
            kernel = jax.jit(functools.partial(kernel, interpret=True))
        recurrent = jax.jit(lambda *a, f=recurrent: f(
            *(t.astype(F32) for t in a)))
        for rows in rows_list:
            args = draw(jax.random.fold_in(key, rows), form, rows)
            tag = f"{form}_{rows}"
            if rows == rows_list[0]:
                start = time.perf_counter()
                kernel.lower(*args).compile()
                out[f"{form}_compile_s"] = time.perf_counter() - start
            out[f"{tag}_ms_call"] = ms_a_call(kernel, args, opts.calls)
            out[f"{tag}_ms_kernel"], out[f"{tag}_ms_outside"] = device_ms(
                kernel, args, opts.calls, name)
            g = args[3]
            by_chunk = g.reshape(1, rows // 64, 64, -1)
            out[f"{tag}_ms_cumsum_xla"] = ms_a_call(
                jax.jit(lambda t: jnp.cumsum(t, axis=2)), (by_chunk,),
                opts.calls)
            out[f"{tag}_ms_recurrence"] = ms_a_call(recurrent, args, 1)
        for label, rows, dtype in (("errors", rows_list[0], jnp.bfloat16),
                                   ("errors_f32", rows_list[-1], F32)):
            table = {}
            for decay, how in DECAYS.items():
                args = draw(jax.random.fold_in(key, 7), form, rows, dtype,
                            **how)
                want_o, want_s = recurrent(*args)
                got_o, got_s = kernel(*args)
                table[decay] = {"o": largest(got_o, want_o),
                                "state": largest(got_s, want_s),
                                "o_abs_max": float(jnp.abs(
                                    want_o.astype(F32)).max()),
                                "finite": bool(jnp.isfinite(
                                    got_o.astype(F32)).all())}
            out[f"{form}_{label}"] = table
    print(json.dumps(out))


if __name__ == "__main__":
    main()
