"""By hand, ON THE CHIP (through the builder's chip tool; five minutes with
every fault and nothing cached, 155 s of them until the engine's own check
is read): what a fault reads in the Xing4.0 cell's check, AT the check's own
length and the published widths, THROUGH THE DRIVER'S OWN COMPARISON
(`serve_open_loop_xing.verdict`, the rule that decides `correct`) with the
engine's own rounding in every reading.

    python benchmark/tests/hc_fault_at_width.py [--seed n] [--faults x,y]
        [--workload name]

One seed a process (an engine's programs keep its pool alive in JAX's
caches: a second engine beside it does not fit the chip). The driver's
engine is built on the weights the driver draws and the check's request goes
through the programs the cell times (a 4,096 chunk, a continuation chunk
over the latent pool, 32 decode steps). Then:

- `engine`: the sound engine against the sound reference: what the cell
  itself reads on this seed;
- faults of the ENGINE's path, planted round the engine's own chunk program
  (`ServingEngine._chunk_fwd`; no program is compiled anew) and read as the
  cell reads itself, the request run again:
  `first_chunk_dropped`: the continuation chunk and every decode step find
  rows of zeros where the first chunk's 4,096 latent rows should lie;
  `chunk_misplaced`: the continuation chunk is written, and turned, one row
  late (row 4,096 stays stale, the prompt's last row is lost);
- faults of the REFERENCE (`reference/xing4.py`'s `faults`) put in the sound
  reference's place against the sound engine's log-probabilities:
  `maps_bf16` (x^, the maps' product, the sigmoids, the exponential and the
  Sinkhorn rounds in bfloat16, the nearest precision below the float32 the
  configuration's maps are stated in), `sinkhorn_bf16` (the twenty rounds
  alone), `sinkhorn_10` (ten rounds for twenty), `no_mscale` (MLA's softmax
  scale without YaRN's m(64, 1)^2 = 2.005), `post_without_2` (H_post =
  sigmoid, not 2 sigmoid);
- `fp8`, always last (it rounds the weights in place, the engine closed): no
  fault of the path but the nearest precision below the bfloat16 the
  configuration's weights are stated in: every matrix of the attention, the
  dense MLP, the experts, the maps' phi and the head rounded to
  float8_e4m3fn with one scale a matrix (an expert's, a layer's); router,
  choosing bias, norms, alpha, b and the embedding kept.

One line on standard output and in `chiprun_out/hc_fault_at_width.jsonl`.

Not a test: it needs the chip (the reference of 6,032 tokens at these widths
takes the CPU tens of minutes) and is too long for a suite. At tiny size on
the CPU it runs in a copy of `benchmark/` that holds the rehearsal's cell
(`test_xing_cell.add_cell`) with `--workload tiny.serve-xing`."""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark import run as bench_run  # noqa: E402
from benchmark.by_name import load_module  # noqa: E402
from benchmark.context import Context  # noqa: E402
from benchmark.reference import xing4 as ref  # noqa: E402

ENGINE_FAULTS = ("first_chunk_dropped", "chunk_misplaced")
REFERENCE_FAULTS = ("maps_bf16", "sinkhorn_bf16", "sinkhorn_10", "no_mscale",
                    "post_without_2")

p = argparse.ArgumentParser()
p.add_argument("--workload", default="xing4.0-29b-a4b.serve-mixed-16k")
p.add_argument("--seed", type=int, default=4141000001)
p.add_argument("--faults",
               default=",".join(ENGINE_FAULTS + REFERENCE_FAULTS + ("fp8",)))
args = p.parse_args()
_, cell, config, mix = bench_run.load_cell(args.workload)
driver = load_module("drivers", mix["driver"])
T = mix["check"]["output"]
OUT = os.path.join(ROOT, "chiprun_out")
os.makedirs(OUT, exist_ok=True)


def plant(engine, name):
    """Wraps the engine's chunk program; returns what takes the fault out."""
    sound = engine._chunk_fwd

    def faulty(params, sub, *rest):
        if int(sub.offset[0]) > 0:              # a continuation chunk
            if name == "first_chunk_dropped":
                sub = sub._replace(c=jnp.zeros_like(sub.c))
            else:
                sub = sub._replace(offset=sub.offset + 1)
        return sound(params, sub, *rest)
    engine._chunk_fwd = faulty
    return lambda: setattr(engine, "_chunk_fwd", sound)


def fp8_tree(tree):
    """Leaf by leaf and in place (the chip has no room for a second tree)."""
    def rnd(path, x):
        name = jax.tree_util.keystr(path)
        if x.ndim < 2 or any(kept in name for kept in (
                "norm", "router", "bias", "embedding", "alpha", "['b']")):
            return x
        # one scale a matrix: behind the layers' axis, and the experts'
        lead = 0 if "['transformer']" not in name else \
            2 if "['moe']['mlp']['w" in name else 1

        def one(x):
            xf = x.astype(jnp.float32)
            scale = jnp.max(jnp.abs(xf), axis=tuple(range(lead, x.ndim)),
                            keepdims=True) / 448.0
            # the barrier keeps the chip's compiler from dropping the pair
            # of conversions as excess precision (it did: 0.0 everywhere)
            x8 = jax.lax.optimization_barrier(
                (xf / scale).astype(jnp.float8_e4m3fn))
            return (x8.astype(jnp.float32) * scale).astype(x.dtype)
        return jax.jit(one, donate_argnums=0)(x)
    return jax.tree_util.tree_map_with_path(rnd, tree)


_programs = {}


def reference(params, tokens, mcfg, planted=()):
    """The reference's log-probabilities of `tokens`' last T, a fault
    planted or none; one program a fault."""
    if planted not in _programs:
        _programs[planted] = jax.jit(lambda p, t: ref.token_logprobs(
            p, t, mcfg, tail=T, faults=frozenset(planted)))
    return np.asarray(_programs[planted](
        params, jnp.asarray(tokens, jnp.int32)), np.float64)


def short(v):
    return {"mean": v["logprob_mean_abs_diff"],
            "max": v["logprob_max_abs_diff"],
            "over_0_05": v["logprob_positions_over_0_05"],
            "correct": v["logprobs_match_reference"]}


def one_seed(seed, faults):
    t0 = time.time()
    ctx = Context(root=ROOT, cell=cell, config=config, traffic=mix, seed=seed,
                  seconds=0.0, trace=False, devices=jax.devices()[:1],
                  peaks=None, compiles=bench_run.CompileCounter(),
                  t_process_start=bench_run.T_PROCESS_START)
    mcfg, params, engine = driver.build_engine(ctx)
    res = {"seed": seed, "device": jax.devices()[0].device_kind,
           "workload": args.workload, "positions": T}
    try:
        engine._thread.start()
        req, tokens, got = driver.check_request(engine, mcfg, mix, seed)
        res["prefill_chunks"] = int(req.prefill_chunks)
        res["engine"] = short(driver.verdict(
            got, reference(params, tokens, mcfg), T))
        print("engine", round(time.time() - t0), "s", file=sys.stderr,
              flush=True)
        for name in faults:
            if name in ENGINE_FAULTS:
                heal = plant(engine, name)
                try:
                    _, toks, lps = driver.check_request(engine, mcfg, mix,
                                                        seed)
                finally:
                    heal()
                res[name] = short(driver.verdict(
                    lps, reference(params, toks, mcfg), T))
            elif name in REFERENCE_FAULTS:
                res[name] = short(driver.verdict(
                    got, reference(params, tokens, mcfg, (name,)), T))
            elif name != "fp8":
                raise SystemExit(f"unknown fault {name!r}")
            print(name, round(time.time() - t0), "s", file=sys.stderr,
                  flush=True)
    finally:
        engine.close()
    # fp8 rounds the weights in place: nothing else may hold them
    del engine, req
    driver._kept.clear()
    gc.collect()
    if "fp8" in faults:
        head = np.asarray(params["lm_head"][:, :256], np.float32)
        params = fp8_tree(params)
        res["fp8_moved_lm_head_by"] = float(np.abs(np.asarray(
            params["lm_head"][:, :256], np.float32) - head).max()
            / np.abs(head).max())       # ~0.03 of the largest entry
        res["fp8"] = short(driver.verdict(
            got, reference(params, tokens, mcfg), T))
    res["seconds"] = round(time.time() - t0)
    return res


wanted = [f for f in args.faults.split(",") if f and f != "fp8"]
if "fp8" in args.faults.split(","):
    wanted.append("fp8")
line = json.dumps(one_seed(args.seed, wanted))
print(line, flush=True)
with open(os.path.join(OUT, "hc_fault_at_width.jsonl"), "a") as f:
    f.write(line + "\n")
