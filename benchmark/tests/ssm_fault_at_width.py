"""By hand, ON THE CHIP (through the builder's chip tool): what a fault
reads in the Jamba cell's check, AT the check's own length and the published
widths, THROUGH THE DRIVER'S OWN COMPARISON (`serve_open_loop_jamba.verdict`,
the rule that decides `correct`) with the engine's own rounding in every
reading.

    python benchmark/tests/ssm_fault_at_width.py [--seed n] [--faults x,y]
        [--workload name]

One seed a process. The driver's engine is built on the weights the driver
draws and the check's request goes through the programs the cell times (four
chunks of 2,048, a continuation chunk of 808 rows padded to 1,024, 32 decode
steps over pool and state). Then:

- `engine`: the sound engine against the sound reference: what the cell
  itself reads on this seed;
- faults of the ENGINE's path, planted round the engine's own chunk program
  (`ServingEngine._chunk_fwd`; no program is compiled anew) and read as the
  cell reads itself, the request run again:
  `chunk_starts_from_zeros`: every continuation chunk finds zeros where the
  scan's matrix and the depthwise kernel's inputs of the chunk before it
  should lie;
  `state_behind_the_padding`: the last chunk (808 real rows in 1,024) is
  told that all its rows are real, so both states are the ones behind the
  216 padding rows and not the ones at row 8,999 (keys, values, offsets and
  the first token's logits as they should be);
- faults of the REFERENCE (`reference/jamba.py`'s `faults`) put in the sound
  reference's place against the sound engine's log-probabilities:
  `state_bf16` (the carried matrix rounded to bfloat16 behind every token,
  the nearest precision below the float32 the configuration's state is
  stated in), `recurrence_bf16` (the update computed in bfloat16 too);
- `fp8`, always last (it rounds the weights in place, the engine closed):
  the nearest precision below the bfloat16 the configuration's weights are
  stated in: every matrix of the mixers and the MLPs rounded to
  float8_e4m3fn with one scale a matrix (a layer's); norms, biases, taps,
  A_log, D and the embedding kept.

One line on standard output and in `chiprun_out/ssm_fault_at_width.jsonl`.

Not a test: it needs the chip (the reference of 9,032 tokens at these widths
takes the CPU tens of minutes) and is too long for a suite. At tiny size on
the CPU it runs in a copy of `benchmark/` that holds the rehearsal's cell
(`test_jamba_cell.add_cell`) with `--workload tiny.serve-jamba`."""
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
from benchmark.reference import jamba as ref  # noqa: E402

ENGINE_FAULTS = ("chunk_starts_from_zeros", "state_behind_the_padding")
REFERENCE_FAULTS = ("state_bf16", "recurrence_bf16")

p = argparse.ArgumentParser()
p.add_argument("--workload", default="jamba2-3b.serve-longdoc-32k")
p.add_argument("--seed", type=int, default=4747000001)
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

    def faulty(params, sub, tokens, last_idx, next_offset, *rest):
        offset = int(sub.offset[0])
        if name == "chunk_starts_from_zeros" and offset > 0:
            sub = sub._replace(ssm=jnp.zeros_like(sub.ssm),
                               conv=jnp.zeros_like(sub.conv))
        if name == "state_behind_the_padding" \
                and int(next_offset) - offset < tokens.shape[1]:
            new, last = sound(params, sub, tokens, last_idx,
                              jnp.int32(offset + tokens.shape[1]), *rest)
            return new._replace(offset=jnp.full_like(
                new.offset, next_offset)), last
        return sound(params, sub, tokens, last_idx, next_offset, *rest)
    engine._chunk_fwd = faulty
    return lambda: setattr(engine, "_chunk_fwd", sound)


def fp8_tree(tree):
    """Leaf by leaf and in place (the chip has no room for a second tree)."""
    def rnd(path, x):
        name = jax.tree_util.keystr(path)
        if x.ndim < 3 or any(kept in name for kept in (
                "norm", "bias", "embedding", "A_log", "['conv']")):
            return x

        def one(x):             # one scale a matrix: behind the layers' axis
            xf = x.astype(jnp.float32)
            scale = jnp.max(jnp.abs(xf), axis=tuple(range(1, x.ndim)),
                            keepdims=True) / 448.0
            # the barrier keeps the chip's compiler from dropping the pair
            # of conversions as excess precision
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
            "first_two": v["logprob_first_two_max_abs_diff"],
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
        params = fp8_tree(params)
        res["fp8"] = short(driver.verdict(
            got, reference(params, tokens, mcfg), T))
    res["seconds"] = round(time.time() - t0)
    return res


wanted = [f for f in args.faults.split(",") if f and f != "fp8"]
if "fp8" in args.faults.split(","):
    wanted.append("fp8")
line = json.dumps(one_seed(args.seed, wanted))
print(line, flush=True)
with open(os.path.join(OUT, "ssm_fault_at_width.jsonl"), "a") as f:
    f.write(line + "\n")
