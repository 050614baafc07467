"""By hand, ON THE CHIP (through the builder's chip tool): what a fault
reads in the Nemotron-3 cell's check, AT the check's own length and the
published widths, THROUGH THE DRIVER'S OWN COMPARISON
(`serve_open_loop_nemotron.verdict`, the rule that decides `correct`) with
the engine's own rounding in every reading. `ssm_fault_at_width.py` for
another model:

    python benchmark/tests/ssd_fault_at_width.py [--seed n] [--faults x,y]
        [--workload name]

One seed a process. The driver's engine is built on the weights the driver
draws and the check's two requests go through the programs the cell times
(two chunks of 2,048, then a continuation chunk of 904 rows padded to 1,024
or of 54 padded to 512, 32 decode steps over pool and state). Every reading
is a pair, a checked request each: the three numbers of the
log-probabilities and the slot's state against the reference's
(`state_verdict`). Then:

- `engine`: the sound engine against the sound reference: what the cell
  itself reads on this seed;
- faults of the ENGINE's path, planted round the engine's own chunk program
  (`ServingEngine._chunk_fwd`; no program is compiled anew) and read as the
  cell reads itself, the request run again:
  `chunk_starts_from_zeros`: every continuation chunk finds zeros where the
  scans' matrices and the depthwise kernel's inputs of the chunk before it
  should lie (the carried state between chunks);
  `state_behind_the_padding`: the last chunk (904 real rows in 1,024) is
  told that all its rows are real, so both states are the ones behind the
  120 padding rows and not the ones at row 4,999;
- faults of the REFERENCE (`reference/nemotron_h.py::FAULTS`, one piece of
  the mathematics each) put in the sound reference's place against the sound
  engine's log-probabilities: `state_bf16` (the carried state rounded to
  bfloat16 behind every token, the nearest precision below the float32 the
  configuration's state is stated in), `pool_bf16` (the same rounding where
  a program hands the state to the pool and no oftener: what a pool held in
  bfloat16 would read), `decay`, `state_reset`, `group`,
  `norm_before_gate`, `latent`, `scale`, `relu`;
- `fp8`, always last (it rounds the weights in place, the engine closed):
  the nearest precision below the bfloat16 the configuration's weights are
  stated in: every matrix of the mixers, the experts and the head rounded to
  float8_e4m3fn with one scale a matrix (a layer's); norms, biases, taps,
  A_log, D, dt_bias and the embedding kept.

One line on standard output and in `chiprun_out/ssd_fault_at_width.jsonl`.

Not a test: it needs the chip (the reference of 5,032 tokens at these widths
takes the CPU tens of minutes) and is too long for a suite. At tiny size on
the CPU it runs in a copy of `benchmark/` that holds the rehearsal's cell
(`test_nemotron_cell.add_cell`) with `--workload tiny.serve-nemotron`."""
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
from benchmark.reference import nemotron_h as ref  # noqa: E402

ENGINE_FAULTS = ("chunk_starts_from_zeros", "state_behind_the_padding")
REFERENCE_FAULTS = tuple(ref.FAULTS)

p = argparse.ArgumentParser()
p.add_argument("--workload", default="nemotron-3-super.serve-agent-8k")
p.add_argument("--seed", type=int, default=5200000001)
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
    def one(x):                 # one scale a matrix: behind the layers' axis
        xf = x.astype(jnp.float32)
        scale = jnp.max(jnp.abs(xf), axis=tuple(range(1, x.ndim)),
                        keepdims=True) / 448.0
        # the barrier keeps the chip's compiler from dropping the pair of
        # conversions as excess precision
        x8 = jax.lax.optimization_barrier(
            (xf / scale).astype(jnp.float8_e4m3fn))
        return (x8.astype(jnp.float32) * scale).astype(x.dtype)

    def rnd(path, x):
        name = jax.tree_util.keystr(path)
        if "lm_head" in name:       # one matrix, no layers' axis
            return jax.jit(lambda m: one(m[None])[0], donate_argnums=0)(x)
        if x.ndim < 3 or any(kept in name for kept in (
                "norm", "bias", "embedding", "A_log", "['conv']", "['D']")):
            return x
        return jax.jit(one, donate_argnums=0)(x)
    return jax.tree_util.tree_map_with_path(rnd, tree)


_programs = {}


def refer(params, tokens, mcfg, planted=()):
    """The reference's reading of `tokens` (`reference.checked`: the
    log-probabilities of the last T, both rows' states), a fault planted or
    none; one program a fault, both checked requests through it."""
    if planted not in _programs:
        _programs[planted] = jax.jit(lambda p, t, live: ref.checked(
            p, t, live, mcfg, T, faults=frozenset(planted)))
    return driver.refer(_programs[planted], params, tokens,
                        driver.padded_length(mix))


def short(got, held, read):
    v = {**driver.verdict(got, read["logprobs"], T),
         **driver.state_verdict(held, read["states"])}
    return {"mean": v["logprob_mean_abs_diff"],
            "max": v["logprob_max_abs_diff"],
            "over_0_05": v["logprob_positions_over_0_05"],
            "state": v["state_rel_err"], "ahead": v["state_rows_ahead"],
            "correct": v["logprobs_match_reference"]
            and v["state_matches_reference"]}


def requests(engine, mcfg, seed):
    """Both checked requests through the engine: (tokens, the engine's
    log-probabilities, the slot's state behind it) each."""
    out = []
    for chk in driver.checked_requests(mix):
        req, slot, tokens, got = driver.check_request(engine, mcfg, mix, seed,
                                                      chk)
        out.append((tokens, got, driver.slot_states(engine, slot),
                    int(req.prefill_chunks)))
    return out


def one_seed(seed, faults):
    t0 = time.time()
    ctx = Context(root=ROOT, cell=cell, config=config, traffic=mix, seed=seed,
                  seconds=0.0, trace=False, devices=jax.devices()[:1],
                  peaks=None, compiles=bench_run.CompileCounter(),
                  t_process_start=bench_run.T_PROCESS_START)
    mcfg, params, engine = driver.build_engine(ctx)
    res = {"seed": seed, "device": jax.devices()[0].device_kind,
           "workload": args.workload, "positions": T,
           "prompts": [c["prompt"] for c in driver.checked_requests(mix)]}

    def read(ran, planted=()):
        """One fault's line: a reading a checked request, in their order."""
        return [short(got, held, refer(params, tokens, mcfg, planted))
                for tokens, got, held, _ in ran]
    try:
        engine._thread.start()
        sound = requests(engine, mcfg, seed)
        res["prefill_chunks"] = [chunks for *_, chunks in sound]
        res["engine"] = read(sound)
        print("engine", round(time.time() - t0), "s", file=sys.stderr,
              flush=True)
        for name in faults:
            if name in ENGINE_FAULTS:
                heal = plant(engine, name)
                try:
                    res[name] = read(requests(engine, mcfg, seed))
                finally:
                    heal()
            elif name in REFERENCE_FAULTS:
                res[name] = read(sound, (name,))
            elif name != "fp8":
                raise SystemExit(f"unknown fault {name!r}")
            print(name, round(time.time() - t0), "s", file=sys.stderr,
                  flush=True)
    finally:
        engine.close()
    # fp8 rounds the weights in place: nothing else may hold them
    del engine
    driver._kept.clear()
    gc.collect()
    if "fp8" in faults:
        params = fp8_tree(params)
        res["fp8"] = read(sound)
    res["seconds"] = round(time.time() - t0)
    return res


wanted = [f for f in args.faults.split(",") if f and f != "fp8"]
if "fp8" in args.faults.split(","):
    wanted.append("fp8")
line = json.dumps(one_seed(args.seed, wanted))
print(line, flush=True)
with open(os.path.join(OUT, "ssd_fault_at_width.jsonl"), "a") as f:
    f.write(line + "\n")
