"""By hand, ON THE CHIP (through the builder's chip tool): what a fault
reads in the Qwen3-Next cell's check, AT the check's own lengths and the
published widths, THROUGH THE DRIVER'S OWN COMPARISON
(`serve_open_loop_qwen3_next.verdict` and `state_verdict`, the rule that
decides `correct`) with the engine's own rounding in every reading.
`kda_fault_at_width.py` for another model:

    python benchmark/tests/gdn_fault_at_width.py [--seed n] [--faults x,y]
        [--workload name]

One seed a process. The driver's engine is built on the weights the driver
draws and the check's two requests go through the programs the cell times
(two chunks of 4,096, then a continuation chunk of 808 or of 58 rows padded
to 4,096, 32 decode steps over pool and state). Every reading is a pair, a
checked request each: the four numbers of the log-probabilities and the
slot's matrices, depthwise inputs and last keys against the reference's
(`state_verdict`). Then:

- `engine`: the sound engine against the sound reference: what the cell
  itself reads on this seed;
- faults of the ENGINE's path, planted round the engine's own chunk program
  (`ServingEngine._chunk_fwd`; no program is compiled anew) and read as the
  cell reads itself, the request run again:
  `chunk_starts_from_zeros`: every continuation chunk finds zeros where the
  rule's matrices of the chunk before it should lie;
  `chunk_starts_from_stale_inputs`: every continuation chunk finds zeros
  where the depthwise kernel's last inputs should lie;
  `state_behind_the_padding`: the last chunk (808 real rows in 4,096) is
  told that all its rows are real, so the state and the inputs are those
  behind the 3,288 padding rows and not the ones at row 8,999;
- faults of the REFERENCE (`reference/qwen3_next.py::FAULTS`, one piece of
  the mathematics each) put in the sound reference's place against the sound
  engine's readings: `state_bf16` (the carried state rounded to bfloat16
  behind every token, the nearest precision below the float32 the
  configuration's state is stated in), `state_reset`, `conv_reset`,
  `decay_after`, `decay_mean`, `norm_w`, `no_gate`, `rope_all`, `key_head`,
  `no_shared_gate`.

One line on standard output and in `chiprun_out/gdn_fault_at_width.jsonl`.

Not a test: it needs the chip (the reference of 9,032 tokens at these widths
takes the CPU tens of minutes) and is too long for a suite. At tiny size on
the CPU it runs in a copy of `benchmark/` that holds the rehearsal's cell
(`test_qwen3_next_cell.add_cell`) with `--workload tiny.serve-qwen3-next`."""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import run as bench_run  # noqa: E402
from benchmark.by_name import load_module  # noqa: E402
from benchmark.context import Context  # noqa: E402
from benchmark.reference import qwen3_next as ref  # noqa: E402

ENGINE_FAULTS = ("chunk_starts_from_zeros", "chunk_starts_from_stale_inputs",
                 "state_behind_the_padding")
REFERENCE_FAULTS = tuple(ref.FAULTS)

p = argparse.ArgumentParser()
p.add_argument("--workload", default="qwen3-next-80b-a3b.serve-longdoc-32k")
p.add_argument("--seed", type=int, default=6000000001)
p.add_argument("--faults",
               default=",".join(ENGINE_FAULTS + REFERENCE_FAULTS))
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
            sub = sub._replace(ssm=jnp.zeros_like(sub.ssm))
        if name == "chunk_starts_from_stale_inputs" and offset > 0:
            sub = sub._replace(conv=jnp.zeros_like(sub.conv))
        if name == "state_behind_the_padding" \
                and int(next_offset) - offset < tokens.shape[1]:
            new, last = sound(params, sub, tokens, last_idx,
                              jnp.int32(offset + tokens.shape[1]), *rest)
            return new._replace(offset=jnp.full_like(
                new.offset, next_offset)), last
        return sound(params, sub, tokens, last_idx, next_offset, *rest)
    engine._chunk_fwd = faulty
    return lambda: setattr(engine, "_chunk_fwd", sound)


_programs = {}


def refer(params, tokens, mcfg, planted=()):
    """The reference's reading of `tokens` (`reference.checked`: the
    log-probabilities of the last T, both rows' states and inputs, the
    last keys), a fault planted or
    none; one program a fault, both checked requests through it."""
    if planted not in _programs:
        _programs[planted] = jax.jit(lambda p, t, live: ref.checked(
            p, t, live, mcfg, T, faults=frozenset(planted)))
    return driver.refer(_programs[planted], params, tokens,
                        driver.padded_length(mix))


def short(got, held, read):
    v = {**driver.verdict(got, read["logprobs"], T),
         **driver.state_verdict(held, read)}
    return {"mean": v["logprob_mean_abs_diff"],
            "median": v["logprob_median_abs_diff"],
            "max": v["logprob_max_abs_diff"],
            "over_0_05": v["logprob_positions_over_0_05"],
            "state": v["state_rel_err"], "ahead": v["state_rows_ahead"],
            "first": v["state_first_layer_rel_err"],
            "by_layer": v["state_rel_err_by_layer"],
            "inputs": v["inputs_rel_err"], "keys": v["keys_rel_err"],
            "correct": v["logprobs_match_reference"]
            and v["state_matches_reference"]}


def requests(engine, mcfg, seed):
    """Both checked requests through the engine: (tokens, the engine's
    log-probabilities, the slot's state behind it) each."""
    out = []
    for chk in driver.checked_requests(mix):
        req, slot, tokens, got = driver.check_request(engine, mcfg, mix, seed,
                                                      chk)
        out.append((tokens, got,
                    driver.slot_states(engine, slot, len(tokens) - 1),
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
            else:
                raise SystemExit(f"unknown fault {name!r}")
            print(name, round(time.time() - t0), "s", file=sys.stderr,
                  flush=True)
    finally:
        engine.close()
    res["seconds"] = round(time.time() - t0)
    return res


wanted = [f for f in args.faults.split(",") if f]
line = json.dumps(one_seed(args.seed, wanted))
print(line, flush=True)
with open(os.path.join(OUT, "gdn_fault_at_width.jsonl"), "a") as f:
    f.write(line + "\n")
