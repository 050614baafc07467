"""Driver `serve_open_loop_kimi_linear`: the open-loop serving driver
(`serve_open_loop.py`: schedule, window, every sample and every end-to-end
quantity are its own, unchanged) with what is
Kimi-Linear-48B-A3B-Instruct's, built as `serve_open_loop_nemotron.py`
builds Nemotron-3's:

- **Set-up draws the weights from the seed in the bfloat16 the model is
  published and held in**: one chip's share of published layers 1 to 8, 64
  of 256 experts a layer under a router of 256, 40,960 rows of the embedding
  and of the head (`benchmark/configs/kimi-linear-48b-a3b-8l.json`), by the
  program's own initialiser, which for the KDA layers' decays is the public
  one (A in [1, 16] a head, step sizes log-uniform in [0.001, 0.1]: a state
  that remembers tens to thousands of tokens).
- **The embedding is drawn at unit scale** (the head is untied), **the
  choosing bias N(0, 0.004^2)** (JoyAI's driver's) and **the output gates'
  bias N(0, 0.25^2)** (the initialiser's is zero, and a bias left out would
  not show).
- **Warm-up compiles what chunked prefill can reach** and no more
  (`serve_open_loop_command_a.py`'s): with `prefill_bucket` = the chunk, ONE
  one-shot prefill program and ONE chunk program, beside the decode step
  and the landing.
- **The check is made against the plain reference**
  (`benchmark/reference/kimi_linear.py`), on TWO seeded greedy requests
  through the programs the cell times, 32 tokens decoded through pool and
  state each: ISSUE 58's, of 9,000 prompt tokens (two whole chunks of 4,096,
  then 808 rows in the 4,096 bucket at offset 8,192 with 3,288 padding rows,
  behind which the state must be row 8,999's), and one of 8,250
  (`check_carry`: the last chunk is 58 rows, so the checked positions lie 58
  to 90 rows behind a chunk's start, inside the memory of the heads: a
  continuation begun from an empty state or from stale depthwise inputs
  cannot pass). Of each: the engine's log-probabilities of its own tokens
  against the float32 reference's full forward, AND what the pool holds in
  the request's slot against the reference's behind the same tokens
  (`state_verdict`): the six KDA states, the depthwise kernels' last inputs
  and the MLA layers' last latent rows, and the FIRST KDA layer's state
  under a limit of its own, which is the one that refuses a state kept or
  accumulated in bfloat16 (the limits' note below). And the pool's own
  count of the state's bytes is held to the float32 the configuration
  states. The reference is compiled ONCE, at the longer request's length.
- `expert_load_window` is the reference's own float32 router on the first
  `load_prompt` tokens of the window's own prompts, as
  `serve_open_loop_command_a.py` counts it (`held_row_share`,
  `groups_hit_per_decode_step`, `held_rows_per_decode_step`: what
  `moe_share_roofline_pct` credits).
- `prefill_chunks`, `prefill_prompts` and `requests_admitted` of the
  engine's own counters at the window's two ends go into the samples for
  `serve_prefill_chunks_per_prompt`; the pool's own counts of its bytes for
  `serve_kv_bytes_per_token`, `serve_state_bytes_per_slot` and
  `serve_kda_state_bytes_per_slot`.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark import loadgen
from benchmark.by_name import load_module

# The engine computes in bf16 over bf16 weights (float32 norm statistics,
# depthwise taps, decays, running sums, the diagonal blocks' inverses, state,
# router, softmax and head accumulator), the reference in float32 over the
# same bf16 values. The log-probabilities lie near -7.0 (an untied head over
# a unit-scale stream). The limits' readings (my chip runs, PR 58; both
# checked requests; mean |difference|, largest position, positions over
# 0.05; then the slot's state, depthwise inputs and latent rows, each the
# largest over its layers of |held - reference| / |reference|; PERF.md
# section 6):
# - the engine over twenty-eight weight seeds, fifty-six readings (the first
#   round's eight runs of the cell, seeds 5800000011, 5800000201-06 and
#   5800000301, and its control; the second round's, 5800000501-02,
#   5800000601, 5800000701, 5800000801-06, 5800001001-06, 5800001101 and
#   5800001201-02): mean 0.0057 to 0.0146, largest position 0.014 to 0.122,
#   0 to 3 positions over 0.05; state 0.0125 to 0.0235, inputs 0.0064 to
#   0.0313, latent rows 0.0071 to 0.0236 (the upper halves are the requests
#   in which a decoded row's top-8 of 256 flipped between bf16 and float32:
#   that row's stream, and so its inputs (one row of the three held), its
#   latent row and a thirty-second of the state, differ);
# - through `verdict` and `state_verdict` below
#   (`benchmark/tests/kda_fault_at_width.py`, on the chip, seed 5800000401,
#   the 9,000-token request | the 8,250-token one): the decay left out:
#   0.343 / 0.93 / 29, state 0.94, inputs 0.30, latent 0.34 | 0.265 / 0.94 /
#   28, 0.94, 0.31, 0.33; the state and the inputs taken behind the last
#   chunk's 3,288 padding rows: 0.209 / 0.81 / 22, 0.73, 0.16, 0.17 | 0.232
#   / 0.57 / 26, 0.73, 0.16, 0.18; a continuation chunk begun from an empty
#   state, planted in the ENGINE: 0.006 / 0.023 / 0, 0.024, 0.007, 0.008 |
#   0.079 / 0.25 / 21, 0.358, 0.071, 0.073, its twin in the reference
#   (`state_reset`) 0.008 / 0.062 / 1, 0.023 | 0.074 / 0.18 / 22, 0.383,
#   0.069, 0.070: the 9,000-token request's positions lie 808 rows behind
#   the chunk's start, where the heads have forgotten, the 8,250-token one's
#   58 (PR 52's review: why there are two); a chunk begun from stale (empty)
#   depthwise inputs, in the ENGINE: 0.012 / 0.059 / 1, 0.020, 0.007, 0.018
#   | 0.019 / 0.062 / 2, 0.075, 0.015, 0.028, its twin in the reference
#   (`conv_reset`): second request 0.022 / 0.077 / 4, 0.075, 0.029, 0.029;
#   the decay applied after the update: 0.022 / 0.061 / 2, 0.092, 0.026,
#   0.029 | 0.022 / 0.054 / 2, 0.095, 0.025, 0.027; the rope channels
#   rotated: latent 0.53 | 0.53 and nothing else (32 decoded positions
#   attend positions whose rotations nearly agree); the scale 1 for 2.446:
#   0.057 / 0.157 / 15, 0.074, 0.055, 0.060 | 0.047 / 0.122 / 13, 0.072,
#   0.051, 0.054.
# So: the MEAN's limit 0.025 lies between the engine's largest 0.0146 (1.7
# times) and the smallest of the faults it is there for (the scale 0.047,
# 1.9 times over it; an empty state 0.074); the COUNT's 5 between the
# engine's 3 and those faults' smallest 13; a SINGLE POSITION's 0.25
# between the engine's largest 0.122 (2.0 times: a flipped expert moves one
# position far, and every fault that passes this limit passes the count's
# too) and the decay's and the padding's 0.57 to 0.94; THE STATE's 0.035
# between the engine's largest 0.0235 (1.5 times) and the smallest of the
# decay after the update 0.092, the scale 0.072, stale inputs 0.075 (2.1
# times over it); THE INPUTS' and THE LATENT ROWS' 0.04 between the
# engine's 0.0313 and 0.0236 (1.3 and 1.7 times; the inputs are three rows
# a layer, so ONE flipped row is a third of them: one reading of fifty-six
# is over 0.0224) and the scale's 0.047 and 0.054, an empty state's 0.071
# and 0.073, the rope's 0.51 (no fault of the eleven needs the inputs'
# limit alone: each that fails it fails another). Each of those
# nine faults fails at least one limit on at least one of the two requests
# (again on seed 5800000601, from the committed files, with the first
# layer's limit beside the others).
# - THE FIRST KDA LAYER'S STATE, `state_first_layer_rel_err` (the review of
#   PR 58: the limits above passed a state kept in bfloat16). The largest
#   over the layers is the sixth layer's, whose rows carry five layers of
#   bf16 rounding and every flipped expert before it: the state rounded to
#   bfloat16 behind EVERY token (the reference's `state_bf16`, the nearest
#   precision below the float32 the configuration states) reads 0.0176 |
#   0.0191 there where the engine reads 0.0137 | 0.0165 on the same seed,
#   inside the engine's own band. The FIRST layer's rows are made from the
#   embedding's own rows: no other layer's rounding and no routing reaches
#   them, and what is left is the engine's own bf16 rows and the kernel's
#   bf16 operands. By layer, seed 5800000501, the 9,000-token request: the
#   engine 0.0039, 0.0055, 0.0071, 0.0111, 0.0127, 0.0184; `state_bf16`
#   0.0101, 0.0108, 0.0133, 0.0146, 0.0160, 0.0206. On the first layer
#   (my chip runs, PR 58; both requests): the engine 0.0037 to 0.0041 over
#   nineteen weight seeds, thirty-eight readings (the second round's seeds
#   above); `state_bf16` 0.0101 | 0.0099 (seed
#   5800000501), 0.0109 | 0.0112 (5800000502), 0.0115 | 0.0114
#   (5800000601). The limit 0.0065 lies 1.6 times over the engine's largest
#   and 1.5 times under the control's smallest: A STATE KEPT OR ACCUMULATED
#   IN BFLOAT16 IS REFUSED, through `state_verdict`, by the timed programs
#   at the timed sizes.
# - What no comparison of numbers separates at these widths: the rule's
#   products with the state SUMMED in bfloat16 (`sums_bf16`: every product
#   and every partial sum of a pairwise tree rounded by `reduce_precision`,
#   which the compiler may not take out; its first form asked the unit for
#   a bfloat16 sum, got a float32 sum rounded once, and read what the sound
#   reference reads to the fourth digit) reads 0.0041 | 0.0041 and 0.0043 |
#   0.0043 on the first layer where the engine reads 0.0039 | 0.0038 and
#   0.0040 | 0.0040 on the same two seeds: it adds 0.0014 in quadrature,
#   less than the kernel's own bf16 OPERANDS add by design (0.0021 to
#   0.0035 against the recurrence on the same rows, PERF.md section 6), and
#   on this chip a product's sum is float32 inside the unit whatever it is
#   asked for. The float32 comparisons at 1e-4 hold it off the chip
#   (`tests/test_kda.py`: bf16 sums by 10 tolerances, a bf16 state by 3;
#   `tests/test_kimi_linear.py`), and the pool's own count of the state's
#   bytes is held to the float32 the configuration states
#   (`state_bytes_as_stated`: a pool kept in bfloat16 fails it too).
OVER = 0.05
TOL_STATE = 0.035
TOL_STATE_FIRST = 0.0065
TOL_INPUTS = 0.04
TOL_LATENT = 0.04
TOL_POSITIONS_OVER = 5
TOL_LOGPROB_MAX = 0.25
TOL_LOGPROB_MEAN = 0.025

EMBEDDING_STD = 1.0
BIAS_STD = 0.004
WINDOW_PROMPTS = 4          # of the window's own, for `expert_load_window`
DECODE_DRAWS = 256

_base = load_module("drivers", "serve_open_loop")
_chunked = load_module("drivers", "serve_open_loop_command_a")
_nemotron = load_module("drivers", "serve_open_loop_nemotron")
_olmoe = load_module("drivers", "serve_open_loop_olmoe")
_xing = load_module("drivers", "serve_open_loop_xing")
# `benchmark/sweep.py` drives `build_engine`, `warm_up`, `offer` of
# whichever driver a mix names
warm_up = _chunked.warm_up
# the engine's counters kept at the window's two ends: Xing4.0's driver's own
WINDOW_COUNTERS = _xing.WINDOW_COUNTERS
_kept = {}                  # the weights and the compiled reference of a run

POOL_COUNTERS = ("kv_bytes_per_token", "kv_pool_bytes", "kv_bytes_per_slot",
                 "conv_state_bytes", "kda_state_bytes")
GATE_BIAS_STD = 0.25
# what Nemotron-3's driver has and this one takes as it is: a request's
# output held under `prompt_plus_output_max` (`offer`), the two checked
# requests and the ONE length the reference is compiled at, a checked
# request through the engine, the reference fed padded tokens
_base_offer = _nemotron._base_offer
offer = _nemotron.offer
padded_length = _nemotron.padded_length
checked_requests = _nemotron.checked_requests
check_request = _nemotron.check_request
refer = _nemotron.refer


def draw_params(rng, mcfg):
    """The served tree from the seed (module docstring)."""
    import jax
    import jax.numpy as jnp
    from megatron_tpu.models import language_model as lm
    params = lm.model_init(rng, mcfg)
    rows = params["embedding"]["word_embeddings"]
    params["embedding"]["word_embeddings"] = rows * (
        EMBEDDING_STD / mcfg.init_method_std)
    for g, group in enumerate(params["transformer"].values()):
        for k, kind in enumerate(group.values()):
            key = jax.random.fold_in(rng, 11 + 2 * g + k)
            mlp = kind["mlp"]
            if "e_score_correction_bias" in mlp:
                b = mlp["e_score_correction_bias"]
                mlp["e_score_correction_bias"] = (BIAS_STD * jax.random.normal(
                    key, b.shape, jnp.float32)).astype(b.dtype)
            if "kda" in kind:
                b = kind["kda"]["g_bias"]
                kind["kda"]["g_bias"] = (GATE_BIAS_STD * jax.random.normal(
                    jax.random.fold_in(key, 1), b.shape,
                    jnp.float32)).astype(b.dtype)
    return params


def build_engine(ctx):
    import jax
    from benchmark.reference import kimi_linear as reference
    from megatron_tpu.arguments import parse_cli
    from megatron_tpu.config import ServingConfig
    from megatron_tpu.inference.generation import Generator
    from megatron_tpu.serving import ServingEngine

    cfg, _ = parse_cli([*ctx.config["cli"], "--bf16"], n_devices=1)
    mcfg = cfg.model
    tail = ctx.traffic["check"]["output"]
    assert tail == ctx.traffic["check_carry"]["output"]
    params = jax.jit(lambda rng: draw_params(rng, mcfg))(
        jax.random.PRNGKey(ctx.seed))
    # one program for both checks and for the window's prompts
    _kept.update(ctx=ctx, params=params, mcfg=mcfg, reference=jax.jit(
        lambda p, t, live: reference.checked(p, t, live, mcfg, tail)))
    gen = Generator(params, mcfg, eos_id=-1, pad_id=0)
    serving = ServingConfig(**ctx.traffic["serving"]).validate(mcfg)
    engine = ServingEngine(gen, serving, start=False)
    # the base driver reads the engine's counters as the window opens and as
    # it closes (`offer`: two of them); what else the engine counted at those
    # two moments is kept here for `serve_prefill_chunks_per_prompt`
    snapshot = engine.metrics.snapshot
    seen = _kept.setdefault("snapshots", [])

    def recording():
        snap = snapshot()
        seen.append((time.monotonic(),
                     {k: snap.get(k, 0) for k in WINDOW_COUNTERS}))
        return snap
    engine.metrics.snapshot = recording
    return mcfg, params, engine


def slot_states(engine, slot, rows):
    """What the pool holds in `slot`, read once the request is out and the
    engine idle (nothing has written to the slot since the request's last
    step): the rule's matrices [KDA layers, H, D, D], the depthwise kernels'
    last inputs [KDA layers, K - 1, 3 H D] and the latent rows of positions
    `rows` - LATENT_ROWS .. `rows` - 1 [MLA layers, LATENT_ROWS, row]."""
    from benchmark.reference.kimi_linear import LATENT_ROWS
    for _ in range(100):
        time.sleep(0.1)
        try:
            caches = engine.pool.caches
            return (np.asarray(caches.ssm[:, slot], np.float32),
                    np.asarray(caches.conv[:, slot], np.float32),
                    np.asarray(caches.c[:, slot, :, rows - LATENT_ROWS:rows],
                               np.float32).swapaxes(1, 2))
        except RuntimeError:            # donated to a step still in flight
            continue
    raise RuntimeError("the pool's state could not be read")


def _rel_errs(held, ref):
    """|held - ref| / |ref| (Frobenius) of each layer."""
    return [float(np.linalg.norm(h - r) / np.linalg.norm(r))
            for h, r in zip(np.asarray(held, np.float64),
                            np.asarray(ref, np.float64))]


def state_verdict(held, ref):
    """The pool's three parts against the reference's. `ref["states"]` [2,
    layers, H, D, D] and `ref["inputs"]` [2, layers, K - 1, 3 H D] are
    behind every token but the last one chosen, and behind that one too (the
    engine dispatches a step ahead of the host's reading, so the slot of a
    finished request has read its last token as well; either is the
    program's right, the same one for both parts); `ref["latent"]` the rows
    either has written. Each error is the largest over the layers of |held
    - ref| / |ref| (Frobenius); `state_first_layer_rel_err` is the FIRST KDA
    layer's alone, whose rows are made from the embedding's own rows and
    carry no other layer's rounding (the limits' note above)."""
    state, inputs, latent = held
    by_layer = [_rel_errs(state, rows) for rows in np.asarray(ref["states"])]
    ahead = int(np.argmin([max(errs) for errs in by_layer]))
    errs = by_layer[ahead]
    conv = max(_rel_errs(inputs, np.asarray(ref["inputs"])[ahead]))
    rows = max(_rel_errs(latent, ref["latent"]))
    return {"state_rel_err": max(errs), "state_rows_ahead": ahead,
            "state_rel_err_by_layer": errs,
            "state_first_layer_rel_err": errs[0],
            "inputs_rel_err": conv, "latent_rel_err": rows,
            "state_tolerance": TOL_STATE,
            "state_first_layer_tolerance": TOL_STATE_FIRST,
            "inputs_tolerance": TOL_INPUTS,
            "latent_tolerance": TOL_LATENT,
            "state_matches_reference": bool(
                max(errs) <= TOL_STATE and errs[0] <= TOL_STATE_FIRST
                and conv <= TOL_INPUTS and rows <= TOL_LATENT)}


def verdict(got, ref, positions):
    """The comparison that decides `correct`, of two arrays alone: the
    engine's log-probabilities of its own tokens and the reference's, with
    the three limits. `benchmark/tests/kda_fault_at_width.py` hands it a
    faulted side, so the control and the cell share one rule."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    n = min(len(got), len(ref))       # a request cut short fails by its length
    diff = np.abs(got[:n] - ref[:n])
    return {"logprob_positions": int(len(got)),
            "logprob_max_abs_diff": float(diff.max()),
            "logprob_mean_abs_diff": float(diff.mean()),
            "logprob_positions_over_0_05": int((diff > OVER).sum()),
            "logprob_reference_mean": float(ref.mean()),
            "logprob_tolerance_max": TOL_LOGPROB_MAX,
            "logprob_tolerance_mean": TOL_LOGPROB_MEAN,
            "logprob_tolerance_positions_over_0_05": TOL_POSITIONS_OVER,
            "logprobs_match_reference":
                bool(len(got) == positions
                     and (diff > OVER).sum() <= TOL_POSITIONS_OVER
                     and diff.max() <= TOL_LOGPROB_MAX
                     and diff.mean() <= TOL_LOGPROB_MEAN)}


def check_against_reference(engine, params, mcfg, mix, seed):
    t0 = time.monotonic()
    verdicts = []
    for chk in checked_requests(mix):
        req, slot, tokens, got = check_request(engine, mcfg, mix, seed, chk)
        # the rows either engine has written: up to the last token but one
        held = slot_states(engine, slot, len(tokens) - 1)
        ref = refer(_kept["reference"], params, tokens, padded_length(mix))
        verdicts.append({**verdict(got, ref["logprobs"], chk["output"]),
                         **state_verdict(held, ref),
                         "prompt": chk["prompt"],
                         "prefill_chunks": int(req.prefill_chunks)})
    whole, carry = verdicts
    snap = engine.metrics.snapshot()
    # the configuration states the state in float32 (`assumed.kda_state`):
    # the pool's own count of its bytes is held to the stated precision
    cfg = _kept["ctx"].config
    group = cfg["linear_attn_config"]
    layers = sum(1 for l in group["kda_layers"]
                 if l <= cfg["num_hidden_layers"])
    stated = (4 * layers * mix["serving"]["num_slots"] * group["num_heads"]
              * group["head_dim"] ** 2)
    return {**whole, "carry": carry,
            "state_bytes_as_stated": stated,
            "logprobs_match_reference": bool(
                snap.get("kda_state_bytes") == stated and all(
                    v["logprobs_match_reference"]
                    and v["state_matches_reference"] for v in verdicts)),
            **{k: snap.get(k) for k in POOL_COUNTERS},
            # counted here, on the prompts the window WILL offer, while the
            # device holds what it held for the check above
            # (`serve_open_loop_command_a.py` says why)
            "expert_load_window": window_expert_load(_kept["ctx"]),
            "check_s": time.monotonic() - t0}


def window_expert_load(ctx):
    """The reference's router on the window's own prompts (module
    docstring). Nothing where the window held no prompt of `load_prompt`
    tokens."""
    mix, mcfg = ctx.traffic, _kept["mcfg"]
    length = int(mix["load_prompt"])
    arrivals = loadgen.schedule(mix, ctx.seed, ctx.seconds)
    prompts = loadgen.prompts_for(arrivals, mcfg.vocab_size, ctx.seed)
    mine = [p for a, p in zip(arrivals, prompts)
            if a.phase == "window" and len(p) >= length][:WINDOW_PROMPTS]
    if not mine:
        return None
    # [layers, tokens, router experts]; the reference reads tokens[:-1]
    chosen = np.concatenate([
        np.asarray(refer(_kept["reference"], _kept["params"], p[:length + 1],
                         padded_length(mix))["chosen"])[:, :length]
        for p in mine], axis=1)
    first = mcfg.moe_first_expert
    held = chosen[:, :, first:first + mcfg.num_experts]
    slots = mix["serving"]["num_slots"]
    rng = np.random.default_rng([ctx.seed, 5])
    hit, rows = [], []
    for layer in held:
        grids = [layer[rng.choice(layer.shape[0], slots, replace=False)]
                 for _ in range(DECODE_DRAWS)]
        hit.append(float(np.mean([g.any(axis=0).sum() for g in grids])))
        rows.append(float(np.mean([g.sum() for g in grids])))
    loads = held.sum(axis=1)
    return {"prompts": len(mine), "tokens": int(chosen.shape[1]),
            "held_row_share": [float(x) for x in
                               held.sum(axis=(1, 2)) / chosen.sum(axis=(1, 2))],
            "max_over_mean": _olmoe._max_over_mean(loads),
            "experts_without_a_token":
                [int(x) for x in (loads == 0).sum(axis=1)],
            "groups_hit_per_decode_step": hit,
            "held_rows_per_decode_step": rows}


def run(ctx):
    _base.build_engine = build_engine
    _base.check_against_reference = check_against_reference
    _base.warm_up = warm_up
    _base.offer = offer
    try:
        result = _base.run(ctx)
        # what the pool itself counts, for `serve_kv_bytes_per_token`,
        # `serve_state_bytes_per_slot` (the depthwise kernels' inputs) and
        # `serve_kda_state_bytes_per_slot` (the rule's matrices)
        slots = ctx.traffic["serving"]["num_slots"]
        checks = result.checks
        result.samples["kv_bytes_per_token"] = checks["kv_bytes_per_token"]
        for sample, counter in (("state_bytes_per_slot", "conv_state_bytes"),
                                ("kda_state_bytes_per_slot",
                                 "kda_state_bytes")):
            held = checks.get(counter)
            result.samples[sample] = held // slots if held else None
        # the engine's counters at the first reading behind the window's
        # opening and the first behind its close: `offer`'s own two
        t_open = result.samples["t_open"]
        ends = [next((c for t, c in _kept["snapshots"] if t >= at), None)
                for at in (t_open, t_open + result.samples["window_s"])]
        if None not in ends:
            result.samples["window_engine_counters"] = {
                k: ends[1][k] - ends[0][k] for k in WINDOW_COUNTERS}
        return result
    finally:
        _base.offer = _base_offer
        _kept.clear()
