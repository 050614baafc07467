"""Driver `serve_open_loop_nemotron`: the open-loop serving driver
(`serve_open_loop.py`: schedule, window, every sample and every end-to-end
quantity are its own, unchanged) with what is
NVIDIA-Nemotron-3-Super-120B-A12B's, built as `serve_open_loop_jamba.py`
builds Jamba's and `serve_open_loop_command_a.py` a share of experts:

- **Set-up draws the weights from the seed in the bfloat16 the model is
  published and held in**: one chip's share of one period of eleven layers,
  128 of 512 experts a layer under a router of 512, 32,768 rows of the
  embedding and of the head (`benchmark/configs/
  nemotron-3-super-120b-a12b-11l.json`), by the program's own initialiser,
  which for the Mamba-2 layers is Mamba-2's published one (decays of 1 to 16
  a step size, step sizes log-uniform in [0.001, 0.1]: a state that
  remembers tens to thousands of tokens).
- **The embedding is drawn at unit scale** (the head is untied: OLMoE's,
  JoyAI's and Xing4.0's drivers' reason) and **the choosing bias N(0,
  0.004^2)** (JoyAI's driver's).
- **Warm-up compiles what chunked prefill can reach** and no more, as
  `serve_open_loop_command_a.py` does it: one prompt of each padded length
  up to the chunk (the one-shot prefill programs) and one of chunk + each
  padded tail (the chunk programs, which start from the state the chunk
  before them left).
- **A request's lengths are held to the mix's `prompt_plus_output_max`**:
  the generator draws the two lengths apart, each inside its own clip, and
  the longest prompt with the longest output would pass the slot's
  `max_len`, which the engine refuses. `offer` shortens such a request's
  output to what is left under the limit (a handful of requests a window)
  and hands the schedule on unchanged otherwise.
- **The check is made against the plain reference**
  (`benchmark/reference/nemotron_h.py`), on TWO seeded greedy requests
  through the programs the cell times, 32 tokens decoded through pool and
  state each: ISSUE 52's, of 5,000 prompt tokens (two whole chunks of 2,048,
  then 904 rows in the 1,024 bucket at offset 4,096 with 120 padding rows,
  behind which both states must be the ones at row 4,999), and one of 4,150
  (`check_carry`: the last chunk is 54 rows in the 512 bucket, so the
  checked positions lie 54 to 86 rows behind a chunk's start, inside the
  memory of the heads, where the first request's lie 904 behind and see no
  chunk start: REVIEW.md, PR 52). Of each: the engine's log-probabilities
  of its own tokens against the float32 reference's full forward (the head
  over the last 32 positions alone), AND the scans' matrices the pool holds
  in the request's slot against the reference's state behind the same
  tokens (`state_verdict`), the same share given to both. And the pool's
  own count of the state's bytes is held to the float32 the configuration
  states. The reference is compiled ONCE, at the longer request's length:
  the shorter one and the window's prompts go through it padded
  (`reference.checked`).
- `expert_load_window` is the reference's own float32 router on the first
  `load_prompt` tokens of the window's own prompts, as
  `serve_open_loop_command_a.py` counts it (`held_row_share`,
  `groups_hit_per_decode_step`, `held_rows_per_decode_step`: what
  `moe_share_roofline_pct` credits). It is counted on prompts cut to
  `load_prompt` tokens and not to the check's length: one window in some
  has no prompt of 5,032.
- `prefill_chunks`, `prefill_prompts` and `requests_admitted` of the
  engine's own counters at the window's two ends go into the samples for
  `serve_prefill_chunks_per_prompt`; the pool's own counts of its bytes for
  `serve_kv_bytes_per_token`, `serve_state_bytes_per_slot` and
  `serve_ssd_state_bytes_per_slot`.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark import loadgen
from benchmark.by_name import load_module

# The engine computes in bf16 over bf16 weights (float32 norm statistics,
# depthwise taps, step sizes, decays, running sums, state, router, softmax
# and head accumulator), the reference in float32 over the same bf16 values.
# The log-probabilities lie near -5.7 (an untied head over a unit-scale
# stream). The limits' readings (my chip runs, PR 52; 32 decoded positions
# behind a 5,000-token prompt; mean |difference|, largest position,
# positions over 0.05; PERF.md section 6):
# - the engine over nineteen weight seeds (fourteen runs of the cell, five
#   of the control below): mean 0.0044 to 0.0078, largest position 0.012 to
#   0.025, no position over 0.05 on any seed;
# - through `verdict` below, on five seeds each
#   (`benchmark/tests/ssd_fault_at_width.py`, on the chip): every matrix
#   rounded to float8_e4m3fn, one scale a layer's matrix, the nearest
#   precision below the weights' bfloat16: 0.268 / 0.65 / 26, 0.332 / 0.79 /
#   31, 0.362 / 1.01 / 27, 0.072 / 0.44 / 8, 0.435 / 0.83 / 31; both states
#   taken behind the last chunk's 120 padding rows: means 0.13 to 0.32,
#   largest 0.46 to 1.31, 23 to 31; every head decaying at A = -1: 0.11 to
#   0.32 / 0.48 to 0.77 / 25 to 29; every head reading group 0's B and C:
#   0.11 to 0.16 / 0.28 to 0.43 / 22 to 27; the norm ahead of the gate: 0.053
#   to 0.127 / 0.15 to 0.43 / 12 to 30; the two latent projections tied:
#   0.058 to 0.138 / 0.16 to 0.21 / 17 to 28; relu for relu^2: 0.35 to 1.03 /
#   0.89 to 1.54 / 28 to 32.
# So, with the second session's seeds (5200000711-13, whose smallest lie
# under the first five's: the norm ahead of the gate 0.046 / 0.134 / 12,
# the latent's projections tied 0.061 / 0.138 / 12): the MEAN's limit 0.025
# lies between the engine's largest, 0.0081 (3.1 times), and the smallest
# of those faults' on any seed and either request, 0.044 (1.8 times over
# it); a SINGLE POSITION's 0.08 between the engine's largest 0.025 (3.2
# times) and the faults' smallest 0.134 (1.7 times; it was 0.12 until those
# seeds read 0.134 and 0.137); the COUNT's 3 between the engine's 0 and the
# faults' smallest 8. Each of those seven faults fails all three on every
# seed.
# - What the 5,000-token request does NOT see at these widths (REVIEW.md, PR
#   52): its positions lie 904 rows behind the last chunk's start, where
#   most heads have forgotten (step sizes of 0.001 to 0.1 under decays of 1
#   to 16), so a continuation chunk started from an empty state reads 0.0047
#   to 0.0095 / 0.013 to 0.033 / 0 there: inside the engine's band. Hence
#   the SECOND request, of 4,150 (`check_carry`): 54 to 86 rows behind a
#   chunk's start (my chip runs, PR 52, second session, seeds 5200000701 and
#   5200000711-13): the engine 0.0051 to 0.0081 / 0.014 to 0.020 / 0; a
#   chunk started from an empty state, planted in the ENGINE, 0.052 to 0.092
#   / 0.137 to 0.265 / 17 to 23, its twin in the reference 0.081 / 0.210 /
#   21; the norm ahead of the gate 0.075 to 0.114 / 0.207 to 0.283 / 20 to
#   24; the latent's projections tied 0.044 to 0.129 / 0.144 to 0.228 / 10
#   to 31; fp8 weights 0.267 to 0.367 / 0.60 to 0.81 / 27 to 30. The same
#   three limits hold there.
# - THE STATE's limit. The log-probabilities do not see the scale of 5 (1
#   for 5 reads 0.009 to 0.015 / 0.029 to 0.046 / 0 on either request: a
#   quarter of 22 routed experts weighs little beside the shared expert
#   under drawn weights) nor the state's precision. So each request's slot
#   is read back from the pool and held against the reference's state behind
#   the same tokens (`state_verdict`: the largest over the five layers of
#   |held - reference| / |reference|). Readings, both requests, same seeds:
#   the engine 0.0073 to 0.0092 (and 0.0064 to 0.0096 over the ten readings
#   of the final tree's five runs, seeds 5200000901-04 and 5200000911:
#   eighteen in all; float32 sums over bf16 rows); the scale of 1 for 5 0.0195 to 0.0251 (the experts' output feeds
#   the next layers' rows); a chunk started from an empty state 0.240 to
#   0.330 at 4,150 (0.009 to 0.057 at 5,000); the latent's projections tied
#   0.099 to 0.134; the norm ahead of the gate 0.131 to 0.165; fp8 weights
#   0.63 to 0.78; relu, the decay, the padding, a group's B and C 0.58 to
#   1.37. TOL_STATE 0.0135 lies between the engine's largest 0.0096 (1.40
#   times) and the scale's smallest 0.0195 (1.44 times under it).
# - What NO comparison of numbers separates at these widths: the state
#   rounded to bfloat16 behind EVERY token (the reference's `state_bf16`)
#   reads a `state_rel_err` of 0.0106 to 0.0367, over the limit on three
#   seeds of four and 0.0106 / 0.0108 on the fourth, and the same rounding
#   only where a program hands the state to the pool, which is what a pool
#   held in bfloat16 would do (`pool_bf16`: 35 roundings a request), reads
#   0.0110 / 0.0142 where the engine reads 0.0064 / 0.0080 (seed
#   5200000911): under the limit on one request, over it on the other.
#   The configuration states float32, so the pool's own count of the state's
#   bytes is held to that (`state_bytes_as_stated`), and the float32
#   comparison at 1e-4 (`tests/test_nemotron_h_serving.py`) holds the step's
#   arithmetic: a bf16 state by 4.7 tolerances there.
OVER = 0.05
TOL_STATE = 0.0135
TOL_POSITIONS_OVER = 3
TOL_LOGPROB_MAX = 0.08
TOL_LOGPROB_MEAN = 0.025

EMBEDDING_STD = 1.0
BIAS_STD = 0.004
WINDOW_PROMPTS = 4          # of the window's own, for `expert_load_window`
DECODE_DRAWS = 256

_base = load_module("drivers", "serve_open_loop")
_chunked = load_module("drivers", "serve_open_loop_command_a")
_olmoe = load_module("drivers", "serve_open_loop_olmoe")
_xing = load_module("drivers", "serve_open_loop_xing")
# `benchmark/sweep.py` drives `build_engine`, `warm_up`, `offer` of
# whichever driver a mix names
warm_up = _chunked.warm_up
# the engine's counters kept at the window's two ends: Xing4.0's driver's own
WINDOW_COUNTERS = _xing.WINDOW_COUNTERS
_kept = {}                  # the weights and the compiled reference of a run

POOL_COUNTERS = ("kv_bytes_per_token", "kv_pool_bytes", "kv_bytes_per_slot",
                 "conv_state_bytes", "ssd_state_bytes")
_base_offer = _base.offer       # `run` puts `offer` below in its place


def offer(engine, mix, arrivals, prompts, window_s, compiles,
          trace_dir=None):
    """The base driver's `offer` over the same schedule, each request's
    output held to what `prompt_plus_output_max` leaves behind its prompt
    (module docstring)."""
    limit = int(mix["prompt_plus_output_max"])
    for a in arrivals:
        a.output_len = max(1, min(a.output_len, limit - a.prompt_len))
    return _base_offer(engine, mix, arrivals, prompts, window_s, compiles,
                       trace_dir)


def draw_params(rng, mcfg):
    """The served tree from the seed (module docstring)."""
    import jax
    import jax.numpy as jnp
    from megatron_tpu.models import language_model as lm
    params = lm.model_init(rng, mcfg)
    rows = params["embedding"]["word_embeddings"]
    params["embedding"]["word_embeddings"] = rows * (
        EMBEDDING_STD / mcfg.init_method_std)
    mlp = params["transformer"]["layers"]["moe"]["mlp"]
    b = mlp["e_score_correction_bias"]
    mlp["e_score_correction_bias"] = (BIAS_STD * jax.random.normal(
        jax.random.fold_in(rng, 11), b.shape, jnp.float32)).astype(b.dtype)
    return params


def padded_length(mix):
    """The ONE length the reference is compiled at: the longer check's
    tokens and one row more, behind which the state the engine's step ahead
    leaves can be read (`reference.checked`: `tokens` padded behind `live`)."""
    return 1 + max(mix["load_prompt"] + 1,
                   *(c["prompt"] + c["output"] for c in checked_requests(mix)))


def checked_requests(mix):
    return [mix["check"], mix["check_carry"]]


def build_engine(ctx):
    import jax
    from benchmark.reference import nemotron_h as reference
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


def refer(program, params, tokens, length):
    """The reference's reading of `tokens` through a compiled
    `reference.checked`, the tokens padded to the program's one `length`."""
    import jax.numpy as jnp
    row = np.zeros(length, np.int32)
    row[:len(tokens)] = tokens
    return program(params, jnp.asarray(row), jnp.int32(len(tokens)))


def check_request(engine, mcfg, mix, seed, chk):
    """One seeded greedy request of the check's through the engine: the
    request, the slot it ran in (the engine's own table, read while it
    runs), prompt + the tokens it chose, the engine's log-probabilities of
    those."""
    from megatron_tpu.serving import SamplingOptions
    rng = np.random.default_rng([seed, 2, chk["prompt"]])
    prompt = rng.integers(1, mcfg.vocab_size, size=chk["prompt"]).tolist()
    req = engine.submit(prompt, chk["output"],
                        SamplingOptions(temperature=0.0), seed=seed)
    slot, give_up = None, time.monotonic() + mix["request_timeout_s"]
    while not req.done() and time.monotonic() < give_up:
        if slot is None:
            slot = next((i for i, r in enumerate(engine._slot_req)
                         if r is req), None)
        time.sleep(0.002)
    tokens, _ = req.result(timeout=1.0)
    return req, slot, tokens, np.asarray(req.gen_logprobs, np.float64)


def slot_states(engine, slot):
    """The scans' matrices the pool holds in `slot` [Mamba-2 layers, H, P,
    N], read once the request is out and the engine idle: nothing has
    written to the slot since the request's last step."""
    for _ in range(100):
        time.sleep(0.1)
        try:
            return np.asarray(engine.pool.caches.ssm[:, slot], np.float32)
        except RuntimeError:            # donated to a step still in flight
            continue
    raise RuntimeError("the pool's state could not be read")


def state_verdict(held, ref):
    """The pool's state against the reference's, `ref` [2, layers, H, P, N]:
    behind every token but the last one chosen, and behind that one too (the
    engine dispatches a step ahead of the host's reading, so the slot of a
    finished request has read its last token as well; either is the
    program's right). The error is the largest over the layers of |held -
    ref| / |ref| (Frobenius), the smaller of the two rows'."""
    ref = np.asarray(ref, np.float64)
    errs = [max(float(np.linalg.norm(h - r) / np.linalg.norm(r))
                for h, r in zip(np.asarray(held, np.float64), rows))
            for rows in ref]
    ahead = int(np.argmin(errs))
    return {"state_rel_err": errs[ahead], "state_rows_ahead": ahead,
            "state_tolerance": TOL_STATE,
            "state_matches_reference": bool(errs[ahead] <= TOL_STATE)}


def verdict(got, ref, positions):
    """The comparison that decides `correct`, of two arrays alone: the
    engine's log-probabilities of its own tokens and the reference's, with
    the three limits. `benchmark/tests/ssd_fault_at_width.py` hands it a
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
        held = slot_states(engine, slot)
        ref = refer(_kept["reference"], params, tokens, padded_length(mix))
        verdicts.append({**verdict(got, ref["logprobs"], chk["output"]),
                         **state_verdict(held, ref["states"]),
                         "prompt": chk["prompt"],
                         "prefill_chunks": int(req.prefill_chunks)})
    whole, carry = verdicts
    snap = engine.metrics.snapshot()
    # the configuration states the state in float32 (`assumed.ssm_state`):
    # no comparison of numbers at these widths tells a pool held in
    # bfloat16 from the engine's own rounding (the limits' readings, above),
    # so the pool's own count of its bytes is held to the stated precision
    cfg = _kept["ctx"].config
    letters = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    stated = (4 * letters.count("M") * mix["serving"]["num_slots"]
              * cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
              * cfg["ssm_state_size"])
    return {**whole, "carry": carry,
            "state_bytes_as_stated": stated,
            "logprobs_match_reference": bool(
                snap.get("ssd_state_bytes") == stated and all(
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
        # `serve_ssd_state_bytes_per_slot` (the scans' matrices)
        slots = ctx.traffic["serving"]["num_slots"]
        checks = result.checks
        result.samples["kv_bytes_per_token"] = checks["kv_bytes_per_token"]
        for sample, counter in (("state_bytes_per_slot", "conv_state_bytes"),
                                ("ssd_state_bytes_per_slot",
                                 "ssd_state_bytes")):
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
