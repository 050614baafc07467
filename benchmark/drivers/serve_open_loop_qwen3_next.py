"""Driver `serve_open_loop_qwen3_next`: the open-loop serving driver
(`serve_open_loop.py`: schedule, window, every sample and every end-to-end
quantity are its own, unchanged) with what is
Qwen3-Next-80B-A3B-Instruct's, built as `serve_open_loop_kimi_linear.py`
builds Kimi Linear's:

- **Set-up draws the weights from the seed in the bfloat16 the model is
  published and held in**: one chip's share of published layers 0 to 7, 128
  of 512 experts a layer under a router of 512, 37,984 rows of the embedding
  and of the head (`benchmark/configs/qwen3-next-80b-a3b-8l.json`), by the
  program's own initialiser, which for the linear layers' decays is KDA's
  and Mamba-2's public one (A in [1, 16] a head, step sizes log-uniform in
  [0.001, 0.1]: a state that remembers tens to thousands of tokens).
- **The embedding is drawn at unit scale** (the head is untied) and **every
  zero-centred norm's scale w ~ N(0, 0.1^2)** (the initialiser's is zero,
  and `w` for `1 + w` would not show): the two norms a layer, the final
  one, q's and k's a head. The attention's output gates and the shared
  expert's gate are sigmoids of products of order 1 with the initialiser's
  own matrices: neither 0 nor 1 as they are drawn.
- **Warm-up compiles what chunked prefill can reach** and no more
  (`serve_open_loop_command_a.py`'s): with `prefill_bucket` = the chunk, ONE
  one-shot prefill program and ONE chunk program, beside the decode step
  and the landing.
- **The check is made against the plain reference**
  (`benchmark/reference/qwen3_next.py`), on TWO seeded greedy requests
  through the programs the cell times, 32 tokens decoded through pool and
  state each: ISSUE 60's, of 9,000 prompt tokens (two whole chunks of 4,096,
  then 808 rows in the 4,096 bucket at offset 8,192 with 3,288 padding rows:
  the flash kernel over the folded rows at a deep offset, behind which the
  state must be row 8,999's), and one of 8,250 (`check_carry`: the last
  chunk is 58 rows, so the checked positions lie 58 to 90 rows behind a
  chunk's start, inside the memory of the heads: a continuation begun from
  an empty state or from stale depthwise inputs cannot pass). Of each: the
  engine's log-probabilities of its own tokens against the float32
  reference's full forward, AND what the pool holds in the request's slot
  against the reference's behind the same tokens (`state_verdict`): the six
  linear layers' states, the depthwise kernel's last inputs and the
  attention layers' last keys, and the FIRST linear layer's state under a
  limit of its own, which is the one that refuses a state kept in bfloat16
  (the limits' note below). And the pool's own count of the state's bytes
  is held to the float32 the configuration states. The reference is
  compiled ONCE, at the longer request's length.
- `expert_load_window` is the reference's own float32 router on the first
  `load_prompt` tokens of the window's own prompts, as
  `serve_open_loop_command_a.py` counts it (`held_row_share`,
  `groups_hit_per_decode_step`, `held_rows_per_decode_step`: what
  `moe_share_roofline_pct` credits).
- `prefill_chunks`, `prefill_prompts` and `requests_admitted` of the
  engine's own counters at the window's two ends go into the samples for
  `serve_prefill_chunks_per_prompt`; the pool's own counts of its bytes for
  `serve_kv_bytes_per_token`, `serve_state_bytes_per_slot` and
  `serve_gdn_state_bytes_per_slot`.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark import loadgen
from benchmark.by_name import load_module

# The engine computes in bf16 over bf16 weights (float32 norm statistics,
# depthwise taps, decays, running sums, the diagonal blocks' inverses, state,
# router, softmax and head accumulator), the reference in float32 over the
# same bf16 values. The log-probabilities lie near -7.1 (an untied head over
# a unit-scale stream). The limits' readings (my chip runs, PR 60; both
# checked requests; mean / median / largest |difference| over the 32
# positions, positions over 0.05; then the slot's state, depthwise inputs
# and last keys, each the largest over its layers of |held - reference| /
# |reference|, and the FIRST linear layer's state alone; PERF.md section 6):
# - the engine over ten weight seeds, twenty readings (the cell's runs, seeds
#   6000000012 and 6000000501-07, and the two controls' own, 6000000201-02):
#   mean 0.0046 to 0.0068, median 0.0036 to 0.0071, largest position 0.011
#   to 0.025, NO position over 0.05; state 0.0109 to 0.0123, first layer
#   0.0041 to 0.0045, inputs 0.0066 to 0.0084, keys 0.0074 to 0.0087 (a band
#   a third as wide as Kimi Linear's: a flipped expert is one of ten of 512
#   under a softmax, a tenth of a gate's weight, where Kimi's was one of
#   eight under 2.446);
# - through `verdict` and `state_verdict` below
#   (`benchmark/tests/gdn_fault_at_width.py`, on the chip, seeds 6000000201
#   (all thirteen) and 6000000202 (seven again, with the median), the
#   9,000-token request | the 8,250-token one; mean / largest / over 0.05,
#   state, first layer, inputs, keys): a continuation chunk begun from an
#   empty state, planted in the ENGINE: 0.007 / 0.024 / 0, 0.012, 0.0041,
#   0.007, 0.008 | 0.033 / 0.108 / 6, 0.358, 0.180, 0.039, 0.042, its twin in
#   the reference (`state_reset`) 0.006 / 0.019 / 0 | 0.031 / 0.090 / 7,
#   0.375, 0.194: the 9,000-token request's positions lie 808 rows behind
#   the chunk's start, where the heads have forgotten, the 8,250-token one's
#   58 (why there are two); a chunk begun from stale (empty) depthwise
#   inputs, in the ENGINE: second request state 0.075 and 0.081, first layer
#   0.038 and 0.063 (the log-probabilities pass: 0.009-0.011 / 0.031 / 0),
#   its twin (`conv_reset`) 0.073 and 0.081, 0.038 and 0.064; the state and
#   the inputs taken behind the last chunk's 3,288 padding rows: 0.172 /
#   0.88 / 20, 0.63, 0.42, 0.100, 0.113 | 0.140 / 0.44 / 24, 0.62, 0.48,
#   0.102, 0.116; the decay applied after the update: 0.050-0.062 / 0.13-0.20
#   / 13-19, 0.19-0.22, 0.09-0.15, 0.059-0.069, 0.070-0.078; every head
#   decaying by the heads' mean: 0.16-0.20 / 0.42-0.48 / 25-28, 1.9, 1.3; `w`
#   for `1 + w`: 3.3 / 4.4 / 32, 11.5; key head j % 16 for j // 2: 0.31-0.33 /
#   0.81-0.87 / 30-31, 1.3; the shared expert's gate left out: 0.063-0.081 /
#   0.18-0.22 / 14-21, 0.115, 0.0041-0.0044, 0.059-0.077, 0.077-0.083; all
#   256 channels rotated: keys 1.10-1.14 and nothing else (32 decoded
#   positions attend positions whose rotations nearly agree); THE
#   ATTENTION'S GATE LEFT OUT, the weakest: mean 0.0236 | 0.0252 and 0.0295
#   | 0.0272, MEDIAN 0.0257 | 0.0266, largest 0.057-0.090, 3 to 8 positions
#   over 0.05, state 0.030-0.031, inputs 0.019-0.020, keys 0.020-0.021 (two
#   gated layers of eight move every position a little and none far).
# So: the MEAN's limit 0.018 lies between the engine's largest 0.0068 (2.6
#   times) and the gate's smallest 0.0236 (1.3 times over it; the scale of
#   the next, the decay after the update, 0.050); the MEDIAN's 0.013, which
#   one flipped position cannot move, between the engine's largest 0.0071
#   (1.8 times) and the gate's 0.0257 (2.0 times): it is the limit the gate
#   left out fails on BOTH requests; the COUNT's 5 between the engine's 0
#   and the faults' it is there for (13 and more; the gate's 3 to 8 is not
#   its to catch); a SINGLE POSITION's 0.25 between the engine's largest
#   0.025 and the padding's and the heads' 0.42 to 0.88; THE STATE's 0.035
#   between the engine's largest 0.0123 (2.8 times) and stale inputs' 0.073
#   (2.1 times over it); THE INPUTS' and THE KEYS' 0.03 between the engine's
#   0.0084 and 0.0087 (3.5 times) and the decay after the update's 0.059 and
#   0.070, the rotation's 1.10 (no fault needs the inputs' limit alone).
#   Each of the thirteen faults fails at least one limit on at least one of
#   the two requests, through the timed programs at the timed sizes.
# - THE FIRST LINEAR LAYER'S STATE, `state_first_layer_rel_err` (PR 58's
#   second round: the largest over the layers cannot refuse a state kept in
#   bfloat16: `state_bf16` reads 0.0140-0.0143 there where the engine reads
#   0.0110-0.0123). The first layer's rows are made from the embedding's own
#   rows: no other layer's rounding and no routing reaches them. The engine
#   0.0041 to 0.0045 over ten weight seeds, twenty-two readings; the state
#   rounded to bfloat16 behind EVERY token (the reference's `state_bf16`, the
#   nearest precision below the float32 the configuration states) 0.0084 |
#   0.0085 (seed 6000000201) and 0.0105 | 0.0105 (6000000202). The limit
#   0.0062 lies 1.38 times over the engine's largest and 1.35 times under
#   the control's smallest: A STATE KEPT IN BFLOAT16 IS REFUSED, on both
#   requests. And the pool's own count of the state's bytes is held to the
#   float32 the configuration states (`state_bytes_as_stated`).
OVER = 0.05
TOL_STATE = 0.035
TOL_STATE_FIRST = 0.0062
TOL_INPUTS = 0.03
TOL_KEYS = 0.03
TOL_POSITIONS_OVER = 5
TOL_LOGPROB_MAX = 0.25
TOL_LOGPROB_MEAN = 0.018
TOL_LOGPROB_MEDIAN = 0.013

EMBEDDING_STD = 1.0
NORM_STD = 0.1
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
                 "conv_state_bytes", "gdn_state_bytes")
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

    # every zero-centred scale: the two norms a layer, the final one, q's
    # and k's a head; NOT the mixers' own head norm, whose scale is w itself
    # (the initialiser's 1)
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    drawn = []
    for i, (path, leaf) in enumerate(flat):
        name = jax.tree_util.keystr(path)
        if "norm" in name and "gdn" not in name:
            leaf = (NORM_STD * jax.random.normal(
                jax.random.fold_in(rng, 101 + i), leaf.shape,
                jnp.float32)).astype(leaf.dtype)
        drawn.append(leaf)
    return jax.tree_util.tree_unflatten(tree, drawn)


def build_engine(ctx):
    import jax
    from benchmark.reference import qwen3_next as reference
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
    step): the rule's matrices [linear layers, H, D, D], the depthwise
    kernel's last inputs [linear layers, K - 1, channels] and the keys of
    positions `rows` - KEY_ROWS .. `rows` - 1 [attention layers, KEY_ROWS,
    n_kv d]."""
    from benchmark.reference.qwen3_next import KEY_ROWS
    for _ in range(100):
        time.sleep(0.1)
        try:
            caches = engine.pool.caches
            return (np.asarray(caches.ssm[:, slot], np.float32),
                    np.asarray(caches.conv[:, slot], np.float32),
                    np.asarray(caches.k[:, slot, rows - KEY_ROWS:rows],
                               np.float32))
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
    layers, H, D, D] and `ref["inputs"]` [2, layers, K - 1, channels] are
    behind every token but the last one chosen, and behind that one too (the
    engine dispatches a step ahead of the host's reading, so the slot of a
    finished request has read its last token as well; either is the
    program's right, the same one for both parts); `ref["keys"]` the rows
    either has written. Each error is the largest over the layers of |held
    - ref| / |ref| (Frobenius); `state_first_layer_rel_err` is the FIRST
    linear layer's alone, whose rows are made from the embedding's own rows
    and carry no other layer's rounding (the limits' note above)."""
    state, inputs, keys = held
    by_layer = [_rel_errs(state, rows) for rows in np.asarray(ref["states"])]
    ahead = int(np.argmin([max(errs) for errs in by_layer]))
    errs = by_layer[ahead]
    conv = max(_rel_errs(inputs, np.asarray(ref["inputs"])[ahead]))
    rows = max(_rel_errs(keys, ref["keys"]))
    return {"state_rel_err": max(errs), "state_rows_ahead": ahead,
            "state_rel_err_by_layer": errs,
            "state_first_layer_rel_err": errs[0],
            "inputs_rel_err": conv, "keys_rel_err": rows,
            "state_tolerance": TOL_STATE,
            "state_first_layer_tolerance": TOL_STATE_FIRST,
            "inputs_tolerance": TOL_INPUTS,
            "keys_tolerance": TOL_KEYS,
            "state_matches_reference": bool(
                max(errs) <= TOL_STATE and errs[0] <= TOL_STATE_FIRST
                and conv <= TOL_INPUTS and rows <= TOL_KEYS)}


def verdict(got, ref, positions):
    """The comparison that decides `correct`, of two arrays alone: the
    engine's log-probabilities of its own tokens and the reference's, with
    the four limits. `benchmark/tests/gdn_fault_at_width.py` hands it a
    faulted side, so the control and the cell share one rule."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    n = min(len(got), len(ref))       # a request cut short fails by its length
    diff = np.abs(got[:n] - ref[:n])
    return {"logprob_positions": int(len(got)),
            "logprob_max_abs_diff": float(diff.max()),
            "logprob_mean_abs_diff": float(diff.mean()),
            "logprob_median_abs_diff": float(np.median(diff)),
            "logprob_positions_over_0_05": int((diff > OVER).sum()),
            "logprob_reference_mean": float(ref.mean()),
            "logprob_tolerance_max": TOL_LOGPROB_MAX,
            "logprob_tolerance_mean": TOL_LOGPROB_MEAN,
            "logprob_tolerance_median": TOL_LOGPROB_MEDIAN,
            "logprob_tolerance_positions_over_0_05": TOL_POSITIONS_OVER,
            "logprobs_match_reference":
                bool(len(got) == positions
                     and (diff > OVER).sum() <= TOL_POSITIONS_OVER
                     and diff.max() <= TOL_LOGPROB_MAX
                     and diff.mean() <= TOL_LOGPROB_MEAN
                     and np.median(diff) <= TOL_LOGPROB_MEDIAN)}


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
    # the configuration states the state in float32 (`assumed.gdn_state`):
    # the pool's own count of its bytes is held to the stated precision
    cfg = _kept["ctx"].config
    layers = sum(1 for i in range(cfg["num_hidden_layers"])
                 if (i + 1) % cfg["full_attention_interval"])
    stated = (4 * layers * mix["serving"]["num_slots"]
              * cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"]
              * cfg["linear_value_head_dim"])
    return {**whole, "carry": carry,
            "state_bytes_as_stated": stated,
            "logprobs_match_reference": bool(
                snap.get("gdn_state_bytes") == stated and all(
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
        # `serve_state_bytes_per_slot` (the depthwise kernel's inputs) and
        # `serve_gdn_state_bytes_per_slot` (the rule's matrices)
        slots = ctx.traffic["serving"]["num_slots"]
        checks = result.checks
        result.samples["kv_bytes_per_token"] = checks["kv_bytes_per_token"]
        for sample, counter in (("state_bytes_per_slot", "conv_state_bytes"),
                                ("gdn_state_bytes_per_slot",
                                 "gdn_state_bytes")):
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
