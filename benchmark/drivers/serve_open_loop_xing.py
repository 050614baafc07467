"""Driver `serve_open_loop_xing`: the open-loop serving driver
(`serve_open_loop.py`: schedule, window, every sample and every end-to-end
quantity are its own, unchanged) with what is Xing4.0-29B-A4B's, built as
`serve_open_loop_joyai.py` builds JoyAI's:

- **Set-up draws the weights from the seed in the bfloat16 the model is
  published and held in**, and **drops the multi-token-prediction module**:
  the served logits do not depend on it and the engine reads no key of it
  (another 0.77 B parameters, 1.4 GiB).
- **The embedding is drawn at unit scale and the router's choosing bias
  N(0, `BIAS_STD`^2)**, for the reasons `serve_open_loop_joyai.py` gives: at
  the initialiser's 0.02 a request's tokens share their experts, and a bias
  of zero chooses nothing.
- **The hyper-connections' phi, alpha, b are drawn so that the maps do
  work.** Their trained values are not public, and the program's initialiser
  starts them at the one-stream residual (H_res the identity, H_pre 1/4,
  H_post 1: a model that has not begun to learn its mixing). Drawn here, a
  sublayer's own: `alpha` = 1 on all three maps; `phi` N(0, 1 / (n C)),
  which makes x^ phi N(0, ~1) a column whatever the streams' scale (x^ has
  unit root mean square over its n C = 14,336 values), so a token's maps
  move about their biases by about one unit of logit; `b_pre`
  N(logit(1/4), `B_STD`^2) and `b_post` N(0, `B_STD`^2) (H_pre spread about
  1/4, H_post about 1, which keeps the streams' scale from layer to layer);
  `b_res` N(0, `B_STD`^2) with `RES_DIAGONAL` added on the diagonal: a
  token's H_res then keeps 0.5 to 0.7 of a stream in place and mixes the
  rest, differently for every token (`checks.hc_maps`: the mean largest
  entry of a row, between the identity's 1 and uniform's 0.25; its spread
  over tokens; the spread of H_pre and H_post; how far twenty rounds leave
  rows and columns from 1). At alpha 0.01, the initialiser's, the maps
  would be one constant matrix a sublayer and the product with phi would do
  no work that shows in a result; at `RES_DIAGONAL` 0 a stream would be
  smeared over all four within two layers and the four would be one.
- **Warm-up compiles what chunked prefill can reach** and no more, as
  `serve_open_loop_command_a.py` does it: one prompt of each padded length up
  to the chunk (the one-shot prefill programs) and one of chunk + each padded
  tail (the chunk programs over the latent pool).
- **The check is made against the plain reference**
  (`benchmark/reference/xing4.py`): one seeded greedy request of 6,000
  prompt tokens (a 4,096 chunk in the expanded form, then 1,904 rows in a
  2,048 bucket at offset 4,096 in the absorbed form with 144 padding rows)
  and 32 tokens decoded through the latent pool, the engine's
  log-probabilities of its own tokens against the float32 reference's full
  forward of all 6,032 (the head over the last 32 positions alone).
- `prefill_chunks`, `prefill_prompts` and `requests_admitted` of the
  engine's own counters, read where the base driver reads the window's two
  ends, go into the samples for `serve_prefill_chunks_per_prompt`.

How evenly the experts are loaded is recorded as JoyAI's driver records it.
"""
from __future__ import annotations

import math
import time

import numpy as np

from benchmark.by_name import load_module

# The engine computes in bf16 over bf16 weights (float32 router, softmax,
# norm statistics, maps, Sinkhorn rounds, mixes' sums, head accumulator), the
# reference in float32 over the same bf16 values. The limits' readings (my
# chip runs, PR 41; 32 decoded positions behind a 6,000-token prompt; PERF.md
# section 6 has every one):
# - the engine over twenty-two weight seeds: mean |difference| 0.0034 to
#   0.0343, largest position 0.012 to 0.464, and 0 to 5 positions over 0.05.
#   The quiet positions read ~0.005, the residual of streams being bf16
#   between the mixes; the loud ones are where a top-4 of 64 flips at a
#   near-tie between the engine's router and the reference's: the swapped
#   expert's renormalised, doubled gate is 2 / 4 = 0.5 of the routed sum, and
#   moves that position by 0.1 to 0.4. The mean follows the flips (0.006 a
#   flip) and has a long tail;
# - the reference with every matrix rounded to float8_e4m3fn, one scale a
#   matrix, the nearest precision below the configuration's bf16, in the
#   sound reference's place against the engine, through `verdict` below
#   (`benchmark/tests/hc_fault_at_width.py`, on the chip, three seeds): 18,
#   18 and 16 of 32 positions over 0.05, mean 0.095, 0.054 and 0.078, largest
#   0.64, 0.21 and 0.44 (against the sound reference alone, two more seeds:
#   13 and 15 positions, mean 0.059 and 0.068).
# So THE COUNT OF POSITIONS OVER 0.05 decides between precisions (PERF.md
# section 7, PR 35 (h), asked for it): a lower precision moves every
# position a little, a flip moves one far. Its limit, 9, is 1.8 times the
# engine's largest reading and fp8's smallest through the rule, 16, is 1.8
# times the limit. The mean (0.07, twice the engine's largest; fp8 passes it
# on one seed of three) and a single position (0.80, 1.7 times the engine's
# largest: a flip reads more than fp8 does) guard against what moves many
# positions at once or few very far: H_post without its 2 reads 31
# positions, 0.41 and 1.00 and fails all three. Limits the
# engine's tail reaches are no use: command-a-plus's 0.40 a position refused
# a PR by the draw of a seed (PERF.md section 7, PR 35 (h)).
# WHAT THE CHECK DOES NOT SEE at these widths and under this draw (same
# file, same rule; each reads inside the engine's own range and passes):
# anything of the ATTENTION: the continuation chunk finding zeros where the
# first chunk's 4,096 latent rows should lie (three seeds: 2-3 positions,
# mean 0.015-0.039, largest 0.11-0.33), the continuation chunk written and
# turned one row late, MLA's scale without YaRN's m^2 (2-5 positions,
# 0.026-0.037, 0.23-0.38); and the fine grain of the maps:
# maps in bfloat16, Sinkhorn rounds in bfloat16, ten rounds for twenty.
# Scores of standard deviation ~0.9 over 6,000 rows weigh thousands of them,
# so a head's output is a hundredth of a stream's scale and the experts, the
# head and the coarse maps carry the logits. What holds those layers is the
# float32 comparison at 1e-4 (`tests/test_xing.py`,
# `tests/test_xing_serving.py`: continuation chunks, prefix hits, verify
# windows, decode; every planted fault fails it by orders of magnitude). A
# draw under which attention carries a tenth of a stream, with the limits
# read again, is a `benchmark` issue's (PERF.md section 7).
OVER = 0.05
TOL_POSITIONS_OVER = 9
TOL_LOGPROB_MAX = 0.80
TOL_LOGPROB_MEAN = 0.07

EMBEDDING_STD = 1.0
BIAS_STD = 0.004
B_STD = 0.5
RES_DIAGONAL = 2.0

_base = load_module("drivers", "serve_open_loop")
_olmoe = load_module("drivers", "serve_open_loop_olmoe")
_chunked = load_module("drivers", "serve_open_loop_command_a")
# `benchmark/sweep.py` drives `build_engine`, `warm_up`, `offer` of
# whichever driver a mix names
warm_up, offer = _chunked.warm_up, _base.offer
_kept = _olmoe._kept        # the weights and the compiled reference of a run

WINDOW_COUNTERS = ("prefill_chunks", "prefill_prompts", "requests_admitted")


def draw_params(rng, mcfg):
    """The served tree from the seed (module docstring)."""
    import jax
    import jax.numpy as jnp
    from megatron_tpu.models import language_model as lm
    params = lm.model_init(rng, mcfg)
    del params["mtp"]                        # not loaded when serving
    rows = params["embedding"]["word_embeddings"]
    params["embedding"]["word_embeddings"] = rows * (
        EMBEDDING_STD / mcfg.init_method_std)
    mlp = params["transformer"]["moe"]["mlp"]
    b = mlp["e_score_correction_bias"]
    mlp["e_score_correction_bias"] = (BIAS_STD * jax.random.normal(
        jax.random.fold_in(rng, 11), b.shape, jnp.float32)).astype(b.dtype)
    n = mcfg.hc_mult
    centre = jnp.concatenate([
        jnp.full((n,), math.log(1.0 / (n - 1.0))), jnp.zeros((n,)),
        RES_DIAGONAL * jnp.eye(n).reshape(-1)])
    key = jax.random.fold_in(rng, 13)
    for stack in params["transformer"].values():
        for name in ("hc_attn", "hc_mlp"):
            key, k_phi, k_b = jax.random.split(key, 3)
            maps = stack[name]
            dtype = maps["phi"].dtype
            maps["phi"] = (jax.random.normal(k_phi, maps["phi"].shape,
                                             jnp.float32)
                           / math.sqrt(n * mcfg.hidden_size)).astype(dtype)
            maps["alpha"] = jnp.ones_like(maps["alpha"])
            maps["b"] = (centre + B_STD * jax.random.normal(
                k_b, maps["b"].shape, jnp.float32)).astype(dtype)
    return params


def build_engine(ctx):
    import jax
    from benchmark.reference import xing4 as reference
    from megatron_tpu.arguments import parse_cli
    from megatron_tpu.config import ServingConfig
    from megatron_tpu.inference.generation import Generator
    from megatron_tpu.serving import ServingEngine

    cfg, _ = parse_cli([*ctx.config["cli"], "--bf16"], n_devices=1)
    mcfg = cfg.model
    tail = ctx.traffic["check"]["output"]
    params = jax.jit(lambda rng: draw_params(rng, mcfg))(
        jax.random.PRNGKey(ctx.seed))
    _kept.update(ctx=ctx, params=params, mcfg=mcfg, reference=jax.jit(
        lambda p, t: reference.token_logprobs(p, t, mcfg, with_choices=True,
                                              tail=tail, with_maps=True)))
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


def map_statistics(maps):
    """What the drawn maps do, over the check request's tokens and every
    sublayer: `maps` as `reference.maps` lists them."""
    pre, post, res = (np.stack([np.asarray(m[i], np.float64)
                                for layer in maps for m in layer])
                      for i in range(3))          # [sublayers, s, n(, n)]
    largest = res.max(axis=-1)                    # of each row
    return {"h_res_row_max_mean": float(largest.mean()),
            "h_res_row_max_std_over_tokens": float(largest.std(axis=1).mean()),
            "h_res_diagonal_mean": float(
                np.diagonal(res, axis1=-2, axis2=-1).mean()),
            "h_pre_mean": float(pre.mean()), "h_pre_std": float(pre.std()),
            "h_post_mean": float(post.mean()),
            "h_post_std": float(post.std()),
            "h_res_row_sum_max_err": float(
                np.abs(res.sum(axis=-1) - 1.0).max()),
            "h_res_column_sum_max_err": float(
                np.abs(res.sum(axis=-2) - 1.0).max())}


def check_request(engine, mcfg, mix, seed):
    """The check's one seeded greedy request through the engine: the
    request, prompt + the tokens it chose, the engine's log-probabilities of
    those."""
    from megatron_tpu.serving import SamplingOptions
    chk = mix["check"]
    rng = np.random.default_rng([seed, 2])
    prompt = rng.integers(1, mcfg.vocab_size, size=chk["prompt"]).tolist()
    req = engine.submit(prompt, chk["output"],
                        SamplingOptions(temperature=0.0), seed=seed)
    tokens, _ = req.result(timeout=mix["request_timeout_s"])
    return req, tokens, np.asarray(req.gen_logprobs, np.float64)


def verdict(got, ref, positions):
    """The comparison that decides `correct`, of two arrays alone: the
    engine's log-probabilities of its own tokens and the reference's, with
    the three limits. `benchmark/tests/hc_fault_at_width.py` hands it a
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
    import jax.numpy as jnp
    req, tokens, got = check_request(engine, mcfg, mix, seed)
    ref, chosen, maps = _kept["reference"](params,
                                           jnp.asarray(tokens, jnp.int32))
    snap = engine.metrics.snapshot()
    return {**verdict(got, ref, mix["check"]["output"]),
            "prefill_chunks": int(req.prefill_chunks),
            "hc_maps": map_statistics(maps),
            "expert_load_max_over_mean":
                _olmoe._max_over_mean(np.asarray(chosen).sum(axis=1)),
            "kv_bytes_per_token": snap.get("kv_bytes_per_token"),
            "kv_pool_bytes": snap.get("kv_pool_bytes"),
            # counted here, on the prompts the window WILL offer, while the
            # device holds what it held for the check above (as
            # `serve_open_loop_command_a.py` does, and for its reason)
            "expert_load_window": _olmoe.window_expert_load(_kept["ctx"])}


def run(ctx):
    _base.build_engine = build_engine
    _base.check_against_reference = check_against_reference
    _base.warm_up = warm_up
    try:
        result = _base.run(ctx)
        # what the pool itself counts, for `serve_kv_bytes_per_token`
        result.samples["kv_bytes_per_token"] = result.checks[
            "kv_bytes_per_token"]
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
        _kept.clear()
