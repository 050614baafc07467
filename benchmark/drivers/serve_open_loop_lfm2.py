"""Driver `serve_open_loop_lfm2`: the open-loop serving driver
(`serve_open_loop.py`: warm-up, schedule, window, every sample and every
end-to-end quantity are its own, unchanged) with what is LFM2-8B-A1B's, built
as `serve_open_loop_joyai.py` builds JoyAI's:

- **Set-up draws the weights from the seed in the bfloat16 the configuration
  holds them in** (`ModelConfig.params_dtype` of the preset): one dense layer
  and three periods of [attention, conv, conv, conv], every expert, the whole
  vocabulary (`benchmark/configs/lfm2-8b-a1b-13l.json`).
- **The embedding is drawn as the program's initialiser draws it (std 0.02)**,
  as command-a-plus's driver draws its own and for its reason: the head is
  TIED, and at unit scale every position predicts its own input with
  probability 1, so that every log-probability the check reads is 0 and any
  precision passes it. What unit scale was for in OLMoE's and JoyAI's cells
  (a drawn attention stack attends evenly, so small embeddings let a
  request's tokens share their experts) does not arise where three layers in
  four are convolutions of unit gain over the last three tokens' own features:
  `expert_load_window.max_over_mean` says how even the load came out.
- **The router's choosing bias is drawn from the seed, N(0, `BIAS_STD`^2)**,
  as JoyAI's driver draws its own and for its reason: at zero it would choose
  nothing.
- **The check is made against the plain reference**
  (`benchmark/reference/lfm2_moe.py`): one seeded greedy request, a 700-token
  prompt (68 padding rows in its bucket of 768: the state the prefill leaves
  must be the one at row 699, not the one behind the padding) and 32 new
  tokens decoded through the pool of keys, values and state beside nothing
  else, the engine's log-probabilities for its own tokens against the float32
  reference's full forward of all 732, every decoded position from the first.

How evenly the experts are loaded is recorded as OLMoE's driver records it,
from the reference's own float32 router: `expert_load_max_over_mean` on the
check request and `expert_load_window` on the window's own prompts, with
`groups_hit_per_decode_step`, which `moe_stacked_bank_roofline_pct` counts a
decode step's bank bytes from. The dense layer has no router: the lists have
one entry an expert layer, in the model's order.
"""
from __future__ import annotations

import numpy as np

from benchmark.by_name import load_module

# The engine computes in bf16 over bf16 weights (float32 router, softmax, norm
# statistics, the convolution's taps, head accumulator and accumulation over a
# token's 4 experts), the reference in float32 over the same bf16 values. The
# readings, all at the cell's cut on the weights this driver draws (PERF.md
# section 6, PR 37):
# - the engine over eight weight seeds (my chip runs, PR 37): mean |difference|
#   over the 32 positions 0.093 to 0.206 (0.120, 0.160, 0.203, 0.145, 0.170,
#   0.093, 0.147, 0.206), largest single position 0.417 to 1.095, 19 to 26
#   positions over 0.05, the first two decoded positions (the ones that read
#   the state a prefill left) 0.095 to 0.417. Ten times JoyAI's and
#   command-a-plus's, and the configuration's and the draw's own, not the
#   kernels': the reference itself with every intermediate result rounded to
#   bf16 where the engine rounds (no engine, no chip; sandbox,
#   `benchmark/tests/state_fault_at_width.py bf16_activations`, two seeds)
#   reads mean 0.169 and 0.056, largest 0.974 and 0.183. A token takes 4 of
#   32 experts by sigmoid scores that lie ~0.13 of a logit apart under drawn
#   weights; a rounding of the router's input flips a choice at a near-tie in
#   one layer or another for most tokens, a flip swaps a quarter of that
#   layer's output (gates are normalised over 4), the stream behind it is
#   another stream, and the log-probabilities lie near -7.6 (a tied head over
#   drawn embeddings spreads the logits by 0.9), where nothing saturates.
# - the reference with its matrices rounded to float8_e4m3fn, one scale a
#   matrix, the nearest precision below the bf16 the configuration states
#   (router, bias, norms, taps and embedding kept; sandbox, two seeds): mean
#   0.446 and 0.554, largest 1.49 and 1.28, 28 and 31 of 32 positions over
#   0.05.
# - the planted fault, the state a prefill leaves taken behind its bucket's
#   68 padding rows (sandbox, same script, at the check's own 700 + 32
#   positions and the published widths): mean 0.548, largest 3.53, and that
#   largest is one of the first two decoded positions.
# So the MEAN decides between precisions: its limit sits between the engine's
# largest reading (0.206) and fp8's smallest (0.446), 1.55 times the one and
# 0.72 of the other: fp8 fails it, and so does the planted fault. The limit on
# a single position is for what moves few positions far (a state from behind
# the padding reads 3.5 on the first decoded token, a gate not normalised
# would be four times off): it sits between the engine's largest (1.095, a
# cascade of flips) and the planted fault's 3.53. It does not tell precisions
# apart (fp8's largest, 1.28 to 1.49, is the engine's own), and one run that
# reads `correct` false refuses a PR. What holds the state and the pattern
# EXACTLY is the float32 tests at 1e-4 (`tests/test_lfm2.py`), which the same
# planted fault fails by four orders of magnitude.
TOL_LOGPROB_MAX = 2.0
TOL_LOGPROB_MEAN = 0.32

BIAS_STD = 0.004

_base = load_module("drivers", "serve_open_loop")
_olmoe = load_module("drivers", "serve_open_loop_olmoe")
# `benchmark/sweep.py` drives `build_engine`, `warm_up`, `offer` of
# whichever driver a mix names
warm_up, offer = _base.warm_up, _base.offer
_kept = _olmoe._kept        # the weights and the compiled reference of a run


def draw_params(rng, mcfg):
    """The program's initialiser, and a choosing bias that chooses."""
    import jax
    import jax.numpy as jnp
    from megatron_tpu.models import language_model as lm
    params = lm.model_init(rng, mcfg)
    for i, (kind, stack) in enumerate(
            sorted(params["transformer"]["moe"].items())):
        b = stack["mlp"]["e_score_correction_bias"]
        stack["mlp"]["e_score_correction_bias"] = (
            BIAS_STD * jax.random.normal(jax.random.fold_in(rng, 11 + i),
                                         b.shape, jnp.float32)).astype(b.dtype)
    return params


def build_engine(ctx):
    import jax
    from benchmark.reference import lfm2_moe as reference
    from megatron_tpu.arguments import parse_cli
    from megatron_tpu.config import ServingConfig
    from megatron_tpu.inference.generation import Generator
    from megatron_tpu.serving import ServingEngine

    cfg, _ = parse_cli([*ctx.config["cli"], "--bf16"], n_devices=1)
    mcfg = cfg.model
    tail = ctx.traffic["check"]["output"]
    params = jax.jit(lambda rng: draw_params(rng, mcfg))(
        jax.random.PRNGKey(ctx.seed))
    _kept.update(params=params, mcfg=mcfg, reference=jax.jit(
        lambda p, t: reference.token_logprobs(p, t, mcfg, with_choices=True,
                                              tail=tail)))
    gen = Generator(params, mcfg, eos_id=-1, pad_id=0)
    serving = ServingConfig(**ctx.traffic["serving"]).validate(mcfg)
    return mcfg, params, ServingEngine(gen, serving, start=False)


def check_against_reference(engine, params, mcfg, mix, seed):
    import jax.numpy as jnp
    from megatron_tpu.serving import SamplingOptions
    chk = mix["check"]
    rng = np.random.default_rng([seed, 2])
    prompt = rng.integers(1, mcfg.vocab_size, size=chk["prompt"]).tolist()
    req = engine.submit(prompt, chk["output"],
                        SamplingOptions(temperature=0.0), seed=seed)
    tokens, _ = req.result(timeout=mix["request_timeout_s"])
    got = np.asarray(req.gen_logprobs, np.float64)
    ref, chosen = _kept["reference"](params, jnp.asarray(tokens, jnp.int32))
    ref = np.asarray(ref, np.float64)
    diff = np.abs(got - ref)
    snap = engine.metrics.snapshot()
    return {"logprob_positions": int(len(got)),
            "logprob_max_abs_diff": float(diff.max()),
            "logprob_mean_abs_diff": float(diff.mean()),
            # the positions a wrong state would move, by themselves
            "logprob_first_two_max_abs_diff": float(diff[1:3].max()),
            "logprob_positions_over_0_05": int((diff > 0.05).sum()),
            "logprob_reference_mean": float(ref.mean()),
            "logprob_tolerance_max": TOL_LOGPROB_MAX,
            "logprob_tolerance_mean": TOL_LOGPROB_MEAN,
            "expert_load_max_over_mean":
                _olmoe._max_over_mean(np.asarray(chosen).sum(axis=1)),
            **{k: snap.get(k) for k in (
                "kv_bytes_per_token", "kv_pool_bytes", "kv_bytes_per_slot",
                "conv_state_bytes")},
            "logprobs_match_reference":
                bool(len(got) == chk["output"]
                     and diff.max() <= TOL_LOGPROB_MAX
                     and diff.mean() <= TOL_LOGPROB_MEAN)}


def run(ctx):
    _base.build_engine = build_engine
    _base.check_against_reference = check_against_reference
    try:
        result = _base.run(ctx)
        result.checks["expert_load_window"] = _olmoe.window_expert_load(ctx)
        # what the pool itself counts, for `serve_kv_bytes_per_token` and
        # `serve_state_bytes_per_slot`
        slots = ctx.traffic["serving"]["num_slots"]
        state = result.checks.get("conv_state_bytes")
        result.samples["kv_bytes_per_token"] = result.checks[
            "kv_bytes_per_token"]
        result.samples["state_bytes_per_slot"] = (
            state // slots if state else None)
        return result
    finally:
        _kept.clear()
