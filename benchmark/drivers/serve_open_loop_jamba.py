"""Driver `serve_open_loop_jamba`: the open-loop serving driver
(`serve_open_loop.py`: schedule, window, every sample and every end-to-end
quantity are its own, unchanged) with what is AI21-Jamba2-3B's, built as
`serve_open_loop_lfm2.py` builds LFM2's:

- **Set-up draws the weights from the seed in the bfloat16 the model is
  published and held in**: all 28 layers, every width, the whole vocabulary
  (`benchmark/configs/jamba2-3b-28l.json`), by the program's own
  initialiser, which for the Mamba layers is Mamba's published one (decays
  of 1 to 16 a step size, step sizes log-uniform in [0.001, 0.1]: a state
  that remembers tens to thousands of tokens).
- **The embedding is drawn as the initialiser draws it (std 0.02)**, for
  the reason `serve_open_loop_lfm2.py` gives: the head is TIED.
- **Warm-up compiles what chunked prefill can reach** and no more, as
  `serve_open_loop_command_a.py` does it: one prompt of each padded length
  up to the chunk (the one-shot prefill programs) and one of chunk + each
  padded tail (the chunk programs, which start from the state the chunk
  before them left).
- **The check is made against the plain reference**
  (`benchmark/reference/jamba.py`): one seeded greedy request of 9,000
  prompt tokens (four whole chunks of 2,048, then 808 rows in the 1,024
  bucket with 216 padding rows, behind which the state must be the one at
  row 8,999) and 32 tokens decoded through pool and state, the engine's
  log-probabilities of its own tokens against the float32 reference's full
  forward of all 9,032 (the head over the last 32 positions alone).
- `prefill_chunks`, `prefill_prompts` and `requests_admitted` of the
  engine's own counters at the window's two ends go into the samples for
  `serve_prefill_chunks_per_prompt`, as `serve_open_loop_xing.py` keeps
  them; the pool's own counts of its bytes for `serve_kv_bytes_per_token`,
  `serve_state_bytes_per_slot` (the depthwise kernels' inputs) and
  `serve_ssm_state_bytes_per_slot` (the scans' matrices).
"""
from __future__ import annotations

import time

import numpy as np

from benchmark.by_name import load_module

# The engine computes in bf16 over bf16 weights (float32 norm statistics,
# depthwise taps, step sizes, recurrence, state, softmax and head
# accumulator), the reference in float32 over the same bf16 values. The
# model is dense: no router flips, every position moves a little, and the
# log-probabilities lie near -7.4 (a tied head over drawn embeddings).
# The limits' readings (my chip runs, PR 47; 32 decoded positions behind a
# 9,000-token prompt; PERF.md section 6 has every one):
# - the engine over thirty-seven weight seeds (eighteen of the first
#   session, nineteen of the second): mean |difference| 0.0108 to 0.0236
#   (median 0.0156; two seeds over 0.0205), largest position 0.024 to 0.089
#   (median 0.044; six seeds over 0.06: 0.061, 0.063, 0.067, 0.070, 0.088,
#   0.089), 0 to 4 positions over 0.05 (twenty-three seeds read 0, ten 1,
#   two 2, one each 3 and 4);
# - through `verdict` below, on five seeds each
#   (`benchmark/tests/ssm_fault_at_width.py`, on the chip; mean, largest
#   position, positions over 0.05): the reference with its carried state
#   rounded to bfloat16, the nearest precision below the float32 the
#   configuration states: 0.100 / 0.38 / 19, 0.068 / 0.26 / 21, 0.033 /
#   0.089 / 8, 0.034 / 0.153 / 6, 0.041 / 0.156 / 9; the whole recurrence
#   in bfloat16: 0.101 / 0.40 / 20, 0.068 / 0.26 / 19, 0.032 / 0.090 / 9,
#   0.034 / 0.155 / 7, 0.041 / 0.160 / 9; every matrix rounded to
#   float8_e4m3fn, one scale a layer's matrix, the nearest precision below
#   the weights' bfloat16: means 0.87 to 1.23, largest 2.3 to 2.7, 29 to 32;
# - the planted faults of the engine's own path: every continuation chunk
#   started from an empty state: 0.055 / 0.12 / 16, 0.051 / 0.18 / 12,
#   0.031 / 0.108 / 5, 0.042 / 0.164 / 10, 0.050 / 0.202 / 12 (a state
#   fades within a chunk's 2,048 rows for most channels: step sizes of
#   0.001 to 0.1 under decays of 1 to 16; the smallest reading of all);
#   both states taken behind the last chunk's 216 padding rows: means 1.05
#   to 1.27, largest 2.3 to 3.1, 30 or 31.
# What a fault reads depends on the seed's weights threefold (the third
# seed's bfloat16 state moves the mean by 0.033 where the first moves it by
# 0.100), and on the third seed both bfloat16 references and the empty
# chunk passed the first session's limits (0.036 / 0.10 / 9). So: the MEAN,
# the steadiest of the three, decides: 0.028 lies between the engine's
# largest, 0.0236 (1.19 times), and the smallest of ANY fault's on any
# seed, 0.0313 (0.89 of it): every fault above fails it on every seed. The
# COUNT 6 lies between the engine's largest, 4, and the bfloat16
# references' 6 to 21 (two readings AT it or under: the mean has those). A
# SINGLE POSITION separates nothing on the third seed (the bfloat16
# state's largest there, 0.089, IS the engine's largest on another): 0.15
# stands 1.7 times over the engine's largest as a rail for what moves one
# position far and the mean little, and no fault is asked to fail it. What
# holds the state, the padding and the chunks EXACTLY is the float32
# comparison at 1e-4 (`tests/test_jamba_serving.py`), which the same
# planted faults fail by two orders of magnitude and more.
OVER = 0.05
TOL_POSITIONS_OVER = 6
TOL_LOGPROB_MAX = 0.15
TOL_LOGPROB_MEAN = 0.028

_base = load_module("drivers", "serve_open_loop")
_chunked = load_module("drivers", "serve_open_loop_command_a")
_xing = load_module("drivers", "serve_open_loop_xing")
# `benchmark/sweep.py` drives `build_engine`, `warm_up`, `offer` of
# whichever driver a mix names
warm_up, offer = _chunked.warm_up, _base.offer
# the check's one seeded greedy request through the engine, and the
# engine's counters kept at the window's two ends: Xing4.0's driver's own
check_request, WINDOW_COUNTERS = _xing.check_request, _xing.WINDOW_COUNTERS
_kept = {}                  # the weights and the compiled reference of a run

POOL_COUNTERS = ("kv_bytes_per_token", "kv_pool_bytes", "kv_bytes_per_slot",
                 "conv_state_bytes", "ssm_state_bytes")


def build_engine(ctx):
    import jax
    from benchmark.reference import jamba as reference
    from megatron_tpu.arguments import parse_cli
    from megatron_tpu.config import ServingConfig
    from megatron_tpu.inference.generation import Generator
    from megatron_tpu.models import language_model as lm
    from megatron_tpu.serving import ServingEngine

    cfg, _ = parse_cli([*ctx.config["cli"], "--bf16"], n_devices=1)
    mcfg = cfg.model
    tail = ctx.traffic["check"]["output"]
    params = jax.jit(lambda rng: lm.model_init(rng, mcfg))(
        jax.random.PRNGKey(ctx.seed))
    _kept.update(params=params, mcfg=mcfg, reference=jax.jit(
        lambda p, t: reference.token_logprobs(p, t, mcfg, tail=tail)))
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


def verdict(got, ref, positions):
    """The comparison that decides `correct`, of two arrays alone: the
    engine's log-probabilities of its own tokens and the reference's, with
    the three limits. `benchmark/tests/ssm_fault_at_width.py` hands it a
    faulted side, so the control and the cell share one rule."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    n = min(len(got), len(ref))       # a request cut short fails by its length
    diff = np.abs(got[:n] - ref[:n])
    return {"logprob_positions": int(len(got)),
            "logprob_max_abs_diff": float(diff.max()),
            "logprob_mean_abs_diff": float(diff.mean()),
            "logprob_positions_over_0_05": int((diff > OVER).sum()),
            # the positions a wrong state would move most, by themselves
            "logprob_first_two_max_abs_diff": float(diff[1:3].max()),
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
    ref = _kept["reference"](params, jnp.asarray(tokens, jnp.int32))
    snap = engine.metrics.snapshot()
    return {**verdict(got, ref, mix["check"]["output"]),
            "prefill_chunks": int(req.prefill_chunks),
            **{k: snap.get(k) for k in POOL_COUNTERS}}


def run(ctx):
    _base.build_engine = build_engine
    _base.check_against_reference = check_against_reference
    _base.warm_up = warm_up
    try:
        result = _base.run(ctx)
        # what the pool itself counts, for `serve_kv_bytes_per_token`,
        # `serve_state_bytes_per_slot` and `serve_ssm_state_bytes_per_slot`
        slots = ctx.traffic["serving"]["num_slots"]
        checks = result.checks
        result.samples["kv_bytes_per_token"] = checks["kv_bytes_per_token"]
        for sample, counter in (("state_bytes_per_slot", "conv_state_bytes"),
                                ("ssm_state_bytes_per_slot",
                                 "ssm_state_bytes")):
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
        _kept.clear()
