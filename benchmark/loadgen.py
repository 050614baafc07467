"""The one general generator of open-loop serving traffic.

A traffic mix is a file of parameters under `benchmark/traffic/`. From it and
a seed this module makes a schedule: for each request the time it is due, its
prompt length, its output length and its own sampling seed. The pacing
arithmetic (sleep until the request is due, never send early, never skip) is
the one `tools/serving_bench.py::_pace` has, copied here so that the
yardstick does not move with the program.

Every seed gets the same work. The lengths of a phase are the mid-quantiles
of the mix's clipped log-normals and its gaps the mid-quantiles of the
exponential distribution, scaled to fill the phase exactly; the seed
permutes them (lengths and gaps independently) and draws the tokens. A seed therefore changes which request meets
which, and never how many tokens or how many arrivals a window holds.
"""
from __future__ import annotations

import dataclasses
import time
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Arrival:
    due_s: float        # seconds after the generator's start
    prompt_len: int
    output_len: int
    seed: int
    phase: str          # "ramp" | "window" | "tail"


def lognormal_quantiles(n: int, median: float, sigma: float,
                        lo: int, hi: int) -> np.ndarray:
    """n lengths at the mid-quantiles of a clipped log-normal, ascending:
    the same multiset for every seed, so that a seed changes the order and
    the contents and never the amount of work."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.round(median * np.exp(sigma * z)), lo, hi).astype(int)


def _lengths(n: int, spec: dict) -> np.ndarray:
    return lognormal_quantiles(n, spec["median"], spec["sigma"],
                               spec["min"], spec["max"])


def _phase(rng, name: str, start_s: float, length_s: float, mix: dict,
           seed_base: int) -> List[Arrival]:
    n = max(int(round(mix["rate_rps"] * length_s)), 1)
    prompts, outputs = _lengths(n, mix["prompt"]), _lengths(n, mix["output"])
    gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n)     # Exp(1) mid-quantiles
    gaps *= length_s / gaps.sum()
    rng.shuffle(prompts)
    rng.shuffle(outputs)
    rng.shuffle(gaps)
    due = start_s + np.cumsum(gaps) - gaps / 2.0      # inside the phase
    return [Arrival(float(due[i]), int(prompts[i]), int(outputs[i]),
                    seed_base + i, name) for i in range(n)]


def schedule(mix: dict, seed: int, window_s: float) -> List[Arrival]:
    """Ramp, window and tail, in order of due time. The tail is as long as
    the mix's `tail_cap_s`: the generator stops offering it as soon as every
    request of the window has finished."""
    rng = np.random.default_rng(seed)
    ramp, tail = float(mix["ramp_s"]), float(mix["tail_cap_s"])
    out = _phase(rng, "ramp", 0.0, ramp, mix, 0)
    out += _phase(rng, "window", ramp, window_s, mix, len(out))
    out += _phase(rng, "tail", ramp + window_s, tail, mix, len(out))
    return out


def prompts_for(arrivals: List[Arrival], vocab: int, seed: int) -> List[list]:
    """Seeded token ids in [1, vocab): distinct prompts, no shared prefix."""
    rng = np.random.default_rng([seed, 1])
    return [rng.integers(1, vocab, size=a.prompt_len).tolist()
            for a in arrivals]


def sleep_until(t: float):
    """`tools/serving_bench.py::_pace`: wait for the due time, never past."""
    delay = t - time.monotonic()
    if delay > 0:
        time.sleep(delay)


def prefill_buckets(mix: dict, bucket: int) -> List[int]:
    """Every padded prompt length the mix can reach."""
    lo = -(-mix["prompt"]["min"] // bucket) * bucket
    hi = -(-mix["prompt"]["max"] // bucket) * bucket
    return list(range(lo, hi + 1, bucket))
