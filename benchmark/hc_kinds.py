"""Which operations of a device trace belong to the hyper-connections' maps
and which to their mixes (`megatron_tpu/models/hyper_connections.py`), by the
shapes in an operation's text alone; no operation's name is written down. The
shapes come from the configuration (`hc_mult` = n, `hidden_size` = C) and the
mix (`num_slots`, `prefill_bucket`, `prefill_chunk`, `prefill_max_batch`):

- "map": an operation that holds the maps' weight, `[n C, n^2 + 2n]` (with
  or without the layers' axis: the product, and the weight's own slices or
  copies), or a float32 plane of the maps as the program holds them, the
  TOKENS MINOR: `[n | n, n | n^2 | n^2 + 2n, tokens]`, where `tokens` is a
  decode step's `[slots(, 1)]` or a prefill's `[(batch,) rows]` (rows: every
  padded length up to the chunk), with unit extents anywhere the compiler
  leaves them; or the product's own result, `[tokens, n^2 + 2n]`. Those are
  the product with phi, the scale by the root mean square, the two sigmoids,
  the exponential and the Sinkhorn rounds (which the compiler makes many
  small operations of: 20 rounds x 2 divisions, PERF.md section 6, PR 41);
- "mix": an operation that holds the residual of n streams, an array whose
  MINOR extent is n C (14,336 at Xing4.0's widths: `[batch, rows, n C]`,
  `[rows, n C]`) or `[.., n, C]`, and is no "map": H_pre X, H_res X + H_post^T
  out, the expand and the collapse, a copy or a transposition of the streams
  that crept in. The reduction over n C values inside the maps' norm holds
  the streams and none of the maps' planes (its result is a value a token)
  and is counted HERE, as the pass over the streams it is.

What neither holds: everything between the mixes, whose arrays are C wide.
Nothing where the configuration has `hc_mult` <= 1 or none.
"""
from __future__ import annotations

import re

from benchmark.program_spans import count_in, on_tpu


def patterns(cfg: dict, serving: dict):
    """{"map", "streams"}: compiled patterns, or None where the configuration
    has no residual of streams."""
    n = int(cfg.get("hc_mult") or 1)
    if n <= 1:
        return None
    c = int(cfg["hidden_size"])
    m = n * n + 2 * n
    slots = int(serving["num_slots"])
    bucket = int(serving.get("prefill_bucket") or 1)
    longest = int(serving.get("prefill_chunk") or serving["max_len"])
    rows = sorted({min(r, longest)
                   for r in range(bucket, longest + bucket, bucket)})
    batches = range(1, int(serving.get("prefill_max_batch", 1)) + 1)
    tokens = {f"{slots}", f"{slots},1"}
    for b in batches:
        for r in rows:
            tokens |= {f"{b},{r}", f"{b * r}"}
    one = r"(1,)*"
    tok = "(" + "|".join(sorted(tokens, key=len, reverse=True)) + ")"
    lead = f"({n},{one}{n}|{n}|{n * n}|{m})"
    return {
        "map": re.compile(
            rf"\[(\d+,)?{n * c},{m}\]"
            rf"|f32\[{one}{lead},{one}{tok}\]"
            rf"|f32\[{one}{tok},{m}\]"),
        "streams": re.compile(rf",{n * c}\]|,{n},{c}\]"),
    }


def kind_of(found, text: str):
    """ "map", "mix" or None for one operation's text."""
    if found["map"].search(text):
        return "map"
    if found["streams"].search(text):
        return "mix"
    return None


def ms_per_step(run, which: str):
    """Self time on the first device of the operations of one kind per
    `mtpu/serve/step` span of the traced window, decode and prefill programs
    together."""
    serving = run.ctx.traffic.get("serving")
    if not on_tpu(run.trace) or not serving:
        return None
    found = patterns(run.ctx.config, serving)
    if found is None:
        return None
    seconds = run.trace.seconds_where(
        lambda text: kind_of(found, text) == which)
    steps = count_in(run.trace, "mtpu/serve/step")
    if not seconds or not steps:
        return None
    return 1e3 * seconds / steps
