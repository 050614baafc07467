"""Which operations of a device trace belong to the window layers' rings and
which to the full layers' whole regions of a pool of two kinds
(`megatron_tpu/models/attention.py::HybridKVCache`), by the shapes in an
operation's text alone; no operation's name is written down. The shapes come
from the configuration (`layer_types`' period, `sliding_window`,
`num_key_value_heads`, `head_dim`, `num_hidden_layers`) and the mix
(`num_slots`, `max_len`, `prefill_bucket`, `prefill_chunk`):

- the stack as the pool holds it, [kind's layers, slots, kv heads, rows, head
  dim] (heads before rows), a layer of it (no leading axis), and the same of
  a prefill's own cache of one sequence (1 in place of slots): rows = the
  ring's (min(sliding_window, max_len)) or the region's (max_len). A full
  layer's flash kernel is handed a layer of the one-sequence cache as it
  lies;
- for a window layer, the keys and values a chunk's flash kernel is handed:
  the ring's rows in time order and the chunk's own behind them, [1, kv
  heads, ring + s, head dim] for every padded chunk length s the mix can
  reach.

With max_len <= sliding_window a ring is as long as a region and the two
kinds cannot be told apart by shape: both readers then return nothing."""
from __future__ import annotations

import re

from benchmark.program_spans import count_in, on_tpu


def patterns(cfg: dict, serving: dict):
    """(window, full): compiled patterns, or None where the configuration
    has no layers of two kinds or the shapes coincide."""
    types = cfg.get("layer_types")
    if not types or "sliding_window" not in cfg or len(set(types)) < 2:
        return None
    ring = min(int(cfg["sliding_window"]), int(serving["max_len"]))
    region = int(serving["max_len"])
    if ring == region:
        return None
    layers = int(cfg["num_hidden_layers"])
    n_full = sum(t == "full_attention" for t in types[:layers])
    nkv, hd = int(cfg["num_key_value_heads"]), int(cfg["head_dim"])
    slots = int(serving["num_slots"])
    bucket = int(serving["prefill_bucket"])
    chunk = int(serving.get("prefill_chunk") or region)
    chunks = "|".join(str(ring + s) for s in range(bucket, chunk + 1, bucket))

    def stack(kind_layers, rows):
        return rf"\[({kind_layers},)?({slots}|1),{nkv},{rows},{hd}\]"
    window = re.compile(stack(layers - n_full, ring)
                        + rf"|\[1,{nkv},({chunks}),{hd}\]")
    return window, re.compile(stack(n_full, region))


def ms_per_step(run, which: int):
    """Self time on the first device of the operations of one kind (0:
    window, 1: full) per `mtpu/serve/step` span of the traced window."""
    serving = run.ctx.traffic.get("serving")
    if not on_tpu(run.trace) or not serving:
        return None
    found = patterns(run.ctx.config, serving)
    if found is None:
        return None
    seconds = run.trace.seconds_where(
        lambda text: bool(found[which].search(text)))
    steps = count_in(run.trace, "mtpu/serve/step")
    if not seconds or not steps:
        return None
    return 1e3 * seconds / steps
