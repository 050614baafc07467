"""Which operations of a device trace belong to a convolution layer's first
product, which to the pool's convolution state and which to its keys and
values (`megatron_tpu/models/short_conv.py`; `megatron_tpu/models/
attention.py::ConvKVCache`), by the shapes in an operation's text alone; no
operation's name is written down. The shapes come from the configuration
(`layer_types`, `num_hidden_layers`, `hidden_size`, `conv_L_cache`,
`num_key_value_heads`, `num_attention_heads`) and the mix (`num_slots`,
`max_len`, `prefill_max_batch`):

- "mix": an array whose minor extent is 3 x hidden (6,144 at LFM2's widths):
  the rows' [.., 3h] product with W_in, what is fused with it (the split, B *
  z, the taps), and W_in's own slices or copies, [h, 3h] with or without the
  layers' axis. No other array of the model is that wide (an expert's first
  product is 2 x 1,792 wide, the dense layer's 2 x 7,168; a prefill of 1 x
  1,536 has 6,144 expert ROWS, which is a major extent and not this one, and
  its router sorts vectors of 6,144, which have no other extent);
- "state": the state as the pool holds it, [conv layers, slots, L - 1,
  hidden], a layer of it, a slot of it, and the same of a prefill's own cache
  (its batch in place of slots): a step's read and in-place write, a
  prefill's landing, a copy of the whole state that crept in;
- "kv": the keys or the values as the pool holds them, [attention layers,
  slots, max_len, kv heads x head dim] (a position's row holds every kv
  head's channels), or a layer of them: a decode step's
  in-place write of each slot's new row, its scores and weighted sum over a
  layer read whole (`models/attention.py::_folded_update_attend`; no kernel
  reads this pool by blocks yet), a prefill's copy of its finished sequences
  into their slots.

Nothing where the configuration has no convolution layers.
"""
from __future__ import annotations

import re

from benchmark.program_spans import count_in, on_tpu


def patterns(cfg: dict, serving: dict):
    """{"mix", "state", "kv"}: compiled patterns, or None where the
    configuration has no convolution layers."""
    layers = int(cfg.get("num_hidden_layers") or 0)
    types = list(cfg.get("layers_kept", {}).get("their_layer_types")
                 or (cfg.get("layer_types") or [])[:layers])
    n_conv = types.count("conv")
    if not n_conv:
        return None
    n_attn = len(types) - n_conv
    hidden = int(cfg["hidden_size"])
    taps = int(cfg["conv_L_cache"]) - 1
    nkv = int(cfg["num_key_value_heads"])
    hd = int(cfg.get("head_dim") or hidden // int(cfg["num_attention_heads"]))
    slots, cap = int(serving["num_slots"]), int(serving["max_len"])
    rows = "|".join(str(b) for b in sorted(
        {1, slots, *range(1, int(serving.get("prefill_max_batch", 1)) + 1)}))
    return {
        "mix": re.compile(rf",{3 * hidden}\]"),
        "state": re.compile(
            rf"\[(1,|{n_conv},)?({rows}),{taps},{hidden}\]"),
        "kv": re.compile(
            rf"\[(1,|{n_attn},)?{slots},{cap},{nkv * hd}\]"),
    }


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
        lambda text: bool(found[which].search(text)))
    steps = count_in(run.trace, "mtpu/serve/step")
    if not seconds or not steps:
        return None
    return 1e3 * seconds / steps
