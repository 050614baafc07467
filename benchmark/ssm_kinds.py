"""Which operations of a device trace hold the pool of a model of Mamba and
attention layers (`megatron_tpu/models/attention.py::ConvKVCache`: `ssm`,
`conv`, `k` / `v`), by the shapes in an operation's text alone; no
operation's name is written down. The shapes come from the configuration
(`num_hidden_layers`, `attn_layer_period`, `attn_layer_offset`,
`hidden_size`, `mamba_expand`, `mamba_d_state`, `mamba_d_conv`,
`num_key_value_heads`, `num_attention_heads`) and the mix (`num_slots`,
`max_len`, `prefill_max_batch`):

- "state": the state as the pool holds it, float32 [mamba layers, slots,
  d_state, d_inner] (the channels minor: 26 x 32 x 16 x 5,120 in the cell),
  a layer of it, a slot of it, and the same of a prefill's or a chunk's own
  cache (its batch in place of slots): a decode step's read and in-place
  write, a chunk's landing, a prefill's copy into its slot, and any copy of
  the whole state that creeps in. The scan kernel's own call holds a layer
  of a batch-1 cache, and fused with the write of its state the whole
  stacked cache of a chunk; it is NOT counted here: it is
  `serve_ssm_scan_ms_per_step`'s (`ssm_roofline.is_selective_scan`);
- "conv": the depthwise kernels' last inputs as the pool holds them, [mamba
  layers, slots, d_conv - 1, d_inner] (26 x 32 x 3 x 5,120), a layer or a
  slot of them, and the same of a prefill's or a chunk's own cache: a step's
  read and in-place write, a chunk's landing;
- "kv": the keys or the values as the pool holds them, [attention layers,
  slots, max_len, kv heads x head dim] (2 x 32 x 32,768 x 128), or a layer
  of them: a decode step's in-place write of each slot's new row and its
  scores and weighted sum over a layer read whole, a prefill's copy of its
  finished sequence into its slot (`conv_kinds.py`'s "kv", which answers
  only a configuration with convolution layers).

Nothing where the configuration has no Mamba layers.
"""
from __future__ import annotations

import re

from benchmark.program_spans import count_in, on_tpu
from benchmark.ssm_roofline import is_selective_scan


def patterns(cfg: dict, serving: dict):
    """{"state", "conv", "kv"}: compiled patterns, or None where the
    configuration has no Mamba layers."""
    period, offset = cfg.get("attn_layer_period"), cfg.get("attn_layer_offset")
    if not period or "mamba_d_state" not in cfg:
        return None
    layers = int(cfg["num_hidden_layers"])
    n_mamba = sum(1 for l in range(layers) if l % period != offset)
    if not n_mamba:
        return None
    hidden = int(cfg["hidden_size"])
    d_inner = int(cfg["mamba_expand"]) * hidden
    d_state = int(cfg["mamba_d_state"])
    rows = "|".join(str(b) for b in sorted(
        {1, int(serving["num_slots"]),
         *range(1, int(serving.get("prefill_max_batch", 1)) + 1)}))
    kv_width = int(cfg["num_key_value_heads"]) * int(
        cfg.get("head_dim") or hidden // int(cfg["num_attention_heads"]))
    slots, cap = int(serving["num_slots"]), int(serving["max_len"])
    return {
        "state": re.compile(
            rf"f32\[(1,|{n_mamba},)?({rows}),{d_state},{d_inner}\]"),
        "conv": re.compile(
            rf"\[(1,|{n_mamba},)?({rows}),{int(cfg['mamba_d_conv']) - 1},"
            rf"{d_inner}\]"),
        "kv": re.compile(
            rf"\[(1,|{layers - n_mamba},)?{slots},{cap},{kv_width}\]"),
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
        lambda text: bool(found[which].search(text))
        and not is_selective_scan(text))
    if not seconds:
        return None
    steps = count_in(run.trace, "mtpu/serve/step")
    return 1e3 * seconds / steps if steps else None
