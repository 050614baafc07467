"""Which operations of a device trace hold the pool of a model of Mamba-2,
attention and expert layers (`megatron_tpu/models/attention.py::
ConvKVCache`: `ssm`, `conv`, `k` / `v`) or its latent's projections, by the
shapes in an operation's text alone; no operation's name is written down.
The shapes come from the configuration (`hybrid_override_pattern` cut to
`num_hidden_layers` letters, `mamba_num_heads`, `mamba_head_dim`,
`ssm_state_size`, `n_groups`, `conv_kernel`, `hidden_size`,
`moe_latent_size`, `num_key_value_heads`, `head_dim`) and the mix
(`num_slots`, `max_len`, `prefill_max_batch`):

- "state": the state as the pool holds it, float32 [Mamba-2 layers, slots,
  heads, head_dim, d_state] (a matrix a head: 5 x 64 x 128 x 64 x 128 in the
  cell), a layer of it, a slot of it, and the same of a prefill's or a
  chunk's own cache (its batch in place of slots): a decode step's read and
  in-place write of every slot's 4 MiB a layer, a chunk's landing, a
  prefill's copy into its slot, and any copy of the whole state that creeps
  in. The scan kernel's own call is NOT counted here: it is
  `serve_ssd_scan_ms_per_step`'s (`ssd_roofline.is_chunk_scan`);
- "conv": the depthwise kernel's last inputs as the pool holds them,
  [Mamba-2 layers, slots, conv_kernel - 1, d_inner + 2 groups x d_state] (5
  x 64 x 3 x 10,240), a layer or a slot of them, and the same of a
  prefill's or a chunk's own cache;
- "kv": the keys or the values as the pool holds them, [attention layers,
  slots, max_len, kv heads x head dim] (1 x 64 x 8,192 x 256), or a layer of
  them;
- "latent": the latent's two projections as the program holds them, stacked
  over the expert layers, [expert layers, hidden, latent] and [expert
  layers, latent, hidden] (5 x 4,096 x 1,024): a cached program keeps the
  stacks whole and the product's fusion reads a layer of them where it lies
  (compile for v5e, PR 52), so the operation that holds the stack IS the
  product under `mtpu/moe/latent_in` or `mtpu/moe/latent_out`.

Nothing where the configuration has no Mamba-2 layers.
"""
from __future__ import annotations

import re

from benchmark.program_spans import count_in, on_tpu
from benchmark.ssd_roofline import is_chunk_scan


def patterns(cfg: dict, serving: dict):
    """{"state", "conv", "kv", "latent"}: compiled patterns, or None where
    the configuration has no Mamba-2 layers."""
    letters = str(cfg.get("hybrid_override_pattern") or "")[
        :int(cfg.get("num_hidden_layers") or 0)]
    n_mamba, n_attn, n_moe = (letters.count(c) for c in "M*E")
    if not n_mamba or "mamba_num_heads" not in cfg:
        return None
    heads, head_dim = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    d_state, groups = int(cfg["ssm_state_size"]), int(cfg["n_groups"])
    channels = heads * head_dim + 2 * groups * d_state
    hidden, latent = int(cfg["hidden_size"]), int(cfg["moe_latent_size"])
    rows = "|".join(str(b) for b in sorted(
        {1, int(serving["num_slots"]),
         *range(1, int(serving.get("prefill_max_batch", 1)) + 1)}))
    kv_width = int(cfg["num_key_value_heads"]) * int(cfg["head_dim"])
    slots, cap = int(serving["num_slots"]), int(serving["max_len"])
    return {
        "state": re.compile(
            rf"f32\[(1,|{n_mamba},)?({rows}),{heads},{head_dim},{d_state}\]"),
        "conv": re.compile(
            rf"\[(1,|{n_mamba},)?({rows}),{int(cfg['conv_kernel']) - 1},"
            rf"{channels}\]"),
        "kv": re.compile(rf"\[(1,|{n_attn},)?{slots},{cap},{kv_width}\]"),
        "latent": re.compile(
            rf"\[{n_moe},({hidden},{latent}|{latent},{hidden})\]"),
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
        and not is_chunk_scan(text))
    if not seconds:
        return None
    steps = count_in(run.trace, "mtpu/serve/step")
    return 1e3 * seconds / steps if steps else None
