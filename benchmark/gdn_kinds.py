"""Which operations of a device trace hold the parts of the pool of a model
of Gated DeltaNet and attention layers (`megatron_tpu/models/
attention.py::ConvKVCache`: `ssm`, `conv`, `k`, `v`), by the shapes in an
operation's text alone; no operation's name is written down but the flash
kernel's own (`_flash_attention_offset`, the name the program jits it
under). The shapes come from the configuration (`num_hidden_layers` and
`full_attention_interval`: layer i is attention where (i + 1) % interval ==
0; `linear_num_key_heads`, `linear_num_value_heads`, `linear_key_head_dim`,
`linear_value_head_dim`, `linear_conv_kernel_dim`; `num_key_value_heads`,
`head_dim`) and the mix (`num_slots`, `prefill_max_batch`, `max_len`):

- "state": the rule's state as the pool holds it, float32 [linear layers,
  slots, value heads, key_head_dim, value_head_dim] (a matrix a value head:
  6 x 32 x 32 x 128 x 128 in the cell), a layer of it, a slot of it, and the
  same of a prefill's or a chunk's own cache (its batch in place of slots):
  a decode step's read and in-place write of every slot's 2 MiB a layer, a
  chunk's landing, a prefill's copy into its slot, and any copy of the
  whole state that creeps in. The chunk kernel's own call is NOT counted
  here: it is `serve_gdn_scan_ms_per_step`'s (`gdn_roofline.is_gdn_chunk`);
- "conv": the depthwise kernel's last inputs as the pool holds them, [linear
  layers, slots, linear_conv_kernel_dim - 1, 2 H_k D_k + H D_v] (6 x 32 x 3
  x 8,192), a layer or a slot of them, and the same of a prefill's or a
  chunk's own cache;
- "kv": the attention layers' keys and values as the pool holds them, a
  position's row the kv heads' channels side by side, [attention layers,
  slots, max_len, n_kv x head_dim] (2 x 32 x 32,768 x 512), a layer, a slot
  or a one-sequence cache of them: a decode step's scores and weighted sums
  over the folded rows and the in-place writes of the new rows; AND the
  flash kernel that reads a slot's folded rows at a chunk's offset
  (`_flash_attention_offset`'s Pallas call).

Nothing where the configuration has no linear-attention layers
(`linear_num_value_heads` absent): the experts' rows, the router's vectors
and the other cells' pools match no pattern.
"""
from __future__ import annotations

import re

from benchmark.gdn_roofline import is_gdn_chunk
from benchmark.program_spans import count_in, on_tpu
from benchmark.trace import is_pallas_kernel, parse_op

FLASH = "_flash_attention_offset"


def patterns(cfg: dict, serving: dict):
    """{"state", "conv", "kv"}: compiled patterns, or None where the
    configuration has no linear-attention layers."""
    heads = cfg.get("linear_num_value_heads")
    interval = cfg.get("full_attention_interval")
    if not heads or not interval:
        return None
    depth = int(cfg["num_hidden_layers"])
    n_attn = sum(1 for i in range(depth) if (i + 1) % interval == 0)
    n_lin = depth - n_attn
    d_k, d_v = int(cfg["linear_key_head_dim"]), int(cfg["linear_value_head_dim"])
    channels = 2 * int(cfg["linear_num_key_heads"]) * d_k + int(heads) * d_v
    taps = int(cfg["linear_conv_kernel_dim"])
    row = int(cfg["num_key_value_heads"]) * int(cfg["head_dim"])
    positions = int(serving["max_len"])
    rows = "|".join(str(b) for b in sorted(
        {1, int(serving["num_slots"]),
         *range(1, int(serving.get("prefill_max_batch", 1)) + 1)}))
    return {
        "state": re.compile(
            rf"f32\[(1,|{n_lin},)?({rows}),{int(heads)},{d_k},{d_v}\]"),
        "conv": re.compile(
            rf"\[(1,|{n_lin},)?({rows}),{taps - 1},{channels}\]"),
        "kv": re.compile(
            rf"\[(1,|{n_attn},)?({rows}),{positions},{row}\]"),
    }


def is_flash_at_offset(text: str) -> bool:
    name, _, _ = parse_op(text)
    return FLASH in name and is_pallas_kernel(text)


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
        lambda text: (bool(found[which].search(text))
                      or (which == "kv" and is_flash_at_offset(text)))
        and not is_gdn_chunk(text))
    if not seconds:
        return None
    steps = count_in(run.trace, "mtpu/serve/step")
    return 1e3 * seconds / steps if steps else None
