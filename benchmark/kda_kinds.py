"""Which operations of a device trace hold the parts of the pool of a model
of Kimi Delta Attention and MLA layers (`megatron_tpu/models/
attention.py::LatentStateCache`: `ssm`, `conv`, `c`), by the shapes in an
operation's text alone; no operation's name is written down. The shapes
come from the configuration (`linear_attn_config`'s `kda_layers` and
`full_attn_layers` among the first `num_hidden_layers`, its `num_heads`,
`head_dim`, `short_conv_kernel_size`; `kv_lora_rank`, `qk_rope_head_dim`,
`num_attention_heads`) and the mix (`num_slots`, `prefill_max_batch`,
`max_len`):

- "state": the rule's state as the pool holds it, float32 [KDA layers,
  slots, heads, head_dim, head_dim] (a matrix a head: 6 x 32 x 32 x 128 x
  128 in the cell), a layer of it, a slot of it, and the same of a
  prefill's or a chunk's own cache (its batch in place of slots): a decode
  step's read and in-place write of every slot's 2 MiB a layer, a chunk's
  landing, a prefill's copy into its slot, and any copy of the whole state
  that creeps in. The chunk kernel's own call is NOT counted here: it is
  `serve_kda_scan_ms_per_step`'s (`kda_roofline.is_kda_chunk`);
- "conv": the three depthwise kernels' last inputs as the pool holds them,
  [KDA layers, slots, short_conv_kernel_size - 1, 3 x heads x head_dim] (6 x
  32 x 3 x 12,288), a layer or a slot of them, and the same of a prefill's
  or a chunk's own cache.

- "latent": the MLA layers' latent rows as the pool holds them, positions
  minor, [MLA layers, slots, kv_lora_rank + qk_rope_head_dim, max_len] (2 x
  32 x 576 x 32,768), a layer, a slot or a one-sequence cache of them, AND
  the absorbed attention's weights over the whole region, [max_len, a block
  of queries, heads] in a chunk and [slots, max_len, heads] in a decode step
  (bf16[32768,256,32] and bf16[32,32768,32]): the scores, the weighted sums
  and the in-place writes of the new rows. `serve_latent_attend_ms_per_step`
  is the same reading for a pool of latent rows alone; its pattern asks for
  `num_hidden_layers` (8) or nothing in front of the slots and takes none
  of these 2 layers' operations (my traced run, PR 58), and it is not this
  PR's to edit.

Nothing where the configuration has no KDA layers: the experts' rows [.., 64,
2304, 2048], the router's vectors [.., 256] and JoyAI's pool [5, 32, 576,
16384] match no pattern.
"""
from __future__ import annotations

import re

from benchmark.kda_roofline import is_kda_chunk
from benchmark.program_spans import count_in, on_tpu


def patterns(cfg: dict, serving: dict):
    """{"state", "conv", "latent"}: compiled patterns, or None where the
    configuration has no KDA layers."""
    group = cfg.get("linear_attn_config") or {}
    depth = int(cfg.get("num_hidden_layers") or 0)
    n_kda = sum(1 for l in group.get("kda_layers", ()) if l <= depth)
    if not n_kda:
        return None
    heads, head_dim = int(group["num_heads"]), int(group["head_dim"])
    taps = int(group["short_conv_kernel_size"])
    n_mla = sum(1 for l in group.get("full_attn_layers", ()) if l <= depth)
    row = int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])
    positions, q_heads = int(serving["max_len"]), cfg["num_attention_heads"]
    rows = "|".join(str(b) for b in sorted(
        {1, int(serving["num_slots"]),
         *range(1, int(serving.get("prefill_max_batch", 1)) + 1)}))
    return {
        "state": re.compile(
            rf"f32\[(1,|{n_kda},)?({rows}),{heads},{head_dim},{head_dim}\]"),
        "conv": re.compile(
            rf"\[(1,|{n_kda},)?({rows}),{taps - 1},{3 * heads * head_dim}\]"),
        "latent": re.compile(
            rf"\[(1,|{n_mla},)?({rows}),{row},{positions}\]"
            rf"|\[{positions},\d+,{q_heads}\]"
            rf"|\[({rows}),{positions},{q_heads}\]"),
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
        and not is_kda_chunk(text))
    if not seconds:
        return None
    steps = count_in(run.trace, "mtpu/serve/step")
    return 1e3 * seconds / steps if steps else None
