"""What a pool may serve: one table of model kind x engine feature.

The decision is made here and nowhere else. `ServingConfig.validate` raises
the first of `refusals`, `ServingEngine.__init__` calls `validate`,
`SlotKVPool.__init__` looks `kv_block_size` up for its kind, and
docs/serving.md holds `markdown()` between two markers
(tests/test_capabilities.py compares them). A new kind of cache is one row
of `ROWS` and `REFUSED`; a lifted refusal is one deleted entry.

Pure host Python: no array is made and nothing is read but the two
configurations.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Callable, Dict, List, Optional, Tuple

from megatron_tpu.inference.generation import kv_region_cap


def pool_kind(model, max_len: int) -> str:
    """What a slot of this model's pool holds. A model is of one kind:
    `MegatronConfig.validate` refuses the crosses."""
    if model.window_layer_period:
        return "rings+regions"  # models/attention.py::HybridKVCache
    if model.mla and model.state_layers:
        # models/attention.py::LatentStateCache: latent rows for the
        # attention layers beside a state of fixed size a slot (the
        # depthwise kernels' last inputs and a matrix a head)
        return "latent+state"
    if model.mla:
        return "latent"  # models/mla.py::LatentKVCache
    if model.state_layers:
        # models/attention.py::ConvKVCache: a state of fixed size a slot (a
        # convolution's last inputs; a scan's matrix, or a matrix a head,
        # beside them)
        return "conv-state"
    return "rolling" if kv_region_cap(model, max_len) < max_len else "regions"


def model_traits(model, kind: str) -> List[str]:
    """What refuses features whatever the pool holds (`kind`: a window
    that rolls, or lies in rings, is its kind's to refuse)."""
    traits = []
    if model.hc_mult > 1:
        traits.append("streams")
    if model.sliding_window is not None and kind == "regions":
        traits.append("sliding-window")
    if model.qk_norm:
        traits.append("qk_norm")
    if model.num_experts > 1 and model.moe_dispatch == "dropless":
        traits.append("dropless-experts")
    return traits


def slot_cap(model, max_len: int) -> int:
    """A slot's capacity in positions: the window on a ROLLING pool, else
    `max_len` (rings beside whole regions have the regions')."""
    rolling = pool_kind(model, max_len) == "rolling"
    return kv_region_cap(model, max_len) if rolling else max_len


def resolved_block_size(model, max_len: int,
                        block_size: Optional[int]) -> Optional[int]:
    """The block size the pool will really have, which divides the slot's
    capacity (the window on a ROLLING pool). Blocks as large as the region
    ARE the regions (None) — except on a ROLLING pool, where the block pool
    is what makes retention possible at all (row-less entries and the trash
    map): there one block a slot is the legitimate degenerate case."""
    if block_size is None:
        return None
    cap = slot_cap(model, max_len)
    if block_size >= cap:
        return cap if cap < max_len else None
    assert cap % block_size == 0, (
        f"kv_block_size={block_size} must divide the slot capacity ({cap})")
    return block_size


# feature -> does this ServingConfig turn it on (the option as GIVEN:
# kv_block_size=999 on a pool of rings is refused, not resolved away)
FEATURES: Dict[str, Callable] = {
    "enable_prefix_cache": lambda s: s.enable_prefix_cache,
    "retained_slots": lambda s: s.retained_slots,
    "preemption": lambda s: s.preemption,
    "speculative_k": lambda s: s.speculative_k,
    "prefill_chunk": lambda s: s.prefill_chunk is not None,
    "kv_block_size": lambda s: s.kv_block_size is not None,
    "block_native_attn": lambda s: s.block_native_attn,
    "serving_tp": lambda s: s.serving_tp > 1,
    "prefill_tp": lambda s: (s.prefill_tp or 1) > 1,
    "decode_tp": lambda s: (s.decode_tp or 1) > 1,
    "serving_pp": lambda s: s.serving_pp > 1,
    "disaggregate_prefill": lambda s: s.disaggregate_prefill,
    "host_kv_bytes": lambda s: s.host_kv_bytes,
    "adapter_slots": lambda s: s.adapter_slots,
    "kv_dtype int8": lambda s: s.kv_dtype == "int8",
}

_MESH = ("serving_tp", "prefill_tp", "decode_tp")

# row -> (what the model has, formatted with m=model; where the feature is
# refused; the ROADMAP item that would lift it). The kinds first, then the
# traits: `refusals` reports in this order.
ROWS: Dict[str, Tuple[str, str, str]] = {
    "regions": ("one region of keys and values a slot", "", ""),
    "rolling": (
        "sliding_window={m.sliding_window} under attention_impl='flash' "
        "(the region holds the last sliding_window positions, ring-ordered)",
        " on ROLLING (sliding-window) KV pools (with or without "
        "kv_block_size)", ""),
    "rolling, whole-region": (
        "sliding_window={m.sliding_window} under attention_impl='flash' "
        "without kv_block_size",
        " on a whole-region ROLLING (sliding-window) KV pool", ""),
    "rings+regions": (
        "window_layer_period={m.window_layer_period} (window and full "
        "attention in one stack)",
        " on the pool of rings and whole regions", " (ROADMAP R3)"),
    "latent": ("MLA (kv_lora_rank set)", " on the latent pool",
               " (ROADMAP R5)"),
    "conv-state": (
        "layer_types with a state of fixed size a slot, mamba, mamba2, "
        "linear_attention or conv layers",
        " on the pool of keys, values and a state of fixed size",
        " (ROADMAP R6)"),
    "latent+state": (
        "MLA (kv_lora_rank set) beside layer_types with a state of fixed "
        "size a slot, kda layers",
        " on the pool of latent rows and a state of fixed size",
        " (ROADMAP R5, R6)"),
    "streams": ("hc_mult={m.hc_mult} (hyper-connections)",
                " under a residual of streams", ""),
    "sliding-window": (
        "sliding_window={m.sliding_window} (a window that does not roll)",
        " on sliding-window models", ""),
    "qk_norm": ("qk_norm (one norm over all heads)",
                " (serve this model at width 1)", ""),
    "dropless-experts": ("moe_dispatch='dropless'",
                         " (serve this model at width 1)", ""),
}

_CUT = ("enable_prefix_cache", "retained_slots", "preemption",
        "speculative_k")
_ARENA = ("kv_block_size", "block_native_attn", "host_kv_bytes",
          "disaggregate_prefill")

# row -> (the features one reason refuses, the reason), in the order
# `refusals` reports them. What is not here is served.
_WHY: Dict[str, List[Tuple[Tuple[str, ...], str]]] = {
    "regions": [],
    "rolling": [
        (("prefill_chunk",),
         "an offset>0 chunk's ring writes evict history its own queries "
         "still need within one dispatch; rolling prefix-hit suffixes append "
         "single-token steps instead"),
        (("speculative_k",),
         "the verify window's ring writes evict history as they land, so "
         "rewinding to the accepted length cannot restore what a rejected "
         "draft overwrote (the write-before-read rewind invariant breaks)"),
        (("block_native_attn",),
         "the ring's slot->position map breaks the kernel's contiguous "
         "position arithmetic, and the kernel has no window-band mask: "
         "sliding-window pools keep the resolve_view/scatter_view bracket"),
        (("disaggregate_prefill",),
         "the ring's exact-length block handoff is not defined: serve "
         "rolling models single-group (chunk-interleave fallback)"),
        (("serving_pp",),
         "the rolling ring's per-layer offset arithmetic does not survive "
         "the staged arena partition"),
    ],
    # what a block pool (kv_block_size) lifts: retained ring blocks hold no
    # grid row, so idle writes land in the shared trash block
    "rolling, whole-region": [
        (("enable_prefix_cache",),
         "it requires the block-granular pool (--kv_block_size, dividing "
         "the window): a retained whole-region ring row still rides the "
         "decode grid and its idle writes wrap into the live ring"),
        (("preemption",),
         "it requires the block-granular pool (--kv_block_size): "
         "whole-region rolling rows cannot park/resume without their idle "
         "ring writes clobbering retained state"),
    ],
    "rings+regions": [
        (_CUT,
         "a ring keeps the last sliding_window rows only, so a retained, "
         "parked, cloned or rewound slot has lost the rows it would need"),
        (_ARENA, "the block arena and its kernel know one region shape"),
        ((*_MESH, "serving_pp", "adapter_slots"),
         "the two stacks have no stage cut, head shard or adapter bank"),
        (("kv_dtype int8",), "the cache of rings and regions has no scales"),
    ],
    "latent": [
        ((*_MESH, "serving_pp"),
         "the latent row has no head axis to shard over a serving mesh and "
         "the two stacks have no stage cut"),
        (("kv_block_size", "block_native_attn"),
         "the block arena (kv_block_size) and its kernel (block_native_attn) "
         "are built round k and v of [kv_heads, head_dim]"),
        (("kv_dtype int8",),
         "the per-(token, head) scales have no head to belong to, and a "
         "scale a latent row has not been tried against the reference"),
        (("disaggregate_prefill", "host_kv_bytes"),
         "disaggregate_prefill and the host tier (host_kv_bytes) move "
         "physical KV blocks, which the latent pool does not have"),
        (("adapter_slots",),
         "the LoRA bank holds factors for wq / wkv / wo, which this "
         "attention does not have"),
    ],
    "conv-state": [
        (("enable_prefix_cache", "retained_slots", "speculative_k"),
         "a state is the last two inputs at the slot's CURRENT length: "
         "cutting, cloning or rewinding a slot to a shorter one needs a "
         "snapshot of the state taken at the cut"),
        (("preemption",),
         "a parked slot's state has to be read out and put back with its "
         "rows: kv_pool.slice_slot cuts keys and values alone"),
        (_ARENA,
         "the block arena, which the host tier and the handoff move by "
         "blocks, has no row for a state"),
        (("serving_pp",),
         "the stages cut ONE stack of identical layers and the arena by "
         "layer; the kinds are stacked apart"),
        (_MESH, "the state and the depthwise kernel need a channel shard"),
        (("adapter_slots",),
         "the adapter bank is stacked over one kind of layer"),
        (("kv_dtype int8",), "the cache of two kinds of state has no scales"),
    ],
    # `conv-state`'s refusals and reasons, over latent rows
    "latent+state": [
        (("enable_prefix_cache", "retained_slots", "speculative_k"),
         "a state is the one at the slot's CURRENT length (the depthwise "
         "kernels' last three inputs and a matrix a head): cutting, cloning "
         "or rewinding a slot to a shorter one needs a snapshot of the "
         "state taken at the cut"),
        (("preemption",),
         "a parked slot's state has to be read out and put back with its "
         "rows: kv_pool.slice_slot cuts latent rows alone"),
        (_ARENA,
         "the block arena, which the host tier and the handoff move by "
         "blocks, is built round k and v of [kv_heads, head_dim] and has no "
         "row for a state"),
        (("serving_pp",),
         "the stages cut ONE stack of identical layers and the arena by "
         "layer; the kinds are stacked apart"),
        (_MESH,
         "the latent row has no head axis to shard, and the state and the "
         "depthwise kernels need a head shard"),
        (("adapter_slots",),
         "the LoRA bank holds factors for wq / wkv / wo, which neither "
         "mixer has"),
        (("kv_dtype int8",),
         "the cache of latent rows and two kinds of state has no scales"),
    ],
    "streams": [
        (("adapter_slots",),
         "the adapter scan (LoRA adapter banks) has not been run under a "
         "residual of streams"),
        ((*_MESH, "serving_pp"),
         "a serving mesh (serving_tp / prefill_tp / decode_tp / serving_pp "
         "> 1) has not been run under a residual of streams: a stage's "
         "`layer_offset` would carry the streams over a stage boundary"),
    ],
    "sliding-window": [
        (("block_native_attn",),
         "the block kernel has no window-band mask: sliding-window pools "
         "keep the resolve_view/scatter_view bracket"),
        (("serving_pp",),
         "a window's per-layer offset arithmetic has not been run over the "
         "staged arena partition"),
    ],
    "qk_norm": [
        (_MESH, "serving widths > 1 have not been made to work with qk_norm "
                "(a norm across sharded heads)")],
    "dropless-experts": [
        (_MESH, "serving widths > 1 have not been made to work with "
                "moe_dispatch='dropless' (one unpartitioned grouped "
                "product)")],
}

# row -> feature -> why it is refused there
REFUSED: Dict[str, Dict[str, str]] = {
    row: {f: why for features, why in groups for f in features}
    for row, groups in _WHY.items()}


def rows_of(model, max_len: int, block_size: Optional[int]) -> List[str]:
    """The rows of `REFUSED` that hold for this model and this layout."""
    kind = pool_kind(model, max_len)
    rows = [kind]
    if kind == "rolling" and block_size is None:
        rows.append("rolling, whole-region")
    return rows + model_traits(model, kind)


def refusal(row: str, feature: str, model) -> str:
    """The message of one refused cell."""
    has, where, item = ROWS[row]
    return (f"{has.format(m=model)}: {feature} is refused{where}: "
            f"{REFUSED[row][feature]}{item}")


def refusals(serving, model) -> List[Tuple[str, str, str]]:
    """(row, feature, message) for every cell that refuses `serving` on
    `model`, the kind's before the traits'."""
    max_len = serving.max_len or model.max_position_embeddings
    return [(row, feature, refusal(row, feature, model))
            for row in rows_of(model, max_len, serving.kv_block_size)
            for feature in REFUSED[row] if FEATURES[feature](serving)]


def markdown() -> str:
    """The matrix of docs/serving.md: a row a kind or trait, a column a
    feature, a cell a ✓ or why it is refused (a reason several cells of a
    row share is written in the first)."""
    # `ROWS`' labels name a model's own numbers; the matrix says N
    any_model = SimpleNamespace(sliding_window="N", window_layer_period="N",
                                hc_mult="N")
    lines = ["| pool or trait | " + " | ".join(
        f"`{f}`" for f in FEATURES) + " |",
        "|---" * (len(FEATURES) + 1) + "|"]
    for row, (has, _, item) in ROWS.items():
        first, cells = {}, []
        for f in FEATURES:
            why = REFUSED[row].get(f)
            cells.append("✓" if why is None else f"✗ {why}"
                         if first.setdefault(why, f) == f
                         else f"✗ as `{first[why]}`")
        lines.append(f"| **{row}**{item}: {has.format(m=any_model)} | "
                     + " | ".join(cells) + " |")
    return "\n".join(lines)
