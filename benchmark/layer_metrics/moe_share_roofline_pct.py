"""Layer: kernels. The grouped product's share of its roofline where the
chip holds a SHARE of the layer's experts (`megatron_tpu/models/moe.py`;
`benchmark/moe_share_roofline.py` says what is counted and why the older two
roofline metrics would read far over 100 % here): over the
`_moe_grouped_matmul*` kernel calls on the first device,

    sum of max(2 m_held k n / peak FLOP/s, bytes / peak bytes/s)
    -------------------------------------------------------------
    sum of the measured durations

with m_held the rows whose expert is held here: the call's rows x the
driver's `checks.expert_load_window.held_row_share` (mean over the layers),
and for a call of a decode step's size the driver's own count of a grid's
held rows and of the held experts they touch. `None` where the
configuration's router is no wider than its banks (`published.num_experts`
absent or equal to `num_experts`), the driver counted nothing, or the trace
holds no such kernel (a parent commit)."""
from benchmark.moe_roofline import kernel_events, rows_of
from benchmark.moe_share_roofline import least_seconds


def read(run):
    cfg = run.ctx.config
    held = int(cfg.get("num_experts") or 0)
    routed = int((cfg.get("published") or {}).get("num_experts") or held)
    load = getattr(run, "checks", {}).get("expert_load_window") or {}
    share = load.get("held_row_share")
    events, peaks = kernel_events(run.trace), run.ctx.peaks
    if not events or not peaks or not share or routed <= held:
        return None
    mean = lambda xs: sum(xs) / len(xs)
    decode_rows = -(-run.ctx.traffic["serving"]["num_slots"]
                    * int(cfg["num_experts_per_tok"]) // 128) * 128
    pairs = []
    for text, d in events:
        decode = rows_of(text) == decode_rows
        least = least_seconds(
            text, peaks, mean(share), held,
            held_rows=mean(load["held_rows_per_decode_step"]) if decode
            else None,
            banks=mean(load["groups_hit_per_decode_step"]) if decode
            else None)
        if least is not None:
            pairs.append((least, d))
    if not pairs:
        return None
    return 100.0 * sum(least for least, _ in pairs) / sum(d for _, d in pairs)
