"""Layer: kernels. The grouped product's share of its roofline over the
traced window: for every `_moe_grouped_matmul*` kernel call on the first
device, the least time the chip could take for the rows it was given
(`benchmark/moe_roofline.py`: operations and bytes from the shapes in the
event's text, peaks from `peaks.json`), summed, over the sum of the measured
durations. Decode steps (bound by the bytes of the banks their rows touch)
and prefills (thousands of rows, bound by the products) are in it together,
weighted by the time they took. A decode step's banks are the experts the
driver saw hit when it routed a grid's worth of the window's own tokens
(`checks.expert_load_window.groups_hit_per_decode_step`, mean over the
layers); where the driver recorded none, every expert that could have a
row."""
from benchmark.moe_roofline import kernel_events, roofline_seconds, rows_of


def read(run):
    events, peaks = kernel_events(run.trace), run.ctx.peaks
    if not events or not peaks:
        return None
    load = getattr(run, "checks", {}).get("expert_load_window") or {}
    hit = load.get("groups_hit_per_decode_step")
    decode_banks = sum(hit) / len(hit) if hit else None
    # a decode step's rows: slots x experts a token, padded to the kernel's
    # row tile; the smallest prefill (one bucket) has more
    decode_rows = -(-run.ctx.traffic["serving"]["num_slots"]
                    * int(run.ctx.config["num_experts_per_tok"]) // 128) * 128
    least = [roofline_seconds(
        text, peaks, decode_banks if (rows_of(text) or 0) == decode_rows
        else None) for text, _ in events]
    if any(x is None for x in least):
        return None
    return 100.0 * sum(least) / sum(d for _, d in events)
