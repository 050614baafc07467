"""Layer: kernels. The chunked scan's share of its roofline over the traced
window: for every `_ssd_chunk_scan` kernel call on the first device, the
least time the chip could take for the four products a chunk and the bytes
it cannot avoid (`benchmark/ssd_roofline.py`: shapes from the event's text,
the peaks from `peaks.json`), summed, over the sum of the measured
durations. It reads low where the vector and exponential units (the decays)
bound the kernel, and cannot pass 100. `None` where there is no such call or
a call's text does not hold a chunked scan's shapes."""
from benchmark.ssd_roofline import kernel_events, roofline_seconds


def read(run):
    events, peaks = kernel_events(run.trace), run.ctx.peaks
    if not events or not peaks:
        return None
    least = [roofline_seconds(text, peaks) for text, _ in events]
    if any(x is None for x in least):
        return None
    return 100.0 * sum(least) / sum(d for _, d in events)
