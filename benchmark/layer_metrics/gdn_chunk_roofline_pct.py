"""Layer: kernels. The scalar-decay chunked delta rule's share of its
roofline over the traced window: for every `_gdn_chunk` kernel call on the
first device, the least time the chip could take for the rule's own
operations (6 T H d_k d_v) and the bytes it cannot avoid
(`benchmark/gdn_roofline.py`: shapes from the event's text, the decays one
a head a row, q and k of the key heads once; the peaks from `peaks.json`),
summed, over the sum of the measured durations. It leaves out the
triangular solve, K K^T and Q K^T and the exponentials, so it reads low by
nature and cannot pass 100. `None` where there is no such call or a call's
text does not hold the chunked rule's shapes."""
from benchmark.gdn_roofline import kernel_events, roofline_seconds


def read(run):
    events, peaks = kernel_events(run.trace), run.ctx.peaks
    if not events or not peaks:
        return None
    least = [roofline_seconds(text, peaks) for text, _ in events]
    if any(x is None for x in least):
        return None
    return 100.0 * sum(least) / sum(d for _, d in events)
