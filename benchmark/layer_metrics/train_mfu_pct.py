"""Layer: training loop and step. Model FLOP/s utilization: tokens per second
times the operations a token needs (forward and backward of the matrix
products and of causal attention, `benchmark/flops.py`; recomputation is not
credited) over chips times the chip's bf16 peak (`benchmark/peaks.json`)."""


def read(run):
    s, peaks = run.samples, run.ctx.peaks
    if not s.get("steps") or "train_flops_per_token" not in s or not peaks:
        return None
    tokens_per_s = s["steps"] * s["tokens_per_step"] / s["window_s"]
    return (100.0 * tokens_per_s * s["train_flops_per_token"]
            / (s["chips"] * peaks["bf16_flops_per_s"]))
