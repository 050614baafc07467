"""Layer: training loop and step. Window over steps, from the same two clock
readings as `train_tokens_per_s_per_chip`."""


def read(run):
    s = run.samples
    if not s.get("steps"):
        return None
    return 1e3 * s["window_s"] / s["steps"]
