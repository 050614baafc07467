"""Layer: engine, decode side. Tokens generated per decode step over the
window, from the engine's own counters: how full the slot grid ran."""


def read(run):
    c = run.samples.get("counters")
    if not c or not c.get("decode_steps"):
        return None
    return c["tokens_generated"] / c["decode_steps"]
