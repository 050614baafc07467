"""Layer: engine, prefill side. Prefill programs dispatched per prompt
admitted in the window, by the engine's own counters at the window's two ends
(`serve_open_loop_xing.py` keeps them in its samples): (`prefill_chunks`, one
for each chunk of a prompt longer than `prefill_chunk`, + `prefill_prompts`,
one for each prompt that went in as ONE program, which `GenRequest.
prefill_chunks` counts as 1 too) / `requests_admitted`. 1 where nothing is
chunked; each chunk past a prompt's first is a program over the cache the
earlier ones left (MLA's absorbed form, `models/mla.py`). `None` where the
driver kept no such counters (another driver's cell, a parent commit) or no
prompt was admitted."""


def read(run):
    c = run.samples.get("window_engine_counters")
    if not c or not c.get("requests_admitted"):
        return None
    return ((c["prefill_chunks"] + c["prefill_prompts"])
            / c["requests_admitted"])
