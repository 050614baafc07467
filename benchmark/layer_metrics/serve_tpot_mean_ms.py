"""Layer: engine, decode side. Mean gap between a stream's tokens, weighted
by tokens: the decode seconds (finish - first token) of the requests that
finished in the window, summed, over their further tokens, summed. Every seed
offers the same lengths and arrivals, so this is nearly free of which request
met which; it stands beside the tail as the steadier reading of the decode
step as a client sees it."""


def read(run):
    gaps = run.samples.get("decode_gaps")
    if not gaps:
        return None
    return 1e3 * run.samples["decode_s"] / gaps
