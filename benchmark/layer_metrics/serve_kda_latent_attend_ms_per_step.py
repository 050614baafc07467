"""Layer: models/mla.py. Device time on the first device, per
`mtpu/serve/step` span of the traced window, of every operation whose text
holds the latent rows of a pool that keeps them BESIDE a delta rule's state
(`benchmark/kda_kinds.py`, "latent": the MLA layers' rows, a layer, a slot
or a one-sequence cache of them, and the absorbed attention's weights over
the whole region): a chunk's and a decode step's scores and weighted sums
over all `max_len` positions whatever the offset, and the in-place writes
of the new rows. `serve_latent_attend_ms_per_step`'s reading for this pool,
whose layers in front that reader's pattern does not take. `None` where the
configuration has no KDA layers, the trace is not a TPU's, or no operation
holds such an array (a parent commit)."""
from benchmark.kda_kinds import ms_per_step


def read(run):
    return ms_per_step(run, "latent")
