"""Layer: models/mla.py. Device time on the first device, per
`mtpu/serve/step` span of the traced window, of every operation whose text
holds an array of the latent pool's shape: slots x max_len x row, in that
order or as the program holds it, slots x row x max_len (positions minor:
`megatron_tpu/models/mla.py::LatentKVCache` says why), with or without the
layers' axis in front. Those are the absorbed attention's two
reads of a layer of the pool (the scores and the weighted sum, or one fusion
of both) and the in-place writes of the new rows, in decode and prefill
programs alike. The shape comes from the configuration (`kv_lora_rank` +
`qk_rope_head_dim`, `num_hidden_layers`) and the mix (`num_slots`,
`max_len`); no operation's name is written down. `None` where the
configuration has no latent row, the trace is not a TPU's, or no operation
holds such an array (a parent commit)."""
import re

from benchmark.program_spans import count_in, on_tpu


def read(run):
    cfg, serving = run.ctx.config, run.ctx.traffic["serving"]
    if not on_tpu(run.trace) or not cfg.get("kv_lora_rank"):
        return None
    row = int(cfg["kv_lora_rank"]) + int(cfg["qk_rope_head_dim"])
    slots, positions = serving["num_slots"], serving["max_len"]
    holds = re.compile(
        rf"\[({int(cfg['num_hidden_layers'])},)?{slots},"
        rf"({positions},{row}|{row},{positions})\]")
    seconds = run.trace.seconds_where(lambda text: bool(holds.search(text)))
    steps = count_in(run.trace, "mtpu/serve/step")
    if not seconds or not steps:
        return None
    return 1e3 * seconds / steps
