"""Layer: sampler and head. Device time on the first device, per
`mtpu/serve/step` span of the traced window, of every operation whose text
holds an array of the decode grid's logits, slots x vocabulary: the head's
product, the sampler's two sorts of that array in a step where a live
request filters by top-k or top-p (`megatron_tpu/inference/sampling.py::
_filter_rows`; a program from before that guard sorts in every step),
softmax, the categorical draw and the argmax, the chosen token's
log-probability, and a prefill's write of its rows' last logits into the
grid. The shape comes from the mix (`num_slots`) and the configuration
(`vocab_size`, padded as the program pads it: to a multiple of
`--make_vocab_size_divisible_by` where the configuration's `cli` names one,
else of the program's 128); no operation's name is written down. `None`
where the trace is not a TPU's, holds no step span, or no operation holds
such an array."""
import re

from benchmark.program_spans import count_in, on_tpu


def padded_vocab(cfg) -> int:
    cli = list(cfg.get("cli", ()))
    flag = "--make_vocab_size_divisible_by"
    multiple = int(cli[cli.index(flag) + 1]) if flag in cli else 128
    return multiple * -(-int(cfg["vocab_size"]) // multiple)


def read(run):
    cfg, serving = run.ctx.config, run.ctx.traffic.get("serving")
    if not on_tpu(run.trace) or not serving or not cfg.get("vocab_size"):
        return None
    holds = re.compile(
        rf"\[{int(serving['num_slots'])},{padded_vocab(cfg)}\]")
    seconds = run.trace.seconds_where(lambda text: bool(holds.search(text)))
    steps = count_in(run.trace, "mtpu/serve/step")
    if not seconds or not steps:
        return None
    return 1e3 * seconds / steps
