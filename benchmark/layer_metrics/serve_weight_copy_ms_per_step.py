"""Layer: models: ops/quantized.py wcast. Device time on the first device,
per `mtpu/serve/step` span of the traced window, of every operation that
WRITES an array of a whole weight's shape: the stack `[layers, a, b]`, a
layer of it `[1, a, b]` or `[a, b]`, for each matrix the layers cast on
their way into a product (`wq`, `wkv`, `wo`, `w1`, `w2`), and the embedding
table `[padded vocabulary, hidden]`. Those are the casts of a whole float32
stack to the compute dtype that the compiler lifts out of the layer loop
(`convert bf16[11,18176,4544]`), a layer's slice copied out of its stack or
into another order, and the copy of the table that the token gather reads;
decode and prefill programs together.

An event's text names its operands' shapes too, and a product that reads a
layer of the float32 stack where it lies has the stack among them: so the
shape is looked for in the operation's RESULT alone, which for a product is
rows of activations. The shapes come from the configuration: the parameter
tree the program's initialiser would build from the configuration's `cli`,
nothing run and no operation's name written down. `None` where the trace is
not a TPU's or holds no step span; 0.0 where it holds steps and no such
operation."""
import re

from benchmark.program_spans import count_in, on_tpu
from benchmark.trace import parse_op

CAST_IN_LAYER = ("wq", "wkv", "wo", "w1", "w2")


def weight_shapes(config) -> set:
    """The stacked shape of every matrix a layer casts, and the table's."""
    import jax
    from megatron_tpu.arguments import parse_cli
    from megatron_tpu.models import language_model as lm

    cfg, _ = parse_cli([*config["cli"], "--bf16"], n_devices=1)
    tree = jax.eval_shape(
        lambda: lm.model_init(jax.random.PRNGKey(0), cfg.model))
    shapes = {tuple(tree["embedding"]["word_embeddings"].shape)}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree["transformer"]):
        if getattr(path[-1], "key", None) in CAST_IN_LAYER:
            shapes.add(tuple(leaf.shape))
    return shapes


def writes_a_weight(shapes):
    """A predicate on an event's text: its result holds one of `shapes`,
    whole or, for a stacked one, a layer of it."""
    forms = set()
    for shape in shapes:
        dims = ",".join(map(str, shape))
        forms.add(re.escape(dims))
        if len(shape) > 2:                   # [layers, ...]: a layer of it
            layer = re.escape(",".join(map(str, shape[1:])))
            forms.update((layer, "1," + layer))
    holds = re.compile(r"\[(" + "|".join(sorted(forms)) + r")\]")
    return lambda text: bool(holds.search(parse_op(text)[2]))


def read(run):
    if not on_tpu(run.trace) or not run.ctx.traffic.get("serving"):
        return None
    steps = count_in(run.trace, "mtpu/serve/step")
    if not steps:
        return None
    seconds = run.trace.seconds_where(
        writes_a_weight(weight_shapes(run.ctx.config)))
    return 1e3 * seconds / steps
