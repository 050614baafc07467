"""serving/capabilities.py: what a pool may serve is decided once.

(a) every refused cell of the table is refused by `ServingConfig.validate`
    with the table's reason, and every other cell of a kind is served;
(b) an engine built WITHOUT `validate` refuses what the table refuses (at
    PR 44 it built the four newest kinds with prefix cache, speculation,
    preemption and adapter banks without a word);
(c) a block as large as the region is no block: `validate` and the engine
    give one answer (at PR 44 `validate` accepted what the engine refused);
(d) every serving cell of BENCHMARK.json is as legal as it was;
(e) docs/serving.md holds `capabilities.markdown()`.
"""

import dataclasses
import json
import os

import jax
import pytest

from megatron_tpu.config import MODEL_PRESETS, ServingConfig
from megatron_tpu.inference.generation import Generator
from megatron_tpu.models import language_model as lm
from megatron_tpu.serving import ServingEngine, SlotKVPool, capabilities
from megatron_tpu.serving.capabilities import FEATURES, REFUSED, ROWS

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _preset(name, **changes):
    return lambda: dataclasses.replace(MODEL_PRESETS[name](), **changes)


# a model a row, on which that row is the first to refuse
MODELS = {
    "regions": _preset("llama2-tiny"),
    "rolling": _preset("llama2-tiny", sliding_window=16,
                       attention_impl="flash"),
    "rolling, whole-region": _preset("llama2-tiny", sliding_window=16,
                                     attention_impl="flash"),
    "rings+regions": _preset("command-a-plus-tiny"),
    "latent": _preset("joyai-llm-flash-tiny"),
    "conv-state": _preset("lfm2-8b-a1b-tiny"),
    "latent+state": _preset("kimi-linear-tiny"),
    "streams": _preset("llama2-tiny", hc_mult=2),
    "sliding-window": _preset("llama2-tiny", sliding_window=16),
    "qk_norm": _preset("olmoe-tiny"),
    "dropless-experts": _preset("olmoe-tiny", qk_norm=False),
}
KINDS = ("regions", "rolling", "rings+regions", "latent", "conv-state",
         "latent+state")

# the options that turn a feature on
ON = {
    "enable_prefix_cache": dict(enable_prefix_cache=True),
    "retained_slots": dict(retained_slots=2),
    "preemption": dict(preemption=True, priority_levels=2),
    "speculative_k": dict(speculative_k=2),
    "prefill_chunk": dict(prefill_chunk=16),
    "kv_block_size": dict(kv_block_size=16),
    "block_native_attn": dict(block_native_attn=True),
    "serving_tp": dict(serving_tp=2),
    "prefill_tp": dict(prefill_tp=2),
    "decode_tp": dict(decode_tp=2),
    "serving_pp": dict(serving_pp=2),
    "disaggregate_prefill": dict(disaggregate_prefill=True),
    "host_kv_bytes": dict(host_kv_bytes=1 << 20),
    "adapter_slots": dict(adapter_slots=2),
    "kv_dtype int8": dict(kv_dtype="int8"),
}
BLOCKS = dict(kv_block_size=16, prefill_bucket=16)
# what a feature needs beside itself to be legal where it is served
# (`validate`'s option x option rules, and on a ROLLING pool the blocks that
# lift the refusal)
NEEDS = {
    "prefill_tp": dict(decode_tp=2),
    "decode_tp": dict(prefill_tp=2),
    "serving_pp": BLOCKS,
    "disaggregate_prefill": BLOCKS,
    "host_kv_bytes": dict(enable_prefix_cache=True, **BLOCKS),
    ("rolling", "enable_prefix_cache"): BLOCKS,
    ("rolling", "preemption"): BLOCKS,
    ("rolling", "host_kv_bytes"): dict(enable_prefix_cache=True, **BLOCKS),
}

REFUSED_CELLS = [(row, f) for row in REFUSED for f in REFUSED[row]]
SERVED_CELLS = [(kind, f) for kind in KINDS for f in FEATURES
                if f not in REFUSED[kind]]


def _serving(**options):
    return ServingConfig(num_slots=2, max_len=64, **options)


def test_the_table_names_what_it_is_read_by():
    assert set(ON) == set(FEATURES) and set(MODELS) == set(ROWS) \
        == set(REFUSED)
    assert all(set(REFUSED[row]) <= set(FEATURES) for row in REFUSED)
    for row, model in MODELS.items():
        rows = capabilities.rows_of(model(), 64, None)
        assert row in rows and rows[0] in KINDS, (row, rows)
        assert rows[0] == row or rows[0] in ("regions", "rolling"), rows


def test_the_refused_cells_are_the_parents():
    """The (kind or trait, feature) pairs PR 44's `validate` refused in its
    six blocks (config.py:949-1056, 1128-1141, 1150-1205, 1246-1253,
    1283-1292, 1339-1343), written out by hand."""
    cut = {"enable_prefix_cache", "retained_slots", "preemption",
           "speculative_k"}
    arena = {"kv_block_size", "block_native_attn", "host_kv_bytes",
             "disaggregate_prefill"}
    mesh = {"serving_tp", "prefill_tp", "decode_tp"}
    parent = {
        "regions": set(),
        "rolling": {"prefill_chunk", "speculative_k", "block_native_attn",
                    "disaggregate_prefill", "serving_pp"},
        "rolling, whole-region": {"enable_prefix_cache", "preemption"},
        "rings+regions": cut | arena | mesh | {
            "serving_pp", "adapter_slots", "kv_dtype int8"},
        "latent": arena | mesh | {"serving_pp", "adapter_slots",
                                  "kv_dtype int8"},
        "conv-state": cut | arena | mesh | {
            "serving_pp", "adapter_slots", "kv_dtype int8"},
        # PR 58's row: `conv-state`'s refusals over latent rows
        "latent+state": cut | arena | mesh | {
            "serving_pp", "adapter_slots", "kv_dtype int8"},
        "streams": mesh | {"serving_pp", "adapter_slots"},
        "sliding-window": {"block_native_attn", "serving_pp"},
        "qk_norm": mesh,
        "dropless-experts": mesh,
    }
    assert {row: set(cells) for row, cells in REFUSED.items()} == parent


@pytest.mark.parametrize("row, feature", REFUSED_CELLS)
def test_validate_refuses_the_cell_with_the_tables_reason(row, feature):
    model = MODELS[row]()
    with pytest.raises(AssertionError) as refused:
        _serving(**ON[feature]).validate(model)
    assert str(refused.value) == capabilities.refusal(row, feature, model)
    assert feature in str(refused.value)


@pytest.mark.parametrize("row, feature", SERVED_CELLS)
def test_validate_serves_every_other_cell_of_a_kind(row, feature):
    options = {**ON[feature], **NEEDS.get(feature, {}),
               **NEEDS.get((row, feature), {})}
    serving = _serving(**options)
    model = MODELS[row]()
    assert FEATURES[feature](serving)
    assert capabilities.refusals(serving, model) == []
    serving.validate(model)


def test_a_width_is_refused_as_given():
    """`serving_tp=2` that both phases' own widths override is refused where
    a width is, as the kinds' rows refused it at PR 44 (the `qk_norm` and
    dropless rows then read the widths in effect and let it pass: the one
    answer that changed beside those of (b) and (c))."""
    dead_width = _serving(serving_tp=2, prefill_tp=1, decode_tp=1)
    for row in ("rings+regions", "qk_norm"):
        with pytest.raises(AssertionError, match="serving_tp is refused"):
            dead_width.validate(MODELS[row]())
    dead_width.validate(MODELS["regions"]())


@pytest.mark.parametrize("feature", sorted(REFUSED["conv-state"]))
def test_the_conv_state_row_holds_for_a_delta_rule_beside_keys_and_values(
        feature):
    """Qwen3-Next (PR 60: `linear_attention` | `full_attention`, a matrix a
    value head beside keys and values in one `ConvKVCache`) is of the
    `conv-state` kind, no new one: the row's refusals, each with the row's
    own reason, and nothing else refused."""
    model = MODEL_PRESETS["qwen3-next-tiny"]()
    assert capabilities.pool_kind(model, 64) == "conv-state"
    cells = capabilities.refusals(_serving(**ON[feature]), model)
    assert ("conv-state", feature) in [(row, f) for row, f, _ in cells]
    lfm2 = capabilities.refusals(_serving(**ON[feature]),
                                 MODELS["conv-state"]())
    assert [m for r, f, m in cells if r == "conv-state"] == \
        [m for r, f, m in lfm2 if r == "conv-state"]
    with pytest.raises(AssertionError, match="a state of fixed size"):
        _serving(**ON[feature]).validate(model)
    _serving(prefill_chunk=16).validate(model)


@pytest.mark.parametrize("feature", sorted(REFUSED["streams"]))
def test_the_streams_refusals_hold_over_the_latent_pool(feature):
    """Xing4.0 is of both rows (and has dropless experts): the kind's reason
    comes first."""
    model = MODEL_PRESETS["xing4.0-29b-a4b-tiny"]()
    cells = capabilities.refusals(_serving(**ON[feature]), model)
    assert [(row, f) for row, f, _ in cells][:2] == [("latent", feature),
                                                     ("streams", feature)]
    with pytest.raises(AssertionError, match="latent pool"):
        _serving(**ON[feature]).validate(model)


ENGINE_MODELS = {
    "regions": MODELS["regions"],
    "rolling": MODELS["rolling"],
    "rings+regions": MODELS["rings+regions"],
    "conv-state": MODELS["conv-state"],
    "latent+state": MODELS["latent+state"],
    "latent": MODELS["latent"],
    "latent, streams": _preset("xing4.0-29b-a4b-tiny"),
}


@pytest.fixture(scope="module")
def generator():
    """A `Generator` over real weights a model, made once."""
    made = {}

    def of(kind):
        if kind not in made:
            cfg = ENGINE_MODELS[kind]()
            params = lm.model_init(jax.random.PRNGKey(0), cfg)
            made[kind] = Generator(params, cfg, eos_id=-1, pad_id=0)
        return made[kind]
    return of


@pytest.mark.parametrize("feature", ["enable_prefix_cache", "speculative_k",
                                     "preemption", "adapter_slots"])
@pytest.mark.parametrize("kind", sorted(set(ENGINE_MODELS) - {"regions"}))
def test_an_engine_built_without_validate_gives_validates_answer(
        generator, kind, feature):
    gen = generator(kind)
    serving = _serving(**ON[feature])
    cells = capabilities.refusals(serving, gen.cfg)
    if not cells:
        ServingEngine(gen, serving, start=False).close()
        return
    with pytest.raises(AssertionError) as refused:
        ServingEngine(gen, serving, start=False)
    assert str(refused.value) == cells[0][2]


@pytest.mark.parametrize("feature", ["host_kv_bytes", "disaggregate_prefill",
                                     "serving_pp"])
def test_a_block_as_large_as_the_region_is_no_block(generator, feature):
    """`kv_block_size >= max_len` on a pool that does not roll resolves to
    the whole-region layout, so what REQUIRES blocks is refused: by
    `validate` and, through it, by the engine."""
    gen = generator("regions")
    assert capabilities.resolved_block_size(gen.cfg, 64, 64) is None
    assert not SlotKVPool(gen.cfg, 2, 64, block_size=64).blocks_enabled
    serving = ServingConfig(
        max_len=64, kv_block_size=64, prefill_bucket=64,
        enable_prefix_cache=True, **ON[feature])
    match = feature.split("_kv")[0] + ".* requires .*kv_block_size"
    with pytest.raises(AssertionError, match=match):
        serving.validate(gen.cfg)
    with pytest.raises(AssertionError, match=match):
        ServingEngine(gen, serving, start=False)
    # on a ROLLING pool the one block a slot is a block pool
    roll = MODELS["rolling"]()
    assert capabilities.resolved_block_size(roll, 64, 64) == 16
    assert SlotKVPool(roll, 2, 64, block_size=64).block_size == 16


@pytest.mark.parametrize("kind", ["rings+regions", "latent", "conv-state",
                                  "latent+state"])
def test_a_pool_built_alone_refuses_blocks_in_the_tables_words(kind):
    model = MODELS[kind]()
    with pytest.raises(AssertionError) as refused:
        SlotKVPool(model, 2, 64, block_size=16)
    assert str(refused.value) == capabilities.refusal(
        kind, "kv_block_size", model)


def _serving_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for cell in bench["workloads"]:
        with open(os.path.join(ROOT, "benchmark", "traffic",
                               cell["traffic"] + ".json")) as f:
            mix = json.load(f)
        if "serving" in mix:
            yield pytest.param(files[cell["config"]], mix["serving"],
                               id=cell["name"])


@pytest.mark.parametrize("config_file, serving", list(_serving_cells()))
def test_every_serving_cell_of_the_benchmark_is_still_legal(
        config_file, serving):
    """The model as benchmark/fit.py builds it and the mix's own serving
    block: host data only, no weights."""
    from megatron_tpu.arguments import parse_cli
    with open(os.path.join(ROOT, config_file)) as f:
        config = json.load(f)
    model = parse_cli([*config["cli"], "--bf16"], n_devices=1)[0].model
    ServingConfig(**serving).validate(model)


def test_the_matrix_of_docs_serving_md_is_the_tables():
    with open(os.path.join(ROOT, "docs", "serving.md")) as f:
        doc = f.read()
    begin = "<!-- capabilities.markdown(): begin -->\n"
    end = "\n<!-- capabilities.markdown(): end -->"
    assert doc.count(begin) == 1 and doc.count(end) == 1
    held = doc[doc.index(begin) + len(begin):doc.index(end)]
    assert held == capabilities.markdown(), (
        "docs/serving.md's matrix is not the table's: paste "
        "`python -c 'from megatron_tpu.serving import capabilities as c; "
        "print(c.markdown())'` between the two markers")
