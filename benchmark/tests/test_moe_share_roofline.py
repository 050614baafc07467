"""`moe_share_roofline.py`'s counts on hand-made event texts, the share's
reader and the two readers of `kv_kinds.py` on hand-made traces."""
import types

import pytest

from benchmark import kv_kinds, moe_share_roofline as ms
from benchmark.by_name import load_module
from benchmark.trace import Trace

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
META = ("s32[]{:T(128)} %g.1, s32[17]{0:T(128)S(1)} %c.3, s32[17]{0:T(128)} "
        "%c.5, s32[17]{0:T(128)} %c.4, s32[1]{0:T(128)} %constant.97, ")
TAIL = '), custom_call_target="tpu_custom_call", operand_layout_constraints={}'


def forward(m, k, n, banks=64, name="_moe_grouped_matmul.3"):
    return (f"%{name} = bf16[{m},{n}]{{1,0:T(8,128)(2,1)}} custom-call({META}"
            f"bf16[{m},{k}]{{1,0:T(8,128)(2,1)}} %rows, "
            f"bf16[{banks},{k},{n}]{{2,1,0:T(8,128)(2,1)}} %bank{TAIL}")


def test_a_chunk_is_credited_with_the_held_rows_alone():
    """32,768 rows given, an eighth of them held: the products of 4,096 rows,
    their bytes, and all 16 banks. (At an eighth the 16 banks' gigabyte takes
    as long to read as 4,096 rows take to multiply: 1.43 against 1.40 ms.)"""
    ops, byts = ms.counts(forward(32768, 4096, 8192), 0.125, 16)
    assert ops == 2 * 4096 * 4096 * 8192
    assert byts == 4096 * (4096 + 8192) * 2 + 16 * 4096 * 8192 * 2
    # what the older count would credit is eight times the products
    from benchmark.moe_roofline import counts as whole
    assert whole(forward(32768, 4096, 8192))[0] == 8 * ops
    assert ms.least_seconds(forward(32768, 4096, 8192), PEAKS, 0.125, 16) \
        == pytest.approx(max(ops / 197e12, byts / 819e9))
    assert ms.least_seconds(forward(32768, 4096, 8192), PEAKS, 0.5, 16) \
        == pytest.approx(2 * 16384 * 4096 * 8192 / 197e12)


def test_a_decode_step_is_bound_by_the_banks_it_touches():
    text = forward(128, 4096, 8192)
    ops, byts = ms.counts(text, 0.125, 16, held_rows=15.5, banks=10.2)
    assert ops == 2 * 15.5 * 4096 * 8192
    assert byts == 15.5 * (4096 + 8192) * 2 + 10.2 * 4096 * 8192 * 2
    assert ms.least_seconds(text, PEAKS, 0.125, 16, 15.5, 10.2) == \
        pytest.approx(byts / 819e9)


@pytest.mark.parametrize("held_rows,banks", [(1e9, 1e9), (200.0, 64.0),
                                             (3.0, 16.0)])
def test_never_more_than_was_given(held_rows, banks):
    """Rows above the call's own, banks above the experts held or above the
    held rows: each is cut."""
    m, e = 128, 16
    ops, byts = ms.counts(forward(m, 4096, 8192), 1.0, e, held_rows, banks)
    rows = min(held_rows, m)
    assert ops == 2 * rows * 4096 * 8192
    assert byts == rows * (4096 + 8192) * 2 \
        + min(banks, e, rows) * 4096 * 8192 * 2
    assert ms.counts("%fusion.1 = bf16[8,8]{1,0} fusion()", 0.1, 16) is None


def _run(events, checks, config=None):
    ops = [(text, 0.1 * i, d) for i, (text, d) in enumerate(events)]
    trace = Trace(kind="tpu", window_s=1.0, ops={0: ops},
                  spans=[("mtpu/serve/step", 0.0, 0.5),
                         ("mtpu/serve/step", 0.5, 0.5)])
    ctx = types.SimpleNamespace(
        peaks=PEAKS, config=config or {
            "num_experts": 16, "published": {"num_experts": 128},
            "num_experts_per_tok": 8},
        traffic={"serving": {"num_slots": 16}})
    return types.SimpleNamespace(ctx=ctx, trace=trace, checks=checks)


def test_share_reader():
    read = load_module("layer_metrics", "moe_share_roofline_pct").read
    load = {"held_row_share": [0.125, 0.125],
            "held_rows_per_decode_step": [16.0, 16.0],
            "groups_hit_per_decode_step": [10.0, 10.0]}
    chunk, step = forward(32768, 4096, 8192), forward(128, 4096, 8192)
    least_chunk = (4096 * (4096 + 8192) * 2 + 16 * 4096 * 8192 * 2) / 819e9
    least_step = (16 * (4096 + 8192) * 2 + 10 * 4096 * 8192 * 2) / 819e9
    run = _run([(chunk, 2 * least_chunk), (step, 4 * least_step)],
               {"expert_load_window": load})
    assert read(run) == pytest.approx(
        100 * (least_chunk + least_step) / (2 * least_chunk + 4 * least_step))
    # a kernel as fast as the count allows reads 100, never more
    run = _run([(chunk, least_chunk), (step, least_step)],
               {"expert_load_window": load})
    assert read(run) == pytest.approx(100.0)
    # nothing where the driver counted nothing, the banks are the whole
    # layer's, or the trace has no such kernel
    assert read(_run([(chunk, 1.0)], {})) is None
    assert read(_run([(chunk, 1.0)], {"expert_load_window": load},
                     {"num_experts": 64, "num_experts_per_tok": 8})) is None
    assert read(_run([("%fusion.2 = f32[8]{0} fusion()", 1.0)],
                     {"expert_load_window": load})) is None


CFG = {"layer_types": ["sliding_attention"] * 3 + ["full_attention"],
       "sliding_window": 4096, "num_hidden_layers": 4,
       "num_key_value_heads": 8, "head_dim": 128}
SERVING = {"num_slots": 16, "max_len": 32768, "prefill_bucket": 1024,
           "prefill_chunk": 4096}


def test_kinds_are_told_apart_by_shape():
    window, full = kv_kinds.patterns(CFG, SERVING)
    rings = ["bf16[3,16,8,4096,128]{4,3,2,1,0}", "bf16[16,8,4096,128]{3,2,1,0}",
             "bf16[3,1,8,4096,128]{4,3,2,1,0}", "bf16[1,8,4096,128]{3,2,1,0}",
             "bf16[1,8,8192,128]{3,2,1,0}", "bf16[1,8,5120,128]{3,2,1,0}"]
    regions = ["bf16[1,16,8,32768,128]{4,3,2,1,0}",
               "bf16[16,8,32768,128]{3,2,1,0}",
               "bf16[1,1,8,32768,128]{4,3,2,1,0}",
               "bf16[1,8,32768,128]{3,2,1,0}"]
    other = ["bf16[16,32768]{1,0}", "bf16[1,128,4096,128]{3,2,1,0}",
             "bf16[1,8,4608,128]{3,2,1,0}", "bf16[64,4096,8192]{2,1,0}"]
    for text in rings:
        assert window.search(text) and not full.search(text), text
    for text in regions:
        assert full.search(text) and not window.search(text), text
    for text in other:
        assert not window.search(text) and not full.search(text), text
    # one kind of layer, or rings as long as regions: nothing to tell
    assert kv_kinds.patterns({"num_hidden_layers": 4}, SERVING) is None
    assert kv_kinds.patterns(CFG, dict(SERVING, max_len=4096)) is None


def test_attend_readers_on_a_hand_built_trace():
    ops = [("%fusion.1 = bf16[16,128,128]{2,1,0} fusion(bf16[3,16,8,4096,128]"
            "{4,3,2,1,0} %ring_k)", 0.0, 0.010),
           ("%_flash_attention_offset.2 = bf16[1,128,4096,128]{3,2,1,0} "
            "custom-call(bf16[1,8,8192,128]{3,2,1,0} %keys)", 0.011, 0.030),
           ("%_flash_attention_offset.3 = bf16[1,128,4096,128]{3,2,1,0} "
            "custom-call(bf16[1,8,32768,128]{3,2,1,0} %keys)", 0.042, 0.070),
           ("%fusion.4 = bf16[16,8,16,32768]{3,2,1,0} fusion("
            "bf16[1,16,8,32768,128]{4,3,2,1,0} %full_k)", 0.113, 0.020),
           ("%fusion.5 = bf16[16,4096]{1,0} fusion(bf16[16,4096]{1,0} %x)",
            0.134, 0.500)]
    spans = [("mtpu/serve/step", 0.001, 0.3), ("mtpu/serve/step", 0.4, 0.2)]
    trace = Trace(kind="tpu", window_s=0.7, ops={0: ops}, spans=spans)
    ctx = types.SimpleNamespace(config=CFG, traffic={"serving": SERVING})
    run = types.SimpleNamespace(ctx=ctx, trace=trace)
    window = load_module("layer_metrics", "serve_window_attend_ms_per_step")
    full = load_module("layer_metrics", "serve_full_attend_ms_per_step")
    assert window.read(run) == pytest.approx(1e3 * 0.040 / 2)
    assert full.read(run) == pytest.approx(1e3 * 0.090 / 2)
    run.trace = Trace(kind="host-xla", window_s=1.0, ops={0: ops}, spans=spans)
    assert window.read(run) is None and full.read(run) is None
