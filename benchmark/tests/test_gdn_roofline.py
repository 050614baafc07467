"""`gdn_roofline.py`'s count on hand-made event texts, and the two readers
of the scalar-decay chunk kernel's calls on a hand-made trace."""
import types

import pytest

from benchmark import gdn_roofline
from benchmark.by_name import load_module
from benchmark.trace import Trace

GDN_PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def gdn_call(rows=4096, batch=1, key_heads=16, heads=32, d=128, hb=2, n=2,
             dtype="bf16", named_operands=False, fused_layers=None):
    """The kernel's event: operands with their shapes, or (the compiled
    module's own text) by name with the shapes among the layout
    constraints; `fused_layers`: the `kCustom` fusion with the write of the
    state into the cache stacked over layers."""
    by_key = f"{dtype}[{batch},{rows},{key_heads * d}]{{2,1,0}}"
    by_value = f"{dtype}[{batch},{rows},{heads * d}]{{2,1,0}}"
    state = f"f32[{batch},{heads},{d},{d}]{{3,2,1,0}}"
    column = f"f32[{batch},{heads // hb},{rows},{hb}]{{3,2,1,0}}"
    shapes = [by_key, by_key, by_value, column, column, state]
    if fused_layers:
        stacked = f"f32[{fused_layers},{batch},{heads},{d},{d}]{{4,3,2,1,0}}"
        return (f"%_gdn_chunk.{n} = ({by_value}, {stacked}) fusion("
                + ", ".join(f"{s} %op.{i}"
                            for i, s in enumerate(shapes + [stacked]))
                + "), kind=kCustom, calls=%fused")
    head = f"%_gdn_chunk.{n} = ({by_value}, {state}) custom-call("
    if named_operands:
        return (head + ", ".join(f"%copy.{i}" for i in range(6))
                + '), custom_call_target="tpu_custom_call", '
                "operand_layout_constraints={" + ", ".join(shapes)
                + "}, frontend_attributes={}")
    return (head + ", ".join(f"{s} %op.{i}" for i, s in enumerate(shapes))
            + '), custom_call_target="tpu_custom_call", '
            "operand_layout_constraints={}")


def test_counts_of_a_scalar_decay_call():
    """4,096 rows of 16 key heads under 32 value heads of 128 x 128: the
    rule's own 6 T H d_k d_v, as `kda_roofline.py` counts it; q and k of the
    KEY heads once, v in and o out at 2 B, the log-decays and beta once a
    row a HEAD at 4 B, the state in and out."""
    ops, nbytes = gdn_roofline.counts(gdn_call())
    assert ops == 6 * 4096 * 32 * 128 * 128 == 12_884_901_888
    assert nbytes == (2 * 4096 * 2048 * 2 + 2 * 4096 * 4096 * 2
                      + 2 * 4096 * 32 * 4 + 2 * 32 * 128 * 128 * 4)
    # the bytes decide on this chip: 0.106 GB at 819 GB/s against 12.9 GFLOP
    assert gdn_roofline.roofline_seconds(gdn_call(), GDN_PEAKS) == \
        pytest.approx(nbytes / 819e9)
    assert 0.1e-3 < nbytes / 819e9 < 0.15e-3
    assert gdn_roofline.counts(gdn_call(named_operands=True)) == (ops, nbytes)
    assert gdn_roofline.counts(gdn_call(fused_layers=6)) == (ops, nbytes)
    ops4, bytes4 = gdn_roofline.counts(gdn_call(rows=1024))
    assert ops4 == ops / 4
    assert bytes4 == (nbytes - 2 * 32 * 128 * 128 * 4) / 4 \
        + 2 * 32 * 128 * 128 * 4
    _, bytes32 = gdn_roofline.counts(gdn_call(dtype="f32"))
    assert bytes32 == nbytes + 2 * 4096 * 2048 * 2 + 2 * 4096 * 4096 * 2
    # whatever block of heads a kernel takes, and a key head a value head
    assert gdn_roofline.counts(gdn_call(hb=4)) == (ops, nbytes)
    same, wide = gdn_roofline.counts(gdn_call(key_heads=32))
    assert same == ops and wide == nbytes + 2 * 4096 * 2048 * 2


def test_what_is_no_scalar_decay_chunk():
    assert gdn_roofline.is_gdn_chunk(gdn_call())
    assert gdn_roofline.is_gdn_chunk(gdn_call(fused_layers=6))
    other = gdn_call().replace("_gdn_chunk", "_kda_chunk")
    assert not gdn_roofline.is_gdn_chunk(other)
    plain = ("%fusion.3 = f32[32,32,128,128]{3,2,1,0} fusion("
             "f32[6,32,32,128,128]{4,3,2,1,0} %pool), kind=kLoop")
    assert not gdn_roofline.is_gdn_chunk(plain)
    assert gdn_roofline.counts(
        '%_gdn_chunk.1 = bf16[8]{0} custom-call(bf16[8]{0} %x), '
        'custom_call_target="tpu_custom_call"') is None


def test_gdn_readers_on_a_hand_built_trace():
    ops = [(gdn_call(n=1), 0.0, 0.001), (gdn_call(rows=4096, n=2), 0.1, 0.003),
           ("%fusion.1 = bf16[1,4096,2048]{2,1,0} fusion()", 0.2, 0.5)]
    spans = [("mtpu/serve/step", 0.0, 0.3), ("mtpu/serve/step", 0.4, 0.2)]
    ctx = types.SimpleNamespace(peaks=GDN_PEAKS, config={}, traffic={})
    run = types.SimpleNamespace(
        ctx=ctx, samples={}, checks={},
        trace=Trace(kind="tpu", window_s=0.7, ops={0: ops}, spans=spans))
    read = lambda name: load_module("layer_metrics", name).read(run)  # noqa: E731
    assert read("serve_gdn_scan_ms_per_step") == pytest.approx(2.0)
    least = gdn_roofline.roofline_seconds(gdn_call(), GDN_PEAKS)
    assert read("gdn_chunk_roofline_pct") == pytest.approx(
        100 * 2 * least / 0.004)
    assert 0 < read("gdn_chunk_roofline_pct") < 100
    # the channel form's reader does not take these calls, nor this one its
    assert read("serve_kda_scan_ms_per_step") is None
    # a program with no such kernel (the parent commit): nothing, no error
    run.trace = Trace(kind="tpu", window_s=0.7, ops={0: ops[2:]}, spans=spans)
    assert read("serve_gdn_scan_ms_per_step") is None
    assert read("gdn_chunk_roofline_pct") is None
    run.trace = Trace(kind="cpu", window_s=0.7, ops={0: ops}, spans=spans)
    assert read("serve_gdn_scan_ms_per_step") is None
