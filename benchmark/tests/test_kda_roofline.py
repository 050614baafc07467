"""`kda_roofline.py`'s count on hand-made event texts, and the two readers
of the chunked delta rule's kernel calls on a hand-made trace."""
import types

import pytest

from benchmark import kda_roofline
from benchmark.by_name import load_module
from benchmark.trace import Trace

KDA_PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def kda_call(rows=4096, batch=1, heads=32, d=128, hb=2, n=2, dtype="bf16",
             named_operands=False, fused_layers=None):
    """The kernel's event: operands with their shapes, or (the compiled
    module's own text) by name with the shapes among the layout
    constraints; `fused_layers`: the `kCustom` fusion with the write of the
    state into the cache stacked over layers."""
    r = f"{batch},{rows},{heads * d}"
    state = f"f32[{batch},{heads},{d},{d}]{{3,2,1,0}}"
    shapes = [f"{dtype}[{r}]{{2,1,0}}"] * 3 + [
        f"f32[{r}]{{2,1,0}}",
        f"f32[{batch},{heads // hb},{rows},{hb}]{{3,2,1,0}}", state]
    if fused_layers:
        stacked = f"f32[{fused_layers},{batch},{heads},{d},{d}]{{4,3,2,1,0}}"
        return (f"%_kda_chunk.{n} = ({dtype}[{r}]{{2,1,0}}, {stacked}) "
                "fusion(" + ", ".join(
                    f"{s} %op.{i}" for i, s in enumerate(shapes + [stacked]))
                + "), kind=kCustom, calls=%fused")
    head = f"%_kda_chunk.{n} = ({dtype}[{r}]{{2,1,0}}, {state}) custom-call("
    if named_operands:
        return (head + ", ".join(f"%copy.{i}" for i in range(6))
                + '), custom_call_target="tpu_custom_call", '
                "operand_layout_constraints={" + ", ".join(shapes)
                + "}, frontend_attributes={}")
    return (head + ", ".join(f"{s} %op.{i}" for i, s in enumerate(shapes))
            + '), custom_call_target="tpu_custom_call", '
            "operand_layout_constraints={}")


def test_counts_of_a_chunked_rules_call():
    """4,096 rows of 32 heads of 128 x 128: the rule's own 6 T H d_k d_v;
    q, k, v in and o out at 2 B, the log-decays once a row a channel and
    beta once a row a head at 4 B, the state in and out."""
    ops, nbytes = kda_roofline.counts(kda_call())
    assert ops == 6 * 4096 * 32 * 128 * 128 == 12_884_901_888
    assert nbytes == (4 * 4096 * 4096 * 2 + 4096 * 4096 * 4 + 4096 * 32 * 4
                      + 2 * 32 * 128 * 128 * 4)
    # the bytes decide on this chip: 0.21 GB at 819 GB/s against 12.9 GFLOP
    assert kda_roofline.roofline_seconds(kda_call(), KDA_PEAKS) == \
        pytest.approx(nbytes / 819e9)
    assert 0.2e-3 < nbytes / 819e9 < 0.3e-3
    assert kda_roofline.counts(kda_call(named_operands=True)) == (ops, nbytes)
    assert kda_roofline.counts(kda_call(fused_layers=6)) == (ops, nbytes)
    # a bucket of 1,024 rows: a quarter of the rows' work, the state whole
    ops4, bytes4 = kda_roofline.counts(kda_call(rows=1024))
    assert ops4 == ops / 4
    assert bytes4 == (nbytes - 2 * 32 * 128 * 128 * 4) / 4 \
        + 2 * 32 * 128 * 128 * 4
    # float32 rows (a test's): four bytes a value
    _, bytes32 = kda_roofline.counts(kda_call(dtype="f32"))
    assert bytes32 == nbytes + 4 * 4096 * 4096 * 2
    # whatever chunk or block of heads a kernel takes, the same work
    assert kda_roofline.counts(kda_call(hb=4)) == (ops, nbytes)


def test_what_is_no_chunked_rule():
    assert kda_roofline.is_kda_chunk(kda_call())
    assert kda_roofline.is_kda_chunk(kda_call(fused_layers=6))
    other = kda_call().replace("_kda_chunk", "_ssd_chunk_scan")
    assert not kda_roofline.is_kda_chunk(other)
    plain = ("%fusion.3 = f32[32,32,128,128]{3,2,1,0} fusion("
             "f32[6,32,32,128,128]{4,3,2,1,0} %pool), kind=kLoop")
    assert not kda_roofline.is_kda_chunk(plain)
    assert kda_roofline.counts(
        '%_kda_chunk.1 = bf16[8]{0} custom-call(bf16[8]{0} %x), '
        'custom_call_target="tpu_custom_call"') is None


def test_kda_readers_on_a_hand_built_trace():
    ops = [(kda_call(n=1), 0.0, 0.001), (kda_call(rows=4096, n=2), 0.1, 0.003),
           ("%fusion.1 = bf16[1,4096,2304]{2,1,0} fusion()", 0.2, 0.5)]
    spans = [("mtpu/serve/step", 0.0, 0.3), ("mtpu/serve/step", 0.4, 0.2)]
    ctx = types.SimpleNamespace(peaks=KDA_PEAKS, config={}, traffic={})
    run = types.SimpleNamespace(
        ctx=ctx, samples={}, checks={},
        trace=Trace(kind="tpu", window_s=0.7, ops={0: ops}, spans=spans))
    read = lambda name: load_module("layer_metrics", name).read(run)  # noqa: E731
    assert read("serve_kda_scan_ms_per_step") == pytest.approx(2.0)
    least = kda_roofline.roofline_seconds(kda_call(), KDA_PEAKS)
    assert read("kda_chunk_roofline_pct") == pytest.approx(
        100 * 2 * least / 0.004)
    assert 0 < read("kda_chunk_roofline_pct") < 100
    # a program with no such kernel (the parent commit): nothing, no error
    run.trace = Trace(kind="tpu", window_s=0.7, ops={0: ops[2:]}, spans=spans)
    assert read("serve_kda_scan_ms_per_step") is None
    assert read("kda_chunk_roofline_pct") is None
    run.trace = Trace(kind="cpu", window_s=0.7, ops={0: ops}, spans=spans)
    assert read("serve_kda_scan_ms_per_step") is None
