"""`ssd_roofline.py`'s count on hand-made event texts, and the two readers
of the chunked scan's kernel calls on a hand-made trace."""
import types

import pytest

from benchmark import ssd_roofline
from benchmark.by_name import load_module
from benchmark.trace import Trace

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def call(rows=2048, batch=1, heads=128, head_dim=64, groups=8, d_state=128,
         chunk=128, n=2, named_operands=False):
    """The kernel's event: operands with their shapes, or (the compiled
    module's own text) by name with the shapes among the layout
    constraints."""
    r = f"{batch},{rows},{heads * head_dim}"
    per = heads // groups
    shapes = [f"bf16[{r}]{{2,1,0}}",
              f"bf16[{batch},{rows},{groups * d_state}]{{2,1,0}}",
              f"bf16[{batch},{rows},{groups * d_state}]{{2,1,0}}",
              f"f32[{batch},{groups},{rows},{per}]{{3,2,1,0}}",
              f"f32[{batch},{groups},{rows},{per}]{{3,2,1,0}}",
              f"f32[{batch},{heads},{rows}]{{2,1,0}}",
              f"f32[{batch},{heads},{rows}]{{2,1,0}}",
              f"f32[{batch},{rows // chunk},{heads},{d_state}]{{3,2,1,0}}",
              f"f32[1,{heads * head_dim}]{{1,0}}",
              f"f32[{batch},{heads},{head_dim},{d_state}]{{3,2,1,0}}"]
    head = (f"%_ssd_chunk_scan.{n} = (bf16[{r}]{{2,1,0}}, "
            f"f32[{batch},{heads},{head_dim},{d_state}]{{3,2,1,0}}) "
            "custom-call(")
    if named_operands:
        return (head + ", ".join(f"%copy.{i}" for i in range(10))
                + '), custom_call_target="tpu_custom_call", '
                "operand_layout_constraints={" + ", ".join(shapes)
                + "}, frontend_attributes={}")
    return (head + ", ".join(f"{s} %op.{i}" for i, s in enumerate(shapes))
            + '), custom_call_target="tpu_custom_call", '
            "operand_layout_constraints={}")


def test_counts_of_a_chunked_scans_call():
    """2,048 rows of 128 heads of 64 over 8 groups of 128 states in chunks
    of 128: sixteen chunks of C B^T a group and three products a head; x in
    and y out at 2 B, B and C at 2 B, the step sizes once at 4 B, the state
    in and out, D once."""
    ops, nbytes = ssd_roofline.counts(call())
    q, n, p = 128, 128, 64
    assert ops == 16 * (8 * 2 * q * q * n
                        + 128 * (2 * q * q * p + 4 * q * p * n))
    assert nbytes == (2 * 2048 * 8192 * 2 + 2 * 2048 * 1024 * 2
                      + 2048 * 128 * 4 + 2 * 128 * 64 * 128 * 4 + 128 * 4)
    # the bytes decide on this chip: 85 MB at 819 GB/s against 13.4 GFLOP
    assert ssd_roofline.roofline_seconds(call(), PEAKS) == \
        pytest.approx(nbytes / 819e9)
    assert ssd_roofline.counts(call(named_operands=True)) == (ops, nbytes)
    # a bucket of 512 rows: a quarter of the rows' work, the state whole
    ops4, bytes4 = ssd_roofline.counts(call(rows=512))
    assert ops4 == ops / 4
    assert bytes4 == (nbytes - 2 * 128 * 64 * 128 * 4 - 512) / 4 \
        + 2 * 128 * 64 * 128 * 4 + 512


def test_only_the_chunked_scans_own_kernel_is_taken():
    assert ssd_roofline.is_chunk_scan(call())
    other = call().replace("_ssd_chunk_scan", "_ssm_selective_scan")
    assert not ssd_roofline.is_chunk_scan(other)
    fusion = "%fusion.7 = f32[1,128,64,128]{3,2,1,0} fusion(" \
        "f32[5,1,128,64,128]{4,3,2,1,0} %_ssd_chunk_scan.3)"
    assert not ssd_roofline.is_chunk_scan(fusion)
    # a text with the scan's name and not its shapes: nothing, never an error
    assert ssd_roofline.counts(
        "%_ssd_chunk_scan.1 = bf16[8,8]{1,0} custom-call(bf16[8,8]{1,0} "
        '%x), custom_call_target="tpu_custom_call"') is None


def _run(trace, config=None):
    ctx = types.SimpleNamespace(peaks=PEAKS, config=config or {},
                                traffic={"serving": {"num_slots": 64}})
    return types.SimpleNamespace(ctx=ctx, trace=trace, samples={}, checks={})


def test_ssd_scan_readers_on_a_hand_built_trace():
    least = ssd_roofline.roofline_seconds(call(), PEAKS)
    ops = [(call(n=1), 0.0, 4 * least), (call(n=2), 0.1, 6 * least),
           ("%fusion.4 = bf16[64,32768]{1,0} fusion(bf16[64,4096]{1,0} %x)",
            0.2, 0.5)]
    spans = [("mtpu/serve/step", 0.0, 0.3), ("mtpu/serve/step", 0.4, 0.2)]
    run = _run(Trace(kind="tpu", window_s=0.7, ops={0: ops}, spans=spans))
    read = lambda name: load_module("layer_metrics", name).read(run)  # noqa: E731
    assert read("ssd_scan_roofline_pct") == pytest.approx(20.0)
    assert read("serve_ssd_scan_ms_per_step") == \
        pytest.approx(1e3 * 10 * least / 2)


@pytest.mark.parametrize("name", [
    "ssd_scan_roofline_pct", "serve_ssd_scan_ms_per_step",
    "serve_ssd_state_ms_per_step", "serve_ssd_state_bytes_per_slot",
    "serve_moe_latent_ms_per_step", "serve_ssd_conv_state_ms_per_step",
    "serve_ssd_kv_attend_ms_per_step"])
def test_every_reader_of_pr_52_returns_none_where_it_has_nothing_to_read(name):
    """A `Run` of a program that lacks this PR's kernel, state and counter
    (the parent commit under this PR's benchmark files), of a CPU, of no
    trace at all: `None`, never an exception."""
    read = load_module("layer_metrics", name).read
    parent_ops = [("%fusion.4 = bf16[32,65536]{1,0} fusion(bf16[32,2560]"
                   "{1,0} %x)", 0.2, 0.5)]
    spans = [("mtpu/serve/step", 0.0, 0.3)]
    for trace in (Trace(kind="tpu", window_s=0.7, ops={0: parent_ops},
                        spans=spans),
                  Trace(kind="tpu", window_s=0.7, ops={0: []}, spans=[]),
                  Trace(kind="host-xla", window_s=1.0, ops={0: parent_ops},
                        spans=spans),
                  None):
        assert read(_run(trace)) is None
        assert read(_run(trace, {"num_hidden_layers": 28,
                                 "attn_layer_period": 14,
                                 "mamba_d_state": 16})) is None
