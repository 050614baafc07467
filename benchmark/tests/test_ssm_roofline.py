"""`ssm_roofline.py`'s count on hand-made event texts, and the two readers
of the scan kernel's calls on a hand-made trace."""
import types

import pytest

from benchmark import ssm_roofline
from benchmark.by_name import load_module
from benchmark.trace import Trace

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def call(rows=2048, batch=1, d_inner=5120, d_state=16, n=3):
    r = f"{batch},{rows},{d_inner}"
    return (f"%_ssm_selective_scan.{n} = (bf16[{r}]{{2,1,0}}, "
            f"f32[{batch},{d_state},{d_inner}]{{2,1,0}}) custom-call("
            f"bf16[{r}]{{2,1,0}} %x, f32[{r}]{{2,1,0}} %dt, "
            f"bf16[{r}]{{2,1,0}} %z, "
            f"f32[{batch},{rows},{d_state},128]{{3,2,1,0}} %b, "
            f"f32[{batch},{rows},{d_state},128]{{3,2,1,0}} %c, "
            f"f32[{d_state},{d_inner}]{{1,0}} %a, f32[1,{d_inner}]{{1,0}} %d, "
            f"f32[{batch},{d_state},{d_inner}]{{2,1,0}} %h0), "
            'custom_call_target="tpu_custom_call", operand_layout_'
            "constraints={}")


def fused(rows=2048, d_inner=5120, d_state=16, layers=26, n=13):
    """The form a served chunk's event takes: the call fused with the write
    of its state into the stacked cache (my chip run, PR 47)."""
    r = f"1,{rows},{d_inner}"
    return (f"%_ssm_selective_scan.{n} = (f32[{layers},1,{d_state},{d_inner}]"
            f"{{3,2,1,0:T(8,128)}}, bf16[{r}]{{2,1,0:T(8,128)(2,1)}}) fusion("
            f"f32[{layers},1,{d_state},{d_inner}]{{3,2,1,0:T(8,128)}} "
            f"%get-tuple-element.2037, s32[]{{:T(128)S(6)}} %select_n.918, "
            f"bf16[{r}]{{2,1,0:T(8,128)(2,1)S(1)}} %multiply_convert_fusion.26,"
            f" f32[{r}]{{2,1,0:T(8,128)S(1)}} %fusion.1882, "
            f"bf16[{r}]{{2,1,0:T(8,128)(2,1)S(1)}} %get-tuple-element.1635, "
            f"f32[1,{rows},{d_state},128]{{3,2,1,0:T(8,128)S(1)}} %b, "
            f"f32[1,{rows},{d_state},128]{{3,2,1,0:T(8,128)S(1)}} %c, "
            f"f32[{d_state},{d_inner}]{{1,0:T(8,128)}} %a, "
            f"f32[1,{d_inner}]{{1,0:T(1,128)}} %d, "
            f"f32[1,{d_state},{d_inner}]{{2,1,0:T(8,128)}} %h0), "
            "kind=kCustom, calls=%fused_computation.979.clone.clone")


def test_the_fused_form_counts_what_the_bare_call_counts():
    assert ssm_roofline.is_selective_scan(fused())
    assert ssm_roofline.counts(fused()) == ssm_roofline.counts(call())
    assert ssm_roofline.counts(fused(rows=512)) == \
        ssm_roofline.counts(call(rows=512))
    # a fusion of another kind under the kernel's name is no kernel
    assert not ssm_roofline.is_selective_scan(
        fused().replace("kind=kCustom", "kind=kLoop"))


def test_counts_of_a_chunks_scan():
    """2,048 rows of 5,120 channels: x and z in and y out at 2 B, dt at 4
    (10 B a channel a row), B and C at 4 B x 16 a row, the state in and out,
    A and D once. No operation on the matrix unit."""
    ops, nbytes = ssm_roofline.counts(call())
    assert ops == 0.0
    assert nbytes == (2048 * 5120 * 10 + 2 * 2048 * 16 * 4
                      + 2 * 16 * 5120 * 4 + 17 * 5120 * 4)
    assert ssm_roofline.roofline_seconds(call(), PEAKS) == \
        pytest.approx(nbytes / 819e9)
    # two sequences of 512 rows: the state twice, the rows as many
    _, two = ssm_roofline.counts(call(rows=512, batch=2))
    assert two == (1024 * 5120 * 10 + 2 * 1024 * 16 * 4
                   + 2 * 2 * 16 * 5120 * 4 + 17 * 5120 * 4)


def test_only_the_scans_own_kernel_is_taken():
    assert ssm_roofline.is_selective_scan(call())
    other = call().replace("_ssm_selective_scan", "_flash_attention")
    assert not ssm_roofline.is_selective_scan(other)
    fusion = "%fusion.7 = f32[1,16,5120]{2,1,0} fusion(f32[26,1,16,5120]" \
        "{3,2,1,0} %_ssm_selective_scan.3)"
    assert not ssm_roofline.is_selective_scan(fusion)
    assert ssm_roofline.counts(fusion) is None
    # a text with a scan's name and not its shapes: nothing, never an error
    assert ssm_roofline.counts(
        "%_ssm_selective_scan.1 = bf16[8,8]{1,0} custom-call(bf16[8,8]{1,0} "
        '%x), custom_call_target="tpu_custom_call"') is None


def _run(trace, config=None):
    ctx = types.SimpleNamespace(peaks=PEAKS, config=config or {},
                                traffic={"serving": {"num_slots": 32}})
    return types.SimpleNamespace(ctx=ctx, trace=trace, samples={}, checks={})


def test_readers_on_a_hand_built_trace():
    least = ssm_roofline.roofline_seconds(call(), PEAKS)
    ops = [(call(n=1), 0.0, 4 * least), (fused(n=2), 0.1, 6 * least),
           ("%fusion.4 = bf16[32,65536]{1,0} fusion(bf16[32,2560]{1,0} %x)",
            0.2, 0.5)]
    spans = [("mtpu/serve/step", 0.0, 0.3), ("mtpu/serve/step", 0.4, 0.2)]
    run = _run(Trace(kind="tpu", window_s=0.7, ops={0: ops}, spans=spans))
    read = lambda name: load_module("layer_metrics", name).read(run)  # noqa: E731
    assert read("ssm_scan_roofline_pct") == pytest.approx(20.0)
    assert read("serve_ssm_scan_ms_per_step") == \
        pytest.approx(1e3 * 10 * least / 2)


@pytest.mark.parametrize("name", [
    "ssm_scan_roofline_pct", "serve_ssm_scan_ms_per_step",
    "serve_ssm_state_ms_per_step", "serve_ssm_state_bytes_per_slot"])
def test_every_new_reader_returns_none_where_it_has_nothing_to_read(name):
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
        assert read(_run(trace, {"num_hidden_layers": 13,
                                 "layer_types": ["conv"] * 13})) is None
