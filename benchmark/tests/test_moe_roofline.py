"""`moe_roofline.py`'s counts on hand-made event texts, and the roofline
share of hand-made traces."""
import types

import pytest

from benchmark import moe_roofline as mr
from benchmark.by_name import load_module
from benchmark.trace import Trace

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
META = ("s32[]{:T(128)} %g.1, s32[65]{0:T(128)S(1)} %c.3, s32[65]{0:T(128)} "
        "%c.5, s32[65]{0:T(128)} %c.4, s32[1]{0:T(128)} %constant.97, ")
TAIL = ('), custom_call_target="tpu_custom_call", operand_layout_constraints='
        '{s32[], bf16[256,2048]{1,0}, bf16[64,2048,2048]{2,1,0}}')


def forward(m, k, n, e=64, name="_moe_grouped_matmul.3"):
    return (f"%{name} = bf16[{m},{n}]{{1,0:T(8,128)(2,1)}} custom-call({META}"
            f"bf16[{m},{k}]{{1,0:T(8,128)(2,1)}} %rows, "
            f"bf16[{e},{k},{n}]{{2,1,0:T(8,128)(2,1)}} %bank{TAIL}")


def test_counts_of_a_decode_step_and_a_prefill():
    ops, byts = mr.counts(forward(256, 2048, 2048))
    assert ops == 2 * 256 * 2048 * 2048
    assert byts == 2 * (256 * 2048 + 256 * 2048 + 64 * 2048 * 2048)
    ops, byts = mr.counts(forward(49152, 1024, 2048))
    assert ops == 2 * 49152 * 1024 * 2048
    assert byts == 2 * (49152 * 1024 + 49152 * 2048 + 64 * 1024 * 2048)
    # the decode step is bound by the banks' bytes, the prefill by products
    assert mr.roofline_seconds(forward(256, 2048, 2048), PEAKS) == \
        pytest.approx(byts_s(256, 2048, 2048, 64))
    assert mr.roofline_seconds(forward(49152, 2048, 2048), PEAKS) == \
        pytest.approx(2 * 49152 * 2048 * 2048 / 197e12)
    # the experts the rows touched, where the driver measured them
    assert mr.counts(forward(256, 2048, 2048), 62.5)[1] == \
        2 * (256 * 2048 + 256 * 2048 + 62.5 * 2048 * 2048)
    assert mr.rows_of(forward(256, 2048, 2048)) == 256


def byts_s(m, k, n, experts):
    return 2 * (m * k + m * n + experts * k * n) / 819e9


@pytest.mark.parametrize("hit", [8, 30, 62.5, 64])
def test_share_cannot_pass_100_when_fewer_experts_than_groups_are_hit(hit):
    """A kernel that streams exactly the `hit` matrices its rows touch at
    the chip's full bandwidth is at 100 % when the count is told how many
    they were, and never above however many the count is told: a count
    above min(E, m) is cut to it."""
    text = forward(256, 2048, 2048)
    fastest = byts_s(256, 2048, 2048, hit)
    assert mr.roofline_seconds(text, PEAKS, hit) / fastest == \
        pytest.approx(1.0)
    assert mr.roofline_seconds(text, PEAKS, 1000) == \
        pytest.approx(byts_s(256, 2048, 2048, 64))


def test_fewer_rows_than_groups():
    text = forward(4, 2048, 2048)          # 4 rows touch 4 experts at most
    assert mr.counts(text)[1] == 2 * (4 * 2048 + 4 * 2048 + 4 * 2048 * 2048)
    assert mr.counts(text, 62.5)[1] == mr.counts(text)[1]


def test_backward_kernels():
    dlhs = (f"%transpose_jvp_jit__moe_grouped_matmul_dlhs___.2 = "
            f"bf16[512,2048]{{1,0}} custom-call({META}bf16[512,1024]{{1,0}} "
            f"%grad, bf16[64,2048,1024]{{2,1,0}} %bank{TAIL}")
    assert mr.is_grouped_matmul(dlhs)
    assert mr.counts(dlhs)[0] == 2 * 512 * 1024 * 2048
    drhs = (f"%transpose_jvp_jit__moe_grouped_matmul_drhs___.2 = "
            f"bf16[64,2048,1024]{{2,1,0}} custom-call({META}"
            f"bf16[2048,512]{{1,0}} %rows_t, bf16[512,1024]{{1,0}} %grad{TAIL}")
    ops, byts = mr.counts(drhs)
    assert ops == 2 * 512 * 2048 * 1024
    assert byts == 2 * (512 * 2048 + 512 * 1024 + 64 * 2048 * 1024)


@pytest.mark.parametrize("text", [
    "%fusion.4 = bf16[256,2048]{1,0} fusion(bf16[256,2048]{1,0} "
    "%_moe_grouped_matmul.3), kind=kLoop",                 # an operand's name
    forward(256, 2048, 2048, name="_flash_attention.26"),  # another kernel
    "%_moe_grouped_matmul.9 = bf16[256,2048]{1,0} custom-call(), "
    'custom_call_target="AllocateBuffer"'])
def test_what_is_not_the_kernel(text):
    assert not mr.is_grouped_matmul(text)


def run_with(events, kind="tpu"):
    trace = Trace(kind=kind, window_s=1.0, ops={0: events},
                  spans=[("mtpu/serve/step", 0.0, 0.5),
                         ("mtpu/serve/step", 0.5, 0.4)])
    ctx = types.SimpleNamespace(peaks=PEAKS,
                                config={"num_experts_per_tok": 8},
                                traffic={"serving": {"num_slots": 32}})
    return types.SimpleNamespace(trace=trace, ctx=ctx, samples={}, checks={})


def test_readers_on_a_hand_made_trace():
    decode, prefill = forward(256, 2048, 2048), forward(49152, 2048, 2048)
    least = (mr.roofline_seconds(decode, PEAKS)
             + mr.roofline_seconds(prefill, PEAKS))
    events = [(decode, 0.10, 1.0e-3), (prefill, 0.20, 4.0e-3),
              ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
               0.0, 0.9)]
    run = run_with(events)
    share = load_module("layer_metrics",
                        "moe_grouped_matmul_roofline_pct").read(run)
    assert share == pytest.approx(100 * least / 5.0e-3) and share < 100
    per_step = load_module("layer_metrics",
                           "serve_moe_experts_ms_per_step").read(run)
    assert per_step == pytest.approx(5.0 / 2)
    # with the experts a decode step touched, as the driver measures them:
    # the decode call's bytes follow, the prefill's do not
    run.checks["expert_load_window"] = {
        "groups_hit_per_decode_step": [60.0, 62.0, 63.0, 63.0]}
    told = load_module("layer_metrics",
                       "moe_grouped_matmul_roofline_pct").read(run)
    assert told == pytest.approx(
        100 * (mr.roofline_seconds(decode, PEAKS, 62.0)
               + mr.roofline_seconds(prefill, PEAKS)) / 5.0e-3)
    assert told < share


@pytest.mark.parametrize("name", ["moe_grouped_matmul_roofline_pct",
                                  "serve_moe_experts_ms_per_step"])
def test_readers_say_nothing_without_the_kernel_or_off_the_tpu(name):
    read = load_module("layer_metrics", name).read
    other = [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop",
              0.0, 0.9)]
    assert read(run_with(other)) is None                    # a parent commit
    assert read(run_with([(forward(256, 2048, 2048), 0.1, 1e-3)],
                         kind="host-xla")) is None          # a CPU rehearsal
    assert read(types.SimpleNamespace(
        trace=None, samples={}, checks={},
        ctx=types.SimpleNamespace(peaks=PEAKS, config={}))) is None
