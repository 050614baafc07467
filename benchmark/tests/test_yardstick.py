"""The arithmetic later PRs are measured with: the traffic generator, the
percentiles, the FLOP count and the trace reduction."""
import collections
import os

import pytest

from benchmark import flops, loadgen, stats, trace

HERE = os.path.dirname(os.path.abspath(__file__))
MIX = {"rate_rps": 4.4, "ramp_s": 15, "tail_cap_s": 10,
       "prompt": {"median": 384, "sigma": 0.8, "min": 32, "max": 1536},
       "output": {"median": 96, "sigma": 0.7, "min": 16, "max": 384}}


def _key(a):
    return (a.due_s, a.prompt_len, a.output_len, a.seed, a.phase)


def test_schedule_repeats_from_a_seed_and_differs_across_seeds():
    a = loadgen.schedule(MIX, 3000000019, 45)
    b = loadgen.schedule(MIX, 3000000019, 45)
    c = loadgen.schedule(MIX, 3000000026, 45)
    assert [_key(x) for x in a] == [_key(x) for x in b]
    assert [_key(x) for x in a] != [_key(x) for x in c]
    assert loadgen.prompts_for(a[:5], 65024, 1) == \
        loadgen.prompts_for(b[:5], 65024, 1)
    assert loadgen.prompts_for(a[:5], 65024, 1) != \
        loadgen.prompts_for(a[:5], 65024, 2)


def test_every_seed_offers_the_same_work():
    a = loadgen.schedule(MIX, 1, 45)
    c = loadgen.schedule(MIX, 2 ** 31 + 5, 45)
    for phase, n in (("ramp", 66), ("window", 198), ("tail", 44)):
        xa = [x for x in a if x.phase == phase]
        xc = [x for x in c if x.phase == phase]
        assert len(xa) == len(xc) == n
        for f in ("prompt_len", "output_len"):
            assert collections.Counter(getattr(x, f) for x in xa) == \
                collections.Counter(getattr(x, f) for x in xc)
    w = [x for x in a if x.phase == "window"]
    assert all(15 <= x.due_s < 60 for x in w)
    assert [x.due_s for x in a] == sorted(x.due_s for x in a)
    assert all(32 <= x.prompt_len <= 1536 and 16 <= x.output_len <= 384
               and x.prompt_len + x.output_len <= 1920 for x in a)
    lens = sorted(x.prompt_len for x in w)
    assert 350 <= lens[len(lens) // 2] <= 420          # the median asked for


def test_prefill_buckets():
    assert loadgen.prefill_buckets(MIX, 256) == [256, 512, 768, 1024, 1280,
                                                 1536]
    assert len(loadgen.prefill_buckets(MIX, 16)) == 95


def test_percentile_and_failures():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 50.5
    assert stats.percentile(xs, 95) == pytest.approx(95.05)
    assert stats.percentile([3.0], 95) == 3.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    # one failure in twenty is the 95th percentile's business
    samples = [0.1] * 18 + [None, None]
    assert stats.percentile(stats.with_failures(samples, 70.0), 95) == 70.0
    assert stats.percentile(stats.with_failures(samples, 70.0), 50) == 0.1


def test_flops_by_hand_for_falcon_7b():
    # per token and layer: q 2*4544*4544, kv 2*4544*128, out 2*4544*4544,
    # MLP 2*2*4544*18176, attention 2*2*4544*(2049/2); head 2*4544*65024
    layer = (2 * 4544 * 4544 * 2 + 2 * 4544 * 128 + 4 * 4544 * 18176
             + 4 * 4544 * 2049 / 2)
    want = 3 * (32 * layer + 2 * 4544 * 65024)
    got = flops.train_flops_per_token(
        layers=32, hidden=4544, heads=71, kv_heads=1, head_dim=64,
        ffn=18176, vocab=65024, seq=2048)
    assert got == pytest.approx(want, rel=1e-12)
    assert 43.2e9 < got < 43.4e9
    two = flops.train_flops_per_token(
        layers=2, hidden=4544, heads=71, kv_heads=1, head_dim=64,
        ffn=18176, vocab=65024, seq=2048)
    assert 0.40 < 3 * 2 * 4544 * 65024 / two < 0.42     # the head's share


def test_trace_reduction_on_a_recorded_v5e_trace():
    """Three calls of a small jitted loop on one TPU v5e chip (PR 24):
    each is a `while` of three fusions between copies and a reduce."""
    t = trace.load(os.path.join(HERE, "fixtures", "v5e-small.xplane.pb"))
    assert t.kind == "tpu" and sorted(t.ops) == [0]
    assert t.span_count("bench/flush") == 3
    assert t.busy_s() == pytest.approx(21.706e-6, rel=1e-3)
    assert t.window_s == pytest.approx(7.0923e-3, rel=1e-3)
    st = t.self_seconds()
    names = {trace.parse_op(k)[0] for k in st}
    assert "while" not in names                     # a container
    fusion = [v for k, v in st.items()
              if trace.parse_op(k)[0] == "convolution_tanh_fusion.2"]
    assert fusion == [pytest.approx(13.335e-6, rel=1e-3)]
    # self times never add up to more than the busy time
    assert sum(st.values()) <= t.busy_s() * (1 + 1e-9)
    assert t.top_ops(1)[0][0].startswith("convolution_tanh_fusion.2 fusion")
    gaps = dict(map(tuple, t.idle_gaps()))
    assert gaps["bench/flush"] == pytest.approx(7.07e-3, rel=1e-2)
    assert t.seconds_where(trace.is_collective) == 0.0
    assert t.seconds_where(trace.is_pallas_kernel) == 0.0


def test_parse_op():
    text = ('%_flash_attention.26 = (bf16[1,71,2048,64]{3,2,1,0:T(8,128)(2,1)}'
            ', f32[1,71,2048,8]{3,2,1,0}) custom-call(bf16[1,71,2048,64] '
            '%bitcast.616), custom_call_target="tpu_custom_call"')
    assert trace.parse_op(text)[:2] == ("_flash_attention.26", "custom-call")
    assert trace.is_pallas_kernel(text)
    alloc = ('%custom-call.22 = bf16[2,18176,4544]{2,1,0} custom-call(), '
             'custom_call_target="AllocateBuffer"')
    assert not trace.is_pallas_kernel(alloc)
    user = ('%fusion.25 = f32[2,4544,128]{2,1,0} fusion(f32[2,4544,128] '
            '%custom-call.27, s32[] %all-gather.3), kind=kLoop')
    assert not trace.is_pallas_kernel(user) and not trace.is_collective(user)
    assert trace.is_collective('%all-gather.3 = bf16[8192]{0} all-gather('
                               'bf16[2048]{0} %x), dimensions={0}')
    assert trace.is_collective('%collective-permute-start.1 = (bf16[4]{0}, '
                               'bf16[4]{0}) collective-permute-start(%y)')
    assert trace.parse_op("dot_general.1") == ("dot_general.1",
                                               "dot_general", "")
