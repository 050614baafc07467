"""CPU smoke tests for the on-chip bench tools.

These tools are written to run on the chip, so an API drift (e.g. a
Generator signature change) would otherwise surface only there. Each test
drives a tool's main() end-to-end at tiny shapes on the virtual-CPU
backend and asserts the measurement lines it promises actually emit.
"""
import os
import runpy
import sys

import pytest

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")


def run_tool(monkeypatch, tmp_path, tool, argv):
    out = tmp_path / "out.log"
    monkeypatch.setattr(sys, "argv", [tool, "--out", str(out)] + argv)
    try:
        runpy.run_path(os.path.join(TOOLS, tool), run_name="__main__")
    except SystemExit as e:  # `raise SystemExit(main())` entry idiom
        assert not e.code, f"{tool} exited rc={e.code}"
    return out.read_text()


def test_bench_head_emits_overhead_table(monkeypatch, tmp_path):
    text = run_tool(
        monkeypatch, tmp_path, "bench_head.py",
        ["--seq", "128", "--hidden", "128", "--ffn", "344", "--heads", "4",
         "--vocab", "512", "--iters", "2"])
    assert "t_layer fwd+bwd" in text
    assert "t_head  fwd+bwd" in text
    # one overhead line per (pp, L) point, all parseable percentages
    lines = [l for l in text.splitlines() if "uniform-head overhead" in l]
    assert len(lines) == 6
    for l in lines:
        pct = float(l.split("=")[-1].strip().rstrip("%"))
        assert 0.0 <= pct < 100.0


@pytest.mark.slow
def test_bench_bubble_fit_and_fractions(monkeypatch, tmp_path):
    """The bubble tool must time the real 1F1B program on the virtual
    pp2 mesh, fit a linear tick model, and report measured-vs-predicted
    bubble fractions for each n_micro and vpp arm."""
    text = run_tool(
        monkeypatch, tmp_path, "bench_bubble.py",
        ["--pp", "2", "--vpp", "1", "2", "--n_micro", "2", "4", "8",
         "--iters", "1", "--hidden", "64", "--seq", "32",
         "--layers_per_pos", "1"])
    assert "fit: t_tick=" in text
    frac_lines = [l for l in text.splitlines() if "measured_bubble=" in l]
    assert len(frac_lines) == 6  # 3 n_micro x 2 vpp
    for l in frac_lines:
        pred = float(l.rsplit("predicted", 1)[1])
        assert 0.0 <= pred < 1.0


def test_bench_decode_emits_throughput(monkeypatch, tmp_path):
    text = run_tool(
        monkeypatch, tmp_path, "bench_decode.py",
        ["--batch", "2", "--prompt", "64", "--new", "16", "--layers", "2",
         "--hidden", "128", "--heads", "4", "--ffn", "344",
         "--vocab", "512", "--int8_weights", "--int8_kv"])
    assert "new-tok/s" in text
    # every quantized arm must measure and report its ratio
    for arm in ("int8 generate(", "int8kv generate(",
                "int8w+kv generate("):
        assert arm in text, f"missing {arm!r}:\n{text}"
    assert "x vs bf16" in text and "param bytes" in text
    # no roofline on cpu (no HBM bandwidth entry) — the line must be
    # absent for EVERY arm rather than printing a nonsense ratio
    assert "roofline" not in text


def test_bench_decode_sliding_window_arm(monkeypatch, tmp_path):
    text = run_tool(
        monkeypatch, tmp_path, "bench_decode.py",
        ["--batch", "1", "--prompt", "64", "--new", "16", "--layers", "2",
         "--hidden", "64", "--heads", "4", "--ffn", "128",
         "--vocab", "128", "--sliding_window", "32"])
    assert "sliding_window=32 (rolling cache)" in text
    assert "new-tok/s" in text
    # no roofline on cpu (no HBM bandwidth entry) — the line must be absent
    # rather than printing a nonsense ratio
    assert "roofline" not in text


def test_serving_bench_emits_record(monkeypatch, tmp_path):
    """The concurrent-load micro-bench must drive the engine end-to-end
    and emit one parseable BENCH-style JSON record."""
    import json
    text = run_tool(
        monkeypatch, tmp_path, "serving_bench.py",
        ["--requests", "6", "--slots", "2", "--prompt", "12", "--new", "6",
         "--layers", "2", "--hidden", "64", "--heads", "4",
         "--vocab", "128", "--seq", "128"])
    rec = json.loads(text)
    assert rec["bench"] == "serving" and rec["mode"] == "engine"
    assert rec["tokens_per_s"] > 0
    assert rec["ttft_p95_ms"] >= rec["ttft_p50_ms"] >= 0
    assert 0 < rec["slot_occupancy"] <= 1
    assert rec["decode_steps"] >= 6  # 6 requests interleaved on 2 slots


def test_serving_bench_overload_arm(monkeypatch, tmp_path):
    """The overload arm (offered load > slot capacity, deadlines +
    early shedding) must emit shed rate, goodput, and queue-delay
    percentiles — and its accounting must cover every offered request."""
    import json
    text = run_tool(
        monkeypatch, tmp_path, "serving_bench.py",
        ["--overload", "--requests", "12", "--slots", "2",
         "--prompt", "12", "--new", "6", "--deadline", "2.0",
         "--layers", "2", "--hidden", "64", "--heads", "4",
         "--vocab", "128", "--seq", "128"])
    rec = json.loads(text)
    assert rec["bench"] == "serving" and rec["mode"] == "overload"
    assert 0.0 <= rec["shed_rate"] <= 1.0
    assert 0.0 <= rec["goodput_frac"] <= 1.0
    assert rec["queue_wait_p99_ms"] >= rec["queue_wait_p50_ms"] >= 0
    # every offered request is accounted: shed, expired, or served
    served = round(rec["goodput_frac"] * rec["requests"])
    assert rec["shed"] + rec["expired_504"] + served == rec["requests"]


def test_bench_prefix_emits_ab_record(monkeypatch, tmp_path):
    """The shared-prefix A/B must show the cache-on arm reusing prefix
    tokens (hits > 0, saved > 0) and forwarding strictly fewer REAL
    prefill tokens than the cache-off arm, with all arms token-exact
    (the tool asserts arm agreement itself and exits nonzero on
    divergence)."""
    import json
    text = run_tool(
        monkeypatch, tmp_path, "bench_prefix.py",
        ["--requests", "5", "--shared", "32", "--unique", "8",
         "--slots", "3", "--new", "4", "--chunk", "16",
         "--sessions", "5", "--block", "16",
         "--layers", "2", "--hidden", "64", "--heads", "4",
         "--vocab", "128", "--seq", "128"])
    rec = json.loads(text)
    assert rec["bench"] == "prefix_cache"
    # multi-turn-chat capacity arm (the block-pool acceptance seam):
    # whole-region retention is bounded by the 3 slots and LRU-thrashes
    # on 5 serial sessions, block retention keeps every session — the
    # hit-rate ratio at FIXED pool bytes must clear 2x
    whole, blocks = (rec["multiturn_whole_region"],
                     rec["multiturn_blocks"])
    assert whole["retained_after_turn1"] <= 3
    assert blocks["retained_after_turn1"] == 5
    assert blocks["turn2_session_hit_rate"] == 1.0
    assert rec["retained_capacity_x"] >= 2.0
    # fragmentation gauge: block retention wastes far fewer reserved
    # bytes than whole-cap regions for the same live prefixes
    assert blocks["kv_bytes_wasted"] < whole["kv_bytes_wasted"]
    base, pref, chnk = (rec["baseline"], rec["prefix"],
                        rec["prefix_chunked"])
    assert base["prefix_hits"] == 0
    assert base["prefill_tokens_saved"] == 0
    # the warmup request seeds the retained prefix, so the burst is
    # guaranteed at least one deterministic hit
    assert pref["prefix_hits"] >= 1
    assert pref["prefill_tokens_saved"] >= 32
    assert pref["prefill_forward_tokens"] < base["prefill_forward_tokens"]
    assert rec["forward_token_reduction_x"] > 1.0
    # the chunked arm splits prefills without losing the cache win
    assert chnk["prefill_chunks"] > pref["prefill_chunks"]
    assert chnk["prefill_tokens_saved"] >= 32


def test_bench_block_attn_emits_ab_record(monkeypatch, tmp_path):
    """The block-native attention A/B must run both arms token-exact
    (the tool asserts agreement itself and exits nonzero on
    divergence), show the bracket arm paying real resolve/scatter
    bytes per step, and pin the kernel arm's gather traffic at
    EXACTLY zero — the ISSUE-11 acceptance seam on the metrics
    gauge."""
    import json
    text = run_tool(
        monkeypatch, tmp_path, "bench_block_attn.py",
        ["--requests", "3", "--prompt", "8", "--new", "6",
         "--slots", "2", "--blocks", "16", "--dtypes",
         "bfloat16,int8", "--max_len", "64", "--layers", "2",
         "--hidden", "64", "--heads", "4", "--vocab", "128"])
    rec = json.loads(text)
    assert rec["bench"] == "block_native_attn"
    assert rec["greedy_arms_token_exact"] is True
    assert [c["kv_dtype"] for c in rec["combos"]] == \
        ["bfloat16", "int8"]
    for combo in rec["combos"]:
        assert combo["bracket"]["kv_gather_bytes_per_step"] > 0
        assert combo["kernel"]["kv_gather_bytes_per_step"] == 0
        assert combo["bracket"]["kv_attn_path"] == 1
        assert combo["kernel"]["kv_attn_path"] == 2
        assert combo["kernel"]["tokens_generated"] == \
            combo["bracket"]["tokens_generated"] > 0


def test_bench_lora_emits_ab_record(monkeypatch, tmp_path):
    """The multi-tenant LoRA A/B must run base / one-adapter / mixed
    arms with every row token-exact vs its own adapter's
    merged-weights serial oracle (the tool asserts agreement itself
    and exits nonzero on divergence), keep ONE decode compile per arm
    with adapters enabled, and report the adapter-gather bytes/step
    seam the on-chip comparison keys on."""
    import json
    text = run_tool(
        monkeypatch, tmp_path, "bench_lora.py", ["--smoke"])
    rec = json.loads(text.splitlines()[-1])
    assert rec["bench"] == "lora_adapters"
    assert rec["rows_token_exact_vs_merged_oracle"] is True
    assert rec["one_decode_compile_per_arm"] is True
    assert rec["adapter_gather_bytes_per_step"] > 0
    assert [a["arm"] for a in rec["arms"]] == \
        ["base", "one_adapter", "mixed_3"]
    base, one, mixed = rec["arms"]
    assert base["adapter_loads"] == 0 and base["active_adapters"] == 0
    assert one["active_adapters"] == 1
    assert mixed["active_adapters"] == 3
    # every arm generated the same token volume (eos_id=-1: no early
    # EOS — the arms measure identical work)
    assert base["tokens_generated"] == one["tokens_generated"] == \
        mixed["tokens_generated"] > 0


def test_bench_spec_emits_ab_record(monkeypatch, tmp_path):
    """The speculative-decode A/B must run greedy arms token-exact vs
    the k=0 baseline (the tool asserts agreement itself and exits
    nonzero on divergence), actually draft and accept on the
    repetitive-motif workload, and report the acceptance-rate /
    tokens-per-round seam the on-chip roofline comparison keys on."""
    import json
    text = run_tool(
        monkeypatch, tmp_path, "bench_spec.py",
        ["--requests", "4", "--prompt", "12", "--new", "16",
         "--slots", "3", "--ks", "2,4", "--layers", "2",
         "--hidden", "64", "--heads", "4", "--vocab", "128",
         "--seq", "128"])
    rec = json.loads(text)
    assert rec["bench"] == "speculative_decode"
    assert rec["greedy_arms_token_exact"] is True
    assert rec["baseline"]["speculative_k"] == 0
    assert rec["baseline"]["draft_tokens"] == 0
    assert [a["speculative_k"] for a in rec["arms"]] == [2, 4]
    for arm in rec["arms"]:
        assert arm["tokens_generated"] == \
            rec["baseline"]["tokens_generated"]
        assert arm["spec_rounds"] >= 1
        assert arm["draft_tokens"] >= 1
        # tokens_per_round = 1 + k * acceptance: the roofline scaler
        assert arm["tokens_per_round"] == pytest.approx(
            1 + arm["speculative_k"] * arm["acceptance_rate"],
            abs=0.02)
    # the repetitive-motif workload must actually exercise acceptance
    assert rec["best_acceptance_rate"] > 0.0
    assert rec["roofline"]["step_bytes"] > 0


def test_bench_sync_emits_cadence_record(monkeypatch, tmp_path):
    """The host-sync cadence A/B must show the async window fetching
    fewer times than per-step and the K-window serving arm syncing at
    exactly 1/K per decode step."""
    import json
    text = run_tool(
        monkeypatch, tmp_path, "bench_sync.py",
        ["--iters", "9", "--log_interval", "3", "--requests", "3",
         "--slots", "2", "--new", "6", "--sync_k", "3",
         "--layers", "2", "--hidden", "64", "--heads", "4",
         "--vocab", "128", "--seq", "64"])
    rec = json.loads(text)
    tr = rec["training"]
    assert tr["sync"]["host_syncs"] == 9          # one fetch per step
    assert tr["async"]["host_syncs"] <= 4         # one per window (+1st)
    assert tr["sync_reduction_x"] >= 2
    sv = rec["serving"]
    assert sv["k1"]["syncs_per_step"] == 1.0
    assert sv["k"]["syncs_per_step"] == pytest.approx(1 / 3, abs=1e-3)
    assert sv["k"]["tokens"] == sv["k1"]["tokens"]  # cadence != semantics


def test_bench_kernels_smoke_runs_all_arms(monkeypatch, tmp_path):
    text = run_tool(monkeypatch, tmp_path, "bench_kernels.py",
                    ["--smoke", "--iters", "2"])
    # every arm must MEASURE in smoke mode (pallas arms run interpreted
    # off-TPU) — a FAILED line here is exactly the bitrot this guards
    assert "FAILED" not in text, text
    for arm in ("rms fwd", "ln  fwd", "rms vjp", "flash fwd", "gemm ["):
        assert arm in text, f"missing arm {arm!r}:\n{text}"


def test_bench_remat_smoke_runs_all_arms(monkeypatch, tmp_path):
    text = run_tool(monkeypatch, tmp_path, "bench_remat.py",
                    ["--smoke", "--iters", "2", "--warmup", "1"])
    assert "FAILED" not in text, text
    for arm in ("remat=none", "remat=selective", "remat=full", "best:"):
        assert arm in text, f"missing arm {arm!r}:\n{text}"


@pytest.mark.slow
def test_bench_32k_fit_emits_extrapolation(monkeypatch, tmp_path):
    # width overrides exist exactly for this smoke path (tool docstring)
    text = run_tool(
        monkeypatch, tmp_path, "bench_32k.py",
        ["--seq_length", "256", "--hidden", "128", "--ffn", "344",
         "--heads", "4", "--iters", "1", "--warmup", "1"])
    assert "_slice_train_tokens_per_sec_per_chip" in text
    assert "extrapolated_7b_" in text
    assert "EXTRAPOLATED" in text  # the honest-labeling contract


def test_bench_disagg_emits_ab_record(monkeypatch, tmp_path):
    """The interleave-vs-disaggregated A/B must run both serving arms
    token-exact (the tool asserts agreement itself and exits nonzero
    on divergence), pin the handoff at ceil(plen/B) live blocks —
    never a cap region — and report the TTFT / inter-token-p99 /
    decode-tok/s seams plus the tp=1-vs-2 decode arm the on-chip
    comparison keys on (PERF_NOTES queue item 10)."""
    import json
    text = run_tool(monkeypatch, tmp_path, "bench_disagg.py",
                    ["--smoke"])
    rec = json.loads(text)
    assert rec["bench"] == "disagg_serving"
    assert rec["greedy_arms_token_exact"] is True
    inter, dis = rec["interleave"], rec["disaggregated"]
    assert inter["handoffs"] == 0  # the fallback never hands off
    # on the 8-virtual-device harness both multi-group arms must RUN
    assert "skipped" not in dis
    assert dis["handoffs"] == rec["requests"]
    assert dis["handoff_bytes_per_req"] > 0
    assert dis["tokens_generated"] == inter["tokens_generated"] > 0
    for key in ("ttft_p50_ms", "inter_token_p99_ms", "decode_tok_s"):
        assert key in inter and key in dis
    assert "skipped" not in rec["tp_arms"]
    assert rec["tp_arms"]["tp_speedup_x"] > 0


def test_bench_phase_topology_emits_ab_record(monkeypatch, tmp_path):
    """The symmetric-vs-asymmetric per-phase split A/B must run all
    three disaggregated arms token-exact (the tool asserts agreement
    and exits nonzero on divergence), keep the handoff byte pin across
    DIFFERENT mesh widths (the P!=D reshard rides inside the one
    device_put — no extra copy), and report the decode-heavy ITL /
    prefill-heavy TTFT ratios the on-chip comparison keys on
    (PERF_NOTES queue item 12)."""
    import json
    text = run_tool(monkeypatch, tmp_path, "bench_phase_topology.py",
                    ["--smoke"])
    rec = json.loads(text)
    assert rec["bench"] == "phase_topology"
    assert rec["greedy_arms_token_exact"] is True
    # the tool forces a 4-virtual-device host: every arm must RUN
    assert "skipped" not in rec and "asymmetric" not in rec
    for name, ptp, dtp in (("symmetric", 1, 1), ("decode_heavy", 1, 2),
                           ("prefill_heavy", 2, 1)):
        arm = rec[name]
        assert (arm["prefill_tp"], arm["decode_tp"]) == (ptp, dtp)
        assert arm["handoffs"] == rec["requests"]
        for key in ("ttft_p50_ms", "inter_token_p99_ms",
                    "decode_tok_s"):
            assert key in arm
    # same byte count on every arm — the reshard added no copy
    assert len({rec[n]["handoff_bytes_per_req"] for n in
                ("symmetric", "decode_heavy", "prefill_heavy")}) == 1
    assert rec["decode_heavy"]["itl_p99_vs_symmetric_x"] > 0
    assert rec["prefill_heavy"]["ttft_vs_symmetric_x"] > 0


def test_bench_pp_serving_emits_ab_record(monkeypatch, tmp_path):
    """The pipeline-sharded serving A/B must run the mono arm and both
    staged arms token-exact (the tool asserts agreement and exits
    nonzero on divergence), read the staged gauges off the live engine
    snapshot — bubble pinned to (S-1)/(W+S-1), activation bytes > 0,
    the mono arm all-zero on the same schema keys — and report the
    per-arm decode tok/s ratio the on-chip comparison keys on
    (PERF_NOTES queue item 13)."""
    import json
    text = run_tool(monkeypatch, tmp_path, "bench_pp_serving.py",
                    ["--smoke"])
    rec = json.loads(text)
    assert rec["bench"] == "pp_serving"
    assert rec["greedy_arms_token_exact"] is True
    # the tool forces a 2-virtual-device host: every arm must RUN
    assert "skipped" not in rec
    for name, pp, waves, bubble in (("mono", 0, 0, 0.0),
                                    ("pp2_w1", 2, 1, 0.5),
                                    ("pp2_w2", 2, 2, 0.3333)):
        arm = rec[name]
        assert (arm["serving_pp"], arm["pp_waves"]) == (pp, waves)
        assert arm["pp_stage_bubble"] == bubble
        for key in ("ttft_p50_ms", "inter_token_p99_ms",
                    "decode_tok_s"):
            assert key in arm
    # one [num_slots, hidden] activation per stage boundary — same
    # bytes at W=1 and W=2 (waves re-time the crossing, not its size)
    assert rec["pp2_w1"]["pp_activation_bytes_per_step"] > 0
    assert (rec["pp2_w1"]["pp_activation_bytes_per_step"]
            == rec["pp2_w2"]["pp_activation_bytes_per_step"])
    assert rec["mono"]["pp_activation_bytes_per_step"] == 0.0
    assert rec["pp2_w1"]["tok_s_vs_mono_x"] > 0
    assert rec["pp2_w2"]["tok_s_vs_mono_x"] > 0


@pytest.mark.slow
def test_bench_serving_queue_runs_pending_abs(monkeypatch, tmp_path):
    """The queue runner must execute every pending serving A/B
    (PERF_NOTES items 8/9/10/12) as independent subprocesses and
    collect their records into one combined line."""
    import json
    text = run_tool(monkeypatch, tmp_path, "bench_serving_queue.py",
                    ["--smoke"])
    rec = json.loads(text)
    assert rec["bench"] == "serving_queue"
    assert rec["all_green"] is True
    assert [r["name"] for r in rec["runs"]] == \
        ["block_attn", "lora", "disagg", "phase_topology",
         "structured"]
    assert rec["results"]["block_attn"]["bench"] == "block_native_attn"
    assert rec["results"]["lora"]["bench"] == "lora_adapters"
    assert rec["results"]["disagg"]["bench"] == "disagg_serving"
    assert rec["results"]["phase_topology"]["bench"] == \
        "phase_topology"
    assert rec["results"]["structured"]["bench"] == "structured_nbest"


def test_bench_structured_emits_ab_record(monkeypatch, tmp_path):
    """The structured-output/n-best A/B must run the constrained arm
    with every output FSM-legal AND parsed (the tool asserts both and
    exits nonzero on violation), pin mask uploads to FSM state changes
    (zero on the free arm), run the n=4 fan-out token-exact vs its
    serially-seeded n=1 twins, and keep ONE decode compile across
    free + constrained + fan-out traffic — the tentpole's zero-new-
    traces contract."""
    import json
    text = run_tool(monkeypatch, tmp_path, "bench_structured.py",
                    ["--smoke"])
    rec = json.loads(text)
    assert rec["bench"] == "structured_nbest"
    assert rec["decode_compiles"] == 1
    ab = rec["constrained_vs_free"]
    assert ab["outputs_parse"] is True
    assert ab["free"]["mask_uploads"] == 0
    assert ab["free"]["structured_requests"] == 0
    assert ab["constrained"]["mask_uploads"] > 0
    assert ab["constrained"]["structured_requests"] == 4
    assert ab["constrained"]["grammar_dead_ends"] == 0
    # mask uploads follow state changes, never one per step per slot
    assert ab["constrained"]["mask_uploads"] <= \
        ab["constrained"]["tokens_generated"] + \
        ab["constrained"]["structured_requests"]
    nb = rec["n1_vs_n4"]
    assert nb["samples_token_exact"] is True
    assert nb["fanout"]["fanout_requests"] == 1
    assert nb["fanout"]["fanout_samples"] == nb["n"] == 4
    assert nb["fanout"]["prefill_tokens_saved"] > 0
    # the aggregate never prefills the prompt once per sample
    assert nb["fanout"]["prefill_forward_tokens"] < nb["n"] * 24
