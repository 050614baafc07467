"""Structured-output + parallel-sampling A/B micro-bench on the
serving engine.

Two arms, both on the SAME engine (one decode compile covers free and
constrained traffic — the mask rides the existing trace, and this tool
pins that):

- **constrained vs free**: the same seeded decode workload run free,
  then under a regex grammar (`serving/structured.py`). The grammar
  seam is a per-slot [vocab] bitmask applied inside the one compiled
  decode step; the HOST cost is the FSM walk plus a mask upload ONLY
  on state change (`mask_uploads` counter — the A/B seam, like
  prefill_forward_tokens was for the prefix cache). Every constrained
  completion must replay FSM-legal and parse (the tool asserts both).
- **n=1 x 4 vs n=4**: four serial submits of one prompt vs ONE
  fan-out submit (`n=4`). The fan-out arm prefills the prompt once and
  COW-aliases its blocks into the other three decode slots
  (`prefill_tokens_saved` / `prefix_hits` are the seam); every sample
  must be token-exact vs its serially-seeded n=1 twin — fan-out is a
  scheduling change, not a semantics change.

On CPU the wall-clock is a harness smoke; ON CHIP mask-upload counts,
prefill tokens removed, and the tok/s ratios transfer directly.

Emits ONE BENCH-style JSON record on stdout (and to --out), like the
other bench tools; runs in the bench.py extras chain and the
bench_serving_queue one-window runner.

  python tools/bench_structured.py [--smoke] [--requests N] [--new N]
                                   [--slots N] [--out FILE]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from megatron_tpu.utils.compile_cache import ensure_compile_cache

# bounded grammar over the identity token table (token i <-> chr(i)):
# digits only, 2-6 chars — enough FSM states that masks actually
# change per step, small enough that every budget covers max_path_len
GRAMMAR = {"type": "regex", "pattern": "[0-9]{2,6}"}


def _build(args):
    import jax
    import numpy as np

    from megatron_tpu.config import ModelConfig, ServingConfig
    from megatron_tpu.inference.generation import Generator
    from megatron_tpu.models import language_model as lm
    from megatron_tpu.serving import ServingEngine

    cfg = ModelConfig(
        num_layers=args.layers, hidden_size=args.hidden,
        num_attention_heads=args.heads,
        num_kv_heads=max(args.heads // 2, 1), vocab_size=args.vocab,
        seq_length=args.seq, max_position_embeddings=args.seq,
        make_vocab_size_divisible_by=64,
        compute_dtype="bfloat16").derived()
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    # eos_id=-1: no early EOS — free rows decode exactly --new tokens,
    # so the constrained-vs-free arms measure comparable volumes
    gen = Generator(params, cfg, eos_id=-1, pad_id=0)
    # block pool + prefix cache: the COW fan-out arm's alias seam
    serving = ServingConfig(num_slots=args.slots,
                            max_queue=max(4 * args.requests, 64),
                            kv_block_size=16,
                            enable_prefix_cache=True,
                            speculative_k=args.speculative_k)
    eng = ServingEngine(gen, serving.validate(cfg))
    rs = np.random.RandomState(0)
    prompts = [rs.randint(1, args.vocab, args.prompt).tolist()
               for _ in range(args.requests)]
    return eng, prompts


def _drain(eng, reqs):
    return [r.result(timeout=600)[0] for r in reqs]


def _arm_constrained_vs_free(eng, prompts, args) -> dict:
    from megatron_tpu.serving import SamplingOptions
    from megatron_tpu.serving.structured import compile_response_format
    sampling = SamplingOptions(temperature=0.0)
    fsm = compile_response_format(GRAMMAR, args.vocab)
    budget = max(args.new, fsm.max_path_len)

    def run(response_format):
        snap0 = eng.metrics.snapshot()
        t0 = time.monotonic()
        reqs = [eng.submit(p, budget, sampling, seed=i,
                           response_format=response_format)
                for i, p in enumerate(prompts)]
        outs = _drain(eng, reqs)
        wall = time.monotonic() - t0
        snap = eng.metrics.snapshot()
        d = {k: int(snap[k] - snap0[k])
             for k in ("tokens_generated", "decode_steps",
                       "mask_uploads", "structured_requests",
                       "grammar_dead_ends")}
        toks = [o[len(p):] for o, p in zip(outs, prompts)]
        return d, toks, wall

    free_d, _, free_wall = run(None)
    con_d, con_toks, con_wall = run(GRAMMAR)
    # validity is the point of the subsystem: every constrained stream
    # must replay FSM-legal AND parse (bounded grammar, covered budget)
    for t in con_toks:
        legal, _ = fsm.replay(t)
        assert legal, f"constrained stream is not FSM-legal: {t}"
        assert fsm.final_text_valid(t), \
            f"constrained output does not parse: {t}"
    # the mask-upload cadence seam: uploads track FSM state CHANGES
    # (at most one per slot-activation + one per committed token),
    # never one per decode step per slot
    transitions = sum(len(t) for t in con_toks) + len(con_toks)
    assert 0 < con_d["mask_uploads"] <= transitions, con_d
    assert free_d["mask_uploads"] == 0, free_d
    return {
        "grammar": GRAMMAR["pattern"],
        "free": {**free_d, "wall_s": round(free_wall, 3),
                 "tok_s": round(free_d["tokens_generated"]
                                / max(free_wall, 1e-9), 1)},
        "constrained": {**con_d, "wall_s": round(con_wall, 3),
                        "tok_s": round(con_d["tokens_generated"]
                                       / max(con_wall, 1e-9), 1)},
        "outputs_parse": True,  # the asserts above
        "constrained_overhead_x": round(
            max(con_wall, 1e-9) / max(free_wall, 1e-9), 2),
    }


def _arm_fanout(eng, prompts, args) -> dict:
    from megatron_tpu.serving import SamplingOptions
    sampling = SamplingOptions(temperature=0.8, top_k=8)
    n = min(4, args.slots)
    prompt = prompts[0]

    def counters(snap0, snap):
        return {k: int(snap[k] - snap0[k])
                for k in ("prefill_forward_tokens",
                          "prefill_tokens_saved", "prefix_hits",
                          "fanout_requests", "fanout_samples")}

    # serial arm: n independent n=1 submits, seeds seed+i — the exact
    # streams the fan-out arm must reproduce. Sequential on purpose:
    # concurrent serial submits would share the prefix cache and blur
    # the prefill-savings A/B.
    snap0 = eng.metrics.snapshot()
    t0 = time.monotonic()
    serial_out = []
    for i in range(n):
        r = eng.submit(prompt, args.new, sampling, seed=7 + i)
        serial_out.append(r.result(timeout=600)[0])
    serial_wall = time.monotonic() - t0
    serial_d = counters(snap0, eng.metrics.snapshot())

    snap0 = eng.metrics.snapshot()
    t0 = time.monotonic()
    agg = eng.submit(prompt, args.new, sampling, seed=7, n=n, best_of=n)
    toks_list, _ = agg.result(timeout=600)
    fan_wall = time.monotonic() - t0
    fan_d = counters(snap0, eng.metrics.snapshot())

    # semantics: each sample token-exact vs its serially-seeded twin
    # (result() orders best-first; children are sample-index ordered)
    got = [list(c.prompt) + list(c.generated) for c in agg.children]
    assert got == serial_out, (
        "fan-out samples diverged from serial n=1 submissions — "
        f"{got} vs {serial_out}")
    assert sorted(map(tuple, toks_list)) == sorted(map(tuple, got))
    # the COW seam: ONE real prefill for n samples — every other
    # sample aliases the leader's blocks (block-aligned savings)
    assert fan_d["fanout_requests"] == 1 and fan_d["fanout_samples"] == n
    assert fan_d["prefill_tokens_saved"] > 0, fan_d
    assert fan_d["prefill_forward_tokens"] < n * len(prompt), fan_d
    return {
        "n": n,
        "serial": {**serial_d, "wall_s": round(serial_wall, 3)},
        "fanout": {**fan_d, "wall_s": round(fan_wall, 3)},
        "samples_token_exact": True,  # the asserts above
        "prefill_reduction_x": round(
            max(serial_d["prefill_forward_tokens"], 1)
            / max(fan_d["prefill_forward_tokens"], 1), 2),
        "fanout_speedup_x": round(
            max(serial_wall, 1e-9) / max(fan_wall, 1e-9), 2),
    }


def main(argv=None):
    ensure_compile_cache()
    p = argparse.ArgumentParser("bench_structured", description=__doc__)
    p.add_argument("--out", default="/tmp/bench_structured.log")
    p.add_argument("--smoke", action="store_true",
                   help="tiny shapes for the CPU harness tier")
    p.add_argument("--requests", type=int, default=8)
    # NOT a multiple of the 16-token block: a whole-prompt prefix hit
    # caps at plen-1, so a block-aligned prompt would round the COW
    # alias down to zero blocks and hide the fan-out savings
    p.add_argument("--prompt", type=int, default=24)
    p.add_argument("--new", type=int, default=24)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--speculative_k", type=int, default=0,
                   help="compose the grammar gate with self-drafting "
                        "(draft tokens violating the FSM fail verify)")
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--hidden", type=int, default=128)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--seq", type=int, default=256)
    args = p.parse_args(argv)
    if args.smoke:
        args.requests = min(args.requests, 4)
        args.new = min(args.new, 10)
        args.hidden, args.vocab, args.seq = 64, 128, 128

    import jax
    eng, prompts = _build(args)
    try:
        # warmup compiles prefill + decode (and verify when spec-k on)
        from megatron_tpu.serving import SamplingOptions
        eng.generate(prompts[0][:8], 2, SamplingOptions(temperature=0.0),
                     seed=0)
        constrained = _arm_constrained_vs_free(eng, prompts, args)
        fanout = _arm_fanout(eng, prompts, args)
        # ZERO new traces: free + constrained + fan-out all rode the
        # same compiled decode step (the tentpole's compile contract)
        decode_traces = int(getattr(eng, "_decode_traces", 1))
        assert decode_traces == 1, \
            f"grammar/fan-out traffic recompiled decode: {decode_traces}"
    finally:
        eng.close()

    dev = jax.devices()[0]
    record = {
        "bench": "structured_nbest",
        "device": getattr(dev, "device_kind", dev.platform),
        "requests": args.requests,
        "new_tokens": args.new,
        "speculative_k": args.speculative_k,
        "decode_compiles": 1,
        "constrained_vs_free": constrained,
        "n1_vs_n4": fanout,
    }
    line = json.dumps(record)
    print(line, flush=True)
    with open(args.out, "w") as f:
        f.write(line + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
