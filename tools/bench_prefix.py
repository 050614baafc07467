"""Prefix-cache + chunked-prefill A/B micro-bench.

Drives the continuous-batching engine over a SHARED-PREFIX workload
(the system-prompt / few-shot-template serving shape the prefix cache
exists for) in three arms on the same seeded request set:

- baseline: cache off, monolithic prefill;
- prefix:   --enable_prefix_cache — hit-rate, prefix tokens reused,
            REAL prefill forward tokens (the engine's
            `prefill_forward_tokens` seam, not wall-clock);
- chunked:  prefix cache + `prefill_chunk` — the Sarathi-Serve arm,
            long-prompt prefill interleaved with decode.

Plus a MULTI-TURN-CHAT arm pair (shared-system-prompt sessions coming
back for a second turn — the production mix ROADMAP item 3 queues):
the same serial session schedule runs against a whole-region pool and
a block-granular pool (--block) of IDENTICAL byte size, and the
record reports each arm's retained-prefix hit rate at turn 2+. This
is the block refactor's capacity seam: whole-region retention is
bounded by the slot count (a retained chat costs a full cap region +
a grid row, so the LRU thrashes), while block retention pins only the
blocks each session's history covers — `retained_capacity_x` is the
hit-rate ratio, the slots-per-HBM-byte win at fixed pool bytes.

Reports per arm: hit rate, prefill tokens saved, prefill forward
tokens, TTFT p50/p95, tokens/s. On CPU the times are a harness smoke;
ON CHIP the forward-token delta is the prefill compute the cache
removed and the TTFT delta is what chunking buys queued work.

Emits ONE BENCH-style JSON record on stdout (and to --out), like the
other bench tools; runs in the bench.py extras chain.

  python tools/bench_prefix.py [--requests N] [--shared N] [--unique N]
                               [--slots N] [--new N] [--chunk N]
                               [--sessions N] [--block N] [--out FILE]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from megatron_tpu.utils.compile_cache import ensure_compile_cache


def _build(args):
    import jax
    import numpy as np

    from megatron_tpu.config import ModelConfig
    from megatron_tpu.inference.generation import Generator
    from megatron_tpu.models import language_model as lm

    cfg = ModelConfig(
        num_layers=args.layers, hidden_size=args.hidden,
        num_attention_heads=args.heads,
        num_kv_heads=max(args.heads // 2, 1), vocab_size=args.vocab,
        seq_length=args.seq, max_position_embeddings=args.seq,
        make_vocab_size_divisible_by=64,
        compute_dtype="bfloat16").derived()
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    gen = Generator(params, cfg, eos_id=0, pad_id=0)
    rs = np.random.RandomState(0)
    shared = rs.randint(1, cfg.vocab_size, args.shared).tolist()
    prompts = [shared + rs.randint(1, cfg.vocab_size,
                                   args.unique).tolist()
               for _ in range(args.requests)]
    return gen, prompts


def _run_arm(gen, prompts, args, *, prefix: bool, chunk) -> dict:
    from megatron_tpu.config import ServingConfig
    from megatron_tpu.serving import SamplingOptions, ServingEngine

    serving = ServingConfig(
        num_slots=args.slots, max_queue=max(len(prompts), 64),
        enable_prefix_cache=prefix, prefill_chunk=chunk)
    with ServingEngine(gen, serving) as eng:
        # warmup: compile prefill/chunk buckets + the one decode trace
        # (in the cache arms it also RETAINS the shared prefix, so the
        # burst measures a warm cache — the steady-state serving shape)
        eng.generate(prompts[0], 2, SamplingOptions(temperature=1.0),
                     seed=0)
        snap0 = eng.metrics.snapshot()  # counters exclude the warmup
        t0 = time.monotonic()
        reqs = [eng.submit(p, args.new,
                           SamplingOptions(temperature=1.0), seed=i)
                for i, p in enumerate(prompts)]
        outs = [r.result(timeout=600)[0] for r in reqs]
        wall = time.monotonic() - t0
        snap = eng.metrics.snapshot()

    def delta(k):
        return int(snap[k] - snap0[k])

    return {
        "enable_prefix_cache": prefix,
        "prefill_chunk": chunk,
        "outputs": outs,  # popped before emit; arms must agree
        "prefix_hits": delta("prefix_hits"),
        "hit_rate": round(delta("prefix_hits") / max(len(prompts), 1),
                          3),
        "prefix_hit_tokens": delta("prefix_hit_tokens"),
        "prefill_tokens_saved": delta("prefill_tokens_saved"),
        "prefill_forward_tokens": delta("prefill_forward_tokens"),
        "prefill_chunks": delta("prefill_chunks"),
        # reservoir percentiles include the warmup's one sample (a
        # deque can't be delta'd); 1-in-N noise, called out here
        "ttft_p50_ms": round(snap["ttft_p50_ms"], 2),
        "ttft_p95_ms": round(snap["ttft_p95_ms"], 2),
        "tokens_per_s": round(delta("tokens_generated")
                              / max(wall, 1e-9), 1),
    }


def _run_multiturn_arm(gen, args, block) -> dict:
    """Serial multi-turn chat sessions (system prompt + per-session
    opener, then each session returns extending its full history) —
    the retained-prefix capacity probe. Pool bytes are FIXED across
    arms (same slots x max_len); only the retention granularity
    changes with `block`."""
    import numpy as np

    from megatron_tpu.config import ServingConfig
    from megatron_tpu.serving import SamplingOptions, ServingEngine

    rs = np.random.RandomState(7)
    vocab = gen.cfg.vocab_size
    system = rs.randint(1, vocab, args.shared).tolist()
    # per-session opener spans one whole block, so a session's OWN
    # history match (system + opener) is distinguishable from the
    # shared-system-block match every sibling session provides
    opener_len = args.block
    own_len = args.shared + opener_len
    openers = [rs.randint(1, vocab, opener_len).tolist()
               for _ in range(args.sessions)]
    followups = [rs.randint(1, vocab, opener_len).tolist()
                 for _ in range(args.sessions)]
    greedy = SamplingOptions(temperature=0.0)
    serving = ServingConfig(
        num_slots=args.slots, max_queue=max(args.sessions, 64),
        enable_prefix_cache=True, kv_block_size=block)
    with ServingEngine(gen, serving) as eng:
        t0 = time.monotonic()
        histories = []
        for i, opener in enumerate(openers):  # turn 1, serial
            toks, _ = eng.generate(system + opener, args.new, greedy,
                                   seed=i, timeout=600)
            histories.append(toks)
        retained_after_t1 = eng.pool.retained_count()
        snap0 = eng.metrics.snapshot()
        outs, own_hits = [], 0
        for i, hist in enumerate(histories):  # turn 2, serial
            req = eng.submit(hist + followups[i], args.new, greedy,
                             seed=100 + i)
            outs.append(req.result(timeout=600)[0])
            # a RETAINED-SESSION hit reuses the session's own history
            # (>= system + opener); a shared-system-block hit off a
            # sibling's entry is not retained-capacity, don't count it
            own_hits += int(req.prefix_len >= own_len)
        wall = time.monotonic() - t0
        snap = eng.metrics.snapshot()
        pool_bytes = eng.pool.nbytes()

    def delta(k):
        return int(snap[k] - snap0[k])

    return {
        "kv_block_size": block,
        "pool_bytes": int(pool_bytes),
        "outputs": outs,  # popped before emit; arms must agree
        "retained_after_turn1": int(retained_after_t1),
        "turn2_hits": delta("prefix_hits"),
        "turn2_session_hits": own_hits,
        "turn2_session_hit_rate": round(own_hits
                                        / max(args.sessions, 1), 3),
        "turn2_hit_tokens": delta("prefix_hit_tokens"),
        "prefill_tokens_saved": delta("prefill_tokens_saved"),
        "prefill_forward_tokens": delta("prefill_forward_tokens"),
        "kv_blocks_retained": snap["kv_blocks_retained"],
        "kv_bytes_wasted": snap["kv_bytes_wasted"],
        "tokens_per_s": round(delta("tokens_generated")
                              / max(wall, 1e-9), 1),
    }


def main(argv=None):
    ensure_compile_cache()
    p = argparse.ArgumentParser("bench_prefix", description=__doc__)
    p.add_argument("--out", default="/tmp/bench_prefix.log")
    p.add_argument("--requests", type=int, default=12)
    p.add_argument("--shared", type=int, default=48,
                   help="shared-prefix length (system prompt stand-in)")
    p.add_argument("--unique", type=int, default=8,
                   help="per-request unique suffix length")
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--new", type=int, default=16)
    p.add_argument("--chunk", type=int, default=16,
                   help="prefill_chunk for the chunked arm")
    p.add_argument("--sessions", type=int, default=8,
                   help="multi-turn arm: chat sessions (each returns "
                        "for a second turn extending its history)")
    p.add_argument("--block", type=int, default=16,
                   help="multi-turn arm: kv_block_size for the "
                        "block-granular pool (vs whole-region at the "
                        "same pool bytes)")
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--hidden", type=int, default=128)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--seq", type=int, default=256)
    args = p.parse_args(argv)

    import jax
    gen, prompts = _build(args)
    base = _run_arm(gen, prompts, args, prefix=False, chunk=None)
    pref = _run_arm(gen, prompts, args, prefix=True, chunk=None)
    chnk = _run_arm(gen, prompts, args, prefix=True, chunk=args.chunk)
    # the cache must be a scheduling change, not a semantics change —
    # every arm replays the same seeded requests token-for-token
    assert pref.pop("outputs") == base.pop("outputs") == \
        chnk.pop("outputs"), "arms diverged: prefix cache is UNSOUND"

    # multi-turn-chat capacity arm pair: whole-region vs blocks at the
    # same pool bytes — the cache must stay a scheduling change here
    # too, so the arms' (greedy, seeded) outputs must agree
    mt_whole = _run_multiturn_arm(gen, args, None)
    mt_blocks = _run_multiturn_arm(gen, args, args.block)
    assert mt_blocks.pop("outputs") == mt_whole.pop("outputs"), (
        "multi-turn arms diverged: block-granular retention is UNSOUND")

    dev = jax.devices()[0]
    record = {
        "bench": "prefix_cache",
        "device": getattr(dev, "device_kind", dev.platform),
        "requests": args.requests,
        "shared": args.shared,
        "unique": args.unique,
        "baseline": base,
        "prefix": pref,
        "prefix_chunked": chnk,
        "forward_token_reduction_x": round(
            base["prefill_forward_tokens"]
            / max(pref["prefill_forward_tokens"], 1), 2),
        "multiturn_whole_region": mt_whole,
        "multiturn_blocks": mt_blocks,
        # retained-prefix capacity at fixed HBM: turn-2 SESSION
        # hit-rate ratio (the whole-region arm's rate is floored at
        # one hit to keep the ratio finite when it thrashes to zero)
        "retained_capacity_x": round(
            mt_blocks["turn2_session_hit_rate"]
            / max(mt_whole["turn2_session_hit_rate"],
                  1.0 / max(args.sessions, 1)), 2),
    }
    line = json.dumps(record)
    print(line, flush=True)
    with open(args.out, "w") as f:
        f.write(line + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
