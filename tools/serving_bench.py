"""Concurrent-load micro-bench for the continuous-batching engine.

The training benches measure the MXU-bound path and bench_decode.py the
single-stream serving path; this measures the ENGINE under concurrent
load — the numbers a capacity plan needs: offered load vs sustained
throughput, TTFT percentiles, slot occupancy. Emits ONE BENCH-style
JSON record on stdout (and to --out), like bench.py.

Three modes:
- in-process (default): builds a model (random params at the given
  shape), drives `ServingEngine` directly at `--rps` offered load
  (0 = submit everything at once);
- `--url host:port`: fires the same load as concurrent HTTP PUTs at a
  RUNNING server (examples/serve.sh LOAD=1 wires this up). TTFT is not
  observable over the non-streaming HTTP contract, so the record
  carries whole-request latency percentiles instead;
- `--overload`: in-process engine driven past slot capacity with
  per-request deadlines and early shedding on
  (docs/serving.md "Overload & failure behavior") — reports shed rate,
  goodput (completions within deadline, per second), and p99 queue
  delay: the numbers an admission-control regression moves first.

  python tools/serving_bench.py [--requests N] [--slots N] [--rps R]
                                [--prompt N] [--new N] [--out FILE]
                                [--overload] [--deadline S]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from megatron_tpu.utils.compile_cache import ensure_compile_cache


def _percentile(vals, q):
    # same nearest-rank convention as the server's /metrics snapshot
    from megatron_tpu.serving.metrics import _percentile as p
    return p(sorted(vals), q)


def _build_workload(args, eos_id: int):
    """Shared model/generator/prompt setup for the in-process arms —
    one definition, so the engine and overload arms always measure
    the same workload shape."""
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
    gen = Generator(params, cfg, eos_id=eos_id, pad_id=0)
    rs = np.random.RandomState(0)
    prompts = [rs.randint(1, cfg.vocab_size,
                          size=rs.randint(max(args.prompt // 2, 1),
                                          args.prompt + 1)).tolist()
               for _ in range(args.requests)]
    return gen, prompts


def _pace(args, t0: float, i: int):
    """Offered-load pacing shared by the in-process arms."""
    if args.rps > 0:
        target = t0 + i / args.rps
        delay = target - time.monotonic()
        if delay > 0:
            time.sleep(delay)


def _bench_engine(args) -> dict:
    from megatron_tpu.config import ServingConfig
    from megatron_tpu.serving import SamplingOptions, ServingEngine

    gen, prompts = _build_workload(args, eos_id=0)
    serving = ServingConfig(num_slots=args.slots,
                            max_queue=max(args.requests, 64))

    with ServingEngine(gen, serving) as eng:
        # warmup: compile prefill buckets + the one decode step
        eng.generate(prompts[0], 2,
                     SamplingOptions(temperature=1.0), seed=0)
        t0 = time.monotonic()
        reqs = []
        for i, p in enumerate(prompts):
            _pace(args, t0, i)
            reqs.append(eng.submit(p, args.new,
                                   SamplingOptions(temperature=1.0),
                                   seed=i))
        gen_tokens = 0
        for r in reqs:
            toks, _ = r.result(timeout=600)
            gen_tokens += len(toks) - len(r.prompt)
        wall = time.monotonic() - t0
        ttfts = [r.ttft for r in reqs if r.ttft is not None]
        snap = eng.metrics.snapshot()
    return {
        "bench": "serving", "mode": "engine",
        "slots": args.slots, "requests": args.requests,
        "offered_rps": args.rps,
        "prompt_len_max": args.prompt, "new_tokens": args.new,
        "wall_s": round(wall, 3),
        "tokens_per_s": round(gen_tokens / max(wall, 1e-9), 2),
        "ttft_p50_ms": round(_percentile(ttfts, 0.50) * 1e3, 1),
        "ttft_p95_ms": round(_percentile(ttfts, 0.95) * 1e3, 1),
        "slot_occupancy": round(snap["slot_occupancy"], 3),
        "decode_steps": int(snap["decode_steps"]),
    }


def _bench_overload(args) -> dict:
    """Offered load > slot capacity: every request carries a deadline,
    the engine sheds what cannot make it (`shed_on_overload`) and
    504s what expires anyway. Goodput counts completions WITHIN the
    deadline — the engine enforces it, so every completion qualifies."""
    from megatron_tpu.config import ServingConfig
    from megatron_tpu.serving import (DeadlineExceededError,
                                      QueueFullError, SamplingOptions,
                                      ServingEngine)

    # eos_id=-1: deterministic request lifetimes, so "offered load vs
    # capacity" is controlled by --requests/--new, not sampling luck
    gen, prompts = _build_workload(args, eos_id=-1)
    serving = ServingConfig(num_slots=args.slots,
                            max_queue=max(args.requests, 64),
                            shed_on_overload=True,
                            request_deadline_s=args.deadline)

    with ServingEngine(gen, serving) as eng:
        # warmup compiles AND seeds the shed estimator's service-time
        # EWMA (it never sheds before the first observed completion);
        # a per-request deadline override keeps the compile-heavy
        # warmup from 504ing against the measured arm's tight default
        eng.submit(prompts[0], args.new,
                   SamplingOptions(temperature=1.0), seed=0,
                   deadline_s=600.0).result(timeout=600)
        t0 = time.monotonic()
        reqs, shed = [], 0
        for i, p in enumerate(prompts):
            _pace(args, t0, i)
            try:
                reqs.append(eng.submit(p, args.new,
                                       SamplingOptions(temperature=1.0),
                                       seed=i))
            except QueueFullError:  # shed (or bounded-queue overflow)
                shed += 1
        good, expired = 0, 0
        for r in reqs:
            try:
                r.result(timeout=600)
                good += 1
            except DeadlineExceededError:
                expired += 1
        wall = time.monotonic() - t0
        snap = eng.metrics.snapshot()
    return {
        "bench": "serving", "mode": "overload",
        "slots": args.slots, "requests": args.requests,
        "offered_rps": args.rps, "deadline_s": args.deadline,
        "prompt_len_max": args.prompt, "new_tokens": args.new,
        "wall_s": round(wall, 3),
        "shed": shed, "expired_504": expired,
        "shed_rate": round(shed / max(args.requests, 1), 3),
        "goodput_rps": round(good / max(wall, 1e-9), 2),
        "goodput_frac": round(good / max(args.requests, 1), 3),
        "queue_wait_p99_ms": round(snap["queue_wait_p99_ms"], 1),
        "queue_wait_p50_ms": round(snap["queue_wait_p50_ms"], 1),
    }


def _bench_url(args) -> dict:
    import urllib.request

    lat, lock = [], threading.Lock()
    gen_tokens = [0]
    rejected = [0]  # 429s — real backpressure, reported, not hidden
    failed = [0]    # anything else (4xx/5xx/transport)
    prompt_text = "the quick brown fox " * max(args.prompt // 8, 1)

    def put(payload):
        req = urllib.request.Request(
            f"http://{args.url}/api", data=json.dumps(payload).encode(),
            method="PUT", headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=600) as resp:
            return json.loads(resp.read())

    # segments come back as prompt + generated; learn the PROMPT's
    # tokenized length once (tokens_to_generate=0 echoes it) so
    # tokens_per_s counts GENERATED tokens only, comparable with the
    # in-process engine mode
    plen = len(put({"prompts": [prompt_text],
                    "tokens_to_generate": 0})["segments"][0])

    def one(i):
        import urllib.error
        t = time.monotonic()
        try:
            out = put({"prompts": [prompt_text],
                       "tokens_to_generate": args.new,
                       "temperature": 1.0, "random_seed": i})
        except urllib.error.HTTPError as e:
            with lock:
                (rejected if e.code == 429 else failed)[0] += 1
            return
        except Exception:
            with lock:
                failed[0] += 1
            return
        dt = time.monotonic() - t
        with lock:
            lat.append(dt)
            gen_tokens[0] += sum(max(len(s) - plen, 0)
                                 for s in out.get("segments", []))

    t0 = time.monotonic()
    threads = []
    for i in range(args.requests):
        if args.rps > 0:
            target = t0 + i / args.rps
            delay = target - time.monotonic()
            if delay > 0:
                time.sleep(delay)
        th = threading.Thread(target=one, args=(i,), daemon=True)
        th.start()
        threads.append(th)
    for th in threads:
        th.join(timeout=600)
    wall = time.monotonic() - t0
    return {
        "bench": "serving", "mode": "http", "url": args.url,
        "requests": args.requests, "offered_rps": args.rps,
        "completed": len(lat), "rejected_429": rejected[0],
        "failed": failed[0],
        "wall_s": round(wall, 3),
        "tokens_per_s": round(gen_tokens[0] / max(wall, 1e-9), 2),
        "latency_p50_ms": round(_percentile(lat, 0.50) * 1e3, 1),
        "latency_p95_ms": round(_percentile(lat, 0.95) * 1e3, 1),
    }


def main(argv=None):
    ensure_compile_cache()
    p = argparse.ArgumentParser("serving_bench", description=__doc__)
    p.add_argument("--out", default="/tmp/serving_bench.log")
    p.add_argument("--url", default=None,
                   help="host:port of a RUNNING server; omit for the "
                        "in-process engine bench")
    p.add_argument("--requests", type=int, default=32)
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--rps", type=float, default=0.0,
                   help="offered load, requests/s (0 = all at once)")
    p.add_argument("--prompt", type=int, default=64,
                   help="max prompt length (engine mode draws uniform "
                        "lengths in [prompt/2, prompt])")
    p.add_argument("--new", type=int, default=32)
    p.add_argument("--overload", action="store_true",
                   help="overload arm: offered load > slot capacity "
                        "with deadlines + early shedding; reports shed "
                        "rate, goodput, p99 queue delay")
    p.add_argument("--deadline", type=float, default=2.0,
                   help="per-request deadline for the overload arm (s)")
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--hidden", type=int, default=256)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--vocab", type=int, default=1024)
    p.add_argument("--seq", type=int, default=512)
    args = p.parse_args(argv)

    if args.url:
        record = _bench_url(args)
    elif args.overload:
        record = _bench_overload(args)
    else:
        record = _bench_engine(args)
    line = json.dumps(record)
    print(line, flush=True)
    with open(args.out, "w") as f:
        f.write(line + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
