"""Launch the REST text-generation server from a checkpoint.

TPU-native port of /root/reference/tools/run_text_generation_server.py:60-84.

  python tools/run_text_generation_server.py --load ckpts/llama7b \
      --tokenizer_type SentencePieceTokenizer --tokenizer_model tok.model \
      --port 5000
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from megatron_tpu.utils.compile_cache import ensure_compile_cache
ensure_compile_cache()


def main(argv=None):
    import jax

    from megatron_tpu.data import build_tokenizer
    from megatron_tpu.inference.generation import Generator
    from megatron_tpu.inference.server import MegatronServer
    from megatron_tpu.models import language_model as lm
    from megatron_tpu.training import checkpointing as ckpt
    from megatron_tpu.training.train_step import TrainState

    p = argparse.ArgumentParser()
    p.add_argument("--load", default=None,
                   help="checkpoint root to serve (required unless "
                        "--fleet: a front tier holds no weights)")
    p.add_argument("--tokenizer_type", default="SentencePieceTokenizer")
    p.add_argument("--tokenizer_model", default=None)
    p.add_argument("--vocab_file", default=None)
    p.add_argument("--merge_file", default=None)
    p.add_argument("--host", default="0.0.0.0")
    p.add_argument("--port", type=int, default=5000)
    p.add_argument("--int8_weights", action="store_true",
                   help="serve with int8-resident transformer weights "
                        "(ops/quantized.quantize_weights): halves the "
                        "decode weight stream at ~0.5%% logit error. "
                        "MoE expert banks are NOT quantized (the router "
                        "dict is skipped), so for Mixtral-class models "
                        "(~95%% of params in experts) the reduction is "
                        "small — use --int8_kv there instead")
    p.add_argument("--int8_kv", action="store_true",
                   help="serve with an int8 KV cache: halves the cache "
                        "stream and residency — at 7B/32k the bf16 "
                        "cache alone outgrows a v5e")
    # continuous-batching engine knobs (megatron_tpu/serving)
    p.add_argument("--num_slots", type=int, default=None,
                   help="batch slots in the persistent decode grid = "
                        "max concurrently-decoding requests. Default: "
                        "up to 8, clamped to what free device memory "
                        "fits AFTER the weights (the slot-grid pool is "
                        "allocated eagerly — 8 full-context Llama-7B "
                        "bf16 slots alone are ~17 GB)")
    p.add_argument("--max_queue", type=int, default=64,
                   help="bounded admission queue; overflow returns 429")
    p.add_argument("--serving_max_len", type=int, default=None,
                   help="per-slot KV region length (prompt+generated); "
                        "defaults to max_position_embeddings")
    p.add_argument("--request_deadline_s", type=float, default=None,
                   help="per-request wall-clock deadline: queued or "
                        "running requests past it are evicted and "
                        "answer 504 (None = no deadline)")
    p.add_argument("--serial", action="store_true",
                   help="serve with the reference's serial one-lock "
                        "path instead of the continuous-batching engine")
    p.add_argument("--adapter_slots", type=int, default=0,
                   help="multi-tenant LoRA serving: device-resident "
                        "adapters servable concurrently (0 disables; "
                        "docs/serving.md 'Multi-tenant LoRA serving')")
    p.add_argument("--adapter_rank", type=int, default=8,
                   help="LoRA rank the adapter bank allocates for")
    p.add_argument("--adapter_host_bytes", type=int, default=0,
                   help="host-RAM overflow budget for evicted adapters")
    p.add_argument("--adapter_dir", type=str, default=None,
                   help="directory of adapter .npz exports (finetune "
                        "--lora_rank) registered at start; adapter_id "
                        "= file stem")
    p.add_argument("--serving_tp", type=int, default=1,
                   help="tensor-parallel width of the serving mesh "
                        "(weights + KV arena shard over 'tp' on the "
                        "head axes; 1 = single-device engine — "
                        "docs/serving.md 'Sharded & disaggregated "
                        "serving')")
    p.add_argument("--kv_block_size", type=int, default=None,
                   help="block-granular KV pool (required by "
                        "--disaggregate_prefill; docs/serving.md)")
    p.add_argument("--disaggregate_prefill", action="store_true",
                   help="prefill and decode on separate serving_tp-"
                        "wide chip groups; the handoff moves only the "
                        "sequence's live KV blocks (needs "
                        "--kv_block_size)")
    p.add_argument("--watch_checkpoints", action="store_true",
                   help="live-weight serving: poll --load's tracker "
                        "and hot-swap (or rolling-upgrade the replica "
                        "fleet to) every newly published checkpoint — "
                        "trainers drive the server with zero operator "
                        "action (docs/serving.md 'Live weights & "
                        "rolling upgrade')")
    p.add_argument("--watch_interval_s", type=float, default=5.0,
                   help="tracker poll cadence for --watch_checkpoints")
    p.add_argument("--swap_timeout_s", type=float, default=120.0,
                   help="live-weight swap barrier budget: how long a "
                        "hot swap waits for in-flight work before it "
                        "cancels (typed refusal, engine keeps serving)")
    # networked front door (docs/serving.md "Front door": process-
    # boundary deployment; serving/remote.py)
    p.add_argument("--replica_mode", action="store_true",
                   help="run this server as one fleet replica process: "
                        "accepts the pre-tokenized prompt_tokens wire "
                        "format plus the /admin /invariants /affinity "
                        "control-plane routes a remote front tier "
                        "(--fleet) drives")
    p.add_argument("--fleet", type=str, default=None,
                   help="run as a thin FRONT TIER over remote replica "
                        "processes at these host:port addresses "
                        "(comma-separated): the prefix-affinity router "
                        "with health polling, typed transport faults, "
                        "token-exact failover, and rolling upgrades "
                        "over TCP — no weights load in this process")
    p.add_argument("--remote_connect_timeout_s", type=float, default=2.0,
                   help="fleet: per-call TCP connect (and health-probe "
                        "read) budget to a replica")
    p.add_argument("--remote_read_timeout_s", type=float, default=30.0,
                   help="fleet: per-call read budget on replica "
                        "responses and SSE inter-frame gaps")
    p.add_argument("--remote_max_retries", type=int, default=2,
                   help="fleet: bounded transport-level retries per "
                        "remote call (exponential backoff + jitter, "
                        "Retry-After honored); whole-request failover "
                        "to a survivor is governed by "
                        "--router_max_retries on top")
    p.add_argument("--remote_digest_interval_s", type=float, default=2.0,
                   help="fleet: refresh cadence of each replica's "
                        "prefix-affinity digest (GET /affinity) — "
                        "staleness only skews routing hints, never "
                        "tokens")
    args = p.parse_args(argv)
    if args.fleet and args.load:
        p.error("--fleet is a thin front tier over remote replicas; it "
                "loads no weights (drop --load)")
    if not args.fleet and not args.load:
        p.error("--load is required (or --fleet for a front tier)")
    if args.fleet and (args.serial or args.replica_mode):
        p.error("--fleet excludes --serial and --replica_mode: the "
                "front tier routes, it does not serve an engine")
    if args.replica_mode and args.serial:
        p.error("--replica_mode requires the serving engine (drop "
                "--serial)")
    if args.fleet:
        # the front tier needs only a tokenizer (text prompts in,
        # pre-tokenized prompt_tokens over the wire) and the router —
        # build neither model nor engine here
        from megatron_tpu.config import ServingConfig
        from megatron_tpu.data import build_tokenizer as _bt
        tokenizer = _bt(args.tokenizer_type, vocab_file=args.vocab_file,
                        merge_file=args.merge_file,
                        tokenizer_model=args.tokenizer_model)
        serving = ServingConfig(
            fleet=args.fleet,
            max_queue=args.max_queue,
            request_deadline_s=args.request_deadline_s,
            remote_connect_timeout_s=args.remote_connect_timeout_s,
            remote_read_timeout_s=args.remote_read_timeout_s,
            remote_max_retries=args.remote_max_retries,
            remote_digest_interval_s=args.remote_digest_interval_s,
            watch_checkpoints=(args.load if args.watch_checkpoints
                               else None),
            watch_interval_s=args.watch_interval_s).validate(None)
        server = MegatronServer(None, tokenizer, serving=serving)
        server.run(args.host, args.port)
        return
    if args.watch_checkpoints and args.serial:
        p.error("--watch_checkpoints requires the serving engine "
                "(drop --serial): the serial path has nothing to "
                "hot-swap")
    if args.watch_checkpoints and args.int8_weights:
        # the engine's swap stages the published FP params tree against
        # gen.params — an int8-resident engine holds the quantized tree
        # (different structure), so every publish would be refused and
        # weight_swap_failures would climb forever. Fail the flag combo
        # loudly instead of shipping a watcher that can never apply.
        p.error("--watch_checkpoints is unsupported with "
                "--int8_weights: hot swap stages the published fp "
                "checkpoint against the engine's params tree, and the "
                "int8-resident tree has a different structure — serve "
                "fp weights (--int8_kv stays available) or drop the "
                "watcher")
    if args.adapter_dir and (args.serial or args.adapter_slots <= 0):
        # fail loudly at the flag boundary: the serial path threads no
        # adapter bank, and without --adapter_slots there is no bank
        # to register into (server.engine would be None / bankless and
        # the registration loop below would crash unexplanatorily)
        p.error("--adapter_dir requires --adapter_slots > 0 and the "
                "serving engine (drop --serial)")

    cfg = ckpt.load_config_from_checkpoint(args.load)
    assert cfg is not None, f"no checkpoint under {args.load}"
    mcfg = cfg.model
    example = TrainState(
        params=jax.eval_shape(lambda: lm.model_init(jax.random.PRNGKey(0),
                                                    mcfg)),
        opt_state=None, iteration=0)
    tokenizer = build_tokenizer(
        args.tokenizer_type, vocab_file=args.vocab_file,
        merge_file=args.merge_file, tokenizer_model=args.tokenizer_model)
    import jax.numpy as jnp

    staged_version = None
    pending_bytes = 0  # weights staged host-side, not on the device yet
    if args.serial or args.int8_weights:
        # serial fallback needs device params anyway; the int8 path
        # quantizes on device and drops the fp originals below
        state, _, _ = ckpt.load_checkpoint(args.load, example,
                                           no_load_optim=True)
        assert state is not None, \
            f"failed to load checkpoint from {args.load}"
        params = state.params
        if args.int8_weights:
            from megatron_tpu.ops.quantized import quantize_weights
            params = quantize_weights(params)
            # drop the fp originals BEFORE serving: `state` would
            # otherwise pin them in device memory for the server's
            # whole lifetime, growing residency ~1.25x instead of
            # shrinking it ~4x
            state = None
    else:
        # HOST-FIRST staging (docs/serving.md "Live weights & rolling
        # upgrade"): params stay NumPy and the engine's placement
        # (sharded per group under --serving_tp/--disaggregate_prefill)
        # is the ONLY device residency — device 0 never pays
        # full-model + shard residency — and the served weight_version
        # (iteration + manifest digest) is known from startup. This is
        # the same mechanism hot swap uses.
        from megatron_tpu.serving.weights import stage_latest
        from megatron_tpu.utils.logging import print_rank_0
        staged = stage_latest(args.load, example.params)
        params = staged.params
        staged_version = staged.version
        pending_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
        print_rank_0(f"serving: staged weights host-side "
                     f"(version {staged_version.label}); device "
                     "residency = the engine's placement only")
    gen = Generator(params, mcfg, eos_id=tokenizer.eod,
                    kv_cache_dtype=jnp.int8 if args.int8_kv
                    else jnp.bfloat16)
    from megatron_tpu.config import ServingConfig
    num_slots = args.num_slots
    if num_slots is None and not args.serial:
        # size the eager slot-grid pool to the memory the weights leave
        # free (a fixed 8-slot default OOMs 7B-class serving on a v5e):
        # resident weights show in the device's bytes_in_use, staged
        # ones are subtracted by their byte count
        from megatron_tpu.serving.kv_pool import fit_num_slots
        from megatron_tpu.utils.logging import print_rank_0
        num_slots = fit_num_slots(
            mcfg, args.serving_max_len or mcfg.max_position_embeddings,
            dtype=jnp.int8 if args.int8_kv else jnp.bfloat16,
            block_size=args.kv_block_size,
            pending_bytes=pending_bytes, shards=args.serving_tp)
        print_rank_0(f"serving: auto-sized num_slots={num_slots} "
                     "(override with --num_slots)")
    if num_slots is None:  # serial fallback: engine never built
        num_slots = 8
    serving = ServingConfig(num_slots=num_slots,
                            max_queue=args.max_queue,
                            max_len=args.serving_max_len,
                            serial_fallback=args.serial,
                            request_deadline_s=args.request_deadline_s,
                            adapter_slots=args.adapter_slots,
                            adapter_rank=args.adapter_rank,
                            adapter_host_bytes=args.adapter_host_bytes,
                            serving_tp=args.serving_tp,
                            kv_block_size=args.kv_block_size,
                            disaggregate_prefill=args.disaggregate_prefill,
                            swap_timeout_s=args.swap_timeout_s,
                            replica_mode=args.replica_mode,
                            watch_checkpoints=(args.load
                                               if args.watch_checkpoints
                                               else None),
                            watch_interval_s=args.watch_interval_s
                            ).validate(mcfg)
    server = MegatronServer(gen, tokenizer, serving=serving,
                            weight_version=staged_version)
    # what the weights and the KV pool took, device by device: under
    # --serving_tp every device should hold about 1/tp of both
    from megatron_tpu.utils.logging import report_memory
    report_memory("serving")
    if args.adapter_dir:
        # pre-register every exported adapter: adapter_id = file stem,
        # validated eagerly (a corrupt export fails the server start,
        # not some later request's admission)
        import glob
        from megatron_tpu.utils.logging import print_rank_0
        for path in sorted(glob.glob(os.path.join(args.adapter_dir,
                                                  "*.npz"))):
            aid = os.path.splitext(os.path.basename(path))[0]
            server.engine.register_adapter(aid, path=path)
            print_rank_0(f"serving: registered adapter {aid!r} "
                         f"from {path}")
    server.run(args.host, args.port)


if __name__ == "__main__":
    main()
