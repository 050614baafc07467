"""Megatron-compatible CLI flag surface -> MegatronConfig.

TPU-native bridge for the reference's argparse config system
(ref: megatron/arguments.py:14-1073 — ~170 flags in 16 groups, stored in a
mutable global namespace). Here flags parse into the frozen dataclass tree
(megatron_tpu/config.py); the flag NAMES match the reference so launch
scripts port by changing only the launcher. `extra_args_provider` mirrors
the extension hook (ref: megatron/arguments.py:14-20, finetune.py:129-138).
Validation/derivation lives in MegatronConfig.validate
(ref: arguments.py:52-345 validate_args).
"""
from __future__ import annotations

import argparse
from typing import Callable, Optional

from megatron_tpu.config import (DataConfig, MegatronConfig, ModelConfig,
                                 OptimizerConfig, ParallelConfig,
                                 ResilienceConfig, ServingConfig,
                                 TrainingConfig)


def build_parser(extra_args_provider: Optional[Callable] = None
                 ) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="megatron_tpu",
                                allow_abbrev=False)

    g = p.add_argument_group("model")
    # default=None so an EXPLICIT "--num_layers 2" is distinguishable from
    # a defaulted one (resolved to 2 in _apply_compat after the
    # --encoder_num_layers alias is considered)
    g.add_argument("--num_layers", type=int, default=None)
    g.add_argument("--hidden_size", type=int, default=128)
    g.add_argument("--ffn_hidden_size", type=int, default=None)
    g.add_argument("--num_attention_heads", type=int, default=4)
    g.add_argument("--num_attention_heads_kv", type=int, default=None,
                   dest="num_kv_heads")
    g.add_argument("--kv_channels", type=int, default=None)
    # default None so model presets keep their native seq_length
    g.add_argument("--seq_length", type=int, default=None)
    g.add_argument("--max_position_embeddings", type=int, default=None)
    g.add_argument("--make_vocab_size_divisible_by", type=int, default=128)
    g.add_argument("--layernorm_epsilon", type=float, default=1e-5,
                   dest="norm_epsilon")
    g.add_argument("--use_rms_norm", action="store_true")
    g.add_argument("--use_post_ln", action="store_true")
    g.add_argument("--use_bias", action="store_true")
    g.add_argument("--parallel_attn", action="store_true")
    g.add_argument("--parallel_layernorm", action="store_true")
    g.add_argument("--use_rotary_emb", action="store_true", default=True)
    g.add_argument("--no_rotary_emb", dest="use_rotary_emb",
                   action="store_false")
    g.add_argument("--position_embedding", action="store_true",
                   dest="use_position_embedding")
    g.add_argument("--rope_theta", type=float, default=10000.0)
    # Mistral-style banded causal attention (None = full causal)
    g.add_argument("--sliding_window", type=int, default=None)
    g.add_argument("--rope_scaling_factor", type=float, default=1.0)
    g.add_argument("--glu_activation", type=str, default=None,
                   choices=["swiglu", "geglu", "reglu", "liglu"])
    g.add_argument("--activation", type=str, default=None)
    g.add_argument("--hidden_dropout", type=float, default=0.0)
    g.add_argument("--attention_dropout", type=float, default=0.0)
    g.add_argument("--lima_dropout", action="store_true")
    g.add_argument("--drop_path_rate", type=float, default=0.0)
    g.add_argument("--tie_embed_logits", action="store_true")
    g.add_argument("--init_method_std", type=float, default=0.02)
    g.add_argument("--bf16", action="store_true")
    g.add_argument("--fp16", action="store_true")
    g.add_argument("--fp32", action="store_true")
    g.add_argument("--use_flash_attn", action="store_true")
    # explicit impl selection (beyond the reference's boolean): overrides
    # preset defaults in BOTH directions — e.g. `--model llama2-7b
    # --attention_impl dot` opts out of the preset's flash default
    g.add_argument("--attention_impl", type=str, default=None,
                   choices=["dot", "flash", "ring", "ulysses"])
    g.add_argument("--recompute_granularity", type=str, default="none",
                   choices=["none", "selective", "full"])
    # TPU-native counterpart of the reference's TE fp8 mode (the --fp8_*
    # flags below stay inert: v5e/v5p have no fp8 datapath; int8 is the
    # hardware's low-precision GEMM lever — see ops/quantized.py)
    g.add_argument("--quantized_gemm", type=str, default="none",
                   choices=["none", "int8"])
    # Mixture-of-Experts (beyond the reference — SURVEY.md §2.8 lists EP
    # as absent there; models/moe.py)
    g.add_argument("--num_experts", type=int, default=1)
    g.add_argument("--moe_top_k", type=int, default=2)
    g.add_argument("--moe_capacity_factor", type=float, default=1.25)
    g.add_argument("--moe_aux_loss_coeff", type=float, default=1e-2)
    g.add_argument("--moe_dispatch", type=str, default="sort",
                   choices=["sort", "dense", "dropless"])
    # a pattern of mixers (ModelConfig.layer_types: the published key, as a
    # comma-separated list) and how many of its leading layers are dense
    # (the published `num_dense_layers`): what a cut of a preset's depth
    # has to say with `--num_layers`
    g.add_argument("--layer_types", default=None,
                   type=lambda s: tuple(s.split(",")),
                   help="the mixer of each layer, e.g. "
                        "conv,full_attention,conv,conv,conv")
    g.add_argument("--num_dense_layers", type=int, default=0,
                   dest="first_k_dense_replace")
    g.add_argument("--model", type=str, default=None,
                   help="preset name (llama2-7b, falcon-40b, gpt2, ...)")

    g = p.add_argument_group("parallel")
    g.add_argument("--tensor_model_parallel_size", type=int, default=1,
                   dest="tensor_parallel")
    g.add_argument("--pipeline_model_parallel_size", type=int, default=1,
                   dest="pipeline_parallel")
    g.add_argument("--context_parallel_size", type=int, default=1,
                   dest="context_parallel")
    g.add_argument("--num_layers_per_virtual_pipeline_stage", type=int,
                   default=None)
    g.add_argument("--pipeline_schedule", type=str, default="1f1b",
                   choices=["1f1b", "gpipe"],
                   help="pp execution schedule: 1f1b bounds per-stage "
                        "memory by pp; gpipe is the lockstep fallback "
                        "(required for vpp>1 interleaving)")
    g.add_argument("--pipeline_store_activations", action="store_true",
                   help="1F1B: carry forward vjp residuals instead of "
                        "recomputing chunk forwards in the backward slot "
                        "(the reference's no-recompute default; ~1/3 less "
                        "pipeline compute, more memory)")
    g.add_argument("--sequence_parallel", action="store_true")
    g.add_argument("--expert_axis", type=str, default="tp",
                   choices=["tp", "dp"],
                   help="mesh axis the MoE expert bank shards over: tp "
                        "(default) or dp (GShard-style expert "
                        "parallelism over the data axis)")
    g.add_argument("--use_distributed_optimizer", action="store_true")
    g.add_argument("--context_parallel_algo", type=str, default="ring",
                   choices=["ring", "ulysses"],
                   help="cp>1 attention: K/V-rotation ring (no head "
                        "constraint) or all-to-all head-parallel ulysses "
                        "(heads %% cp == 0, lower comm volume)")

    g = p.add_argument_group("training")
    g.add_argument("--micro_batch_size", type=int, default=1)
    g.add_argument("--global_batch_size", type=int, default=None)
    g.add_argument("--rampup_batch_size", nargs=3, type=int, default=None)
    g.add_argument("--train_iters", type=int, default=100)
    g.add_argument("--eval_interval", type=int, default=1000)
    g.add_argument("--eval_iters", type=int, default=10)
    g.add_argument("--log_interval", type=int, default=10)
    g.add_argument("--save_interval", type=int, default=None)
    g.add_argument("--exit_interval", type=int, default=None)
    g.add_argument("--exit_duration_in_mins", type=float, default=None)
    g.add_argument("--seed", type=int, default=1234)
    # jax.profiler trace window (SURVEY.md §5 profiling)
    g.add_argument("--profile", action="store_true")
    g.add_argument("--profile_step_start", type=int, default=10)
    g.add_argument("--profile_step_end", type=int, default=12)
    g.add_argument("--profile_dir", type=str, default=None)
    g.add_argument("--save", type=str, default=None, dest="checkpoint_dir")
    g.add_argument("--load", type=str, default=None, dest="load_dir")
    g.add_argument("--finetune", action="store_true")
    g.add_argument("--no_load_optim", action="store_true")
    g.add_argument("--no_load_rng", action="store_true")
    g.add_argument("--use_checkpoint_args", action="store_true")
    g.add_argument("--wandb_logger", action="store_true")
    g.add_argument("--tensorboard_dir", type=str, default=None)
    g.add_argument("--sync_metrics", action="store_true",
                   help="fetch loss/found_inf every step (step-exact "
                        "debugging); default is ONE metrics transfer "
                        "per log window with the loop dispatching "
                        "ahead of the device (training/loop.py)")

    g = p.add_argument_group("optimizer")
    g.add_argument("--optimizer", type=str, default="adam",
                   choices=["adam", "sgd"])
    g.add_argument("--lr", type=float, default=3e-4)
    g.add_argument("--min_lr", type=float, default=0.0)
    g.add_argument("--lr_decay_style", type=str, default="cosine")
    g.add_argument("--lr_decay_iters", type=int, default=None)
    g.add_argument("--lr_warmup_iters", type=int, default=0)
    g.add_argument("--lr_warmup_fraction", type=float, default=None)
    g.add_argument("--weight_decay", type=float, default=0.01)
    g.add_argument("--start_weight_decay", type=float, default=None)
    g.add_argument("--end_weight_decay", type=float, default=None)
    g.add_argument("--weight_decay_incr_style", type=str, default="constant")
    g.add_argument("--adam_beta1", type=float, default=0.9)
    g.add_argument("--adam_beta2", type=float, default=0.999)
    g.add_argument("--adam_eps", type=float, default=1e-8)
    g.add_argument("--sgd_momentum", type=float, default=0.9)
    g.add_argument("--clip_grad", type=float, default=1.0)
    g.add_argument("--loss_scale", type=float, default=None)
    g.add_argument("--initial_loss_scale", type=float, default=2.0 ** 32)
    g.add_argument("--min_loss_scale", type=float, default=1.0)
    g.add_argument("--loss_scale_window", type=int, default=1000)
    g.add_argument("--hysteresis", type=int, default=2)
    g.add_argument("--log_num_zeros_in_grad", action="store_true")

    g = p.add_argument_group("data")
    g.add_argument("--data_path", nargs="*", default=None)
    g.add_argument("--split", type=str, default="969,30,1")
    g.add_argument("--tokenizer_type", type=str,
                   default="SentencePieceTokenizer")
    g.add_argument("--vocab_file", type=str, default=None)
    g.add_argument("--merge_file", type=str, default=None)
    g.add_argument("--tokenizer_model", type=str, default=None,
                   dest="tokenizer_model")
    g.add_argument("--vocab_size", type=int, default=32000)
    g.add_argument("--dataloader_type", type=str, default="single",
                   choices=["single", "cyclic"])
    g.add_argument("--num_workers", type=int, default=2)
    g.add_argument("--reset_position_ids", action="store_true")
    g.add_argument("--reset_attention_mask", action="store_true")
    g.add_argument("--eod_mask_loss", action="store_true")
    g.add_argument("--vocab_extra_ids", type=int, default=0)
    g.add_argument("--vocab_extra_ids_list", type=str, default=None)
    g.add_argument("--no_new_tokens", dest="new_tokens",
                   action="store_false", default=True)
    g.add_argument("--data_impl", type=str, default="mmap")
    g.add_argument("--strict_data", action="store_true",
                   help="fail fast (DatasetCorruptionError) on "
                        "out-of-bounds documents or corrupt blend "
                        "prefixes instead of the default "
                        "skip-and-count (docs/resilience.md)")
    g.add_argument("--mask_prob", type=float, default=0.15,
                   dest="masked_lm_prob",
                   help="masked-LM probability (ref: --mask_prob)")
    g.add_argument("--short_seq_prob", type=float, default=0.1)
    g.add_argument("--train_data_path", nargs="*", default=None)
    g.add_argument("--valid_data_path", nargs="*", default=None)
    g.add_argument("--test_data_path", nargs="*", default=None)

    g = p.add_argument_group(
        "resilience",
        "fault tolerance for long preemptible runs (docs/resilience.md)")
    g.add_argument("--no_checkpoint_integrity", action="store_true",
                   help="skip writing/verifying per-checkpoint SHA-256 "
                        "manifests")
    g.add_argument("--keep_last_k", type=int, default=None,
                   help="retain only the newest K iter_* checkpoints "
                        "(the last VALID one always survives)")
    g.add_argument("--io_retries", type=int, default=4,
                   help="max attempts for checkpoint/tracker I/O "
                        "(1 = no retry)")
    g.add_argument("--io_backoff_s", type=float, default=0.5)
    g.add_argument("--io_backoff_max_s", type=float, default=30.0)
    g.add_argument("--max_consecutive_nonfinite", type=int, default=3,
                   help="NaN/inf steps in a row before rolling back to "
                        "the last checkpoint (0 disables)")
    g.add_argument("--loss_spike_factor", type=float, default=None,
                   help="roll back when a finite loss exceeds this "
                        "multiple of the rolling mean (None disables)")
    g.add_argument("--loss_spike_window", type=int, default=32)
    g.add_argument("--max_rollbacks", type=int, default=2,
                   help="divergence rollbacks before aborting with "
                        "TrainingDivergedError")
    g.add_argument("--step_timeout_s", type=float, default=None,
                   help="hung-step watchdog deadline; on expiry dump "
                        "stacks, attempt a final checkpoint, exit with "
                        "--watchdog_exit_code (None disables)")
    g.add_argument("--watchdog_exit_code", type=int, default=43)
    g.add_argument("--request_deadline_s", type=float, default=None,
                   help="serving: per-request wall-clock deadline "
                        "(expired requests are evicted with a "
                        "504-style error)")
    g.add_argument("--decode_sync_interval", type=int, default=1,
                   help="serving: decode steps dispatched per host "
                        "sync — 1/K syncs per token, up to K-1 wasted "
                        "steps per finished request (docs/serving.md)")
    g.add_argument("--prefill_max_batch", type=int, default=8,
                   help="serving: max same-bucket admissions coalesced "
                        "into one batched prefill call (1 disables)")
    g.add_argument("--enable_prefix_cache", action="store_true",
                   help="serving: retain finished slots' KV on an LRU "
                        "and reuse bucket-aligned shared prefixes "
                        "through one on-device region copy (token-"
                        "exact vs off; rolling sliding-window pools "
                        "need --kv_block_size — docs/serving.md)")
    g.add_argument("--prefill_chunk", type=int, default=None,
                   help="serving: split prompts/suffixes longer than "
                        "this into chunks interleaved with decode "
                        "steps (bounds ITL of running requests during "
                        "long prefills; None = monolithic prefill)")
    g.add_argument("--retained_slots", type=int, default=None,
                   help="serving: prefix-cache retained-slot budget — "
                        "at most this many finished slots keep their "
                        "KV for reuse (None retains all; they are "
                        "reclaimed lazily when admission needs a slot)")
    g.add_argument("--kv_block_size", type=int, default=None,
                   help="serving: block-granular KV pool — carve each "
                        "slot's region into this many-token blocks "
                        "over one arena with a per-slot block map "
                        "resolved at dispatch (bit-identical outputs, "
                        "one decode compile). Retention pins blocks "
                        "instead of whole regions and holds no grid "
                        "row, prefix hits alias shared blocks, and "
                        "rolling pools become cloneable/preemptible. "
                        "Must divide the slot capacity; None keeps "
                        "whole-region layout (docs/serving.md)")
    g.add_argument("--block_native_attn", action="store_true",
                   help="serving: block-NATIVE decode attention — the "
                        "Pallas kernel reads the KV arena through the "
                        "per-slot block map directly, dropping the "
                        "per-step resolve/scatter full-pool bracket "
                        "(gather bytes -> 0 on the decode/verify hot "
                        "path) and scattering only the touched block "
                        "on append; token-exact vs off, one compile. "
                        "Inert without --kv_block_size; rejected on "
                        "sliding-window models (docs/serving.md)")
    g.add_argument("--speculative_k", type=int, default=0,
                   help="serving: speculative decoding — propose this "
                        "many draft tokens per running slot each "
                        "iteration (self-drafting n-gram prompt-lookup "
                        "by default) and verify all slots' drafts in "
                        "one [slots, k+1]-token forward; greedy output "
                        "stays token-exact vs non-speculative "
                        "(0 disables; unsupported on rolling pools — "
                        "docs/serving.md)")
    g.add_argument("--priority_levels", type=int, default=1,
                   help="serving: distinct request priority classes — "
                        "requests carry priority in [0, levels); "
                        "higher wins admission ordering and (with "
                        "--preemption) may evict lower-priority "
                        "running slots (1 = all requests equal)")
    g.add_argument("--shed_on_overload", action="store_true",
                   help="serving: fail a new request at SUBMIT time "
                        "(retryable 429 + Retry-After) when its "
                        "estimated queue delay already exceeds its "
                        "deadline, instead of queue-then-504 "
                        "(docs/serving.md overload section)")
    g.add_argument("--degrade_ladder", type=int, default=0,
                   help="serving: graceful-degradation brownout ladder "
                        "max level — under sustained overload walk "
                        "1: no speculative decoding, 2: + cap "
                        "best_of/max_new_tokens for new admissions, "
                        "3: + shed lowest priority class, 4: shed all, "
                        "with hysteresis on both edges (0 disables — "
                        "bit-identical to the ladderless engine; "
                        "docs/serving.md 'Overload, degradation & SLO "
                        "conformance')")
    g.add_argument("--slo_ttft_ms", type=float, default=None,
                   help="serving: TTFT SLO target in ms — first tokens "
                        "arriving later count slo_ttft_violations and "
                        "the request's tokens leave goodput_tokens "
                        "(observability only; None = unset)")
    g.add_argument("--slo_itl_p99_ms", type=float, default=None,
                   help="serving: inter-token-latency SLO target in ms "
                        "— a host-visible token gap beyond it counts "
                        "slo_itl_violations (observability only; "
                        "None = unset)")
    g.add_argument("--preemption", action="store_true",
                   help="serving: a queued higher-priority request "
                        "with no allocatable slot evicts the lowest-"
                        "priority running slot; the victim's KV parks "
                        "and it resumes token-exact later (rolling "
                        "pools need --kv_block_size)")
    g.add_argument("--max_engine_restarts", type=int, default=2,
                   help="serving: supervisor loop restarts after a "
                        "crashed/hung engine step before the crash-"
                        "loop circuit breaker trips (engine goes "
                        "unhealthy, submits 503)")
    g.add_argument("--engine_step_timeout_s", type=float, default=None,
                   help="serving: hung-iteration watchdog deadline — "
                        "no engine-loop progress within this many "
                        "seconds fails the in-flight requests and "
                        "restarts the loop (None disables; must "
                        "exceed the worst prefill compile time)")
    g.add_argument("--num_replicas", type=int, default=1,
                   help="serving: engine replicas behind the in-process "
                        "prefix-affinity router — requests route to the "
                        "replica whose prefix cache holds the longest "
                        "match (ties: least-loaded); unhealthy replicas "
                        "are ejected and their work retries on a "
                        "survivor (1 = no router, docs/serving.md "
                        "'Front door')")
    g.add_argument("--router_max_retries", type=int, default=2,
                   help="serving: bounded failover retries per request "
                        "before its error surfaces (503 only when "
                        "every replica is down)")
    g.add_argument("--replica_mode", action="store_true",
                   help="serving: run this server as one fleet replica "
                        "process — accepts the pre-tokenized "
                        "prompt_tokens wire format plus the /admin, "
                        "/invariants and /affinity control-plane "
                        "routes a remote front tier (--fleet) drives "
                        "(docs/serving.md 'Front door')")
    g.add_argument("--fleet", type=str, default=None,
                   help="serving: run the router as a thin front tier "
                        "over remote replica processes at these "
                        "host:port addresses (comma-separated) — "
                        "health polling, typed transport faults, "
                        "token-exact failover, and rolling upgrades "
                        "over TCP; this process loads no weights")
    g.add_argument("--remote_connect_timeout_s", type=float,
                   default=2.0,
                   help="serving (fleet): per-call TCP connect and "
                        "health-probe read budget to a replica")
    g.add_argument("--remote_read_timeout_s", type=float, default=30.0,
                   help="serving (fleet): per-call read budget on "
                        "replica responses and SSE inter-frame gaps")
    g.add_argument("--remote_max_retries", type=int, default=2,
                   help="serving (fleet): bounded transport-level "
                        "retries per remote call (backoff + jitter, "
                        "Retry-After honored); request-level failover "
                        "is --router_max_retries on top")
    g.add_argument("--remote_digest_interval_s", type=float,
                   default=2.0,
                   help="serving (fleet): refresh cadence of each "
                        "replica's prefix-affinity digest "
                        "(GET /affinity); staleness skews routing "
                        "hints only, never tokens")
    g.add_argument("--host_kv_bytes", type=int, default=0,
                   help="serving: host-RAM KV tier byte budget — "
                        "retained prefix block lists evicted under "
                        "block pressure demote to host memory "
                        "(checksum-verified on restore) and restore "
                        "via device_put on a later hit; needs "
                        "--enable_prefix_cache + --kv_block_size "
                        "(0 disables)")
    g.add_argument("--serving_tp", type=int, default=1,
                   help="serving: tensor-parallel width of the serving "
                        "mesh — weights, the KV arena, and prefill "
                        "subs shard over 'tp' on the head axes with "
                        "the same GSPMD rules training uses; dispatch "
                        "data (block map, lengths, sampling state) "
                        "stays replicated, so decode/verify/prefill "
                        "keep one compile each (1 = no serving mesh, "
                        "bit-identical; docs/serving.md 'Sharded & "
                        "disaggregated serving')")
    g.add_argument("--disaggregate_prefill", action="store_true",
                   help="serving: split prefill and decode onto "
                        "separate serving_tp-wide chip groups "
                        "(DistServe) — prompts prefill on the prefill "
                        "group and hand off to decode as a "
                        "device-to-device copy of the sequence's live "
                        "KV blocks only; needs --kv_block_size "
                        "(docs/serving.md)")
    g.add_argument("--prefill_tp", type=int, default=None,
                   help="serving: tensor-parallel width of the PREFILL "
                        "group (defaults to --serving_tp) — prefill is "
                        "compute-bound, so a disaggregated engine may "
                        "run it wider or narrower than decode; unequal "
                        "widths need --disaggregate_prefill, and the "
                        "handoff device_put reshards the kv-head axis "
                        "P->D in the one transfer (docs/serving.md "
                        "'Per-phase topology & placement')")
    g.add_argument("--decode_tp", type=int, default=None,
                   help="serving: tensor-parallel width of the DECODE "
                        "group (defaults to --serving_tp) — decode is "
                        "HBM-bound; see --prefill_tp")
    g.add_argument("--serving_pp", type=int, default=1,
                   help="serving: pipeline-stage count for the decode "
                        "group — the group's devices split into "
                        "serving_pp layer-stage sub-meshes (each "
                        "decode_tp wide); stage i holds layers "
                        "[i*L/S,(i+1)*L/S) plus embedding on stage 0 "
                        "and head/final-norm on the last stage, the "
                        "KV arena partitions on the layer axis, and "
                        "decode runs as a staged program chain with "
                        "one [slots, hidden] device_put between "
                        "stages; needs --kv_block_size and "
                        "num_layers divisible by serving_pp; 1 = no "
                        "staged topology, bit-identical "
                        "(docs/serving.md 'Pipeline-sharded serving')")
    g.add_argument("--pp_waves", type=int, default=1,
                   help="serving: interleaved wave count under "
                        "--serving_pp (1F1B on the slot grid) — the "
                        "slot grid splits into this many waves so "
                        "stage i works wave k while stage i+1 works "
                        "wave k-1; bubble fraction "
                        "(S-1)/(W+S-1) exports as pp_stage_bubble; "
                        "needs num_slots divisible by pp_waves")
    g.add_argument("--placement_auto", action="store_true",
                   help="serving: let serving/placement.py choose the "
                        "prefill:decode split and per-phase tp widths "
                        "from the replica's device budget at build, "
                        "re-planned from observed busy/queue/TTFT "
                        "signals ONLY at the rolling-upgrade drain "
                        "barrier; the chosen plan is exported through "
                        "health() and /metrics (needs "
                        "--disaggregate_prefill)")
    g.add_argument("--placement_budget", type=int, default=None,
                   help="serving: device budget per replica for "
                        "--placement_auto (the optimizer picks "
                        "prefill_tp + decode_tp <= budget; default = "
                        "what the explicit widths occupy)")
    g.add_argument("--adapter_slots", type=int, default=0,
                   help="serving: device-resident LoRA adapters "
                        "servable concurrently (multi-tenant serving, "
                        "docs/serving.md 'Multi-tenant LoRA serving') "
                        "— a per-slot adapter index selects each "
                        "request's A/B factors from a stacked bank "
                        "inside the one compiled decode step; 0 "
                        "disables (bit-identical engine)")
    g.add_argument("--adapter_rank", type=int, default=8,
                   help="serving: LoRA rank the adapter bank "
                        "allocates for (smaller exported ranks "
                        "zero-pad up; larger are rejected)")
    g.add_argument("--adapter_host_bytes", type=int, default=0,
                   help="serving: host-RAM overflow budget for "
                        "adapters evicted from a full bank "
                        "(checksum-verified on restore; a corrupt "
                        "copy reloads from disk — never wrong "
                        "weights; 0 = evictions drop to disk reload)")
    g.add_argument("--swap_timeout_s", type=float, default=120.0,
                   help="serving: how long a live-weight hot swap "
                        "waits for in-flight work to drain at the "
                        "swap barrier before it is cancelled (typed "
                        "refusal; the engine keeps serving — "
                        "docs/serving.md 'Live weights & rolling "
                        "upgrade')")
    g.add_argument("--watch_checkpoints", type=str, default=None,
                   help="serving: training checkpoint root to watch — "
                        "every newly published (tracker-named, "
                        "manifest-verified) checkpoint hot-swaps onto "
                        "the running engine, or rolling-upgrades the "
                        "replica fleet drain->swap->canary->re-admit "
                        "with zero 503s; a corrupt publish is refused "
                        "and retried only on the NEXT publish "
                        "(docs/serving.md)")
    g.add_argument("--watch_interval_s", type=float, default=5.0,
                   help="serving: tracker poll cadence for "
                        "--watch_checkpoints")
    g.add_argument("--lora_rank", type=int, default=0,
                   help="finetune: train ONLY LoRA low-rank adapter "
                        "factors at this rank (base frozen) and "
                        "export them for the serving adapter bank "
                        "(0 = normal full finetune)")
    g.add_argument("--lora_alpha", type=float, default=16.0,
                   help="finetune: LoRA alpha — the delta scales by "
                        "alpha/rank (folded at serving load)")
    g.add_argument("--lora_export", type=str, default=None,
                   help="finetune: path for the trained adapter .npz "
                        "(default <save>/adapter.npz)")

    g = p.add_argument_group(
        "reference compat",
        "reference flags accepted with equivalent TPU semantics")
    g.add_argument("--train_samples", type=int, default=None,
                   help="sample-based run length; converted to iters via "
                        "global_batch_size (ref: --train_samples)")
    g.add_argument("--lr_decay_samples", type=int, default=None)
    g.add_argument("--lr_warmup_samples", type=int, default=None)
    g.add_argument("--position_embedding_type", type=str, default=None,
                   choices=["rope", "rotary", "learned_absolute",
                            "absolute"])
    g.add_argument("--encoder_num_layers", type=int, default=None)
    g.add_argument("--encoder_seq_length", type=int, default=None)
    g.add_argument("--decoder_num_layers", type=int, default=None)
    g.add_argument("--decoder_seq_length", type=int, default=128,
                   dest="max_seq_length_dec")
    g.add_argument("--no_save_optim", action="store_true")
    g.add_argument("--no_save_rng", action="store_true")
    g.add_argument("--recompute_activations", action="store_true",
                   help="alias for --recompute_granularity selective")
    g.add_argument("--recompute_method", type=str, default=None,
                   choices=["uniform", "block"],
                   help="accepted; the scan-stacked formulation remats "
                        "uniformly per layer either way")
    g.add_argument("--recompute_num_layers", type=int, default=None)
    g.add_argument("--attention_softmax_in_fp32", action="store_true",
                   dest="softmax_compute_fp32", default=True)
    g.add_argument("--exit_signal_handler", action="store_true",
                   help="accepted; SIGTERM checkpoint-and-exit is always "
                        "installed")
    g.add_argument("--override_opt_param_scheduler", action="store_true",
                   help="accepted; CLI schedule always wins unless "
                        "--use_checkpoint_args")
    g.add_argument("--use_checkpoint_opt_param_scheduler",
                   action="store_true",
                   help="accepted; subsumed by --use_checkpoint_args")
    g.add_argument("--log_params_norm", action="store_true")
    g.add_argument("--log_timers_to_tensorboard", action="store_true")
    g.add_argument("--log_validation_ppl_to_tensorboard",
                   action="store_true")
    g.add_argument("--wandb_project", type=str, default=None)
    g.add_argument("--wandb_entity", type=str, default=None)
    g.add_argument("--wandb_id", type=str, default=None)
    g.add_argument("--wandb_resume", action="store_true")
    # retrieval stack paths (ref: arguments.py retriever/biencoder args;
    # the ict-specific ones live on pretrain_ict.py / tasks.main)
    g.add_argument("--bert_load", type=str, default=None)
    g.add_argument("--ict_load", type=str, default=None)
    g.add_argument("--biencoder_projection_dim", type=int, default=0)
    g.add_argument("--block_data_path", type=str, default=None)
    g.add_argument("--embedding_path", type=str, default=None)
    g.add_argument("--evidence_data_path", type=str, default=None)
    g.add_argument("--indexer_batch_size", type=int, default=128)
    g.add_argument("--indexer_log_interval", type=int, default=1000)
    g.add_argument("--retriever_report_topk_accuracies", nargs="+",
                   type=int, default=[])
    g.add_argument("--retriever_score_scaling", action="store_true")
    g.add_argument("--retriever_seq_length", type=int, default=256)

    # CUDA/cluster-mechanics flags that dissolve under XLA/TPU: accepted so
    # reference launch scripts run unmodified; a note is logged when one is
    # set (ref: arguments.py — fused-kernel toggles, NCCL/DDP knobs, fp8/TE,
    # vision/DINO, ADLR autoresume)
    for flag in _NOOP_FLAGS:
        p.add_argument(flag, nargs="?", const=True, default=None,
                       help=argparse.SUPPRESS)

    if extra_args_provider is not None:
        p = extra_args_provider(p)
    return p


# Reference flags with no TPU-side effect (the mechanism they tune does not
# exist under XLA: stream ordering, fused CUDA kernels, NCCL backends, fp8
# Transformer Engine, vision/DINO models, ADLR cluster autoresume).
_NOOP_FLAGS = [
    "--DDP_impl",  # local-vs-torch DDP choice; dp is a mesh axis here
    "--accumulate_allreduce_grads_in_fp32",  # grads are always fp32 here
    "--adlr_autoresume", "--adlr_autoresume_interval",
    "--barrier_with_L1_time",  # timers design differs (block_until_ready)
    "--apply_residual_connection_post_layernorm",
    "--classes_fraction", "--data_parallel_random_init",
    "--data_per_class_fraction",
    "--dino_bottleneck_size", "--dino_freeze_last_layer",
    "--dino_head_hidden_size", "--dino_local_crops_number",
    "--dino_local_img_size", "--dino_norm_last_layer",
    "--dino_teacher_temp", "--dino_warmup_teacher_temp",
    "--dino_warmup_teacher_temp_epochs",
    "--distribute_saved_activations", "--distributed_backend",
    "--empty_unused_memory_level", "--fp16_lm_cross_entropy",
    "--fp32_residual_connection",
    # fp8/TE: no fp8 datapath on v5e/v5p — the TPU-native low-precision
    # GEMM mode is --quantized_gemm int8 (ops/quantized.py)
    "--fp8_amax_compute_algo", "--fp8_amax_history_len", "--fp8_e4m3",
    "--fp8_hybrid", "--fp8_interval", "--fp8_margin", "--no_fp8_wgrad",
    "--head_lr_mult", "--img_h", "--img_w",
    "--inference_batch_times_seqlen_threshold",
    "--init_method_xavier_uniform", "--iter_per_epoch", "--local_rank",
    "--log_batch_size_to_tensorboard", "--log_memory_to_tensorboard",
    "--log_world_size_to_tensorboard", "--max_tokens_to_oom",
    "--no_async_tensor_model_parallel_allreduce",
    "--no_bias_dropout_fusion", "--no_bias_gelu_fusion",
    "--no_contiguous_buffers_in_local_ddp", "--no_data_sharding",
    # the fusion itself (wgrad_gemm_accum_fp32: dW summed into main_grad by
    # the product) is ops/grad_accum.py, in every step of several
    # micro-batches: nothing turns it off
    "--no_gradient_accumulation_fusion", "--no_initialization",
    "--mmap_warmup",  # np.memmap needs no page-in pass
    "--no_masked_softmax_fusion", "--no_persist_layer_norm",
    "--no_query_key_layer_scaling",
    "--sample_rate",  # BERT-dataset subsampling knob of the CUDA loader
    "--no_scatter_gather_tensors_in_pipeline",
    "--num_channels", "--num_classes", "--onnx_safe", "--patch_dim",
    "--pipeline_model_parallel_split_rank", "--standalone_embedding_stage",
    "--tensorboard_log_interval", "--tensorboard_queue_size",
    "--timing_log_level", "--timing_log_option", "--transformer_impl",
    "--use_cpu_initialization", "--use_one_sent_docs",
    "--use_ring_exchange_p2p",
]


def _pick(ns: argparse.Namespace, cls, **renames):
    import dataclasses
    fields = {f.name for f in dataclasses.fields(cls)}
    d = {k: v for k, v in vars(ns).items() if k in fields}
    d.update({k: v for k, v in renames.items() if v is not None})
    return d


def _apply_compat(args: argparse.Namespace) -> None:
    """Resolve reference-compat aliases into the native arg surface and
    warn for accepted-but-inert CUDA-mechanics flags."""
    # aliases (mutating the namespace keeps _pick/_preset logic unchanged);
    # an explicit --num_layers (even "--num_layers 2") beats
    # --encoder_num_layers; unset resolves to the alias, then to 2. The
    # sentinel tells the preset-override loop a resolved 2 was NOT explicit
    # (a preset's layer count must not be clobbered by the fallback default).
    # hasattr-guarded so re-running compat on the same namespace (e.g.
    # config_from_args called twice) stays idempotent.
    if not hasattr(args, "_num_layers_defaulted"):
        args._num_layers_defaulted = False
        if args.num_layers is None:
            enc = getattr(args, "encoder_num_layers", None)
            args.num_layers = enc if enc is not None else 2
            args._num_layers_defaulted = enc is None
    if getattr(args, "encoder_seq_length", None) and not args.seq_length:
        args.seq_length = args.encoder_seq_length
    if getattr(args, "recompute_activations", False) and \
            args.recompute_granularity == "none":
        args.recompute_granularity = "selective"
    pet = getattr(args, "position_embedding_type", None)
    if pet in ("rope", "rotary"):
        args.use_rotary_emb = True
    elif pet in ("learned_absolute", "absolute"):
        args.use_rotary_emb = False
        args.use_position_embedding = True
    # sample-based run length -> iterations (ref: --train_samples; the
    # reference's samples-mode microbatch calculator is equivalent to this
    # conversion when no batch rampup is active)
    if getattr(args, "train_samples", None):
        assert args.rampup_batch_size is None, (
            "--train_samples with --rampup_batch_size is not supported; "
            "use --train_iters")
        assert args.global_batch_size, (
            "--train_samples needs an explicit --global_batch_size (the "
            "derived gbs depends on dp size, which is unknown at parse "
            "time)")
        gbs = args.global_batch_size
        args.train_iters = -(-args.train_samples // gbs)
        if getattr(args, "lr_decay_samples", None) and \
                not args.lr_decay_iters:
            args.lr_decay_iters = -(-args.lr_decay_samples // gbs)
        if getattr(args, "lr_warmup_samples", None) and \
                not args.lr_warmup_iters:
            args.lr_warmup_iters = -(-args.lr_warmup_samples // gbs)
    if args.data_path and getattr(args, "train_data_path", None):
        raise SystemExit(
            "--data_path and --train_data_path are mutually exclusive — "
            "pick one train corpus (ref: arguments.py validate_args). "
            "--valid/test_data_path MAY combine with --data_path: "
            "data_path trains, the per-split paths evaluate.")
    # inert flags: say so once, loudly enough to audit
    set_noops = [f for f in _NOOP_FLAGS
                 if getattr(args, f.lstrip("-"), None) is not None]
    if set_noops:
        from megatron_tpu.utils.logging import print_rank_0
        print_rank_0("compat: accepted with no TPU-side effect: "
                     + ", ".join(set_noops))


def config_from_args(args: argparse.Namespace,
                     n_devices: Optional[int] = None,
                     defaults: Optional[dict] = None) -> MegatronConfig:
    from megatron_tpu.config import MODEL_PRESETS

    _apply_compat(args)

    if args.model:
        model = MODEL_PRESETS[args.model]()
        import dataclasses
        # a preset is a baseline, not a gag order: any model-field flag the
        # user EXPLICITLY set (differs from the parser default) overrides
        # the preset — e.g. --model llama2-7b --drop_path_rate 0.1
        overrides = {}
        if defaults:
            handled = {"seq_length", "recompute_granularity",
                       "attention_impl"}
            for f in dataclasses.fields(type(model)):
                if f.name in handled or f.name not in defaults:
                    continue
                if f.name == "num_layers" and args._num_layers_defaulted:
                    continue  # resolved fallback, not a user choice
                v = getattr(args, f.name, None)
                if v != defaults[f.name]:
                    overrides[f.name] = v
        model = dataclasses.replace(
            model, seq_length=args.seq_length or model.seq_length,
            recompute_granularity=args.recompute_granularity,
            attention_impl=(args.attention_impl or
                            ("flash" if args.use_flash_attn
                             else model.attention_impl)), **overrides)
    else:
        activation = (args.glu_activation or args.activation or
                      ("swiglu" if args.use_rms_norm else "gelu"))
        params_dtype = ("bfloat16" if args.bf16 else
                        "float16" if args.fp16 else "float32")
        md = _pick(args, ModelConfig)
        if md.get("seq_length") is None:
            md["seq_length"] = 512
        md.update(dict(
            norm_type="rmsnorm" if args.use_rms_norm else "layernorm",
            activation=activation,
            params_dtype=params_dtype,
            compute_dtype="bfloat16" if args.bf16 or args.fp16 else "float32",
            attention_impl=(args.attention_impl or
                            ("flash" if args.use_flash_attn else "dot")),
        ))
        model = ModelConfig(**md)

    if args.context_parallel > 1 and \
            model.attention_impl not in ("ring", "ulysses"):
        # cp>1 needs a context-parallel attention impl; the algo flag
        # picks ring vs ulysses (both run flash on the local block)
        import dataclasses
        model = dataclasses.replace(
            model, attention_impl=args.context_parallel_algo)

    vpp = 1
    if args.num_layers_per_virtual_pipeline_stage:
        per_stage = model.num_layers // max(args.pipeline_parallel, 1)
        vpp = per_stage // args.num_layers_per_virtual_pipeline_stage

    cfg = MegatronConfig(
        model=model,
        parallel=ParallelConfig(
            tensor_parallel=args.tensor_parallel,
            pipeline_parallel=args.pipeline_parallel,
            context_parallel=args.context_parallel,
            sequence_parallel=args.sequence_parallel,
            expert_axis=args.expert_axis,
            virtual_pipeline_chunks=vpp,
            pipeline_schedule=args.pipeline_schedule,
            pipeline_store_activations=args.pipeline_store_activations,
            use_distributed_optimizer=args.use_distributed_optimizer,
        ),
        optimizer=OptimizerConfig(**_pick(args, OptimizerConfig)),
        training=TrainingConfig(**{
            **_pick(args, TrainingConfig),
            "rampup_batch_size": tuple(args.rampup_batch_size)
            if args.rampup_batch_size else None}),
        data=DataConfig(**_pick(args, DataConfig)),
        serving=ServingConfig(
            request_deadline_s=args.request_deadline_s,
            decode_sync_interval=args.decode_sync_interval,
            prefill_max_batch=args.prefill_max_batch,
            enable_prefix_cache=args.enable_prefix_cache,
            prefill_chunk=args.prefill_chunk,
            retained_slots=args.retained_slots,
            kv_block_size=args.kv_block_size,
            block_native_attn=args.block_native_attn,
            speculative_k=args.speculative_k,
            priority_levels=args.priority_levels,
            shed_on_overload=args.shed_on_overload,
            degrade_ladder=args.degrade_ladder,
            slo_ttft_ms=args.slo_ttft_ms,
            slo_itl_p99_ms=args.slo_itl_p99_ms,
            preemption=args.preemption,
            max_engine_restarts=args.max_engine_restarts,
            engine_step_timeout_s=args.engine_step_timeout_s,
            num_replicas=args.num_replicas,
            router_max_retries=args.router_max_retries,
            replica_mode=args.replica_mode,
            fleet=args.fleet,
            remote_connect_timeout_s=args.remote_connect_timeout_s,
            remote_read_timeout_s=args.remote_read_timeout_s,
            remote_max_retries=args.remote_max_retries,
            remote_digest_interval_s=args.remote_digest_interval_s,
            host_kv_bytes=args.host_kv_bytes,
            serving_tp=args.serving_tp,
            disaggregate_prefill=args.disaggregate_prefill,
            prefill_tp=args.prefill_tp,
            decode_tp=args.decode_tp,
            serving_pp=args.serving_pp,
            pp_waves=args.pp_waves,
            placement_auto=args.placement_auto,
            placement_budget=args.placement_budget,
            adapter_slots=args.adapter_slots,
            adapter_rank=args.adapter_rank,
            adapter_host_bytes=args.adapter_host_bytes,
            swap_timeout_s=args.swap_timeout_s,
            watch_checkpoints=args.watch_checkpoints,
            watch_interval_s=args.watch_interval_s),
        resilience=ResilienceConfig(**{
            **_pick(args, ResilienceConfig),
            "checkpoint_integrity": not args.no_checkpoint_integrity}),
    )
    return cfg.validate(n_devices=n_devices)


def parse_cli(argv=None, extra_args_provider=None, n_devices=None
              ) -> tuple[MegatronConfig, argparse.Namespace]:
    # multi-host bring-up first: jax.distributed must initialize before
    # any backend query so jax.devices() sees the whole pod (no-op on
    # single-host runs; ref: initialize.py:124-151 ordering)
    from megatron_tpu.parallel.multihost import initialize_distributed
    initialize_distributed()
    parser = build_parser(extra_args_provider)
    args = parser.parse_args(argv)
    defaults = {a.dest: a.default for a in parser._actions}
    return config_from_args(args, n_devices=n_devices,
                            defaults=defaults), args
