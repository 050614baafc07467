"""Benchmark: training-step throughput on the available accelerator.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline"}.

Metric: tokens/sec on a Llama-2-architecture training step (bf16 compute,
fp32 params/Adam), sized to the chip. vs_baseline compares achieved MFU
against the reference's published A100 number — Llama2-7B at 890 tokens/s/GPU
(ref: docs/guide/getting_started.md:200-201), i.e. 6*7e9*890/312e12 = 12.0%
MFU on A100-80GB bf16 — so the ratio is hardware-normalized.

A run that finds no TPU fails, a `device_kind` missing from PEAK_FLOPS is
an error, and the one configuration either runs or the run fails: there
is no probe, no CPU fallback and no smaller second try. (A `benchmark` PR
replaces this file with a table of cells — ROADMAP.md S1.)
"""
from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp

from megatron_tpu.utils.compile_cache import ensure_compile_cache
ensure_compile_cache()

# bf16 peak FLOP/s per chip, keyed by the exact `device_kind` JAX reports
# (Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16)
PEAK_FLOPS = {
    "TPU v5 lite": 197e12,
}

A100_BASELINE_MFU = 6 * 7.0e9 * 890 / 312e12  # = 0.1198


def detect_peak(device) -> float:
    kind = device.device_kind
    if kind not in PEAK_FLOPS:
        raise SystemExit(
            f"bench: no peak FLOP/s known for device_kind {kind!r}; add it "
            "to PEAK_FLOPS with its source")
    return PEAK_FLOPS[kind]


def run_config(dev, model, micro_bs, n_micro, iters, warmup):
    from megatron_tpu.config import (MegatronConfig, OptimizerConfig,
                                     TrainingConfig)
    from megatron_tpu.training import init_train_state, make_train_step

    cfg = MegatronConfig(
        model=model,
        optimizer=OptimizerConfig(lr=1e-4, clip_grad=1.0),
        training=TrainingConfig(micro_batch_size=micro_bs,
                                global_batch_size=micro_bs * n_micro,
                                train_iters=iters),
    ).validate(n_devices=1)

    rng = jax.random.PRNGKey(0)
    state = init_train_state(rng, cfg)
    step = make_train_step(cfg)
    seq = cfg.model.seq_length
    tokens = jax.random.randint(
        jax.random.PRNGKey(1), (n_micro, micro_bs, seq + 1), 0,
        cfg.model.vocab_size, dtype=jnp.int32)
    batch = {"tokens": tokens,
             "loss_mask": jnp.ones((n_micro, micro_bs, seq), jnp.float32)}

    # param count for the FLOP model
    n_params = sum(p.size for p in jax.tree.leaves(state.params))

    for i in range(warmup):
        state, m = step(state, batch, jax.random.fold_in(rng, i))
    jax.block_until_ready(m["lm_loss"])

    t0 = time.perf_counter()
    for i in range(iters):
        state, m = step(state, batch, jax.random.fold_in(rng, warmup + i))
    jax.block_until_ready(m["lm_loss"])
    dt = time.perf_counter() - t0

    tokens_per_iter = n_micro * micro_bs * seq
    tok_s = tokens_per_iter * iters / dt
    flops_per_token = 6 * n_params  # fwd+bwd dense FLOPs, attention excluded
    mfu = tok_s * flops_per_token / detect_peak(dev)
    return {
        "metric": "train_tokens_per_sec_per_chip",
        "value": round(tok_s, 1),
        "unit": f"tok/s ({n_params/1e9:.2f}B params, {dev.device_kind}, "
                f"MFU={mfu:.3f})",
        "vs_baseline": round(mfu / A100_BASELINE_MFU, 3),
        "device_kind": dev.device_kind,
    }


def main():
    from megatron_tpu.config import llama2_config

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench: needs a TPU, JAX found platform {dev.platform!r}")
    detect_peak(dev)  # an unknown chip fails before the compile, not after
    # ~0.74B llama-architecture model at seq 2048. Params fp32 + two Adam
    # moments + fp32 grads = 16 bytes/param -> ~12 GB of the v5e's 16 GB
    # HBM, micro_bs=2 + full remat.
    model = llama2_config(
        "tiny", num_layers=12, hidden_size=2048,
        num_attention_heads=16, num_kv_heads=16, ffn_hidden_size=5504,
        vocab_size=32000, seq_length=2048, compute_dtype="bfloat16",
        attention_impl="flash", recompute_granularity="full")
    print(json.dumps(run_config(dev, model, 2, 4, 10, 3)), flush=True)


if __name__ == "__main__":
    main()
