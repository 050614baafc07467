#!/usr/bin/env python3
"""Train -> checkpoint -> serve on the chip, through the normal entry points.

    python chip_smoke.py              # one TPU chip, Falcon-7B widths
    python chip_smoke.py --chips 4    # four chips: the sharded path only
    python chip_smoke.py --rehearse   # CPU rehearsal at a tiny size; never ok

One chip, in this order, each phase one JSON line on standard output:

1. device   jax.devices() is a TPU (anything else fails the script)
2. kernels  the Pallas kernels, compiled, against their plain references
3. train    a seeded corpus, then finetune.py for a few steps and a save
4. serve    tools/run_text_generation_server.py on that checkpoint, a few
            requests over real HTTP, SIGTERM and a clean drain

and then the verdict, the LAST line of standard output and nothing else:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

A chip belongs to one process at a time, so this process never imports
JAX: every phase that needs the chip is a child (`--phase NAME`), run to
its end before the next starts. No child inherits this process's standard
output — the package logger and the serving threads write there — the
parent reads the child's pipe, keeps what it wrote in a log file under
the output directory and relays only the phase's own record.

Weights are random (from --seed), depth is cut to what one chip holds,
no width is cut. Everything the script writes (corpus, tokenizer,
checkpoint, logs) goes under one output directory inside the checkout.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))

# ---------------------------------------------------------------------------
# What runs. Widths are the presets' own (megatron_tpu/config.py
# falcon_config); only depth is cut.
# ---------------------------------------------------------------------------
# One chip: Falcon-7B (hidden 4544, 71 heads of 64 over 1 kv head, ffn 4x,
# vocabulary 65024, sequence 2048). The embedding is 295 M parameters and a
# layer 207 M; fp32 parameters + two Adam moments are 12 bytes a parameter.
# compiled.memory_analysis() for a described v5e puts 2 layers (710 M) at
# 7.9 GiB of state + 6.4 GiB of temporaries = 14.4 GiB, 1 layer (503 M) at
# 5.6 + 5.6 = 11.2 GiB, of the chip's 15.75 GiB: see CHANGES.md PR 22 for
# what the chip itself said.
ONE_CHIP = dict(model="falcon-7b", layers=2, vocab=65024, seq=2048,
                heads=71, kv_heads=1, head_dim=64, hidden=4544, lr=1e-5)
# Four chips: Falcon-40B (hidden 8192, 128 heads of 64 over 8 kv heads, two
# layer norms a block, 680 M a layer + 533 M embedding). One layer is
# 1.2 B parameters: 19 GB of training state, more than a chip, 4.9 GB a
# chip sharded four ways; the parameters alone (4.9 GB fp32) fit one chip
# for the unsharded comparison.
FOUR_CHIP = dict(model="falcon-40b", layers=1, vocab=65024, seq=2048,
                 heads=128, kv_heads=8, head_dim=64, hidden=8192, lr=1e-5)
# --rehearse: the tiny preset with a small vocabulary, so that the CPU
# gets through every path, argument and child in under a minute.
REHEARSE = dict(model="falcon-tiny", layers=2, vocab=512, seq=128,
                heads=4, kv_heads=1, head_dim=64, hidden=256, lr=1e-4)
# ... and with four kv heads for `--rehearse --chips 4`: the serving mesh
# shards the KV arena on the kv-head axis, which 4 must divide
REHEARSE4 = dict(REHEARSE, kv_heads=4,
                 extra=("--num_attention_heads_kv", "4"))

# Learning rates: Adam's first step moves every weight by lr whatever the
# gradient's size, and a matmul of fan-in 18176 then moves every activation
# by lr * 18176 * E|x|: at 1e-4 the Falcon-7B-width loss jumped 11.7 -> 32.6
# on the chip before coming down, at 1e-5 it fell monotonically to 7.9 in 8
# steps (CHANGES.md PR 22). The 256-wide rehearsal model takes 1e-4.
TRAIN_ITERS = 8
NEW_TOKENS = 24

# every wait has a limit of its own (seconds), so that a hang becomes a
# failed phase and not a run cut from outside with no last line
LIMITS = dict(device=120, kernels=420, train=900,
              server_up=420, request=300, drain=60)

# --- tolerances, each beside its reason ------------------------------------
# Kernel checks compare max|got - want| / max|want| over the whole tensor.
# Inputs and outputs are bf16 (8 significant bits, one rounding is 2^-9 =
# 0.2 % relative); the references run in fp32 at HIGHEST matmul precision.
# flash attention: the kernel feeds bf16 q/k/v and bf16 probabilities to
# the MXU with fp32 accumulation, the reference keeps everything fp32: two
# bf16 roundings on the way in and one on the way out, ~1 % of the largest
# value; the backward chains two such products.
TOL_FLASH_FWD = 2e-2
TOL_FLASH_BWD = 4e-2
# fused norms: statistics are fp32 on both sides; the reference rounds the
# normalized value to bf16 BEFORE the affine, the kernel after it: one bf16
# rounding (0.4 %) either way. The weight-grad partials are fp32 sums.
TOL_NORM = 2e-2
# block-native attention: fp32 online softmax over bf16 (or dequantized
# int8) blocks against the gathered full-row fp32 softmax; the output is
# rounded to bf16 once and the MXU may take fp32 operands in bf16 passes.
TOL_BLOCK = 2e-2
# first loss: random tied embeddings of std 0.02 under a unit-variance
# final norm give logits of variance hidden * 0.02^2 (1.8 at 4544, 3.3 at
# 8192), and E[loss] = ln(vocab) + variance / 2 for Gaussian logits — so
# the first loss sits ABOVE ln(vocab) by up to ~1.7 and never far below.
TOL_FIRST_LOSS = 2.0
# engine route vs serial route: greedy tokens are compared up to the first
# divergence; there, each route's log-probability for its own choice must
# be a near tie. The slot grid and the serial path multiply at different
# shapes in bf16: a logit of magnitude 16..32 has a bf16 spacing of 0.125,
# and two roundings apart is 0.25.
TOL_TIE_LOGPROB = 0.25
# sharded first-step loss vs the unsharded forward of the same parameters
# and batch: same math, different reduction order and bf16 collectives;
# the loss is a mean over 2048 tokens so the roundings average out.
TOL_SHARDED_LOSS = 5e-2


def verdict_line(ok: bool, device: dict) -> str:
    """The contract's last line: exactly these two keys, and these three
    inside `device`. Timings, losses and phases go on the earlier lines."""
    return json.dumps({
        "ok": bool(ok),
        "device": {"platform": device.get("platform"),
                   "kind": device.get("kind"),
                   "count": device.get("count")}})


def widths(args) -> dict:
    if args.rehearse:
        return REHEARSE4 if args.chips == 4 else REHEARSE
    return FOUR_CHIP if args.chips == 4 else ONE_CHIP


def paths(out: str) -> dict:
    return dict(corpus=os.path.join(out, "corpus", "docs"),
                tokenizer=os.path.join(out, "tokenizer"),
                ckpt=os.path.join(out, "ckpt"),
                logs=os.path.join(out, "logs"))


# ===========================================================================
# Children. Each prints ONE record line {"phase": ..., "ok": ...} as the
# last thing it does; an exception is not caught — it ends the child with
# a traceback on stderr and a non-zero code, and the parent reports that.
# ===========================================================================
def emit(phase: str, ok: bool, t0: float, **checked):
    print(json.dumps({"phase": phase, "ok": bool(ok),
                      "seconds": round(time.time() - t0, 2), **checked}),
          flush=True)
    return 0 if ok else 1


def phase_device(args) -> int:
    t0 = time.time()
    import jax
    devs = jax.devices()
    d = devs[0]
    # a rehearsal reports what it found and carries on; its verdict is
    # false all the same, because the platform is not the chip
    ok = (d.platform == "tpu" or args.rehearse) and len(devs) == args.chips
    return emit("device", ok, t0, platform=d.platform, kind=d.device_kind,
                count=len(devs), jax=jax.__version__, wanted_chips=args.chips)


def _rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, (got.shape, want.shape)
    assert np.isfinite(got).all(), "non-finite kernel output"
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def _has_kernel(fn, *a) -> bool:
    import jax
    return "tpu_custom_call" in jax.jit(fn).lower(*a).compile().as_text()


def phase_kernels(args) -> int:
    """Each Pallas kernel of the main path, compiled (interpret only in
    the CPU rehearsal), against its plain reference at the model's widths."""
    t0 = time.time()
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    from megatron_tpu.utils.compile_cache import ensure_compile_cache
    ensure_compile_cache()
    from megatron_tpu.models import norms
    from megatron_tpu.ops.block_attention_pallas import \
        block_native_attention
    from megatron_tpu.ops.flash_attention import (_blockwise_attention,
                                                  flash_attention)
    from megatron_tpu.ops.flash_attention_pallas import \
        pallas_flash_attention
    from megatron_tpu.ops.fused_norms import (pallas_layernorm,
                                              pallas_rmsnorm)

    w = widths(args)
    on_tpu = jax.default_backend() == "tpu"
    interp = not on_tpu          # only ever true off the chip (rehearsal)
    assert on_tpu or args.rehearse, "kernels phase needs the chip"
    key = jax.random.PRNGKey(args.seed)
    errs, in_hlo = {}, {}
    bf = jnp.bfloat16
    hi = jax.lax.Precision.HIGHEST

    # --- flash attention fwd + bwd vs the XLA blockwise path -------------
    s, nq, nkv, hd = w["seq"], w["heads"], w["kv_heads"], w["head_dim"]
    kq, kk, kv, kw, key = jax.random.split(key, 5)
    q = jax.random.normal(kq, (1, s, nq, hd), bf)
    k = jax.random.normal(kk, (1, s, nkv, hd), bf)
    v = jax.random.normal(kv, (1, s, nkv, hd), bf)
    wo = jax.random.normal(kw, (1, s, nq, hd), jnp.float32)

    def flash(q, k, v):
        if interp:
            return pallas_flash_attention(q, k, v, True, None, 128, 128,
                                          True)
        return flash_attention(q, k, v, causal=True)  # the users' dispatch

    def blockwise(q, k, v):
        with jax.default_matmul_precision("highest"):
            return _blockwise_attention(q, k, v, causal=True, scale=None,
                                        block_kv=512)

    def out_and_grads(f, weight):
        """f's output, and the gradients of sum(output * weight) with
        respect to f's three arguments."""
        def loss(*a):
            return jnp.sum(f(*a).astype(jnp.float32) * weight)
        return jax.jit(lambda *a: (f(*a), jax.grad(loss, (0, 1, 2))(*a)))
    got_o, got_g = out_and_grads(flash, wo)(q, k, v)
    ref_o, ref_g = out_and_grads(blockwise, wo)(q, k, v)
    errs["flash_fwd"] = _rel_err(got_o, ref_o)
    errs["flash_bwd"] = max(_rel_err(a, b) for a, b in zip(got_g, ref_g))
    if on_tpu:
        in_hlo["flash"] = _has_kernel(flash, q, k, v)

    # --- fused norms fwd + bwd vs models/norms.py ------------------------
    h = w["hidden"]
    kx, ks, kb, kd, key = jax.random.split(key, 5)
    x = jax.random.normal(kx, (1, s, h), bf)
    sc = 1.0 + 0.1 * jax.random.normal(ks, (h,), jnp.float32)
    bi = 0.1 * jax.random.normal(kb, (h,), jnp.float32)
    wy = jax.random.normal(kd, (1, s, h), jnp.float32)
    for name, kern, ref in (
            ("rmsnorm", lambda x, sc, bi: pallas_rmsnorm(x, sc, 1e-5, interp),
             lambda x, sc, bi: norms.rmsnorm({"scale": sc}, x)),
            ("layernorm",
             lambda x, sc, bi: pallas_layernorm(x, sc, bi, 1e-5, interp),
             lambda x, sc, bi: norms.layernorm({"scale": sc, "bias": bi},
                                               x))):
        go, gg = out_and_grads(kern, wy)(x, sc, bi)
        ro, rg = out_and_grads(ref, wy)(x, sc, bi)
        n_grads = 2 if name == "rmsnorm" else 3   # rmsnorm has no bias
        errs[f"{name}_fwd"] = _rel_err(go, ro)
        errs[f"{name}_bwd"] = max(_rel_err(a, b) for a, b in
                                  list(zip(gg, rg))[:n_grads])
        if on_tpu:
            in_hlo[name] = _has_kernel(kern, x, sc, bi)

    # --- block-native attention vs the gathered dot path -----------------
    S, B = 8, 16
    nb = s // B
    T = S * nb + 1
    kperm, klen, key = jax.random.split(key, 3)
    bmap = jax.random.permutation(kperm, T - 1)[:S * nb] \
        .reshape(S, nb).astype(jnp.int32)

    def gathered_dot(q, ka, va, lengths, ks_, vs_):
        """The resolve_view reference: gather each slot's blocks into a
        contiguous [cap] view, full-row fp32 softmax, causal from each
        query's own position."""
        def view(a):
            return a[bmap].reshape(S, nb * B, *a.shape[2:]) \
                .astype(jnp.float32)
        kk_, vv_ = view(ka), view(va)
        if ks_ is not None:
            kk_, vv_ = kk_ * view(ks_), vv_ * view(vs_)
        wq = q.shape[1]
        g = nq // nkv
        qf = q.astype(jnp.float32).reshape(S, wq, nkv, g, hd) * hd ** -0.5
        sc_ = jnp.einsum("swngd,scnd->swngc", qf, kk_, precision=hi)
        qpos = lengths[:, None] + jnp.arange(wq)[None, :]
        keep = jnp.arange(nb * B)[None, None, :] <= qpos[:, :, None]
        sc_ = jnp.where(keep[:, :, None, None, :], sc_, -1e30)
        p = jax.nn.softmax(sc_, axis=-1)
        o = jnp.einsum("swngc,scnd->swngd", p, vv_, precision=hi)
        return o.reshape(S, wq, nq, hd)

    for wq in (1, 5):            # decode, and the k+1 = 5 verify window
        for quant in (False, True):
            ka_, kb_, kc_, kd_, ke_, key = jax.random.split(key, 6)
            qq = jax.random.normal(ka_, (S, wq, nq, hd), bf)
            if quant:
                ka = jax.random.randint(kb_, (T, B, nkv, hd), -127, 128,
                                        jnp.int8)
                va = jax.random.randint(kc_, (T, B, nkv, hd), -127, 128,
                                        jnp.int8)
                ks_ = 0.02 * jax.random.uniform(kd_, (T, B, nkv, 1))
                vs_ = 0.02 * jax.random.uniform(ke_, (T, B, nkv, 1))
            else:
                ka = jax.random.normal(kb_, (T, B, nkv, hd), bf)
                va = jax.random.normal(kc_, (T, B, nkv, hd), bf)
                ks_ = vs_ = None
            # lengths: empty slot, mid-block tails, the last full window
            lengths = jnp.array([0, 1, B - 1, B, 3 * B + 5, s // 2,
                                 s - 2 * wq, s - wq], jnp.int32)

            def kern(qq, ka, va, lengths, ks_, vs_):
                # interpret=None: the op's own dispatch (compiled on TPU)
                return block_native_attention(
                    qq, ka, va, bmap, lengths, scale=hd ** -0.5,
                    k_scale=ks_, v_scale=vs_,
                    interpret=True if interp else None)
            got = kern(qq, ka, va, lengths, ks_, vs_)
            want = jax.jit(gathered_dot)(qq, ka, va, lengths, ks_, vs_)
            name = f"block_w{wq}_{'int8' if quant else 'bf16'}"
            errs[name] = _rel_err(got, want)
            if on_tpu:
                in_hlo[name] = _has_kernel(kern, qq, ka, va, lengths,
                                           ks_, vs_)

    tol = {"flash_fwd": TOL_FLASH_FWD, "flash_bwd": TOL_FLASH_BWD}
    bad = {n: e for n, e in errs.items()
           if e > tol.get(n, TOL_BLOCK if n.startswith("block")
                          else TOL_NORM)}
    missing = [n for n, there in in_hlo.items() if not there]
    return emit("kernels", not bad and not missing, t0,
                widths={k_: w[k_] for k_ in ("hidden", "heads", "kv_heads",
                                             "head_dim", "seq")},
                compiled=not interp, max_rel_err=errs,
                over_tolerance=bad, kernel_missing_from_hlo=missing,
                tolerances=dict(flash_fwd=TOL_FLASH_FWD,
                                flash_bwd=TOL_FLASH_BWD, norm=TOL_NORM,
                                block=TOL_BLOCK))


def write_corpus(prefix: str, vocab: int, seq: int, seed: int):
    """A seeded corpus in the indexed format finetune.py reads: tokens
    drawn Zipf-like from a seeded alphabet of at most 512 ids (id 0, the
    tokenizer's end-of-document, is never used), so that a few steps can
    lower the loss; plus ONE empty document, which sends GPTDataset's
    sample index through the native helper built from helpers.cpp."""
    import numpy as np
    from megatron_tpu.data.indexed_dataset import IndexedDatasetBuilder
    os.makedirs(os.path.dirname(prefix), exist_ok=True)
    rng = np.random.default_rng(seed)
    alphabet = rng.choice(np.arange(1, vocab), size=min(512, vocab // 2),
                          replace=False)
    p = 1.0 / np.arange(1, len(alphabet) + 1)
    p /= p.sum()
    b = IndexedDatasetBuilder(prefix)
    for i in range(4 * TRAIN_ITERS):
        if i == 1:
            b.add_item([])
            b.end_document()
        b.add_item(alphabet[rng.choice(len(alphabet), size=seq + 1,
                                       p=p)].tolist())
        b.end_document()
    b.finalize()


def train_argv(args, w, p, extra=()) -> list:
    return ["--model", w["model"], "--num_layers", str(w["layers"]),
            "--bf16", "--use_flash_attn",
            "--vocab_size", str(w["vocab"]), "--seq_length", str(w["seq"]),
            "--data_path", p["corpus"], "--split", "100,0,0",
            "--micro_batch_size", "1", "--global_batch_size", "1",
            "--train_iters", str(TRAIN_ITERS), "--lr", str(w["lr"]),
            "--lr_decay_style", "constant", "--log_interval", "1",
            "--eval_iters", "0", "--seed", str(args.seed),
            "--save", p["ckpt"], "--save_interval", str(TRAIN_ITERS),
            *w.get("extra", ()), *extra]


class _LoopLog:
    """Reads the training loop's own log lines: per iteration the lm
    loss and the milliseconds, and after the first step what every
    device holds (utils/logging.report_memory)."""

    def __init__(self):
        import logging
        self.losses, self.ms, self.gib_in_use = [], [], None
        pat = re.compile(r"elapsed time per iteration \(ms\): ([0-9.]+) .*"
                         r"lm loss: ([0-9.eE+-]+|nan|inf)")
        outer = self

        class H(logging.Handler):
            def emit(self, record):
                msg = record.getMessage()
                m = pat.search(msg)
                if m:
                    outer.ms.append(float(m.group(1)))
                    outer.losses.append(float(m.group(2)))
                outer.gib_in_use = (memory_line(msg, "after first step")
                                    or outer.gib_in_use)
        logging.getLogger("megatron_tpu").addHandler(H())


def memory_line(text: str, name: str):
    """Per-device GiB in use from utils/logging.report_memory's line
    `[memory NAME] 0: used X GiB | peak Y GiB ... || 1: used ...`."""
    m = re.search(r"\[memory " + re.escape(name) + r"\] (.*)", text)
    return m and [float(x) for x in
                  re.findall(r"\d+: used ([0-9.]+) GiB", m.group(1))]


def balanced(per_device, n: int) -> bool:
    """Every one of `n` devices holds about 1/n: none more than 1.25x
    the mean (replicated norms, scalars and the compiler's own scratch
    are the slack), none empty."""
    if not per_device or len(per_device) != n or min(per_device) <= 0:
        return False
    return max(per_device) <= 1.25 * sum(per_device) / n


def _checkpoint_on_disk(root: str) -> dict:
    from megatron_tpu.resilience.integrity import (MANIFEST,
                                                   verify_checkpoint)
    from megatron_tpu.training.checkpointing import read_tracker
    tag = read_tracker(root)
    d = os.path.join(root, f"iter_{int(tag):07d}") if tag else None
    valid, why = verify_checkpoint(d) if d else (False, "no tracker")
    return dict(tracker=tag, valid=bool(valid), why=why,
                manifest=bool(d) and os.path.exists(
                    os.path.join(d, MANIFEST)))


def phase_train(args) -> int:
    """finetune.py for a few steps and a save. With --chips 4 the model
    is one whose training state does not fit a chip, trained tensor +
    sequence parallel over the four, and compared with an unsharded
    forward of the same parameters and batch on one of them."""
    t0 = time.time()
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import finetune  # noqa: the entry point; calls ensure_compile_cache()
    from megatron_tpu.arguments import parse_cli
    from megatron_tpu.data import helpers
    from megatron_tpu.parallel.mesh import build_mesh
    from megatron_tpu.training import init_train_state, make_train_step

    w, p = widths(args), paths(args.out)
    n = len(jax.devices())
    on_tpu = jax.default_backend() == "tpu"
    assert on_tpu or args.rehearse, "train phase needs the chip"
    assert n == args.chips, (n, args.chips)
    write_corpus(p["corpus"], w["vocab"], w["seq"], args.seed)
    argv = train_argv(args, w, p)
    if n > 1:
        argv += ["--tensor_model_parallel_size", str(n),
                 "--sequence_parallel"]
    cfg, _ = parse_cli(argv, n_devices=n)
    mesh = build_mesh(cfg.parallel) if n > 1 else None   # as finetune.py

    # the step finetune.py is about to run, compiled ahead from shapes:
    # compile seconds apart from step seconds, the compiler's own memory
    # count, the flash kernel and the collectives in the compiled text
    shapes = jax.eval_shape(
        lambda: init_train_state(jax.random.PRNGKey(0), cfg))
    n_params = sum(x.size for x in jax.tree.leaves(shapes.params))
    batch = {"tokens": jax.ShapeDtypeStruct((1, 1, w["seq"] + 1), jnp.int32),
             "loss_mask": jax.ShapeDtypeStruct((1, 1, w["seq"]),
                                               jnp.float32)}
    tc = time.time()
    compiled = make_train_step(cfg, mesh=mesh).lower(
        shapes, batch, jax.ShapeDtypeStruct((2,), jnp.uint32)).compile()
    compile_s = time.time() - tc
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    del compiled

    log = _LoopLog()
    rc = finetune.main(argv)
    losses, ms = log.losses, log.ms
    ln_v = math.log(w["vocab"])
    ckpt = _checkpoint_on_disk(p["ckpt"])
    checks = dict(
        exit_0=rc == 0,
        all_steps_logged=len(losses) == TRAIN_ITERS,
        losses_finite=all(math.isfinite(x) for x in losses),
        first_loss_near_ln_vocab=bool(losses) and
        abs(losses[0] - ln_v) <= TOL_FIRST_LOSS,
        last_loss_lower=len(losses) > 1 and losses[-1] < losses[0],
        checkpoint_valid=ckpt["valid"] and ckpt["manifest"]
        and ckpt["tracker"] == str(TRAIN_ITERS),
        native_helper_built=helpers._lib is not None,
        # off the chip flash_attention() takes its XLA path by design
        flash_kernel_in_step="tpu_custom_call" in text or not on_tpu)
    sharded = {}
    if n > 1:
        sharded = _sharded_evidence(cfg, mesh, text, losses[:1])
        checks.update(sharded.pop("checks"))
        # the CPU backend reports no memory stats
        checks["memory_a_quarter_each"] = \
            balanced(log.gib_in_use, n) or not on_tpu
    return emit(
        "train", all(checks.values()), t0, model=w["model"],
        num_layers=w["layers"], params_m=round(n_params / 1e6, 1),
        checks=checks, ln_vocab=round(ln_v, 3), losses=losses,
        compile_seconds=round(compile_s, 1),
        first_iteration_ms=ms[0] if ms else None,
        step_ms_after_first=ms[1:],
        compiler_gib_per_device=dict(
            arguments=round(mem.argument_size_in_bytes / 2 ** 30, 2),
            temporaries=round(mem.temp_size_in_bytes / 2 ** 30, 2)),
        device_gib_in_use_after_first_step=log.gib_in_use,
        checkpoint=ckpt, **sharded)


def _sharded_evidence(cfg, mesh, step_text: str, first_loss: list) -> dict:
    """What only the four-chip run can show: the collectives in the
    compiled step, where a fresh state's parameters live, and the first
    loss against an unsharded forward on one chip."""
    import jax
    import jax.numpy as jnp
    import finetune
    from megatron_tpu.models import language_model as lm
    collectives = {
        c: len(re.findall(r"= \S+ " + c + r"(?:-start)?\(", step_text))
        for c in ("all-gather", "reduce-scatter", "all-reduce",
                  "all-to-all", "collective-permute")}

    # where a fresh state lives, leaf by leaf (finetune.py's own init)
    rng = jax.random.PRNGKey(cfg.training.seed)
    state = finetune.init_state(cfg, mesh, rng)
    per_dev = {d.id: 0 for d in jax.devices()}
    for x in jax.tree.leaves(state.params):
        for sh in x.addressable_shards:
            per_dev[sh.device.id] += sh.data.nbytes
    per_dev = [per_dev[d.id] for d in jax.devices()]
    del state

    # the comparison: parameters alone fit one chip. jit(model_init)
    # gives the bits the sharded init gave; the batch is the loader's first
    with jax.default_device(jax.devices()[0]):
        params = jax.jit(lambda r: lm.model_init(r, cfg.model))(rng)
        first = next(finetune.build_data(cfg, None, 0, mesh=None)[0])
        rope = lm.make_rope(cfg.model)
        ref_loss = float(jax.jit(lambda pr, t, m: lm.loss_fn(
            pr, t, cfg.model, loss_mask=m, rope=rope))(
                params, jnp.asarray(first["tokens"][0]),
                jnp.asarray(first["loss_mask"][0])))
    return dict(
        checks=dict(
            first_loss_matches_unsharded=bool(first_loss) and
            abs(first_loss[0] - ref_loss) <= TOL_SHARDED_LOSS,
            params_a_quarter_each=balanced(per_dev, len(per_dev)),
            collectives_in_step=collectives["all-gather"] > 0 and
            collectives["reduce-scatter"] + collectives["all-reduce"] > 0),
        mesh=dict(mesh.shape), unsharded_first_loss=ref_loss,
        sharded_loss_tolerance=TOL_SHARDED_LOSS,
        param_bytes_per_device=per_dev, collectives=collectives)


# ===========================================================================
# The server phase runs in the parent: it needs no JAX, and the server is
# the child that holds the chip.
# ===========================================================================
def write_tokenizer(path: str, vocab: int, seed: int) -> list:
    """A word-level tokenizer of the model's vocabulary size, written
    with the installed `tokenizers` package (the machine has no network
    for a hub name): words w0..w{V-2} under a seeded permutation of the
    ids 1..V-1, id 0 the end-of-document token. Returns two prompts."""
    import random

    from tokenizers import Tokenizer, models, pre_tokenizers
    os.makedirs(path, exist_ok=True)
    rnd = random.Random(seed)
    ids = list(range(1, vocab))
    rnd.shuffle(ids)
    vocab_map = {"<|endoftext|>": 0}
    vocab_map.update({f"w{i}": t for i, t in enumerate(ids)})
    tok = Tokenizer(models.WordLevel(vocab_map, unk_token="<|endoftext|>"))
    tok.pre_tokenizer = pre_tokenizers.WhitespaceSplit()
    tok.save(os.path.join(path, "tokenizer.json"))
    with open(os.path.join(path, "tokenizer_config.json"), "w") as f:
        json.dump({"tokenizer_class": "PreTrainedTokenizerFast",
                   "eos_token": "<|endoftext|>",
                   "unk_token": "<|endoftext|>"}, f)
    return [" ".join(f"w{rnd.randrange(vocab - 1)}" for _ in range(n))
            for n in (12, 7)]


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(method: str, url: str, body=None, timeout: float = 30.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method,
                                 headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def compare_routes(engine: dict, serial: dict) -> dict:
    """Tokens equal up to the first divergence; at a divergence the two
    routes' log-probabilities for their own choice are a near tie."""
    et, st = engine["segments"][0], serial["segments"][0]
    el, sl = engine["logprobs"][0], serial["logprobs"][0]
    n = min(len(et), len(st))
    d = next((i for i in range(n) if et[i] != st[i]), None)
    agreed = n if d is None else d
    gap = None if d is None else abs(el[d] - sl[d])
    return dict(tokens_engine=len(et), tokens_serial=len(st),
                tokens_agreed=agreed, diverged_at=d, tie_gap=gap)


def phase_serve(args) -> dict:
    """The server CLI on the checkpoint, over real HTTP. With --chips 4
    it is started with `--serving_tp 4`, every device holds about a
    quarter of the weights and the KV arena, and the engine traces its
    decode program once."""
    t0 = time.time()
    w, p = widths(args), paths(args.out)
    prompts = write_tokenizer(p["tokenizer"], w["vocab"], args.seed)
    port = free_port()
    base = f"http://127.0.0.1:{port}"
    log_path = os.path.join(p["logs"], "server.log")
    env = child_env(args)
    env["HF_HUB_OFFLINE"] = "1"   # the tokenizer is a local directory
    cmd = [sys.executable,
           os.path.join(ROOT, "tools", "run_text_generation_server.py"),
           "--load", p["ckpt"], "--tokenizer_type", "FalconTokenizer",
           "--tokenizer_model", p["tokenizer"],
           "--host", "127.0.0.1", "--port", str(port)]
    if args.chips > 1:
        cmd += ["--serving_tp", str(args.chips)]
    rec = dict(phase="serve", ok=False, checks={})
    checks = rec["checks"]
    with open(log_path, "w") as log:
        srv = subprocess.Popen(cmd, stdout=log, stderr=log, cwd=ROOT,
                               env=env, start_new_session=True)
    try:
        # --- up: /healthz answers 200 --------------------------------
        deadline = time.time() + LIMITS["server_up"]
        up = False
        while time.time() < deadline and srv.poll() is None:
            try:
                up = http("GET", base + "/healthz", timeout=5)[0] == 200
            except (OSError, ValueError):
                up = False
            if up:
                break
            time.sleep(1.0)
        checks["healthz_200"] = up
        rec["seconds_to_up"] = round(time.time() - t0, 1)
        if not up:
            rec["error"] = (f"server exited {srv.returncode}"
                            if srv.poll() is not None else
                            "no /healthz 200 within its limit")
            return rec
        # --- engine route vs serial route, seeded greedy --------------
        pairs = []
        for i, prompt in enumerate(prompts):
            body = {"prompts": [prompt], "tokens_to_generate": NEW_TOKENS,
                    "temperature": 0.0, "random_seed": args.seed + i,
                    "logprobs": True}
            tr = time.time()
            es, eng = http("PUT", base + "/api", body, LIMITS["request"])
            te = time.time() - tr
            ss, ser = http("PUT", base + "/api", {**body, "serial": True},
                           LIMITS["request"])
            if es != 200 or ss != 200:
                rec["error"] = f"PUT /api -> {es} {eng} / {ss} {ser}"
                return rec
            c = compare_routes(eng, ser)
            c["prompt_tokens"] = len(prompt.split())
            c["engine_seconds"] = round(te, 2)
            c["serial_seconds"] = round(time.time() - tr - te, 2)
            pairs.append(c)
        rec["routes"] = pairs
        checks["routes_generated"] = all(
            c["tokens_engine"] > c["prompt_tokens"] for c in pairs)
        checks["divergences_are_near_ties"] = all(
            c["tie_gap"] is None or c["tie_gap"] <= TOL_TIE_LOGPROB
            for c in pairs)
        checks["not_all_diverge_at_first_token"] = not all(
            c["diverged_at"] == c["prompt_tokens"] for c in pairs)
        rec["tokens_agreed"] = [c["tokens_agreed"] - c["prompt_tokens"]
                                for c in pairs]
        rec["tie_tolerance"] = TOL_TIE_LOGPROB
        # --- two concurrent requests, both answered -------------------
        answers = [None, None]

        def fire(i):
            answers[i] = http(
                "PUT", base + "/api",
                {"prompts": [prompts[i]], "tokens_to_generate": NEW_TOKENS,
                 "temperature": 0.0, "random_seed": args.seed + 10 + i},
                LIMITS["request"])
        threads = [threading.Thread(target=fire, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(LIMITS["request"] + 5)
        checks["concurrent_answered"] = all(
            a is not None and a[0] == 200 and a[1]["segments"][0]
            for a in answers)
        # --- /metrics and /healthz -------------------------------------
        ms, met = http("GET", base + "/metrics")
        rec["metrics"] = {k: met.get(k) for k in (
            "requests_completed", "ttft_p50_ms", "ttft_p95_ms",
            "num_slots")}
        checks["metrics_completions_and_ttft"] = (
            ms == 200 and met.get("requests_completed", 0) >= 4
            and (met.get("ttft_p50_ms") or 0) > 0)
        checks["healthz_200_after_traffic"] = \
            http("GET", base + "/healthz")[0] == 200
        # --- SIGTERM: a clean drain within seconds ---------------------
        td = time.time()
        srv.send_signal(signal.SIGTERM)
        try:
            code = srv.wait(LIMITS["drain"])
        except subprocess.TimeoutExpired:
            code = None
        rec["drain_seconds"] = round(time.time() - td, 2)
        # the engine logs how often it traced each program as it drains,
        # the CLI what every device held once weights and KV pool were up
        with open(log_path) as f:
            log_text = f.read()
        m = re.search(r"serving engine drained.*program traces: "
                      r"decode=(\d+) prefill=(\d+) chunk=(\d+) "
                      r"verify=(\d+)", log_text)
        rec["device_gib_in_use"] = memory_line(log_text, "serving")
        rec["program_traces"] = m and dict(zip(
            ("decode", "prefill", "chunk", "verify"), map(int, m.groups())))
        checks["clean_drain"] = code == 0 and m is not None
        rec["server_exit_code"] = code
        if args.chips > 1:
            checks["decode_traced_once"] = \
                rec["program_traces"]["decode"] == 1 if m else False
            if not args.rehearse:   # the CPU backend reports no stats
                checks["every_device_holds_a_quarter"] = balanced(
                    rec["device_gib_in_use"], args.chips)
        rec["ok"] = all(checks.values())
        return rec
    finally:
        # every process this script starts is stopped: a server that
        # outlives its limit is killed, and that is a failure
        if srv.poll() is None:
            os.killpg(srv.pid, signal.SIGKILL)
            srv.wait()
            rec["ok"] = False
            rec["error"] = rec.get("error", "server had to be killed")
        rec["seconds"] = round(time.time() - t0, 2)
        rec["server_log"] = os.path.relpath(log_path, ROOT)


# ===========================================================================
# Parent
# ===========================================================================
def child_env(args) -> dict:
    env = dict(os.environ)   # JAX_COMPILATION_CACHE_DIR passes through
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("TPU_LOG_DIR", "disabled")
    if args.rehearse and args.chips > 1 and \
            "xla_force_host_platform_device_count" not in \
            env.get("XLA_FLAGS", ""):
        # a rehearsal of the four-chip path runs on virtual CPU devices
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={args.chips}").strip()
    return env


def run_child(args, phase: str) -> dict:
    """One phase in a process of its own. Its stdout is a pipe: what it
    wrote is kept in logs/<phase>.log and only its record is relayed."""
    t0 = time.time()
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--seed", str(args.seed), "--chips", str(args.chips),
           "--out", args.out] + (["--rehearse"] if args.rehearse else [])
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, stderr=None,
                            cwd=ROOT, env=child_env(args), text=True,
                            start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=LIMITS[phase])
        timed_out = False
    except subprocess.TimeoutExpired:
        os.killpg(proc.pid, signal.SIGKILL)
        out, _ = proc.communicate()
        timed_out = True
    with open(os.path.join(paths(args.out)["logs"], f"{phase}.log"),
              "w") as f:
        f.write(out or "")
    rec = None
    for line in (out or "").splitlines():
        if line.startswith('{"phase"'):
            try:
                rec = json.loads(line)
            except ValueError:
                pass
    if rec is None or rec.get("phase") != phase:
        rec = dict(phase=phase, ok=False,
                   seconds=round(time.time() - t0, 2),
                   error=(f"killed at its {LIMITS[phase]} s limit"
                          if timed_out else
                          f"exit code {proc.returncode}, no record"))
    elif proc.returncode != 0 or timed_out:
        rec["ok"] = False
        rec.setdefault("error", f"exit code {proc.returncode}")
    return rec


def say(rec: dict):
    sys.stdout.write(json.dumps(rec) + "\n")
    sys.stdout.flush()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="weights, corpus, tokenizer and prompts")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run ONLY the sharded path (Falcon-40B widths, "
                         "tp 4 + sequence parallel, --serving_tp 4) and "
                         "what it is compared with")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: tiny preset, kernels interpreted, "
                         "phases carry on past a device that is not a TPU; "
                         "the verdict is always \"ok\": false")
    ap.add_argument("--out", default=os.path.join(ROOT, "chip_smoke_out"),
                    help="corpus, tokenizer, checkpoint and logs go here")
    ap.add_argument("--phase", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    args.out = os.path.abspath(args.out)

    if args.phase:   # child mode
        return CHILD_PHASES[args.phase](args)

    import shutil
    shutil.rmtree(args.out, ignore_errors=True)   # a run starts clean
    os.makedirs(paths(args.out)["logs"])
    device = dict(platform=None, kind=None, count=0)
    results = []

    def not_run(phase, why):
        rec = dict(phase=phase, ok=False, not_run=why)
        results.append(rec)
        say(rec)

    def child(phase):
        rec = run_child(args, phase)
        results.append(rec)
        say(rec)
        return rec["ok"]

    dev = run_child(args, "device")
    results.append(dev)
    say(dev)
    for k in device:
        device[k] = dev.get(k, device[k])
    go = dev["ok"] or (args.rehearse and dev.get("platform") is not None)
    # four chips: the sharded path and what it is compared with, only
    plan = (("train", "serve") if args.chips == 4
            else ("kernels", "train", "serve"))
    trained = False
    for phase in plan:
        if not go:
            not_run(phase, "no TPU with the wanted number of chips")
        elif phase != "serve":
            ok = child(phase)
            trained = ok if phase == "train" else trained
        elif not trained:
            not_run(phase, "the train phase left no checkpoint to serve")
        else:
            rec = phase_serve(args)
            results.append(rec)
            say(rec)

    # the verdict: every child has exited. A rehearsal can never print
    # true — its platform is not the chip.
    ok = (all(r["ok"] for r in results) and not args.rehearse
          and device["platform"] == "tpu")
    sys.stdout.write(verdict_line(ok, device) + "\n")
    sys.stdout.flush()
    return 0 if ok else 1


CHILD_PHASES = dict(device=phase_device, kernels=phase_kernels,
                    train=phase_train)

if __name__ == "__main__":
    sys.exit(main())
