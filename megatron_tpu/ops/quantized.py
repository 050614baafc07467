"""Int8 quantized GEMM — the TPU-native counterpart of the reference's
Transformer Engine fp8 path (ref: megatron/model/transformer.py:931-950 and
the --fp8_* flag group, megatron/arguments.py:303-313).

The reference reaches low-precision GEMM throughput through TE's fp8
(H100-only; inert on its A100 targets too). TPU v5e/v5p MXUs have no fp8
datapath — the hardware's low-precision lever is **int8**, at ~2x the bf16
MACs/cycle on v5e. This module is the TE recipe rebuilt on that datapath:

- forward GEMMs run int8 x int8 -> int32 on the MXU, with **per-token
  activation scales** and **per-output-channel weight scales** (the
  "current scaling" recipe: amax is taken from the tensor being quantized,
  no cross-step amax history to thread through the train state);
- the backward runs in the compute dtype on the *unquantized* operands
  (straight-through estimate; the hybrid recipe the reference exposes as
  --no_fp8_wgrad, extended to dgrad because e5m2 has no int analogue).

Applied to the attention q/kv/out projections and both MLP GEMMs when
`ModelConfig.quantized_gemm == "int8"`; the embedding and lm head stay in
the compute dtype (TE keeps those out of fp8 for the same accuracy
reasons). Opt in with --quantized_gemm int8.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class W8(NamedTuple):
    """A weight stored int8 with per-output-channel fp32 scales — the
    serving-side (weight-only storage) half of the int8 path: decode is
    HBM-bandwidth-bound, and an int8-resident weight halves its stream.
    Produced by `quantize_weights`; consumed transparently by `qdense`
    (the GEMM runs on the int8 datapath against per-token-quantized
    activations). As a NamedTuple it is a pytree: `lax.scan` slices the
    stacked [L, ...] serving layout per layer, and shardings ride the
    aligned axes from `quantize_axes`."""
    q: jax.Array      # int8, same shape as the source weight
    scale: jax.Array  # fp32, source shape minus the contraction axis


def quantize_rows(x):
    """x [..., K] -> (int8 values, fp32 scale [..., 1]) with per-row amax."""
    ax = jnp.max(jnp.abs(x), axis=-1, keepdims=True).astype(jnp.float32)
    scale = jnp.where(ax > 0, ax / 127.0, 1.0)
    xi = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return xi.astype(jnp.int8), scale


def _quantize_cols(w):
    """w [K, N] -> (int8 values, fp32 scale [N]) with per-column amax."""
    aw = jnp.max(jnp.abs(w), axis=0).astype(jnp.float32)
    scale = jnp.where(aw > 0, aw / 127.0, 1.0)
    wi = jnp.clip(jnp.round(w.astype(jnp.float32) / scale[None, :]),
                  -127, 127)
    return wi.astype(jnp.int8), scale


def _int8_matmul_impl(x, w):
    xi, sx = quantize_rows(x)
    wi, sw = _quantize_cols(w)
    yi = jax.lax.dot_general(
        xi, wi, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    return (yi.astype(jnp.float32) * sx * sw).astype(x.dtype)


@jax.custom_vjp
def int8_matmul(x, w):
    """[..., K] @ [K, N] with an int8-MXU forward and a full-precision
    backward. Numerics: per-row/per-column symmetric quantization bounds
    the forward's relative error at ~0.4% rms for well-conditioned
    operands; gradients are exact for the straight-through estimate."""
    return _int8_matmul_impl(x, w)


def _int8_matmul_fwd(x, w):
    return _int8_matmul_impl(x, w), (x, w)


def _int8_matmul_bwd(res, dy):
    x, w = res
    # contract dy's N against w's N for dx; batch dims of x against dy for dw
    dx = jax.lax.dot_general(dy, w, (((dy.ndim - 1,), (1,)), ((), ())))
    lead = tuple(range(x.ndim - 1))
    dw = jnp.tensordot(x, dy, axes=(lead, lead))
    return dx.astype(x.dtype), dw.astype(w.dtype)


int8_matmul.defvjp(_int8_matmul_fwd, _int8_matmul_bwd)


def _w8_matmul(x, w8: W8):
    """[..., K] against a pre-quantized weight: per-token-quantize x,
    int8 dot against the resident int8 weight, dequantize by both scales.
    No custom_vjp — this is the serving path; jnp.round's zero cotangent
    makes accidental differentiation loud (zero grads), not silently
    wrong."""
    xi, sx = quantize_rows(x)
    k = w8.q.shape[0]
    wi = w8.q.reshape(k, -1)
    yi = jax.lax.dot_general(
        xi, wi, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    y = (yi.astype(jnp.float32) * sx
         * w8.scale.reshape(-1).astype(jnp.float32))
    return y.astype(x.dtype).reshape(*x.shape[:-1], *w8.q.shape[1:])


# the contraction axis quantize_weights removes from each STACKED
# transformer weight [L, K, ...]; quantize_axes must drop the same one
_STACKED_CONTRACT_AXIS = 1
_QUANTIZABLE = ("wq", "wkv", "wo", "w1", "w2")


def quantize_weights(params):
    """Serving-time transform: re-store the transformer attention/MLP
    weights (the _QUANTIZABLE names, scan-stacked [L, K, ...]) as int8
    W8 leaves with per-layer per-output-channel scales. Embedding, norms
    and lm head keep their dtype (the TE-style accuracy carve-out).
    Returns a new params tree; pair with `quantize_axes` for sharded
    serving."""
    def walk(name, node):
        if isinstance(node, dict):
            if "router" in node:
                # MoE expert bank: [L, E, K, ...] layout — axis 1 is the
                # EXPERT dim, not the contraction, and _w8_matmul has no
                # banked path; experts stay in the compute dtype
                # (int8_expert_matmul covers the training-side lever)
                return node
            return {k: walk(k, v) for k, v in node.items()}
        if name in _QUANTIZABLE:
            ax = _STACKED_CONTRACT_AXIS
            amax = jnp.max(jnp.abs(node), axis=ax).astype(jnp.float32)
            scale = jnp.where(amax > 0, amax / 127.0, 1.0)
            qv = jnp.clip(jnp.round(node.astype(jnp.float32)
                                    / jnp.expand_dims(scale, ax)),
                          -127, 127).astype(jnp.int8)
            return W8(q=qv, scale=scale)
        return node
    out = dict(params)
    if "transformer" in out:
        out["transformer"] = walk("", params["transformer"])
    return out


def quantize_axes(axes, params):
    """Align a logical-axes tree with a `quantize_weights`-transformed
    params tree: wherever params holds a W8, the tuple axes leaf expands
    to W8(q=<original>, scale=<original minus the contraction axis>)."""
    def fix(ax, p):
        if isinstance(p, W8):
            a = _STACKED_CONTRACT_AXIS
            return W8(q=ax, scale=ax[:a] + ax[a + 1:])
        return ax
    # type(x) is tuple: stop at plain axes tuples, but a W8 ALREADY in
    # the axes tree (double application) would recurse — harmless, fix()
    # only rewraps against params
    return jax.tree.map(fix, axes, params,
                        is_leaf=lambda x: type(x) is tuple)


def has_quantized_weights(params) -> bool:
    return any(isinstance(x, W8) for x in jax.tree.leaves(
        params, is_leaf=lambda x: isinstance(x, W8)))


def wcast(w, dtype, *, read_once: bool = False):
    """The call-site weight cast: fp weights cast to the compute dtype;
    W8 weights pass through untouched (dequantization is fused into the
    int8 GEMM inside qdense).

    `read_once` is what the caller knows of its program: a program that
    carries a KV cache (decode, prefill, chunk, verify) multiplies by each
    weight once a call, a training step 3 to 24 times (forward, backward,
    recompute, micro-batches). It chooses the cast's FORM for a weight held
    wider than `dtype`, never its value:

    - not `read_once`: a bare `astype`. Inside the layer loop the TPU
      compiler moves a narrowing convert above the layer's slice and out of
      the loop, so the whole stack is cast once a call and every use reads
      the narrow copy: right where there are many uses.
    - `read_once`: the slice is first rounded to `dtype`'s grid in its OWN
      dtype (`reduce_precision`, round to nearest even), then narrowed, which
      is then exact: bit for bit `w.astype(dtype)`, NaN and infinities
      included. The compiler does not lift that pair; it fuses slice,
      rounding and narrowing into the product's operand, which reads the
      wide layer where it lies. A lifted cast costs such a program 8 bytes a
      weight (read 4, write 2, read 2) and a narrow copy of every stack among
      its temporaries (4.3 GiB at Falcon-7B widths and depth 11) where 4
      bytes do (PERF.md section 6, PR 34).

    Every matrix that comes through here takes the in-place form in a
    cached program; none was left on the lifted path (compile for v5e and
    my chip runs, PR 34, Falcon-7B widths at depth 11): both MLP products
    fuse it as written and run near the float32 bytes' rate (~0.42 ms a
    call where a layer's 330 MB take 0.40 at 819 GB/s); wq, wkv
    and wo do once each projection is a product of its own
    (`models/attention.py::_project`: left free, a decode step copied wq
    three times over and a two-prompt prefill copied wq's and wo's slice
    into another order, 20 bytes a weight). The embedding's lookup is not
    a cast of this kind and does not come through here: the copy of a
    whole tied table that a served program of Falcon-7B's made was the
    token gather's, for the table's layout and not for its dtype (the
    head's product reads the float32 table in place), and went with
    `ops/embed_gather.py` (PR 53).

    The rule covers a narrowing that keeps the exponent's width (float32
    to bfloat16, the served case). `reduce_precision` flushes what would be
    a subnormal of a format with a narrower exponent (float16) to zero where
    `astype` rounds to it, so such a cast keeps the bare form and its exact
    value. A weight already in `dtype`, or narrower, emits nothing."""
    if isinstance(w, W8):
        return w
    if read_once and jnp.issubdtype(w.dtype, jnp.floating) \
            and jnp.issubdtype(dtype, jnp.floating):
        wide, narrow = jnp.finfo(w.dtype), jnp.finfo(dtype)
        if wide.nexp == narrow.nexp and wide.nmant > narrow.nmant:
            w = jax.lax.reduce_precision(w, exponent_bits=narrow.nexp,
                                         mantissa_bits=narrow.nmant)
    return w.astype(dtype)


def qdense(x, w, quantized_gemm: str):
    """Dense-layer dispatch shared by the attention/MLP call sites.

    `w` may carry extra trailing structure (the GLU [h, 2, ffn] layout) —
    it is flattened to [K, prod(rest)] for the GEMM and the output is
    reshaped back, so gate/value splits keep their leading-index layout.
    A W8 weight (serving-time int8 storage) takes the int8 datapath
    regardless of the training-mode flag — the resident weight demands
    it."""
    if isinstance(w, W8):
        return _w8_matmul(x, w)
    if quantized_gemm == "none":
        if w.ndim == 2:
            return x @ w
        return jnp.einsum("...h,hcf->...cf", x, w)
    assert quantized_gemm == "int8", quantized_gemm
    if w.ndim == 2:
        return int8_matmul(x, w)
    k = w.shape[0]
    y = int8_matmul(x, w.reshape(k, -1))
    return y.reshape(*y.shape[:-1], *w.shape[1:])


def _int8_bmm_impl(x, w):
    """x [..., E, C, K] against a per-expert bank w [E, K, N] on the int8
    datapath: per-row activation scales, per-(expert, column) weight
    scales, int32 accumulation."""
    xi, sx = quantize_rows(x)
    # one quantization recipe: per-expert vmap of the dense per-column rule
    wi, sw = jax.vmap(_quantize_cols)(w)                      # [E,K,N],[E,N]
    yi = jnp.einsum("...eck,ekn->...ecn", xi, wi,
                    preferred_element_type=jnp.int32)
    y = yi.astype(jnp.float32) * sx * sw[:, None, :]
    return y.astype(x.dtype)


@jax.custom_vjp
def int8_expert_matmul(x, w):
    """Per-expert batched GEMM (MoE banks) with the same int8-forward /
    full-precision-backward recipe as int8_matmul. x [..., E, C, K],
    w [E, K, N] -> [..., E, C, N]."""
    return _int8_bmm_impl(x, w)


def _int8_bmm_fwd(x, w):
    return _int8_bmm_impl(x, w), (x, w)


def _int8_bmm_bwd(res, dy):
    x, w = res
    dx = jnp.einsum("...ecn,ekn->...eck", dy, w)
    dw = jnp.einsum("...eck,...ecn->ekn", x, dy)
    return dx.astype(x.dtype), dw.astype(w.dtype)


int8_expert_matmul.defvjp(_int8_bmm_fwd, _int8_bmm_bwd)
