"""Weight conversion: HuggingFace Llama/Falcon <-> megatron_tpu param trees.

TPU-native equivalent of the reference's conversion toolchain
(ref: weights2megatron/weights2megatron.py:16-261 — HF/Meta -> Megatron,
weights2megatron/megatron2hf.py:60-471 — Megatron -> HF, and the rotary
QKV permutation permute_qkv.py:12-81).

Layout notes:
- HF nn.Linear stores W as [out, in] and computes y = x @ W^T; our params
  store [in, out], so every projection transposes on the way in.
- RoPE convention: HF applies rotate-half (pairs (i, i+hd/2)); we use the
  Meta interleaved-pair convention (pairs (2i, 2i+1)) like the reference
  (ref: permute_qkv.py docstring + megatron/model/positional_embeddings.py).
  Conversion reorders each head's output channels so
  new[2i], new[2i+1] = hf[i], hf[i + hd/2] — numerics then match end-to-end.
- Vocab padding: the embedding/lm_head are zero-padded to
  cfg.padded_vocab_size (ref: megatron/tokenizer/tokenizer.py:42-62).
- The result is the layout-free logical tree; sharding/stacking for the
  device mesh happens at load time (unlike the reference, which bakes
  tp/pp into checkpoint files and needs the offline resharder
  tools/checkpoint_util.py).
"""
from __future__ import annotations

from typing import Mapping

import numpy as np

from megatron_tpu.config import ModelConfig


def _t(w) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(w).T)


def interleave_rope_rows(w: np.ndarray, n_heads: int, head_dim: int
                         ) -> np.ndarray:
    """Reorder a [n_heads*head_dim, in] projection's output rows from HF
    rotate-half order to Meta interleaved order
    (ref: weights2megatron/permute_qkv.py:12-81, inverse direction)."""
    out, inp = w.shape
    assert out == n_heads * head_dim
    w = w.reshape(n_heads, head_dim, inp)
    half = head_dim // 2
    inter = np.empty_like(w)
    inter[:, 0::2] = w[:, :half]
    inter[:, 1::2] = w[:, half:]
    return inter.reshape(out, inp)


def deinterleave_rope_rows(w: np.ndarray, n_heads: int, head_dim: int
                           ) -> np.ndarray:
    """Inverse of interleave_rope_rows (ours -> HF)."""
    out, inp = w.shape
    w = w.reshape(n_heads, head_dim, inp)
    half = head_dim // 2
    de = np.empty_like(w)
    de[:, :half] = w[:, 0::2]
    de[:, half:] = w[:, 1::2]
    return de.reshape(out, inp)


def _pad_vocab(w: np.ndarray, padded: int) -> np.ndarray:
    v = w.shape[0]
    if v == padded:
        return w
    assert v < padded
    return np.concatenate(
        [w, np.zeros((padded - v, w.shape[1]), w.dtype)], axis=0)


def _llama_backbone_import(sd: Mapping[str, np.ndarray], cfg: ModelConfig,
                           dtype, mlp_import) -> dict:
    """Shared Llama-backbone import (attention/norms/embedding/head);
    `mlp_import(get, prefix) -> {name: array}` supplies the per-layer MLP
    mapping — dense GLU for Llama, block_sparse_moe for Mixtral."""
    hd = cfg.kv_channels
    nq = cfg.num_attention_heads
    nkv = cfg.num_kv_heads
    L = cfg.num_layers

    def get(name):
        return np.asarray(sd[name], dtype=dtype)

    layers = {"attention": {"wq": [], "wkv": [], "wo": []},
              "mlp": None,
              "input_norm": {"scale": []},
              "post_attn_norm": {"scale": []}}
    for i in range(L):
        p = f"model.layers.{i}."
        wq = interleave_rope_rows(get(p + "self_attn.q_proj.weight"), nq, hd)
        wk = interleave_rope_rows(get(p + "self_attn.k_proj.weight"), nkv, hd)
        wv = get(p + "self_attn.v_proj.weight")
        layers["attention"]["wq"].append(_t(wq))
        layers["attention"]["wkv"].append(
            np.concatenate([_t(wk), _t(wv)], axis=1))
        layers["attention"]["wo"].append(_t(get(p + "self_attn.o_proj.weight")))
        mlp = mlp_import(get, p)
        if layers["mlp"] is None:
            layers["mlp"] = {k: [] for k in mlp}
        for k, v in mlp.items():
            layers["mlp"][k].append(v)
        layers["input_norm"]["scale"].append(get(p + "input_layernorm.weight"))
        layers["post_attn_norm"]["scale"].append(
            get(p + "post_attention_layernorm.weight"))

    stacked = {k: ({kk: np.stack(vv) for kk, vv in v.items()})
               for k, v in layers.items()}
    params = {
        "embedding": {"word_embeddings": _pad_vocab(
            get("model.embed_tokens.weight"), cfg.padded_vocab_size)},
        "transformer": stacked,
        "final_norm": {"scale": get("model.norm.weight")},
    }
    if not cfg.tie_embed_logits:
        params["lm_head"] = _t(_pad_vocab(get("lm_head.weight"),
                                          cfg.padded_vocab_size))
    return params


def hf_llama_to_params(sd: Mapping[str, np.ndarray], cfg: ModelConfig,
                       dtype=np.float32) -> dict:
    """HF LlamaForCausalLM state dict -> megatron_tpu param tree
    (ref: weights2megatron.py llama_to_megatron + permute_qkv)."""

    def mlp_import(get, p):
        gate = _t(get(p + "mlp.gate_proj.weight"))  # [h, ffn]
        up = _t(get(p + "mlp.up_proj.weight"))
        return {"w1": np.stack([gate, up], axis=1),  # [h, 2, ffn]
                "w2": _t(get(p + "mlp.down_proj.weight"))}

    return _llama_backbone_import(sd, cfg, dtype, mlp_import)


def _llama_backbone_export(params, cfg: ModelConfig, dtype,
                           mlp_export) -> dict:
    """Shared Llama-backbone export; `mlp_export(t, i, prefix) ->
    {hf_name: array}` supplies the per-layer MLP mapping."""
    hd = cfg.kv_channels
    nq = cfg.num_attention_heads
    nkv = cfg.num_kv_heads
    L = cfg.num_layers
    t = params["transformer"]
    sd = {}
    v = cfg.vocab_size
    sd["model.embed_tokens.weight"] = np.asarray(
        params["embedding"]["word_embeddings"], dtype)[:v]
    sd["model.norm.weight"] = np.asarray(params["final_norm"]["scale"], dtype)
    if not cfg.tie_embed_logits:
        sd["lm_head.weight"] = _t(np.asarray(params["lm_head"], dtype))[:v]
    else:
        sd["lm_head.weight"] = sd["model.embed_tokens.weight"]
    for i in range(L):
        p = f"model.layers.{i}."
        wq = _t(np.asarray(t["attention"]["wq"][i], dtype))  # [nq*hd, h]
        sd[p + "self_attn.q_proj.weight"] = deinterleave_rope_rows(wq, nq, hd)
        wkv = np.asarray(t["attention"]["wkv"][i], dtype)  # [h, 2*nkv*hd]
        wk, wv = wkv[:, :nkv * hd], wkv[:, nkv * hd:]
        sd[p + "self_attn.k_proj.weight"] = deinterleave_rope_rows(
            _t(wk), nkv, hd)
        sd[p + "self_attn.v_proj.weight"] = _t(wv)
        sd[p + "self_attn.o_proj.weight"] = _t(
            np.asarray(t["attention"]["wo"][i], dtype))
        sd.update(mlp_export(t, i, p))
        sd[p + "input_layernorm.weight"] = np.asarray(
            t["input_norm"]["scale"][i], dtype)
        sd[p + "post_attention_layernorm.weight"] = np.asarray(
            t["post_attn_norm"]["scale"][i], dtype)
    return sd


def params_to_hf_llama(params, cfg: ModelConfig, dtype=np.float32) -> dict:
    """megatron_tpu param tree -> HF LlamaForCausalLM state dict
    (ref: megatron2hf.py:60-471, inverse QKV permute)."""

    def mlp_export(t, i, p):
        w1 = np.asarray(t["mlp"]["w1"][i], dtype)  # [h, 2, ffn]
        return {p + "mlp.gate_proj.weight": _t(w1[:, 0]),
                p + "mlp.up_proj.weight": _t(w1[:, 1]),
                p + "mlp.down_proj.weight": _t(
                    np.asarray(t["mlp"]["w2"][i], dtype))}

    return _llama_backbone_export(params, cfg, dtype, mlp_export)


def hf_falcon_to_params(sd: Mapping[str, np.ndarray], cfg: ModelConfig,
                        dtype=np.float32) -> dict:
    """HF FalconForCausalLM state dict -> megatron_tpu param tree
    (ref: weights2megatron.py falcon_to_megatron).

    Falcon fuses QKV as nkv groups of (q_per_group + 2) heads
    [nkv, q_per_kv + 2, hd, h] — the last two heads of each group are that
    group's K and V (same grouped layout the reference reshapes to at
    megatron/model/transformer.py:440-455)."""
    hd = cfg.kv_channels
    nq = cfg.num_attention_heads
    nkv = cfg.num_kv_heads
    qpg = nq // nkv
    L = cfg.num_layers
    h = cfg.hidden_size

    def get(name):
        return np.asarray(sd[name], dtype=dtype)

    layers: dict = {
        "attention": {"wq": [], "wkv": [], "wo": []},
        "mlp": {"w1": [], "w2": []},
    }
    if cfg.use_post_ln or not cfg.parallel_attn:
        raise NotImplementedError("falcon conversion expects parallel_attn")
    layers["input_norm"] = {"scale": [], "bias": []}
    if cfg.parallel_layernorm:
        layers["mlp_norm"] = {"scale": [], "bias": []}

    for i in range(L):
        p = f"transformer.h.{i}."
        qkv = get(p + "self_attention.query_key_value.weight")
        qkv = qkv.reshape(nkv, qpg + 2, hd, h)
        q = qkv[:, :qpg].reshape(nq * hd, h)
        k = qkv[:, qpg].reshape(nkv * hd, h)
        v = qkv[:, qpg + 1].reshape(nkv * hd, h)
        q = interleave_rope_rows(q, nq, hd)
        k = interleave_rope_rows(k, nkv, hd)
        layers["attention"]["wq"].append(_t(q))
        layers["attention"]["wkv"].append(np.concatenate([_t(k), _t(v)], 1))
        layers["attention"]["wo"].append(
            _t(get(p + "self_attention.dense.weight")))
        layers["mlp"]["w1"].append(_t(get(p + "mlp.dense_h_to_4h.weight")))
        layers["mlp"]["w2"].append(_t(get(p + "mlp.dense_4h_to_h.weight")))
        if cfg.parallel_layernorm:  # falcon-40b: ln_attn + ln_mlp
            layers["input_norm"]["scale"].append(get(p + "ln_attn.weight"))
            layers["input_norm"]["bias"].append(get(p + "ln_attn.bias"))
            layers["mlp_norm"]["scale"].append(get(p + "ln_mlp.weight"))
            layers["mlp_norm"]["bias"].append(get(p + "ln_mlp.bias"))
        else:  # falcon-7b: single input_layernorm
            layers["input_norm"]["scale"].append(
                get(p + "input_layernorm.weight"))
            layers["input_norm"]["bias"].append(
                get(p + "input_layernorm.bias"))

    stacked = {k: {kk: np.stack(vv) for kk, vv in v.items()}
               for k, v in layers.items()}
    params = {
        "embedding": {"word_embeddings": _pad_vocab(
            get("transformer.word_embeddings.weight"),
            cfg.padded_vocab_size)},
        "transformer": stacked,
        "final_norm": {"scale": get("transformer.ln_f.weight"),
                       "bias": get("transformer.ln_f.bias")},
    }
    if not cfg.tie_embed_logits:
        # released falcons tie embeddings; an untied config (e.g. after
        # finetuning with untied head) round-trips through lm_head.weight
        params["lm_head"] = _t(_pad_vocab(get("lm_head.weight"),
                                          cfg.padded_vocab_size))
    return params


def params_to_hf_falcon(params, cfg: ModelConfig, dtype=np.float32) -> dict:
    """megatron_tpu param tree -> HF FalconForCausalLM state dict — the
    inverse of hf_falcon_to_params, completing the export direction the
    reference covers at megatron2hf.py:60-471 (Falcon branch).

    Rebuilds the fused grouped QKV [nkv*(q_per_kv+2)*hd, h] with each
    group's K and V as its last two heads, and un-permutes the rotary row
    order back to HF rotate-half convention."""
    if cfg.use_post_ln or not cfg.parallel_attn or cfg.use_bias:
        # mirror of the import-side guard (hf_falcon_to_params): other
        # layouts would silently drop norm/bias tensors
        raise NotImplementedError(
            "falcon export expects parallel_attn, pre-LN, no biases")
    hd = cfg.kv_channels
    nq = cfg.num_attention_heads
    nkv = cfg.num_kv_heads
    qpg = nq // nkv
    h = cfg.hidden_size
    L = cfg.num_layers
    t = params["transformer"]
    v = cfg.vocab_size

    sd = {}
    sd["transformer.word_embeddings.weight"] = np.asarray(
        params["embedding"]["word_embeddings"], dtype)[:v]
    if cfg.tie_embed_logits:
        sd["lm_head.weight"] = sd["transformer.word_embeddings.weight"]
    else:
        sd["lm_head.weight"] = _t(np.asarray(params["lm_head"], dtype))[:v]
    sd["transformer.ln_f.weight"] = np.asarray(params["final_norm"]["scale"],
                                               dtype)
    sd["transformer.ln_f.bias"] = np.asarray(params["final_norm"]["bias"],
                                             dtype)
    for i in range(L):
        p = f"transformer.h.{i}."
        q = deinterleave_rope_rows(
            _t(np.asarray(t["attention"]["wq"][i], dtype)), nq, hd)
        wkv = np.asarray(t["attention"]["wkv"][i], dtype)  # [h, 2*nkv*hd]
        k = deinterleave_rope_rows(_t(wkv[:, :nkv * hd]), nkv, hd)
        vv = _t(wkv[:, nkv * hd:])
        qkv = np.concatenate(
            [q.reshape(nkv, qpg, hd, h), k.reshape(nkv, 1, hd, h),
             vv.reshape(nkv, 1, hd, h)], axis=1)
        sd[p + "self_attention.query_key_value.weight"] = qkv.reshape(
            nkv * (qpg + 2) * hd, h)
        sd[p + "self_attention.dense.weight"] = _t(
            np.asarray(t["attention"]["wo"][i], dtype))
        sd[p + "mlp.dense_h_to_4h.weight"] = _t(
            np.asarray(t["mlp"]["w1"][i], dtype))
        sd[p + "mlp.dense_4h_to_h.weight"] = _t(
            np.asarray(t["mlp"]["w2"][i], dtype))
        if cfg.parallel_layernorm:  # falcon-40b
            sd[p + "ln_attn.weight"] = np.asarray(
                t["input_norm"]["scale"][i], dtype)
            sd[p + "ln_attn.bias"] = np.asarray(
                t["input_norm"]["bias"][i], dtype)
            sd[p + "ln_mlp.weight"] = np.asarray(
                t["mlp_norm"]["scale"][i], dtype)
            sd[p + "ln_mlp.bias"] = np.asarray(
                t["mlp_norm"]["bias"][i], dtype)
        else:  # falcon-7b
            sd[p + "input_layernorm.weight"] = np.asarray(
                t["input_norm"]["scale"][i], dtype)
            sd[p + "input_layernorm.bias"] = np.asarray(
                t["input_norm"]["bias"][i], dtype)
    return sd


def hf_mixtral_to_params(sd: Mapping[str, np.ndarray], cfg: ModelConfig,
                         dtype=np.float32) -> dict:
    """HF MixtralForCausalLM state dict -> megatron_tpu param tree.

    Beyond the reference (it has no MoE at all — SURVEY.md §2.8): the
    attention/norm/embedding mapping is exactly the Llama one (Mixtral IS
    a Llama backbone: GQA + RMSNorm + rotate-half RoPE at theta 1e6), and
    each block_sparse_moe maps onto models/moe.py:
      gate.weight [E, h]            -> router [h, E]
      experts.{e}.w1 (gate proj)    -> w1[e, :, :ffn]
      experts.{e}.w3 (up proj)      -> w1[e, :, ffn:]
      experts.{e}.w2 (down proj)    -> w2[e]
    Routing semantics match by construction: Mixtral's softmax-then-top-k
    renormalization equals our renormalized top-k of the full softmax.
    Mixtral is DROPLESS — set moe_capacity_factor >= num_experts /
    moe_top_k for bit-faithful inference (guarantees no capacity drops).
    """
    assert cfg.num_experts > 1, "mixtral conversion needs num_experts > 1"
    E = cfg.num_experts

    def mlp_import(get, p):
        m = p + "block_sparse_moe."
        w1 = np.stack([
            np.concatenate([_t(get(m + f"experts.{e}.w1.weight")),   # gate
                            _t(get(m + f"experts.{e}.w3.weight"))],  # up
                           axis=1)
            for e in range(E)])                                # [E, h, 2 ffn]
        return {"router": _t(get(m + "gate.weight")),
                "w1": w1,
                "w2": np.stack([_t(get(m + f"experts.{e}.w2.weight"))
                                for e in range(E)])}

    return _llama_backbone_import(sd, cfg, dtype, mlp_import)


def params_to_hf_mixtral(params, cfg: ModelConfig, dtype=np.float32) -> dict:
    """megatron_tpu MoE param tree -> HF MixtralForCausalLM state dict
    (inverse of hf_mixtral_to_params)."""
    E = cfg.num_experts

    def mlp_export(t, i, p):
        m = p + "block_sparse_moe."
        out = {m + "gate.weight": _t(np.asarray(t["mlp"]["router"][i],
                                                dtype))}
        w1 = np.asarray(t["mlp"]["w1"][i], dtype)   # [E, h, 2 ffn]
        w2 = np.asarray(t["mlp"]["w2"][i], dtype)   # [E, ffn, h]
        ffn = w2.shape[1]
        for e in range(E):
            out[m + f"experts.{e}.w1.weight"] = _t(w1[e, :, :ffn])
            out[m + f"experts.{e}.w3.weight"] = _t(w1[e, :, ffn:])
            out[m + f"experts.{e}.w2.weight"] = _t(w2[e])
        return out

    return _llama_backbone_export(params, cfg, dtype, mlp_export)
