"""Embedding, LM head, and the full causal language model.

TPU-native equivalent of TransformerLanguageModel / Embedding /
parallel_lm_logits / GPTModel (ref: megatron/model/language_model.py:329-638,
:133-326, :24-53; megatron/model/gpt_model.py:18-100).

- VocabParallelEmbedding's mask-ids-outside-shard + all-reduce
  (ref: core/tensor_parallel/layers.py:187-210) is a plain gather whose table
  carries 'vocab'-axis sharding; GSPMD emits the same collective.
- Untied lm_head (`not tie_embed_logits`) is a separate ('embed','vocab')
  parameter (ref: language_model.py:436-457); tied mode reuses the embedding
  table like parallel_lm_logits (ref: language_model.py:24-53).
- The vocab-parallel cross-entropy with its three TP all-reduces
  (ref: core/tensor_parallel/cross_entropy.py:14-143) is a
  shard-friendly log-softmax cross-entropy in megatron_tpu/ops/cross_entropy.py.
- Activations are [batch, seq, hidden] (batch-major): the reference's
  [s, b, h] transpose (ref: language_model.py:248) existed for NCCL-contiguity
  of sequence-parallel scatters, which GSPMD makes unnecessary.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from megatron_tpu.config import ModelConfig
from megatron_tpu.models import hyper_connections as hc
from megatron_tpu.models import transformer as tfm
from megatron_tpu.models.norms import apply_norm, norm_axes, norm_init
from megatron_tpu.models.rope import precompute_freqs, yarn_freqs
from megatron_tpu.ops import grad_accum
from megatron_tpu.ops.cross_entropy import cross_entropy_loss
from megatron_tpu.ops.dropout import dropout
from megatron_tpu.ops.embed_gather import embed_tokens
from megatron_tpu.parallel.sharding import constrain


def model_init(rng, cfg: ModelConfig, dtype=None):
    """Full-model parameter tree."""
    from megatron_tpu.config import as_dtype
    dtype = dtype or as_dtype(cfg.params_dtype)
    k_emb, k_stack, k_head, k_pos = jax.random.split(rng, 4)
    v = cfg.padded_vocab_size
    h = cfg.hidden_size
    params = {
        "embedding": {
            "word_embeddings": jax.random.normal(k_emb, (v, h), dtype) * cfg.init_method_std,
        },
        "transformer": tfm.stack_init(k_stack, cfg, dtype=dtype),
        "final_norm": norm_init(cfg.norm_type, h, dtype),
    }
    if cfg.use_position_embedding:
        params["embedding"]["position_embeddings"] = (
            jax.random.normal(k_pos, (cfg.max_position_embeddings, h), dtype)
            * cfg.init_method_std)
    if not cfg.tie_embed_logits:
        params["lm_head"] = jax.random.normal(k_head, (h, v), dtype) * cfg.init_method_std
    if cfg.mtp_num_layers:
        params["mtp"] = mtp_init(jax.random.fold_in(rng, 7), cfg, dtype)
    return params


def model_axes(cfg: ModelConfig):
    axes = {
        "embedding": {"word_embeddings": ("vocab", "embed")},
        "transformer": tfm.stack_axes(cfg),
        "final_norm": norm_axes(cfg.norm_type),
    }
    if cfg.use_position_embedding:
        axes["embedding"]["position_embeddings"] = (None, "embed")
    if not cfg.tie_embed_logits:
        axes["lm_head"] = ("embed", "vocab")
    if cfg.mtp_num_layers:
        axes["mtp"] = {
            "enorm": norm_axes(cfg.norm_type),
            "hnorm": norm_axes(cfg.norm_type),
            "eh_proj": (None, "embed"),
            "layer": tfm.layer_axes(cfg.expert_layers()),
            "final_norm": norm_axes(cfg.norm_type),
        }
    return axes


def mtp_init(rng, cfg: ModelConfig, dtype):
    """The multi-token-prediction module (depth 1, DeepSeek-V3's form): two
    norms, the projection `eh_proj` [2h, h] over [norm(embedding of the next
    token) ; norm(trunk's state)] (the embedding's half first, as the
    published checkpoints lay it out), one block, its own final norm. The
    embedding and the head are the model's own. It is in the training loss
    (`loss_fn`) and in nothing else: `model_forward` does not read it, so a
    server that drops the key serves the same model."""
    h = cfg.hidden_size
    k_proj, k_layer = jax.random.split(rng)
    return {
        "enorm": norm_init(cfg.norm_type, h, dtype),
        "hnorm": norm_init(cfg.norm_type, h, dtype),
        "eh_proj": jax.random.normal(k_proj, (2 * h, h), dtype)
        * cfg.init_method_std,
        "layer": tfm.layer_init(k_layer, cfg.expert_layers(), dtype),
        "final_norm": norm_init(cfg.norm_type, h, dtype),
    }


def mtp_logits(params, hidden, next_tokens, cfg: ModelConfig, *, rope,
               position_ids=None, segment_ids=None,
               logits_dtype=jnp.float32):
    """(logits [b, s, vocab], router aux). Position i holds the trunk's
    state `hidden[:, i]` (before the final norm) and the token after it,
    `next_tokens[:, i]`; its logits predict the token after that."""
    from megatron_tpu.config import as_dtype
    mtp, eps = params["mtp"], cfg.norm_epsilon
    e = params["embedding"]["word_embeddings"][next_tokens].astype(
        as_dtype(cfg.compute_dtype))
    x = jnp.concatenate(
        [apply_norm(cfg.norm_type, mtp["enorm"], e, eps),
         apply_norm(cfg.norm_type, mtp["hnorm"], hidden, eps)], axis=-1)
    x = x @ mtp["eh_proj"].astype(x.dtype)
    if cfg.hc_mult > 1:
        # the module's one block has the trunk's residual: its input in
        # every stream, its output the streams' sum
        x = hc.expand(x, cfg)
    x, _, aux = tfm.layer_apply(
        mtp["layer"], x, cfg.expert_layers(), rope_cos=rope.cos,
        rope_sin=rope.sin, position_ids=position_ids,
        segment_ids=segment_ids, layer_number=cfg.num_layers + 1)
    if cfg.hc_mult > 1:
        x = hc.collapse(x, cfg)
    logits = head_logits({**params, "final_norm": mtp["final_norm"]}, x, cfg,
                         logits_dtype=logits_dtype)
    return logits, aux


class RopeTables(NamedTuple):
    cos: jax.Array
    sin: jax.Array


def make_rope(cfg: ModelConfig, max_len: Optional[int] = None) -> Optional[RopeTables]:
    if not cfg.use_rotary_emb:
        return None
    max_len = max_len or cfg.max_position_embeddings
    if cfg.rope_scaling_type == "yarn":
        return RopeTables(*yarn_freqs(
            cfg.kv_channels, max_len, cfg.rope_theta,
            cfg.rope_scaling_factor, cfg.rope_original_max_position,
            cfg.rope_beta_fast, cfg.rope_beta_slow, cfg.rope_mscale,
            cfg.rope_mscale_all_dim))
    cos, sin = precompute_freqs(
        cfg.rotary_dim, max_len, theta=cfg.rope_theta,
        scaling_factor=cfg.rope_scaling_factor)
    return RopeTables(cos, sin)


def model_forward(
    params,
    tokens,  # [b, s] int32
    cfg: ModelConfig,
    *,
    position_ids=None,
    kv_caches=None,
    rope: Optional[RopeTables] = None,
    rng=None,
    deterministic: bool = True,
    logits_dtype=jnp.float32,
    segment_ids=None,
    cp_pre_zigzag: bool = False,
    return_aux: bool = False,
    return_hidden: bool = False,
    adapters=None,
    logits_rows=None,
):
    """Forward to logits [b, s, padded_vocab]. Returns (logits, kv_caches),
    or (logits, kv_caches, moe_aux) with `return_aux=True` (loss_fn uses
    it to add the MoE router's load-balancing loss), and with
    `return_hidden` one more: the last layer's output before the final
    norm (the streams' sum where `cfg.hc_mult` > 1), which the MTP module
    takes.

    `cp_pre_zigzag`: the caller pre-permuted tokens/positions into the
    ring-cp zigzag order (see loss_fn / parallel/ring_attention.py
    data_zigzag_cp) — logits come back in the SAME permuted order.

    `adapters`: (stacked LoraAdapter bank, adapter_idx [b]) — per-row
    low-rank deltas on the attention projections (multi-tenant LoRA
    serving / LoRA finetuning; models/attention.py).

    `logits_rows` [b] int: the head runs on that one position of each
    sequence alone and the logits are [b, 1, padded_vocab] (every served
    prefill, whole or chunked, wants its last real position's alone)."""
    from megatron_tpu.config import as_dtype
    compute_dtype = as_dtype(cfg.compute_dtype)
    emb = params["embedding"]["word_embeddings"]
    # the rows where the table lies, in a served program whose table would
    # be copied whole to gather them; `emb[tokens]` everywhere else
    x = embed_tokens(emb, tokens, compute_dtype, cached=kv_caches is not None)
    if cfg.use_position_embedding:
        if position_ids is None:
            pos = jnp.arange(tokens.shape[1])[None, :]
            if kv_caches is not None:
                # incremental decode: positions continue from the cache offset
                # (all layers share one offset; ref: InferenceParams keeps a
                # single sequence_len_offset, forward_step.py:17-42)
                off = kv_caches.offset[0]
                # per-slot serving pools carry [batch] offsets per layer
                pos = pos + (off[:, None] if jnp.ndim(off) == 1 else off)
        else:
            pos = position_ids
        x = x + params["embedding"]["position_embeddings"][pos].astype(compute_dtype)
    if rope is None:
        rope = make_rope(cfg)
    if rng is not None and not deterministic and cfg.hidden_dropout > 0.0:
        rng, r_emb = jax.random.split(rng)
        x = dropout(r_emb, x, cfg.hidden_dropout)
    # SP: scatter the embedding output along seq (ref: language_model.py:
    # 255-258 scatter_to_sequence_parallel_region); no-op without a mesh ctx
    x = constrain(x, tfm.RESIDUAL_AXES)
    if cfg.hc_mult > 1:
        # the residual of hc_mult streams, [b, s, hc_mult x hidden]
        # (models/hyper_connections.py): the embedding in every stream
        x = hc.expand(x, cfg)

    x, kv_caches, aux = tfm.stack_apply(
        params["transformer"], x, cfg,
        rope_cos=rope.cos if rope else None,
        rope_sin=rope.sin if rope else None,
        position_ids=position_ids, kv_caches=kv_caches,
        rng=rng, deterministic=deterministic, segment_ids=segment_ids,
        cp_pre_zigzag=cp_pre_zigzag, adapters=adapters)

    # final norm + SP gather + vocab-parallel head: ONE implementation
    # shared with both pp schedules (head_logits below)
    if logits_rows is not None:
        x = jnp.take_along_axis(x, logits_rows[:, None, None], axis=1)
    if cfg.hc_mult > 1:
        x = hc.collapse(x, cfg)         # the streams summed: [b, s, hidden]
    logits = head_logits(params, x, cfg, logits_dtype=logits_dtype)
    if return_hidden:
        return logits, kv_caches, aux, x
    if return_aux:
        return logits, kv_caches, aux
    return logits, kv_caches


def head_logits(params, x, cfg: ModelConfig, *, mb_axis: bool = False,
                logits_dtype=jnp.float32):
    """Final norm + (tied/untied) LM head with SP-aware sharding hints —
    the single implementation behind the sequential forward AND both
    pipelined tails (the lockstep pipeline's post-shard_map head and the
    1F1B per-microbatch head), so execution schedules cannot drift.
    `mb_axis` adds the leading 'microbatch' logical axis used when the
    head work is spread over 'pp'. The seq constrain is the SP gather the
    reference places before parallel_lm_logits (ref: language_model.py:
    24-53 + mappings.py:191-230): logits shard vocab over 'tp', so the
    seq dim must come off it."""
    from megatron_tpu.config import as_dtype
    compute_dtype = as_dtype(cfg.compute_dtype)
    pre = ("microbatch",) if mb_axis else ()
    x = constrain(x, pre + ("batch", "seq_sp", "act_embed"))
    x = apply_norm(cfg.norm_type, params["final_norm"], x, cfg.norm_epsilon)
    x = constrain(x, pre + ("batch", "seq", "act_embed"))
    if cfg.tie_embed_logits:
        w_out = params["embedding"]["word_embeddings"].T
    else:
        w_out = params["lm_head"]
    if w_out.dtype == compute_dtype and logits_dtype != compute_dtype:
        # a head held in the compute dtype (a model served in bf16): the
        # product's float32 accumulator comes out as it is. Rounded to bf16
        # and widened again, the top logit of a 129,280-word vocabulary
        # (about 4, where bf16 steps by 0.031) alone costs 0.008 of mean
        # log-probability error, more than int8 weights do (PERF.md section
        # 6, PR 31). A head held in float32 keeps the program it had.
        logits = jnp.dot(x, w_out, preferred_element_type=logits_dtype)
    else:
        logits = (x @ w_out.astype(compute_dtype)).astype(logits_dtype)
    return constrain(logits, pre + ("batch", "seq", "vocab"))


def loss_fn(
    params,
    tokens,  # [b, s+1] or (inputs [b,s], labels [b,s])
    cfg: ModelConfig,
    *,
    loss_mask=None,
    rope=None,
    rng=None,
    deterministic: bool = True,
    position_ids=None,
    segment_ids=None,
    adapters=None,
):
    """Causal LM loss: mean CE over unmasked positions
    (ref: finetune.py:83 loss_func — masked mean).

    `adapters` threads a LoRA factor bank + per-row index into the
    forward (training/lora.py differentiates wrt the factors with the
    base frozen — the train-side of multi-tenant adapter serving)."""
    if isinstance(tokens, tuple):
        inputs, labels = tokens
    else:
        inputs, labels = tokens[:, :-1], tokens[:, 1:]
        if loss_mask is not None and loss_mask.shape[1] == tokens.shape[1]:
            loss_mask = loss_mask[:, 1:]
    # the leaves outside the stacks take their gradient accumulators here,
    # once, ahead of every use (a tied table has two); the stacks' leaves
    # take theirs inside the scans over layers (ops/grad_accum.py)
    params = {k: v if k == "transformer"
              else grad_accum.join(grad_accum.pairs(v))
              for k, v in params.items()}

    # ring-cp zigzag: permute the batch ONCE here (ints + mask — cheap)
    # so ring attention skips its per-call q/k/v/out permute-gathers. The
    # masked-mean loss is permutation-invariant because labels and mask
    # ride the same permutation; RoPE/positions stay correct because the
    # permuted position_ids carry the original positions.
    from megatron_tpu.parallel.ring_attention import (data_zigzag_cp,
                                                      zigzag_permutation)
    cp = data_zigzag_cp(cfg, inputs.shape[1], segment_ids=segment_ids)
    pre_zigzag = cp > 0
    if pre_zigzag:
        perm, _ = zigzag_permutation(inputs.shape[1], cp)
        if position_ids is None:
            position_ids = jnp.broadcast_to(
                jnp.arange(inputs.shape[1], dtype=jnp.int32),
                inputs.shape)
        inputs = inputs[:, perm]
        labels = labels[:, perm]
        position_ids = position_ids[:, perm]
        if loss_mask is not None:
            loss_mask = loss_mask[:, perm]

    if cfg.mtp_num_layers:
        return _loss_with_mtp(params, inputs, labels, loss_mask, cfg,
                              rope=rope, rng=rng,
                              deterministic=deterministic,
                              position_ids=position_ids,
                              segment_ids=segment_ids,
                              pre_zigzag=pre_zigzag, adapters=adapters)
    logits, _, aux = model_forward(params, inputs, cfg, rope=rope, rng=rng,
                                   deterministic=deterministic,
                                   position_ids=position_ids,
                                   segment_ids=segment_ids,
                                   cp_pre_zigzag=pre_zigzag,
                                   return_aux=True, adapters=adapters)
    losses = cross_entropy_loss(logits, labels, vocab_size=cfg.vocab_size)
    # MoE router load-balancing loss (0 for dense stacks)
    aux_term = cfg.moe_aux_loss_coeff * aux if cfg.num_experts > 1 else 0.0
    if loss_mask is None:
        return jnp.mean(losses) + aux_term
    loss_mask = loss_mask.astype(losses.dtype)
    return (jnp.sum(losses * loss_mask)
            / jnp.maximum(jnp.sum(loss_mask), 1.0)) + aux_term


def _loss_with_mtp(params, inputs, labels, loss_mask, cfg: ModelConfig, *,
                   rope, rng, deterministic, position_ids, segment_ids,
                   pre_zigzag, adapters):
    """`loss_fn` for a model with an MTP module: L_main + mtp_loss_coeff x
    L_mtp. At position i the module sees the trunk's state and the NEXT
    token, `labels[:, i]`, and is scored on the one after, `labels[:, i +
    1]`; the last position has no such token and is masked out, as is every
    position whose target the loss mask leaves out. Both terms are masked
    means."""
    assert cfg.mtp_num_layers == 1 and not pre_zigzag and adapters is None
    if rope is None:
        rope = make_rope(cfg)
    logits, _, aux, hidden = model_forward(
        params, inputs, cfg, rope=rope, rng=rng, deterministic=deterministic,
        position_ids=position_ids, segment_ids=segment_ids,
        return_hidden=True)
    if loss_mask is None:
        loss_mask = jnp.ones(labels.shape, jnp.float32)
    loss_mask = loss_mask.astype(jnp.float32)

    def masked_mean(losses, mask):
        return jnp.sum(losses * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    main = masked_mean(
        cross_entropy_loss(logits, labels, vocab_size=cfg.vocab_size),
        loss_mask)
    logits2, aux2 = mtp_logits(params, hidden, labels, cfg, rope=rope,
                               position_ids=position_ids,
                               segment_ids=segment_ids)
    # the target of position i is labels[:, i + 1]; the last has none
    targets = jnp.concatenate([labels[:, 1:], labels[:, -1:]], axis=1)
    mask2 = jnp.concatenate(
        [loss_mask[:, 1:], jnp.zeros_like(loss_mask[:, :1])], axis=1)
    if segment_ids is not None:
        # a target in the next document is not this position's to predict
        mask2 = mask2 * jnp.concatenate(
            [segment_ids[:, 1:] == segment_ids[:, :-1],
             jnp.zeros_like(segment_ids[:, :1], bool)], axis=1)
    mtp = masked_mean(
        cross_entropy_loss(logits2, targets, vocab_size=cfg.vocab_size),
        mask2)
    aux_term = (cfg.moe_aux_loss_coeff * (aux + aux2)
                if cfg.num_experts > 1 else 0.0)
    return main + cfg.mtp_loss_coeff * mtp + aux_term
