"""Transformer layer and scan-stacked transformer.

TPU-native equivalent of ParallelTransformerLayer / ParallelTransformer
(ref: megatron/model/transformer.py:581-815 and :896-1251). Structural
features carried over:

- pre-LN (default) vs post-LN (`use_post_ln`, ref: transformer.py:629-633)
- Falcon-style parallel attention+MLP sharing one input norm, with no
  attention residual-dropout (`parallel_attn`, ref: transformer.py:647,773-805)
- dedicated MLP layernorm for Falcon-40B (`parallel_layernorm`,
  ref: transformer.py:604,612-628,770-771)
- LIMA per-layer dropout ramp p_l = l/L * p (ref: transformer.py:963-970)
- activation recompute: 'full' remats each layer, 'selective' saves GEMM
  outputs but recomputes the attention softmax — the jax.checkpoint
  formulation of the reference's tensor_parallel.checkpoint machinery
  (ref: megatron/core/tensor_parallel/random.py:175-252, transformer.py:357,
  1079-1145). No RNG save/restore is needed: jax.random keys are pure.

TPU-first design choices: all layers share one set of stacked parameters
(leading 'layers' dim) applied via `lax.scan` — one compiled layer body
regardless of depth, which keeps compile time flat for 80-layer models and
gives the pipeline partitioner a natural chunking axis.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from megatron_tpu.config import STATE_KINDS, ModelConfig
from megatron_tpu.models.attention import attention_apply, attention_axes, attention_init
from megatron_tpu.models.mlp import mlp_apply, mlp_axes, mlp_init
from megatron_tpu.models.norms import apply_norm, norm_axes, norm_init
from megatron_tpu.ops import grad_accum
from megatron_tpu.ops.dropout import drop_path as _drop_path
from megatron_tpu.ops.dropout import dropout as _dropout
from megatron_tpu.parallel.sharding import constrain

# Residual-stream activations between TP blocks live seq-sharded when
# sequence parallelism is on (ref: layers.py:225-296 — the SP all-gather/
# reduce-scatter pair); `constrain` is a no-op outside a mesh context.
RESIDUAL_AXES = ("batch", "seq_sp", "act_embed")


# ---------------------------------------------------------------------------
# single layer
# ---------------------------------------------------------------------------

def layer_init(rng, cfg: ModelConfig, dtype=jnp.float32,
               cross_attn: bool = False, mixer: str = "full_attention"):
    """`mixer` "kda": a Kimi Delta Attention mixer (`params["kda"]`,
    models/kda.py), "linear_attention": a Gated DeltaNet mixer
    (`params["gdn"]`, models/gated_delta.py), "conv": a gated short convolution (`params["conv"]`,
    models/short_conv.py), "mamba": a selective state-space mixer
    (`params["mamba"]`, models/mamba.py), where the others have
    `params["attention"]`.

    Norm layout mirrors ref: transformer.py:606-633 —
    pre-LN: input_layernorm + post_attention_layernorm (output_layernorm=Id);
    post-LN: input_layernorm=Id, post_attention_layernorm + output_layernorm;
    parallel_attn drops post_attention_layernorm; parallel_layernorm adds a
    dedicated mlp norm.

    A layer of ONE sublayer (`cfg.one_sublayer`) is `input_norm` and the one
    thing `mixer` names: "mamba2" (`params["mamba2"]`, models/mamba2.py),
    "full_attention" (`params["attention"]`) or "moe" (`params["mlp"]`, the
    experts)."""
    k_attn, k_mlp, k_inter = jax.random.split(rng, 3)
    if cfg.one_sublayer:
        assert not cross_attn
        name, init, _ = _one_sublayer(mixer)
        return {"input_norm": norm_init(cfg.norm_type, cfg.hidden_size,
                                        dtype),
                name: init(k_mlp if mixer == "moe" else k_attn, cfg, dtype)}
    if cfg.num_experts > 1:
        from megatron_tpu.models.moe import moe_init
        mlp_params = moe_init(k_mlp, cfg, dtype)
    else:
        mlp_params = mlp_init(k_mlp, cfg, dtype)
    if mixer == "conv":
        from megatron_tpu.models.short_conv import short_conv_init
        params = {"conv": short_conv_init(k_attn, cfg, dtype)}
    elif mixer == "mamba":
        from megatron_tpu.models.mamba import mamba_init
        params = {"mamba": mamba_init(k_attn, cfg, dtype)}
    elif mixer == "kda":
        from megatron_tpu.models.kda import kda_init
        params = {"kda": kda_init(k_attn, cfg, dtype)}
    elif mixer == "linear_attention":
        from megatron_tpu.models.gated_delta import gdn_init
        params = {"gdn": gdn_init(k_attn, cfg, dtype)}
    elif cfg.mla:
        from megatron_tpu.models.mla import mla_init
        params = {"attention": mla_init(k_attn, cfg, dtype)}
    else:
        params = {"attention": attention_init(k_attn, cfg, dtype)}
    params["mlp"] = mlp_params
    if cross_attn:
        # decoder cross-attention + its input norm
        # (ref: transformer.py:664-683,782-794)
        params["inter_attention"] = attention_init(k_inter, cfg, dtype)
        params["post_inter_norm"] = norm_init(cfg.norm_type,
                                              cfg.hidden_size, dtype)
    if not cfg.use_post_ln:
        params["input_norm"] = norm_init(cfg.norm_type, cfg.hidden_size, dtype)
    else:
        params["output_norm"] = norm_init(cfg.norm_type, cfg.hidden_size, dtype)
    if not cfg.parallel_attn:
        params["post_attn_norm"] = norm_init(cfg.norm_type, cfg.hidden_size, dtype)
    if cfg.parallel_layernorm:
        params["mlp_norm"] = norm_init(cfg.norm_type, cfg.hidden_size, dtype)
    if cfg.hc_mult > 1:
        # the maps of the two sublayers' hyper-connections
        from megatron_tpu.models.hyper_connections import hc_init
        k_a, k_m = jax.random.split(jax.random.fold_in(rng, 41))
        params["hc_attn"] = hc_init(k_a, cfg, dtype)
        params["hc_mlp"] = hc_init(k_m, cfg, dtype)
    return params


def _one_sublayer(mixer: str):
    """(its name in the layer's parameters, its init, its axes) of the ONE
    sublayer a layer of kind `mixer` holds (`cfg.one_sublayer`)."""
    if mixer == "mamba2":
        from megatron_tpu.models.mamba2 import mamba2_axes, mamba2_init
        return "mamba2", mamba2_init, mamba2_axes
    if mixer == "moe":
        from megatron_tpu.models.moe import moe_axes, moe_init
        return "mlp", moe_init, moe_axes
    assert mixer == "full_attention", mixer
    return "attention", attention_init, attention_axes


def layer_axes(cfg: ModelConfig, cross_attn: bool = False,
               mixer: str = "full_attention"):
    if cfg.one_sublayer:
        name, _, axes = _one_sublayer(mixer)
        return {"input_norm": norm_axes(cfg.norm_type), name: axes(cfg)}
    if cfg.num_experts > 1:
        from megatron_tpu.models.moe import moe_axes
        mlp_ax = moe_axes(cfg)
    else:
        mlp_ax = mlp_axes(cfg)
    if mixer == "conv":
        from megatron_tpu.models.short_conv import short_conv_axes
        axes = {"conv": short_conv_axes(cfg)}
    elif mixer == "mamba":
        from megatron_tpu.models.mamba import mamba_axes
        axes = {"mamba": mamba_axes(cfg)}
    elif mixer == "kda":
        from megatron_tpu.models.kda import kda_axes
        axes = {"kda": kda_axes(cfg)}
    elif mixer == "linear_attention":
        from megatron_tpu.models.gated_delta import gdn_axes
        axes = {"gdn": gdn_axes(cfg)}
    elif cfg.mla:
        from megatron_tpu.models.mla import mla_axes
        axes = {"attention": mla_axes(cfg)}
    else:
        axes = {"attention": attention_axes(cfg)}
    axes["mlp"] = mlp_ax
    if cross_attn:
        axes["inter_attention"] = attention_axes(cfg)
        axes["post_inter_norm"] = norm_axes(cfg.norm_type)
    if not cfg.use_post_ln:
        axes["input_norm"] = norm_axes(cfg.norm_type)
    else:
        axes["output_norm"] = norm_axes(cfg.norm_type)
    if not cfg.parallel_attn:
        axes["post_attn_norm"] = norm_axes(cfg.norm_type)
    if cfg.parallel_layernorm:
        axes["mlp_norm"] = norm_axes(cfg.norm_type)
    if cfg.hc_mult > 1:
        from megatron_tpu.models.hyper_connections import hc_axes
        axes["hc_attn"] = axes["hc_mlp"] = hc_axes(cfg)
    return axes


def layer_apply(
    params,
    x,
    cfg: ModelConfig,
    *,
    rope_cos=None,
    rope_sin=None,
    position_ids=None,
    kv_cache=None,
    cache_layer=None,
    kind_layer=None,
    expert_banks=None,
    bank_layer=None,
    layer_number: int = 1,
    hidden_dropout: Optional[float] = None,
    drop_path_rate=None,
    rng=None,
    deterministic: bool = True,
    segment_ids=None,
    causal: bool = True,
    encoder_output=None,
    cp_pre_zigzag: bool = False,
    adapters=None,
    mixer: str = "full_attention",
):
    """One transformer layer. x: [b, s, h]. Returns (x, kv_cache, aux) —
    `aux` is the MoE router's load-balancing loss (0.0 for dense MLPs).

    With `cfg.hc_mult` = n > 1 the residual is n streams, x: [b, s, n h],
    and each of the two sublayers (with its own pre-norm) is wrapped in its
    hyper-connection (models/hyper_connections.py): X' = H_res X + H_post^T
    F(H_pre X). `hc_mult` 1 takes none of that code.

    `kv_cache` is the cache stacked over layers and `cache_layer` this
    layer's index in it; both pass through to attention_apply, which
    appends in place and hands the stack back. `expert_banks`, where the
    loop gives any, are the MoE parameters it did not scan, stacked over
    the layers of their own stack and read at `bank_layer` (models/moe.py::
    split_stacked_banks), which is `cache_layer` where the model has one
    stack. `kind_layer`: in a stack of window and full layers
    (`_period_stack_apply`) or of convolution and attention layers
    (`_pattern_stack_apply`; `mixer` says which this one is), the layer's
    index in its own kind's cache stack. In a pattern of ONE-sublayer layers
    (`cfg.one_sublayer`) `mixer` names the layer's only sublayer, which may
    be the feed-forward ("moe": no cache row, no mixer).

    `adapters`: (per-layer LoraAdapter bank, adapter_idx [b]) for the
    SELF-attention projections only (multi-tenant LoRA serving —
    models/attention.py; cross-attention has no adapter path).

    `encoder_output` enables the decoder cross-attention sublayer between
    self-attention and the MLP (ref: transformer.py:782-794).

    Residual structure follows ref: transformer.py:754-815 exactly:
      ln_out = input_norm(x)            (Identity when post-LN)
      attn   = attention(ln_out)
      parallel_attn:  out = output-ish residual handled below
      else:  ln_in  = x + drop(attn)
             ln_out = post_attn_norm(ln_in)
             mlp    = mlp(ln_out)
             out    = ln_in + drop(mlp)
      out = output_norm(out)            (Identity when pre-LN)
    """
    eps = cfg.norm_epsilon
    p_drop = cfg.hidden_dropout if hidden_dropout is None else hidden_dropout
    if deterministic:
        rng = None
    r_attn = r_mlp = r_score = r_inter = r_dp1 = r_dp2 = None
    if rng is not None:
        (r_attn, r_mlp, r_score, r_inter,
         r_dp1, r_dp2) = jax.random.split(rng, 6)

    def _branch(r_dp, branch):
        # residual + drop_path(dropout(branch)) when stochastic depth is
        # on (ref: transformer.py:723-730); drop_path_rate may be a
        # traced per-layer scalar from the scanned linspace ramp
        if drop_path_rate is None or r_dp is None:
            return branch
        return _drop_path(r_dp, branch, drop_path_rate)

    def _mlp_branch(inp):
        """Dense MLP or the MoE expert bank: (out, aux_loss)."""
        if cfg.num_experts > 1:
            from megatron_tpu.models.moe import moe_apply
            if expert_banks:
                return moe_apply({**params["mlp"], **expert_banks}, inp, cfg,
                                 bank_layer=bank_layer)
            return moe_apply(params["mlp"], inp, cfg)
        return (mlp_apply(params["mlp"], inp, cfg,
                          read_once=kv_cache is not None),
                jnp.zeros((), jnp.float32))

    def _mixer_branch(ln_out, kv_cache):
        """The layer's mixer on its normed input: (out, the cache)."""
        if mixer in STATE_KINDS:
            assert causal and encoder_output is None and adapters is None \
                and segment_ids is None and not cp_pre_zigzag, (
                "a convolution or state-space layer is causal, unsharded, "
                "over one document")
            if mixer == "kda":
                from megatron_tpu.models.kda import kda_apply
                return kda_apply(
                    params["kda"], ln_out, cfg, kv_cache=kv_cache,
                    kind_layer=kind_layer)
            if mixer == "linear_attention":
                from megatron_tpu.models.gated_delta import gdn_apply
                return gdn_apply(
                    params["gdn"], ln_out, cfg, kv_cache=kv_cache,
                    kind_layer=kind_layer)
            if mixer == "mamba2":
                from megatron_tpu.models.mamba2 import mamba2_apply
                return mamba2_apply(
                    params["mamba2"], ln_out, cfg, kv_cache=kv_cache,
                    kind_layer=kind_layer)
            if mixer == "mamba":
                from megatron_tpu.models.mamba import mamba_apply
                return mamba_apply(
                    params["mamba"], ln_out, cfg, kv_cache=kv_cache,
                    kind_layer=kind_layer)
            from megatron_tpu.models.short_conv import short_conv_apply
            return short_conv_apply(
                params["conv"], ln_out, cfg, kv_cache=kv_cache,
                kind_layer=kind_layer)
        if cfg.mla:
            from megatron_tpu.models.mla import mla_apply
            assert causal and encoder_output is None and adapters is None \
                and not cp_pre_zigzag, "MLA is causal self-attention, unsharded"
            # in a pattern of mixers the latent rows are the attention
            # layers' alone: the layer's index among its own kind
            return mla_apply(
                params["attention"], ln_out, cfg,
                rope_cos=rope_cos, rope_sin=rope_sin, position_ids=position_ids,
                kv_cache=kv_cache,
                cache_layer=cache_layer if kind_layer is None else kind_layer,
                segment_ids=segment_ids)
        return attention_apply(
            params["attention"], ln_out, cfg,
            rope_cos=rope_cos, rope_sin=rope_sin, position_ids=position_ids,
            kv_cache=kv_cache, cache_layer=cache_layer,
            layer_number=layer_number,
            dropout_rng=r_score, deterministic=deterministic,
            segment_ids=segment_ids, causal=causal,
            cp_pre_zigzag=cp_pre_zigzag, adapters=adapters,
            kind_layer=kind_layer)

    if cfg.one_sublayer:
        # x + F(norm(x)): F the mixer, or the feed-forward alone ("moe")
        ln_out = apply_norm(cfg.norm_type, params["input_norm"], x, eps)
        aux = jnp.zeros((), jnp.float32)
        if mixer == "moe":
            out, aux = _mlp_branch(ln_out)
        else:
            out, kv_cache = _mixer_branch(ln_out, kv_cache)
        out = x + _branch(r_dp1, _dropout(r_attn, out, p_drop))
        return constrain(out, RESIDUAL_AXES), kv_cache, aux

    if cfg.hc_mult > 1:
        from megatron_tpu.models.hyper_connections import hc_sublayer
        assert (not cfg.parallel_attn and not cfg.use_post_ln
                and mixer == "full_attention" and encoder_output is None
                and adapters is None and drop_path_rate is None), (
            "hyper-connections wrap a pre-norm attention sublayer and a "
            "feed-forward sublayer: no parallel block, post-LN, convolution, "
            "encoder, adapter bank or drop_path (config.validate)")

        def attn_sublayer(inp):
            out, cache = _mixer_branch(apply_norm(
                cfg.norm_type, params["input_norm"], inp, eps), kv_cache)
            return _dropout(r_attn, out, p_drop), cache

        def mlp_sublayer(inp):
            out, aux = _mlp_branch(apply_norm(
                cfg.norm_type, params["post_attn_norm"], inp, eps))
            return _dropout(r_mlp, out, p_drop), aux
        x, kv_cache = hc_sublayer(params["hc_attn"], x, cfg, attn_sublayer)
        x, aux = hc_sublayer(params["hc_mlp"], x, cfg, mlp_sublayer)
        return x, kv_cache, aux

    residual = x
    if cfg.use_post_ln:
        ln_out = x  # input_layernorm = Identity (ref: transformer.py:630-631)
    else:
        ln_out = apply_norm(cfg.norm_type, params["input_norm"], x, eps)

    attn_out, kv_cache = _mixer_branch(ln_out, kv_cache)

    if cfg.parallel_attn:
        # Falcon block: no dropout-add after attention
        # (ref: transformer.py:781-782 layernorm_input = attention_output);
        # mlp input is mlp_norm(x) (Falcon-40B) or the shared input norm
        # (ref: transformer.py:770-771, 796-801)
        if cfg.parallel_layernorm:
            mlp_in = apply_norm(cfg.norm_type, params["mlp_norm"], residual, eps)
        else:
            mlp_in = ln_out
        mlp_out, aux = _mlp_branch(mlp_in)
        out = residual + _branch(r_dp1,
                                 _dropout(r_mlp, mlp_out + attn_out, p_drop))
    else:
        ln_in = constrain(
            residual + _branch(r_dp1, _dropout(r_attn, attn_out, p_drop)),
            RESIDUAL_AXES)
        if encoder_output is not None and "inter_attention" in params:
            # decoder cross-attention sublayer (ref: transformer.py:782-794)
            ln_x = apply_norm(cfg.norm_type, params["post_inter_norm"],
                              ln_in, eps)
            inter_out, _ = attention_apply(
                params["inter_attention"], ln_x, cfg,
                deterministic=deterministic, causal=False,
                kv_input=encoder_output)
            ln_in = ln_in + _dropout(r_inter, inter_out, p_drop)
        ln2 = apply_norm(cfg.norm_type, params["post_attn_norm"], ln_in, eps)
        mlp_out, aux = _mlp_branch(ln2)
        out = ln_in + _branch(r_dp2, _dropout(r_mlp, mlp_out, p_drop))

    if cfg.use_post_ln:
        out = apply_norm(cfg.norm_type, params["output_norm"], out, eps)
    return constrain(out, RESIDUAL_AXES), kv_cache, aux


# ---------------------------------------------------------------------------
# stacked transformer (scan over layers)
# ---------------------------------------------------------------------------

def stack_init(rng, cfg: ModelConfig, num_layers: Optional[int] = None,
               dtype=jnp.float32, cross_attn: bool = False):
    """Stacked params with leading 'layers' dim via vmap over per-layer init.
    A model whose first layers are dense and whose others have experts
    (`cfg.first_k_dense_replace`) has two stacks, {"dense", "moe"}."""
    n = num_layers if num_layers is not None else cfg.num_layers
    k = cfg.first_k_dense_replace
    if cfg.layer_types is not None:
        assert num_layers is None and not cross_attn
        return {name: {
            kind: jax.vmap(lambda key, kind=kind: layer_init(
                key, group_cfg, dtype, mixer=kind))(jax.random.split(
                    jax.random.fold_in(rng, i), kinds.count(kind)))
            for i, kind in enumerate(sorted(set(kinds)))}
            for name, group_cfg, kinds, rng in _pattern_groups(cfg, rng)}
    if k:
        assert num_layers is None and not cross_attn
        r_dense, r_moe = jax.random.split(rng)
        return {"dense": stack_init(r_dense, cfg.dense_layers(), k, dtype),
                "moe": stack_init(r_moe, cfg.expert_layers(), n - k, dtype)}
    keys = jax.random.split(rng, n)
    return jax.vmap(lambda k: layer_init(k, cfg, dtype,
                                         cross_attn=cross_attn))(keys)


def stack_axes(cfg: ModelConfig, cross_attn: bool = False):
    """Logical axes for stacked params: prepend 'layers'."""
    stacked = lambda per_layer: jax.tree.map(  # noqa: E731
        lambda ax: ("layers",) + ax, per_layer,
        is_leaf=lambda x: isinstance(x, tuple))
    if cfg.layer_types is not None:
        return {name: {kind: stacked(layer_axes(group_cfg, mixer=kind))
                       for kind in sorted(set(kinds))}
                for name, group_cfg, kinds, _ in _pattern_groups(cfg)}
    if cfg.first_k_dense_replace:
        return {"dense": stack_axes(cfg.dense_layers()),
                "moe": stack_axes(cfg.expert_layers())}
    return stacked(layer_axes(cfg, cross_attn=cross_attn))


def _pattern_groups(cfg: ModelConfig, rng=None):
    """The groups of a model with `cfg.layer_types`, in order: (name, the
    group's configuration, its layers' mixers, its share of `rng`). One
    group "layers" where every layer has the same feed-forward, else "dense"
    (the `first_k_dense_replace` leading layers) and "moe". A group's
    parameters are stacked BY KIND, {"conv": ..., "full_attention": ...},
    each in the model's order: the kinds' trees differ."""
    k, types = cfg.first_k_dense_replace, cfg.layer_types
    if not k:
        return [("layers", cfg, types, rng)]
    rngs = (None, None) if rng is None else jax.random.split(rng)
    return [("dense", cfg.dense_layers(), types[:k], rngs[0]),
            ("moe", cfg.expert_layers(), types[k:], rngs[1])]


def _pattern_period(kinds):
    """(P, n): the first n * P of `kinds` are n >= 1 repeats of its first P
    entries, chosen to cover the most layers with n >= 2 (the shortest such
    P), else one period of them all."""
    best = (len(kinds), 1)
    covered = 0
    for P in range(1, len(kinds) // 2 + 1):
        n = 1
        while kinds[n * P:(n + 1) * P] == kinds[:P]:
            n += 1
        if n >= 2 and n * P > covered:
            best, covered = (P, n), n * P
    return best


def lima_dropout_rates(cfg: ModelConfig, num_layers: int):
    """LIMA ramp: linspace(0, p_hidden, L) — first layer exactly 0.0
    (ref: transformer.py:963-970 torch.linspace(0, hidden_dropout, L))."""
    if not cfg.lima_dropout:
        return jnp.full((num_layers,), cfg.hidden_dropout, jnp.float32)
    return jnp.linspace(0.0, cfg.hidden_dropout, num_layers, dtype=jnp.float32)


def drop_path_rates(cfg: ModelConfig, num_layers: int):
    """Stochastic-depth ramp: linspace(0, drop_path_rate, L)
    (ref: transformer.py:961 drop_path_rates)."""
    return jnp.linspace(0.0, cfg.drop_path_rate, num_layers,
                        dtype=jnp.float32)


def stack_apply(
    stacked_params,
    x,
    cfg: ModelConfig,
    *,
    rope_cos=None,
    rope_sin=None,
    position_ids=None,
    kv_caches=None,  # stacked KVCache with leading layers dim, or None
    rng=None,
    deterministic: bool = True,
    layer_offset: int = 0,
    segment_ids=None,
    causal: bool = True,
    encoder_output=None,
    cp_pre_zigzag: bool = False,
    adapters=None,
    cache_offset: int = 0,
):
    """Apply all (or a pipeline stage's worth of) layers via lax.scan.

    Two stacks ({"dense", "moe"}: `cfg.first_k_dense_replace` dense layers
    ahead of the expert layers) run one after the other, each its own scan
    over its own kind of layer, with ONE cache and one running layer number
    through both: `cache_offset` is where a stack's first layer sits in the
    cache.

    Returns (x, kv_caches, aux) — `aux` sums the layers' MoE router
    load-balancing losses (0.0 for dense stacks; loss_fn weighs it by
    cfg.moe_aux_loss_coeff).

    `layer_offset` preserves layer_number-dependent behavior across pipeline
    stages (ref: transformer.py:1014-1044 layer offsets for vpp).

    `adapters`: (STACKED LoraAdapter with a leading 'layers' dim,
    adapter_idx [b]) — the factor bank rides the scan with the params
    (each step slices one layer's [n, ...] bank), the per-row
    index is layer-invariant and closes over the body. None compiles to
    exactly today's graph (multi-tenant LoRA serving,
    models/attention.py).

    Inside a training step of several micro-batches each leaf takes its
    float32 gradient accumulator with it (`grad_accum.pairs`) and the loop
    over layers joins the two (`grad_accum.scan`), so that a layer's weight
    gradients are summed into the accumulators where the backward pass
    writes them: ops/grad_accum.py. Anywhere else the first finds nothing
    and the second is `jax.lax.scan`."""
    if cfg.layer_types is not None:
        assert layer_offset == 0 and adapters is None \
            and encoder_output is None and not cp_pre_zigzag and causal \
            and segment_ids is None, (
            "a pattern of convolution and attention layers has no pipeline "
            "stage, no adapter bank, no encoder and one document a row")
        return _pattern_stack_apply(
            stacked_params, x, cfg, rope_cos=rope_cos, rope_sin=rope_sin,
            position_ids=position_ids, kv_caches=kv_caches, rng=rng,
            deterministic=deterministic)
    if cfg.window_layer_period:
        assert layer_offset == 0 and adapters is None \
            and encoder_output is None and not cp_pre_zigzag and causal, (
            "a stack of window and full layers has no pipeline stage, no "
            "adapter bank and no encoder")
        return _period_stack_apply(
            stacked_params, x, cfg, rope_cos=rope_cos, rope_sin=rope_sin,
            position_ids=position_ids, kv_caches=kv_caches, rng=rng,
            deterministic=deterministic, segment_ids=segment_ids)
    k_dense = cfg.first_k_dense_replace
    if cfg.hc_mult > 1:
        # x: [b, s, hc_mult x hidden], the streams side by side
        assert adapters is None and encoder_output is None and causal, (
            "a residual of streams (hc_mult > 1) has no adapter bank and "
            "no encoder")
    if k_dense:
        assert layer_offset == 0 and adapters is None, (
            "two stacks have no pipeline stage and no adapter bank")
        common = dict(
            rope_cos=rope_cos, rope_sin=rope_sin, position_ids=position_ids,
            rng=rng, deterministic=deterministic, segment_ids=segment_ids,
            causal=causal, encoder_output=encoder_output,
            cp_pre_zigzag=cp_pre_zigzag)
        x, kv_caches, aux_dense = stack_apply(
            stacked_params["dense"], x, cfg.dense_layers(),
            kv_caches=kv_caches, **common)
        x, kv_caches, aux_moe = stack_apply(
            stacked_params["moe"], x, cfg.expert_layers(),
            kv_caches=kv_caches, layer_offset=k_dense, cache_offset=k_dense,
            **common)
        return x, kv_caches, aux_dense + aux_moe
    stacked_params = grad_accum.pairs(stacked_params)
    num_layers = jax.tree.leaves(stacked_params)[0].shape[0]
    drop_rates = lima_dropout_rates(cfg, cfg.num_layers)
    drop_rates = jax.lax.dynamic_slice_in_dim(drop_rates, layer_offset, num_layers)
    dp_rates = jax.lax.dynamic_slice_in_dim(
        drop_path_rates(cfg, cfg.num_layers), layer_offset, num_layers)
    use_drop_path = cfg.drop_path_rate > 0.0
    layer_ids = layer_offset + jnp.arange(num_layers)
    # the stacked factor bank scans with the params/caches; the per-row
    # adapter index is the same for every layer and closes over the body
    lora_stack, adapter_idx = (adapters if adapters is not None
                               else (None, None))

    # The stacked caches ride the loop as its CARRY and go down whole,
    # with the layer's index: attention_apply writes the layer's new
    # tokens into the carried buffer itself (XLA updates a carry in
    # place) and reads its layer of it for the products. Scanned xs -> ys
    # they cost a second stacked cache and its copy back; handed down one
    # layer at a time, a copy of that layer out and in (PERF.md section
    # 6, PRs 27 and 28). Without caches the carry holds None, an empty
    # pytree, and the loop is the plain one. The dropless experts' banks go
    # down whole beside the caches, for the same reason: they close over
    # the body and are read at the layer's index, never cut out.
    expert_banks = None
    if kv_caches is not None and cfg.num_experts > 1:
        from megatron_tpu.models.moe import split_stacked_banks
        expert_banks, scanned_mlp = split_stacked_banks(
            stacked_params["mlp"], cfg)
        stacked_params = {**stacked_params, "mlp": scanned_mlp}

    def body(carry, scanned):
        h, aux_sum, caches = carry
        p, rate, dp_rate, lid, lw = scanned
        layer_rng = None
        if rng is not None and not deterministic:
            layer_rng = jax.random.fold_in(rng, lid)
        # the layer's index in its own stack (its banks) and in the cache,
        # which a stack behind another enters at `cache_offset`
        li = None if caches is None else lid - layer_offset
        h, caches, aux = layer_apply(
            p, h, cfg, rope_cos=rope_cos, rope_sin=rope_sin,
            position_ids=position_ids, kv_cache=caches,
            cache_layer=(li + cache_offset
                         if cache_offset and caches is not None else li),
            expert_banks=expert_banks, bank_layer=li,
            layer_number=lid + 1, hidden_dropout=rate,
            drop_path_rate=dp_rate if use_drop_path else None,
            rng=layer_rng,
            deterministic=deterministic, segment_ids=segment_ids,
            causal=causal, encoder_output=encoder_output,
            cp_pre_zigzag=cp_pre_zigzag,
            adapters=(lw, adapter_idx) if lw is not None else None)
        return (h, aux_sum + aux, caches), None

    if cfg.recompute_granularity == "full":
        body = jax.checkpoint(body, prevent_cse=False)
    elif cfg.recompute_granularity == "selective":
        # save GEMM outputs, recompute the attention softmax — the analogue of
        # the reference's selective core-attention recompute (transformer.py:357)
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            prevent_cse=False)

    aux0 = jnp.zeros((), jnp.float32)
    # None entries are empty pytrees: scan passes them through untouched
    # (the no-adapters case scans the same body shape)
    xs = (stacked_params, drop_rates, dp_rates, layer_ids, lora_stack)
    (x, aux, kv_caches), _ = grad_accum.scan(body, (x, aux0, kv_caches), xs)
    return x, kv_caches, aux


def _period_stack_apply(stacked_params, x, cfg: ModelConfig, *, rope_cos,
                        rope_sin, position_ids, kv_caches, rng,
                        deterministic, segment_ids):
    """`stack_apply` for a stack that mixes two kinds of attention layer
    (`cfg.window_layer_period` = P: P - 1 window layers, then one full
    layer, and so on): ONE `lax.scan` over PERIODS. The parameters stay
    stacked over layers (a checkpoint does not know the period) and are
    viewed `[periods, P, ...]`, which moves nothing; the body applies the
    period's layers in order, each under its own kind's configuration
    (`ModelConfig.window_layers()` / `.full_layers()`: the window and the
    rotation are the kind's), so every layer of a kind shares one traced
    body per place in the period. The cache (`attention.HybridKVCache`: rings
    for the window layers, whole regions for the full ones) is the loop's
    carry and goes down whole with three indices: the layer's place in the
    model (its offset), in its kind's stack (its rows) and in the expert
    banks' stack, all written in place, as the one-kind loop does it."""
    P = cfg.window_layer_period
    n_win = cfg.window_layers_per_period
    stacked_params = grad_accum.pairs(stacked_params)
    num_layers = jax.tree.leaves(stacked_params)[0].shape[0]
    periods = num_layers // P
    kinds = [cfg.window_layers()] * n_win + [cfg.full_layers()]
    drop_rates = lima_dropout_rates(cfg, cfg.num_layers).reshape(periods, P)
    expert_banks = None
    if kv_caches is not None and cfg.num_experts > 1:
        from megatron_tpu.models.moe import split_stacked_banks
        expert_banks, scanned_mlp = split_stacked_banks(
            stacked_params["mlp"], cfg)
        stacked_params = {**stacked_params, "mlp": scanned_mlp}
    by_period = jax.tree.map(
        lambda a: a.reshape(periods, P, *a.shape[1:]), stacked_params)

    def body(carry, scanned):
        h, aux_sum, caches = carry
        params, rates, period = scanned
        for j, kind in enumerate(kinds):
            lid = period * P + j
            layer_rng = None
            if rng is not None and not deterministic:
                layer_rng = jax.random.fold_in(rng, lid)
            cached = caches is not None
            h, caches, aux = layer_apply(
                jax.tree.map(lambda a: a[j], params), h, kind,
                rope_cos=rope_cos, rope_sin=rope_sin,
                position_ids=position_ids, kv_cache=caches,
                cache_layer=lid if cached else None,
                kind_layer=((period * n_win + j if j < n_win else period)
                            if cached else None),
                expert_banks=expert_banks,
                bank_layer=lid if cached else None,
                layer_number=lid + 1, hidden_dropout=rates[j],
                rng=layer_rng, deterministic=deterministic,
                segment_ids=segment_ids)
            aux_sum = aux_sum + aux
        return (h, aux_sum, caches), None

    if cfg.recompute_granularity == "full":
        body = jax.checkpoint(body, prevent_cse=False)
    elif cfg.recompute_granularity == "selective":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            prevent_cse=False)
    (x, aux, kv_caches), _ = grad_accum.scan(
        body, (x, jnp.zeros((), jnp.float32), kv_caches),
        (by_period, drop_rates, jnp.arange(periods)))
    return x, kv_caches, aux


def _pattern_stack_apply(stacked_params, x, cfg: ModelConfig, *, rope_cos,
                         rope_sin, position_ids, kv_caches, rng,
                         deterministic):
    """`stack_apply` for a model whose layers' mixers follow a pattern
    (`cfg.layer_types`: convolutions, state-space mixers and attention, each
    with its feed-forward, or layers of ONE sublayer each). Group by group
    (`_pattern_groups`: the leading dense layers, then the expert layers),
    ONE `lax.scan` over the whole PERIODS of the group's pattern
    (`_pattern_period`); what lies past the last whole period (a published
    tail off the period) runs behind the scan, layer by layer: right, not
    fast. The kinds' parameters are stacked apart, so the scan takes a
    period's worth of each kind, [periods, layers of the kind a period,
    ...] (with a cache the stacks stay outside and a layer's are read at
    its own index), and the body applies the period's layers in order. The
    cache (`attention.ConvKVCache`) is the loop's carry and goes down whole
    with the layer's index among its own kind in the MODEL; the dropless
    experts' banks go down whole beside it, each kind's own, with the
    layer's index among its kind in the GROUP."""
    rates = lima_dropout_rates(cfg, cfg.num_layers)
    cached = kv_caches is not None
    aux = jnp.zeros((), jnp.float32)
    first = 0                       # the group's first layer in the model
    for name, group_cfg, kinds, _ in _pattern_groups(cfg):
        params = grad_accum.pairs(stacked_params[name])
        banks = {kind: None for kind in params}
        if cached and group_cfg.num_experts > 1:
            from megatron_tpu.models.moe import split_stacked_banks
            for kind in list(params):
                if "mlp" not in params[kind]:   # a mixer alone
                    continue
                banks[kind], rest = split_stacked_banks(
                    params[kind]["mlp"], group_cfg)
                params = {**params, kind: {**params[kind], "mlp": rest}}
        # layers of each kind ahead of this group: where its cache rows start
        ahead = {kind: cfg.layer_types[:first].count(kind)
                 for kind in params}

        def apply_one(h, caches, layer_params, kind, lid, at):
            """Layer `lid` of the model, the `at`-th of its kind in the
            group."""
            layer_rng = None
            if rng is not None and not deterministic:
                layer_rng = jax.random.fold_in(rng, lid)
            return layer_apply(
                layer_params, h, group_cfg, mixer=kind, rope_cos=rope_cos,
                rope_sin=rope_sin, position_ids=position_ids,
                kv_cache=caches, cache_layer=lid if cached else None,
                kind_layer=ahead[kind] + at if cached else None,
                expert_banks=banks[kind],
                bank_layer=at if cached else None, layer_number=lid + 1,
                hidden_dropout=rates[lid], rng=layer_rng,
                deterministic=deterministic)

        P, periods = _pattern_period(kinds)
        per = {kind: kinds[:P].count(kind) for kind in params}

        def body(carry, scanned):
            h, aux_sum, caches = carry
            by_kind, period = scanned
            for j, kind in enumerate(kinds[:P]):
                jk = kinds[:j].count(kind)
                at = period * per[kind] + jk
                if cached:
                    # read where the stack lies, at the layer's own index
                    layer_params = jax.tree.map(
                        lambda t: jax.lax.dynamic_index_in_dim(
                            t, at, 0, keepdims=False), params[kind])
                else:
                    layer_params = jax.tree.map(lambda t: t[jk],
                                                by_kind[kind])
                h, caches, a = apply_one(h, caches, layer_params, kind,
                                         first + period * P + j, at)
                aux_sum = aux_sum + a
            return (h, aux_sum, caches), None

        if cfg.recompute_granularity == "full":
            body = jax.checkpoint(body, prevent_cse=False)
        elif cfg.recompute_granularity == "selective":
            body = jax.checkpoint(
                body, prevent_cse=False,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        # With a cache (serving) the stacks stay whole outside the loop and
        # each layer's parameters are read at its own index: a scan over
        # periods cuts a period's parameters out of every stack in every
        # iteration, a copy of them all (2.5 GiB a decode step at 13 bf16
        # layers of Jamba2-3B's widths; compile for v5e, PR 47). Training
        # scans periods: its loop joins each layer's gradient accumulator
        # (`grad_accum.scan`).
        by_period = None if cached else {
            kind: jax.tree.map(
                lambda t: t[:periods * per[kind]].reshape(
                    periods, per[kind], *t.shape[1:]), params[kind])
            for kind in params if per[kind]}
        (x, aux, kv_caches), _ = grad_accum.scan(
            body, (x, aux, kv_caches), (by_period, jnp.arange(periods)))
        for i in range(periods * P, len(kinds)):        # the tail
            kind, at = kinds[i], kinds[:i].count(kinds[i])
            x, kv_caches, a = apply_one(
                x, kv_caches, grad_accum.join(
                    jax.tree.map(lambda t: t[at], params[kind])),
                kind, first + i, at)
            aux = aux + a
        first += len(kinds)
    return x, kv_caches, aux
