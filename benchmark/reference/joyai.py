"""Plain reference of the JoyAI-LLM-Flash decoder: float32 `jax.numpy`, no
kernel, no cache, no batching, no sorting, no capacity. Written from the
equations the published config.json names (jdopensource/JoyAI-LLM-Flash:
DeepSeek-V3's block), the expanded form of the attention only:

    h = E[tokens]
    per layer:
        a   = RMSNorm(h)
        c_q = RMSNorm(a W_dq) ; q_h = c_q W_uq,h = [q_nope_h (128) ; q_rope_h (64)]
        [c_kv ; k_r] = a W_dkv ; c_kv = RMSNorm(c_kv)      (over the 512 alone)
        q_rope_h, k_r = rotary(q_rope_h), rotary(k_r)      (theta 32e6; ONE k_r
                                                 a token, shared by the heads)
        [k_nope_h ; v_h] = c_kv W_ukv,h ; k_h = [k_nope_h ; k_r]
        h   = h + concat_h(softmax(q_h k_h^T / sqrt(192), causal) v_h) W_o
        m   = RMSNorm(h)
        layer < first_k_dense_replace:  h = h + W_down(silu(W_gate m) * W_up m)
        else:
            s = sigmoid(m W_r) in float32 over the 256 experts
            S = the 8 largest of s + b      (b: e_score_correction_bias)
            g = s[S] / (sum s[S] + 1e-20) * 2.5      (b is NOT in the value)
            h = h + sum_{e in S} g_e Expert_e(m) + Shared(m)
    logits = RMSNorm(h) W_head                                   (untied)

and the multi-token-prediction module (depth 1) for the training loss:

    x_i = W_eh [RMSNorm_e(E[t_{i+1}]) ; RMSNorm_h(h_i)]   (h_i before the final
          norm; the embedding's half first), one more expert-kind block,
          its own final RMSNorm, the model's own E and W_head: logits for
          t_{i+2};  L = L_main + lambda L_mtp, both masked means.

Every expert is computed for every token and weighted by `g` where the
expert is among the token's 8 and by 0 elsewhere: a Python loop over layers,
and inside it a `fori_loop` over the experts and a `lax.map` over the heads.
(The two are loops for the compiler's sake and nothing else: unrolled, the
1,024 expert blocks and 160 heads of depth 5 took the chip's compiler 19
minutes, in every run's set-up; PERF.md section 6, PR 31.) An expert's
matrices are cut out of the stacked banks and upcast where they are used, one
expert at a time, so that the reference fits on the chip beside the engine's
bf16 tree.

It reads the program's own parameter tree (`lm.model_init`), so these follow
the program's layout and not the Hugging Face file's, and are noted as
departures: `transformer` is two stacks, `dense` (the first
`first_k_dense_replace` layers) and `moe`; rotary pairs are the adjacent
channels (2i, 2i+1), which `rope_interleave` true means; `wkv_b` [512, 32 x
256] holds a head's k_nope columns then its v columns; a dense MLP's and the
shared expert's `w1` is [h, 2, f] (gate, up); a routed expert's gate and up
are the first and second `f` columns of `w1[e]` [h, 2f]; `lm_head` is [h,
vocab]. `assumed`, not in config.json: lambda (`cfg.mtp_loss_coeff`, 0.3)
and the order inside W_eh.

Every matrix product runs under `jax.default_matmul_precision("highest")`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(F32), tree)


def _rmsnorm(p, x, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * p["scale"].astype(F32)


def _rotary(x, theta):
    """x: [s, heads, d]; position p rotates the pair (2i, 2i+1) by
    p * theta^(-2i/d)."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                     axis=-1).reshape(x.shape)


def attention(p, a, cfg):
    """The expanded form. `p`: one layer's attention parameters, float32;
    a [s, h] -> [s, h]."""
    s = a.shape[0]
    n, r = cfg.num_attention_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    eps = cfg.norm_epsilon
    q = (_rmsnorm(p["q_norm"], a @ p["wq_a"], eps)
         @ p["wq_b"]).reshape(s, n, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rotary(q[..., dn:], cfg.rope_theta)],
                        axis=-1)
    down = a @ p["wkv_a"]
    c_kv = _rmsnorm(p["kv_norm"], down[:, :r], eps)
    k_r = _rotary(down[:, None, r:], cfg.rope_theta)          # [s, 1, dr]
    kv = (c_kv @ p["wkv_b"]).reshape(s, n, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.repeat(k_r, n, axis=1)], axis=-1)
    v = kv[..., dn:]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def head(qkv):              # a head at a time: [s, s] scores, not [n, s, s]
        q_h, k_h, v_h = qkv
        scores = q_h @ k_h.T / math.sqrt(dn + dr)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return probs @ v_h
    out = jax.lax.map(head, (q.swapaxes(0, 1), k.swapaxes(0, 1),
                             v.swapaxes(0, 1)))               # [n, s, dv]
    return out.swapaxes(0, 1).reshape(s, n * dv) @ p["wo"]


def gate_weights(router, bias, m, cfg):
    """[s, experts] float32: g where the expert is among the token's top k
    of s + b (ties to the lower index, as `jax.lax.top_k` breaks them), 0
    elsewhere. `router` [h, E] and `bias` [E] float32."""
    scores = jax.nn.sigmoid(m @ router)
    _, idx = jax.lax.top_k(scores + bias, cfg.moe_top_k)
    g = jnp.take_along_axis(scores, idx, axis=-1)
    g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    g = g * cfg.moe_routed_scaling_factor
    rows = jnp.arange(m.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, idx].set(g)


def _glu(m, gate, up, down):
    return (jax.nn.silu(m @ gate) * (m @ up)) @ down


def _dense_mlp(p, m):
    """`p`: a dense MLP's (or the shared expert's) parameters, w1 [h, 2, f]."""
    w1 = p["w1"].astype(F32)
    return _glu(m, w1[:, 0], w1[:, 1], p["w2"].astype(F32))


def experts(mlp, m, cfg, layer=None):
    """(routed sum + shared expert [s, h], the gate weights [s, experts]) of
    an expert layer: `mlp` its parameters as held, or with `layer` the
    stack's. Each expert's matrices are cut straight out of the (stacked)
    banks and upcast where they are used, so that no copy of a whole layer's
    bank (2.25 GiB at 256 experts of width 768) stands beside the weights."""
    at = (lambda x: x) if layer is None else (lambda x: x[layer])
    w = gate_weights(at(mlp["router"]).astype(F32),
                     at(mlp["e_score_correction_bias"]).astype(F32), m, cfg)
    f = mlp["w2"].shape[-2]
    lead = () if layer is None else (layer,)

    def pick(bank, e):          # expert e's matrix, cut where the bank lies
        cut = jax.lax.dynamic_slice(
            bank, (*lead, e, 0, 0), (1,) * (len(lead) + 1) + bank.shape[-2:])
        return cut.reshape(bank.shape[-2:]).astype(F32)

    def add_expert(e, out):
        w1 = pick(mlp["w1"], e)
        y = _glu(m, w1[:, :f], w1[:, f:], pick(mlp["w2"], e))
        return out + jax.lax.dynamic_slice_in_dim(w, e, 1, axis=1) * y
    out = jax.lax.fori_loop(0, cfg.num_experts, add_expert, jnp.zeros_like(m))
    return out + _dense_mlp(jax.tree.map(at, mlp["shared"]), m), w


def _block(p, h, cfg, dense: bool, layer=None):
    """One layer: `p` its parameters as held, or with `layer` the stack's."""
    eps = cfg.norm_epsilon
    at = (lambda x: x) if layer is None else (lambda x: x[layer])
    rest = jax.tree.map(at, {k: v for k, v in p.items() if k != "mlp"})
    h = h + attention(_f32(rest["attention"]),
                      _rmsnorm(rest["input_norm"], h, eps), cfg)
    m = _rmsnorm(rest["post_attn_norm"], h, eps)
    if dense:
        return h + _dense_mlp(jax.tree.map(at, p["mlp"]), m), None
    y, w = experts(p["mlp"], m, cfg, layer)
    return h + y, w


def _head(params, final_norm, h, cfg, columns: int = 16384):
    """The head's matrix is upcast a block of columns at a time (whole, it is
    1 GB in float32 at 129,280 words)."""
    x = _rmsnorm(final_norm, h, cfg.norm_epsilon)
    head = params["lm_head"]
    return jnp.concatenate(
        [x @ head[:, i:i + columns].astype(F32)
         for i in range(0, cfg.vocab_size, columns)], axis=-1)[:, :cfg.vocab_size]


def _trunk(params, tokens, cfg):
    """(the last layer's output [s, h] before the final norm, the gate
    weights of every expert layer)."""
    assert (cfg.mla and cfg.num_experts > 1 and cfg.activation == "swiglu"
            and cfg.norm_type == "rmsnorm" and cfg.n_shared_experts
            and cfg.moe_scoring_func == "sigmoid"
            and cfg.moe_score_correction_bias and cfg.moe_norm_topk_prob
            and not cfg.use_bias and not cfg.tie_embed_logits), \
        "this reference is the JoyAI-LLM-Flash block only"
    h = params["embedding"]["word_embeddings"][tokens].astype(F32)
    stacks = params["transformer"]
    weights = []
    for i in range(cfg.first_k_dense_replace):
        h, _ = _block(stacks["dense"], h, cfg, dense=True, layer=i)
    for i in range(cfg.num_layers - cfg.first_k_dense_replace):
        h, w = _block(stacks["moe"], h, cfg, dense=False, layer=i)
        weights.append(w)
    return h, weights


def logits(params, tokens, cfg):
    """tokens [s] int -> logits [s, vocab] float32."""
    with jax.default_matmul_precision("highest"):
        h, _ = _trunk(params, tokens, cfg)
        return _head(params, params["final_norm"], h, cfg)


def token_logprobs(params, tokens, cfg, with_choices: bool = False,
                   tail: int | None = None):
    """log p(tokens[i+1] | tokens[:i+1]) for every i: [s-1] float32, or with
    `tail` for the last `tail` of them alone (the head over 2,500 positions
    of a 129,280-word vocabulary is 1.3 GB, twice with its softmax, beside
    an engine that fills the chip). With `with_choices` also [expert layers,
    s-1, experts] bool: which experts each of the s-1 input tokens chose, by
    this reference's own router."""
    tail = tokens.shape[0] - 1 if tail is None else tail
    with jax.default_matmul_precision("highest"):
        h, weights = _trunk(params, tokens[:-1], cfg)
        out = _head(params, params["final_norm"], h[-tail:], cfg)
    lp = jnp.take_along_axis(jax.nn.log_softmax(out, axis=-1),
                             tokens[-tail:, None], axis=-1)[:, 0]
    if with_choices:
        return lp, jnp.stack([w > 0 for w in weights])
    return lp


def _masked_mean(x, mask):
    return jnp.sum(x * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def loss(params, tokens, loss_mask, cfg):
    """L_main + lambda L_mtp of a stack of sequences, tokens [n, s+1],
    loss_mask [n, s], one sequence at a time, each term a masked mean over
    the whole stack. The MTP term: position i (0 <= i < s-1) holds the
    trunk's state h_i and the embedding of t_{i+1}, and is scored on
    t_{i+2}, where the mask keeps that target."""
    mtp = params["mtp"]
    mask = loss_mask.astype(F32)
    main, extra = [], []
    with jax.default_matmul_precision("highest"):
        for t in tokens:
            h, _ = _trunk(params, t[:-1], cfg)
            out = _head(params, params["final_norm"], h, cfg)
            main.append(-jnp.take_along_axis(
                jax.nn.log_softmax(out, axis=-1), t[1:, None], axis=-1)[:, 0])
            e = params["embedding"]["word_embeddings"][t[1:-1]].astype(F32)
            x = jnp.concatenate(
                [_rmsnorm(mtp["enorm"], e, cfg.norm_epsilon),
                 _rmsnorm(mtp["hnorm"], h[:-1], cfg.norm_epsilon)],
                axis=-1) @ mtp["eh_proj"].astype(F32)
            x, _ = _block(mtp["layer"], x, cfg, dense=False)
            out2 = _head(params, mtp["final_norm"], x, cfg)
            extra.append(-jnp.take_along_axis(
                jax.nn.log_softmax(out2, axis=-1), t[2:, None], axis=-1)[:, 0])
    return (_masked_mean(jnp.stack(main), mask)
            + cfg.mtp_loss_coeff * _masked_mean(jnp.stack(extra), mask[:, 1:]))


def loss_and_grads(params, tokens, loss_mask, cfg):
    """(loss, its gradient in the parameters' own tree)."""
    return jax.value_and_grad(loss)(params, tokens, loss_mask, cfg)
