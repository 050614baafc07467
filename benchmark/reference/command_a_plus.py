"""Plain reference of the command-a-plus-05-2026 decoder (`cohere2_moe`):
float32 `jax.numpy`, no kernel, no cache, no ring, no batching, no sorting,
no capacity. Written from the equations ISSUE 33 derives from the published
config.json (CohereLabs/command-a-plus-05-2026). With x the residual stream
and layer l (0-based) FULL where (l + 1) % 4 == 0, else SLIDING:

    u   = LN(x) = (x - mean(x)) / sqrt(var(x) + 1e-5) * g      scale, no bias
    q   = u Wq (128 heads of 128)    k = u Wk, v = u Wv (8 heads of 128)
    sliding:  q, k rotated (theta 50000, adjacent pairs, all 128 channels);
              position i reads j with 0 <= i - j < 4096
    full:     q, k NOT rotated;  i reads every j <= i
    attn = softmax(q k^T / sqrt(128) + mask) v Wo   (kv head g serves q heads
                                                      16g .. 16g + 15)
    s   = sigmoid(u Wr) over ALL 128 experts; I = the 8 largest;
    g_e = s_e / sum_{j in I} s_j
    routed = sum_{e in I, e held here} g_e E_e(u),
             E_e(u) = (silu(u Wg_e) * (u Wu_e)) Wd_e
    shared = 1/4 sum_{s=1..4} S_s(u)                 (the same form and width)
    x'  = x + attn + routed + shared                          (parallel block)
    logits = LN_f(x_L) E^T                   (tied head; `logit_scale` is 1)

**The share.** `cfg.num_experts` experts from `cfg.moe_first_expert` on are
held (the banks have that many); the router is `cfg.router_experts` wide and
the gates are normalised over all 8 chosen, held or not. A choice of an expert
that is not held adds nothing: that is another chip's part of the layer. With
`num_experts == router_experts` this is the uncut model.

The window is a band mask over the whole sequence. Attention runs a head and
a block of `Q_BLOCK` queries at a time (`lax.map` over both) and the experts
in a `fori_loop`, each expert's matrices cut out of the stacked banks and
upcast where they are used: loops for the compiler's sake (PERF.md section 6,
PR 31: unrolled, JoyAI's reference took 19 minutes to compile) and so that
the reference fits beside an engine that fills the chip. Every held expert is
computed for every token and weighted by g or by 0.

It reads the program's own parameter tree (`lm.model_init`), so these follow
the program's layout and not the Hugging Face file's, and are noted as
departures: `transformer` is one stack over layers; `wkv` [h, 2 x 8 x 128]
holds k's columns, then v's; rotary pairs are the adjacent channels (2i,
2i + 1) (`rope_gptj`); a routed expert's gate and up are the first and second
`f` columns of `w1[e]` [h, 2f]; the four shared experts lie side by side in
one `w1` [h, 2, 4f] (gate, up) and one `w2` [4f, h], expert s in columns and
rows s f .. (s + 1) f; the norm's parameter is `scale`. `assumed`, not in
config.json: benchmark/configs/command-a-plus-4l.json lists them (one
expert's width, what "average" means, no rotation on full layers, the window
counting the position itself).

Every matrix product runs under `jax.default_matmul_precision("highest")`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 1024


def _layernorm(p, x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"].astype(F32)


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


def is_full(cfg, layer: int) -> bool:
    return (layer + 1) % cfg.window_layer_period == 0


def attention(p, u, cfg, full: bool):
    """`p`: one layer's attention parameters as held; u [s, h] -> [s, h]. A
    head at a time, its columns of wq and rows of wo cut out and upcast
    where they are used: no array of every head's queries or outputs
    (0.66 GB each at 10,000 positions of 128 heads) is ever made."""
    s = u.shape[0]
    nq, nkv, hd = cfg.num_attention_heads, cfg.num_kv_heads, cfg.kv_channels
    kv = (u @ p["wkv"].astype(F32)).reshape(s, 2, nkv, hd)
    k, v = kv[:, 0], kv[:, 1]
    if not full:
        k = _rotary(k, cfg.rope_theta)
    pad = -s % Q_BLOCK
    blocks = (s + pad) // Q_BLOCK
    kv_pos = jnp.arange(s)[None, :]

    def add_head(n, out):
        k_h = jax.lax.dynamic_index_in_dim(k, n // (nq // nkv), 1, False)
        v_h = jax.lax.dynamic_index_in_dim(v, n // (nq // nkv), 1, False)
        q_h = u @ jax.lax.dynamic_slice_in_dim(
            p["wq"], n * hd, hd, axis=1).astype(F32)          # [s, hd]
        if not full:
            q_h = _rotary(q_h[:, None], cfg.rope_theta)[:, 0]
        q_h = jnp.pad(q_h, ((0, pad), (0, 0)))

        def block(i):           # [Q_BLOCK, s] scores, never [s, s]
            q_b = jax.lax.dynamic_slice_in_dim(q_h, i * Q_BLOCK, Q_BLOCK)
            q_pos = (i * Q_BLOCK + jnp.arange(Q_BLOCK))[:, None]
            mask = kv_pos <= q_pos
            if not full:
                mask = mask & (q_pos - kv_pos < cfg.sliding_window)
            scores = q_b @ k_h.T / math.sqrt(hd)
            return jax.nn.softmax(jnp.where(mask, scores, -jnp.inf),
                                  axis=-1) @ v_h
        o_h = jax.lax.map(block, jnp.arange(blocks)).reshape(-1, hd)[:s]
        return out + o_h @ jax.lax.dynamic_slice_in_dim(
            p["wo"], n * hd, hd, axis=0).astype(F32)
    return jax.lax.fori_loop(0, nq, add_head, jnp.zeros_like(u))


def gate_weights(router, u, cfg):
    """[s, router_experts] float32: g where the expert is among the token's
    top k (ties to the lower index, as `jax.lax.top_k` breaks them), 0
    elsewhere; normalised over the chosen, whoever holds them."""
    scores = jax.nn.sigmoid(u @ router)
    g, idx = jax.lax.top_k(scores, cfg.moe_top_k)
    g = g / jnp.maximum(jnp.sum(g, axis=-1, keepdims=True), 1e-9)
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, idx].set(g)


def _glu(u, gate, up, down):
    return (jax.nn.silu(u @ gate) * (u @ up)) @ down


def shared_experts(p, u, cfg):
    """1/4 sum_s S_s(u): `p` one layer's `shared`, w1 [h, 2, 4f], w2 [4f, h]."""
    n = cfg.n_shared_experts
    f = p["w2"].shape[0] // n
    out = jnp.zeros_like(u)
    for s in range(n):
        w1 = p["w1"][:, :, s * f:(s + 1) * f].astype(F32)
        out = out + _glu(u, w1[:, 0], w1[:, 1],
                         p["w2"][s * f:(s + 1) * f].astype(F32))
    return out / n if cfg.moe_shared_combination == "average" else out


def experts(mlp, u, cfg, layer):
    """(the routed sum over the experts HELD [s, h], the shared experts'
    part [s, h], the gate weights [s, router_experts]) of layer `layer` of
    the stack's `mlp`."""
    w = gate_weights(mlp["router"][layer].astype(F32), u, cfg)
    f = mlp["w2"].shape[-2]

    def pick(bank, e):          # expert e's matrix, cut where the bank lies
        cut = jax.lax.dynamic_slice(
            bank, (layer, e, 0, 0), (1, 1) + bank.shape[-2:])
        return cut.reshape(bank.shape[-2:]).astype(F32)

    def add_expert(e, out):
        w1 = pick(mlp["w1"], e)
        y = _glu(u, w1[:, :f], w1[:, f:], pick(mlp["w2"], e))
        g = jax.lax.dynamic_slice_in_dim(w, cfg.moe_first_expert + e, 1,
                                         axis=1)
        return out + g * y
    routed = jax.lax.fori_loop(0, cfg.num_experts, add_expert,
                               jnp.zeros_like(u))
    shared = shared_experts(jax.tree.map(lambda x: x[layer], mlp["shared"]),
                            u, cfg)
    return routed, shared, w


def block(stack, x, cfg, layer: int):
    """One layer of the stacked parameters: (x', the gate weights)."""
    at = lambda t: jax.tree.map(lambda a: a[layer], t)
    u = _layernorm(at(stack["input_norm"]), x, cfg.norm_epsilon)
    attn = attention(at(stack["attention"]), u, cfg, is_full(cfg, layer))
    routed, shared, w = experts(stack["mlp"], u, cfg, layer)
    return x + attn + routed + shared, w


def _trunk(params, tokens, cfg):
    assert (cfg.window_layer_period and cfg.parallel_attn and cfg.norm_type == "layernorm_nobias"
            and cfg.moe_scoring_func == "sigmoid" and cfg.moe_norm_topk_prob
            and cfg.n_shared_experts and cfg.activation == "swiglu"
            and cfg.tie_embed_logits and not cfg.use_bias
            and not cfg.qk_norm), \
        "this reference is the command-a-plus block only"
    x = params["embedding"]["word_embeddings"][tokens].astype(F32)
    weights = []
    for i in range(cfg.num_layers):
        x, w = block(params["transformer"], x, cfg, i)
        weights.append(w)
    return x, weights


def _head(params, x, cfg, columns: int = 16384):
    """The tied head's matrix is upcast a block of rows of the embedding at
    a time."""
    x = _layernorm(params["final_norm"], x, cfg.norm_epsilon)
    emb = params["embedding"]["word_embeddings"]
    return jnp.concatenate(
        [x @ emb[i:i + columns].astype(F32).T
         for i in range(0, cfg.vocab_size, columns)], axis=-1)


def logits(params, tokens, cfg):
    """tokens [s] int -> logits [s, vocab] float32."""
    with jax.default_matmul_precision("highest"):
        x, _ = _trunk(params, tokens, cfg)
        return _head(params, x, cfg)


def token_logprobs(params, tokens, cfg, with_choices: bool = False,
                   tail: int | None = None):
    """log p(tokens[i+1] | tokens[:i+1]) for every i: [s-1] float32, or with
    `tail` for the last `tail` of them alone. With `with_choices` also
    [layers, s-1, router_experts] bool: which experts each of the s-1 input
    tokens chose, by this reference's own router (held here or not)."""
    tail = tokens.shape[0] - 1 if tail is None else tail
    with jax.default_matmul_precision("highest"):
        x, weights = _trunk(params, tokens[:-1], cfg)
        out = _head(params, x[-tail:], cfg)
    lp = jnp.take_along_axis(jax.nn.log_softmax(out, axis=-1),
                             tokens[-tail:, None], axis=-1)[:, 0]
    if with_choices:
        return lp, jnp.stack([w > 0 for w in weights])
    return lp
