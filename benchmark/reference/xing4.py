"""Plain reference of the Xing4.0-29B-A4B decoder: float32 `jax.numpy`, no
kernel, no cache, no batching, no sorting, no capacity. Written from the
equations the published config.json names (XingChen-AGI/Xing4.0-29B-A4B,
`xing4_0`: DeepSeek-V3's block, YaRN positions, and a residual of `hc_mult`
streams mixed by manifold-constrained hyper-connections, arXiv:2512.24880 on
arXiv:2409.19606); it imports nothing of `megatron_tpu/models`.

    X = E[tokens] in each of n = hc_mult streams                   [s, n, C]
    per layer, round each of its two sublayers F (own phi, alpha, b):
        x^     = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)            [s, n C]
        H~     = alpha_k (x^ phi_k) + b_k     k = pre [n], post [n], res [n, n]
        H_pre  = sigmoid(H~_pre) ;  H_post = 2 sigmoid(H~_post)
        M      = exp(clip(H~_res, -30, +30))
        20 times: each column of M / (its sum + hc_eps), then each row
        X      = M X + H_post^T F(H_pre X)
      F_1(u) = MLA(RMSNorm(u)):
        c_q = RMSNorm(a W_dq) ; q_h = c_q W_uq,h = [q_nope_h (128) ; q_rope_h (64)]
        [c_kv ; k_r] = a W_dkv ; c_kv = RMSNorm(c_kv)     (over the 512 alone)
        q_rope_h, k_r = rotary(q_rope_h), rotary(k_r)     (YaRN's frequencies;
                                      ONE k_r a token, shared by the heads)
        [k_nope_h ; v_h] = c_kv W_ukv,h ; k_h = [k_nope_h ; k_r]
        concat_h(softmax(q_h k_h^T m^2 / sqrt(192), causal) v_h) W_o
      F_2(u), m = RMSNorm(u):
        layer < first_k_dense_replace:  W_down(silu(W_gate m) * W_up m)
        else:  s = sigmoid(m W_r) in float32 over the 64 experts
               S = the 4 largest of s + b_e      (e_score_correction_bias)
               g = s[S] / (sum s[S] + 1e-20) * 2   (b_e is NOT in the value)
               sum_{e in S} g_e Expert_e(m) + Shared(m)
    logits = RMSNorm(sum of the n streams) W_head                    (untied)

YaRN, for the rotary width d = 64, base 10,000, factor f = 64 over L0 = 4,096
positions, pair i of d / 2:

    dim(beta) = d ln(L0 / (2 pi beta)) / (2 ln base)
    low, high = floor(dim(beta_fast)), ceil(dim(beta_slow)), inside [0, d - 1]
    ramp_i    = clip((i - low) / (high - low), 0, 1)
    w_i       = (1 - ramp_i) base^(-2i/d) + ramp_i base^(-2i/d) / f
    m(f, a)   = 0.1 a ln f + 1
    cos, sin  = cos, sin(position w_i) m(f, mscale) / m(f, mscale_all_dim)
    the softmax scale carries m(f, mscale_all_dim)^2

and the multi-token-prediction module (depth 1) for the training loss:

    x_i = W_eh [RMSNorm_e(E[t_{i+1}]) ; RMSNorm_h(h_i)]  (h_i the streams' sum,
          before the final norm; the embedding's half first), put in each of
          n streams, one more expert-kind layer with its own hyper-connections,
          the streams summed, its own final RMSNorm, the model's own E and
          W_head: logits for t_{i+2};  L = L_main + lambda L_mtp, masked means.

Every expert is computed for every token and weighted by `g` where the expert
is among the token's 4 and by 0 elsewhere: a Python loop over layers, inside
it a `fori_loop` over the experts and a `lax.map` over the heads, and what is
computed a token at a time (the maps, the mixes, the feed-forward) runs
`ROW_BLOCK` rows at a time, so that the reference of a 6,032-token check fits
on the chip beside the engine's bf16 tree and its pool. An expert's matrices
are cut out of the stacked banks and upcast where they are used.

It reads the program's own parameter tree (`lm.model_init`), so these follow
the program's layout and not the Hugging Face file's, and are noted as
departures: `transformer` is two stacks, `dense` (the first
`first_k_dense_replace` layers) and `moe`; rotary pairs are the adjacent
channels (2i, 2i+1), which `rope_interleave` true means; `wkv_b` [512, 32 x
256] holds a head's k_nope columns then its v columns; a dense MLP's and the
shared expert's `w1` is [h, 2, f] (gate, up); a routed expert's gate and up
are the first and second `f` columns of `w1[e]` [h, 2f]; `lm_head` is [h,
vocab]; a sublayer's maps are `hc_attn` / `hc_mlp` = {phi [n C, n^2 + 2 n]
(columns: pre, post, then res row by row), alpha [3] (pre, post, res), b
[n^2 + 2 n]}, and vec(X) runs stream by stream. `assumed`, not in
config.json: see benchmark/configs/xing4.0-29b-a4b-6l.json.

`faults` (a set of names) plants what `benchmark/tests/hc_fault_at_width.py`
and `tests/test_xing.py` show the comparison catches: "sinkhorn_1" /
"sinkhorn_10" (that many rounds for twenty), "post_without_2" (H_post =
sigmoid), "no_mscale" (the softmax scale without m^2), "maps_bf16" (x^, the
maps' product, the sigmoids and the Sinkhorn rounds in bfloat16),
"sinkhorn_bf16" (the rounds alone).

Every matrix product runs under `jax.default_matmul_precision("highest")`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
BF16 = jnp.bfloat16
ROW_BLOCK = 1024
NO_FAULTS = frozenset()


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(F32), tree)


def _rmsnorm(p, x, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * p["scale"].astype(F32)


def _by_rows(f, *xs):
    """f over arrays of s rows each, `ROW_BLOCK` rows at a time (the last
    block padded with rows of zeros, which are cut off again): f must treat
    every row for itself."""
    s = xs[0].shape[0]
    if s <= ROW_BLOCK:
        return f(*xs)
    blocks = -(-s // ROW_BLOCK)

    def cut(x):
        x = jnp.pad(x, ((0, blocks * ROW_BLOCK - s),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape(blocks, ROW_BLOCK, *x.shape[1:])
    out = jax.lax.map(lambda blk: f(*blk), tuple(cut(x) for x in xs))
    return jax.tree.map(lambda y: y.reshape(-1, *y.shape[2:])[:s], out)


# ---------------------------------------------------------------------------
# YaRN
# ---------------------------------------------------------------------------

def _mscale(factor, a):
    return 1.0 if factor <= 1.0 else 0.1 * a * math.log(factor) + 1.0


def yarn_inv_freq(cfg):
    """[d / 2] float32: the blended frequencies."""
    d, base = cfg.qk_rope_head_dim, cfg.rope_theta
    factor, l0 = cfg.rope_scaling_factor, cfg.rope_original_max_position

    def dim(beta):
        return d * math.log(l0 / (2 * math.pi * beta)) / (2 * math.log(base))
    low = max(math.floor(dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(dim(cfg.rope_beta_slow)), d - 1)
    if low == high:
        high += 0.001
    i = jnp.arange(d // 2, dtype=F32)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    plain = base ** (-2.0 * i / d)
    return (1.0 - ramp) * plain + ramp * plain / factor


def rope_tables(cfg, s):
    """(cos, sin) [s, d / 2] with YaRN's factor on both."""
    ang = jnp.arange(s, dtype=F32)[:, None] * yarn_inv_freq(cfg)[None, :]
    m = _mscale(cfg.rope_scaling_factor, cfg.rope_mscale) / _mscale(
        cfg.rope_scaling_factor, cfg.rope_mscale_all_dim)
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def softmax_scale(cfg, faults=NO_FAULTS):
    scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    if cfg.rope_mscale_all_dim and "no_mscale" not in faults:
        scale *= _mscale(cfg.rope_scaling_factor, cfg.rope_mscale_all_dim) ** 2
    return scale


def _rotary(x, cos, sin):
    """x [s, heads, d]: the pair (2i, 2i+1) of position p turned by p w_i."""
    cos, sin = cos[:, None, :], sin[:, None, :]
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                     axis=-1).reshape(x.shape)


def attention(p, a, cfg, faults=NO_FAULTS):
    """The expanded form. `p`: one layer's attention parameters, float32;
    a [s, h] -> [s, h]."""
    s = a.shape[0]
    n, r = cfg.num_attention_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    eps = cfg.norm_epsilon
    cos, sin = rope_tables(cfg, s)
    q = (_rmsnorm(p["q_norm"], a @ p["wq_a"], eps)
         @ p["wq_b"]).reshape(s, n, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rotary(q[..., dn:], cos, sin)], axis=-1)
    down = a @ p["wkv_a"]
    c_kv = _rmsnorm(p["kv_norm"], down[:, :r], eps)
    k_r = _rotary(down[:, None, r:], cos, sin)                # [s, 1, dr]
    kv = (c_kv @ p["wkv_b"]).reshape(s, n, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.repeat(k_r, n, axis=1)], axis=-1)
    v = kv[..., dn:]
    causal = jnp.tril(jnp.ones((s, s), bool))
    scale = softmax_scale(cfg, faults)

    def head(qkv):              # a head at a time: [s, s] scores, not [n, s, s]
        q_h, k_h, v_h = qkv
        scores = q_h @ k_h.T * scale
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return probs @ v_h
    out = jax.lax.map(head, (q.swapaxes(0, 1), k.swapaxes(0, 1),
                             v.swapaxes(0, 1)))               # [n, s, dv]
    return out.swapaxes(0, 1).reshape(s, n * dv) @ p["wo"]


# ---------------------------------------------------------------------------
# hyper-connections
# ---------------------------------------------------------------------------

def sinkhorn(m, iters, eps):
    """m [.., n, n] positive: `iters` rounds, each every column divided by
    its sum + eps, then every row."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
    return m


def hc_maps(p, x, cfg, faults=NO_FAULTS):
    """One sublayer's maps of the residual x [s, n, C]: (H_pre [s, n],
    H_post [s, n], H_res [s, n, n])."""
    s, n, _ = x.shape
    low = BF16 if "maps_bf16" in faults else F32
    flat = x.reshape(s, -1)
    x_hat = (flat / jnp.sqrt(jnp.mean(jnp.square(flat), axis=-1,
                                      keepdims=True) + cfg.hc_eps)).astype(low)
    raw = (x_hat @ p["phi"].astype(low)).astype(low)
    alpha, b = p["alpha"].astype(low), p["b"].astype(low)
    pre = jax.nn.sigmoid(alpha[0] * raw[:, :n] + b[:n])
    post = jax.nn.sigmoid(alpha[1] * raw[:, n:2 * n] + b[n:2 * n])
    if "post_without_2" not in faults:
        post = 2.0 * post
    res = (alpha[2] * raw[:, 2 * n:] + b[2 * n:]).reshape(s, n, n)
    m = jnp.exp(jnp.clip(res, -cfg.hc_res_clamp, cfg.hc_res_clamp))
    if "sinkhorn_bf16" in faults:
        m = m.astype(BF16)
    iters = (1 if "sinkhorn_1" in faults else 10 if "sinkhorn_10" in faults
             else cfg.hc_sinkhorn_iters)
    return (pre.astype(F32), post.astype(F32),
            sinkhorn(m, iters, cfg.hc_eps).astype(F32))


def hyper_connected(p, x, cfg, sublayer, faults=NO_FAULTS):
    """X' = H_res X + H_post^T F(H_pre X): x [s, n, C]; `sublayer` takes the
    whole [s, C] (attention reads every row) and returns (out, extra).
    Returns (X', extra, the maps (H_pre, H_post, H_res))."""
    def read(x):
        pre, post, res = hc_maps(p, x, cfg, faults)
        return jnp.einsum("sn,snc->sc", pre, x), pre, post, res
    inp, pre, post, res = _by_rows(read, x)
    out, extra = sublayer(inp)

    def write(x, out, post, res):
        return (jnp.einsum("sij,sjc->sic", res, x)
                + post[:, :, None] * out[:, None, :])
    return _by_rows(write, x, out, post, res), extra, (pre, post, res)


# ---------------------------------------------------------------------------
# the feed-forwards
# ---------------------------------------------------------------------------

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
    banks and upcast where they are used."""
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


def _block(p, x, cfg, dense: bool, layer=None, faults=NO_FAULTS):
    """One layer over the residual x [s, n, C]: `p` its parameters as held,
    or with `layer` the stack's. Returns (x, the experts' gate weights, the
    two sublayers' maps)."""
    eps = cfg.norm_epsilon
    at = (lambda t: t) if layer is None else (lambda t: t[layer])
    rest = jax.tree.map(at, {k: v for k, v in p.items() if k != "mlp"})

    def attend(u):
        return attention(_f32(rest["attention"]),
                         _rmsnorm(rest["input_norm"], u, eps), cfg,
                         faults), None

    def feed_forward(u):
        def rows(u):
            m = _rmsnorm(rest["post_attn_norm"], u, eps)
            if dense:
                return _dense_mlp(jax.tree.map(at, p["mlp"]), m), \
                    jnp.zeros((u.shape[0], 1), F32)      # no router
            return experts(p["mlp"], m, cfg, layer)
        return _by_rows(rows, u)
    x, _, maps_a = hyper_connected(_f32(rest["hc_attn"]), x, cfg, attend,
                                   faults)
    x, w, maps_f = hyper_connected(_f32(rest["hc_mlp"]), x, cfg, feed_forward,
                                   faults)
    return x, (None if dense else w), (maps_a, maps_f)


def _expand(h, cfg):
    return jnp.repeat(h[:, None, :], cfg.hc_mult, axis=1)


def _head(params, final_norm, h, cfg, columns: int = 16384):
    """The head's matrix is upcast a block of columns at a time (whole, it is
    1.9 GB in float32 at 131,072 words)."""
    x = _rmsnorm(final_norm, h, cfg.norm_epsilon)
    head = params["lm_head"]
    return jnp.concatenate(
        [x @ head[:, i:i + columns].astype(F32)
         for i in range(0, cfg.vocab_size, columns)], axis=-1)[:, :cfg.vocab_size]


def _trunk(params, tokens, cfg, faults=NO_FAULTS):
    """(the streams' sum behind the last layer [s, h], before the final
    norm; the gate weights of every expert layer; every layer's maps, the
    attention sublayer's and the feed-forward's, each (H_pre [s, n], H_post
    [s, n], H_res [s, n, n]))."""
    assert (cfg.mla and cfg.num_experts > 1 and cfg.activation == "swiglu"
            and cfg.norm_type == "rmsnorm" and cfg.n_shared_experts
            and cfg.moe_scoring_func == "sigmoid"
            and cfg.moe_score_correction_bias and cfg.moe_norm_topk_prob
            and not cfg.use_bias and not cfg.tie_embed_logits
            and cfg.hc_mult > 1 and cfg.rope_scaling_type == "yarn"), \
        "this reference is the Xing4.0 block only"
    x = _expand(params["embedding"]["word_embeddings"][tokens].astype(F32),
                cfg)
    stacks = params["transformer"]
    weights, maps = [], []
    for i in range(cfg.first_k_dense_replace):
        x, _, m = _block(stacks["dense"], x, cfg, True, i, faults)
        maps.append(m)
    for i in range(cfg.num_layers - cfg.first_k_dense_replace):
        x, w, m = _block(stacks["moe"], x, cfg, False, i, faults)
        weights.append(w)
        maps.append(m)
    return jnp.sum(x, axis=1), weights, maps


def logits(params, tokens, cfg):
    """tokens [s] int -> logits [s, vocab] float32."""
    with jax.default_matmul_precision("highest"):
        h, _, _ = _trunk(params, tokens, cfg)
        return _head(params, params["final_norm"], h, cfg)


def token_logprobs(params, tokens, cfg, with_choices: bool = False,
                   tail: int | None = None, faults=NO_FAULTS,
                   with_maps: bool = False):
    """log p(tokens[i+1] | tokens[:i+1]) for every i: [s-1] float32, or with
    `tail` for the last `tail` of them alone (the head over 6,031 positions
    of a 131,072-word vocabulary is 3.2 GB, beside an engine that fills the
    chip). With `with_choices` also [expert layers, s-1, experts] bool: which
    experts each of the s-1 input tokens chose, by this reference's own
    router."""
    tail = tokens.shape[0] - 1 if tail is None else tail
    with jax.default_matmul_precision("highest"):
        h, weights, maps = _trunk(params, tokens[:-1], cfg, faults)
        out = _head(params, params["final_norm"], h[-tail:], cfg)
    lp = jnp.take_along_axis(jax.nn.log_softmax(out, axis=-1),
                             tokens[-tail:, None], axis=-1)[:, 0]
    out = (lp,)
    if with_choices:
        out += (jnp.stack([w > 0 for w in weights]),)
    if with_maps:
        out += (maps,)
    return out if len(out) > 1 else lp


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
            h, _, _ = _trunk(params, t[:-1], cfg)
            out = _head(params, params["final_norm"], h, cfg)
            main.append(-jnp.take_along_axis(
                jax.nn.log_softmax(out, axis=-1), t[1:, None], axis=-1)[:, 0])
            e = params["embedding"]["word_embeddings"][t[1:-1]].astype(F32)
            x = jnp.concatenate(
                [_rmsnorm(mtp["enorm"], e, cfg.norm_epsilon),
                 _rmsnorm(mtp["hnorm"], h[:-1], cfg.norm_epsilon)],
                axis=-1) @ mtp["eh_proj"].astype(F32)
            x = _block(mtp["layer"], _expand(x, cfg), cfg, dense=False)[0]
            out2 = _head(params, mtp["final_norm"], jnp.sum(x, axis=1), cfg)
            extra.append(-jnp.take_along_axis(
                jax.nn.log_softmax(out2, axis=-1), t[2:, None], axis=-1)[:, 0])
    return (_masked_mean(jnp.stack(main), mask)
            + cfg.mtp_loss_coeff * _masked_mean(jnp.stack(extra), mask[:, 1:]))


def loss_and_grads(params, tokens, loss_mask, cfg):
    """(loss, its gradient in the parameters' own tree)."""
    return jax.value_and_grad(loss)(params, tokens, loss_mask, cfg)
