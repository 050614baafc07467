"""Plain reference of the Kimi-Linear-48B-A3B-Instruct decoder
(`kimi_linear`): float32 `jax.numpy`, no kernel, no cache, no state carried
between calls, no batching, the delta rule TOKEN BY TOKEN and not its chunked
form, the attention expanded with full heads. Written from the equations of
Kimi Linear (arXiv:2510.26692) and the keys of the published config.json
(moonshotai/Kimi-Linear-48B-A3B-Instruct; ISSUE 58 writes them out), with H
= 32 heads of D = 128 key and value channels, K = 4 taps:

    x = E[tokens];  n(x) = x / sqrt(mean(x^2) + 1e-5) * w
    layer l:  a = n_l(x)
      a KDA layer (`linear_attn_config.kda_layers`):
        [q~, k~, v~] = a W_in                       W_in [h, 3 H D], no bias
        [q^, k^, v^]_t = silu(sum_j w_j [q~, k~, v~]_{t-K+1+j})   depthwise,
              causal: the sequence left-padded with K - 1 zeros; no bias
        q = q^ / |q^|_head / sqrt(D);  k = k^ / |k^|_head;  v = v^
        g = -exp(A_log[h]) softplus((a W_fa) W_fb + dt_bias)   [H, D] <= 0
        beta = sigmoid(a W_b)                                  [H]
        S' = Diag(exp(g_t)) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
        o_t = S_t^T q_t                              S [D, D] a head, S_0 = 0
        m = [n_head(o; w[D]) * sigmoid((a W_ga) W_gb + b_g)] W_o
      an MLA layer (`full_attn_layers`), NoPE:
        q_h = a W_q,h = [.. 128 ; .. 64] (ONE matrix, no norm, NOTHING rotated)
        [c_kv ; k_r] = a W_dkv;  c_kv = n(c_kv) over the 512 alone
        [k_nope_h ; v_h] = c_kv W_ukv,h;  k_h = [k_nope_h ; k_r]
        m = concat_h(softmax(q_h k_h^T / sqrt(192), causal) v_h) W_o
      x = x + m;  f = n'_l(x)
      layer 1:  x = x + W_down(silu(W_gate f) * W_up f)           (width 9216)
      else:     s = sigmoid(f W_r) over the 256;  chosen = top-8 of s + b
                w_e = 2.446 s_e / sum_chosen s
                x = x + sum_{e chosen, HELD here} w_e Expert_e(f) + Shared(f)
    logits = n_f(x) W_head                                          (untied)

The share is the program's own: the experts held are `cfg.moe_first_expert`
.. + `cfg.num_experts` of the router's `cfg.router_experts`, what the others
would add is left out, and the vocabulary is the slice the configuration
gives. The recurrence is a `lax.scan` over single tokens with the state [H,
D, D] float32 its carry; attention is a `lax.map` over the heads ([s, s]
scores, never [heads, s, s]); the experts are a loop over the held ones,
each over every row with its gate weight (0 where not chosen); the head is
computed for the positions asked for alone, so that 9,033 positions fit on
the chip beside the engine's bf16 tree. `checked` is what the benchmark
compiles, once, at one length: tokens padded behind `live` move no state
(the model is causal), and each KDA layer's last states and depthwise
inputs and each MLA layer's last latent rows are handed back beside the
log-probabilities, for the comparison with what the engine's pool holds.

It reads the program's own parameter tree (`lm.model_init`), so these follow
the program's layout and not the Hugging Face file's, and are noted as
departures: `transformer` is two groups, `dense` (layer 1) and `moe`, each
{"kda", "full_attention"} with the kind's layers stacked in the model's
order; q, k, v's projections are ONE matrix `in_proj` [h, 3 H D] (q's
columns, then k's, then v's) and the three depthwise kernels ONE array
`conv` [K, 3 H D]; W_fa, W_ga and W_b are ONE matrix `low_proj` [h, 128 +
128 + 32] in that order, W_fb `f_b`, W_gb `g_b` with its bias `g_bias`;
`wkv_b` [512, 32 x 256] holds a head's k_nope columns then its v columns; a
dense MLP's and the shared expert's `w1` is [h, 2, f] (gate, up); a routed
expert's gate and up are the first and second f columns of `w1[e]`.

Every matrix product runs under `jax.default_matmul_precision("highest")`.

`faults` (`benchmark/tests/kda_fault_at_width.py` and the unit tests alone;
empty everywhere else) plants a fault in one piece of the mathematics: see
`FAULTS`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
BF16 = jnp.bfloat16
L2_EPS = 1e-6
LATENT_ROWS = 8         # of each MLA layer's last rows, handed back
EDGE = 4096             # the cell's chunk: where `state_reset` and
#                         `conv_reset` start anew

FAULTS = {
    "state_bf16": "the carried state rounded to bfloat16 behind every token: "
                  "the nearest precision below the configuration's float32",
    "decay": "the decay left out: g = 0, whatever A_log and the gate say",
    "sums_bf16": "the rule's two products with the state (S'^T k and S^T q) "
                 "summed in bfloat16: every product and every partial sum of "
                 "a pairwise tree rounded",
    "decay_after": "the decay applied after the update and not before it",
    "state_reset": "the state starts from zeros at every 4,096th row: a "
                   "chunk that does not carry the state of the one before",
    "conv_reset": "the depthwise kernels see zeros before every 4,096th "
                  "row: a chunk that starts from stale (empty) inputs",
    "rope": "the 64 rope channels of q and of the shared key rotated (theta "
            "10,000) where the model rotates nothing",
    "scale": "routed_scaling_factor 1 for 2.446",
}


def _rmsnorm(p, x, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * p["scale"].astype(F32)


def _l2norm(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                        + L2_EPS)


def _bf16(x):
    """Float32 rounded to bfloat16's eight bits of mantissa (fault
    `sums_bf16` alone)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def kda(p, a, cfg, faults=frozenset(), keep=None):
    """`p`: one layer's `kda` parameters as held; a [s, h] -> ([s, h], two
    states [2, H, D, D]: behind the last row that `keep` [s] bool marks (the
    rows behind it are padding and move no state; every row where None) and
    one row ahead of that; the depthwise kernels' last K - 1 inputs behind
    the same two rows [2, K - 1, 3 H D])."""
    s = a.shape[0]
    keep = jnp.ones((s,), bool) if keep is None else keep
    heads, hd, taps = cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_conv_kernel
    rank, di = cfg.kda_gate_rank, cfg.kda_num_heads * cfg.kda_head_dim
    qkv = a @ p["in_proj"].astype(F32)                        # [s, 3 d]
    padded = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
    w = p["conv"].astype(F32)
    if "conv_reset" in faults:
        # row t's tap j reads row t - (taps - 1) + j: zero where that row
        # lies before the start of t's chunk
        t = jnp.arange(s)[:, None]
        mixed = sum(w[j] * jnp.where(
            (t - (taps - 1) + j) >= (t // EDGE) * EDGE,
            padded[j:j + s], 0.0) for j in range(taps))
    else:
        mixed = sum(w[j] * padded[j:j + s] for j in range(taps))
    mixed = jax.nn.silu(mixed)
    by_head = lambda t: t.reshape(s, heads, hd)               # noqa: E731
    q = _l2norm(by_head(mixed[:, :di])) / math.sqrt(hd)
    k = _l2norm(by_head(mixed[:, di:2 * di]))
    v = by_head(mixed[:, 2 * di:])
    low = a @ p["low_proj"].astype(F32)
    f_a, z_a, b_l = low[:, :rank], low[:, rank:2 * rank], low[:, 2 * rank:]
    g = -jnp.exp(p["A_log"].astype(F32))[:, None] * by_head(jax.nn.softplus(
        f_a @ p["f_b"].astype(F32) + p["dt_bias"].astype(F32)))
    if "decay" in faults:
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(b_l)                                # [s, H]
    carried = BF16 if "state_bf16" in faults else F32
    fresh = jnp.arange(s) % EDGE == 0 if "state_reset" in faults \
        else jnp.zeros((s,), bool)

    def read(state, vec):
        """state^T vec a head: [H, D(k), D(v)], [H, D(k)] -> [H, D(v)]."""
        if "sums_bf16" in faults:
            # every product and every partial sum of a pairwise tree rounded:
            # a `convert` there and back is the compiler's to take out, and
            # a sum asked for in bfloat16 is float32 inside the unit
            terms = _bf16(_bf16(state) * _bf16(vec)[..., None])
            while terms.shape[-2] > 1:
                terms = _bf16(terms[..., 0::2, :] + terms[..., 1::2, :])
            return terms[..., 0, :]
        return jnp.sum(state * vec[..., None], axis=-2)

    def token(carry, row):
        before, ahead = carry
        q_t, k_t, v_t, g_t, b_t, fresh_t, keep_t, edge_t = row
        state = jnp.where(fresh_t, 0.0, before.astype(F32))
        if "decay_after" in faults:
            u = b_t[:, None] * (v_t - read(state, k_t))
            state = jnp.exp(g_t)[..., None] * (
                state + k_t[..., None] * u[:, None, :])
        else:
            state = jnp.exp(g_t)[..., None] * state
            u = b_t[:, None] * (v_t - read(state, k_t))
            state = state + k_t[..., None] * u[:, None, :]
        o_t = read(state, q_t)
        state = state.astype(carried)
        return (jnp.where(keep_t, state, before),
                jnp.where(keep_t | edge_t, state, ahead)), o_t
    # the first padding row: its step is made from the last kept state, so
    # what it leaves is the state one row AHEAD of the kept ones
    edge = keep != jnp.pad(keep, (1, 0), constant_values=True)[:-1]
    zeros = jnp.zeros((heads, hd, hd), carried)
    last, o = jax.lax.scan(token, (zeros, zeros),
                           (q, k, v, g, beta, fresh, keep, edge))
    var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
    o = o / jnp.sqrt(var + cfg.norm_epsilon) * p["norm"]["scale"].astype(F32)
    gate = jax.nn.sigmoid(z_a @ p["g_b"].astype(F32)
                          + p["g_bias"].astype(F32))
    out = (o.reshape(s, di) * gate) @ p["out_proj"].astype(F32)
    # the K - 1 inputs up to the last kept row, and up to the row behind it
    n = jnp.sum(keep)
    inputs = jnp.stack([
        jax.lax.dynamic_slice_in_dim(padded, n + ahead, taps - 1, axis=0)
        for ahead in (0, 1)])
    return out, jnp.stack(last).astype(F32), inputs


def _rotary(x, theta):
    """The fault `rope` alone: x [s, heads, d], adjacent pairs."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                     axis=-1).reshape(x.shape)


def attention(p, a, cfg, faults=frozenset()):
    """The expanded form, NoPE. `p`: one layer's attention parameters as
    held; a [s, h] -> ([s, h], the latent rows [s, kv_lora + rope] the cache
    would keep)."""
    s = a.shape[0]
    n, r = cfg.num_attention_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q = (a @ p["wq"].astype(F32)).reshape(s, n, dn + dr)
    down = a @ p["wkv_a"].astype(F32)
    c_kv = _rmsnorm(p["kv_norm"], down[:, :r], cfg.norm_epsilon)
    k_r = down[:, None, r:]                                   # [s, 1, dr]
    if "rope" in faults:
        q = jnp.concatenate([q[..., :dn], _rotary(q[..., dn:], 1e4)], axis=-1)
        k_r = _rotary(k_r, 1e4)
    kv = (c_kv @ p["wkv_b"].astype(F32)).reshape(s, n, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.repeat(k_r, n, axis=1)], axis=-1)
    v = kv[..., dn:]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def head(qkv):
        q_h, k_h, v_h = qkv
        scores = q_h @ k_h.T / math.sqrt(dn + dr)
        return jax.nn.softmax(jnp.where(causal, scores, -jnp.inf),
                              axis=-1) @ v_h
    out = jax.lax.map(head, (q.swapaxes(0, 1), k.swapaxes(0, 1),
                             v.swapaxes(0, 1)))               # [n, s, dv]
    return (out.swapaxes(0, 1).reshape(s, n * dv) @ p["wo"].astype(F32),
            jnp.concatenate([c_kv, k_r[:, 0]], axis=-1))


def gate_weights(router, bias, f, cfg, faults=frozenset()):
    """[s, router_experts] float32: the gate where the expert is among the
    token's top k of s + b (ties to the lower index, as `jax.lax.top_k`
    breaks them), 0 elsewhere. `router` [h, E] and `bias` [E] float32."""
    scores = jax.nn.sigmoid(f @ router)
    _, idx = jax.lax.top_k(scores + bias, cfg.moe_top_k)
    g = jnp.take_along_axis(scores, idx, axis=-1)
    g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    g = g * (1.0 if "scale" in faults else cfg.moe_routed_scaling_factor)
    rows = jnp.arange(f.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, idx].set(g)


def _glu(f, gate, up, down):
    return (jax.nn.silu(f @ gate) * (f @ up)) @ down


def _dense_mlp(p, f):
    """`p`: a dense MLP's (or the shared expert's) parameters, w1 [h, 2, f]."""
    w1 = p["w1"].astype(F32)
    return _glu(f, w1[:, 0], w1[:, 1], p["w2"].astype(F32))


def experts(stack, f, cfg, at: int, faults=frozenset()):
    """(the routed sum over the experts HELD [s, h]; the shared expert's
    part [s, h]; the gate weights [s, router_experts]) of layer `at` of the
    stacked `mlp` parameters of one kind. Each expert's matrices are cut
    straight out of the stacked banks and upcast where they are used."""
    layer = lambda t: t[at].astype(F32)                       # noqa: E731
    w = gate_weights(layer(stack["router"]),
                     layer(stack["e_score_correction_bias"]), f, cfg, faults)
    width = stack["w2"].shape[-2]

    def pick(bank, e):          # expert e's matrix, cut where the bank lies
        cut = jax.lax.dynamic_slice(bank, (at, e, 0, 0),
                                    (1, 1) + bank.shape[-2:])
        return cut.reshape(bank.shape[-2:]).astype(F32)

    def add_expert(e, out):
        w1 = pick(stack["w1"], e)
        y = _glu(f, w1[:, :width], w1[:, width:], pick(stack["w2"], e))
        g = jax.lax.dynamic_slice_in_dim(w, cfg.moe_first_expert + e, 1,
                                         axis=1)
        return out + g * y
    routed = jax.lax.fori_loop(0, cfg.num_experts, add_expert,
                               jnp.zeros_like(f))
    shared = _dense_mlp(jax.tree.map(lambda t: t[at], stack["shared"]), f)
    return routed, shared, w


def block(stack, x, cfg, kind: str, at: int, dense: bool,
          faults=frozenset(), keep=None):
    """Layer `at` of the stacked parameters of one kind of one group: (x',
    the gate weights or None, the KDA layer's (states, inputs) or None, the
    MLA layer's latent rows or None)."""
    eps = cfg.norm_epsilon
    rest = jax.tree.map(lambda t: t[at],
                        {k: v for k, v in stack.items() if k != "mlp"})
    a = _rmsnorm(rest["input_norm"], x, eps)
    state = rows = None
    if kind == "kda":
        m, states, inputs = kda(rest["kda"], a, cfg, faults, keep)
        state = (states, inputs)
    else:
        m, rows = attention(rest["attention"], a, cfg, faults)
    x = x + m
    f = _rmsnorm(rest["post_attn_norm"], x, eps)
    if dense:
        return (x + _dense_mlp(jax.tree.map(lambda t: t[at], stack["mlp"]),
                               f), None, state, rows)
    routed, shared, w = experts(stack["mlp"], f, cfg, at, faults)
    return x + routed + shared, w, state, rows


def _trunk(params, tokens, cfg, faults=frozenset(), keep=None):
    """(the last layer's output [s, h] before the final norm; the gate
    weights of every expert layer; every KDA layer's (states, inputs);
    every MLA layer's latent rows)."""
    assert (cfg.mla and cfg.mla_nope and cfg.q_lora_rank is None
            and cfg.layers_of("kda") and not cfg.use_rotary_emb
            and set(cfg.layer_types) <= {"kda", "full_attention"}
            and cfg.first_k_dense_replace >= 1
            and cfg.n_shared_experts == 1 and cfg.activation == "swiglu"
            and cfg.moe_scoring_func == "sigmoid"
            and cfg.moe_score_correction_bias and cfg.moe_norm_topk_prob
            and cfg.norm_type == "rmsnorm" and not cfg.use_bias
            and not cfg.tie_embed_logits), \
        "this reference is the Kimi Linear (kimi_linear) block only"
    x = params["embedding"]["word_embeddings"][tokens].astype(F32)
    types, lead = cfg.layer_types, cfg.first_k_dense_replace
    weights, states, latents = [], [], []
    for l, kind in enumerate(types):
        dense = l < lead
        group = types[:lead] if dense else types[lead:]
        at = group[:l if dense else l - lead].count(kind)
        x, w, state, rows = block(
            params["transformer"]["dense" if dense else "moe"][kind], x, cfg,
            kind, at, dense, faults, keep)
        if w is not None:
            weights.append(w)
        if state is not None:
            states.append(state)
        if rows is not None:
            latents.append(rows)
    return x, weights, states, latents


def _head(params, x, cfg, columns: int = 16384):
    """The head's matrix is upcast a block of columns at a time."""
    x = _rmsnorm(params["final_norm"], x, cfg.norm_epsilon)
    head = params["lm_head"]
    return jnp.concatenate(
        [x @ head[:, i:i + columns].astype(F32)
         for i in range(0, cfg.vocab_size, columns)],
        axis=-1)[:, :cfg.vocab_size]


def logits(params, tokens, cfg, faults=frozenset()):
    """tokens [s] int -> logits [s, vocab] float32."""
    with jax.default_matmul_precision("highest"):
        return _head(params, _trunk(params, tokens, cfg, faults)[0], cfg)


def checked(params, tokens, live, cfg, tail: int, faults=frozenset()):
    """What a check reads of `tokens[:live]`, whose last `tail` the engine
    chose: `logprobs` [tail], log p(tokens[i+1] | tokens[:i+1]) of those;
    `chosen` [expert layers, s-1, router_experts] bool, the experts each row
    chose; `states` [2, KDA layers, H, D, D], each layer's state behind
    tokens[:live-1], which is what the last log-probability was read
    behind, and behind tokens[:live] (what an engine that has fed its last
    token to a step ahead holds); `inputs` [2, KDA layers, K - 1, 3 H D],
    the depthwise kernels' last inputs behind the same two; `latent` [MLA
    layers, LATENT_ROWS, kv_lora + rope], the rows of positions live - 1 -
    LATENT_ROWS .. live - 2, which either engine has written. `tokens` [s]
    may be padded behind `live`, a traced number: the model is causal and
    the padding rows move no state (`keep`), so one program serves every
    length up to s."""
    n = live - 1                                # the rows the trunk reads
    rows = jnp.arange(tokens.shape[0] - 1)
    with jax.default_matmul_precision("highest"):
        x, weights, states, latents = _trunk(
            params, tokens[:-1], cfg, faults, keep=rows < n)
        out = _head(params, jax.lax.dynamic_slice_in_dim(x, n - tail, tail),
                    cfg)
    chose = jax.lax.dynamic_slice_in_dim(tokens, live - tail, tail)
    logp = jnp.take_along_axis(jax.nn.log_softmax(out, axis=-1),
                               chose[:, None], axis=-1)[:, 0]
    return {"logprobs": logp,
            "chosen": jnp.stack([w > 0 for w in weights]),
            "states": jnp.stack([s for s, _ in states], axis=1),
            "inputs": jnp.stack([i for _, i in states], axis=1),
            # (a sequence shorter than that hands back what rows it has)
            "latent": jnp.stack([
                jax.lax.dynamic_slice_in_dim(
                    r, n - LATENT_ROWS, min(LATENT_ROWS, r.shape[0]))
                for r in latents])}


def token_logprobs(params, tokens, cfg, tail: int | None = None,
                   faults=frozenset()):
    """log p(tokens[i+1] | tokens[:i+1]) for every i: [s-1] float32, or with
    `tail` for the last `tail` of them alone."""
    tail = tokens.shape[0] - 1 if tail is None else tail
    return checked(params, tokens, tokens.shape[0], cfg, tail,
                   faults)["logprobs"]


def loss(params, tokens, loss_mask, cfg):
    """The masked mean of -log p(t_{i+1} | t_{<=i}) over a stack of
    sequences, tokens [n, s+1], loss_mask [n, s], one sequence at a time."""
    mask = loss_mask.astype(F32)
    with jax.default_matmul_precision("highest"):
        terms = []
        for t in tokens:
            out = _head(params, _trunk(params, t[:-1], cfg)[0], cfg)
            terms.append(-jnp.take_along_axis(
                jax.nn.log_softmax(out, axis=-1), t[1:, None], axis=-1)[:, 0])
    return jnp.sum(jnp.stack(terms) * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def loss_and_grads(params, tokens, loss_mask, cfg):
    """(loss, its gradient in the parameters' own tree)."""
    return jax.value_and_grad(loss)(params, tokens, loss_mask, cfg)
