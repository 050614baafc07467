"""Plain reference of the Qwen3-Next-80B-A3B-Instruct decoder (`qwen3_next`):
float32 `jax.numpy`, no kernel, no cache, no state carried between calls, no
batching, the delta rule TOKEN BY TOKEN and not its chunked form. Written
from the published config.json's keys, the public `modeling_qwen3_next.py`
beside it and Gated Delta Networks (arXiv:2412.06464; ISSUE 60 writes the
layers out), with H_k = 16 key heads under H = 32 value heads of D = 128
channels, K = 4 taps, n = 16 heads over n_kv = 2 of d = 256, the first 64
channels of a head rotated:

    x = E[tokens];  N(x) = x / sqrt(mean(x^2) + 1e-6) * (1 + w)   ZERO-CENTRED
    layer i:  a = N_i(x)
      a linear-attention layer ((i + 1) % 4 != 0), Gated DeltaNet:
        [q~, k~, v~, z] = a W_in                  W_in [h, 2 H_k D + 2 H D]
        [b, a_] = a W_ba                          W_ba [h, 2 H]
        [q^, k^, v^]_t = silu(sum_j w_j [q~, k~, v~]_{t-K+1+j})   ONE
              depthwise causal kernel over the 8,192 channels, the sequence
              left-padded with K - 1 zeros; no bias
        q = q^ / |q^|_head / sqrt(D);  k = k^ / |k^|_head;  v = v^
        value head j reads key head j // (H / H_k)'s q and k
        beta = sigmoid(b);  g = -exp(A_log) softplus(a_ + dt_bias)   [H] <= 0
        S' = exp(g_t) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
        o_t = S_t^T q_t                          S [D, D] a value head, S_0 = 0
        m = [n_head(o; w [D], the scale w ITSELF) * silu(z)] W_out
      an attention layer ((i + 1) % 4 == 0), gated:
        [q_h, gate_h] = a W_q,h  (a head's 256 of q, then its 256 of gate)
        k = a W_k;  v = a W_v;   q_h = N(q_h), k_g = N(k_g) over d, 1 + w
        the first 64 channels of q_h and k_g rotated (theta 1e7), the other
        192 as they are
        m = concat_h(softmax(q_h k_g^T / sqrt(256), causal) v_g
                     * sigmoid(gate_h)) W_o
      x = x + m;  f = N'_i(x)
      s = softmax(f W_r) over the 512;  chosen = top-10 of s
      w_e = s_e / sum_chosen s
      x = x + sum_{e chosen, HELD here} w_e Expert_e(f)
            + sigmoid(f . w_sg) Shared(f)
    logits = N_f(x) W_head                                          (untied)

The share is the program's own: the experts held are `cfg.moe_first_expert`
.. + `cfg.num_experts` of the router's `cfg.router_experts`, what the others
would add is left out, and the vocabulary is the slice the configuration
gives. The recurrence is a `lax.scan` over single tokens with the state [H,
D, D] float32 its carry; attention is a `lax.map` over the heads ([s, s]
scores, never [heads, s, s]); the experts are a loop over the held ones,
each over every row with its gate weight (0 where not chosen), each cut out
of the banks where it is used; the head is computed for the positions asked
for alone, so that 9,033 positions fit on the chip beside the engine's bf16
tree. `checked` is what the benchmark compiles, once, at one length: tokens
padded behind `live` move no state (the model is causal), and each linear
layer's last states and depthwise inputs and each attention layer's last
keys are handed back beside the log-probabilities, for the comparison with
what the engine's pool holds.

Departures from the public modelling code, each the program's layout and a
converter's to permute: it reads the program's own parameter tree
(`lm.model_init`): `transformer.layers` is {"linear_attention",
"full_attention"}, the kind's layers stacked in the model's order; the four
projections of a linear layer are ONE matrix `in_proj` with q | k | v | z
side by side (the public `in_proj_qkvz` groups them a key head), `ba_proj` b
| a; the rotary pairs ADJACENT channels (2i, 2i + 1) of the first 64 (the
public one pairs i with i + 32); `wkv` holds the kv heads' k columns then
their v columns; the shared expert's `w1` is [h, 2, f] (gate, up), a routed
expert's gate and up the first and second f columns of `w1[e]`; the
multi-token-prediction module the model card names has no key in the config
and is not built.

Every matrix product runs under `jax.default_matmul_precision("highest")`.

`faults` (`benchmark/tests/gdn_fault_at_width.py` and the unit tests alone;
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
KEY_ROWS = 8            # of each attention layer's last keys, handed back
EDGE = 4096             # the cell's chunk: where `state_reset` and
#                         `conv_reset` start anew

FAULTS = {
    "state_bf16": "the carried state rounded to bfloat16 behind every token: "
                  "the nearest precision below the configuration's float32",
    "state_reset": "the state starts from zeros at every 4,096th row: a "
                   "chunk that does not carry the state of the one before",
    "conv_reset": "the depthwise kernel sees zeros before every 4,096th "
                  "row: a chunk that starts from stale (empty) inputs",
    "decay_after": "the decay applied after the update and not before it",
    "decay_mean": "every head decays by the mean of the heads' log-decays "
                  "a row and not by its own",
    "norm_w": "every zero-centred norm scales by w where the model has 1 + w",
    "no_gate": "the attention's output gate left out",
    "rope_all": "all 256 channels of a head rotated where the model rotates "
                "the first 64",
    "key_head": "value head j reads key head j % H_k where the model has "
                "j // (H / H_k)",
    "no_shared_gate": "the shared expert's own gate left out",
}


def _norm(p, x, eps, faults=frozenset()):
    """The zero-centred RMSNorm: x / rms(x) * (1 + w)."""
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    w = p["scale"].astype(F32)
    return x / jnp.sqrt(var + eps) * (w if "norm_w" in faults else 1.0 + w)


def _l2norm(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                        + L2_EPS)


def gated_delta(p, a, cfg, faults=frozenset(), keep=None):
    """`p`: one layer's `gdn` parameters as held; a [s, h] -> ([s, h], two
    states [2, H, D, D]: behind the last row that `keep` [s] bool marks (the
    rows behind it are padding and move no state; every row where None) and
    one row ahead of that; the depthwise kernel's last K - 1 inputs behind
    the same two rows [2, K - 1, channels])."""
    s = a.shape[0]
    keep = jnp.ones((s,), bool) if keep is None else keep
    hk, hv = cfg.gdn_key_heads, cfg.gdn_value_heads
    d_k, d_v, taps = (cfg.gdn_key_head_dim, cfg.gdn_value_head_dim,
                      cfg.gdn_conv_kernel)
    dk, dv, ratio = hk * d_k, hv * d_v, hv // hk
    qkvz = a @ p["in_proj"].astype(F32)
    qkv, z = qkvz[:, :2 * dk + dv], qkvz[:, 2 * dk + dv:]
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
    q = _l2norm(mixed[:, :dk].reshape(s, hk, d_k)) / math.sqrt(d_k)
    k = _l2norm(mixed[:, dk:2 * dk].reshape(s, hk, d_k))
    v = mixed[:, 2 * dk:].reshape(s, hv, d_v)
    if "key_head" in faults:
        q, k = jnp.tile(q, (1, ratio, 1)), jnp.tile(k, (1, ratio, 1))
    else:
        q, k = jnp.repeat(q, ratio, axis=1), jnp.repeat(k, ratio, axis=1)
    ba = a @ p["ba_proj"].astype(F32)
    beta = jax.nn.sigmoid(ba[:, :hv])                         # [s, H]
    g = -jnp.exp(p["A_log"].astype(F32)) * jax.nn.softplus(
        ba[:, hv:] + p["dt_bias"].astype(F32))                # [s, H]
    if "decay_mean" in faults:
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    carried = BF16 if "state_bf16" in faults else F32
    fresh = jnp.arange(s) % EDGE == 0 if "state_reset" in faults \
        else jnp.zeros((s,), bool)

    def read(state, vec):
        """state^T vec a head: [H, D(k), D(v)], [H, D(k)] -> [H, D(v)]."""
        return jnp.sum(state * vec[..., None], axis=-2)

    def token(carry, row):
        before, ahead = carry
        q_t, k_t, v_t, g_t, b_t, fresh_t, keep_t, edge_t = row
        state = jnp.where(fresh_t, 0.0, before.astype(F32))
        decay = jnp.exp(g_t)[:, None, None]
        if "decay_after" in faults:
            u = b_t[:, None] * (v_t - read(state, k_t))
            state = decay * (state + k_t[..., None] * u[:, None, :])
        else:
            state = decay * state
            u = b_t[:, None] * (v_t - read(state, k_t))
            state = state + k_t[..., None] * u[:, None, :]
        o_t = read(state, q_t)
        state = state.astype(carried)
        return (jnp.where(keep_t, state, before),
                jnp.where(keep_t | edge_t, state, ahead)), o_t
    # the first padding row: its step is made from the last kept state, so
    # what it leaves is the state one row AHEAD of the kept ones
    edge = keep != jnp.pad(keep, (1, 0), constant_values=True)[:-1]
    zeros = jnp.zeros((hv, d_k, d_v), carried)
    last, o = jax.lax.scan(token, (zeros, zeros),
                           (q, k, v, g, beta, fresh, keep, edge))
    var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
    o = o / jnp.sqrt(var + cfg.norm_epsilon) * p["norm"]["scale"].astype(F32)
    out = (o.reshape(s, dv) * jax.nn.silu(z)) @ p["out_proj"].astype(F32)
    # the K - 1 inputs up to the last kept row, and up to the row behind it
    n = jnp.sum(keep)
    inputs = jnp.stack([
        jax.lax.dynamic_slice_in_dim(padded, n + ahead, taps - 1, axis=0)
        for ahead in (0, 1)])
    return out, jnp.stack(last).astype(F32), inputs


def _rotary(x, theta, turned: int):
    """x [s, heads, d]: ADJACENT pairs of the first `turned` channels turned
    by the position, the others as they are."""
    s = x.shape[0]
    inv = 1.0 / (theta ** (jnp.arange(0, turned, 2, dtype=F32) / turned))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x0, x1 = x[..., 0:turned:2], x[..., 1:turned:2]
    front = jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                      axis=-1).reshape(*x.shape[:-1], turned)
    return jnp.concatenate([front, x[..., turned:]], axis=-1)


def attention(p, a, cfg, faults=frozenset()):
    """`p`: one layer's attention parameters as held; a [s, h] -> ([s, h],
    the keys [s, n_kv d] the cache would keep: normed and rotated)."""
    s = a.shape[0]
    n, nkv, d = cfg.num_attention_heads, cfg.num_kv_heads, cfg.kv_channels
    qg = (a @ p["wq"].astype(F32)).reshape(s, n, 2 * d)
    q, gate = qg[..., :d], qg[..., d:].reshape(s, n * d)
    kv = (a @ p["wkv"].astype(F32)).reshape(s, 2, nkv, d)
    k, v = kv[:, 0], kv[:, 1]
    q = _norm(p["q_norm"], q, cfg.norm_epsilon, faults)
    k = _norm(p["k_norm"], k, cfg.norm_epsilon, faults)
    turned = d if "rope_all" in faults else cfg.rotary_dim
    q, k = _rotary(q, cfg.rope_theta, turned), _rotary(k, cfg.rope_theta,
                                                       turned)
    causal = jnp.tril(jnp.ones((s, s), bool))
    group = n // nkv
    out = jax.lax.map(
        lambda at: _one_head(q, k, v, at, group, d, causal), jnp.arange(n))
    out = out.swapaxes(0, 1).reshape(s, n * d)                # [s, n d]
    if "no_gate" not in faults:
        out = out * jax.nn.sigmoid(gate)
    return out @ p["wo"].astype(F32), k.reshape(s, nkv * d)


def _one_head(q, k, v, at, group, d, causal):
    """Head `at` (traced) of q [s, n, d] over its kv head's k and v: [s, d],
    the scores [s, s] and never [heads, s, s]."""
    q_h = jax.lax.dynamic_index_in_dim(q, at, 1, keepdims=False)
    k_h = jax.lax.dynamic_index_in_dim(k, at // group, 1, keepdims=False)
    v_h = jax.lax.dynamic_index_in_dim(v, at // group, 1, keepdims=False)
    scores = q_h @ k_h.T / math.sqrt(d)
    return jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1) @ v_h


def gate_weights(router, f, cfg):
    """[s, router_experts] float32: the gate where the expert is among the
    token's top k of softmax(f W_r) (ties to the lower index, as
    `jax.lax.top_k` breaks them), 0 elsewhere. `router` [h, E] float32."""
    scores = jax.nn.softmax(f @ router, axis=-1)
    g, idx = jax.lax.top_k(scores, cfg.moe_top_k)
    g = g / jnp.maximum(jnp.sum(g, axis=-1, keepdims=True), 1e-9)
    rows = jnp.arange(f.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, idx].set(g)


def _glu(f, gate, up, down):
    return (jax.nn.silu(f @ gate) * (f @ up)) @ down


def experts(stack, f, cfg, at: int, faults=frozenset()):
    """(the routed sum over the experts HELD [s, h]; the gated shared
    expert's part [s, h]; the gate weights [s, router_experts]) of layer
    `at` of the stacked `mlp` parameters of one kind. Each expert's matrices
    are cut straight out of the stacked banks and upcast where they are
    used."""
    layer = lambda t: t[at].astype(F32)                       # noqa: E731
    w = gate_weights(layer(stack["router"]), f, cfg)
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
    w1 = layer(stack["shared"]["w1"])
    shared = _glu(f, w1[:, 0], w1[:, 1], layer(stack["shared"]["w2"]))
    if "no_shared_gate" not in faults:
        shared = shared * jax.nn.sigmoid(f @ layer(stack["shared_gate"]))
    return routed, shared, w


def block(stack, x, cfg, kind: str, at: int, faults=frozenset(), keep=None):
    """Layer `at` of the stacked parameters of one kind: (x', the gate
    weights, the linear layer's (states, inputs) or None, the attention
    layer's keys or None)."""
    eps = cfg.norm_epsilon
    rest = jax.tree.map(lambda t: t[at],
                        {k: v for k, v in stack.items() if k != "mlp"})
    a = _norm(rest["input_norm"], x, eps, faults)
    state = rows = None
    if kind == "linear_attention":
        m, states, inputs = gated_delta(rest["gdn"], a, cfg, faults, keep)
        state = (states, inputs)
    else:
        m, rows = attention(rest["attention"], a, cfg, faults)
    x = x + m
    f = _norm(rest["post_attn_norm"], x, eps, faults)
    routed, shared, w = experts(stack["mlp"], f, cfg, at, faults)
    return x + routed + shared, w, state, rows


def _trunk(params, tokens, cfg, faults=frozenset(), keep=None):
    """(the last layer's output [s, h] before the final norm; the gate
    weights of every layer; every linear layer's (states, inputs); every
    attention layer's keys)."""
    assert (not cfg.mla and cfg.layers_of("linear_attention")
            and set(cfg.layer_types) <= {"linear_attention",
                                         "full_attention"}
            and cfg.use_rotary_emb and cfg.attn_output_gate
            and cfg.qk_head_norm and cfg.norm_type == "rmsnorm_1p"
            and not cfg.first_k_dense_replace and cfg.num_experts > 1
            and cfg.n_shared_experts == 1 and cfg.moe_shared_expert_gate
            and cfg.activation == "swiglu"
            and cfg.moe_scoring_func == "softmax"
            and not cfg.moe_score_correction_bias and cfg.moe_norm_topk_prob
            and cfg.moe_routed_scaling_factor == 1.0 and not cfg.use_bias
            and not cfg.tie_embed_logits), \
        "this reference is the Qwen3-Next (qwen3_next) block only"
    x = params["embedding"]["word_embeddings"][tokens].astype(F32)
    types = cfg.layer_types
    weights, states, keys = [], [], []
    for l, kind in enumerate(types):
        x, w, state, rows = block(
            params["transformer"]["layers"][kind], x, cfg, kind,
            types[:l].count(kind), faults, keep)
        weights.append(w)
        if state is not None:
            states.append(state)
        if rows is not None:
            keys.append(rows)
    return x, weights, states, keys


def _head(params, x, cfg, columns: int = 16384, faults=frozenset()):
    """The head's matrix is upcast a block of columns at a time."""
    x = _norm(params["final_norm"], x, cfg.norm_epsilon, faults)
    head = params["lm_head"]
    return jnp.concatenate(
        [x @ head[:, i:i + columns].astype(F32)
         for i in range(0, cfg.vocab_size, columns)],
        axis=-1)[:, :cfg.vocab_size]


def logits(params, tokens, cfg, faults=frozenset()):
    """tokens [s] int -> logits [s, vocab] float32."""
    with jax.default_matmul_precision("highest"):
        return _head(params, _trunk(params, tokens, cfg, faults)[0], cfg,
                     faults=faults)


def checked(params, tokens, live, cfg, tail: int, faults=frozenset()):
    """What a check reads of `tokens[:live]`, whose last `tail` the engine
    chose: `logprobs` [tail], log p(tokens[i+1] | tokens[:i+1]) of those;
    `chosen` [layers, s-1, router_experts] bool, the experts each row chose;
    `states` [2, linear layers, H, D, D], each layer's state behind
    tokens[:live-1], which is what the last log-probability was read behind,
    and behind tokens[:live] (what an engine that has fed its last token to
    a step ahead holds); `inputs` [2, linear layers, K - 1, channels], the
    depthwise kernel's last inputs behind the same two; `keys` [attention
    layers, KEY_ROWS, n_kv d], the keys of positions live - 1 - KEY_ROWS ..
    live - 2, which either engine has written. `tokens` [s] may be padded
    behind `live`, a traced number: the model is causal and the padding rows
    move no state (`keep`), so one program serves every length up to s."""
    n = live - 1                                # the rows the trunk reads
    rows = jnp.arange(tokens.shape[0] - 1)
    with jax.default_matmul_precision("highest"):
        x, weights, states, keys = _trunk(
            params, tokens[:-1], cfg, faults, keep=rows < n)
        out = _head(params, jax.lax.dynamic_slice_in_dim(x, n - tail, tail),
                    cfg, faults=faults)
    chose = jax.lax.dynamic_slice_in_dim(tokens, live - tail, tail)
    logp = jnp.take_along_axis(jax.nn.log_softmax(out, axis=-1),
                               chose[:, None], axis=-1)[:, 0]
    return {"logprobs": logp,
            "chosen": jnp.stack([w > 0 for w in weights]),
            "states": jnp.stack([s for s, _ in states], axis=1),
            "inputs": jnp.stack([i for _, i in states], axis=1),
            # (a sequence shorter than that hands back what rows it has)
            "keys": jnp.stack([
                jax.lax.dynamic_slice_in_dim(
                    r, n - KEY_ROWS, min(KEY_ROWS, r.shape[0]))
                for r in keys])}


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
