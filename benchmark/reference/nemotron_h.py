"""Plain reference of the NVIDIA-Nemotron-3-Super-120B-A12B decoder
(`nemotron_h`): float32 `jax.numpy`, no kernel, no cache, no state carried
between calls, no batching, the SEQUENTIAL recurrence and not its chunked
form. Written from the equations the published config.json names
(nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16; ISSUE 52 writes them out),
with H = mamba_num_heads, P = mamba_head_dim, G = n_groups, N =
ssm_state_size, K = conv_kernel:

    x = E[tokens];  n(x) = x / sqrt(mean(x^2) + 1e-5) * g
    layer l, by the letter hybrid_override_pattern[l]:  u = n_l(x)
      M (a Mamba-2 mixer):
        [z, xBC, dt] = u W_in              W_in [h, HP + (HP + 2GN) + H]
        xBC_t = silu(b + sum_j w_j xBC_{t-K+1+j})   w [K, HP + 2GN] depthwise,
              causal: the sequence left-padded with K - 1 zeros; bias b
        [a, B, C] = xBC                    a [s, H, P]; B, C [s, G, N]
        dt = softplus(dt + dt_bias);  A = -exp(A_log) [H];  S_0 = 0
        S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] a_t[h] (x) B_t[h // (H/G)]
        y_t[h] = S_t[h] C_t[h // (H/G)] + D[h] a_t[h]
        y = y * silu(z), then RMSNorm over each of the G groups of HP / G
            channels (eps 1e-5), times the scale w [HP]
        m = y W_out
      * (attention): q = u Wq (32 heads of 128), k, v = u Wkv (2 heads of 128
        each), no bias, NO positional term of any kind
        m = softmax(q k^T / sqrt(128) + causal) v Wo
      E (experts in a latent):
        s = sigmoid(u W_r) [512];  chosen = top-22 of s + b
        w_e = 5 s_e / sum_chosen s
        l = u W_down [1024]
        r = sum_{e chosen, HELD here} w_e relu(l W1_e)^2 W2_e
        m = r W_up + relu(u S1)^2 S2
      x = x + m
    logits = n_f(x) W_head                                        (untied)

The share is the program's own: the experts held are `cfg.moe_first_expert`
.. + `cfg.num_experts` of the router's `cfg.router_experts`, what the others
would add is left out, and the vocabulary is the slice the configuration
gives. The recurrence is a `lax.scan` over single tokens with the state [H,
P, N] float32 its carry; attention is a `lax.map` over the heads; the
experts are a loop over the held ones, each over every row with its gate
weight (0 where not chosen); the head is computed for the positions asked
for alone, so that the reference fits on the chip beside the engine's bf16
tree. `checked` is what the benchmark compiles, once, at one length: tokens
padded behind `live` move no state (the model is causal), and each Mamba-2
layer's last state is handed back beside the log-probabilities, for the
comparison with the state the engine's pool holds.

It reads the program's own parameter tree (`lm.model_init`), so these follow
the program's layout and not the Hugging Face file's, and are noted as
departures: `transformer["layers"]` is {"mamba2", "full_attention", "moe"},
each kind's layers stacked in the model's order, each layer `input_norm` and
its one sublayer; `in_proj`'s columns are (z, xBC, dt) and xBC's (x, B, C)
in that order; `wkv` [h, 2 x 2 x 128] holds k's columns then v's; the
experts' banks are `w1` [E, latent, f] and `w2` [E, f, latent], the latent's
projections `latent_in` [h, latent] and `latent_out` [latent, h], the shared
expert `shared.w1` [h, 5376] and `shared.w2`.

Every matrix product runs under `jax.default_matmul_precision("highest")`.

`faults` (`benchmark/tests/ssd_fault_at_width.py` alone; empty everywhere
else) plants a fault in one piece of the mathematics: see `FAULTS`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32

FAULTS = {
    "state_bf16": "the carried state rounded to bfloat16 behind every token: "
                  "the nearest precision below the configuration's float32",
    "decay": "every head decays at A = -1, whatever its A_log",
    "state_reset": "the state starts from zeros at every 2,048th row: a "
                   "chunk that does not carry the state of the one before",
    "pool_bf16": "the state rounded to bfloat16 where a program hands it to "
                 "the pool and no oftener: behind every 2,048th row, behind "
                 "the prompt and behind every decoded row (`stored`)",
    "group": "every head reads group 0's B and C",
    "norm_before_gate": "the norm by group ahead of the gate",
    "latent": "W_up taken as W_down's transpose: the two projections tied",
    "scale": "routed_scaling_factor 1 for 5",
    "relu": "relu for relu^2 in the routed and the shared experts",
}


def _rmsnorm(p, x, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * p["scale"].astype(F32)


def mamba2(p, u, cfg, faults=frozenset(), keep=None, stored=None):
    """`p`: one layer's `mamba2` parameters as held; u [s, h] -> ([s, h], two
    states [2, H, P, N]: behind the last row that `keep` [s] bool marks (the
    rows behind it are padding and move no state; every row where None),
    and one row ahead of that). `stored` [s] bool is read by the fault
    `pool_bf16` alone."""
    s = u.shape[0]
    keep = jnp.ones((s,), bool) if keep is None else keep
    stored = jnp.zeros((s,), bool) if stored is None or \
        "pool_bf16" not in faults else stored
    heads, hd, groups, n, k = (cfg.mamba_num_heads, cfg.mamba_head_dim,
                               cfg.mamba_n_groups, cfg.mamba_d_state,
                               cfg.mamba_d_conv)
    di, per = heads * hd, heads // groups
    zxd = u @ p["in_proj"].astype(F32)
    z, xbc, dt = (zxd[:, :di], zxd[:, di:2 * di + 2 * groups * n],
                  zxd[:, 2 * di + 2 * groups * n:])
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    w = p["conv"].astype(F32)
    xbc = sum(w[j] * padded[j:j + s] for j in range(k))
    if "conv_bias" in p:
        xbc = xbc + p["conv_bias"].astype(F32)
    xbc = jax.nn.silu(xbc)
    a = xbc[:, :di].reshape(s, heads, hd)
    b = xbc[:, di:di + groups * n].reshape(s, groups, n)
    c = xbc[:, di + groups * n:].reshape(s, groups, n)
    if "group" in faults:
        b, c = (jnp.broadcast_to(t[:, :1], t.shape) for t in (b, c))
    b, c = (jnp.repeat(t, per, axis=1) for t in (b, c))      # [s, H, N]
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))      # [s, H]
    a_neg = -jnp.exp(p["A_log"].astype(F32))                 # [H]
    if "decay" in faults:
        a_neg = -jnp.ones_like(a_neg)
    carried = jnp.bfloat16 if "state_bf16" in faults else F32
    fresh = jnp.arange(s) % 2048 == 0 if "state_reset" in faults \
        else jnp.zeros((s,), bool)

    def token(carry, row):
        before, ahead = carry
        a_t, dt_t, b_t, c_t, fresh_t, keep_t, edge_t, stored_t = row
        state = jnp.where(fresh_t, 0.0, before.astype(F32))
        state = jnp.exp(dt_t * a_neg)[:, None, None] * state \
            + (dt_t[:, None] * a_t)[:, :, None] * b_t[:, None, :]
        y_t = jnp.sum(state * c_t[:, None, :], axis=-1)
        # the barrier keeps the chip's compiler from dropping the pair of
        # conversions as excess precision
        state = jnp.where(stored_t, jax.lax.optimization_barrier(
            state.astype(jnp.bfloat16)).astype(F32), state).astype(carried)
        return (jnp.where(keep_t, state, before),
                jnp.where(keep_t | edge_t, state, ahead)), y_t
    # the first padding row: its step is made from the last kept state, so
    # what it leaves is the state one row AHEAD of the kept ones
    edge = keep != jnp.pad(keep, (1, 0), constant_values=True)[:-1]
    zeros = jnp.zeros((heads, hd, n), carried)
    last, y = jax.lax.scan(token, (zeros, zeros),
                           (a, dt, b, c, fresh, keep, edge, stored))
    y = (y + p["D"].astype(F32)[:, None] * a).reshape(s, di)

    def by_group(t):
        t = t.reshape(s, groups, di // groups)
        var = jnp.mean(jnp.square(t), axis=-1, keepdims=True)
        return (t / jnp.sqrt(var + cfg.norm_epsilon)).reshape(s, di)
    scale = p["norm"]["scale"].astype(F32)
    if "norm_before_gate" in faults:
        y = by_group(y) * scale * jax.nn.silu(z)
    else:
        y = by_group(y * jax.nn.silu(z)) * scale
    return y @ p["out_proj"].astype(F32), jnp.stack(last).astype(F32)


def attention(p, u, cfg):
    """`p`: one layer's `attention` parameters as held; u [s, h] -> [s, h].
    A head at a time: [s, s] scores, never [heads, s, s]. No positions."""
    s = u.shape[0]
    nq, nkv, hd = cfg.num_attention_heads, cfg.num_kv_heads, cfg.kv_channels
    q = (u @ p["wq"].astype(F32)).reshape(s, nq, hd)
    kv = (u @ p["wkv"].astype(F32)).reshape(s, 2, nkv, hd)
    k, v = kv[:, 0], kv[:, 1]
    causal = jnp.tril(jnp.ones((s, s), bool))
    group = nq // nkv

    def head(i):
        q_h = jax.lax.dynamic_index_in_dim(q, i, 1, False)
        k_h = jax.lax.dynamic_index_in_dim(k, i // group, 1, False)
        v_h = jax.lax.dynamic_index_in_dim(v, i // group, 1, False)
        scores = q_h @ k_h.T / math.sqrt(hd)
        return jax.nn.softmax(jnp.where(causal, scores, -jnp.inf),
                              axis=-1) @ v_h
    out = jax.lax.map(head, jnp.arange(nq))                   # [nq, s, hd]
    return out.swapaxes(0, 1).reshape(s, nq * hd) @ p["wo"].astype(F32)


def gate_weights(router, bias, u, cfg, faults=frozenset()):
    """[s, router_experts] float32: the gate where the expert is among the
    token's top k of s + b (ties to the lower index, as `jax.lax.top_k`
    breaks them), 0 elsewhere. `router` [h, E] and `bias` [E] float32."""
    scores = jax.nn.sigmoid(u @ router)
    _, idx = jax.lax.top_k(scores + bias, cfg.moe_top_k)
    g = jnp.take_along_axis(scores, idx, axis=-1)
    g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    g = g * (1.0 if "scale" in faults else cfg.moe_routed_scaling_factor)
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, idx].set(g)


def experts(stack, u, cfg, at: int, faults=frozenset()):
    """(the routed sum over the experts HELD, back in the hidden size [s,
    h]; the shared expert's part [s, h]; the gate weights [s,
    router_experts]) of layer `at` of the stacked `mlp` parameters. Each
    expert's matrices are cut straight out of the stacked banks and upcast
    where they are used."""
    act = jax.nn.relu if "relu" in faults \
        else (lambda t: jnp.square(jax.nn.relu(t)))
    layer = lambda t: t[at].astype(F32)                       # noqa: E731
    w = gate_weights(layer(stack["router"]),
                     layer(stack["e_score_correction_bias"]), u, cfg, faults)
    down, up = layer(stack["latent_in"]), layer(stack["latent_out"])
    if "latent" in faults:
        up = down.T
    low = u @ down                                            # [s, latent]

    def pick(bank, e):          # expert e's matrix, cut where the bank lies
        cut = jax.lax.dynamic_slice(bank, (at, e, 0, 0),
                                    (1, 1) + bank.shape[-2:])
        return cut.reshape(bank.shape[-2:]).astype(F32)

    def add_expert(e, out):
        y = act(low @ pick(stack["w1"], e)) @ pick(stack["w2"], e)
        g = jax.lax.dynamic_slice_in_dim(w, cfg.moe_first_expert + e, 1,
                                         axis=1)
        return out + g * y
    routed = jax.lax.fori_loop(0, cfg.num_experts, add_expert,
                               jnp.zeros_like(low))
    shared = act(u @ layer(stack["shared"]["w1"])) \
        @ layer(stack["shared"]["w2"])
    return routed @ up, shared, w


def block(stack, x, cfg, kind: str, at: int, faults=frozenset(), keep=None,
          stored=None):
    """Layer `at` of the stacked parameters of one kind: (x', the expert
    layer's gate weights or None, the Mamba-2 layer's last states or None)."""
    u = _rmsnorm(jax.tree.map(lambda a: a[at], stack["input_norm"]), x,
                 cfg.norm_epsilon)
    if kind == "moe":
        routed, shared, w = experts(stack["mlp"], u, cfg, at, faults)
        return x + routed + shared, w, None
    p = jax.tree.map(lambda a: a[at], stack[
        "mamba2" if kind == "mamba2" else "attention"])
    if kind == "mamba2":
        m, state = mamba2(p, u, cfg, faults, keep, stored)
        return x + m, None, state
    return x + attention(p, u, cfg), None, None


def _trunk(params, tokens, cfg, faults=frozenset(), keep=None, stored=None):
    """(the last layer's output [s, h] before the final norm, the gate
    weights of every expert layer, the two last states of every Mamba-2
    layer)."""
    assert (cfg.one_sublayer and cfg.layers_of("mamba2")
            and set(cfg.layer_types) <= {"mamba2", "full_attention", "moe"}
            and not cfg.use_rotary_emb and not cfg.use_position_embedding
            and cfg.moe_latent_size and cfg.n_shared_experts == 1
            and cfg.moe_scoring_func == "sigmoid"
            and cfg.moe_score_correction_bias and cfg.moe_norm_topk_prob
            and cfg.activation == "squared_relu"
            and cfg.norm_type == "rmsnorm" and not cfg.use_bias
            and not cfg.tie_embed_logits), \
        "this reference is the Nemotron-3 (nemotron_h) block only"
    x = params["embedding"]["word_embeddings"][tokens].astype(F32)
    types = cfg.layer_types
    weights, states = [], []
    for l, kind in enumerate(types):
        x, w, state = block(params["transformer"]["layers"][kind], x, cfg,
                            kind, types[:l].count(kind), faults, keep, stored)
        if w is not None:
            weights.append(w)
        if state is not None:
            states.append(state)
    return x, weights, states


def _head(params, x, cfg, columns: int = 16384):
    """The head's matrix is upcast a block of columns at a time."""
    x = _rmsnorm(params["final_norm"], x, cfg.norm_epsilon)
    head = params["lm_head"]
    return jnp.concatenate(
        [x @ head[:, i:i + columns].astype(F32)
         for i in range(0, cfg.vocab_size, columns)],
        axis=-1)[:, :cfg.vocab_size]


def logits(params, tokens, cfg):
    """tokens [s] int -> logits [s, vocab] float32."""
    with jax.default_matmul_precision("highest"):
        return _head(params, _trunk(params, tokens, cfg)[0], cfg)


def checked(params, tokens, live, cfg, tail: int, faults=frozenset()):
    """What a check reads of `tokens[:live]`, whose last `tail` the engine
    chose: `logprobs` [tail], log p(tokens[i+1] | tokens[:i+1]) of those;
    `chosen` [expert layers, s-1, router_experts] bool, the experts each row
    chose; `states` [2, Mamba-2 layers, H, P, N], each layer's state behind
    tokens[:live-1], which is what the last log-probability was read
    behind, and behind tokens[:live] (equal to the first where no padding
    follows): what an engine that has fed its last token to a step ahead
    holds. `tokens` [s] may be padded behind `live`, a traced number: the
    model is causal and the padding rows move no state (`keep`), so one
    program serves every length up to s."""
    n = live - 1                                # the rows the trunk reads
    rows = jnp.arange(tokens.shape[0] - 1)
    with jax.default_matmul_precision("highest"):
        x, weights, states = _trunk(
            params, tokens[:-1], cfg, faults, keep=rows < n,
            stored=((rows + 1) % 2048 == 0) | (rows >= n - tail))
        out = _head(params, jax.lax.dynamic_slice_in_dim(x, n - tail, tail),
                    cfg)
    chose = jax.lax.dynamic_slice_in_dim(tokens, live - tail, tail)
    logp = jnp.take_along_axis(jax.nn.log_softmax(out, axis=-1),
                               chose[:, None], axis=-1)[:, 0]
    return {"logprobs": logp,
            "chosen": jnp.stack([w > 0 for w in weights]),
            "states": jnp.stack(states, axis=1)}


def token_logprobs(params, tokens, cfg, tail: int | None = None,
                   faults=frozenset()):
    """log p(tokens[i+1] | tokens[:i+1]) for every i: [s-1] float32, or with
    `tail` for the last `tail` of them alone."""
    tail = tokens.shape[0] - 1 if tail is None else tail
    return checked(params, tokens, tokens.shape[0], cfg, tail,
                   faults)["logprobs"]
