"""By hand, on the CPU (a few minutes on 8 cores, 60 GB): what a wrong
convolution state, and what fp8 matrices, read in the LFM2 cell's check, AT
the check's own length and the published widths.

    python benchmark/tests/state_fault_at_width.py [seed] [fault,fault]

The cell's reference (`reference/lfm2_moe.py`, float32) with a fault planted,
against the reference as it is, on the weights the driver draws from the
seed; compared as the driver compares (the log-probability of the sound
reference's top token at the last 32 of 732 positions, the ones the engine
decodes: mean and largest |difference|, the largest over the first two
decoded positions, positions over 0.05). Faults:

- `state_at_bucket_end`: the state a prefill leaves taken behind its
  bucket's padding (the check's 700 tokens lie in a bucket of 768: the two
  inputs kept are those of padding rows 766 and 767, token 0 at those
  positions, and not of rows 698 and 699). The reference has no state, so
  the fault is planted in its convolution: at position 700 the taps read the
  padded forward's last two inputs in place of a_698 and a_699, at position
  701 its last in place of a_699; everything behind (later layers, the keys
  and values later positions attend) follows from the stream so moved, as it
  does in the engine.
- `fp8`: every matrix of the mixers, the dense MLP and the experts rounded
  to float8_e4m3fn with one scale a matrix (an expert's, a layer's), the
  nearest precision below the bf16 the configuration states; router, choosing
  bias, norms, taps and embedding kept.

- `bf16_activations`: the reference with its weights as they are and every
  intermediate result rounded to bfloat16 where the engine rounds its own
  (norm outputs, every product's result, B * z, the gated sum, the rotated
  q and k, the probabilities, each expert's two results and the activation
  between them, the residual stream), sums in float32 as the engine's are:
  what the configuration's OWN precision reads, no engine and no chip.

Not a test: no chip, and too long for a suite."""
import json, os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
import jax, jax.numpy as jnp, numpy as np
from benchmark import run as bench_run
from benchmark.by_name import load_module
from benchmark.reference import lfm2_moe as ref
from megatron_tpu.arguments import parse_cli

_, _, config, mix = bench_run.load_cell("lfm2-8b-a1b.serve-chat-2k")
cfg, _ = parse_cli([*config["cli"], "--bf16"], n_devices=1)
mcfg = cfg.model
seed = int(sys.argv[1]) if len(sys.argv) > 1 else 3737000001
faults = (sys.argv[2].split(",") if len(sys.argv) > 2
          else ["state_at_bucket_end", "fp8"])
P, T = mix["check"]["prompt"], mix["check"]["output"]
BUCKET = mix["serving"]["prefill_bucket"]
PADDED = -(-P // BUCKET) * BUCKET
driver = load_module("drivers", "serve_open_loop_lfm2")
t0 = time.time()
params = jax.jit(lambda r: driver.draw_params(r, mcfg))(
    jax.random.PRNGKey(seed))
jax.block_until_ready(params)
print("weights", round(time.time() - t0), "s", flush=True)
tokens = np.random.default_rng([seed, 2]).integers(
    1, mcfg.vocab_size, size=P + T)
F32 = jnp.float32
sound_conv = ref.short_conv
STATE = {"mode": "sound", "kept": [], "layer": 0}


def short_conv(p, u, cfg):
    """The reference's own, with the fault's lines."""
    s, h = u.shape
    L = cfg.conv_L_cache
    bcz = u @ p["in_proj"].astype(F32)
    gate_b, gate_c, z = bcz[:, :h], bcz[:, h:2 * h], bcz[:, 2 * h:]
    a = gate_b * z
    if STATE["mode"] == "keep":             # the padded forward: rows 766, 767
        STATE["kept"].append(a[-(L - 1):])
    padded = jnp.pad(a, ((L - 1, 0), (0, 0)))
    if STATE["mode"] == "plant":
        kept = STATE["kept"][STATE["layer"]]
        STATE["layer"] += 1
        # the inputs older than the newest, by position: row t reads
        # older[t + j] for tap j; rows P and P + 1 read the padding's
        older = [padded[j:j + s] for j in range(L - 1)]
        older[0] = older[0].at[P].set(kept[0]).at[P + 1].set(kept[1])
        older[1] = older[1].at[P].set(kept[1])
        shifted = older + [a]
    else:
        shifted = [padded[j:j + s] for j in range(L)]
    w = p["conv"].astype(F32)
    c = sum(w[j] * shifted[j] for j in range(L))
    return (gate_c * c) @ p["out_proj"].astype(F32)


ref.short_conv = short_conv


def bf16_reference():
    """The reference's functions with the engine's roundings."""
    import math
    r = lambda x: x.astype(jnp.bfloat16).astype(F32)         # noqa: E731
    norm = lambda p, x, eps: r(r(ref._rmsnorm(               # noqa: E731
        {"scale": jnp.ones_like(p["scale"])}, x, eps)) * p["scale"].astype(F32))

    def conv(p, u, cfg):
        s, h = u.shape
        L = cfg.conv_L_cache
        bcz = r(u @ p["in_proj"].astype(F32))
        a = jnp.pad(r(bcz[:, :h] * bcz[:, 2 * h:]), ((L - 1, 0), (0, 0)))
        w = p["conv"].astype(F32)
        c = sum(w[j] * a[j:j + s] for j in range(L))
        return r(r(bcz[:, h:2 * h] * c) @ p["out_proj"].astype(F32))

    def attention(p, u, cfg):
        s = u.shape[0]
        nq, nkv, hd = (cfg.num_attention_heads, cfg.num_kv_heads,
                       cfg.kv_channels)
        q = r(u @ p["wq"].astype(F32)).reshape(s, nq, hd)
        kv = r(u @ p["wkv"].astype(F32)).reshape(s, 2, nkv, hd)
        k, v = kv[:, 0], kv[:, 1]
        q = r(ref._rotary(norm(p["q_norm"], q, cfg.norm_epsilon),
                          cfg.rope_theta))
        k = r(ref._rotary(norm(p["k_norm"], k, cfg.norm_epsilon),
                          cfg.rope_theta))
        causal = jnp.tril(jnp.ones((s, s), bool))

        def head(n):
            q_h = jax.lax.dynamic_index_in_dim(q, n, 1, False)
            k_h = jax.lax.dynamic_index_in_dim(k, n // (nq // nkv), 1, False)
            v_h = jax.lax.dynamic_index_in_dim(v, n // (nq // nkv), 1, False)
            scores = q_h @ k_h.T / math.sqrt(hd)
            return r(r(jax.nn.softmax(jnp.where(causal, scores, -jnp.inf),
                                      axis=-1)) @ v_h)
        out = jax.lax.map(head, jnp.arange(nq))
        return r(out.swapaxes(0, 1).reshape(s, nq * hd)
                 @ p["wo"].astype(F32))

    glu = lambda v, g, u, d: r(r(                              # noqa: E731
        jax.nn.silu(r(v @ g)) * r(v @ u)) @ d)

    def block(stack, x, cfg, kind, at, dense):
        eps = cfg.norm_epsilon
        rest = jax.tree.map(lambda a: a[at], {k: v for k, v in stack.items()
                                              if k != "mlp"})
        u = norm(rest["input_norm"], x, eps)
        x = r(x + (conv(rest["conv"], u, cfg) if kind == "conv"
                   else attention(rest["attention"], u, cfg)))
        v = norm(rest["post_attn_norm"], x, eps)
        if dense:
            w1 = stack["mlp"]["w1"][at].astype(F32)
            return r(x + glu(v, w1[:, 0], w1[:, 1],
                             stack["mlp"]["w2"][at].astype(F32))), None
        ref._glu = glu
        y, w = sound_experts(stack["mlp"], v, cfg, at)
        return r(x + r(y)), w
    sound_experts = ref.experts
    return block


def fp8_tree(tree):
    def rnd(path, x):
        name = jax.tree_util.keystr(path)
        if x.ndim < 2 or "norm" in name or "router" in name \
                or "bias" in name or "embedding" in name \
                or name.endswith("['conv']['conv']"):
            return x
        xf = x.astype(F32)
        # one scale a matrix: the last two axes
        scale = jnp.max(jnp.abs(xf), axis=(-2, -1), keepdims=True) / 448.0
        return ((xf / scale).astype(jnp.float8_e4m3fn).astype(F32)
                * scale).astype(x.dtype)
    return jax.tree_util.tree_map_with_path(rnd, tree)


def tail_logprobs(name, p, toks):
    def f(p, t):
        with jax.default_matmul_precision("highest"):
            x, _ = ref._trunk(p, t[:-1], mcfg)
            return jax.nn.log_softmax(ref._head(p, x[-T:], mcfg), axis=-1)
    t0 = time.time()
    STATE["layer"] = 0
    out = np.asarray(jax.jit(f)(p, jnp.asarray(toks, jnp.int32)), np.float64)
    print(name, "forward", round(time.time() - t0), "s", flush=True)
    return out


clean = tail_logprobs("clean", params, tokens)
top = clean.argmax(-1)
base = clean[np.arange(T), top]
res = {"seed": seed, "positions": T, "top_logprob_mean": float(base.mean())}
for name in faults:
    if name == "state_at_bucket_end":
        padded = np.concatenate([tokens[:P], np.zeros(PADDED - P, int)])

        def keep(p, t):             # rows 0 .. PADDED - 1 of the bucket
            STATE.update(mode="keep", kept=[])
            with jax.default_matmul_precision("highest"):
                ref._trunk(p, t, mcfg)
            return STATE["kept"]
        STATE["kept"] = jax.jit(keep)(params, jnp.asarray(padded, jnp.int32))
        STATE["mode"] = "plant"
        lp = tail_logprobs(name, params, tokens)
        STATE["mode"] = "sound"
    elif name == "fp8":
        lp = tail_logprobs(name, jax.jit(fp8_tree)(params), tokens)
    elif name == "bf16_activations":
        sound_block, sound_glu = ref.block, ref._glu
        ref.block = bf16_reference()
        lp = tail_logprobs(name, params, tokens)
        ref.block, ref._glu = sound_block, sound_glu
    else:
        raise SystemExit(f"unknown fault {name!r}")
    d = np.abs(lp[np.arange(T), top] - base)
    res[name] = {"mean": float(d.mean()), "max": float(d.max()),
                 "first_two_max": float(d[1:3].max()),
                 "over_0_05": int((d > 0.05).sum())}
    print(json.dumps(res), flush=True)
