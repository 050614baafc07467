"""By hand, on the CPU (about 25 minutes on 8 cores, 40 GB): what a ring
fault reads in the command-a-plus cell's check, AT the check's own length and
the published widths.

    python benchmark/tests/ring_fault_at_width.py [seed] [fault,fault]

The cell's reference (`reference/command_a_plus.py`, float32) with a fault
planted in its window layers' band mask, against the reference as it is, on
the weights the driver draws from the seed; compared as the driver compares
(the log-probability of the sound reference's top token at the last 32 of
10,032 positions: mean and largest |difference|, positions over 0.05).
Faults: `padding_rows` (the last chunk's padding rows written into the
rings, as many as the mix's `prefill_bucket` pads: 2,288 with the cell's
bucket of a whole chunk, positions 5,904 to 8,191 gone for every query from
10,000 on; 240 with a bucket of 1,024), `one_row` (the window's oldest row
lost: a ring of 4,095), `chunk_alone` (a chunk that missed the ring's earlier
rows). PR 33's readings, seed 3333500001: 0.446 / 1.15 / 30 (0.075 / 0.21 /
19 at 240 rows); 0.005 / 0.017 / 0; 2.4 / 4.7 / 32, beside the driver's
limits 0.045 (mean) and 0.40 (a position). Not a test: no chip, and
too long for a suite. `attention` below is the reference's own with the
fault's lines added."""
import json, math, os, sys, time
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
import jax, jax.numpy as jnp, numpy as np
from benchmark import run as bench_run
from benchmark.reference import command_a_plus as ref
from megatron_tpu.arguments import parse_cli
from megatron_tpu.models import language_model as lm

_, _, config, mix = bench_run.load_cell("command-a-plus.serve-longdoc-32k")
cfg, _ = parse_cli([*config["cli"], "--bf16"], n_devices=1)
mcfg = cfg.model
seed = int(sys.argv[1]) if len(sys.argv) > 1 else 3333500001
P, T = mix["check"]["prompt"], mix["check"]["output"]
CHUNK = mix["serving"]["prefill_chunk"]
BUCKET = mix["serving"]["prefill_bucket"]
W = mcfg.sliding_window
t0 = time.time()
params = jax.jit(lambda r: lm.model_init(r, mcfg))(jax.random.PRNGKey(seed))
jax.block_until_ready(params)
print("weights", round(time.time() - t0), "s", flush=True)
tokens = jnp.asarray(np.random.default_rng([seed, 2]).integers(
    1, mcfg.vocab_size, size=P + T), jnp.int32)

FAULT = {"name": "clean"}
F32 = jnp.float32

def attention(p, u, cfg, full):
    s = u.shape[0]
    nq, nkv, hd = cfg.num_attention_heads, cfg.num_kv_heads, cfg.kv_channels
    kv = (u @ p["wkv"].astype(F32)).reshape(s, 2, nkv, hd)
    k, v = kv[:, 0], kv[:, 1]
    if not full:
        k = ref._rotary(k, cfg.rope_theta)
    pad = -s % ref.Q_BLOCK
    blocks = (s + pad) // ref.Q_BLOCK
    kv_pos = jnp.arange(s)[None, :]
    def add_head(n, out):
        k_h = jax.lax.dynamic_index_in_dim(k, n // (nq // nkv), 1, False)
        v_h = jax.lax.dynamic_index_in_dim(v, n // (nq // nkv), 1, False)
        q_h = u @ jax.lax.dynamic_slice_in_dim(p["wq"], n * hd, hd, axis=1).astype(F32)
        if not full:
            q_h = ref._rotary(q_h[:, None], cfg.rope_theta)[:, 0]
        q_h = jnp.pad(q_h, ((0, pad), (0, 0)))
        def block(i):
            q_b = jax.lax.dynamic_slice_in_dim(q_h, i * ref.Q_BLOCK, ref.Q_BLOCK)
            q_pos = (i * ref.Q_BLOCK + jnp.arange(ref.Q_BLOCK))[:, None]
            mask = kv_pos <= q_pos
            if not full:
                mask = mask & (q_pos - kv_pos < cfg.sliding_window)
                f = FAULT["name"]
                if f == "padding_rows":
                    # the last chunk's padding rows written into the
                    # ring: positions 5,904 on are gone for every query
                    # from 10,000 on (their rows hold something else)
                    real = P - (P // CHUNK) * CHUNK
                    n_pad = -real % BUCKET
                    lo = P - W
                    mask = mask & ~((q_pos >= P) & (kv_pos >= lo) & (kv_pos < lo + n_pad))
                elif f == "one_row":
                    # the oldest row of the window lost (a ring of 4,095)
                    mask = mask & (q_pos - kv_pos < cfg.sliding_window - 1)
                elif f == "chunk_alone":
                    # a chunk that missed the ring's earlier rows: a window
                    # layer's query reads its own chunk's keys alone
                    mask = mask & (kv_pos >= (q_pos // CHUNK) * CHUNK)
            scores = q_b @ k_h.T / math.sqrt(hd)
            return jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1) @ v_h
        o_h = jax.lax.map(block, jnp.arange(blocks)).reshape(-1, hd)[:s]
        return out + o_h @ jax.lax.dynamic_slice_in_dim(p["wo"], n * hd, hd, axis=0).astype(F32)
    return jax.lax.fori_loop(0, nq, add_head, jnp.zeros_like(u))

ref.attention = attention

def tail_logits(name):
    FAULT["name"] = name
    def f(p, t):
        with jax.default_matmul_precision("highest"):
            x, _ = ref._trunk(p, t[:-1], mcfg)
            return jax.nn.log_softmax(ref._head(p, x[-T:], mcfg), axis=-1)
    t0 = time.time()
    out = np.asarray(jax.jit(f)(params, tokens), np.float64)
    print(name, "forward", round(time.time() - t0), "s", flush=True)
    return out

clean = tail_logits("clean")
top = clean.argmax(-1)
base = clean[np.arange(T), top]
res = {"seed": seed, "positions": T, "top_logprob_mean": float(base.mean())}
for name in sys.argv[2].split(",") if len(sys.argv) > 2 else ["padding_rows", "one_row", "chunk_alone"]:
    lp = tail_logits(name)[np.arange(T), top]
    d = np.abs(lp - base)
    res[name] = {"mean": float(d.mean()), "max": float(d.max()), "over_0_05": int((d > 0.05).sum())}
    print(json.dumps(res), flush=True)
