"""The gated delta rule of a Kimi Delta Attention mixer (models/kda.py),
token by token and chunk by chunk.

For every sequence and head, with the state S a matrix [d_k, d_v] float32,
q_t and k_t [d_k] (k of unit length, q of length 1 / sqrt(d_k): the mixer
norms them), v_t [d_v], g_t [d_k] <= 0 a log-decay a CHANNEL and beta_t in
(0, 1):

    S' = Diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

A row with beta = 0 and g = 0 leaves S exactly where it stood: that is how
the caller keeps a bucket's padding rows out of the state, and how rows are
padded to whole chunks here. `h0` is the state the sequence carries in and
the state behind the last row is handed back, float32 [batch, H, d_k, d_v]
whatever the rows' dtype.

Three forms, one function each:

(a) `kda_recurrent`: the rule as written, a `lax.scan` over the rows, all
    float32. The DEFINITION: the path off the chip, what `jax.grad`
    differentiates (there is no backward kernel) and the tests' arbiter.
(b) `kda_step`: one row a sequence, the decode step's, elementwise over the
    pool's layer of state, which the caller updates in place.
(c) `_kda_chunk`: a Pallas kernel for a prefill or a chunk on the TPU, jitted
    under that name so that the device trace names its calls after it. Grid
    (sequence, blocks of heads, chunks), the chunks innermost: a block's
    states stay in fast memory from a sequence's first chunk to its last and
    meet HBM twice (`ops/ssd_scan.py`'s frame).

The chunk form, derived from (a). Inside a chunk of C rows let G_r be the
running sum of g up to and with row r, and write S_r = Diag(e^{G_r}) S_0 +
sum_{i<=r} Diag(e^{G_r - G_i}) k_i u_i^T, with u_i = beta_i (v_i - S'_i^T
k_i) the row's correction. Putting the first into the second,

    (I + tril(Diag(beta) A, -1)) U = Diag(beta) (V - (K e^G) S_0),
                                     A_ri = sum_d k_r[d] k_i[d] e^{G_r[d] - G_i[d]}
    O   = (Q e^G) S_0 + tril(B) U,   B_ri = sum_d q_r[d] k_i[d] e^{G_r[d] - G_i[d]}
    S_C = Diag(e^{G_C}) S_0 + (K e^{G_C - G})^T U

one unit lower triangular system a chunk. A and B are NOT made as (K
e^G)(K e^-G)^T: e^-G passes float32 after eleven rows of g = -8. Every
exponent taken here is a difference G_r - G_i with i <= r, which is <= 0:

- between sub-chunks of `SUB` rows, e^{G_r - G_i} = e^{G_r - G*} e^{G* -
  G_i} with G* the sum behind the row before r's sub-chunk: both factors <=
  1, one product a strip of `SUB` rows;
- inside a sub-chunk, the difference itself, a column at a time (`SUB`
  columns: the rows of every sub-chunk against their own sub-chunk's j-th).

The system is solved in blocks of `SUB`: the unit lower triangular diagonal
blocks D are inverted exactly in float32 (forward substitution on the
vector unit, every block at once), and with N = D^-1 L_off strictly BLOCK
lower, (I + N)^-1 is the finite product (I - N)(I + N^2)(I + N^4).. over
the chunk's sub-chunks, applied to D^-1 times the right-hand side.

Precision (`flash_attention_pallas.py`'s rule): products take their
operands in the rows' dtype (bf16 on the chip) and accumulate in float32;
the ones that read the float32 state are float32 ("highest"); G, every
exponential, the diagonal blocks' inverses and every accumulator float32.

(d) `_gdn_chunk`, the SCALAR-DECAY form of (c), for a rule whose decay is
    ONE number a head a row (a Gated DeltaNet mixer, models/gated_delta.py:
    g [batch, rows, H]) and whose H value heads read H_k <= H key heads
    (value head j reads q and k of key head j // (H / H_k), through the
    block index: q and k are not repeated in HBM). With G a number a row,

        A_ri = (k_r . k_i) e^{G_r - G_i},   B_ri = (q_r . k_i) e^{G_r - G_i}

    ONE product K K^T and one Q K^T a chunk a key head on the matrix unit
    (operands as they come, float32 sums: exact for bf16 rows) times a [C,
    C] matrix of exponentials of differences, each <= 0 where it is kept (i
    <= r); where (c) walks `SUB` columns a sub-chunk on the vector unit.
    The solve, the state's path and the grid are (c)'s. `gdn_chunk` is its
    `kda_chunk`; off the chip it is form (a) with g broadcast over the
    channels and q and k repeated.

No option chooses between (a) and (c), and none sets the chunk: `CHUNK` rows
a grid step (the kernel on the chip took 64 and 128 alike, 4.6 ms a
4,096-row call; PERF.md section 6, PR 58), `kda_block_heads` is the kernel's
shape rule, and (c) runs where it holds on a TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LANES = 128
SUB = 16
CHUNK = 64
F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def kda_step(q, k, v, g, beta, s):
    """Form (b): q, k [batch, H, d_k], v [batch, H, d_v], g [batch, H, d_k]
    float32 (<= 0), beta [batch, H], s [batch, H, d_k, d_v] float32 -> (o
    [batch, H, d_v] in v's dtype, the new state). Sums over the state's key
    axis and not `einsum`s: elementwise float32 whatever the backend's
    default precision of a product is."""
    qf, kf, vf = q.astype(F32), k.astype(F32), v.astype(F32)
    s = jnp.exp(g.astype(F32))[..., None] * s
    u = beta.astype(F32)[..., None] * (
        vf - jnp.sum(s * kf[..., None], axis=-2))
    s = s + kf[..., None] * u[..., None, :]
    o = jnp.sum(s * qf[..., None], axis=-2)
    return o.astype(v.dtype), s


def kda_recurrent(q, k, v, g, beta, h0=None):
    """Form (a): q, k [batch, rows, H, d_k], v [batch, rows, H, d_v], g
    [batch, rows, H, d_k], beta [batch, rows, H]; h0 [batch, H, d_k, d_v]
    float32 or None (zeros) -> (o [batch, rows, H, d_v] in v's dtype, the
    state behind the last row, float32)."""
    batch, _, heads, d_k = q.shape
    if h0 is None:
        h0 = jnp.zeros((batch, heads, d_k, v.shape[-1]), F32)

    def row(s, x):
        o, s = kda_step(*x, s)
        return s, o
    by_row = lambda t: jnp.swapaxes(t, 0, 1)                 # noqa: E731
    last, o = jax.lax.scan(row, h0.astype(F32),
                           tuple(by_row(t) for t in (q, k, v, g, beta)))
    return by_row(o), last


def kda_block_heads(heads: int, d_k: int, d_v: int, *, aligned: bool = True):
    """The kernel's shape rule: the heads a grid step takes, or None where
    the kernel does not take the shape (form (a) then): on the chip
    (`aligned`) heads of whole lane tiles."""
    if aligned and (d_k % LANES or d_v % LANES):
        return None
    return 2 if heads % 2 == 0 else 1


def kda_chunk(q, k, v, g, beta, h0=None, *, chunk: int = CHUNK,
              use_kernel=None, interpret: bool = False):
    """A prefill's or a chunk's rows: form (c) where its shape rule holds
    on a TPU (or `interpret`), else form (a). Shapes as `kda_recurrent`.
    `chunk` is the tests': a chunk the kernel cannot take is refused there,
    not sent to form (a)."""
    batch, _, heads, d_k = q.shape
    d_v = v.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((batch, heads, d_k, d_v), F32)
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if (use_kernel or interpret) and kda_block_heads(
            heads, d_k, d_v, aligned=not interpret) is not None:
        return _kda_chunk(q, k, v, g, beta, h0, chunk=chunk,
                          interpret=interpret)
    return kda_recurrent(q, k, v, g, beta, h0)


def _dot(a, b, dims, dtype, precision=None):
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype),
                               (dims, ((), ())), preferred_element_type=F32,
                               precision=precision)


def _unit_lower_inverse(low_own, eye_rows, base: int = 0):
    """The inverse of one unit lower triangular diagonal block of `SUB`
    rows, exact in float32 on the vector unit. `low_own` [SUB, SUB] is the
    block's strictly lower part (or, with `base`, the block's rows of the
    whole strictly lower matrix, [SUB, C], the block's columns from `base`
    on); `eye_rows` [SUB, C] the block's rows of the identity. Forward
    substitution, right-looking: once row m of the inverse is whole, every
    later row i sheds L[i, m] times it. Returns the block's rows of the
    block diagonal inverse, [SUB, C]."""
    x = eye_rows
    for m in range(SUB - 1):
        x = x - low_own[:, base + m:base + m + 1] * x[m:m + 1]
    return x


def _solve_and_out(kf, qf, big_g, beta, low, b_mat, x_d, v, state, *,
                   chunk: int, dtype):
    """What both chunk kernels do once A (as `low` = tril(Diag(beta) A, -1)),
    B (`b_mat`, lower with its diagonal) and the diagonal blocks' inverses
    (`x_d`) are made: the state's part, the block solve, the chunk's
    outputs and the state behind its last row. `big_g` [C, d_k] (a decay a
    channel) or [C, 1] (a decay a head): the running sums. Returns (o [C,
    d_v] float32, the new state [d_k, d_v] float32)."""
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    subs = chunk // SUB
    # ---- the state's part, float32 -------------------------------------
    e_g = jnp.exp(big_g)                                     # <= 1
    from_state = _dot(jnp.concatenate([kf * e_g, qf * e_g], axis=0),
                      state, ((1,), (0,)), F32, HIGHEST)
    rhs = beta * (v.astype(F32) - from_state[:chunk])
    # ---- the solve: T = D (I + N), N = D^-1 L_off -----------------------
    u = _dot(x_d, rhs, ((1,), (0,)), dtype)
    if subs > 1:
        off = jnp.where(row // SUB != col // SUB, low, 0.0)
        n1 = _dot(x_d, off, ((1,), (0,)), dtype)
        u = u - _dot(n1, u, ((1,), (0,)), dtype)
        power = 2
        while power < subs:          # (I + N^2)(I + N^4)..
            n1 = _dot(n1, n1, ((1,), (0,)), dtype)
            u = u + _dot(n1, u, ((1,), (0,)), dtype)
            power *= 2
    # ---- out -------------------------------------------------------------
    o = from_state[chunk:] + _dot(b_mat, u, ((1,), (0,)), dtype)
    end = big_g[chunk - 1:chunk]                             # [1, d_k | 1]
    k_end = kf * jnp.exp(end - big_g)                        # <= 1
    if big_g.shape[1] == 1:
        # ONE decay a head: e^{G_C} along the lanes, picked out of the
        # column's broadcast by its row (Mosaic broadcasts a [1, 1] in one
        # direction only, and folds two broadcasts into one)
        wide = jnp.broadcast_to(e_g, (chunk, state.shape[1]))
        last = jax.lax.broadcasted_iota(jnp.int32, wide.shape, 0) == chunk - 1
        decay = jnp.sum(jnp.where(last, wide, 0.0), axis=0, keepdims=True)
    else:
        decay = jnp.exp(end).reshape(-1, 1)                  # [d_k, 1]
    return o, decay * state + _dot(k_end, u, ((0,), (0,)), dtype)


def _chunk_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, h0_ref, o_ref,
                  state_ref, *, heads: int, d_k: int, d_v: int, chunk: int):
    from jax.experimental import pallas as pl
    dtype = q_ref.dtype
    subs = chunk // SUB

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = h0_ref[...]

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    eye = jnp.where(row == col, 1.0, 0.0).astype(F32)
    # a strip of SUB rows against the chunk, and against its own block
    col_s = jax.lax.broadcasted_iota(jnp.int32, (SUB, chunk), 1)
    row_o = jax.lax.broadcasted_iota(jnp.int32, (SUB, SUB), 0)
    col_o = jax.lax.broadcasted_iota(jnp.int32, (SUB, SUB), 1)
    for j in range(heads):
        at_k = slice(j * d_k, (j + 1) * d_k)
        at_v = slice(j * d_v, (j + 1) * d_v)
        qf = q_ref[0, :, at_k].astype(F32)                   # [C, d_k]
        kf = k_ref[0, :, at_k].astype(F32)
        big_g = g_ref[0, :, at_k]                            # running sums
        beta = beta_ref[0, 0, :, j:j + 1]                    # [C, 1]
        # ---- A, B and the diagonal blocks' inverses, a strip of SUB rows
        # at a time, every exponent a difference <= 0 ---------------------
        strips_a, strips_b, strips_x = [], [], []
        for i in range(subs):
            rows = slice(i * SUB, (i + 1) * SUB)
            g_i, k_i, q_i = big_g[rows], kf[rows], qf[rows]
            a_i = jnp.zeros((SUB, chunk), F32)
            b_i = jnp.zeros((SUB, chunk), F32)
            own = jnp.zeros((SUB, SUB), F32)
            # inside the sub-chunk: its rows against its own jj-th
            for jj in range(SUB):
                w = jnp.exp(jnp.minimum(g_i - g_i[jj:jj + 1], 0.0)) \
                    * k_i[jj:jj + 1]
                a_col = jnp.sum(k_i * w, axis=1, keepdims=True)
                a_i = jnp.where(col_s == i * SUB + jj, a_col, a_i)
                own = jnp.where(col_o == jj, a_col, own)
                b_i = jnp.where(col_s == i * SUB + jj,
                                jnp.sum(q_i * w, axis=1, keepdims=True), b_i)
            if i:
                # against the rows before: G* the sum behind the last of them
                ref = big_g[i * SUB - 1:i * SUB]             # [1, d_k]
                down = jnp.exp(g_i - ref)                    # <= 1
                up = kf * jnp.exp(jnp.minimum(ref - big_g, 0.0))
                before = col_s < i * SUB
                a_i = jnp.where(before, _dot(k_i * down, up, ((1,), (1,)),
                                             dtype), a_i)
                b_i = jnp.where(before, _dot(q_i * down, up, ((1,), (1,)),
                                             dtype), b_i)
            strips_a.append(a_i)
            strips_b.append(b_i)
            strips_x.append(_unit_lower_inverse(
                jnp.where(row_o > col_o, beta[rows] * own, 0.0), eye[rows]))
        low = jnp.where(row > col,
                        beta * jnp.concatenate(strips_a, axis=0), 0.0)
        b_mat = jnp.where(row >= col, jnp.concatenate(strips_b, axis=0), 0.0)
        x_d = jnp.concatenate(strips_x, axis=0)              # D^-1
        o, state_ref[0, j] = _solve_and_out(
            kf, qf, big_g, beta, low, b_mat, x_d, v_ref[0, :, at_v],
            state_ref[0, j], chunk=chunk, dtype=dtype)
        o_ref[0, :, at_v] = o.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _kda_chunk(q, k, v, g, beta, h0, *, chunk=CHUNK, interpret=False):
    """Form (c), under the name the device trace reads."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    batch, rows, heads, d_k = q.shape
    d_v = v.shape[-1]
    subs = chunk // SUB
    assert chunk % SUB == 0 and subs & (subs - 1) == 0, (
        f"a chunk is whole sub-chunks of {SUB} rows, a power of two of them "
        f"(the block solve's doubling), not {chunk}")
    hb = kda_block_heads(heads, d_k, d_v, aligned=not interpret)
    pad = -rows % chunk
    if pad:     # rows of beta 0 and g 0 move no state; their o is cut off
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta))
    total = rows + pad
    n = total // chunk
    # the running sums of g inside each chunk, made outside (a cumulative
    # sum over [rows, H d_k] float32) as ops/ssd_scan.py makes its own
    run = jnp.cumsum(g.astype(F32).reshape(batch, n, chunk, heads * d_k),
                     axis=2).reshape(batch, total, heads * d_k)
    # beta a column a head: [batch, blocks of heads, rows, heads a block]
    cols = beta.astype(F32).reshape(batch, total, heads // hb, hb) \
        .swapaxes(1, 2)
    by_k = pl.BlockSpec((1, chunk, hb * d_k), lambda bi, hi, ci: (bi, ci, hi))
    by_v = pl.BlockSpec((1, chunk, hb * d_v), lambda bi, hi, ci: (bi, ci, hi))
    # a block's states come in with the sequence's first chunk and go out
    # behind its last: the block's index stands still over the chunks
    state_spec = pl.BlockSpec((1, hb, d_k, d_v),
                              lambda bi, hi, ci: (bi, hi, 0, 0))
    products = 2 * batch * total * heads * (
        2 * chunk * d_k + 2 * d_k * d_v + 2 * chunk * d_v + d_k * d_v)
    call = pl.pallas_call(
        functools.partial(_chunk_kernel, heads=hb, d_k=d_k, d_v=d_v,
                          chunk=chunk),
        grid=(batch, heads // hb, n),
        in_specs=[by_k, by_k, by_v, by_k,
                  pl.BlockSpec((1, 1, chunk, hb),
                               lambda bi, hi, ci: (bi, hi, ci, 0)),
                  state_spec],
        out_specs=[by_v, state_spec],
        out_shape=[jax.ShapeDtypeStruct((batch, total, heads * d_v), v.dtype),
                   jax.ShapeDtypeStruct(h0.shape, F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=products,
            transcendentals=batch * total * heads * d_k * (SUB + n + 2),
            bytes_accessed=(q.size + k.size) * q.dtype.itemsize
            + 2 * v.size * v.dtype.itemsize + 4 * g.size + 4 * beta.size
            + 2 * h0.size * 4),
        interpret=interpret)
    # the kernel's products take their operands in the rows' dtype and
    # accumulate in float32 whatever precision the caller has set as JAX's
    # default (Mosaic refuses "highest" on bf16 operands); those that read
    # the float32 state name their own
    with jax.default_matmul_precision("bfloat16" if q.dtype == jnp.bfloat16
                                      else "highest"):
        o, last = call(
            q.reshape(batch, total, heads * d_k),
            k.reshape(batch, total, heads * d_k).astype(q.dtype),
            v.reshape(batch, total, heads * d_v), run, cols, h0.astype(F32))
    return o.reshape(batch, total, heads, d_v)[:, :rows], last


# ---- form (d): ONE decay a head a row, key heads under value heads --------

def _per_key_head(t, ratio: int):
    """[.., H_k, d] -> [.., H_k * ratio, d]: value head j reads key head j
    // ratio (forms (a) and (b), which have no block index to read it
    through)."""
    return t if ratio == 1 else jnp.repeat(t, ratio, axis=-2)


def gdn_step(q, k, v, g, beta, s):
    """Form (b) for a rule with one decay a head: q, k [batch, H_k, d_k], v
    [batch, H, d_v], g [batch, H] float32 (<= 0), beta [batch, H], s
    [batch, H, d_k, d_v] float32 -> (o [batch, H, d_v], the new state)."""
    ratio = v.shape[1] // q.shape[1]
    return kda_step(_per_key_head(q, ratio), _per_key_head(k, ratio), v,
                    g[..., None], beta, s)


def gdn_recurrent(q, k, v, g, beta, h0=None):
    """Form (a) for a rule with one decay a head: q, k [batch, rows, H_k,
    d_k], v [batch, rows, H, d_v], g and beta [batch, rows, H]."""
    ratio = v.shape[2] // q.shape[2]
    return kda_recurrent(_per_key_head(q, ratio), _per_key_head(k, ratio),
                         v, g[..., None], beta, h0)


def gdn_chunk(q, k, v, g, beta, h0=None, *, chunk: int = CHUNK,
              use_kernel=None, interpret: bool = False):
    """`kda_chunk` for a rule with one decay a head: form (d) where the
    shape rule holds on a TPU (or `interpret`), else form (a). Shapes as
    `gdn_recurrent`."""
    batch, _, heads, d_v = v.shape
    d_k = q.shape[-1]
    if h0 is None:
        h0 = jnp.zeros((batch, heads, d_k, d_v), F32)
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu"
    if (use_kernel or interpret) and kda_block_heads(
            heads, d_k, d_v, aligned=not interpret) is not None:
        return _gdn_chunk(q, k, v, g, beta, h0, chunk=chunk,
                          interpret=interpret)
    return gdn_recurrent(q, k, v, g, beta, h0)


def _gdn_chunk_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, h0_ref, o_ref,
                      state_ref, *, heads: int, key_heads: int, d_k: int,
                      d_v: int, chunk: int):
    """`heads` value heads a grid step over the `key_heads` key heads they
    read (`heads` a multiple of `key_heads`, or ONE key head)."""
    from jax.experimental import pallas as pl
    dtype = q_ref.dtype
    subs = chunk // SUB

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = h0_ref[...]

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    eye = jnp.where(row == col, 1.0, 0.0).astype(F32)
    products = {}       # a key head's K K^T and Q K^T, made once
    for j in range(heads):
        jk = j * key_heads // heads
        at_k = slice(jk * d_k, (jk + 1) * d_k)
        at_v = slice(j * d_v, (j + 1) * d_v)
        if jk not in products:
            q_j, k_j = q_ref[0, :, at_k], k_ref[0, :, at_k]
            products[jk] = (
                q_j.astype(F32), k_j.astype(F32),
                _dot(k_j, k_j, ((1,), (1,)), dtype),          # [C, C]
                _dot(q_j, k_j, ((1,), (1,)), dtype))
        qf, kf, kk, qk = products[jk]
        big_g = g_ref[0, 0, :, j:j + 1]                      # [C, 1] sums
        beta = beta_ref[0, 0, :, j:j + 1]                    # [C, 1]
        # G along the columns: the identity picks each row's own sum
        g_cols = jnp.sum(eye * big_g, axis=0, keepdims=True)  # [1, C]
        # e^{G_r - G_i}: <= 1 wherever it is kept (i <= r)
        decay = jnp.exp(jnp.minimum(big_g - g_cols, 0.0))
        low = jnp.where(row > col, beta * (kk * decay), 0.0)
        b_mat = jnp.where(row >= col, qk * decay, 0.0)
        x_d = jnp.concatenate([
            _unit_lower_inverse(low[i * SUB:(i + 1) * SUB],
                                eye[i * SUB:(i + 1) * SUB], i * SUB)
            for i in range(subs)], axis=0)                   # D^-1
        o, state_ref[0, j] = _solve_and_out(
            kf, qf, big_g, beta, low, b_mat, x_d, v_ref[0, :, at_v],
            state_ref[0, j], chunk=chunk, dtype=dtype)
        o_ref[0, :, at_v] = o.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _gdn_chunk(q, k, v, g, beta, h0, *, chunk=CHUNK, interpret=False):
    """Form (d), under the name the device trace reads."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    batch, rows, key_heads, d_k = q.shape
    heads, d_v = v.shape[2], v.shape[3]
    ratio = heads // key_heads
    subs = chunk // SUB
    assert chunk % SUB == 0 and subs & (subs - 1) == 0, (
        f"a chunk is whole sub-chunks of {SUB} rows, a power of two of them "
        f"(the block solve's doubling), not {chunk}")
    assert heads == key_heads * ratio, (heads, key_heads)
    hb = kda_block_heads(heads, d_k, d_v, aligned=not interpret)
    if hb % ratio and ratio % hb:
        hb = 1
    # the key heads a step reads: its value heads' own, or the ONE above them
    kb = max(hb // ratio, 1)
    pad = -rows % chunk
    if pad:     # rows of beta 0 and g 0 move no state; their o is cut off
        q, k, v, g, beta = (
            jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
            for t in (q, k, v, g, beta))
    total = rows + pad
    n = total // chunk

    def cols(t):    # a column a head: [batch, blocks of heads, rows, hb]
        return t.reshape(batch, total, heads // hb, hb).swapaxes(1, 2)
    # the running sums of g inside each chunk, made outside
    run = jnp.cumsum(g.astype(F32).reshape(batch, n, chunk, heads),
                     axis=2).reshape(batch, total, heads)
    by_k = pl.BlockSpec((1, chunk, kb * d_k),
                        lambda bi, hi, ci: (bi, ci, hi * hb // ratio // kb))
    by_v = pl.BlockSpec((1, chunk, hb * d_v), lambda bi, hi, ci: (bi, ci, hi))
    by_col = pl.BlockSpec((1, 1, chunk, hb),
                          lambda bi, hi, ci: (bi, hi, ci, 0))
    state_spec = pl.BlockSpec((1, hb, d_k, d_v),
                              lambda bi, hi, ci: (bi, hi, 0, 0))
    products = 2 * batch * total * (
        key_heads * 2 * chunk * d_k
        + heads * (2 * d_k * d_v + 2 * chunk * d_v + d_k * d_v))
    call = pl.pallas_call(
        functools.partial(_gdn_chunk_kernel, heads=hb, key_heads=kb,
                          d_k=d_k, d_v=d_v, chunk=chunk),
        grid=(batch, heads // hb, n),
        in_specs=[by_k, by_k, by_v, by_col, by_col, state_spec],
        out_specs=[by_v, state_spec],
        out_shape=[jax.ShapeDtypeStruct((batch, total, heads * d_v), v.dtype),
                   jax.ShapeDtypeStruct(h0.shape, F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=products,
            transcendentals=batch * total * heads * (chunk + 2),
            bytes_accessed=(q.size + k.size) * q.dtype.itemsize
            + 2 * v.size * v.dtype.itemsize + 4 * g.size + 4 * beta.size
            + 2 * h0.size * 4),
        interpret=interpret)
    with jax.default_matmul_precision("bfloat16" if q.dtype == jnp.bfloat16
                                      else "highest"):
        o, last = call(
            q.reshape(batch, total, key_heads * d_k),
            k.reshape(batch, total, key_heads * d_k).astype(q.dtype),
            v.reshape(batch, total, heads * d_v), cols(run),
            cols(beta.astype(F32)), h0.astype(F32))
    return o.reshape(batch, total, heads, d_v)[:, :rows], last
