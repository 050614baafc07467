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

- between sub-chunks of `SUB` rows, by halves: at each level the rows of a
  block's second half against the columns of its first, e^{G_r - G_i} =
  e^{G_r - G*} e^{G* - G_i} with G* the sum at the first half's last row:
  both factors <= 1, ONE product a level for A and B together;
- inside a sub-chunk, the difference itself, a column at a time (`SUB`
  columns a CHUNK: the rows of every sub-chunk against their own
  sub-chunk's j-th, and from the sub-chunk's second half on only the rows
  of that half: the others lie above the diagonal).

The running sums G are made in the kernel from g as it comes (PR 61; up to
PR 60 a `jnp.cumsum` outside wrote and the kernel read again 67 MB a
4,096-row call): inside blocks of eight rows by shifted adds, the blocks'
totals carried one behind the other, so a sum is a few roundings from the
sequential one whatever its size.

The system is solved in blocks of `SUB`: the unit lower triangular diagonal
blocks D are inverted exactly in float32 (forward substitution on the
vector unit, every block of every head of a grid step a step at a time),
and with N = D^-1 L_off strictly BLOCK lower, (I + N)^-1 is the finite
product (I - N)(I + N^2)(I + N^4).. over the chunk's sub-chunks, applied to
D^-1 times the right-hand side.

A grid step takes `kda_block_heads` heads and walks them STAGE BY STAGE
(every head's sums, then every head's A and B, inverses, solve, outputs:
`_solve_and_out` takes lists): the heads' chains of dependent products are
independent, and written side by side the compiler overlaps them; written
head after head it ran them one behind the other (PERF.md section 6, PR
61). Every operand is read before and every result stored after the work.

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
    <= r); where (c) walks `SUB` columns a chunk on the vector unit.
    The solve, the state's path and the grid are (c)'s. `gdn_chunk` is its
    `kda_chunk`; off the chip it is form (a) with g broadcast over the
    channels and q and k repeated.

No option chooses between (a) and (c), and none sets the chunk: `CHUNK` rows
a grid step (PR 58's kernel took 64 and 128 alike on the chip: 3.2 ms a
4,096-row call, 4.6 ms for the whole jitted function with the running sums
XLA then made outside it; PERF.md section 6, PR 58 and PR 61),
`kda_block_heads` is the kernel's shape rule, and (c) runs where it holds
on a TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LANES = 128
SUB = 16
CHUNK = 64
HEADS_A_STEP = 4
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
    (`aligned`) heads of whole lane tiles. As many as divide the heads, up
    to `HEADS_A_STEP`: their chains overlap inside a grid step."""
    if aligned and (d_k % LANES or d_v % LANES):
        return None
    return next(hb for hb in (HEADS_A_STEP, 2, 1) if heads % hb == 0)


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


def _running_sums(g):
    """The running sums over the rows of g [C, W] float32, each row with
    itself: inside blocks of eight rows (a vector register's) by three
    shifted adds, then the blocks' totals carried one behind the other, so
    that a sum stays a few roundings from the sequential one (a shifted add
    over the whole chunk is log2(C) roundings at the size of the largest
    sum apart from its neighbour's, and the neighbours' difference is what
    is used)."""
    from jax.experimental.pallas import tpu as pltpu
    row = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0)
    step = 1
    while step < 8:
        g = g + jnp.where(row % 8 >= step, pltpu.roll(g, step, 0), 0.0)
        step *= 2
    blocks, carry = [g[:8]], g[7:8]
    for t in range(1, g.shape[0] // 8):
        blocks.append(g[8 * t:8 * t + 8] + carry)
        carry = carry + g[8 * t + 7:8 * t + 8]
    return jnp.concatenate(blocks, axis=0)


def _block_inverses(lows, eye, chunk: int):
    """D^-1 for every head of a grid step: `lows` the heads' strictly lower
    matrices [C, C]; returns each head's block diagonal inverse [C, C]. The
    unit lower triangular diagonal blocks of `SUB` rows are inverted exactly
    in float32 on the vector unit by forward substitution, right-looking:
    once row m of a block's inverse is whole, every later row i sheds L[i,
    m] times it. One step of EVERY block of every head at a time: the
    blocks' chains of `SUB` - 1 dependent steps are independent."""
    subs = chunk // SUB
    bases = [i * SUB for _ in lows for i in range(subs)]
    own = [low[i * SUB:(i + 1) * SUB] for low in lows for i in range(subs)]
    xs = [eye[base:base + SUB] for base in bases]             # [SUB, C]
    for m in range(SUB - 1):
        xs = [x - rows[:, base + m:base + m + 1] * x[m:m + 1]
              for x, rows, base in zip(xs, own, bases)]
    return [jnp.concatenate(xs[j * subs:(j + 1) * subs], axis=0)
            for j in range(len(lows))]


def _solve_and_out(kf, qf, big_g, beta, low, b_mat, v_ref, o_ref, state_ref,
                   *, d_v: int, chunk: int, dtype):
    """What both chunk kernels do once A (as `low` = tril(Diag(beta) A, -1))
    and B (`b_mat`, lower with its diagonal) are made: the diagonal blocks'
    inverses, the state's part, the block solve, the chunk's outputs into
    `o_ref` and the state behind its last row into `state_ref`. Every
    argument but the refs a LIST over the heads of a grid step, and every
    stage written for all of them before the next; nothing is stored
    before the last head's last product. `big_g` [C, d_k] (a decay a
    channel) or [C, 1] (a decay a head): the running sums."""
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    subs = chunk // SUB
    n = range(len(kf))
    v = [v_ref[0, :, j * d_v:(j + 1) * d_v] for j in n]
    state = [state_ref[0, j] for j in n]
    x_d = _block_inverses(
        low, jnp.where(row == col, 1.0, 0.0).astype(F32), chunk)    # D^-1
    # ---- the state's part, float32 -------------------------------------
    e_g = [jnp.exp(big_g[j]) for j in n]                     # <= 1
    from_state = [_dot(jnp.concatenate([kf[j] * e_g[j], qf[j] * e_g[j]],
                                       axis=0),
                       state[j], ((1,), (0,)), F32, HIGHEST) for j in n]
    rhs = [beta[j] * (v[j].astype(F32) - from_state[j][:chunk]) for j in n]
    # ---- the solve: T = D (I + N), N = D^-1 L_off -----------------------
    u = [_dot(x_d[j], rhs[j], ((1,), (0,)), dtype) for j in n]
    if subs > 1:
        off = row // SUB != col // SUB
        n1 = [_dot(x_d[j], jnp.where(off, low[j], 0.0), ((1,), (0,)), dtype)
              for j in n]
        u = [u[j] - _dot(n1[j], u[j], ((1,), (0,)), dtype) for j in n]
        power = 2
        while power < subs:          # (I + N^2)(I + N^4)..
            n1 = [_dot(n1[j], n1[j], ((1,), (0,)), dtype) for j in n]
            u = [u[j] + _dot(n1[j], u[j], ((1,), (0,)), dtype) for j in n]
            power *= 2
    # ---- out -------------------------------------------------------------
    o = [from_state[j][chunk:] + _dot(b_mat[j], u[j], ((1,), (0,)), dtype)
         for j in n]
    new = []
    for j in n:
        end = big_g[j][chunk - 1:chunk]                      # [1, d_k | 1]
        k_end = kf[j] * jnp.exp(end - big_g[j])              # <= 1
        if big_g[j].shape[1] == 1:
            # ONE decay a head: e^{G_C} along the lanes, picked out of the
            # column's broadcast by its row (Mosaic broadcasts a [1, 1] in
            # one direction only, and folds two broadcasts into one)
            wide = jnp.broadcast_to(e_g[j], (chunk, state[j].shape[1]))
            last = jax.lax.broadcasted_iota(
                jnp.int32, wide.shape, 0) == chunk - 1
            decay = jnp.sum(jnp.where(last, wide, 0.0), axis=0,
                            keepdims=True)
        else:
            decay = jnp.exp(end).reshape(-1, 1)              # [d_k, 1]
        new.append(decay * state[j]
                   + _dot(k_end, u[j], ((0,), (0,)), dtype))
    for j in n:
        o_ref[0, :, j * d_v:(j + 1) * d_v] = o[j].astype(o_ref.dtype)
        state_ref[0, j] = new[j]


def _rows_of(t, picks, span: int):
    """[len(picks) * span, W]: row picks[b] of t under the b-th block of
    `span` rows."""
    return jnp.concatenate([jnp.broadcast_to(t[p:p + 1], (span, t.shape[1]))
                            for p in picks], axis=0)


def _between_sub_chunks(qf, kf, big_g, chunk: int, dtype):
    """A and B between the sub-chunks of a chunk, by halves: at each level
    the rows of a block's second half against the columns of its first, G*
    the sum at the first half's last row: k_r e^{G_r - G*} against k_i
    e^{G* - G_i}, both factors <= 1 and ONE exponential (e^{-|G - G*|}: the
    first half's rows lie above G*, the second's below). [C, C] each, zero
    inside a sub-chunk and above the diagonal."""
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    a = jnp.zeros((chunk, chunk), F32)
    b = jnp.zeros((chunk, chunk), F32)
    half = SUB
    while half < chunk:
        span = 2 * half
        ref = _rows_of(big_g, range(half - 1, chunk, span), span)
        scale = jnp.exp(-jnp.abs(big_g - ref))
        ks = kf * scale
        both = _dot(jnp.concatenate([qf * scale, ks], axis=0), ks,
                    ((1,), (1,)), dtype)                     # [2C, C]
        at = (row // span == col // span) & (row % span >= half) \
            & (col % span < half)
        a = jnp.where(at, both[chunk:], a)
        b = jnp.where(at, both[:chunk], b)
        half = span
    return a, b


def _inside_sub_chunks(qf, kf, big_g, chunk: int):
    """A and B inside the sub-chunks, float32 on the vector unit: the rows
    of EVERY sub-chunk against their own sub-chunk's j-th, `SUB` columns a
    chunk. The exponent is the difference itself, <= 0 where it is kept (at
    and under the diagonal) and clamped there above it. The columns of a
    sub-chunk's second half meet only that half's rows (the first half's
    lie above the diagonal). [C, C] each, only the sub-chunks' own blocks
    written; what is above the diagonal there is the caller's to drop."""
    subs, half = chunk // SUB, SUB // 2

    def columns(g_s, k_s, q_s, first: int):
        """Columns first .. first + half - 1 of every sub-chunk against
        the rows given: `span` rows a sub-chunk."""
        span = g_s.shape[0] // subs
        lane = jax.lax.broadcasted_iota(jnp.int32, (subs * span, chunk), 1)
        a = b = jnp.zeros((subs * span, chunk), F32)
        for jj in range(first, first + half):
            picks = range(jj, chunk, SUB)
            w = jnp.exp(jnp.minimum(
                g_s - _rows_of(big_g, picks, span), 0.0)) \
                * _rows_of(kf, picks, span)
            at = lane % SUB == jj
            a = jnp.where(at, jnp.sum(k_s * w, axis=1, keepdims=True), a)
            b = jnp.where(at, jnp.sum(q_s * w, axis=1, keepdims=True), b)
        return a, b

    def second(t):      # the second halves of the sub-chunks, [C / 2, W]
        return jnp.concatenate([t[i * SUB + half:(i + 1) * SUB]
                                for i in range(subs)], axis=0)
    early = columns(big_g, kf, qf, 0)
    late = columns(second(big_g), second(kf), second(qf), half)
    # the two write different columns: a second half's rows are their sum
    return tuple(jnp.concatenate([
        part for i in range(subs) for part in (
            whole[i * SUB:i * SUB + half],
            whole[i * SUB + half:(i + 1) * SUB]
            + lower[i * half:(i + 1) * half])], axis=0)
        for whole, lower in zip(early, late))


def _chunk_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, h0_ref, o_ref,
                  state_ref, *, heads: int, d_k: int, d_v: int, chunk: int):
    from jax.experimental import pallas as pl
    dtype = q_ref.dtype
    n = range(heads)

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = h0_ref[...]

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    own = row // SUB == col // SUB
    sums = _running_sums(g_ref[0])                  # every head's at once
    qf = [q_ref[0, :, j * d_k:(j + 1) * d_k].astype(F32) for j in n]
    kf = [k_ref[0, :, j * d_k:(j + 1) * d_k].astype(F32) for j in n]
    big_g = [sums[:, j * d_k:(j + 1) * d_k] for j in n]      # [C, d_k]
    beta = [beta_ref[0, 0, :, j:j + 1] for j in n]           # [C, 1]
    # ---- A and B, every exponent a difference <= 0 -----------------------
    off = [_between_sub_chunks(qf[j], kf[j], big_g[j], chunk, dtype)
           for j in n]
    ins = [_inside_sub_chunks(qf[j], kf[j], big_g[j], chunk) for j in n]
    low = [jnp.where(row > col,
                     beta[j] * jnp.where(own, ins[j][0], off[j][0]), 0.0)
           for j in n]
    b_mat = [jnp.where(row >= col, jnp.where(own, ins[j][1], off[j][1]), 0.0)
             for j in n]
    _solve_and_out(kf, qf, big_g, beta, low, b_mat, v_ref, o_ref, state_ref,
                   d_v=d_v, chunk=chunk, dtype=dtype)


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
            v.reshape(batch, total, heads * d_v),
            g.astype(F32).reshape(batch, total, heads * d_k), cols,
            h0.astype(F32))
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
    n = range(heads)

    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = h0_ref[...]

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    eye = jnp.where(row == col, 1.0, 0.0).astype(F32)
    sums = _running_sums(g_ref[0, 0])               # every head's at once
    # a key head's rows, K K^T and Q K^T, made once for the heads over it
    rows = [(q_ref[0, :, jk * d_k:(jk + 1) * d_k],
             k_ref[0, :, jk * d_k:(jk + 1) * d_k]) for jk in range(key_heads)]
    kk = [_dot(k_j, k_j, ((1,), (1,)), dtype) for _, k_j in rows]  # [C, C]
    qk = [_dot(q_j, k_j, ((1,), (1,)), dtype) for q_j, k_j in rows]
    of = [j * key_heads // heads for j in n]
    qf = [rows[jk][0].astype(F32) for jk in of]
    kf = [rows[jk][1].astype(F32) for jk in of]
    big_g = [sums[:, j:j + 1] for j in n]                    # [C, 1] sums
    beta = [beta_ref[0, 0, :, j:j + 1] for j in n]           # [C, 1]
    # G along the columns: the identity picks each row's own sum; then
    # e^{G_r - G_i}: <= 1 wherever it is kept (i <= r)
    decay = [jnp.exp(jnp.minimum(
        big_g[j] - jnp.sum(eye * big_g[j], axis=0, keepdims=True), 0.0))
        for j in n]
    low = [jnp.where(row > col, beta[j] * (kk[of[j]] * decay[j]), 0.0)
           for j in n]
    b_mat = [jnp.where(row >= col, qk[of[j]] * decay[j], 0.0) for j in n]
    _solve_and_out(kf, qf, big_g, beta, low, b_mat, v_ref, o_ref, state_ref,
                   d_v=d_v, chunk=chunk, dtype=dtype)


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
            v.reshape(batch, total, heads * d_v), cols(g.astype(F32)),
            cols(beta.astype(F32)), h0.astype(F32))
    return o.reshape(batch, total, heads, d_v)[:, :rows], last
