"""Token sampling: temperature / top-k / top-p.

TPU-native port of the reference's sampler
(ref: megatron/text_generation/sampling.py:14-93 `modify_logits_for_top_k/p_
filtering` + `sample`): greedy when top_k==0 and top_p==0 and temperature==0;
otherwise temperature-scaled logits filtered by top-k then top-p. In-place
masking becomes functional `jnp.where`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def top_k_filter(logits, k: int):
    """Keep the k largest logits per row (ref: sampling.py:14-23)."""
    if k <= 0:
        return logits
    kth = jnp.sort(logits, axis=-1)[..., -k][..., None]
    return jnp.where(logits < kth, -jnp.inf, logits)


def top_p_filter(logits, p: float):
    """Nucleus filtering (ref: sampling.py:26-42): drop the tail whose
    cumulative probability exceeds 1-p (keeping at least the top token)."""
    if p <= 0.0 or p >= 1.0:
        return logits
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # a sorted position is kept while the cumulative mass BEFORE it is < p
    keep_sorted = (cum - probs) < p
    min_kept = jnp.min(jnp.where(keep_sorted, sorted_logits, jnp.inf),
                       axis=-1, keepdims=True)
    return jnp.where(logits < min_kept, -jnp.inf, logits)


def sample(rng, logits, *, top_k: int = 0, top_p: float = 0.0,
           temperature: float = 1.0, vocab_size: int | None = None):
    """One sampling step over [batch, vocab] logits
    (ref: sampling.py:45-93). Returns int32 [batch]."""
    logits = logits.astype(jnp.float32)
    if vocab_size is not None and vocab_size < logits.shape[-1]:
        iota = jnp.arange(logits.shape[-1])
        logits = jnp.where(iota < vocab_size, logits, -jnp.inf)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if temperature == 0.0 or (top_k == 1):
        return greedy
    logits = logits / max(temperature, 1e-6)
    logits = top_k_filter(logits, top_k)
    logits = top_p_filter(logits, top_p)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def _top_k_filter_rows(logits, k):
    """top_k_filter with a PER-ROW traced k [b] (0 disables the row)."""
    V = logits.shape[-1]
    srt = jnp.sort(logits, axis=-1)  # ascending
    # sorted[V - k] == sorted[-k], the serial filter's threshold
    idx = jnp.clip(V - jnp.maximum(k, 1), 0, V - 1).astype(jnp.int32)
    kth = jnp.take_along_axis(srt, idx[:, None], axis=-1)
    filtered = jnp.where(logits < kth, -jnp.inf, logits)
    return jnp.where((k > 0)[:, None], filtered, logits)


def _top_p_filter_rows(logits, p):
    """top_p_filter with a PER-ROW traced p [b] (<=0 or >=1 disables)."""
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_sorted = (cum - probs) < p[:, None]
    min_kept = jnp.min(jnp.where(keep_sorted, sorted_logits, jnp.inf),
                       axis=-1, keepdims=True)
    filtered = jnp.where(logits < min_kept, -jnp.inf, logits)
    return jnp.where(((p > 0.0) & (p < 1.0))[:, None], filtered, logits)


def rows_need_filter(temperature, top_k, top_p):
    """Per row [b]: does top-k or top-p change what the row samples? Not
    for a greedy row (its token is the argmax whatever is filtered) and not
    with both knobs at their disabled values. Plain operators only, so the
    engine asks the same question of its host (numpy) mirrors."""
    greedy = (temperature == 0.0) | (top_k == 1)
    return ~greedy & ((top_k > 1) | ((top_p > 0.0) & (top_p < 1.0)))


def _filter_rows(x, temperature, top_k, top_p):
    """top-k then top-p over the [b, vocab] grid, under ONE `lax.cond`:
    both full-vocabulary sorts run only in a step where some row
    `rows_need_filter`. With none, each filter's closing
    `jnp.where(False, filtered, x)` would hand `x` back row for row, which
    is what the false branch returns: bit-identical, without the sorts.
    The predicate is data (the per-row knobs), so it stays one program."""
    return jax.lax.cond(
        jnp.any(rows_need_filter(temperature, top_k, top_p)),
        lambda x: _top_p_filter_rows(_top_k_filter_rows(x, top_k), top_p),
        lambda x: x, x)


def sample_batched(rngs, logits, *, temperature, top_k, top_p,
                   vocab_size: int | None = None, banned=None,
                   mask=None):
    """One sampling step with PER-ROW keys and sampling params — the
    continuous-batching engine's path (serving/engine.py), where one
    compiled decode step serves slots carrying different requests.

    rngs: [b, 2] uint32 (one PRNG key per row); logits: [b, vocab];
    temperature/top_p: float32 [b]; top_k: int32 [b]. Returns int32 [b].

    Row-for-row it reproduces `sample(rngs[i], logits[i:i+1], ...)`
    bit-exactly: the filters are the same row-wise math with traced
    instead of static knobs (and run only in a step where some row's
    knobs ask for one: `_filter_rows`), and a vmapped `categorical` over
    a [V] row draws the same threefry bits as the serial [1, V] call (the
    counter stream depends only on the key and the element count).

    `banned` (int32 [b], < 0 disables a row): mask ONE token per row
    out of the PROCESSED distribution — i.e. AFTER temperature/top-k/
    top-p, so the result is exactly the renormalized residual
    norm(max(p - q, 0)) of point-mass rejection sampling against draft
    q = delta(banned) (speculative decoding, serving engine). Applied
    post-filter on purpose: masking before top-k would admit a
    replacement token the original distribution filtered out. Greedy
    rows ignore the ban — a greedy rejection already implies
    banned != argmax, so the residual of the argmax point mass IS the
    unchanged argmax. Rows with banned < 0 are bit-identical to the
    banned=None call (the categorical consumes the same key bits).

    `mask` (bool [b, vocab], True = allowed): the SET generalization
    of `banned` — grammar-constrained decoding's per-slot legal-token
    bitmask (serving/structured.py). Applied at the same post-filter
    seam and composing with `banned` (an accepted residual carry must
    also be grammar-legal). Unlike `banned`, greedy rows OBEY the
    mask: the constrained greedy answer is the argmax over legal
    tokens, not the unconstrained argmax. A row whose mask admits NO
    candidate returns the sentinel -1 (for greedy AND stochastic rows)
    instead of sampling from a renormalized-empty distribution — the
    engine fails that request typed (GrammarDeadEndError -> 422). An
    all-True mask row is bit-identical to mask=None (the masking
    `where` is the identity and the categorical consumes the same key
    bits), so free rows ride the same trace unchanged."""
    logits = logits.astype(jnp.float32)
    if vocab_size is not None and vocab_size < logits.shape[-1]:
        iota = jnp.arange(logits.shape[-1])
        logits = jnp.where(iota < vocab_size, logits, -jnp.inf)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    greedy_rows = (temperature == 0.0) | (top_k == 1)
    x = logits / jnp.maximum(temperature, 1e-6)[:, None]
    x = _filter_rows(x, temperature, top_k, top_p)
    if banned is not None:
        iota = jnp.arange(x.shape[-1])
        x = jnp.where((banned >= 0)[:, None]
                      & (iota[None, :] == banned[:, None]), -jnp.inf, x)
    if mask is not None:
        greedy = jnp.argmax(jnp.where(mask, logits, -jnp.inf),
                            axis=-1).astype(jnp.int32)
        x = jnp.where(mask, x, -jnp.inf)
    sampled = jax.vmap(
        lambda r, row: jax.random.categorical(r, row, axis=-1))(rngs, x)
    out = jnp.where(greedy_rows, greedy, sampled).astype(jnp.int32)
    if mask is not None:
        dead = ~jnp.any(mask, axis=-1)
        out = jnp.where(dead, jnp.int32(-1), out)
    return out


def verify_draft_probs(logits, drafts, *, temperature, top_k, top_p,
                       vocab_size: int | None = None, mask=None):
    """Per-(row, position) acceptance inputs for speculative decoding.

    logits: [b, w, vocab] — the verify forward's outputs, position j
    holding the model's distribution for the token draft[:, j] claims;
    drafts: [b, w] int32; temperature/top_p: float32 [b]; top_k:
    int32 [b] (per-ROW knobs, shared across the row's positions).

    Returns (probs [b, w] float32, greedy_targets [b, w] int32):
    `probs[i, j]` is the PROCESSED probability of drafts[i, j] — the
    same temperature/top-k/top-p pipeline `sample_batched` draws from,
    which is what point-mass rejection sampling must accept against
    (accept with probability min(1, p(d)/q(d)) = p(d) for q = delta(d));
    `greedy_targets` is the plain argmax (greedy rows accept by exact
    match). The [b, w] grid folds to [b*w] rows with each row's knobs
    repeated, so the filters are bit-identical to a serial
    one-position-at-a-time verify of the same logits. A window in which
    no row filters skips the sorts (`_filter_rows`); a GREEDY row's
    `probs` then come unfiltered even where it names a top_p, and nobody
    reads them: greedy rows accept on `greedy_targets` alone.

    `mask` (bool [b, w, vocab], True = allowed): grammar-constrained
    rows' per-POSITION legal-token masks (the host steps the FSM along
    the draft chain — serving/structured.py). Masked positions accept
    against the masked renormalized distribution: an illegal draft's
    processed probability is exactly 0 (never accepted, since the
    acceptance uniform lives in [0, 1)), and greedy targets become
    the masked argmax (-1 on a dead position, which never equals a
    real draft). All-True positions are bit-identical to mask=None —
    free rows share the trace unchanged."""
    b, w, V = logits.shape
    x = logits.astype(jnp.float32).reshape(b * w, V)
    if vocab_size is not None and vocab_size < V:
        iota = jnp.arange(V)
        x = jnp.where(iota < vocab_size, x, -jnp.inf)
    if mask is not None:
        m = mask.reshape(b * w, V)
        greedy_targets = jnp.argmax(jnp.where(m, x, -jnp.inf),
                                    axis=-1).astype(jnp.int32)
        greedy_targets = jnp.where(jnp.any(m, axis=-1), greedy_targets,
                                   jnp.int32(-1))
    else:
        greedy_targets = jnp.argmax(x, axis=-1).astype(jnp.int32)
    temp = jnp.repeat(temperature, w)
    x = x / jnp.maximum(temp, 1e-6)[:, None]
    x = _filter_rows(x, temp, jnp.repeat(top_k, w), jnp.repeat(top_p, w))
    if mask is not None:
        x = jnp.where(mask.reshape(b * w, V), x, -jnp.inf)
    p = jax.nn.softmax(x, axis=-1)
    probs = jnp.take_along_axis(
        p, drafts.reshape(b * w, 1).astype(jnp.int32), axis=-1)[:, 0]
    return probs.reshape(b, w), greedy_targets.reshape(b, w)
