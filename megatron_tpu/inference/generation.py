"""Autoregressive generation engine with KV cache.

TPU-native equivalent of the reference's generation stack
(ref: megatron/text_generation/generation.py:89-285
`generate_tokens_probs_and_return_on_first_stage`, forward_step.py:17-204
InferenceParams/ForwardStep, beam_utils.py). Structural mapping:

- *InferenceParams KV dict* -> the functional `KVCache` pytree
  (models/attention.py) stacked over layers, threaded through `lax.scan`.
- *Incremental context growth* (the reference re-runs the model on
  tokens[prev:cur] per step) -> one PREFILL pass over the padded prompts,
  then a jitted per-token decode loop. Shapes are static (max_len fixed at
  trace time): no recompilation per request length bucket.
- *Early termination* (done-flag broadcast, generation.py:260-263) -> the
  loop still runs to max_len under jit (static bound) but finished rows keep
  emitting pad via the done mask — same outputs, no host sync per token.
- *Per-step last-stage sample + broadcast to first stage*
  (generation.py:179-263, communication.py:111) -> nothing: single program,
  GSPMD owns placement.
- *Scoring path* (generation.py:20-86) -> `score_tokens` returning per-token
  logprobs.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from megatron_tpu.config import ModelConfig
from megatron_tpu.inference.sampling import sample
from megatron_tpu.models import language_model as lm
from megatron_tpu.models.attention import (ConvKVCache, HybridKVCache,
                                           KVCache, LatentStateCache)
from megatron_tpu.utils.tracing import phase


class SamplingParams(NamedTuple):
    """(ref: api.py:70-102 broadcast_float_list of sampling knobs)"""
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 0.0


KV_CACHE_AXES = ("layers", None, None, "kv_heads", None)

# Generator.generate rounds the prefill length DOWN to this multiple
# (jit-cache bucketing); the serving engine's seeded-determinism burn
# counts the serial path's in-prompt RNG splits from the SAME constant
# (serving/engine.py: _rng_burn on the host, and the length of the
# masked loop in _burned_key, the one compiled call per prefill group
# that makes the burned keys) — change it in one place only.
PREFILL_BUCKET = 16


def kv_region_cap(cfg: ModelConfig, max_len: int,
                  prefill_len=None) -> int:
    """Token capacity of one sequence's KV region — THE single source
    of the rolling-cap decision. `init_kv_caches` allocates this many
    positions per row, and `serving.kv_pool.slot_nbytes` sizes pools
    from the same number, so the two can never disagree.

    With cfg.sliding_window < max_len the region rolls (holds only the
    last W positions) when the prefill can land in the W-slot buffer:
    the flash impl computes prefill outputs from the raw k/v, and a
    dot-impl prefill that FITS the window overwrites nothing. A
    dot-impl prompt longer than the window keeps the full-length
    region (correct, just not memory-bounded)."""
    if cfg.sliding_window is not None and (
            cfg.attention_impl == "flash"
            or (prefill_len is not None
                and prefill_len <= cfg.sliding_window)):
        return min(max_len, cfg.sliding_window)
    return max_len


def init_kv_caches(cfg: ModelConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16, prefill_len=None,
                   per_slot_offsets: bool = False):
    """Stacked-over-layers KV cache [L, b, max_len, nkv, hd]; for a model
    with latent attention (`cfg.mla`) the latent cache [L, b, max_len, row].

    Under a mesh context the cache is sharded over 'tp' on the kv-head dim
    (and 'pp' on layers) — the TP-sharded serving layout the reference
    reaches with per-rank InferenceParams dicts
    (ref: text_generation_server.py + forward_step.py:17-42). Batch stays
    replicated like the reference's broadcast-to-all-ranks tokens.

    dtype=jnp.int8: quantized cache with per-(token, head) scales — decode
    streams the whole cache every step, so this halves the dominant HBM
    stream at long context AND the residency (a 7B 32k bf16 cache alone
    outgrows a v5e).

    With cfg.sliding_window < max_len the cache is a ROLLING buffer of
    exactly `sliding_window` slots (Mistral's rolling-buffer serving):
    banded attention never reads past the window, so memory is O(W)
    regardless of stream length — attention_apply writes position % W
    and masks by the slot->position map.

    per_slot_offsets=True allocates PER-ROW offsets [L, batch] instead of
    the shared per-layer scalar [L]: the continuous-batching engine's
    slot-grid layout (serving/kv_pool.py), where every batch row is an
    independent request at its own sequence position."""
    from megatron_tpu.parallel.sharding import constrain
    if cfg.window_layer_period:
        # window and full layers in one stack: rings beside whole regions
        # (models/attention.py::HybridKVCache)
        return HybridKVCache.create(cfg, batch, max_len, dtype,
                                    per_slot_offsets=per_slot_offsets)
    if cfg.state_layers:
        # keys and values (or, under MLA, latent rows) for the attention
        # layers alone, the convolutions' (and the scans') state beside them
        # (models/attention.py::ConvKVCache, LatentStateCache)
        kind = LatentStateCache if cfg.mla else ConvKVCache
        return kind.create(cfg, batch, max_len, dtype,
                           per_slot_offsets=per_slot_offsets)
    # rolling-cap decision single-sourced in kv_region_cap (the serving
    # pool's slot_nbytes sizes from the same helper)
    max_len = kv_region_cap(cfg, max_len, prefill_len)
    if cfg.mla:
        # one latent row a token a layer (models/mla.py), no head axis
        from megatron_tpu.models.mla import LatentKVCache
        return LatentKVCache.create(cfg.num_layers, batch, max_len,
                                    cfg.kv_row_width, dtype,
                                    per_slot_offsets=per_slot_offsets)
    caches = KVCache.create(cfg.num_layers, batch, max_len, cfg.num_kv_heads,
                            cfg.kv_channels, dtype,
                            per_slot_offsets=per_slot_offsets)
    return jax.tree.map(
        lambda a: constrain(a, KV_CACHE_AXES) if a.ndim == 5 else a, caches)


def prefill_chunk(params, tokens, caches, cfg: ModelConfig, *, rope,
                  last_idx, next_offset, adapters=None):
    """Forward one [1, s] prompt chunk through a batch-1 cache at the
    cache's CURRENT offset and return (caches, last_logits_row).

    Offset 0 is the classic whole-prompt prefill; offset > 0 is the
    continuation form the serving engine's prefix cache and chunked
    prefill rely on — a multi-token append whose causal mask starts at
    the cache offset (models/attention.py generalizes the decode
    masking to q-len > 1; the flash impl routes offset > 0 through the
    cached dot path via its lax.cond). `last_idx` (traced) picks the
    logits row of the chunk's last REAL token.

    `next_offset` (traced) is the REAL token count after this chunk:
    the attention write advances the offset by the full padded chunk
    length, so a bucket-padded chunk would leave the cache pointing
    past its pad garbage and the NEXT chunk would append at the wrong
    positions. Resetting to the real count makes the next chunk's
    write start right after the real tokens, overwriting the pads
    write-before-read — the same invariant bucketed prefill +
    insert_prefill already rely on for the final pads."""
    if isinstance(caches, HybridKVCache):
        # a ring takes no padding row
        caches = caches._replace(
            live_end=jnp.asarray(next_offset, jnp.int32))
    if isinstance(caches, (ConvKVCache, LatentStateCache)):
        # a state is left as it stood after the chunk's last real row; the
        # chunk starts where every attention layer's offset stands
        caches = caches._replace(live_rows=jnp.asarray(
            next_offset, jnp.int32) - caches.offset[0])
    logits, caches = lm.model_forward(
        params, tokens, cfg, kv_caches=caches, rope=rope,
        logits_dtype=jnp.float32, adapters=adapters,
        logits_rows=jnp.asarray(last_idx, jnp.int32)[None])
    last = logits[0, 0]
    caches = caches._replace(offset=jnp.full_like(
        caches.offset, jnp.asarray(next_offset, jnp.int32)))
    return caches, last


def verify_tokens(params, tokens, caches, cfg: ModelConfig, *, rope,
                  lengths, max_len: int, adapters=None):
    """Forward a [slots, w]-token window through the slot-grid cache at
    per-row offsets `lengths` and return (logits [slots, w, Vp], caches).

    The speculative-decode verify primitive (serving/engine.py
    `--speculative_k`): `prefill_chunk`'s continuation form generalized
    from batch-1/scalar-offset to the whole grid with vector offsets —
    row i's w tokens append at positions lengths[i]..lengths[i]+w-1,
    each query causally masked from its row's own offset
    (models/attention.py grid-batched multi-token append). Rows parked
    at the capacity clamp write nothing past max_len-1 (the scatter
    drops out-of-region indices) and their rope positions clamp to the
    table — garbage logits for garbage rows, discarded by the caller's
    accept mask, never an OOB read/write. The caller owns the offset
    bookkeeping: committed length after acceptance is a REWIND of the
    window (lengths + accepted + 1 <= lengths + w), and rejected
    positions' KV is overwritten write-before-read by the next
    dispatch, the same invariant bucket-padded prefill relies on.

    `caches` may be the contiguous slot-grid KVCache (the classic
    view) OR a block-native BlockKVCache (models/attention.py —
    serving's `--block_native_attn`): the offset broadcast and the
    per-row positions below are layout-agnostic, and attention_apply
    dispatches the window through the Pallas block-map kernel in the
    latter case — speculative verify keeps ONE trace either way."""
    w = tokens.shape[1]
    L = caches.offset.shape[0]
    caches = caches._replace(offset=jnp.broadcast_to(
        lengths[None, :], (L, lengths.shape[0])).astype(jnp.int32))
    positions = jnp.minimum(lengths[:, None] + jnp.arange(w)[None, :],
                            jnp.int32(max_len - 1))
    logits, caches = lm.model_forward(params, tokens, cfg,
                                      kv_caches=caches,
                                      position_ids=positions, rope=rope,
                                      logits_dtype=jnp.float32,
                                      adapters=adapters)
    return logits, caches


def _decode_fn(params, tokens, lengths, rng, *, cfg: ModelConfig,
               max_len: int, min_prompt: int, sp: SamplingParams,
               eos_id: int, pad_id: int, rope, kv_dtype=jnp.bfloat16):
    """tokens: [b, max_len] prompts right-padded; lengths: [b] prompt lens.
    `min_prompt` is static (host-computed): the prefill length.
    Returns (tokens [b, max_len], logprobs [b, max_len])."""
    b = tokens.shape[0]

    caches = init_kv_caches(cfg, b, max_len, dtype=kv_dtype,
                            prefill_len=min_prompt)

    # PREFILL on the common prefix [0, min_prompt) — mirrors the reference
    # starting generation at the min prompt length and re-using prompt tokens
    # for the longer rows (ref: generation.py:179-199)
    prefill = tokens[:, :min_prompt]
    logits, caches = lm.model_forward(params, prefill, cfg, kv_caches=caches,
                                      rope=rope, logits_dtype=jnp.float32)

    def step(carry, pos):
        tokens, caches, last_logits, rng, done = carry
        rng, r = jax.random.split(rng)
        sampled = sample(r, last_logits, top_k=sp.top_k, top_p=sp.top_p,
                         temperature=sp.temperature,
                         vocab_size=cfg.vocab_size)
        # rows still inside their prompt keep their prompt token
        # (ref: generation.py:210-214 "context tokens are kept")
        in_prompt = pos < lengths
        prompt_tok = jax.lax.dynamic_index_in_dim(tokens, pos, axis=1,
                                                  keepdims=False)
        cur = jnp.where(in_prompt, prompt_tok, sampled)
        cur = jnp.where(done, pad_id, cur)
        tokens = jax.lax.dynamic_update_index_in_dim(tokens, cur, pos, axis=1)
        logprob = jax.nn.log_softmax(last_logits, axis=-1)
        lp = jnp.take_along_axis(logprob, cur[:, None], axis=-1)[:, 0]
        done = done | ((cur == eos_id) & ~in_prompt)
        logits, caches = lm.model_forward(
            params, cur[:, None], cfg, kv_caches=caches, rope=rope,
            logits_dtype=jnp.float32)
        return (tokens, caches, logits[:, 0], rng, done), lp

    done0 = jnp.zeros((b,), bool)
    (tokens, _, _, _, done), lps = jax.lax.scan(
        step, (tokens, caches, logits[:, -1], rng, done0),
        min_prompt + jnp.arange(max_len - min_prompt))
    logprobs = jnp.zeros((b, max_len), jnp.float32)
    logprobs = jax.lax.dynamic_update_slice_in_dim(
        logprobs, lps.T, min_prompt, axis=1)
    return tokens, logprobs


class Generator:
    """Jit-cached generation engine. One compile per (batch, max_len) bucket
    (the reference instead pays a fresh CUDA graph per request shape).

    `mesh`: serve a sharded model in place — params consume their
    tp/pp-sharded layout via in_shardings (no re-layout on every call), the
    KV cache shards over 'tp' on kv-heads, logits shard over 'tp' on vocab.
    The reference's equivalent is the 8-GPU TP text_generation_server with
    broadcast tokens (ref: megatron/text_generation_server.py)."""

    @phase("generator")
    def __init__(self, params, cfg: ModelConfig, eos_id: int,
                 pad_id: Optional[int] = None, mesh=None,
                 kv_cache_dtype=jnp.bfloat16, expert_axis: str = "tp"):
        self.params = params
        self.cfg = cfg
        self.eos_id = eos_id
        self.pad_id = pad_id if pad_id is not None else eos_id
        self.rope = lm.make_rope(cfg, max_len=cfg.max_position_embeddings)
        self.mesh = mesh
        # jnp.int8: quantized KV cache (see init_kv_caches) — halves the
        # decode-dominant cache stream and residency at ~0.4% k/v error
        self.kv_cache_dtype = kv_cache_dtype
        self._decode = {}
        self._rules = None
        self._param_sh = None
        if mesh is not None:
            from megatron_tpu.ops.quantized import quantize_axes
            from megatron_tpu.parallel import sharding as shd
            # expert_axis mirrors ParallelConfig.expert_axis: a model
            # trained with dp-sharded expert banks must serve with the
            # same 'experts' mapping or the bank gets resharded
            self._rules = shd.make_logical_rules(False,
                                                 expert_axis=expert_axis)
            # int8-quantized weights (ops/quantized.quantize_weights)
            # restructure the params tree — align the axes tree with it
            # so in_shardings still match leaf-for-leaf
            self._param_sh = shd.tree_logical_to_sharding(
                mesh, quantize_axes(lm.model_axes(cfg), params),
                self._rules)

        def _score_fn(params, tokens):
            logits, _ = lm.model_forward(params, tokens, self.cfg,
                                         rope=self.rope,
                                         logits_dtype=jnp.float32)
            lp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
            return jnp.take_along_axis(
                lp, tokens[:, 1:, None], axis=-1)[..., 0]

        # one cached jit; retraces only on new (batch, len) shapes
        self._score_fn = self._jit(_score_fn, n_array_args=1)

    def _jit(self, fn, n_array_args: int, donate_argnums=()):
        """jit with the mesh treatment: params consumed in their sharded
        layout, activation ctx active during trace. The `None` in_shardings
        entries mean 'inherit the argument's own sharding' (host numpy
        inputs land replicated, which is the broadcast-tokens serving
        layout; a pre-sharded array would be consumed as-is).

        `donate_argnums`: buffer donation for persistently-resident state
        (the serving engine's KV pool — without donation every decode
        step would copy the whole pool; ignored on backends without
        aliasing support, e.g. CPU)."""
        if self.mesh is None:
            return jax.jit(fn, donate_argnums=donate_argnums)
        from megatron_tpu.parallel import sharding as shd
        mesh, rules = self.mesh, self._rules

        def fn_ctx(*args, **kwargs):
            with shd.activation_shardings(mesh, rules):
                return fn(*args, **kwargs)

        return jax.jit(fn_ctx,
                       in_shardings=(self._param_sh,) + (None,) * n_array_args,
                       donate_argnums=donate_argnums)

    def _get_decode(self, max_len: int, min_prompt: int,
                    sp: SamplingParams):
        key = (max_len, min_prompt, sp)
        if key not in self._decode:
            self._decode[key] = self._jit(functools.partial(
                _decode_fn, cfg=self.cfg, max_len=max_len,
                min_prompt=min_prompt, sp=sp,
                eos_id=self.eos_id, pad_id=self.pad_id, rope=self.rope,
                kv_dtype=self.kv_cache_dtype),
                n_array_args=3)
        return self._decode[key]

    def generate(self, prompts: list[list[int]], max_new_tokens: int,
                 sampling: SamplingParams = SamplingParams(),
                 seed: int = 0):
        """prompts: list of token id lists. Returns (tokens, lengths,
        logprobs) as numpy, one row per prompt
        (ref: generation.py:89-285)."""
        b = len(prompts)
        lengths = np.array([len(p) for p in prompts], np.int32)
        max_len = int(lengths.max()) + max_new_tokens
        max_pos = self.cfg.max_position_embeddings
        if max_len > max_pos:
            raise ValueError(
                f"prompt ({int(lengths.max())}) + max_new_tokens "
                f"({max_new_tokens}) = {max_len} exceeds "
                f"max_position_embeddings={max_pos}; positions past the RoPE "
                "table would silently clamp")
        # bucket shapes so the jit cache actually hits across request sizes:
        # max_len rounds UP to 64, prefill length DOWN to PREFILL_BUCKET
        max_len = min(-(-max_len // 64) * 64, max_pos)
        min_prompt = max(
            (int(lengths.min()) // PREFILL_BUCKET) * PREFILL_BUCKET, 1)
        toks = np.full((b, max_len), self.pad_id, np.int32)
        for i, p in enumerate(prompts):
            toks[i, :len(p)] = p
        fn = self._get_decode(max_len, min_prompt, sampling)
        tokens, logprobs = fn(self.params, jnp.asarray(toks),
                              jnp.asarray(lengths),
                              jax.random.PRNGKey(seed))
        tokens = np.asarray(tokens)
        logprobs = np.asarray(logprobs)
        out_lens = []
        for i in range(b):
            # the decode ran to the BUCKETED max_len; the caller asked for at
            # most lengths[i] + max_new_tokens
            requested = int(lengths[i]) + max_new_tokens
            row = tokens[i, lengths[i]:requested]
            hits = np.where(row == self.eos_id)[0]
            end = int(lengths[i]) + (int(hits[0]) + 1 if len(hits)
                                     else requested - int(lengths[i]))
            out_lens.append(end)
        return tokens, np.asarray(out_lens, np.int32), logprobs

    def score(self, token_rows: list[list[int]]):
        """Per-token logprobs of given sequences (ref: generation.py:20-86
        score_and_return_on_first_stage)."""
        b = len(token_rows)
        lengths = np.array([len(t) for t in token_rows], np.int32)
        max_len = int(lengths.max())
        toks = np.full((b, max_len), self.pad_id, np.int32)
        for i, t in enumerate(token_rows):
            toks[i, :len(t)] = t
        return np.asarray(self._score_fn(self.params, jnp.asarray(toks)))


def beam_search(generator: Generator, prompt: list[int], beam_width: int,
                max_new_tokens: int, length_penalty: float = 1.0):
    """Beam search decode (ref: generation.py:288-415 + beam_utils.py:19-64).

    Jit-friendly formulation: all `beam_width` hypotheses run as one batch;
    each step expands to beam_width^2 candidates and keeps the top
    beam_width by cumulative logprob (length-penalized at finalization,
    matching the reference's scoring)."""
    cfg = generator.cfg
    assert not cfg.window_layer_period and not cfg.state_layers, (
        "beam_search reorders one k/v cache by beam: a stack of window and "
        "full layers (window_layer_period) and a pattern with convolution "
        "layers (layer_types) are refused")
    eos = generator.eos_id
    params = generator.params
    rope = generator.rope
    prompt_len = len(prompt)
    max_len = prompt_len + max_new_tokens
    bw = beam_width

    toks = np.full((bw, max_len), generator.pad_id, np.int32)
    toks[:, :prompt_len] = prompt

    def prefill(params, tokens):
        caches = init_kv_caches(cfg, bw, max_len,
                                dtype=generator.kv_cache_dtype,
                                prefill_len=prompt_len)
        logits, caches = lm.model_forward(
            params, tokens[:, :prompt_len], cfg, kv_caches=caches, rope=rope,
            logits_dtype=jnp.float32)
        return logits[:, -1], caches

    def step(params, tokens, caches, scores, done, pos, last_logits):
        lp = jax.nn.log_softmax(last_logits, axis=-1)  # [bw, V]
        V = lp.shape[-1]
        iota = jnp.arange(V)
        lp = jnp.where(iota[None, :] < cfg.vocab_size, lp, -jnp.inf)
        # finished beams only extend with pad at no cost
        cand = jnp.where(done[:, None], -jnp.inf, lp) + scores[:, None]
        cand = cand.reshape(-1)
        # keep finished beams alive as single candidates
        keep_done = jnp.where(done, scores, -jnp.inf)
        all_scores = jnp.concatenate([cand, keep_done])
        top = jax.lax.top_k(all_scores, bw)[1]
        is_kept_done = top >= bw * V
        parent = jnp.where(is_kept_done, top - bw * V, top // V)
        token = jnp.where(is_kept_done, generator.pad_id, top % V)
        scores = all_scores[top]
        tokens = tokens[parent]
        caches = KVCache(
            k=caches.k[:, parent], v=caches.v[:, parent],
            offset=caches.offset,
            k_scale=(None if caches.k_scale is None
                     else caches.k_scale[:, parent]),
            v_scale=(None if caches.v_scale is None
                     else caches.v_scale[:, parent]))
        tokens = jax.lax.dynamic_update_index_in_dim(
            tokens, token.astype(jnp.int32), pos, axis=1)
        done = done[parent] | (token == eos)
        logits, caches = lm.model_forward(
            params, tokens[:, pos][:, None], cfg, kv_caches=caches,
            rope=rope, logits_dtype=jnp.float32)
        return tokens, caches, scores, done, logits[:, 0]

    # route through the generator's mesh-aware jit so TP-sharded serving
    # applies to beam decode too (same treatment as generate/score)
    prefill = generator._jit(prefill, n_array_args=1)
    step = generator._jit(step, n_array_args=6)

    last_logits, caches = prefill(params, jnp.asarray(toks))
    tokens = jnp.asarray(toks)
    scores = jnp.asarray([0.0] + [-1e9] * (bw - 1), jnp.float32)
    done = jnp.zeros((bw,), bool)
    for pos in range(prompt_len, max_len):
        tokens, caches, scores, done, last_logits = step(
            params, tokens, caches, scores, done, pos, last_logits)
        if bool(done.all()):
            break
    # length-penalized final ranking (ref: beam_utils.py:19-64)
    tokens = np.asarray(tokens)
    out_len = np.full((bw,), max_len)
    for i in range(bw):
        hits = np.where(tokens[i, prompt_len:] == eos)[0]
        if len(hits):
            out_len[i] = prompt_len + hits[0] + 1
    gen_len = np.maximum(out_len - prompt_len, 1)
    final = np.asarray(scores) / (gen_len ** length_penalty)
    order = np.argsort(-final)
    return tokens[order], out_len[order], final[order]
