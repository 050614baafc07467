"""command-a-plus-05-2026 on the normal path (PR 33): window and full
attention in one scanned stack over a cache of rings and whole regions
(`attention.HybridKVCache`), chunked prefill over both kinds, one chip's share
of an expert layer, the shared experts averaged, LayerNorm without a bias.
Each against the float32 reference (`benchmark/reference/command_a_plus.py`:
no cache, no ring, a band mask over the whole sequence). Logits and
log-probabilities, never tokens. Float32 throughout, so the tolerances are
those of sums taken in another order: 1e-4 on logits of magnitude ~5."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import command_a_plus as reference
from megatron_tpu.config import (MODEL_PRESETS, MegatronConfig, ModelConfig,
                                 ServingConfig)
from megatron_tpu.inference import Generator
from megatron_tpu.inference.generation import init_kv_caches, prefill_chunk
from megatron_tpu.models import language_model as lm
from megatron_tpu.models.attention import HybridKVCache, attention_apply
from megatron_tpu.models.moe import moe_apply
from megatron_tpu.serving import SamplingOptions, ServingEngine
from megatron_tpu.serving.kv_pool import SlotKVPool, slot_nbytes

WINDOW = 16                 # the tiny preset's sliding_window
TOL = 1e-4


def _model(impl="dot", **over):
    """The tiny preset as a chip's share: experts 2 to 5 of a router of 8."""
    cfg = dataclasses.replace(
        MODEL_PRESETS["command-a-plus-tiny"](), compute_dtype="float32",
        attention_impl=impl, num_experts=4, moe_first_expert=2, **over)
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    # logits of magnitude ~5, and tokens that differ (module docstring of
    # benchmark/drivers/serve_open_loop_command_a.py on the tied head)
    params["embedding"]["word_embeddings"] *= 12.0
    return cfg, params


@pytest.fixture(scope="module")
def model():
    return _model()


def _chunked_logits(cfg, params, tokens, chunk, bucket, ring=None):
    """Logits [len(tokens), vocab] of a prompt prefilled by chunks of `chunk`
    (the last one padded up to `bucket`) through a HybridKVCache of one
    sequence, as the engine's `_chunk_fwd` does it. `ring`: rows of the
    window layers' buffers (default: the window)."""
    rope = lm.make_rope(cfg)
    max_len = 128
    caches = init_kv_caches(cfg, 1, max_len, dtype=jnp.float32)
    if ring is not None:
        shape = caches.ring_k.shape[:3] + (ring,) + caches.ring_k.shape[4:]
        caches = caches._replace(ring_k=jnp.zeros(shape, jnp.float32),
                                 ring_v=jnp.zeros(shape, jnp.float32))
    rows, pos = [], 0
    while pos < len(tokens):
        n = min(chunk, len(tokens) - pos)
        padded = min(-(-n // bucket) * bucket, max_len - pos)
        toks = np.zeros((1, padded), np.int32)
        toks[0, :n] = tokens[pos:pos + n]
        caches = caches._replace(live_end=jnp.int32(pos + n))
        out, caches = lm.model_forward(params, jnp.asarray(toks), cfg,
                                       kv_caches=caches, rope=rope)
        caches = caches._replace(
            offset=jnp.full_like(caches.offset, pos + n))
        rows.append(np.asarray(out[0, :n, :cfg.vocab_size]))
        pos += n
    return np.concatenate(rows), caches


@pytest.mark.parametrize("impl", ["dot", "flash"])
def test_chunked_prefill_through_rings_matches_reference(impl):
    """41 tokens, more than two windows of 16, in chunks of 12 (not a
    multiple of either): the second chunk's rows wrap the ring inside the
    chunk, the last chunk is padded from 5 to 8 rows, and the padding must
    not land in a ring."""
    cfg, params = _model(impl)
    tokens = np.random.default_rng(0).integers(1, cfg.vocab_size, 41)
    want = np.asarray(reference.logits(params, jnp.asarray(tokens), cfg))
    got, caches = _chunked_logits(cfg, params, tokens, chunk=12, bucket=4)
    assert isinstance(caches, HybridKVCache)
    assert caches.ring_k.shape == (3, 1, 2, WINDOW, 16)   # heads, then rows
    assert caches.full_k.shape == (1, 1, 2, 128, 16)
    assert np.abs(got - want).max() < TOL
    # one chunk longer than the window: its first rows are written nowhere
    got, _ = _chunked_logits(cfg, params, tokens, chunk=40, bucket=8)
    assert np.abs(got - want).max() < TOL
    # bucket = chunk = window (the benchmark's cell): the last chunk's 9 rows
    # are padded to a whole ring's 16
    got, _ = _chunked_logits(cfg, params, tokens, chunk=WINDOW, bucket=WINDOW)
    assert np.abs(got - want).max() < TOL


def test_rings_equal_whole_regions_under_a_band_mask(model):
    """The same prompt with the window layers' buffers as long as the whole
    sequence (no row is ever overwritten; the window is the mask alone)."""
    cfg, params = model
    tokens = np.random.default_rng(1).integers(1, cfg.vocab_size, 53)
    rings, _ = _chunked_logits(cfg, params, tokens, chunk=12, bucket=4)
    whole, _ = _chunked_logits(cfg, params, tokens, chunk=12, bucket=4,
                               ring=128)
    assert np.abs(rings - whole).max() < 1e-5


@pytest.mark.parametrize("chunk,bucket,chunks", [
    (12, 4, 4), (None, 4, 1), (WINDOW, WINDOW, 3)])
def test_engine_prefill_and_decode_match_reference(model, chunk, bucket,
                                                   chunks):
    """`ServingEngine`: a prompt of 41 (chunked: 12 + 12 + 12 + 5 padded to
    8; one shot in a bucket of 44; or, as the benchmark's cell has it, bucket
    = chunk = window: 16 + 16 + 9 padded to a whole ring's 16 rows) then 8
    tokens decoded through rings and region beside an unrelated request at
    another length."""
    cfg, params = model
    gen = Generator(params, cfg, eos_id=-1, pad_id=0,
                    kv_cache_dtype=jnp.float32)
    serving = ServingConfig(num_slots=3, max_queue=8, max_len=128,
                            prefill_bucket=bucket, prefill_max_batch=2,
                            prefill_chunk=chunk).validate(cfg)
    rng = np.random.default_rng(2)
    with ServingEngine(gen, serving) as eng:
        other = eng.submit(rng.integers(1, cfg.vocab_size, 9).tolist(), 30,
                           SamplingOptions(temperature=1.0), seed=3)
        prompt = rng.integers(1, cfg.vocab_size, 41).tolist()
        req = eng.submit(prompt, 8, SamplingOptions(temperature=0.0), seed=1)
        tokens, _ = req.result(timeout=600)
        other.result(timeout=600)
        snap = eng.metrics.snapshot()
    got = np.asarray(req.gen_logprobs, np.float64)
    want = np.asarray(reference.token_logprobs(
        params, jnp.asarray(tokens), cfg, tail=8), np.float64)
    assert np.abs(got - want).max() < TOL
    assert req.prefill_chunks == chunks
    # the pool's own count: 3 rings of 16 rows + 1 region of 128, k and v of
    # 2 heads x 16 channels, float32
    row = 2 * 2 * 16 * 4
    assert snap["kv_bytes_per_slot"] == (3 * WINDOW + 128) * row
    assert snap["kv_ring_bytes"] == 3 * 3 * WINDOW * row
    assert snap["kv_full_bytes"] == 3 * 128 * row
    assert snap["kv_pool_bytes"] == 3 * snap["kv_bytes_per_slot"]
    assert snap["kv_bytes_per_token"] == row        # one full layer


def test_serial_generate_matches_engine_reference(model):
    """`Generator.generate` (the serial route: scalar offsets, a batch of
    two) through the same cache."""
    cfg, params = model
    gen = Generator(params, cfg, eos_id=-1, pad_id=0,
                    kv_cache_dtype=jnp.float32)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist() for n in (37, 21)]
    from megatron_tpu.inference.generation import SamplingParams
    tokens, lengths, logprobs = gen.generate(
        prompts, 6, SamplingParams(temperature=0.0), seed=0)
    for i, p in enumerate(prompts):
        seq = tokens[i, :lengths[i]]
        want = np.asarray(reference.token_logprobs(
            params, jnp.asarray(seq), cfg, tail=6))
        got = logprobs[i, len(p):lengths[i]]
        assert np.abs(got - want).max() < TOL


def test_shares_add_up_to_the_uncut_layer():
    """Eight shares of two experts each of a layer of sixteen: their routed
    parts, with the shared experts counted once, are the uncut layer's
    output, in the program (`moe_apply`) and in the reference alike, and
    both agree."""
    whole = dataclasses.replace(
        MODEL_PRESETS["command-a-plus-tiny"](), compute_dtype="float32",
        num_experts=16, moe_router_experts=16, moe_top_k=4)
    params = lm.model_init(jax.random.PRNGKey(1), whole)
    mlp = params["transformer"]["mlp"]
    u = jax.random.normal(jax.random.PRNGKey(2), (1, 24, whole.hidden_size))
    layer = 1
    one = jax.tree.map(lambda a: a[layer], mlp)
    uncut, _ = moe_apply(one, u, whole)
    routed, shared, _ = reference.experts(mlp, u[0], whole, layer)
    assert np.abs(np.asarray(uncut[0]) - (routed + shared)).max() < 1e-5
    total_prog, total_ref = 0.0, 0.0
    for c in range(8):
        cfg = dataclasses.replace(whole, num_experts=2,
                                  moe_first_expert=2 * c)
        held = {k: (v[:, 2 * c:2 * c + 2] if k in ("w1", "w2") else v)
                for k, v in mlp.items()}
        y, _ = moe_apply(jax.tree.map(lambda a: a[layer], held), u, cfg)
        total_prog = total_prog + (np.asarray(y[0]) - np.asarray(shared))
        r, s, _ = reference.experts(held, u[0], cfg, layer)
        assert np.abs(np.asarray(s) - np.asarray(shared)).max() == 0.0
        total_ref = total_ref + np.asarray(r)
    assert np.abs(total_ref - np.asarray(routed)).max() < 1e-5
    assert np.abs(total_prog + np.asarray(shared)
                  - np.asarray(uncut[0])).max() < 1e-5


def test_rows_of_absent_experts_read_as_zero(monkeypatch):
    """Whatever the grouped product leaves in the rows behind the last group
    (on the chip: whatever the buffer held), a token whose choices are all
    held elsewhere gets the shared experts' part and nothing else."""
    from megatron_tpu.ops import grouped_matmul as gm
    cfg, params = _model()
    plain = gm._plain_grouped_matmul

    def poisoned(lhs, rhs, group_sizes):
        out = plain(lhs, rhs, group_sizes)
        behind = jnp.arange(lhs.shape[0])[:, None] >= jnp.sum(group_sizes)
        return jnp.where(behind, jnp.nan, out)
    monkeypatch.setattr(gm, "_plain_grouped_matmul", poisoned)
    one = jax.tree.map(lambda a: a[0], params["transformer"]["mlp"])
    u = jax.random.normal(jax.random.PRNGKey(4), (1, 32, cfg.hidden_size))
    y, _ = moe_apply(one, u, cfg)
    routed, shared, w = reference.experts(params["transformer"]["mlp"], u[0],
                                          cfg, 0)
    assert np.isfinite(np.asarray(y)).all()
    assert np.abs(np.asarray(y[0]) - (routed + shared)).max() < 1e-5
    nowhere = np.asarray(w[:, 2:6].sum(axis=1) == 0)       # none held here
    assert nowhere.any()
    assert np.abs(np.asarray(y[0])[nowhere]
                  - np.asarray(shared)[nowhere]).max() < 1e-6


def test_full_layers_take_no_rotation(model):
    """Another `rope_theta` moves the keys a WINDOW layer writes and leaves
    a FULL layer's as they were."""
    cfg, params = model
    attn = jax.tree.map(lambda a: a[0], params["transformer"]["attention"])
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 8, cfg.hidden_size))
    written = {}
    for theta in (50000.0, 100.0):
        c = dataclasses.replace(cfg, rope_theta=theta)
        rope = lm.make_rope(c)
        for kind, kcfg, layer, kl in (("window", c.window_layers(), 0, 0),
                                      ("full", c.full_layers(), 3, 0)):
            _, cache = attention_apply(
                attn, x, kcfg, rope_cos=rope.cos, rope_sin=rope.sin,
                kv_cache=init_kv_caches(c, 1, 32, dtype=jnp.float32),
                cache_layer=layer, kind_layer=kl)
            written[kind, theta] = np.asarray(
                cache.ring_k if kind == "window" else cache.full_k)
    assert np.abs(written["window", 50000.0]
                  - written["window", 100.0]).max() > 1e-3
    assert np.array_equal(written["full", 50000.0], written["full", 100.0])
    assert np.abs(written["full", 100.0]).max() > 0


def test_pool_byte_counts():
    cfg = MODEL_PRESETS["command-a-plus"]()
    cfg = dataclasses.replace(cfg, num_layers=4, num_experts=16,
                              vocab_size=32768)
    # the cell's: 16 slots x 32,768; a row is 2 x 8 x 128 bf16 = 4,096 B
    assert slot_nbytes(cfg, 32768) == (3 * 4096 + 32768) * 4096 == 184549376
    one_kind = dataclasses.replace(cfg, window_layer_period=0,
                                   sliding_window=None)
    assert slot_nbytes(one_kind, 32768) == 4 * 32768 * 4096 == 536870912
    tiny = MODEL_PRESETS["command-a-plus-tiny"]()
    pool = SlotKVPool(tiny, 5, 64, dtype=jnp.bfloat16)
    row = 2 * 2 * 16 * 2
    assert pool.hybrid and not pool.rolling and pool.cap == 64
    assert pool.bytes_per_slot() == slot_nbytes(tiny, 64) \
        == (3 * WINDOW + 64) * row
    assert pool.ring_nbytes() == 5 * 3 * WINDOW * row
    assert pool.full_nbytes() == 5 * 64 * row
    assert pool.nbytes() == pool.ring_nbytes() + pool.full_nbytes()
    assert pool.bytes_per_token() == row
    # a pool of one kind: no rings, and the regions are all of it
    falcon = SlotKVPool(MODEL_PRESETS["falcon-tiny"](), 2, 64)
    assert falcon.ring_nbytes() == 0
    assert falcon.full_nbytes() == falcon.nbytes() \
        == 2 * falcon.bytes_per_slot()


REFUSED = dict(
    enable_prefix_cache=dict(enable_prefix_cache=True),
    retained_slots=dict(retained_slots=2),
    preemption=dict(preemption=True, priority_levels=2),
    speculative_k=dict(speculative_k=2),
    kv_block_size=dict(kv_block_size=16),
    block_native_attn=dict(block_native_attn=True),
    serving_pp=dict(serving_pp=2),
    serving_tp=dict(serving_tp=2),
    disaggregate_prefill=dict(disaggregate_prefill=True),
    host_kv_bytes=dict(host_kv_bytes=1 << 20),
    adapter_slots=dict(adapter_slots=2),
    kv_dtype=dict(kv_dtype="int8"),
)


@pytest.mark.parametrize("name", sorted(REFUSED))
def test_serving_refusals_by_name(name):
    cfg = MODEL_PRESETS["command-a-plus-tiny"]()
    with pytest.raises(AssertionError, match="window_layer_period=4.*"
                       + name.split("=")[0] + ".*refused"):
        ServingConfig(num_slots=2, max_len=64, **REFUSED[name]).validate(cfg)
    # and what the cell uses is taken
    ServingConfig(num_slots=2, max_len=64, prefill_chunk=16,
                  prefill_bucket=8).validate(cfg)


@pytest.mark.parametrize("change,parallel,match", [
    (dict(num_layers=6), {}, "whole number of periods"),
    (dict(sliding_window=None), {}, "needs sliding_window"),
    (dict(mtp_num_layers=1), {}, "refused with MLA"),
    ({}, dict(tensor_parallel=2), "one device only"),
    ({}, dict(pipeline_parallel=2), "one device only"),
    (dict(attention_impl="ring"), {}, "context-parallel"),
    (dict(moe_first_expert=6), {}, "not among the router's 8"),
    (dict(moe_dispatch="sort", moe_first_expert=1), {}, "dropless"),
    (dict(moe_shared_combination="max"), {}, "moe_shared_combination"),
])
def test_model_refusals_by_name(change, parallel, match):
    from megatron_tpu.config import ParallelConfig
    base = dict(num_experts=4) if "moe_first_expert" in change else {}
    cfg = dataclasses.replace(MODEL_PRESETS["command-a-plus-tiny"](),
                              **base, **change)
    with pytest.raises(AssertionError, match=match):
        MegatronConfig(model=cfg, parallel=ParallelConfig(**parallel)
                       ).validate(2 if parallel else 1)


def test_presets_hold_the_published_sizes():
    cfg = MODEL_PRESETS["command-a-plus"]()
    assert isinstance(cfg, ModelConfig)
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_kv_heads, cfg.kv_channels, cfg.ffn_hidden_size) == \
        (32, 4096, 128, 8, 128, 4096)
    assert (cfg.num_experts, cfg.router_experts, cfg.moe_top_k,
            cfg.n_shared_experts, cfg.moe_shared_combination) == \
        (128, 128, 8, 4, "average")
    assert (cfg.window_layer_period, cfg.sliding_window, cfg.rope_theta) == \
        (4, 4096, 50000.0)
    assert cfg.norm_type == "layernorm_nobias" and cfg.parallel_attn \
        and cfg.tie_embed_logits
    MegatronConfig(model=cfg).validate(1)
    MegatronConfig(model=MODEL_PRESETS["command-a-plus-tiny"]()).validate(1)
    # the kinds: the window and the rotation are the kind's
    assert cfg.window_layers().sliding_window == 4096 \
        and cfg.window_layers().use_rotary_emb
    assert cfg.full_layers().sliding_window is None \
        and not cfg.full_layers().use_rotary_emb
    # one layer's parameters: a norm with a scale alone
    tiny = MODEL_PRESETS["command-a-plus-tiny"]()
    shapes = jax.eval_shape(lambda: lm.model_init(jax.random.PRNGKey(0), tiny))
    assert set(shapes["transformer"]["input_norm"]) == {"scale"}
    assert set(shapes["final_norm"]) == {"scale"}
    assert "post_attn_norm" not in shapes["transformer"]
    assert shapes["transformer"]["mlp"]["router"].shape == (4, 64, 8)


def test_training_loss_runs_through_the_period_scan(model):
    """`loss_fn` (no cache: the band mask on the window layers) has a finite
    gradient in every parameter, and its logits are the reference's."""
    cfg, params = model
    tokens = jnp.asarray(np.random.default_rng(5).integers(
        1, cfg.vocab_size, (2, 40)))
    rope = lm.make_rope(cfg)
    loss, grads = jax.value_and_grad(
        lambda p: lm.loss_fn(p, tokens, cfg, rope=rope)[0]
        if isinstance(lm.loss_fn(p, tokens, cfg, rope=rope), tuple)
        else lm.loss_fn(p, tokens, cfg, rope=rope))(params)
    assert np.isfinite(float(loss))
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree.leaves(grads))
    out, _ = lm.model_forward(params, tokens[:1, :-1], cfg, rope=rope)
    want = reference.logits(params, tokens[0, :-1], cfg)
    assert np.abs(np.asarray(out[0, :, :cfg.vocab_size])
                  - np.asarray(want)).max() < TOL


@pytest.mark.parametrize("off,start,window", [
    (512, 0, None), (256, 0, None), (512, 200, 300), (512, 512, 512),
    (0, 0, None), (384, 0, 128)])
def test_flash_offset_kernel_matches_dense(off, start, window):
    """`pallas_flash_attention_offset` (interpreted) and the XLA blockwise
    path against plain masked softmax: a chunk of 256 queries at position
    `off` of 768 keys, keys before `start` holding nothing."""
    from megatron_tpu.ops.flash_attention import _blockwise_attention
    from megatron_tpu.ops.flash_attention_pallas import \
        pallas_flash_attention_offset
    rng = np.random.default_rng(0)
    b, sq, sk, nq, nkv, d = 1, 256, 768, 4, 2, 128
    q = jnp.asarray(rng.normal(size=(b, sq, nq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, sk, nkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, sk, nkv, d)), jnp.float32)
    s = jnp.einsum("bsngd,btnd->bngst",
                   q.reshape(b, sq, nkv, nq // nkv, d), k) * d ** -0.5
    qp, kp = off + jnp.arange(sq)[:, None], jnp.arange(sk)[None, :]
    mask = (qp >= kp) & (kp >= start)
    if window:
        mask &= qp - kp < window
    dense = jnp.einsum("bngst,btnd->bsngd",
                       jax.nn.softmax(jnp.where(mask, s, -1e30), -1),
                       v).reshape(b, sq, nq, d)
    xla = _blockwise_attention(q, k, v, causal=True, scale=None,
                               block_kv=128, sliding_window=window,
                               q_offset=off, kv_start=start)
    kernel = pallas_flash_attention_offset(
        q, k, v, jnp.int32(off), jnp.int32(start), sliding_window=window,
        block_q=128, block_kv=128, interpret=True)
    assert float(jnp.abs(xla - dense).max()) < 1e-5
    assert float(jnp.abs(kernel - dense).max()) < 1e-5


def test_chunks_go_to_the_oldest_prefill(model, monkeypatch):
    """One chunk an iteration, to the prefill admitted first (the order of
    work every chunked engine had before this model: PERF.md section 6, PR
    33, on what another order would take)."""
    cfg, params = model
    gen = Generator(params, cfg, eos_id=-1, pad_id=0)
    eng = ServingEngine(gen, ServingConfig(num_slots=2, max_len=64,
                                           prefill_chunk=16), start=False)
    served = []
    monkeypatch.setattr(eng, "_prefill_one_chunk",
                        lambda st: served.append(st) or 0)
    try:
        import types
        long, short = (types.SimpleNamespace(req=types.SimpleNamespace(id=i))
                       for i in (1, 2))
        eng._prefilling = [long, short]
        eng._advance_prefill()
        eng._advance_prefill()
        assert served == [long, long]
    finally:
        eng._prefilling = []
        eng.close()


def test_rotary_tables_end_at_the_engines_positions(model):
    """On a pool of rings and regions the programs close over `max_len` rows
    of the rotary tables, not the model's whole context; any other pool
    keeps the generator's tables (and so the programs it had)."""
    cfg, params = model
    gen = Generator(params, cfg, eos_id=-1, pad_id=0)
    assert gen.rope.cos.shape[0] == cfg.max_position_embeddings > 64
    eng = ServingEngine(gen, ServingConfig(num_slots=2, max_len=64),
                        start=False)
    try:
        assert eng._rope.cos.shape[0] == eng._rope.sin.shape[0] == 64
        assert np.array_equal(eng._rope.cos, gen.rope.cos[:64])
    finally:
        eng.close()
    falcon = MODEL_PRESETS["falcon-tiny"]()
    fgen = Generator(lm.model_init(jax.random.PRNGKey(0), falcon), falcon,
                     eos_id=-1, pad_id=0)
    eng = ServingEngine(fgen, ServingConfig(num_slots=2, max_len=64),
                        start=False)
    try:
        assert eng._rope is fgen.rope
    finally:
        eng.close()


# Two periods: everything above runs a model of ONE period, where the scan
# over periods is a single trip; here `period * n_win + j` (the kind's own
# layer), the parameters viewed [periods, P, ...] and the banks' layer are
# read at a traced period 1 as well.

@pytest.fixture(scope="module")
def two_periods():
    return _model(num_layers=8)


@pytest.mark.parametrize("impl", ["dot", "flash"])
def test_two_periods_chunked_prefill_matches_reference(impl):
    cfg, params = _model(impl, num_layers=8)
    tokens = np.random.default_rng(6).integers(1, cfg.vocab_size, 41)
    want = np.asarray(reference.logits(params, jnp.asarray(tokens), cfg))
    got, caches = _chunked_logits(cfg, params, tokens, chunk=12, bucket=4)
    assert caches.ring_k.shape == (6, 1, 2, WINDOW, 16)
    assert caches.full_k.shape == (2, 1, 2, 128, 16)
    assert np.array_equal(np.asarray(caches.offset), [41] * 8)
    assert np.abs(got - want).max() < TOL
    # every kind's every layer wrote its own buffer: no two alike, none empty
    for stack in (caches.ring_k, caches.full_k):
        flat = np.asarray(stack).reshape(stack.shape[0], -1)
        assert (np.abs(flat).max(axis=1) > 0).all()
        assert len({row.tobytes() for row in flat}) == stack.shape[0]


def test_two_periods_differ_from_one_period_twice(two_periods):
    """The second period runs ITS OWN parameters: with the first period's
    copied over them the logits move."""
    cfg, params = two_periods
    tokens = jnp.asarray(np.random.default_rng(7).integers(
        1, cfg.vocab_size, 20))
    rope = lm.make_rope(cfg)
    out, _ = lm.model_forward(params, tokens[None], cfg, rope=rope)
    twice = dict(params, transformer=jax.tree.map(
        lambda a: jnp.concatenate([a[:4], a[:4]]), params["transformer"]))
    out2, _ = lm.model_forward(twice, tokens[None], cfg, rope=rope)
    assert np.abs(np.asarray(out - out2)).max() > 1e-2
    want = reference.logits(twice, tokens, cfg)
    assert np.abs(np.asarray(out2[0, :, :cfg.vocab_size])
                  - np.asarray(want)).max() < TOL


@pytest.mark.parametrize("chunk", [12, None])
def test_two_periods_engine_prefill_and_decode(two_periods, chunk):
    """`ServingEngine` over a pool of 6 rings and 2 regions a slot: a prompt
    of 41 (chunked or one shot) then 8 tokens decoded, beside another
    request at another length."""
    cfg, params = two_periods
    gen = Generator(params, cfg, eos_id=-1, pad_id=0,
                    kv_cache_dtype=jnp.float32)
    serving = ServingConfig(num_slots=3, max_queue=8, max_len=128,
                            prefill_bucket=4, prefill_max_batch=2,
                            prefill_chunk=chunk).validate(cfg)
    rng = np.random.default_rng(8)
    with ServingEngine(gen, serving) as eng:
        assert eng.pool.caches.ring_k.shape == (6, 3, 2, WINDOW, 16)
        assert eng.pool.caches.full_k.shape == (2, 3, 2, 128, 16)
        other = eng.submit(rng.integers(1, cfg.vocab_size, 9).tolist(), 30,
                           SamplingOptions(temperature=1.0), seed=3)
        prompt = rng.integers(1, cfg.vocab_size, 41).tolist()
        req = eng.submit(prompt, 8, SamplingOptions(temperature=0.0), seed=1)
        tokens, _ = req.result(timeout=600)
        other.result(timeout=600)
        snap = eng.metrics.snapshot()
    got = np.asarray(req.gen_logprobs, np.float64)
    want = np.asarray(reference.token_logprobs(
        params, jnp.asarray(tokens), cfg, tail=8), np.float64)
    assert np.abs(got - want).max() < TOL
    row = 2 * 2 * 16 * 4
    assert snap["kv_bytes_per_slot"] == (6 * WINDOW + 2 * 128) * row
    assert snap["kv_bytes_per_token"] == 2 * row        # two full layers
