"""A decode step's attention over the contiguous slot pool stops at each
slot's live length (PR 36): `attention_apply`'s per-slot branch hands the
stacked `KVCache` pool to the block kernel under the identity chain
(`ops/block_attention_pallas.py`).

- which pools take it is one rule of shapes (`pool_block_rows`): a table;
- an engine whose pool the rule admits, kernel forced (interpreted: the CPU
  is not a backend the rule picks), gives the dot path's tokens and
  log-probabilities over admissions, frees and re-admissions;
- `kv_blocks_read` / `kv_blocks_held` count what the lengths the device was
  handed say.

The kernel's numerics: tests/test_block_attention_pallas.py; that the chip's
compiler reads the pool in place: tests/test_tpu_compile.py.
"""
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from megatron_tpu.config import ModelConfig, ServingConfig
from megatron_tpu.inference import Generator
from megatron_tpu.inference.generation import init_kv_caches
from megatron_tpu.models import language_model as lm
from megatron_tpu.ops import block_attention_pallas as bap
from megatron_tpu.serving import SamplingOptions, ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BF16, F32, INT8 = jnp.bfloat16, jnp.float32, jnp.int8
OLMOE = (4, 24, 4096, 16, 128)      # the cell's pool, as KVCache.k holds it


def cell_pool(config, traffic):
    """(shape, dtype) of `KVCache.k` as the cell's engine builds it."""
    from megatron_tpu.arguments import parse_cli
    with open(os.path.join(ROOT, "benchmark/configs", config + ".json")) as f:
        cli = json.load(f)["cli"]
    with open(os.path.join(ROOT, "benchmark/traffic", traffic + ".json")) as f:
        serving = json.load(f)["serving"]
    cfg, _ = parse_cli([*cli, "--bf16"], n_devices=1)
    k = jax.eval_shape(lambda: init_kv_caches(
        cfg.model, serving["num_slots"], serving["max_len"],
        per_slot_offsets=True)).k
    return k.shape, k.dtype


DECODE = dict(queries=(24, 1, 16), per_slot=True, window=False, mesh=False,
              backend="tpu")


@pytest.mark.parametrize("shape,dtype,how,rows", [
    # OLMoE-1B-7B's pool: 4,096 B a row, 512 KiB blocks of 128 rows
    (OLMOE, BF16, {}, 128),
    # a rolling pool, or any sliding window: the mask is not the kernel's
    (OLMOE, BF16, {"window": True}, None),
    # a step at a scalar offset (a prefill, a chunk)
    (OLMOE, BF16, {"per_slot": False}, None),
    # a verify window of 5 queries a row; a grid of 128-token prompts at
    # per-slot offsets, whose scores against a block do not fit beside it
    (OLMOE, BF16, {"queries": (24, 5, 16)}, 128),
    (OLMOE, BF16, {"queries": (24, 128, 16)}, None),
    # under a mesh that shards heads or rows
    (OLMOE, BF16, {"mesh": True}, None),
    # not on a TPU, and nobody asked
    (OLMOE, BF16, {"backend": "cpu"}, None),
    (OLMOE, BF16, {"backend": None}, None),
    # one block covers the region: nothing inside a slot to skip
    ((4, 24, 128, 16, 128), BF16, {}, None),
    # Falcon-7B's: 1 kv head of 64, 128 B a row
    ((11, 64, 2048, 1, 64), BF16, {}, None),
    # kv heads that do not fill the tile's rows: 8 in bf16, 16 in int8
    ((32, 8, 8192, 8, 128), BF16, {}, None),
    (OLMOE, INT8, {}, None),
    # ... and that do: 8 in float32 (2 KiB a row), 32 in int8
    ((32, 8, 8192, 8, 128), F32, {}, 128),
    ((2, 8, 4096, 32, 128), INT8, {}, 128),
    # B halves until it divides the region
    ((4, 8, 4096 + 64, 16, 128), BF16, {}, 64),
], ids=["olmoe", "window", "chunk", "verify", "prompts", "mesh", "cpu", "backend_unasked",
        "one_block", "falcon", "gqa8_bf16", "mha16_int8", "gqa8_f32",
        "mha32_int8", "odd_region"])
def test_which_pools_read_through_the_kernel(shape, dtype, how, rows):
    assert bap.pool_block_rows(shape, dtype, **{**DECODE, **how}) == rows


@pytest.mark.parametrize("config,traffic,rows", [
    ("olmoe-1b-7b-4l", "chat-4k-open-loop", 128),
    ("falcon-7b-11l", "chat-open-loop", None)])
def test_the_cells_fall_where_the_issue_says(config, traffic, rows):
    """The two serving cells whose engines hold a `KVCache` pool, from the
    benchmark's own files: OLMoE's decode step takes the kernel, Falcon's
    keeps the parent's program."""
    shape, dtype = cell_pool(config, traffic)
    if rows:
        assert (shape, dtype) == (OLMOE, BF16)
    assert bap.pool_block_rows(shape, dtype, **DECODE) == rows


# -- in an engine ----------------------------------------------------------
# 8 kv heads of 128 in float32: 4 KiB a row, as OLMoE's 16 in bf16; with the
# block cut to 64 KiB for the test, a 64-position region is 4 blocks of 16
MAX_LEN, ROWS, SLOTS = 64, 16, 3
# (prompt length, new tokens): lengths on both sides of every block edge,
# more requests than slots, so rows are freed and taken again
REQUESTS = [(3, 20), (15, 6), (16, 18), (30, 5), (33, 30), (47, 12), (9, 40)]


def tiny_generator():
    cfg = ModelConfig(num_layers=2, hidden_size=64, num_attention_heads=8,
                      num_kv_heads=8, kv_channels=128, vocab_size=96,
                      seq_length=MAX_LEN, make_vocab_size_divisible_by=32,
                      compute_dtype="float32").derived()
    params = lm.model_init(jax.random.PRNGKey(0), cfg)
    return Generator(params, cfg, eos_id=-1, pad_id=0,
                     kv_cache_dtype=jnp.float32)


@pytest.fixture
def forced(monkeypatch):
    """The rule answers as on a TPU; the kernel itself still asks the
    backend and is interpreted."""
    monkeypatch.setattr(bap, "BLOCK_BYTES", ROWS * 8 * 128 * 4)
    monkeypatch.setattr(bap, "pool_block_rows", functools.partial(
        bap.pool_block_rows, backend="tpu"))
    traced, kernel = [], bap.contiguous_pool_attention
    monkeypatch.setattr(
        bap, "contiguous_pool_attention",
        lambda q, *a, **kw: (traced.append(q.shape), kernel(q, *a, **kw))[1])
    return traced


def serve(interval, spy=None):
    """The REQUESTS through a fresh engine: [(tokens, logprobs)], its
    metrics, and the block it reads its pool in."""
    greedy = SamplingOptions(temperature=0.0)
    with ServingEngine(tiny_generator(), ServingConfig(
            num_slots=SLOTS, max_queue=16, max_len=MAX_LEN,
            decode_sync_interval=interval), start=False) as eng:
        if spy is not None:
            decode = eng._decode
            eng._decode = lambda *a: (spy(a[4]), decode(*a))[1]
        eng._thread.start()
        rs = np.random.RandomState(1)
        reqs = [eng.submit(rs.randint(1, 90, p).tolist(), n, greedy, seed=i)
                for i, (p, n) in enumerate(REQUESTS)]
        out = [r.result(timeout=600) for r in reqs]
        assert eng._decode_traces == 1
        return out, eng.metrics.snapshot(), eng._attend_rows


@pytest.fixture(scope="module")
def dot_path():
    return {k: serve(k) for k in (1, 4)}


@pytest.mark.parametrize("interval", [1, 4])
def test_engine_through_the_kernel_is_the_dot_paths(forced, dot_path,
                                                    interval):
    want, dot_snap, dot_rows = dot_path[interval]
    assert dot_rows == 0
    assert dot_snap["kv_blocks_read"] == dot_snap["kv_blocks_held"] == 0
    got, snap, rows = serve(interval)
    assert rows == ROWS
    # the decode program's one trace (its layers are a scan) took the kernel
    assert forced == [(SLOTS, 1, 8, 128)]
    for (toks, lps), (want_toks, want_lps), (p, n) in zip(got, want,
                                                          REQUESTS):
        assert len(toks) == p + n
        assert toks == want_toks
        np.testing.assert_allclose(lps, want_lps, rtol=0, atol=2e-2)
    assert 0 < snap["kv_blocks_read"] < snap["kv_blocks_held"]


@pytest.mark.parametrize("interval", [1, 4])
def test_the_counters_are_a_hand_count_of_the_lengths(forced, interval):
    """Every dispatched decode step reads, a grid row, the blocks up to the
    length the program was handed for it (a parked row's first), out of the
    `MAX_LEN / ROWS` it holds. The lengths are taken off the device at the
    dispatch, chained steps included; the engine counts from its host copy
    before it dispatches."""
    seen = []
    # a copy: the program may reuse the buffer a view would look into
    _, snap, _ = serve(interval, spy=lambda d: seen.append(np.array(d)))
    nb = MAX_LEN // ROWS
    assert snap["decode_steps"] == len(seen)
    assert snap["kv_blocks_held"] == len(seen) * SLOTS * nb
    assert snap["kv_blocks_read"] == sum(
        int((np.minimum(d // ROWS, nb - 1) + 1).sum()) for d in seen)
    # one request alone for most of its life, three slots: well under half
    assert snap["kv_blocks_read"] < 0.75 * snap["kv_blocks_held"]
