"""The main path's Pallas kernels compile for a described TPU v5e.

Interpret mode (the other kernel tests) checks numerics and cannot see
what the chip's compiler refuses: a lowering that does not exist, a
block that is not aligned to the (8, 128) tiling. The TPU compiler is
installed here and compiles for a chip that is described, not attached
(`on-chip-measurement` guide, section 2) — so these tests compile each
kernel at Falcon-7B widths (hidden 4544, 71 query heads of 64 over one
kv head, sequence 2048) for `v5e:2x2`, about two seconds each. Nothing
runs: a compile that passes says nothing about results or times.

The topology is described inside a module-scoped fixture and nowhere
else: only one process may load the TPU's library, so nothing here
touches `jax.experimental.topologies` while a module is imported.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

SEQ, HIDDEN, NQ, NKV, HD = 2048, 4544, 71, 1, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no compiler for it here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # an entry compiled for a described chip cannot be read back here:
    # keep the persistent cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on_chip(one_chip, tree):
    """`tree`'s shapes and dtypes as arguments that lie on the described
    chip."""
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip), tree)


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text, "the Pallas kernel is not in the HLO"


def test_flash_attention_fwd_bwd(one_chip):
    from megatron_tpu.ops.flash_attention import flash_attention

    def S(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        # use_pallas=True: the backend question the dispatch asks sees
        # the CPU here, so the test steers it
        return flash_attention(q, k, v, causal=True, use_pallas=True) \
            .astype(jnp.float32).sum()
    q, kv = S((1, SEQ, NQ, HD)), S((1, SEQ, NKV, HD))
    _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), q, kv, kv)


@pytest.mark.parametrize("case", ["mla_256", "segments", "dropout",
                                  "float32"])
def test_flash_attention_forward_fits_vmem(one_chip, case):
    """The forward's blocks of 1,024 (PR 42) against the 16 MiB of VMEM a
    kernel may take, where a block is largest: MLA's expanded heads padded
    to 256 channels (JoyAI's 32 heads at 4,096 rows), packed documents,
    float32 rows; and a forward with dropout, which keeps blocks of 512
    because at 1,024 the compiler refuses it."""
    from megatron_tpu.ops.flash_attention import flash_attention
    n, d = (32, 256) if case == "mla_256" else (8, 128)
    dtype = jnp.float32 if case == "float32" else jnp.bfloat16
    rows = jax.ShapeDtypeStruct((1, 4096, n, d), dtype, sharding=one_chip)
    seg = jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=one_chip)

    def fn(q, k, v, seg):
        return flash_attention(
            q, k, v, causal=True, use_pallas=True,
            segment_ids=seg if case == "segments" else None,
            dropout_rate=0.1 if case == "dropout" else 0.0,
            dropout_rng=jax.random.PRNGKey(0) if case == "dropout" else None)
    _compile(fn, rows, rows, rows, seg)


@pytest.mark.parametrize("keys,window", [(8192, 4096), (32768, None)])
def test_flash_attention_at_an_offset(one_chip, keys, window):
    """A serving chunk that continues a cache (PR 33): 4,096 queries of 128
    heads over 8 kv heads of 128 against a window layer's ring + chunk
    (8,192 keys, the band) and a full layer's region (32,768 keys), keys
    heads-major, the offset and the first live key prefetched scalars."""
    from megatron_tpu.ops.flash_attention import flash_attention

    def S(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(q, k, v, off, start):
        return flash_attention(q, k, v, causal=True, use_pallas=True,
                               sliding_window=window, q_offset=off,
                               kv_start=start, kv_heads_major=True)
    kv = S((1, 8, keys, 128))
    _compile(fn, S((1, 4096, 128, 128)), kv, kv, S((), jnp.int32),
             S((), jnp.int32))


@pytest.mark.parametrize("batch,rows", [(1, 2048), (2, 512)])
def test_selective_scan_at_jamba_widths(one_chip, batch, rows):
    """The scan's kernel (PR 47) at AI21-Jamba2-3B's widths: a 2,048-row
    chunk and a two-prompt bucket of 512 rows of 5,120 channels, a state of
    [16, 5120] float32 a sequence in and out, bf16 rows, float32 step
    sizes; and one kv head's region of 32,768 rows through the flash kernel
    at an offset, as the model's two attention layers read it."""
    from megatron_tpu.ops.flash_attention import flash_attention
    from megatron_tpu.ops.selective_scan import _ssm_selective_scan

    def S(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    f32 = jnp.float32
    by_rows = (batch, rows, 5120)
    _compile(_ssm_selective_scan, S(by_rows), S(by_rows, f32),
             S((16, 5120), f32), S((batch, rows, 16), f32),
             S((batch, rows, 16), f32), S((5120,), f32), S(by_rows),
             S((batch, 16, 5120), f32))

    def attend(q, k, v, off):
        return flash_attention(q, k, v, causal=True, use_pallas=True,
                               q_offset=off, kv_heads_major=True)
    kv = S((batch, 1, 32768, 128))
    _compile(attend, S((batch, rows, 20, 128)), kv, kv, S((), jnp.int32))


def test_flash_attention_under_tp_mesh(topo):
    """XLA cannot partition a Mosaic call: under a tensor-parallel mesh
    the kernel has to sit in flash_attention's shard_map (Falcon-40B's
    128 query heads over 8 kv heads, four ways)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from megatron_tpu.ops.flash_attention import flash_attention
    from megatron_tpu.parallel import sharding as shd
    from megatron_tpu.parallel.mesh import MESH_AXES
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 1, 1, 4), MESH_AXES)
    heads = NamedSharding(mesh, P(None, None, "tp", None))

    def loss(q, k, v):
        with shd.activation_shardings(mesh, shd.make_logical_rules(True)):
            return flash_attention(q, k, v, causal=True, use_pallas=True) \
                .astype(jnp.float32).sum()
    q = jax.ShapeDtypeStruct((1, SEQ, 128, HD), jnp.bfloat16, sharding=heads)
    kv = jax.ShapeDtypeStruct((1, SEQ, 8, HD), jnp.bfloat16, sharding=heads)
    _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), q, kv, kv)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("w", [1, 5], ids=["decode", "verify"])
def test_block_native_attention(one_chip, w, quant):
    from megatron_tpu.ops.block_attention_pallas import \
        block_native_attention
    slots, nb, B = 8, SEQ // 16, 16
    T = slots * nb + 1

    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    kv = S((T, B, NKV, HD), jnp.int8 if quant else jnp.bfloat16)
    scales = (S((T, B, NKV, 1), jnp.float32),) * 2 if quant else ()

    def fn(q, k, v, bmap, lengths, *sc):
        ks, vs = sc if sc else (None, None)
        return block_native_attention(
            q, k, v, bmap, lengths, scale=HD ** -0.5,
            k_scale=ks, v_scale=vs, interpret=False)
    _compile(fn, S((slots, w, NQ, HD), jnp.bfloat16), kv, kv,
             S((slots, nb), jnp.int32), S((slots,), jnp.int32), *scales)


@pytest.mark.parametrize("norm", ["rmsnorm", "layernorm"])
def test_fused_norm_fwd_bwd(one_chip, norm):
    from megatron_tpu.ops.fused_norms import (pallas_layernorm,
                                              pallas_rmsnorm)
    x = jax.ShapeDtypeStruct((1, SEQ, HIDDEN), jnp.bfloat16,
                             sharding=one_chip)
    s = jax.ShapeDtypeStruct((HIDDEN,), jnp.float32, sharding=one_chip)

    def loss(x, s, b):
        y = (pallas_rmsnorm(x, s, 1e-5, False) if norm == "rmsnorm"
             else pallas_layernorm(x, s, b, 1e-5, False))
        return y.astype(jnp.float32).sum()
    argnums = (0, 1) if norm == "rmsnorm" else (0, 1, 2)
    _compile(jax.value_and_grad(loss, argnums=argnums), x, s, s)


@pytest.mark.parametrize("batch,rows", [(1, 2048), (1, 512)])
def test_ssd_chunk_scan_at_nemotron_widths(one_chip, batch, rows):
    """The chunked scan's kernel (PR 52) at NVIDIA-Nemotron-3-Super's
    widths: a 2,048-row chunk and a 512-row bucket of 128 heads of 64
    channels over 8 groups of B and C of 128, chunks of 128 rows, a state of
    [128, 64, 128] float32 a sequence in and out, bf16 rows, float32 step
    sizes: a group's sixteen heads a grid step, 64-channel slices of a
    1,024-lane block."""
    from megatron_tpu.ops.ssd_scan import _ssd_chunk_scan, ssd_block_heads

    def S(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    f32 = jnp.float32
    assert ssd_block_heads(rows, 128, 64, 8, 128, 128) == 16
    text = jax.jit(functools.partial(_ssd_chunk_scan, chunk=128)).lower(
        S((batch, rows, 128, 64)), S((batch, rows, 128), f32),
        S((128,), f32), S((batch, rows, 8, 128)), S((batch, rows, 8, 128)),
        S((128,), f32), S((batch, 128, 64, 128), f32)).compile().as_text()
    # the trace finds the kernel by this name (benchmark/ssd_roofline.py)
    assert any("%_ssd_chunk_scan" in line and "tpu_custom_call" in line
               for line in text.splitlines())


@pytest.mark.parametrize("rows,k,n", [
    (1408, 1024, 2688),     # a decode step: 64 slots x 22 choices, w1
    (1408, 2688, 1024),     # ... and w2: k over its tile, 640 rows behind
    (45056, 1024, 2688),    # a 2,048-row chunk's (token, choice) rows
    (45056, 2688, 1024)])
def test_grouped_matmul_at_nemotron_latent_widths(one_chip, rows, k, n):
    """The dropless experts' grouped product over banks whose rows are the
    LATENT's 1,024 and not the hidden size: 128 held experts of width 2,688
    stacked over 5 expert layers in bf16, read where they lie at the
    layer's index. 2,688 is 21 lane tiles: the first product's columns
    overhang its 2,048-column tile, the second's contraction its 2,048-row
    tile (`ops/grouped_matmul.py::_tiling`, `past_k`)."""
    from megatron_tpu.ops.grouped_matmul import grouped_matmul

    def S(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def product(lhs, bank, layer, sizes):
        return grouped_matmul(lhs, bank, sizes, layer=layer, use_kernel=True)
    text = jax.jit(product).lower(
        S((rows, k)), S((5, 128, k, n)), S((), jnp.int32),
        S((128,), jnp.int32)).compile().as_text()
    assert any("%_moe_grouped_matmul." in line and "tpu_custom_call" in line
               for line in text.splitlines())


@pytest.mark.parametrize("rows,k,n,grad", [
    (640, 2048, 2048, False),      # a decode step of 80 slots, first product
    (640, 1024, 2048, False),      # ... and the second
    (8192, 2048, 2048, True),      # a 1024-token prefill; training's backward
    (49152, 2048, 2048, False),    # the largest prefill, 2 x 3072 tokens
    (49152, 1024, 2048, False)])
def test_grouped_matmul_at_olmoe_widths(one_chip, rows, k, n, grad):
    """The dropless experts' grouped product (`ops/grouped_matmul.py`: its
    own forward kernel, megablox's two for the backward pass) at
    OLMoE-1B-7B's widths: 64 experts, hidden 2048, expert width 1024. A tile
    that does not fit the kernel's 16 MiB of fast memory is refused here and
    nowhere on the CPU."""
    from megatron_tpu.ops.grouped_matmul import grouped_matmul

    def S(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def product(lhs, rhs, sizes):
        return grouped_matmul(lhs, rhs, sizes, use_kernel=True)

    def loss(lhs, rhs, sizes):
        return product(lhs, rhs, sizes).astype(jnp.float32).sum()
    fn = jax.grad(loss, argnums=(0, 1)) if grad else product
    text = jax.jit(fn).lower(S((rows, k)), S((64, k, n)),
                             S((64,), jnp.int32)).compile().as_text()
    # the trace finds the kernels by these names (benchmark/moe_roofline.py)
    names = ["_moe_grouped_matmul_dlhs", "_moe_grouped_matmul_drhs"] \
        if grad else ["%_moe_grouped_matmul."]
    for name in names:
        assert any(name in line and "tpu_custom_call" in line
                   for line in text.splitlines()), name


@pytest.mark.parametrize("k,n", [(2048, 2048), (1024, 2048), (4544, 1024)],
                         ids=["w1", "w2", "k_not_a_multiple_of_its_tile"])
@pytest.mark.parametrize("rows", [256, 8192, 49152])
def test_grouped_matmul_reads_the_stacked_float32_bank(one_chip, rows, k, n):
    """The same kernel over the bank as a cached program's layer loop hands
    it down: float32, stacked over 4 layers, with the layer's index. Two
    buffers of a float32 block and its rounded copy have to fit the 16 MiB
    (512 columns at k = 2048, not the bf16 bank's 1024), and the stack has
    to reach the kernel as it is, its two leading axes merged and nothing
    moved: no cast, no copy and no slice of it beside the call."""
    from megatron_tpu.ops.grouped_matmul import grouped_matmul

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def product(lhs, bank, layer, sizes):
        return grouped_matmul(lhs, bank, sizes, layer=layer, use_kernel=True)
    text = jax.jit(product).lower(
        S((rows, k), jnp.bfloat16), S((4, 64, k, n), jnp.float32),
        S((), jnp.int32), S((64,), jnp.int32)).compile().as_text()
    calls = [line for line in text.splitlines()
             if "%_moe_grouped_matmul." in line and "tpu_custom_call" in line]
    assert len(calls) == 1 and f"f32[256,{k},{n}]" in calls[0], [
        c[:400] for c in calls]
    assert _bank_shaped(text, {(4, 64, k, n)}) == []


# an instruction's result: `%name = type[dims]{layout} op(`
_RESULT = re.compile(r"^\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\S* ([\w-]+)\(")


def _bank_shaped(text, stacks):
    """The instructions of an HLO text that MAKE an array of a stacked
    bank's shape, of the stack with its leading axes merged or of one layer
    of it, in any type: a cast, a copy, a slice or a fusion of them. A stack
    that is only handed on (a parameter, an element of the loop's state) or
    seen under another shape (a bitcast) is not made."""
    shapes = (set(stacks) | {s[1:] for s in stacks}
              | {(s[0] * s[1],) + s[2:] for s in stacks})
    made = []
    for line in text.splitlines():
        m = _RESULT.match(line)
        if not m:
            continue
        dtype, dims, op = m.groups()
        dims = tuple(int(d) for d in dims.split(",") if d)
        if dims in shapes and op not in ("parameter", "get-tuple-element",
                                         "bitcast"):
            made.append(line.strip()[:160])
    return made


def test_the_check_sees_a_cast_and_a_slice_of_a_bank(one_chip):
    """What the layer loop did before the banks went down whole, in small:
    the checker has to find both."""
    def before(bank, i, x):
        one = jax.lax.dynamic_index_in_dim(bank.astype(jnp.bfloat16), i, 0,
                                           keepdims=False)
        return jnp.einsum("mk,ekn->emn", x, one)
    text = jax.jit(before).lower(
        jax.ShapeDtypeStruct((4, 8, 256, 128), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((16, 256), jnp.bfloat16, sharding=one_chip),
    ).compile().as_text()
    assert _bank_shaped(text, {(4, 8, 256, 128)})


def _olmoe_compiled(one_chip, monkeypatch, positions, cap=256, vocab=4096):
    """A cached forward of OLMoE-1B-7B's widths at depth 4, 24 slots of
    `positions` tokens over a pool of `cap` positions a slot (donated, as
    the engine donates it), compiled for the chip; and its configuration."""
    from megatron_tpu.config import olmoe_config
    from megatron_tpu.models import language_model as lm
    from megatron_tpu.inference.generation import init_kv_caches

    cfg = olmoe_config("1b-7b", num_layers=4, compute_dtype="bfloat16",
                       vocab_size=vocab, make_vocab_size_divisible_by=128)
    slots = 24
    # the program asks the backend which product to take; this test
    # compiles for the chip from a CPU process
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    on_chip = functools.partial(_on_chip, one_chip)
    params = on_chip(jax.eval_shape(
        lambda: lm.model_init(jax.random.PRNGKey(0), cfg)))
    caches = on_chip(jax.eval_shape(lambda: init_kv_caches(
        cfg, slots, cap, per_slot_offsets=True)))
    tokens = jax.ShapeDtypeStruct((slots, positions), jnp.int32,
                                  sharding=one_chip)

    def forward(params, tokens, caches):
        return lm.model_forward(params, tokens, cfg, kv_caches=caches)
    return jax.jit(forward, donate_argnums=2).lower(
        params, tokens, caches).compile(), cfg


def _olmoe_program(one_chip, monkeypatch, positions):
    compiled, cfg = _olmoe_compiled(one_chip, monkeypatch, positions)
    return compiled.as_text(), cfg


def _kernel_calls(text, name):
    return [line for line in text.splitlines()
            if line.lstrip().startswith(f"%{name}.")
            and "tpu_custom_call" in line]


def _as_traced(call):
    """A trace's event names each operand's shape where the compiled text
    names the operand; the shapes are in the layout constraints."""
    operands = re.search(r"operand_layout_constraints=\{(.*?\})\}", call)
    return (call.partition("custom-call(")[0] + "custom-call("
            + operands.group(1) + '), custom_call_target="tpu_custom_call"')


def test_decode_program_makes_no_copy_of_an_expert_bank(one_chip, monkeypatch):
    """A decode step of OLMoE-1B-7B's widths at depth 4 through the cache,
    compiled for the chip: the two stacked float32 banks are parameters of
    the program and operands of the kernel inside the layer loop (as
    `[4*64,..]`, a bitcast), and nothing of a bank's shape (`[4,64,..]`,
    `[256,..]` or a layer's `[64,..]`) is made in between. Before PR 30 XLA
    cast both stacks to bf16 ahead of the loop and copied a layer of the
    cast out in every pass (PERF.md section 6). The benchmark's accepted
    reader of the kernel's roofline has to find its shapes in the call as
    compiled, at 4 bytes a weight."""
    from benchmark.moe_roofline import counts
    text, cfg = _olmoe_program(one_chip, monkeypatch, positions=1)
    E, h, f = cfg.num_experts, cfg.hidden_size, cfg.ffn_hidden_size
    calls = _kernel_calls(text, "_moe_grouped_matmul")
    assert len(calls) == 2, [c[:400] for c in calls]
    rows = -(-24 * cfg.moe_top_k // 128) * 128
    for call, (k, n) in zip(calls, [(h, 2 * f), (f, h)]):
        assert f"f32[{4 * E},{k},{n}]" in call, call[:400]
        assert counts(_as_traced(call), 60.0) == (
            2.0 * rows * k * n,
            2.0 * rows * (k + n) + 60.0 * k * n * 4), call[:400]
    assert _bank_shaped(text, {(4, E, h, 2 * f), (4, E, f, h)}) == []


def test_prefill_program_rounds_a_layers_banks_in_one_pass(one_chip,
                                                           monkeypatch):
    """A prefill of 24 x 128 tokens through the cache: the only arrays of a
    bank's shape the program makes are a layer's two banks in bf16, each
    written by the rounding kernel from the float32 stack where it lies.
    No cast of a stack ahead of the loop, no layer cut out of one; and the
    product's calls are the shape the benchmark's accepted reader counts
    (a layer's matrices, 2 bytes a weight)."""
    from benchmark.moe_roofline import counts
    text, cfg = _olmoe_program(one_chip, monkeypatch, positions=128)
    E, h, f = cfg.num_experts, cfg.hidden_size, cfg.ffn_hidden_size
    rows = 24 * 128 * cfg.moe_top_k
    calls = _kernel_calls(text, "_moe_grouped_matmul")
    assert len(calls) == 2, [c[:400] for c in calls]
    for call, (k, n) in zip(calls, [(h, 2 * f), (f, h)]):
        assert f"bf16[{E},{k},{n}]" in call, call[:400]
        assert counts(_as_traced(call)) == (
            2.0 * rows * k * n,
            2.0 * (rows * (k + n) + E * k * n)), call[:400]
    rounded = _kernel_calls(text, "_moe_round_bank")
    assert len(rounded) == 2 and all("f32[4,64," in _as_traced(c)
                                     for c in rounded), rounded
    made = _bank_shaped(text, {(4, E, h, 2 * f), (4, E, f, h)})
    assert len(made) == 2 and all("%_moe_round_bank." in line
                                  for line in made), made


def test_decode_program_reads_the_kv_pool_where_it_lies(one_chip,
                                                        monkeypatch):
    """A decode step at OLMoE's widths over the cell's pool, 24 slots of
    4,096 positions (16 kv heads of 128 in bf16, 3 GiB over 4 layers): the
    attention of every layer is ONE call of the block kernel, handed the
    stacked pool as `[4 * 24 * 32 blocks, 128 rows * 16 heads, 128]` (the
    carry of the layer loop under another shape: a bitcast) after the
    in-place write of the step's rows. Nothing else the program makes has a
    slots axis beside a positions axis: no layer cut out of the pool, no
    copy of it into the order a kernel wants, none of `_dot_attention`'s
    scores or products over `[24, 4096, 16, ..]` (PR 36; the parent read a
    layer whole, twice, 3.2 GB a step). Its temporaries are a few MiB: less
    than a layer of k by two orders."""
    compiled, cfg = _olmoe_compiled(one_chip, monkeypatch, positions=1,
                                    cap=4096, vocab=8192)
    text = compiled.as_text()
    L, S, P, nkv, hd = pool = (4, 24, 4096, cfg.num_kv_heads,
                               cfg.kv_channels)
    calls = _kernel_calls(text, "block_native_attention")
    assert len(calls) == 1, [c[:400] for c in calls]
    view = f"bf16[{L * S * 32},{128 * nkv},{hd}]"
    assert _as_traced(calls[0]).count(view) == 2, calls[0][:600]
    for line in text.splitlines():
        m = _RESULT.match(line)
        if not m:
            continue
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        if dims == pool:
            # the pool's own shape: handed on, or written in place (a
            # scatter of the step's rows, fused or not); never a copy
            assert m.group(3) in ("parameter", "get-tuple-element", "bitcast",
                                  "scatter") or (
                m.group(3) == "fusion" and "scatter" in line), line[:300]
        else:
            # ([24, 4096] is a row of projections: k and v, 2 x 2048 wide)
            assert not (S in dims and P in dims and len(dims) > 2), line[:200]
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < S * P * nkv * hd * 2 // 100, memory
    # arguments + outputs - aliased: the pool is updated where it lies
    assert memory.alias_size_in_bytes >= 2 * L * S * P * nkv * hd * 2


def _train_step_text(one_chip, step, n_micro=2):
    """`step` (a train step's signature) at two layers of 1,024 wide under
    a vocabulary of 4,096, bf16 over float32 parameters, `n_micro`
    micro-batches of 512 tokens, compiled for the chip; and the shapes of
    its stacked matrices."""
    from megatron_tpu.config import (MegatronConfig, ModelConfig,
                                     OptimizerConfig, TrainingConfig)
    from megatron_tpu.training import init_train_state
    model = ModelConfig(num_layers=2, hidden_size=1024,
                        num_attention_heads=8, vocab_size=4096,
                        seq_length=512, compute_dtype="bfloat16").derived()
    cfg = MegatronConfig(
        model=model, optimizer=OptimizerConfig(lr=1e-4),
        training=TrainingConfig(micro_batch_size=1,
                                global_batch_size=n_micro, train_iters=4),
    ).validate(n_devices=1)

    on_chip = functools.partial(_on_chip, one_chip)
    state = on_chip(jax.eval_shape(
        lambda: init_train_state(jax.random.PRNGKey(0), cfg)))
    batch = on_chip({
        "tokens": jax.ShapeDtypeStruct((n_micro, 1, 513), jnp.int32),
        "loss_mask": jax.ShapeDtypeStruct((n_micro, 1, 512), jnp.float32)})
    rng = on_chip(jax.ShapeDtypeStruct((2,), jnp.uint32))
    text = jax.jit(functools.partial(step, cfg=cfg), donate_argnums=0).lower(
        state, batch, rng).compile().as_text()
    return text, {tuple(x.shape) for x in jax.tree.leaves(
        state.params["transformer"]) if x.ndim == 3}


def _passes_over_a_stack(text, stacks):
    """The instructions inside the program's loops that make a float32
    array of a stacked matrix's shape, or of one layer of it, and hold no
    product: an add, a copy, a slice or a fill of their own. The entry
    computation is left out: the step's one zero fill and Adam's pass over
    the state are there, once a step. Nor are the compiler's prefetches
    into fast memory counted (`copy-start`, `slice-start` and their `done`s:
    stacks of this test's size fit there, the cells' do not)."""
    shapes = set(stacks) | {(1,) + s[1:] for s in stacks}
    holds_product = {m.group(1) for m in re.finditer(
        r"\n(%\S+) \([^\n]*\{\n(?:[^}][^\n]*\n)*?[^\n]* convolution\(", text)}
    found = []
    for block in re.split(r"\n(?=(?:ENTRY )?%\S+ \()", text):
        if block.startswith("ENTRY") or "fused_computation" in \
                block.split("(", 1)[0] or block.split(" ", 1)[0] \
                in holds_product:
            continue
        for line in block.splitlines()[1:]:
            m = _RESULT.match(line)
            if not m or m.group(1) != "f32":
                continue
            dims = tuple(int(d) for d in m.group(2).split(",") if d)
            calls = re.search(r"calls=(%[\w.\-]+)", line)
            if dims in shapes and m.group(3) in (
                    "fusion", "broadcast", "copy", "add", "select",
                    "convert", "dynamic-slice", "dynamic-update-slice"
                    ) and not (calls and calls.group(1) in holds_product):
                found.append(line.strip()[:160])
    return found


def test_train_step_sums_a_layers_gradient_inside_its_product(one_chip):
    """Several micro-batches: inside the loops every float32 array of a
    stacked matrix's shape is made by a fusion that holds a product (`dW`
    added to the accumulator's layer, written where it lies). The step as it
    was (`tests/test_grad_accum_fused.py::unfused_step`) fills a stack with
    zeros and adds it to the accumulator in a pass of its own, once a
    micro-batch: the reader has to see those."""
    from megatron_tpu.training.train_step import train_step
    from tests.test_grad_accum_fused import unfused_step
    text, stacks = _train_step_text(one_chip, train_step)
    assert len(stacks) >= 3
    assert _passes_over_a_stack(text, stacks) == []
    before, _ = _train_step_text(
        one_chip, functools.partial(unfused_step, loop="scan"))
    assert _passes_over_a_stack(before, stacks)


def test_xing_prefill_program_makes_no_buckets_logits(one_chip, monkeypatch):
    """Xing4.0's 4,096-row prefill program (`generation.prefill_chunk`, what
    the engine's `_chunk_fwd_fn` runs and, at offset 0, its one-shot
    prefill) at the cell's widths: hidden 3,584 under four residual streams,
    the 131,072-word untied head in bf16, a 16,384-position latent cache;
    depth cut to the one dense layer and one expert layer. Compiled for the
    chip it holds no array over `[4096, 131072]` (the whole bucket's float32
    logits are exactly 2 GiB), one row's `f32[131072]` in their place, and
    its temporaries are 958,918,144 B (0.893 GiB) where the parent's
    (31bbf77, the same program through this test's own code) are
    2,183,069,184 B (2.033 GiB). The fall is 1.14 GiB and not the logits'
    2 GiB: the compiler had laid the logits over the layers' temporaries,
    which are dead by then. At the cell's own depth of 6
    (`benchmark/fit_chunked.py`) the chunk program's temporaries went
    2.03 -> 0.91 GiB and the one-shot prefill's 2.24 -> 1.13 (PR 49)."""
    from megatron_tpu.config import xing_config
    from megatron_tpu.models import language_model as lm
    from megatron_tpu.inference.generation import (init_kv_caches,
                                                   prefill_chunk)
    rows, cap = 4096, 16384
    cfg = xing_config("29b-a4b", num_layers=2, first_k_dense_replace=1,
                      compute_dtype="bfloat16")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    on_chip = functools.partial(_on_chip, one_chip)

    def init():
        params = lm.model_init(jax.random.PRNGKey(0), cfg)
        params.pop("mtp")        # a server does not load the module
        return params
    params = on_chip(jax.eval_shape(init))
    caches = on_chip(jax.eval_shape(lambda: init_kv_caches(cfg, 1, cap)))
    rope = lm.make_rope(cfg, max_len=cap)
    tokens = jax.ShapeDtypeStruct((1, rows), jnp.int32, sharding=one_chip)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def chunk(params, tokens, caches, last_idx, next_offset):
        return prefill_chunk(params, tokens, caches, cfg, rope=rope,
                             last_idx=last_idx, next_offset=next_offset)
    compiled = jax.jit(chunk).lower(params, tokens, caches, scalar,
                                    scalar).compile()
    text = compiled.as_text()
    vocab = cfg.padded_vocab_size
    assert vocab == 131072
    assert not re.search(rf"\[(\d+,)*{rows},{vocab}\]", text)
    assert f"f32[{vocab}]" in text
    parent = 2_183_069_184
    assert compiled.memory_analysis().temp_size_in_bytes <= parent - (1 << 30)


# Falcon-7B's tied word embedding table
TABLE = (65024, 4544)


def _table_shaped(text, vocab, hidden):
    """The instructions of an HLO text that RUN and whose result is an array
    of the table's shape, in any type: what `serve_weight_copy_ms_per_step`
    reads of a trace (`benchmark/layer_metrics`). A parameter is not made, a
    bitcast moves nothing, and what stands inside a fusion's own computation
    (the head's product narrows the table it reads on the way in) is no
    operation of its own."""
    fused = set(re.findall(r" fusion\(.*calls=%([\w.-]+)", text))
    made, inside = [], None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.-]+) \(.*\{$", line)
        if head:
            inside = head.group(1)
        m = _RESULT.match(line)
        if m and inside not in fused and m.group(2) == f"{vocab},{hidden}" \
                and m.group(3) not in ("parameter", "get-tuple-element",
                                       "bitcast"):
            made.append(line.strip()[:160])
    return made


def _gather_and_tied_head(one_chip, monkeypatch, rows, *, hidden=TABLE[1],
                          table_dtype=jnp.float32, cached=True):
    """A program's first and last uses of a tied table compiled for the
    chip: the token gather as `model_forward` asks for it
    (`ops/embed_gather.py::embed_tokens`, the rule deciding), and the
    head's product over the same parameter."""
    from megatron_tpu.ops.embed_gather import embed_tokens
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def S(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def fn(emb, tokens, x):
        got = embed_tokens(emb, tokens, jnp.bfloat16, cached=cached)
        return got, (x @ emb.T.astype(jnp.bfloat16)).astype(jnp.float32)
    return jax.jit(fn).lower(
        S((TABLE[0], hidden), table_dtype), S((1, rows), jnp.int32),
        S((64, hidden), jnp.bfloat16)).compile().as_text()


@pytest.mark.parametrize("table_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rows", [64, 512], ids=["decode", "prefill_512"])
def test_served_token_gather_makes_no_copy_of_the_table(one_chip,
                                                        monkeypatch, rows,
                                                        table_dtype):
    """A decode step's 64 rows and a 512-row prefill's from Falcon-7B's
    table (PR 53): the kernel is in the program under its own scope, its
    operand is the parameter seen transposed (a bitcast: the table lies
    vocabulary-minor), and nothing in the program makes an array of the
    table's shape."""
    text = _gather_and_tied_head(one_chip, monkeypatch, rows,
                                 table_dtype=jnp.dtype(table_dtype))
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "custom-call(" in line]
    assert len(calls) == 1 and "mtpu/embed/gather" in calls[0], calls
    name = re.search(r"custom-call\(%\S+, %(\S+?)\)", calls[0]).group(1)
    operand = [line for line in text.splitlines()
               if line.lstrip().startswith(f"%{name} = ")]
    assert len(operand) == 1 and " bitcast(" in operand[0], operand
    assert f"[{TABLE[1]},{TABLE[0]}]{{1,0:" in operand[0], operand
    assert _table_shaped(text, *TABLE) == []


@pytest.mark.parametrize("table_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rows,cached", [(64, False), (1536, True)],
                         ids=["no_cache", "over_the_edge"])
def test_plain_token_gather_copies_the_whole_table(one_chip, monkeypatch,
                                                   rows, cached,
                                                   table_dtype):
    """What the rule's (c) stands on: `emb[tokens]` of a table whose hidden
    is not a multiple of 128 copies it whole, a bf16 table too. The day
    this fails the compiler has stopped copying, and the rule can go."""
    text = _gather_and_tied_head(one_chip, monkeypatch, rows, cached=cached,
                                 table_dtype=jnp.dtype(table_dtype))
    assert "tpu_custom_call" not in text
    made = _table_shaped(text, *TABLE)
    assert made and all(" copy(" in line for line in made), made


def test_a_table_of_whole_lane_tiles_is_gathered_in_place(one_chip,
                                                          monkeypatch):
    """... and the other side of (c): at a hidden of 36 lane tiles the table
    lies row-major, the rule keeps `emb[tokens]` for a served program, and
    the compiler gathers the rows where they lie."""
    text = _gather_and_tied_head(one_chip, monkeypatch, 64, hidden=4608)
    assert "tpu_custom_call" not in text
    assert _table_shaped(text, TABLE[0], 4608) == []


@pytest.mark.parametrize("batch,rows,chunk", [(1, 4096, 64), (1, 512, 64),
                                              (2, 1024, 128)])
def test_kda_chunk_at_kimi_linear_widths(one_chip, batch, rows, chunk):
    """The chunked delta rule's kernel (PR 58) at Kimi Linear's widths: a
    4,096-row chunk and shorter ones of 32 heads of 128 key and value
    channels, chunks of 64 rows (four sub-chunks of 16) or 128, a state of
    [32, 128, 128] float32 a sequence in and out, bf16 rows, float32
    log-decays (the kernel makes their running sums: PR 61): four heads a
    grid step, 128-channel slices of a 512-lane block."""
    from megatron_tpu.ops.kda_chunk import _kda_chunk, kda_block_heads

    def S(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    f32 = jnp.float32
    assert kda_block_heads(32, 128, 128) == 4
    by_rows = (batch, rows, 32, 128)
    text = jax.jit(functools.partial(_kda_chunk, chunk=chunk)).lower(
        S(by_rows), S(by_rows), S(by_rows), S(by_rows, f32),
        S((batch, rows, 32), f32), S((batch, 32, 128, 128), f32)
    ).compile().as_text()
    # the trace finds the kernel by this name (benchmark/kda_roofline.py)
    assert any("%_kda_chunk" in line and "tpu_custom_call" in line
               for line in text.splitlines())


def _made_in_memory(text):
    """The instructions of a compiled module whose result is an array in
    memory: every line but those inside a computation that a fusion calls
    (a slice there is part of the fusion's own operand read)."""
    fused = set(re.findall(r"fusion\(.*calls=%([\w.-]+)", text))
    inside = None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.-]+) \(.*\{\s*$", line)
        if head:
            inside = head.group(1)
        elif inside not in fused:
            yield line


def _kimi_linear_programs(one_chip, monkeypatch):
    """The three served programs (a decode step over the slot grid, a
    one-shot prefill and a continuation chunk of one sequence) of a
    `kimi-linear-tiny` whose KDA heads are as wide as the published ones
    (2 heads of 128: the chunk kernel's shape rule holds), over a pool of
    256 slots of 256 positions (a state of 200 MB: a smaller one the
    compiler moves whole into the chip's fast memory and back, which is no
    copy in HBM and not what 2.4 GiB at the cell's size can do), compiled
    for the chip with the cache donated as the engine donates it."""
    import dataclasses

    from megatron_tpu.config import MODEL_PRESETS
    from megatron_tpu.inference.generation import (init_kv_caches,
                                                   prefill_chunk)
    from megatron_tpu.models import language_model as lm

    cfg = dataclasses.replace(
        MODEL_PRESETS["kimi-linear-tiny"](), compute_dtype="bfloat16",
        params_dtype="bfloat16", kda_num_heads=2, kda_head_dim=128,
        attention_impl="flash")
    slots, cap, bucket = 256, 256, 128
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    on_chip = functools.partial(_on_chip, one_chip)
    params = on_chip(jax.eval_shape(
        lambda: lm.model_init(jax.random.PRNGKey(0), cfg)))
    pool = on_chip(jax.eval_shape(lambda: init_kv_caches(
        cfg, slots, cap, per_slot_offsets=True)))
    one = on_chip(jax.eval_shape(lambda: init_kv_caches(cfg, 1, cap)))
    ids = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one_chip)

    def forward(params, tokens, caches):
        return lm.model_forward(params, tokens, cfg, kv_caches=caches)

    def chunk(params, tokens, caches, last, nxt):
        return prefill_chunk(params, tokens, caches, cfg, rope=None,
                             last_idx=last, next_offset=nxt)
    return cfg, (slots, cap), {
        "decode": jax.jit(forward, donate_argnums=2).lower(
            params, ids(slots, 1), pool).compile(),
        "prefill": jax.jit(forward, donate_argnums=2).lower(
            params, ids(1, bucket), one).compile(),
        "chunk": jax.jit(chunk, donate_argnums=2).lower(
            params, ids(1, bucket), one, ids(), ids()).compile()}


def test_kimi_linear_served_programs_copy_no_state_and_no_latent_layer(
        one_chip, monkeypatch):
    """No program of the three makes a copy of the rule's state (float32
    [6 KDA layers, batch, 2, 128, 128]) or of a layer of latent rows: what
    has the state's shape is handed on or written in place, the cache is
    aliased whole, and the temporaries are smaller than the state."""
    cfg, (slots, cap), programs = _kimi_linear_programs(one_chip,
                                                        monkeypatch)
    row = cfg.kv_row_width
    for name, compiled in programs.items():
        batch = slots if name == "decode" else 1
        text = compiled.as_text()
        state = (6, batch, 2, 128, 128)
        layer = (batch, row, cap)
        for line in _made_in_memory(text):
            m = _RESULT.match(line)
            if not m:
                continue
            dims = tuple(int(d) for d in m.group(2).split(",") if d)
            if dims == state:
                assert m.group(3) in (
                    "parameter", "get-tuple-element", "bitcast",
                    "dynamic-update-slice", "while", "tuple",
                    "custom-call") or (
                    m.group(3) == "fusion"
                    and ("dynamic-update-slice" in line
                         or "kind=kCustom" in line)) or (
                    # ONE sequence's state (12 MiB at the cell's size) may
                    # be staged in the chip's fast memory round its use
                    name != "decode"
                    and m.group(3) in ("copy-start", "copy-done")), \
                    (name, line[:300])
            # a layer of the latent rows cut out of the stack, or the stack
            # in another order: never made
            assert dims not in (layer, (1, *layer),
                                (batch, cap, row)), (name, line[:300])
        if name != "decode":
            assert len(_kernel_calls(text, "_kda_chunk")) >= 1, name
        memory = compiled.memory_analysis()
        nbytes = 6 * batch * 2 * 128 * 128 * 4
        assert memory.alias_size_in_bytes >= nbytes \
            + 2 * batch * row * cap * 2, (name, memory)
        if name == "decode":
            assert memory.temp_size_in_bytes < nbytes, (name, memory)


# ---- PR 60: a delta rule with one decay a head beside keys and values -----

@pytest.mark.parametrize("batch,rows,chunk", [(1, 4096, 64), (1, 512, 64),
                                              (2, 1024, 128)])
def test_gdn_chunk_at_qwen3_next_widths(one_chip, batch, rows, chunk):
    """The scalar-decay chunk kernel (PR 60) at Qwen3-Next's widths: 16 key
    heads under 32 value heads of 128 channels, a decay a head a row, a
    state of [32, 128, 128] float32 a sequence in and out, bf16 rows: four
    value heads a grid step over the TWO key heads they read."""
    from megatron_tpu.ops.kda_chunk import _gdn_chunk, kda_block_heads

    def S(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    f32 = jnp.float32
    assert kda_block_heads(32, 128, 128) == 4
    text = jax.jit(functools.partial(_gdn_chunk, chunk=chunk)).lower(
        S((batch, rows, 16, 128)), S((batch, rows, 16, 128)),
        S((batch, rows, 32, 128)), S((batch, rows, 32), f32),
        S((batch, rows, 32), f32), S((batch, 32, 128, 128), f32)
    ).compile().as_text()
    # the trace finds the kernel by this name (benchmark/gdn_roofline.py)
    assert any("%_gdn_chunk" in line and "tpu_custom_call" in line
               for line in text.splitlines())
    # q and k of a key head are read through the block index: no array of
    # q's or k's rows at the value heads' width is made beside v and o
    wide = re.findall(rf"= bf16\[{batch},{rows + (-rows % chunk)},4096\]"
                      r"\S* (?!parameter|custom-call)", text)
    assert len(wide) <= 2, wide


@pytest.mark.parametrize("form", ["kda", "gdn"])
def test_delta_rule_kernels_are_counted_by_the_benchmarks_readers(one_chip,
                                                                  form):
    """PR 61 changed what the chunk kernels take (g itself where the running
    sums were, four heads a grid step): the compiled kernel's line at the
    cells' widths, as a trace names it, is still FOUND and COUNTED by the
    benchmark's accepted readers (`benchmark/kda_roofline.py`,
    `gdn_roofline.py`: the rule's own 12.9 GFLOP; 206 MB for a decay a
    channel, 106 MB for a decay a head under 16 key heads), so that a
    changed operand list fails here and not a check on the chip."""
    from benchmark import gdn_roofline, kda_roofline
    from megatron_tpu.ops.kda_chunk import _gdn_chunk, _kda_chunk

    def S(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    f32 = jnp.float32
    rows, heads, d = 4096, 32, 128
    if form == "kda":
        reader, kernel, name = kda_roofline, _kda_chunk, "_kda_chunk"
        found, key_heads, by_g = kda_roofline.is_kda_chunk, heads, (d,)
        nbytes = rows * heads * (3 * d * 2 + d * 2 + d * 4 + 4)
    else:
        reader, kernel, name = gdn_roofline, _gdn_chunk, "_gdn_chunk"
        found, key_heads, by_g = gdn_roofline.is_gdn_chunk, 16, ()
        nbytes = rows * (2 * key_heads * d * 2 + heads * (2 * d * 2 + 2 * 4))
    nbytes += 2 * heads * d * d * 4
    text = jax.jit(kernel).lower(
        S((1, rows, key_heads, d)), S((1, rows, key_heads, d)),
        S((1, rows, heads, d)), S((1, rows, heads) + by_g, f32),
        S((1, rows, heads), f32), S((1, heads, d, d), f32)
    ).compile().as_text()
    calls = [line for line in text.splitlines()
             if f"%{name}" in line and "tpu_custom_call" in line]
    assert len(calls) == 1, [c[:300] for c in calls]
    traced = _as_traced(calls[0])
    assert found(traced), traced[:400]
    assert reader.counts(traced) == (6.0 * rows * heads * d * d,
                                     float(nbytes)), traced[:600]
    assert round(nbytes / 1e6) == (206 if form == "kda" else 106)


def test_flash_kernel_reads_folded_rows_at_an_offset(one_chip):
    """A 4,096-row chunk of 16 heads over 2 kv heads of 256 channels against
    the folded rows [32,768, 2 x 256] of a slot, at a traced offset: the
    kernel indexes kv head g's channels as a block of the row, and no
    transposed or cut copy of the rows is made."""
    from megatron_tpu.ops.flash_attention import _flash_attention_offset

    def S(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = jax.jit(functools.partial(
        _flash_attention_offset, scale=1 / 16.0, block_kv=512,
        use_pallas=True, sliding_window=None, kv_heads_major=False,
        kv_folded=2)).lower(
            S((1, 4096, 16, 256)), S((1, 32768, 512)), S((1, 32768, 512)),
            S((), jnp.int32), S((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    made = [line for line in text.splitlines()
            if re.search(r"bf16\[1,(2,32768,256|32768,2,256)\]", line)
            and " copy(" in line]
    assert made == [], made
    # the queries' and the output's transposes, and nothing like the rows
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 32768 * 512 * 2


def _qwen3_next_programs(one_chip, monkeypatch):
    """The three served programs of a `qwen3-next-tiny` whose linear heads
    and attention heads are as wide as the published ones (1 key head under
    2 value heads of 128: the chunk kernel's shape rule holds; 4 heads over
    2 kv heads of 256: the folded flash form's), over a pool of 256 slots of
    384 positions, compiled for the chip with the cache donated as the
    engine donates it."""
    import dataclasses

    from megatron_tpu.config import MODEL_PRESETS
    from megatron_tpu.inference.generation import (init_kv_caches,
                                                   prefill_chunk)
    from megatron_tpu.models import language_model as lm

    cfg = dataclasses.replace(
        MODEL_PRESETS["qwen3-next-tiny"](), compute_dtype="bfloat16",
        params_dtype="bfloat16", gdn_key_heads=1, gdn_value_heads=2,
        gdn_key_head_dim=128, gdn_value_head_dim=128, kv_channels=256,
        attention_impl="flash")
    slots, cap, bucket = 256, 384, 128
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    on_chip = functools.partial(_on_chip, one_chip)
    params = on_chip(jax.eval_shape(
        lambda: lm.model_init(jax.random.PRNGKey(0), cfg)))
    pool = on_chip(jax.eval_shape(lambda: init_kv_caches(
        cfg, slots, cap, per_slot_offsets=True)))
    one = on_chip(jax.eval_shape(lambda: init_kv_caches(cfg, 1, cap)))
    rope = lm.make_rope(cfg, cap)
    ids = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
        shape, jnp.int32, sharding=one_chip)

    def forward(params, tokens, caches):
        return lm.model_forward(params, tokens, cfg, rope=rope,
                                kv_caches=caches)

    def chunk(params, tokens, caches, last, nxt):
        return prefill_chunk(params, tokens, caches, cfg, rope=rope,
                             last_idx=last, next_offset=nxt)
    return cfg, (slots, cap, bucket), {
        "decode": jax.jit(forward, donate_argnums=2).lower(
            params, ids(slots, 1), pool).compile(),
        "prefill": jax.jit(forward, donate_argnums=2).lower(
            params, ids(1, bucket), one).compile(),
        "chunk": jax.jit(chunk, donate_argnums=2).lower(
            params, ids(1, bucket), one, ids(), ids()).compile()}


def test_qwen3_next_served_programs_copy_no_state_and_make_no_scores(
        one_chip, monkeypatch):
    """No program of the three makes a copy of the rule's state (float32 [6
    linear layers, batch, 2, 128, 128]); a prefill and a chunk run the
    scalar-decay kernel and the flash kernel over the folded rows, and make
    no [heads, rows, max_seq] array of scores; the cache is aliased whole."""
    cfg, (slots, cap, bucket), programs = _qwen3_next_programs(one_chip,
                                                               monkeypatch)
    for name, compiled in programs.items():
        batch = slots if name == "decode" else 1
        text = compiled.as_text()
        state = (6, batch, 2, 128, 128)
        for line in _made_in_memory(text):
            m = _RESULT.match(line)
            if not m:
                continue
            dims = tuple(int(d) for d in m.group(2).split(",") if d)
            if dims == state:
                assert m.group(3) in (
                    "parameter", "get-tuple-element", "bitcast",
                    "dynamic-update-slice", "while", "tuple",
                    "custom-call") or (
                    m.group(3) == "fusion"
                    and ("dynamic-update-slice" in line
                         or "kind=kCustom" in line)) or (
                    name != "decode"
                    and m.group(3) in ("copy-start", "copy-done")), \
                    (name, line[:300])
            if name != "decode":
                # [heads, rows, max_seq] scores, in any order of the three
                assert sorted(d for d in dims if d != 1) != \
                    sorted((cfg.num_attention_heads, bucket, cap)), \
                    (name, line[:300])
        if name != "decode":
            assert len(_kernel_calls(text, "_gdn_chunk")) >= 1, name
            assert len(_kernel_calls(text, "_flash_attention_offset")) >= 1, \
                name
        memory = compiled.memory_analysis()
        nbytes = 6 * batch * 2 * 128 * 128 * 4
        assert memory.alias_size_in_bytes >= nbytes \
            + 2 * 2 * batch * cap * 512 * 2, (name, memory)
        if name == "decode":
            assert memory.temp_size_in_bytes < nbytes, (name, memory)
