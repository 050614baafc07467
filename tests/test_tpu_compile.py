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
            q, k, v, bmap, lengths, scale=HD ** -0.5, block_size=B,
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


@pytest.mark.parametrize("rows,k,n,grad", [
    (640, 2048, 2048, False),      # a decode step of 80 slots, first product
    (640, 1024, 2048, False),      # ... and the second
    (8192, 2048, 2048, True),      # a 1024-token prefill; training's backward
    (49152, 2048, 2048, False),    # the largest prefill, 2 x 3072 tokens
    (49152, 1024, 2048, False)])
def test_grouped_matmul_at_olmoe_widths(one_chip, rows, k, n, grad):
    """The dropless experts' grouped product (megablox under
    `ops/grouped_matmul.py`'s tiling) at OLMoE-1B-7B's widths: 64 experts,
    hidden 2048, expert width 1024. A tile that does not fit the kernel's
    16 MiB of fast memory is refused here and nowhere on the CPU."""
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
