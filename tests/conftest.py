"""Test harness: hermetic multi-chip simulation on CPU.

The reference has no below-hardware multi-node story (SURVEY.md §4 — all
distributed tests need real GPUs + NCCL under torchrun). Here every
parallelism test runs on an 8-device virtual CPU mesh via
`--xla_force_host_platform_device_count`, so TP/PP/DP/SP semantics are
CI-testable with no accelerator.
"""
import os

# Must be set before jax is imported anywhere. Hard override: whatever the
# environment presets, the hermetic suite runs on the virtual CPU mesh.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# persistent compilation cache makes repeated suite runs fast. It stays
# OUTSIDE the checkout (the chip tool copies the tree as it stands on
# disk, and thousands of small CPU entries would travel with it)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", "/tmp/jax_test_cache")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")

import jax  # noqa: E402
import pytest  # noqa: E402

# Numerical-equivalence tests compare different contraction orders of the same
# math; run matmuls at full precision so tolerances reflect algorithms, not
# the backend's default bf16-ish matmul mode.
jax.config.update("jax_default_matmul_precision", "highest")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: compile-heavy (8-device shard_map / pipeline / e2e) tests; "
        "deselect with `pytest -m 'not slow'` for the fast green/red tier "
        "(see README 'Running the tests')")
    config.addinivalue_line(
        "markers",
        "chaos: fault-injection / resilience tests (tests/"
        "test_resilience.py) — deliberately corrupt checkpoints, fail "
        "writes, poison batches, stall steps; sized to stay inside the "
        "tier-1 budget, select with `pytest -m chaos`")


@pytest.fixture(autouse=True, scope="module")
def _bound_jax_cache_growth():
    """Clear jax's in-memory executable caches after every test module.

    The full suite jit-compiles hundreds of distinct programs in ONE
    process; with every executable retained, RSS grows monotonically
    until XLA's CPU compiler segfaults deep in the run (reproducibly at
    ~330/434 tests, crash inside backend_compile with the process near
    the memory ceiling). Cross-module executable reuse is minimal —
    each module compiles its own shapes — and the persistent on-disk
    cache above keeps recompiles cheap, so per-module clearing bounds
    memory at negligible wall-clock cost."""
    yield
    import gc
    jax.clear_caches()
    gc.collect()


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


def make_test_mesh(devices, dp=1, pp=1, cp=1, tp=1):
    """Shared (dp, pp, cp, tp) mesh factory for parallelism tests."""
    import numpy as np
    from jax.sharding import Mesh

    from megatron_tpu.parallel.mesh import MESH_AXES
    n = dp * pp * cp * tp
    return Mesh(np.asarray(devices[:n]).reshape(dp, pp, cp, tp), MESH_AXES)
