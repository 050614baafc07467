"""Device-mesh topology for megatron_tpu.

TPU-native replacement for the reference's process-group factory
(ref: megatron/core/parallel_state.py:51-205 `initialize_model_parallel` and
its group getters :217-481). The reference builds explicit NCCL communicators
for each of dp/tp/pp/model/embedding groups with the rank-order convention
"tp-fastest, then dp, then pp" (ref: core/parallel_state.py:68-82 docstring).

Here the entire grid is a single `jax.sharding.Mesh` with named axes:

    ('dp', 'pp', 'cp', 'tp')

and "groups" are just mesh axes — a TP all-reduce is `psum` over 'tp', the
pipeline send/recv is `ppermute` over 'pp', the embedding-group sync
(ref: optimizer.py:203-229) is a psum over the 'pp' edge ranks expressed in
the pipeline schedule itself. Axis order puts 'tp' innermost so TP collectives
ride the fastest ICI links, matching the reference's tp-fastest rank packing.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from megatron_tpu.config import ParallelConfig
from megatron_tpu.utils.tracing import phase

# Canonical mesh axis names, outermost (slowest-varying) first.
DATA_AXIS = "dp"
PIPELINE_AXIS = "pp"
CONTEXT_AXIS = "cp"
TENSOR_AXIS = "tp"
MESH_AXES = (DATA_AXIS, PIPELINE_AXIS, CONTEXT_AXIS, TENSOR_AXIS)


@phase("mesh")
def build_mesh(
    parallel: ParallelConfig,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Create the (dp, pp, cp, tp) mesh.

    Equivalent of `initialize_model_parallel(tp, pp)`
    (ref: core/parallel_state.py:51); dp is derived from the device count the
    same way the reference derives it from world size
    (ref: megatron/arguments.py:86-100).
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    tp = parallel.tensor_parallel
    pp = parallel.pipeline_parallel
    cp = parallel.context_parallel
    dp = parallel.data_parallel or parallel.derive_dp(n)
    assert dp * pp * cp * tp == n, (
        f"mesh {dp}x{pp}x{cp}x{tp} != {n} devices")
    dev_array = np.asarray(devices).reshape(dp, pp, cp, tp)
    return Mesh(dev_array, MESH_AXES)


def single_device_mesh(device: Optional[jax.Device] = None) -> Mesh:
    devices = [device] if device is not None else jax.devices()[:1]
    return Mesh(np.asarray(devices).reshape(1, 1, 1, 1), MESH_AXES)


# ---------------------------------------------------------------------------
# Rank predicates — the reference exposes is_pipeline_{first,last}_stage etc.
# (ref: core/parallel_state.py:304-358). Inside shard_map'ed code the same
# information comes from `jax.lax.axis_index`.
# ---------------------------------------------------------------------------

def axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis]


def mesh_info(mesh: Mesh) -> dict:
    return {a: mesh.shape[a] for a in mesh.axis_names}
