"""Multi-host (pod-scale) runtime: process init + global batch assembly.

TPU-native replacement for the reference's multi-node launch machinery
(ref: megatron/initialize.py:124-151 _initialize_distributed via torchrun +
NCCL init_process_group, and the "dataloader on tp-rank-0 then broadcast"
trick at training.py:855-939). On TPU pods every host runs the SAME
single-controller program over one global mesh; what remains host-side is

1. `initialize_distributed()` — jax.distributed.initialize, opted in via
   MEGATRON_TPU_MULTIHOST=1 (TPU-pod auto-detection) or env-driven
   (JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID).
2. `make_global_batch()` — lift host-local numpy batches into globally
   sharded jax.Arrays. Every process builds the same global batch order
   (same seed -> same sampler stream), and each host materializes on its
   devices only the dp rows it owns: the callback formulation means no
   host ever holds more device data than its addressable shard.

Single-process runs bypass all of this (the jit transfer path is already
optimal), so the train loop can call `make_global_batch` unconditionally.
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import numpy as np


def initialize_distributed(coordinator: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None) -> int:
    """Bring up the JAX distributed runtime (multi-controller).

    No-ops when already initialized or when nothing indicates a multi-host
    launch (single-host dev loops must not pay a coordinator timeout).
    Returns the process index. (ref: initialize.py:124-151 — the
    torch.distributed.init_process_group equivalent.)"""
    coordinator = coordinator or os.environ.get("JAX_COORDINATOR_ADDRESS")
    num_processes = num_processes or _env_int("JAX_NUM_PROCESSES")
    process_id = process_id if process_id is not None \
        else _env_int("JAX_PROCESS_ID")
    # only an EXPLICIT opt-in triggers pod auto-detection:
    # TPU_WORKER_HOSTNAMES alone is unreliable (single-host machines set it)
    on_pod = bool(os.environ.get("MEGATRON_TPU_MULTIHOST"))
    if not coordinator and not on_pod:
        # single-host: return WITHOUT touching jax — backend init must stay
        # where the entry point put it
        return 0
    try:
        if coordinator:
            jax.distributed.initialize(coordinator_address=coordinator,
                                       num_processes=num_processes,
                                       process_id=process_id)
        else:
            jax.distributed.initialize()  # TPU-pod auto-detection
    except RuntimeError as e:
        # already initialized, or a backend was touched first (interactive
        # sessions): proceed with whatever process topology exists
        print(f"initialize_distributed: {e}")
    return jax.process_index()


def _env_int(name: str) -> Optional[int]:
    v = os.environ.get(name)
    return int(v) if v else None


def make_global_batch(batch: dict, mesh, batch_sharding) -> dict:
    """Host-local numpy batch -> globally dp-sharded jax.Arrays.

    `batch` leaves are the FULL global batch in every process (identical
    sampler streams); `batch_sharding` is the NamedSharding the train step
    expects ([n_micro, batch, ...] with batch over 'dp'). Each process
    materializes only its addressable shards. Single-process: returned
    unchanged — jit's implicit transfer is equivalent and avoids an extra
    host copy."""
    if jax.process_count() == 1:
        return batch

    def lift(v):
        arr = np.asarray(v)
        return jax.make_array_from_callback(
            arr.shape, batch_sharding, lambda idx: arr[idx])

    return {k: lift(v) for k, v in batch.items()}


def process_batch_rows(mesh, global_rows: int) -> tuple:
    """(row_lo, row_hi) of the global batch dim owned by THIS process —
    the hook for samplers that skip tokenizing other hosts' rows (the
    per-host sharded-loader optimization the reference approximates with
    its tp-rank-0 broadcast)."""
    if jax.process_count() == 1:
        return 0, global_rows
    dp = mesh.shape.get("dp", 1)
    assert global_rows % dp == 0
    per = global_rows // dp
    # dp coordinate range covered by this process's addressable devices
    # (dp axis located by NAME so a mesh-axis reorder can't silently map
    # hosts to wrong row ranges)
    dp_dim = mesh.axis_names.index("dp")
    coords = sorted({int(np.argwhere(mesh.devices == d)[0][dp_dim])
                     for d in mesh.devices.ravel()
                     if d.process_index == jax.process_index()})
    lo, hi = coords[0], coords[-1]
    assert coords == list(range(lo, hi + 1)), (
        f"process {jax.process_index()} owns non-contiguous dp coords "
        f"{coords}; a row-range slice would cover other hosts' rows — "
        "lay the mesh out with dp contiguous per process")
    return lo * per, (hi + 1) * per
