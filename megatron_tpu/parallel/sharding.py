"""Logical-axis sharding rules.

TPU-native replacement for the reference's hand-written tensor-parallel layer
classes (ref: megatron/core/tensor_parallel/layers.py — ColumnParallelLinear
:410, RowParallelLinear :566, VocabParallelEmbedding :128) and autograd-wrapped
collectives (ref: megatron/core/tensor_parallel/mappings.py:127-278).

Under GSPMD the same placement is expressed declaratively: every parameter and
activation carries logical axis names, and a rules table maps logical names to
mesh axes. XLA then inserts exactly the collectives the reference hand-codes:

  Column-parallel (out-dim on 'tp')  -> matmul keeps activations replicated,
                                        no comm fwd (ref: layers.py:463-474)
  Row-parallel (in-dim on 'tp')      -> XLA inserts psum (== the forward
                                        all-reduce at layers.py:690-694)
  Vocab-parallel embedding           -> vocab-dim shard + psum gather
                                        (ref: layers.py:187-210)
  Sequence parallel                  -> activations sharded ('sp' -> tp) along
                                        seq outside attention/MLP; the
                                        all-gather/reduce-scatter pair the
                                        reference codes at layers.py:225-296
                                        falls out of the sharding switch.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from megatron_tpu.parallel.mesh import (
    CONTEXT_AXIS, DATA_AXIS, PIPELINE_AXIS, TENSOR_AXIS)

# ---------------------------------------------------------------------------
# Logical axis vocabulary.
# Parameters:
#   "embed"      hidden dim (replicated over tp unless fsdp)
#   "heads"      attention-head output dim of QKV proj   -> tp
#   "mlp"        ffn hidden dim                          -> tp
#   "vocab"      vocabulary dim                          -> tp
#   "layers"     stacked-layer dim (scan over layers)    -> pp (when pipelined)
# Activations:
#   "batch"      global batch                            -> dp
#   "seq"        sequence dim inside attention           -> cp (ring attention)
#   "seq_sp"     sequence dim outside attn/mlp (SP)      -> tp
#   "act_embed"  activation hidden dim (replicated)
# ---------------------------------------------------------------------------

# rules as (logical_name, mesh_axis-or-None) pairs; first match wins.
def make_logical_rules(sequence_parallel: bool = False,
                       expert_axis: str = "tp"):
    assert expert_axis in ("tp", "dp"), expert_axis
    return (
        ("batch", DATA_AXIS),
        ("layers", PIPELINE_AXIS),
        ("stage", PIPELINE_AXIS),
        # microbatch stream dim: resharded over 'pp' for the post-pipeline
        # LM-head/CE so the head's FLOPs spread across stages
        ("microbatch", PIPELINE_AXIS),
        ("heads", TENSOR_AXIS),
        ("kv_heads", TENSOR_AXIS),
        ("mlp", TENSOR_AXIS),
        # MoE expert bank: each device holds whole experts; the mesh axis
        # is selectable (ParallelConfig.expert_axis) — 'tp' (default) or
        # 'dp' (GShard-style EP over the data axis; models/moe.py)
        ("experts", DATA_AXIS if expert_axis == "dp" else TENSOR_AXIS),
        ("vocab", TENSOR_AXIS),
        ("seq", CONTEXT_AXIS),
        # Megatron-SP: the residual-stream sequence dim is sharded over 'tp'
        # outside attention/MLP (ref: core/tensor_parallel/layers.py:225-296,
        # mappings.py:191-246). With context parallelism the same dim is
        # additionally split over 'cp' (ring attention), so the full rule is
        # ('cp','tp') when SP is on and 'cp' alone when it is off.
        ("seq_sp", (CONTEXT_AXIS, TENSOR_AXIS) if sequence_parallel
         else CONTEXT_AXIS),
        ("embed", None),
        ("act_embed", None),
        ("head_dim", None),
        ("qkv", None),
    )


def logical_to_spec(logical_axes: tuple, rules) -> P:
    """Map a tuple of logical axis names to a PartitionSpec via rules."""
    table = dict(rules)
    out = []
    for name in logical_axes:
        if name is None:
            out.append(None)
        else:
            out.append(table.get(name))
    # trim trailing Nones for cleanliness
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def logical_sharding(mesh: Mesh, logical_axes: tuple, rules) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(logical_axes, rules))


def tree_logical_to_sharding(mesh: Mesh, logical_tree, rules):
    """Map a pytree of logical-axis tuples to NamedShardings.

    `type(x) is tuple` (not isinstance): axes LEAVES are plain tuples,
    while NamedTuple pytree nodes in the tree (e.g. the W8 int8-weight
    containers from ops/quantized.quantize_axes) must be recursed INTO —
    isinstance would swallow a W8 whole and emit a replicated
    PartitionSpec() for its int8 payload."""
    return jax.tree.map(
        lambda ax: logical_sharding(mesh, ax, rules),
        logical_tree,
        is_leaf=lambda x: type(x) is tuple,
    )


def with_sharding(x, mesh: Mesh, logical_axes: tuple, rules):
    """Constrain an intermediate activation's sharding (GSPMD hint).

    This is the declarative analogue of the reference's explicit
    scatter/gather mapping functions (ref: mappings.py:253-278).

    When an ambient abstract mesh is active (jax.set_mesh — the pipelined
    paths run under one), pass the raw PartitionSpec so jax resolves it
    against the CONTEXT mesh: inside a partial-manual shard_map region the
    context mesh marks 'pp' Manual, and a NamedSharding built on the
    concrete (all-Auto) mesh would poison the value's aval — the next
    dot_general consuming it unchanged (e.g. post-LN models feed a layer
    output straight into the next QKV matmul) raises a mesh-mismatch."""
    spec = logical_to_spec(logical_axes, rules)
    if not jax.sharding.get_abstract_mesh().empty:
        return jax.lax.with_sharding_constraint(x, spec)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Activation-sharding context: lets pure model code place
# with_sharding_constraint hints without threading a mesh through every call.
#
# make_train_step enters the context around tracing; model code calls
# `constrain(x, logical_axes)`, a no-op outside the context (single-device
# runs, inference decode). This is how sequence parallelism becomes REAL: the
# residual stream is pinned to [b, s/(cp*tp), h] between TP blocks, and GSPMD
# inserts the all-gather on entry to QKV/MLP-in and the reduce-scatter on
# exit of the row-parallel projections — exactly the collective placement the
# reference hand-codes (ref: layers.py:225-296, mappings.py:191-246).
# ---------------------------------------------------------------------------

_ACT_CTX = threading.local()


@contextlib.contextmanager
def activation_shardings(mesh: Mesh, rules):
    prev = getattr(_ACT_CTX, "cur", None)
    _ACT_CTX.cur = (mesh, rules)
    try:
        yield
    finally:
        _ACT_CTX.cur = prev


def constrain(x, logical_axes: tuple):
    """Pin activation `x` to the sharding its logical axes imply, if an
    activation-sharding context is active; identity otherwise."""
    cur = getattr(_ACT_CTX, "cur", None)
    if cur is None:
        return x
    mesh, rules = cur
    if all(a is None for a in logical_to_spec(logical_axes, rules)):
        return x
    return with_sharding(x, mesh, logical_axes, rules)


def active_tp_mesh():
    """The activation-sharding context's mesh when it actually shards
    the tensor axis (tp > 1), else None. Model code that must wrap a
    hand-written kernel in an explicit shard_map (XLA cannot partition
    a custom call — e.g. the serving block-attention Pallas kernel,
    models/attention.py) reads the mesh from here at TRACE time, the
    same context `constrain` uses — so the wrap appears exactly when
    the enclosing jit runs the mesh treatment and never on
    single-device traces."""
    cur = getattr(_ACT_CTX, "cur", None)
    if cur is None:
        return None
    mesh = cur[0]
    if TENSOR_AXIS in mesh.shape and mesh.shape[TENSOR_AXIS] > 1:
        return mesh
    return None


def active_kernel_mesh():
    """The activation-sharding context's mesh when a hand-written kernel
    traced now has to be wrapped in an explicit shard_map over it: the
    mesh shards batch rows ('dp') or heads ('tp') over more than one
    device, and the trace is not already inside a manual region (the
    pipeline's and the context-parallel rings' own shard_maps hand their
    kernels per-device blocks). None otherwise — single-device traces
    lower the bare kernel."""
    cur = getattr(_ACT_CTX, "cur", None)
    if cur is None:
        return None
    mesh = cur[0]
    if mesh.shape.get(TENSOR_AXIS, 1) * mesh.shape.get(DATA_AXIS, 1) <= 1:
        return None
    if jax.sharding.get_abstract_mesh().manual_axes:
        return None
    return mesh


def distributed_opt_sharding(mesh: Mesh, logical_axes: tuple, rules,
                             shape: tuple,
                             pipelined: bool = False) -> NamedSharding:
    """ZeRO-1 optimizer-state sharding (ref: megatron/optimizer/
    distrib_optimizer.py:32-610 DistributedOptimizer).

    The reference shards Adam state across DP ranks over the *flattened* grad
    buffer (ranges ignore parameter boundaries) and hand-codes grad
    reduce-scatter + param all-gather. The GSPMD formulation: give each
    optimizer-state leaf its parameter's spec PLUS 'dp' on the first
    dimension that is unsharded and dp-divisible. XLA then reduce-scatters
    the grads feeding the update and all-gathers the updated params — the
    same collectives, derived from the placement (SURVEY.md §7).

    `pipelined`: with pp>1 the non-stacked params (embedding / final norm /
    lm_head) enter the pipeline shard_map pp-replicated and their grads exit
    as pp-psums; dp-sharding THEIR moments trips a CHECK in XLA's SPMD
    partitioner (spmd_partitioner_util.cc partition-group mismatch), so
    ZeRO sharding is applied to the 'layers'-stacked params only — which at
    scale is >98% of the state."""
    if pipelined and "layers" not in logical_axes:
        return logical_sharding(mesh, logical_axes, rules)
    spec = list(logical_to_spec(logical_axes, rules))
    spec += [None] * (len(shape) - len(spec))
    dp = mesh.shape[DATA_AXIS]
    # expert_axis='dp' already places 'dp' on the bank's experts dim —
    # adding it to a second dim would be a DuplicateSpecError; those
    # moments are dp-sharded (by the expert dim) either way
    if dp > 1 and DATA_AXIS not in spec:
        for i, (ax, dim) in enumerate(zip(spec, shape)):
            if ax is None and dim % dp == 0:
                spec[i] = DATA_AXIS
                break
    while spec and spec[-1] is None:
        spec.pop()
    return NamedSharding(mesh, P(*spec))


def tree_distributed_opt_sharding(mesh: Mesh, logical_tree, rules,
                                  shape_tree, pipelined: bool = False):
    return jax.tree.map(
        lambda ax, sh: distributed_opt_sharding(mesh, ax, rules,
                                                tuple(sh.shape),
                                                pipelined=pipelined),
        logical_tree, shape_tree,
        is_leaf=lambda x: type(x) is tuple,  # see tree_logical_to_sharding
    )
