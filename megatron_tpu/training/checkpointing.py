"""Checkpoint save/load with Megatron resume semantics.

TPU-native equivalent of megatron/checkpointing.py (ref: :77-140 layout,
:170-174 tracker file, :243-337 save, :476-677 load). Semantics kept:

- `latest_checkpointed_iteration.txt` tracker naming the newest checkpoint;
- `iter_{N:07d}/` directories; `release` mode for converted weights
  (ref: checkpointing.py:96-101);
- the full config is embedded in the checkpoint and can override the runtime
  config on load (`use_checkpoint_args`, ref: checkpointing.py:476-558);
- `consumed_samples` is restored so the data sampler fast-forwards
  (ref: checkpointing.py:600-607, training.py:861-868);
- `finetune` loads weights only — no optimizer state, iteration reset
  (ref: --finetune, checkpointing.py:568-580).

Differences by design:
- ONE logical checkpoint regardless of device layout. The reference writes
  per-rank `mp_rank_{tp}_{pp}` shards whose contents depend on the parallel
  config, requiring the offline resharder (ref: tools/checkpoint_util.py) to
  change tp/pp. Here the tree is saved in logical form and re-laid-out at
  load against the current mesh's shardings — tp/pp/dp resharding is a
  load-time no-op, which deletes the C3 tool (SURVEY.md §2.7).
- No CUDA/torch RNG blobs: jax PRNG keys live inside the saved state.
- Backend: orbax (TensorStore/OCDBT) — each device writes its own shards,
  so a dp x pp x tp-sharded 70B state never materializes on one host, and
  `async_save=True` overlaps the write with training (the iteration only
  becomes visible in the tracker once the write is durable; see
  `finalize_async_saves`). The reference's equivalent is the torch.save of
  a full state dict per rank (ref: checkpointing.py:304-337) — synchronous
  and layout-bound. Legacy `.npz` checkpoints from round 1 remain readable.
"""
from __future__ import annotations

import json
import os
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from megatron_tpu.config import MegatronConfig, ResilienceConfig
from megatron_tpu.resilience import integrity
from megatron_tpu.resilience.faults import fault_point
from megatron_tpu.resilience.retry import RetryPolicy, policy_from, retry
from megatron_tpu.training.train_step import TrainState
from megatron_tpu.utils.logging import print_rank_0
from megatron_tpu.utils.tracing import phase

TRACKER = "latest_checkpointed_iteration.txt"
STATE_DIR = "state"  # orbax pytree directory inside an iteration dir


class LoadedCheckpoint:
    """load_checkpoint result: unpacks/indexes like the historical
    (state, iteration, consumed_samples) 3-tuple, plus named extras —
    `data_state` (the data-iterator exact-resume state_dict stored in
    checkpoint metadata; None for legacy checkpoints or fresh starts),
    `quarantine` (list of poison-batch windows skipped by divergence
    rollbacks, see training/loop.py), and `ckpt_dir`."""

    __slots__ = ("state", "iteration", "consumed_samples", "data_state",
                 "quarantine", "ckpt_dir")

    def __init__(self, state, iteration: int, consumed_samples: int,
                 data_state: Optional[dict] = None,
                 quarantine: Optional[list] = None,
                 ckpt_dir: Optional[str] = None):
        self.state = state
        self.iteration = iteration
        self.consumed_samples = consumed_samples
        self.data_state = data_state
        self.quarantine = list(quarantine or [])
        self.ckpt_dir = ckpt_dir

    def _tuple(self):
        return (self.state, self.iteration, self.consumed_samples)

    def __iter__(self):
        return iter(self._tuple())

    def __getitem__(self, i):
        return self._tuple()[i]

    def __len__(self):
        return 3

    def __repr__(self):
        return (f"LoadedCheckpoint(iteration={self.iteration}, "
                f"consumed_samples={self.consumed_samples}, "
                f"data_state={'yes' if self.data_state else 'no'}, "
                f"quarantine={len(self.quarantine)} windows, "
                f"ckpt_dir={self.ckpt_dir!r})")

# one async checkpointer per process; saves are serialized through it
_ASYNC_CKPTR = None
# (root, tag, ckpt_dir, resilience) awaiting durability; the manifest
# and tracker publish in finalize_async_saves, in this order, so the
# tracker can never name a checkpoint whose manifest (and therefore
# whose payload) is not fully on disk
_PENDING_TRACKERS: list[tuple[str, str, str, ResilienceConfig]] = []


def _orbax():
    import orbax.checkpoint as ocp
    return ocp


def _get_async_checkpointer():
    global _ASYNC_CKPTR
    if _ASYNC_CKPTR is None:
        ocp = _orbax()
        _ASYNC_CKPTR = ocp.AsyncCheckpointer(ocp.StandardCheckpointHandler())
    return _ASYNC_CKPTR


def _write_text_atomic(path: str, text: str,
                       policy: RetryPolicy = RetryPolicy()) -> None:
    """Tracker/metadata writes: fault-injectable, retried, and atomic
    (tmp + rename — a crash mid-write can tear a direct tracker write,
    and a torn tracker strands EVERY restart until a human edits it)."""

    def _write():
        fault_point("checkpoint_write")
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write(text)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)

    retry(_write, policy, label=f"write:{os.path.basename(path)}")


def _publish(root: str, tag: str, d: str,
             resil: ResilienceConfig) -> None:
    """Seal + announce one durable checkpoint: manifest (integrity
    gate), then tracker (visibility), then retention (pruning — only
    after the new checkpoint is fully published)."""
    policy = policy_from(resil)
    if resil.checkpoint_integrity:
        retry(lambda: integrity.write_manifest(d), policy,
              label="write_manifest")
    _write_text_atomic(os.path.join(root, TRACKER), tag, policy)
    if resil.keep_last_k:
        integrity.apply_retention(root, resil.keep_last_k)


def finalize_async_saves() -> None:
    """Block until in-flight async saves are durable, then publish their
    manifest + tracker entries. Called automatically before the next
    save and must be called before process exit (the train loop does)."""
    global _PENDING_TRACKERS
    if _ASYNC_CKPTR is not None:
        _ASYNC_CKPTR.wait_until_finished()
    for root, tag, d, resil in _PENDING_TRACKERS:
        _publish(root, tag, d, resil)
    _PENDING_TRACKERS = []


def _iter_dir(root: str, iteration: int, release: bool = False) -> str:
    name = "release" if release else f"iter_{iteration:07d}"
    return os.path.join(root, name)


def _flatten(tree) -> dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(_path_str(p) for p in path)
        flat[key] = np.asarray(leaf)
    return flat


def _path_str(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return str(p.idx)
    if hasattr(p, "name"):
        return str(p.name)
    return str(p)


def _unflatten_like(example, flat: dict[str, np.ndarray], shardings=None):
    """Rebuild a pytree shaped like `example` from flat path->array, placing
    leaves onto `shardings` (same structure) when given."""
    paths_and_leaves = jax.tree_util.tree_flatten_with_path(example)
    treedef = jax.tree_util.tree_structure(example)
    sh_leaves = (jax.tree.leaves(shardings) if shardings is not None
                 else [None] * len(paths_and_leaves[0]))
    leaves = []
    for (path, ex), sh in zip(paths_and_leaves[0], sh_leaves):
        key = "/".join(_path_str(p) for p in path)
        if key not in flat:
            raise KeyError(f"checkpoint missing tensor {key!r}")
        arr = flat[key]
        if tuple(arr.shape) != tuple(ex.shape):
            raise ValueError(
                f"shape mismatch for {key}: ckpt {arr.shape} vs model {ex.shape}")
        arr = arr.astype(ex.dtype)
        leaves.append(jax.device_put(arr, sh) if sh is not None
                      else jnp.asarray(arr))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def save_checkpoint(
    root: str,
    state: TrainState,
    cfg: MegatronConfig,
    iteration: int,
    consumed_samples: int = 0,
    release: bool = False,
    backend: str = "orbax",
    async_save: bool = False,
    data_state: Optional[dict] = None,
    quarantine: Optional[list] = None,
) -> str:
    """(ref: checkpointing.py:243-337 save_checkpoint)

    backend="orbax" (default) writes per-device shards via TensorStore —
    a sharded state never gathers onto one host. backend="npz" keeps the
    round-1 single-file format. async_save=True returns once the save is
    scheduled; the manifest + tracker are published by
    `finalize_async_saves()` (run automatically before the next save),
    so a crash mid-write can never leave the tracker naming a torn
    checkpoint.

    Resilience (cfg.resilience, docs/resilience.md): every file write is
    retried with exponential backoff, a SHA-256 `manifest.json` seals
    the checkpoint before the tracker names it, and `keep_last_k` prunes
    old iter_* dirs after a successful publish."""
    finalize_async_saves()  # serialize with any in-flight save (all
    # backends: an npz tracker written now must not be regressed by a
    # pending async tracker publishing later)
    resil = getattr(cfg, "resilience", None) or ResilienceConfig()
    policy = policy_from(resil)
    d = _iter_dir(root, iteration, release)
    os.makedirs(d, exist_ok=True)
    tag = "release" if release else str(iteration)

    tree = {"params": state.params}
    if (state.opt_state is not None and not release
            and not cfg.training.no_save_optim):  # ref: --no_save_optim
        tree["opt_state"] = state.opt_state

    if backend == "orbax":
        ckptr = _get_async_checkpointer()
        ocp = _orbax()
        state_path = os.path.join(os.path.abspath(d), STATE_DIR)
        ckptr.save(state_path, args=ocp.args.StandardSave(tree), force=True)
        if not async_save:
            ckptr.wait_until_finished()
    elif backend == "npz":

        def _savez(path, tree_part):
            def _write():
                fault_point("checkpoint_write")
                np.savez(path, **_flatten(tree_part))
            retry(_write, policy, label=f"write:{os.path.basename(path)}")

        _savez(os.path.join(d, "params.npz"), state.params)
        if state.opt_state is not None and not release:
            _savez(os.path.join(d, "opt_state.npz"), state.opt_state)
    else:
        raise ValueError(f"unknown checkpoint backend {backend!r}")

    meta = {
        "iteration": int(iteration),
        "consumed_samples": int(consumed_samples),
        "release": release,
        "has_opt_state": "opt_state" in tree,
        "format_version": 2 if backend == "orbax" else 1,
    }
    if data_state is not None:
        # data-iterator exact-resume state (samplers.state_dict):
        # restoring it replays the identical batch sequence
        meta["data_state"] = data_state
    if quarantine:
        # poison-batch windows deterministically skipped by divergence
        # rollbacks (training/loop.py) — carried forward so a resumed
        # run keeps the audit trail
        meta["quarantine"] = list(quarantine)
    _write_text_atomic(os.path.join(d, "metadata.json"),
                       json.dumps(meta, indent=2), policy)
    _write_text_atomic(os.path.join(d, "config.json"), cfg.to_json(),
                       policy)
    if backend == "orbax" and async_save:
        # payload not yet durable: manifest + tracker (+ retention)
        # publish in finalize_async_saves
        _PENDING_TRACKERS.append((root, tag, d, resil))
    else:
        _publish(root, tag, d, resil)
    print_rank_0(f"saved checkpoint to {d} (iteration {iteration}"
                 f"{', async' if async_save else ''})")
    return d


def read_tracker(root: str,
                 policy: RetryPolicy = RetryPolicy()) -> Optional[str]:
    p = os.path.join(root, TRACKER)
    if not os.path.exists(p):
        return None

    def _read():
        fault_point("tracker_read")
        with open(p) as f:
            return f.read().strip()

    return retry(_read, policy, label="tracker_read")


def _dir_for_tag(root: str, tag: Optional[str]) -> Optional[str]:
    """Tracker tag -> checkpoint dir; None for a missing/empty/garbage
    tag (an empty or corrupted tracker file must read as "no
    checkpoint", not crash on int())."""
    if not tag:
        return None
    if tag == "release":
        return os.path.join(root, "release")
    try:
        return os.path.join(root, f"iter_{int(tag):07d}")
    except ValueError:
        print_rank_0(f"warning: tracker in {root} holds garbage "
                     f"({tag!r}); treating as no tracker and scanning "
                     "for the newest valid iter_* checkpoint")
        return None


@phase("load")
def load_checkpoint(
    root: str,
    example_state: TrainState,
    *,
    shardings: Optional[TrainState] = None,
    finetune: bool = False,
    no_load_optim: bool = False,
    resilience: Optional[ResilienceConfig] = None,
) -> LoadedCheckpoint:
    """Load newest checkpoint under `root`.

    Returns a `LoadedCheckpoint` — unpacks like the historical
    (state, iteration, consumed_samples) 3-tuple, with `.data_state` /
    `.quarantine` extras for exact data resume; (None, 0, 0) if absent
    (ref: checkpointing.py:561-643 load_checkpoint). `finetune` loads model
    weights only and resets iteration/optimizer (ref: --finetune).

    Robust to a bad tip: an empty/garbage tracker is treated as "no
    tracker", and (with `resilience.checkpoint_integrity`, the default)
    each candidate is verified against its SHA-256 manifest before any
    tensor is read — a torn/corrupt checkpoint is skipped with a warning
    and the newest VALID `iter_*` checkpoint loads instead. Only when no
    candidate survives does this return (None, 0, 0)."""
    resil = resilience or ResilienceConfig()
    policy = policy_from(resil)
    tag = read_tracker(root, policy)
    tracked = _dir_for_tag(root, tag)
    if tag is None and not integrity.list_iter_checkpoints(root):
        print_rank_0(f"no checkpoint tracker in {root}; starting from scratch")
        return LoadedCheckpoint(None, 0, 0)

    # candidate order: the tracker-named dir, then every other iter_*
    # dir newest-first (the fallback chain for a torn/corrupt tip)
    candidates = []
    if tracked is not None:
        candidates.append(tracked)
    for _, d2 in integrity.list_iter_checkpoints(root):
        if d2 not in candidates:
            candidates.append(d2)

    for d in candidates:
        if not os.path.isdir(d):
            print_rank_0(f"warning: tracker names missing checkpoint "
                         f"{d}; falling back")
            continue
        # integrity disabled = the caller opted out of fallback
        # machinery: restore errors propagate as before
        verified = not resil.checkpoint_integrity
        if resil.checkpoint_integrity:
            ok, why = integrity.verify_checkpoint(d)
            if not ok:
                print_rank_0(f"warning: checkpoint {d} failed integrity "
                             f"verification ({why}); falling back to "
                             "the previous valid checkpoint")
                continue
            verified = why == "ok"
            if not verified:
                print_rank_0(f"checkpoint {d}: {why}")
        try:
            with open(os.path.join(d, "metadata.json")) as f:
                meta = json.load(f)
        except (OSError, ValueError) as e:
            print_rank_0(f"warning: checkpoint {d} metadata unreadable "
                         f"({e}); falling back")
            continue
        try:
            return _restore_from_dir(d, meta, example_state,
                                     shardings=shardings,
                                     finetune=finetune,
                                     no_load_optim=no_load_optim)
        except Exception as e:  # noqa: BLE001 — see below
            if verified:
                # the payload checksummed clean, so this is a REAL
                # error (tree/shape mismatch, wrong model config) —
                # silently falling back would mask a misconfiguration
                raise
            # no manifest to vouch for this dir (e.g. an async save
            # whose process died before finalize published one): a
            # restore failure means it is torn — keep falling back
            print_rank_0(f"warning: restore from unverified checkpoint "
                         f"{d} failed ({type(e).__name__}: {e}); "
                         "falling back")
            continue

    print_rank_0(f"no valid checkpoint under {root}; starting from scratch")
    return LoadedCheckpoint(None, 0, 0)


def _restore_from_dir(
    d: str,
    meta: dict,
    example_state: TrainState,
    *,
    shardings: Optional[TrainState] = None,
    finetune: bool = False,
    no_load_optim: bool = False,
) -> LoadedCheckpoint:
    release = bool(meta.get("release", os.path.basename(d) == "release"))
    load_optim = (not finetune and not no_load_optim and not release
                  and example_state.opt_state is not None)
    state_path = os.path.join(os.path.abspath(d), STATE_DIR)
    if os.path.isdir(state_path):
        # orbax sharded restore: each leaf lands directly on its target
        # sharding — load-time resharding to any tp/pp/dp layout
        ocp = _orbax()

        def abstract(tree, sh_tree, default=None):
            sh_leaves = (jax.tree.leaves(sh_tree) if sh_tree is not None
                         else [default] * len(jax.tree.leaves(tree)))
            return jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(tree),
                [jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s)
                 for x, s in zip(jax.tree.leaves(tree), sh_leaves)])

        on_disk_opt = meta.get("has_opt_state", not release)

        def make_target(default_sharding):
            target = {"params": abstract(
                example_state.params,
                shardings.params if shardings is not None else None,
                default_sharding)}
            if load_optim and on_disk_opt:
                target["opt_state"] = abstract(
                    example_state.opt_state,
                    shardings.opt_state if shardings is not None else None,
                    default_sharding)
            return target

        def _restore_args(leaf):
            return ocp.ArrayRestoreArgs(
                sharding=getattr(leaf, "sharding", None) or None,
                global_shape=leaf.shape, dtype=leaf.dtype)

        def do_restore(target):
            # partial_restore: unwanted subtrees (optimizer moments for
            # finetune / inference loads) are never read off disk — a 70B
            # Adam state must not materialize just to be discarded.
            args = ocp.args.PyTreeRestore(
                item=target,
                restore_args=jax.tree.map(_restore_args, target),
                partial_restore=True)
            with ocp.PyTreeCheckpointer() as ckptr:
                return ckptr.restore(state_path, args=args)

        try:
            # no explicit shardings: let orbax re-apply the layout from
            # the save-time sharding file (sharded resume on one mesh)
            restored = do_restore(make_target(None))
        except ValueError as e:
            # the sharding file names devices that don't exist here (e.g.
            # TPU-saved checkpoint restored on CPU, or a resized mesh):
            # checkpoints are topology-free, so land everything on local
            # device 0 and let the caller's jit re-shard. Only
            # sharding/device-resolution failures are retried —
            # tree/shape mismatches must surface as-is.
            msg = str(e).lower()
            if "sharding" not in msg and "device" not in msg:
                raise
            restored = do_restore(make_target(
                jax.sharding.SingleDeviceSharding(jax.devices()[0])))
        params = restored["params"]
        opt_state = (restored["opt_state"] if load_optim and on_disk_opt
                     else example_state.opt_state)
    else:
        # legacy round-1 .npz format
        flat_p = dict(np.load(os.path.join(d, "params.npz")))
        params = _unflatten_like(
            example_state.params, flat_p,
            shardings.params if shardings is not None else None)
        opt_state = example_state.opt_state
        opt_path = os.path.join(d, "opt_state.npz")
        if load_optim and os.path.exists(opt_path):
            flat_o = dict(np.load(opt_path))
            opt_state = _unflatten_like(
                example_state.opt_state, flat_o,
                shardings.opt_state if shardings is not None else None)

    if finetune or release:
        # fresh run: the data stream restarts too — no exact-resume
        # state or quarantine history carries over
        iteration, consumed = 0, 0
        data_state, quarantine = None, []
    else:
        iteration = meta["iteration"]
        consumed = meta.get("consumed_samples", 0)
        data_state = meta.get("data_state")
        quarantine = meta.get("quarantine", [])

    state = TrainState(
        params=params, opt_state=opt_state,
        iteration=jnp.asarray(iteration, jnp.int32))
    print_rank_0(f"loaded checkpoint {d} (iteration {iteration}, "
                 f"consumed_samples {consumed}"
                 + (", exact data-resume state" if data_state else "")
                 + (f", {len(quarantine)} quarantined window(s)"
                    if quarantine else "") + ")")
    return LoadedCheckpoint(state, iteration, consumed,
                            data_state=data_state, quarantine=quarantine,
                            ckpt_dir=d)


def _unflatten_host(example, flat: dict[str, np.ndarray]):
    """Rebuild a pytree shaped like `example` from flat path->array with
    every leaf a HOST NumPy array (cast to the example dtype) — the
    no-device-transfer sibling of `_unflatten_like`, for weight-swap
    staging (serving/weights.py)."""
    paths_and_leaves = jax.tree_util.tree_flatten_with_path(example)
    treedef = jax.tree_util.tree_structure(example)
    leaves = []
    for path, ex in paths_and_leaves[0]:
        key = "/".join(_path_str(p) for p in path)
        if key not in flat:
            raise KeyError(f"checkpoint missing tensor {key!r}")
        arr = flat[key]
        if tuple(arr.shape) != tuple(ex.shape):
            raise ValueError(
                f"shape mismatch for {key}: ckpt {arr.shape} vs model "
                f"{ex.shape}")
        leaves.append(np.asarray(arr, dtype=ex.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def load_params_host(ckpt_dir: str, example_params):
    """Load ONLY the params tree from one checkpoint dir into HOST
    memory: every returned leaf is a NumPy array, no device transfer
    happens at any point, and the optimizer state is never read off
    disk. This is the weight-swap staging path (serving/weights.py
    `load_staged`) and the host-first serving startup path — the
    serving engine device-puts the staged tree straight onto its
    serving mesh(es), so device 0 never pays a full-model source copy
    on top of the shards (the PR 13 residency fix).

    Shapes are validated against `example_params` (which also supplies
    the dtype each leaf casts to); a mismatch raises — swapping a
    different model's checkpoint under a running engine must refuse,
    not reshape."""
    state_path = os.path.join(os.path.abspath(ckpt_dir), STATE_DIR)
    if os.path.isdir(state_path):
        # orbax sharded payload: restore each leaf as a plain
        # np.ndarray (RestoreArgs(restore_type=...)) — TensorStore
        # reads land in host RAM, nothing rides a device transfer
        ocp = _orbax()
        target = {"params": jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            example_params)}
        restore_args = jax.tree.map(
            lambda _: ocp.RestoreArgs(restore_type=np.ndarray), target)
        args = ocp.args.PyTreeRestore(
            item=target, restore_args=restore_args, partial_restore=True)
        with ocp.PyTreeCheckpointer() as ckptr:
            restored = ckptr.restore(state_path, args=args)
        flat_ex = jax.tree.leaves(example_params)
        flat_got = jax.tree.leaves(restored["params"])
        leaves = []
        for ex, got in zip(flat_ex, flat_got):
            arr = np.asarray(got)
            if tuple(arr.shape) != tuple(ex.shape):
                raise ValueError(
                    f"shape mismatch: ckpt {arr.shape} vs model "
                    f"{tuple(ex.shape)}")
            leaves.append(arr.astype(ex.dtype))
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(example_params), leaves)
    # legacy .npz payload
    flat = dict(np.load(os.path.join(ckpt_dir, "params.npz")))
    return _unflatten_host(example_params, flat)


def load_config_from_checkpoint(root: str) -> Optional[MegatronConfig]:
    """`use_checkpoint_args` (ref: checkpointing.py:476-558). Shares
    load_checkpoint's tolerance for a garbage tracker: falls back to
    the newest iter_* dir whose config is readable."""
    d = _dir_for_tag(root, read_tracker(root))
    candidates = ([d] if d is not None else []) + \
        [d2 for _, d2 in integrity.list_iter_checkpoints(root)
         if d2 != d]
    for c in candidates:
        try:
            with open(os.path.join(c, "config.json")) as f:
                return MegatronConfig.from_dict(json.load(f))
        except (OSError, ValueError):
            continue
    return None


def merge_restored_params(fresh, restored, *, label: str = "checkpoint"):
    """Leaf-wise overlay of a partial restore onto freshly initialized
    params: orbax partial_restore returns ShapeDtypeStruct placeholders for
    leaves absent on disk (e.g. a task head the pretraining checkpoint
    never had) — those keep the fresh init, and the skips are reported
    (a silently random subtree reads as a broken finetune)."""
    skipped = []

    def _merge(path, fresh_leaf, restored_leaf):
        if isinstance(restored_leaf, (jax.Array, np.ndarray)):
            return restored_leaf
        skipped.append(jax.tree_util.keystr(path))
        return fresh_leaf

    merged = jax.tree_util.tree_map_with_path(_merge, fresh, restored)
    if skipped:
        print_rank_0(f"{label}: kept fresh init for {len(skipped)} leaves "
                     f"absent on disk: {', '.join(skipped[:8])}"
                     f"{' ...' if len(skipped) > 8 else ''}")
    return merged
