"""Live-weight serving: host-side staging, the manifest gate, version
bookkeeping, and the checkpoint watcher (docs/serving.md "Live weights
& rolling upgrade").

The repo has both halves of the production loop — trainers that publish
checksummed checkpoints (resilience/integrity.py) and a replicated
serving fleet (serving/router.py) — but until this module a new
checkpoint meant stopping the world. The pieces here close the loop:

- `load_staged(ckpt_dir, example)`: load checkpoint N+1 into a
  HOST-side staging buffer (NumPy — nothing touches a device), after
  verifying it against the resilience layer's SHA-256 manifest. A
  corrupt, truncated, or mid-publish checkpoint is a typed
  `WeightSwapError` refusal BEFORE any tensor rides a transfer — the
  engine keeps serving the current weights, never wrong ones. (The
  tracker publishes only after the manifest is durable, so a
  manifest-less dir IS a mid-publish dir; the gate refuses it.)
- `WeightVersion`: checkpoint iteration + manifest digest — the value
  that threads through `health()`, `/healthz`, `/metrics`
  (`weight_version` gauge), and every SSE start frame so a
  mixed-version fleet is observable.
- `host_params(params)`: hold a Generator's source weights host-side
  (NumPy), so `topology.place_params` sharding is the ONLY device
  residency — the fix for the PR 13 limit where device 0 paid
  full-model + shard residency. Engine construction and hot swap now
  share one mechanism: stage host-first, then `device_put` per group.
- `CheckpointWatcher`: polls the training tracker
  (`--watch_checkpoints`) and drives `rolling_upgrade` /
  `swap_weights` when a new checkpoint publishes — trainers upgrade
  the fleet with zero operator action. A refused checkpoint is counted
  (`weight_swap_failures`) and NOT retried until the tracker names a
  NEW one: no restart loop on a corrupt publish.

The consumers are `ServingEngine.swap_weights` (in-place hot swap
between engine iterations — serving/engine.py) and
`EngineRouter.rolling_upgrade` (drain → swap → canary → re-admit, one
replica at a time — serving/router.py).
"""
from __future__ import annotations

import hashlib
import json
import os
import threading
import time
from typing import Optional

import numpy as np

from megatron_tpu.resilience import integrity
from megatron_tpu.utils.logging import print_rank_0
from megatron_tpu.utils.tracing import phase


class WeightSwapError(RuntimeError):
    """Typed refusal: the checkpoint failed the manifest gate, could
    not be staged host-side, or the swap could not be applied. The
    engine that raised it KEEPS SERVING its current weights — a refusal
    is always safe, wrong weights never are."""


class WeightVersion:
    """What the fleet is serving: the checkpoint iteration plus a short
    digest of its manifest (content-addressed — two different payloads
    at the same iteration get different digests)."""

    __slots__ = ("iteration", "digest")

    def __init__(self, iteration: int, digest: str):
        self.iteration = int(iteration)
        self.digest = str(digest)

    @property
    def label(self) -> str:
        return f"{self.iteration}:{self.digest}"

    def __eq__(self, other):
        return (isinstance(other, WeightVersion)
                and other.iteration == self.iteration
                and other.digest == self.digest)

    def __hash__(self):
        return hash((self.iteration, self.digest))

    def __repr__(self):
        return f"WeightVersion({self.label})"


class StagedWeights:
    """A checkpoint staged HOST-side: the params pytree with every leaf
    a NumPy array (cast to the serving dtypes), plus its version. This
    is the unit the engine device-puts onto the serving mesh(es) at the
    swap point — and the unit a host-first engine CONSTRUCTION places
    at startup, so both paths share one mechanism."""

    __slots__ = ("params", "version", "ckpt_dir")

    def __init__(self, params, version: WeightVersion,
                 ckpt_dir: Optional[str] = None):
        self.params = params
        self.version = version
        self.ckpt_dir = ckpt_dir


def host_params(params):
    """Copy a params pytree to HOST memory (NumPy leaves). A Generator
    built over the result holds no device copy of the weights at all —
    the serving engine's `place_params` sharding (or its one
    `device_put` on topology-free engines) becomes the only device
    residency, erasing the PR 13 double-residency limit."""
    import jax
    return jax.tree.map(lambda x: np.asarray(x), params)


def manifest_digest(ckpt_dir: str) -> str:
    """Short content digest of the checkpoint's manifest (the manifest
    itself digests every payload file, so this is transitively a
    content address for the whole checkpoint)."""
    path = os.path.join(ckpt_dir, integrity.MANIFEST)
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()[:12]


@phase("load")
def load_staged(ckpt_dir: str, example_params, *,
                require_manifest: bool = True) -> StagedWeights:
    """Verify + stage one checkpoint HOST-side. The order is the
    contract: the SHA-256 manifest verifies FIRST (deep — every payload
    file re-digested), the params load into NumPy second, and no
    device is touched at any point — so a corrupt, truncated, or
    mid-publish checkpoint is refused with `WeightSwapError` while the
    caller's current weights keep serving untouched.

    `example_params` supplies the expected tree structure, shapes, and
    dtypes (a shape mismatch is a refusal too — swapping a DIFFERENT
    model is not a weight update). `require_manifest=False` admits
    legacy pre-manifest checkpoints (valid-with-warning) for STARTUP
    staging; the swap path keeps the default — a manifest-less dir is
    indistinguishable from a torn mid-publish one."""
    ok, why = integrity.verify_checkpoint(ckpt_dir, deep=True)
    if not ok:
        raise WeightSwapError(
            f"checkpoint {ckpt_dir} refused at the manifest gate: {why} "
            "(nothing touched a device; the current weights keep "
            "serving)")
    unverified = why != "ok"
    if unverified and require_manifest:
        raise WeightSwapError(
            f"checkpoint {ckpt_dir} refused at the manifest gate: no "
            "manifest.json — either a pre-manifest legacy dir or a "
            "mid-publish checkpoint whose payload is not yet sealed; "
            "the swap gate cannot tell them apart (the current weights "
            "keep serving)")
    try:
        with open(os.path.join(ckpt_dir, "metadata.json")) as f:
            meta = json.load(f)
        iteration = int(meta.get("iteration", 0))
    except (OSError, ValueError) as e:
        raise WeightSwapError(
            f"checkpoint {ckpt_dir} metadata unreadable ({e}); refused "
            "before any device transfer") from e
    try:
        from megatron_tpu.training.checkpointing import load_params_host
        params = load_params_host(ckpt_dir, example_params)
    except WeightSwapError:
        raise
    except Exception as e:  # noqa: BLE001 — any staging failure refuses
        raise WeightSwapError(
            f"checkpoint {ckpt_dir} failed host-side staging "
            f"({type(e).__name__}: {e}); refused before any device "
            "transfer — the current weights keep serving") from e
    digest = (manifest_digest(ckpt_dir) if not unverified
              else "unverified")
    return StagedWeights(params, WeightVersion(iteration, digest),
                         ckpt_dir=ckpt_dir)


def stage_latest(root: str, example_params) -> StagedWeights:
    """Resolve the newest loadable checkpoint under `root` — the
    tracker-named dir first, then every other `iter_*` dir newest-first
    (the `load_checkpoint` candidate order) — and stage it HOST-side.
    The serving-startup path: unlike the swap gate, legacy
    manifest-less dirs are admitted (`require_manifest=False`) — at
    startup there is no old version to keep serving, so
    valid-with-warning beats refusing to start. Raises
    `WeightSwapError` when nothing under `root` stages."""
    from megatron_tpu.training.checkpointing import (_dir_for_tag,
                                                     read_tracker)
    candidates = []
    d = _dir_for_tag(root, read_tracker(root))
    if d is not None:
        candidates.append(d)
    for _, d2 in integrity.list_iter_checkpoints(root):
        if d2 not in candidates:
            candidates.append(d2)
    last_err: Optional[Exception] = None
    for d in candidates:
        if not os.path.isdir(d):
            continue
        try:
            return load_staged(d, example_params, require_manifest=False)
        except WeightSwapError as e:
            last_err = e
            print_rank_0(f"weights: checkpoint {d} refused ({e}); "
                         "falling back to the previous one")
    raise WeightSwapError(
        f"no stageable checkpoint under {root}"
        + (f" (last refusal: {last_err})" if last_err else ""))


class CheckpointWatcher:
    """Poll a training checkpoint root's tracker and drive the serving
    side to the newest published checkpoint — the zero-operator-action
    half of the training→serving loop (`--watch_checkpoints`).

    `target` is an `EngineRouter` (fleet: `rolling_upgrade` — drain →
    swap → canary → re-admit per replica, zero 503s) or a bare
    `ServingEngine` (`swap_weights`). Failure discipline: a refused or
    failed swap is logged and remembered by TAG — the watcher does NOT
    hammer the same publish (no restart loop on a corrupt checkpoint);
    a NEW tracker tag tries immediately, and the SAME tag re-tries only
    after a long backoff (transient refusals like a drain timeout on a
    busy engine must not permanently strand the fleet on old weights
    when this was the trainer's final publish). The engine/router count
    `weight_swap_failures` themselves, so the watcher adds no double
    accounting."""

    def __init__(self, target, root: str, interval_s: float = 5.0,
                 initial_tag: Optional[str] = None):
        self.target = target
        self.root = str(root)
        self.interval_s = max(float(interval_s), 0.05)
        # `initial_tag`: the tracker tag the target ALREADY serves
        # (host-first startup staging) — without it the first poll
        # would redundantly re-swap the very checkpoint the fleet
        # booted from
        self.applied: Optional[str] = initial_tag
        self.failed: Optional[str] = None    # last tag refused
        self.failures = 0
        self._last_tried: Optional[str] = initial_tag
        self._retry_at = 0.0  # failed-tag backoff deadline
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="ckpt-watcher")

    def start(self):
        self._thread.start()
        return self

    def close(self):
        self._stop.set()
        if self._thread.ident is not None:
            self._thread.join(timeout=10)

    def _run(self):
        while not self._stop.wait(self.interval_s):
            try:
                self.poll_once()
            except Exception as e:  # noqa: BLE001 — the watcher outlives
                #                     any single bad poll
                print_rank_0(f"checkpoint watcher: poll failed ({e!r})")

    def poll_once(self) -> bool:
        """One poll beat (public so tests and tools can drive it
        synchronously). Returns True when a swap/upgrade was APPLIED
        this beat."""
        from megatron_tpu.training.checkpointing import (_dir_for_tag,
                                                         read_tracker)
        try:
            tag = read_tracker(self.root)
        except Exception:  # noqa: BLE001 — racing a publish; next beat
            return False
        if not tag:
            return False
        if tag == self._last_tried:
            if self.failed != tag:
                return False  # already applied (or applying)
            if time.monotonic() < self._retry_at:
                return False  # refused tag: long backoff, no hammering
        d = _dir_for_tag(self.root, tag)
        if d is None or not os.path.isdir(d):
            return False
        self._last_tried = tag
        try:
            if hasattr(self.target, "rolling_upgrade"):
                version = self.target.rolling_upgrade(d)
            else:
                version = self.target.swap_weights(d)
        except Exception as e:  # noqa: BLE001 — refusal/failure is safe
            self.failed = tag
            self.failures += 1
            self._retry_at = time.monotonic() + max(
                self.interval_s * 10, 60.0)
            print_rank_0(
                f"checkpoint watcher: swap to {d} refused/failed "
                f"({e}); the fleet keeps its current weights — "
                "retrying on the next publish (or this one after a "
                "backoff)")
            return False
        self.failed = None
        self.applied = tag
        label = version.label if version is not None else tag
        print_rank_0(f"checkpoint watcher: fleet now serving {label} "
                     f"(tracker tag {tag})")
        return True
