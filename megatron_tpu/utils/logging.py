"""Logging, metric writers, and the training dashboard.

TPU-native equivalent of the reference's observability stack
(ref: megatron/global_vars.py:119-153 TB writer, megatron/wandb_logger.py:13-173
wandb shim, megatron/training.py:452-626 training_log,
megatron/utils.py:197-228 print helpers). Single-controller JAX: every host
runs the same program, so `print_rank_0` becomes plain logging gated on
process index.
"""
from __future__ import annotations

import logging
import sys
from typing import Optional

import jax

logger = logging.getLogger("megatron_tpu")
if not logger.handlers:
    _h = logging.StreamHandler(sys.stdout)
    _h.setFormatter(logging.Formatter("%(asctime)s %(levelname)s %(message)s"))
    logger.addHandler(_h)
    logger.setLevel(logging.INFO)


def print_rank_0(msg: str):
    """(ref: megatron/utils.py:197-204) — log only on the first host."""
    if jax.process_index() == 0:
        logger.info(msg)


class NullWriter:
    def add_scalar(self, *a, **k):
        pass

    def add_text(self, *a, **k):
        pass

    def flush(self):
        pass


class TensorBoardWriter(NullWriter):
    """Thin TB writer (ref: global_vars.py:119-153). Gated on availability —
    torch's SummaryWriter is present in this image via torch (cpu)."""

    def __init__(self, log_dir: str):
        from torch.utils.tensorboard import SummaryWriter
        self._w = SummaryWriter(log_dir=log_dir)

    def add_scalar(self, tag, value, step):
        self._w.add_scalar(tag, float(value), int(step))

    def add_text(self, tag, text, step=0):
        self._w.add_text(tag, text, int(step))

    def flush(self):
        self._w.flush()


class WandbWriter(NullWriter):
    """TB-compatible wandb shim (ref: wandb_logger.py:90-161): buffers scalars
    per step and commits when the step advances."""

    def __init__(self, project: str = "megatron_tpu",
                 name: Optional[str] = None, config: Optional[dict] = None,
                 entity: Optional[str] = None, run_id: Optional[str] = None,
                 resume: bool = False):
        import wandb
        self._wandb = wandb
        self._run = wandb.init(
            project=project, name=name, config=config or {}, entity=entity,
            id=run_id, resume="must" if resume and run_id else
            ("allow" if resume else None))
        self._step = None
        self._buf: dict = {}

    def add_scalar(self, tag, value, step):
        if self._step is not None and step != self._step:
            self._wandb.log(self._buf, step=self._step)
            self._buf = {}
        self._step = step
        self._buf[tag] = float(value)

    def flush(self):
        if self._buf:
            self._wandb.log(self._buf, step=self._step)
            self._buf = {}


def make_writer(tensorboard_dir: Optional[str] = None,
                use_wandb: bool = False, **wandb_kwargs):
    """Writer factory; last-process-only like the reference (TB on last rank,
    ref: global_vars.py:142-153; wandb on last rank, wandb_logger.py:44-56)."""
    if jax.process_index() != jax.process_count() - 1:
        return NullWriter()
    if use_wandb:
        try:
            return WandbWriter(**wandb_kwargs)
        except Exception as e:  # wandb not installed / no creds
            logger.warning(f"wandb unavailable ({e}); falling back")
    if tensorboard_dir:
        try:
            return TensorBoardWriter(tensorboard_dir)
        except Exception as e:
            logger.warning(f"tensorboard unavailable ({e})")
    return NullWriter()


def report_memory(name: str = "") -> str:
    """Per-device HBM usage line after the first step
    (ref: megatron/utils.py:82-96 report_memory; CUDA
    allocated/reserved becomes PJRT bytes_in_use/peak_bytes_in_use).
    Returns "" when the backend exposes no stats (CPU)."""
    parts = []
    for d in jax.local_devices():
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:
            pass
        if not stats:
            continue
        gib = 1024 ** 3
        used = stats.get("bytes_in_use", 0) / gib
        peak = stats.get("peak_bytes_in_use", 0) / gib
        limit = stats.get("bytes_limit", 0) / gib
        parts.append(f"{d.id}: used {used:.2f} GiB | peak {peak:.2f} GiB"
                     + (f" | limit {limit:.2f} GiB" if limit else ""))
    if not parts:
        return ""
    line = f"[memory{' ' + name if name else ''}] " + " || ".join(parts)
    print_rank_0(line)
    return line
