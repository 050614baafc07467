"""ctypes loader for the native data helpers.

TPU-native replacement for the reference's runtime-compiled pybind11 module
(ref: megatron/data/Makefile:1-9, megatron/data/dataset_utils.py:82-92
`compile_helper`). Same compile-on-first-use behavior, but via g++ + ctypes —
pybind11 is not available in this image.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "helpers.cpp")
_lock = threading.Lock()
_lib = None


def _so_path() -> str:
    """The binary is keyed by a hash of its source: a binary left on
    disk by another version of helpers.cpp (git ignores *.so, a copy of
    the tree does not) is never loaded, whatever its mtime says."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(_HERE, f"_helpers_{digest}.so")


def _compile(so: str):
    # build beside the target and rename: several processes (pytest
    # workers) may build at once, and none may load a half-written file
    tmp = f"{so}.{os.getpid()}.tmp"
    subprocess.run(
        ["g++", "-O3", "-shared", "-fPIC", "-o", tmp, _SRC],
        check=True, capture_output=True)
    os.replace(tmp, so)


def _load():
    global _lib
    with _lock:
        if _lib is not None:
            return _lib
        so = _so_path()
        if not os.path.exists(so):
            _compile(so)
        lib = ctypes.CDLL(so)
        lib.build_sample_idx.argtypes = [
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int64, ctypes.c_int32, ctypes.c_int32, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32)]
        lib.build_sample_idx.restype = None
        lib.build_blending_indices.argtypes = [
            ctypes.POINTER(ctypes.c_double), ctypes.c_int32, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_uint8), ctypes.POINTER(ctypes.c_int64)]
        lib.build_blending_indices.restype = None
        lib.build_mapping.argtypes = [
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32), ctypes.c_int32, ctypes.c_uint64,
            ctypes.c_int32, ctypes.c_double, ctypes.c_int32, ctypes.c_int32,
            ctypes.POINTER(ctypes.c_int64)]
        lib.build_mapping.restype = ctypes.c_int64
        lib.build_blocks_mapping.argtypes = [
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int32, ctypes.c_uint64, ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int32, ctypes.POINTER(ctypes.c_int64)]
        lib.build_blocks_mapping.restype = ctypes.c_int64
        _lib = lib
        return lib


def _ptr(arr, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


def build_sample_idx_native(sizes: np.ndarray, doc_idx: np.ndarray,
                            seq_length: int, num_epochs: int,
                            tokens_per_epoch: int) -> np.ndarray:
    lib = _load()
    sizes = np.ascontiguousarray(sizes, dtype=np.int32)
    doc_idx = np.ascontiguousarray(doc_idx, dtype=np.int32)
    num_samples = (num_epochs * tokens_per_epoch - 1) // seq_length
    out = np.zeros((num_samples + 1, 2), dtype=np.int32)
    lib.build_sample_idx(
        _ptr(sizes, ctypes.c_int32), _ptr(doc_idx, ctypes.c_int32),
        ctypes.c_int64(len(doc_idx)), ctypes.c_int32(seq_length),
        ctypes.c_int32(num_epochs), ctypes.c_int64(tokens_per_epoch),
        _ptr(out, ctypes.c_int32))
    return out


def build_mapping_native(docs: np.ndarray, sizes: np.ndarray, *,
                         num_epochs: int, max_num_samples: int,
                         max_seq_length: int, short_seq_prob: float,
                         seed: int, min_num_sent: int = 2) -> np.ndarray:
    """Sentence-pair sample map [n, 3] of (start sentence, end sentence,
    target seq len) — the reference's build_mapping contract
    (ref: megatron/data/helpers.cpp:188-451)."""
    lib = _load()
    docs = np.ascontiguousarray(docs, dtype=np.int64)
    sizes = np.ascontiguousarray(sizes, dtype=np.int32)
    args = [_ptr(docs, ctypes.c_int64), ctypes.c_int64(len(docs) - 1),
            _ptr(sizes, ctypes.c_int32), ctypes.c_int32(num_epochs),
            ctypes.c_uint64(max_num_samples),
            ctypes.c_int32(max_seq_length),
            ctypes.c_double(short_seq_prob), ctypes.c_int32(seed),
            ctypes.c_int32(min_num_sent)]
    n = lib.build_mapping(*args, None)
    out = np.zeros((n, 3), dtype=np.int64)
    lib.build_mapping(*args, _ptr(out, ctypes.c_int64))
    return out


def build_blocks_mapping_native(docs: np.ndarray, sizes: np.ndarray,
                                titles_sizes: np.ndarray, *,
                                num_epochs: int, max_num_samples: int,
                                max_seq_length: int, seed: int,
                                use_one_sent_blocks: bool = False
                                ) -> np.ndarray:
    """ICT/REALM block map [n, 4] of (start sentence, end sentence, doc,
    block id) — the reference's build_blocks_mapping contract
    (ref: megatron/data/helpers.cpp:453-670)."""
    lib = _load()
    docs = np.ascontiguousarray(docs, dtype=np.int64)
    sizes = np.ascontiguousarray(sizes, dtype=np.int32)
    titles_sizes = np.ascontiguousarray(titles_sizes, dtype=np.int32)
    args = [_ptr(docs, ctypes.c_int64), ctypes.c_int64(len(docs) - 1),
            _ptr(sizes, ctypes.c_int32), _ptr(titles_sizes, ctypes.c_int32),
            ctypes.c_int32(num_epochs), ctypes.c_uint64(max_num_samples),
            ctypes.c_int32(max_seq_length), ctypes.c_int32(seed),
            ctypes.c_int32(int(use_one_sent_blocks))]
    n = lib.build_blocks_mapping(*args, None)
    out = np.zeros((n, 4), dtype=np.int64)
    lib.build_blocks_mapping(*args, _ptr(out, ctypes.c_int64))
    return out


def build_blending_indices_native(weights: np.ndarray, size: int):
    lib = _load()
    weights = np.ascontiguousarray(weights, dtype=np.float64)
    assert len(weights) <= 256
    dataset_index = np.zeros(size, dtype=np.uint8)
    dataset_sample_index = np.zeros(size, dtype=np.int64)
    lib.build_blending_indices(
        _ptr(weights, ctypes.c_double), ctypes.c_int32(len(weights)),
        ctypes.c_int64(size), _ptr(dataset_index, ctypes.c_uint8),
        _ptr(dataset_sample_index, ctypes.c_int64))
    return dataset_index, dataset_sample_index
