"""ctypes binding for the native index builders, with numpy fallbacks.

Replaces the reference's pybind11 `helpers` module and its on-demand build
(reference: fengshen/data/megatron_dataloader/dataset_utils.py:77-88
`compile_helper`). The shared object is never checked in: on first use
it is built from `native/index_helpers.cpp` with make. Where that fails
(no toolchain) the pure-numpy builders take over — same results, slower
— and a log line says so.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import sys
from typing import Optional

import numpy as np

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))), "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libindex_helpers.so")

_lib: Optional[ctypes.CDLL] = None
_lib_tried = False


def compile_helper() -> Optional[str]:
    """Build the shared object (reference: dataset_utils.py:77-88).
    Returns None on success, else why it could not be built."""
    try:
        subprocess.run(["make", "-C", _NATIVE_DIR], check=True,
                       capture_output=True, text=True)
    except OSError as e:                       # no make on this machine
        return f"{type(e).__name__}: {e}"
    except subprocess.CalledProcessError as e:  # no compiler / bad build
        return f"make failed: {(e.stderr or '').strip()[-300:]}"
    return None


def _get_lib() -> Optional[ctypes.CDLL]:
    global _lib, _lib_tried
    if _lib is not None or _lib_tried:
        return _lib
    _lib_tried = True
    why = None if os.path.exists(_LIB_PATH) else compile_helper()
    if why is None:
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError as e:
            why = f"{type(e).__name__}: {e}"
    if why is not None:
        print("[fengshen-tpu] native index helpers unavailable "
              f"({why}); the numpy index builders are in use",
              file=sys.stderr, flush=True)
        return None
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    i8p = ctypes.POINTER(ctypes.c_int8)
    dp = ctypes.POINTER(ctypes.c_double)
    lib.build_sample_idx.argtypes = [i32p, i32p, ctypes.c_int64,
                                     ctypes.c_int32, ctypes.c_int32,
                                     ctypes.c_int64, i32p, ctypes.c_int64]
    lib.build_blending_indices.argtypes = [i8p, i64p, dp, ctypes.c_int32,
                                           ctypes.c_int64, ctypes.c_int32]
    lib.build_mapping.argtypes = [i64p, ctypes.c_int64, i32p,
                                  ctypes.c_int32, ctypes.c_double,
                                  ctypes.c_int32, i64p, ctypes.c_int64]
    lib.build_mapping.restype = ctypes.c_int64
    lib.build_blocks_mapping.argtypes = [i64p, ctypes.c_int64, i32p,
                                         ctypes.c_int32, i64p,
                                         ctypes.c_int64]
    lib.build_blocks_mapping.restype = ctypes.c_int64
    _lib = lib
    return _lib


def _ptr(arr: np.ndarray, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


def build_sample_idx(sizes: np.ndarray, doc_idx: np.ndarray,
                     seq_length: int, num_epochs: int,
                     tokens_per_epoch: int) -> np.ndarray:
    """[(num_samples+1), 2] (document position, token offset) boundaries."""
    sizes = np.ascontiguousarray(sizes, np.int32)
    doc_idx = np.ascontiguousarray(doc_idx, np.int32)
    total_tokens = int(sizes[doc_idx].sum())
    num_samples = max((total_tokens - 1) // seq_length, 1)
    lib = _get_lib()
    if lib is not None:
        out = np.zeros((num_samples + 1, 2), np.int32)
        lib.build_sample_idx(_ptr(sizes, ctypes.c_int32),
                             _ptr(doc_idx, ctypes.c_int32),
                             len(doc_idx), seq_length, num_epochs,
                             tokens_per_epoch,
                             _ptr(out, ctypes.c_int32), num_samples)
        return out
    # numpy fallback
    out = np.zeros((num_samples + 1, 2), np.int32)
    doc_pos, doc_offset = 0, 0
    for s in range(1, num_samples + 1):
        remaining = seq_length + 1
        while remaining > 0 and doc_pos < len(doc_idx):
            doc_len = int(sizes[doc_idx[doc_pos]]) - doc_offset
            if doc_len >= remaining:
                # one-token overlap (reference: helpers.cpp:165): next
                # sample re-starts at this sample's last (label) token
                doc_offset += remaining - 1
                remaining = 0
            else:
                remaining -= doc_len
                doc_pos += 1
                doc_offset = 0
        out[s] = (doc_pos, doc_offset)
        if doc_pos >= len(doc_idx):
            out[s + 1:] = out[s]
            break
    return out


def build_blending_indices(weights: np.ndarray, size: int,
                           verbose: bool = False
                           ) -> tuple[np.ndarray, np.ndarray]:
    weights = np.ascontiguousarray(weights, np.float64)
    weights = weights / weights.sum()
    lib = _get_lib()
    dataset_index = np.zeros((size,), np.int8)
    dataset_sample_index = np.zeros((size,), np.int64)
    if lib is not None:
        lib.build_blending_indices(
            _ptr(dataset_index, ctypes.c_int8),
            _ptr(dataset_sample_index, ctypes.c_int64),
            _ptr(weights, ctypes.c_double), len(weights), size,
            int(verbose))
        return dataset_index, dataset_sample_index
    counts = np.zeros((len(weights),), np.int64)
    for i in range(size):
        gaps = weights * (i + 1) - counts
        best = int(gaps.argmax())
        dataset_index[i] = best
        dataset_sample_index[i] = counts[best]
        counts[best] += 1
    return dataset_index, dataset_sample_index


def build_mapping(docs: np.ndarray, sizes: np.ndarray, max_seq_length: int,
                  short_seq_prob: float, seed: int) -> np.ndarray:
    """[(N, 3)] (start sentence, end sentence, target length) windows."""
    docs = np.ascontiguousarray(docs, np.int64)
    sizes = np.ascontiguousarray(sizes, np.int32)
    lib = _get_lib()
    if lib is None:
        raise RuntimeError(
            "native index helpers unavailable; run make -C native "
            "(build_mapping has no numpy fallback)")
    null = ctypes.POINTER(ctypes.c_int64)()
    count = lib.build_mapping(_ptr(docs, ctypes.c_int64), len(docs) - 1,
                              _ptr(sizes, ctypes.c_int32), max_seq_length,
                              short_seq_prob, seed, null, 0)
    out = np.zeros((count, 3), np.int64)
    lib.build_mapping(_ptr(docs, ctypes.c_int64), len(docs) - 1,
                      _ptr(sizes, ctypes.c_int32), max_seq_length,
                      short_seq_prob, seed, _ptr(out, ctypes.c_int64),
                      count)
    return out


def build_blocks_mapping(docs: np.ndarray, sizes: np.ndarray,
                         max_seq_length: int) -> np.ndarray:
    docs = np.ascontiguousarray(docs, np.int64)
    sizes = np.ascontiguousarray(sizes, np.int32)
    lib = _get_lib()
    if lib is None:
        raise RuntimeError("native index helpers unavailable; run "
                           "make -C native")
    null = ctypes.POINTER(ctypes.c_int64)()
    count = lib.build_blocks_mapping(_ptr(docs, ctypes.c_int64),
                                     len(docs) - 1,
                                     _ptr(sizes, ctypes.c_int32),
                                     max_seq_length, null, 0)
    out = np.zeros((count, 3), np.int64)
    lib.build_blocks_mapping(_ptr(docs, ctypes.c_int64), len(docs) - 1,
                             _ptr(sizes, ctypes.c_int32), max_seq_length,
                             _ptr(out, ctypes.c_int64), count)
    return out
