"""Loader for the native dataset index builders.

ref analogue: megatron/data/dataset_utils.py `compile_helper` +
`from megatron.data import helpers`. Here the C++ is compiled once with g++
into `_helpers.so` next to the source (built on demand, never committed)
and bound via ctypes; a pure-numpy fallback keeps everything working when
no compiler is available. Which of the two is in use is printed once.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional, Tuple

import numpy as np

_CSRC = os.path.join(os.path.dirname(__file__), "csrc")
_SO_PATH = os.path.join(_CSRC, "_helpers.so")
_LIB: Optional[ctypes.CDLL] = None
_TRIED = False


def _compile() -> Optional[str]:
    """Build _helpers.so; returns None on success, else why it failed."""
    src = os.path.join(_CSRC, "helpers.cpp")
    try:
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-o", _SO_PATH, src],
            check=True,
            capture_output=True,
            timeout=120,
        )
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"{type(e).__name__}: {e}"
    except subprocess.CalledProcessError as e:
        return "g++ failed: " + e.stderr.decode(errors="replace")[-500:]
    return None


def _load() -> Optional[ctypes.CDLL]:
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    _TRIED = True
    why = None
    if not os.path.exists(_SO_PATH) or os.path.getmtime(_SO_PATH) < os.path.getmtime(
        os.path.join(_CSRC, "helpers.cpp")
    ):
        why = _compile()
    lib = None
    if why is None:
        try:
            lib = ctypes.CDLL(_SO_PATH)
        except OSError as e:
            why = f"OSError: {e}"
    if lib is None:
        print(f"dataset index builder: numpy fallback ({why})", flush=True)
        return None
    print(f"dataset index builder: native ({_SO_PATH})", flush=True)
    lib.build_sample_idx.argtypes = [
        ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int32,
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int32),
    ]
    lib.build_blending_indices.argtypes = [
        ctypes.POINTER(ctypes.c_uint8),
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_double),
        ctypes.c_int32,
        ctypes.c_int64,
    ]
    lib.build_mapping.argtypes = [
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int32,
        ctypes.c_uint64, ctypes.c_int32, ctypes.c_double, ctypes.c_int32,
        ctypes.c_int32, ctypes.POINTER(ctypes.c_int64),
    ]
    lib.build_mapping.restype = ctypes.c_int64
    lib.build_blocks_mapping.argtypes = [
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
        ctypes.c_int32, ctypes.c_uint64, ctypes.c_int32, ctypes.c_int32,
        ctypes.c_int32, ctypes.POINTER(ctypes.c_int64),
    ]
    lib.build_blocks_mapping.restype = ctypes.c_int64
    _LIB = lib
    return lib


def _ptr(arr: np.ndarray, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


def build_sample_idx(
    sizes: np.ndarray,
    doc_idx: np.ndarray,
    seq_length: int,
    num_epochs: int,
    tokens_per_epoch: int,
) -> np.ndarray:
    """(num_samples+1, 2) int32 array of (doc_idx_index, doc_offset)
    (ref: helpers.cpp:83-175)."""
    sizes = np.ascontiguousarray(sizes, np.int32)
    doc_idx = np.ascontiguousarray(doc_idx, np.int32)
    num_samples = (num_epochs * tokens_per_epoch - 1) // seq_length
    out = np.zeros((num_samples + 1, 2), np.int32)
    lib = _load()
    if lib is not None:
        lib.build_sample_idx(
            _ptr(sizes, ctypes.c_int32),
            _ptr(doc_idx, ctypes.c_int32),
            seq_length,
            num_epochs,
            tokens_per_epoch,
            _ptr(out, ctypes.c_int32),
        )
        return out
    return _build_sample_idx_np(sizes, doc_idx, seq_length, num_samples)


def _build_sample_idx_np(sizes, doc_idx, seq_length, num_samples):
    """Numpy fallback (ref python twin: gpt_dataset.py:449-491)."""
    out = np.zeros((num_samples + 1, 2), np.int32)
    doc_idx_index = 0
    doc_offset = 0
    for s in range(1, num_samples + 1):
        remaining = seq_length + 1
        while remaining != 0:
            doc_length = sizes[doc_idx[doc_idx_index]] - doc_offset
            remaining -= doc_length
            if remaining <= 0:
                doc_offset += remaining + doc_length - 1
                remaining = 0
            else:
                doc_idx_index += 1
                doc_offset = 0
        out[s, 0] = doc_idx_index
        out[s, 1] = doc_offset
    return out


def build_blending_indices(
    weights: np.ndarray, size: int
) -> Tuple[np.ndarray, np.ndarray]:
    """(dataset_index uint8[size], dataset_sample_index int64[size])
    (ref: helpers.cpp:20-81)."""
    weights = np.ascontiguousarray(weights, np.float64)
    dataset_index = np.zeros(size, np.uint8)
    dataset_sample_index = np.zeros(size, np.int64)
    lib = _load()
    if lib is not None:
        lib.build_blending_indices(
            _ptr(dataset_index, ctypes.c_uint8),
            _ptr(dataset_sample_index, ctypes.c_int64),
            _ptr(weights, ctypes.c_double),
            len(weights),
            size,
        )
        return dataset_index, dataset_sample_index
    # numpy fallback
    current = np.zeros(len(weights), np.int64)
    for i in range(size):
        i_d = max(float(i), 1.0)
        err = weights * i_d - current
        best = int(np.argmax(err))
        dataset_index[i] = best
        dataset_sample_index[i] = current[best]
        current[best] += 1
    return dataset_index, dataset_sample_index


def build_mapping(
    docs: np.ndarray,  # (n_docs+1,) int64 sentence-boundary offsets
    sizes: np.ndarray,  # per-sentence token counts, int32
    num_epochs: int,
    max_num_samples: int,
    max_seq_length: int,
    short_seq_prob: float,
    seed: int,
    min_num_sent: int = 2,
) -> np.ndarray:
    """(num_samples, 3) int64 rows of (start_sent, end_sent, target_len)
    for BERT-style pair datasets (ref: helpers.cpp build_mapping
    :187-452). Two C calls: count, then fill+shuffle."""
    docs = np.ascontiguousarray(docs, np.int64)
    sizes = np.ascontiguousarray(sizes, np.int32)
    lib = _load()
    assert lib is not None, (
        "build_mapping requires the native helpers (g++); the reference "
        "has no python twin for its RNG-dependent mapping either"
    )
    n = lib.build_mapping(
        _ptr(docs, ctypes.c_int64), len(docs), _ptr(sizes, ctypes.c_int32),
        num_epochs, max_num_samples, max_seq_length, short_seq_prob, seed,
        min_num_sent, None,
    )
    out = np.zeros((n, 3), np.int64)
    lib.build_mapping(
        _ptr(docs, ctypes.c_int64), len(docs), _ptr(sizes, ctypes.c_int32),
        num_epochs, max_num_samples, max_seq_length, short_seq_prob, seed,
        min_num_sent, _ptr(out, ctypes.c_int64),
    )
    return out


def build_blocks_mapping(
    docs: np.ndarray,
    sizes: np.ndarray,
    titles_sizes: np.ndarray,  # (n_docs,) int32 title token counts
    num_epochs: int,
    max_num_samples: int,
    max_seq_length: int,
    seed: int,
    use_one_sent_blocks: bool = False,
) -> np.ndarray:
    """(num_samples, 4) int64 rows of (start_sent, end_sent, doc, block_id)
    for ICT/REALM block datasets (ref: helpers.cpp build_blocks_mapping
    :453-680)."""
    docs = np.ascontiguousarray(docs, np.int64)
    sizes = np.ascontiguousarray(sizes, np.int32)
    titles_sizes = np.ascontiguousarray(titles_sizes, np.int32)
    lib = _load()
    assert lib is not None, "build_blocks_mapping requires the native helpers"
    args = (
        _ptr(docs, ctypes.c_int64), len(docs), _ptr(sizes, ctypes.c_int32),
        _ptr(titles_sizes, ctypes.c_int32), num_epochs, max_num_samples,
        max_seq_length, seed, int(use_one_sent_blocks),
    )
    n = lib.build_blocks_mapping(*args, None)
    out = np.zeros((n, 4), np.int64)
    lib.build_blocks_mapping(*args, _ptr(out, ctypes.c_int64))
    return out


def helpers_available() -> bool:
    return _load() is not None
