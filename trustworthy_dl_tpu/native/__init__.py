"""Native host-runtime tier: C++ data-loader core with ctypes bindings.

The reference framework is pure Python (SURVEY §0: no native code anywhere);
its data layer is an implied module that doesn't even exist in the snapshot
(§2.3).  This package gives the TPU build a real native input pipeline:
``dataloader.cpp`` implements the batch-assembly hot path (synthetic token
chains, epoch permutations, multi-threaded row gathers), compiled lazily
with g++ into ``libtddl_native.so`` and loaded via ctypes — no pybind11
dependency, per the environment contract.

Every entry point has a bit-exact numpy fallback in this module, selected
automatically when no compiler/library is available (or when
``TDDL_NATIVE=0``).  tests/test_native.py pins C++ == Python on every
routine, so the two tiers can never drift.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import tempfile
from typing import Optional

import numpy as np

logger = logging.getLogger(__name__)

_GOLDEN = np.uint64(0x9E3779B97F4A7C15)
_LIB: Optional[ctypes.CDLL] = None
_LIB_TRIED = False


# ---------------------------------------------------------------------------
# Build / load
# ---------------------------------------------------------------------------


def _source_path() -> str:
    return os.path.join(os.path.dirname(__file__), "dataloader.cpp")


def _lib_path() -> str:
    """The built library, named after a hash of its source: a copied
    tree has arbitrary mtimes, and a library left over from another
    ``dataloader.cpp`` must never be loaded for this one."""
    with open(_source_path(), "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    return os.path.join(os.path.dirname(__file__),
                        f"libtddl_native.{digest}.so")


def build_library(force: bool = False) -> Optional[str]:
    """Compile dataloader.cpp with g++ (cached next to the source)."""
    out = _lib_path()
    src = _source_path()
    if not force and os.path.exists(out):
        return out
    # Build into a temp file then rename, so a concurrent test runner never
    # dlopens a half-written library.
    tmp_path = None
    try:
        with tempfile.NamedTemporaryFile(
            dir=os.path.dirname(out), suffix=".so", delete=False
        ) as tmp:
            tmp_path = tmp.name
        subprocess.run(
            ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-o", tmp_path,
             src, "-lpthread"],
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp_path, out)
        logger.info("native: built %s", out)
        return out
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired,
            FileNotFoundError, OSError) as exc:
        if tmp_path is not None:
            try:
                os.unlink(tmp_path)
            except OSError:
                pass
        logger.warning("native: build failed (%s); using Python fallback", exc)
        return None


def _load() -> Optional[ctypes.CDLL]:
    global _LIB, _LIB_TRIED
    if _LIB_TRIED:
        return _LIB
    _LIB_TRIED = True
    if os.environ.get("TDDL_NATIVE") == "0":
        return None
    path = build_library()
    if path is None:
        return None
    try:
        lib = ctypes.CDLL(path)
    except OSError as exc:
        logger.warning("native: dlopen failed (%s); using Python fallback", exc)
        return None
    u64p = ctypes.POINTER(ctypes.c_uint64)
    i64p = ctypes.POINTER(ctypes.c_int64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.tddl_splitmix_fill.argtypes = [ctypes.c_uint64, ctypes.c_int64, u64p]
    lib.tddl_synthetic_tokens.argtypes = [
        ctypes.c_int64, ctypes.c_int32, ctypes.c_uint64, i32p
    ]
    lib.tddl_permutation.argtypes = [ctypes.c_uint64, ctypes.c_int64, i64p]
    lib.tddl_gather_rows.argtypes = [
        u8p, i64p, ctypes.c_int64, ctypes.c_int64, u8p, ctypes.c_int32
    ]
    lib.tddl_window_gather.argtypes = [
        i32p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
        ctypes.c_uint64, i32p, i32p, ctypes.c_int32
    ]
    lib.tddl_bpe_load.argtypes = [i32p, i32p, i32p, ctypes.c_int64]
    lib.tddl_bpe_encode.argtypes = [
        i32p, i64p, ctypes.c_int64, i32p, i64p
    ]
    _LIB = lib
    return _LIB


def native_available() -> bool:
    return _load() is not None


# ---------------------------------------------------------------------------
# splitmix64 — shared deterministic generator (numpy fallback)
# ---------------------------------------------------------------------------


def _splitmix64_np(x: np.ndarray) -> np.ndarray:
    """Vectorised splitmix64 finaliser on uint64 states (wrapping)."""
    with np.errstate(over="ignore"):
        z = x + _GOLDEN
        z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return z ^ (z >> np.uint64(31))


def splitmix_fill(seed: int, n: int) -> np.ndarray:
    """u64[n] raw stream: splitmix64(seed + i*GOLDEN)."""
    lib = _load()
    out = np.empty(n, np.uint64)
    if lib is not None and n:
        lib.tddl_splitmix_fill(
            ctypes.c_uint64(seed), n,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        )
        return out
    with np.errstate(over="ignore"):
        states = np.uint64(seed) + np.arange(n, dtype=np.uint64) * _GOLDEN
    return _splitmix64_np(states)


def _splitmix_scalar(x: int) -> int:
    return int(_splitmix64_np(np.asarray([x], np.uint64))[0])


def synthetic_tokens(n: int, vocab: int, seed: int) -> np.ndarray:
    """i32[n] learnable affine next-token chain with 10% uniform resets —
    the LM synthetic source of data/loader.py, native-accelerated."""
    lib = _load()
    if lib is not None and n:
        out = np.empty(n, np.int32)
        lib.tddl_synthetic_tokens(
            n, vocab, ctypes.c_uint64(seed),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        )
        return out
    a, b = 31, 7
    noise_seed = _splitmix_scalar(seed ^ 0xA5A5A5A5A5A5A5A5)
    tok_seed = _splitmix_scalar(seed ^ 0x5A5A5A5A5A5A5A5A)
    noise_u = splitmix_fill(noise_seed, n) if n else np.empty(0, np.uint64)
    reset = (noise_u >> np.uint64(48)) < np.uint64(6554)
    resets_tok = (splitmix_fill(tok_seed, n) % np.uint64(vocab)).astype(np.int32)
    out = np.empty(n, np.int32)
    t = _splitmix_scalar(seed) % vocab
    out[0] = t
    for i in range(1, n):
        t = int(resets_tok[i]) if reset[i] else (a * t + b) % vocab
        out[i] = t
    return out


def permutation(seed: int, n: int) -> np.ndarray:
    """i64[n] Fisher-Yates permutation from the splitmix stream."""
    lib = _load()
    if lib is not None and n:
        out = np.empty(n, np.int64)
        lib.tddl_permutation(
            ctypes.c_uint64(seed), n,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        )
        return out
    out = np.arange(n, dtype=np.int64)
    if n:
        with np.errstate(over="ignore"):
            us = _splitmix64_np(
                np.uint64(seed) + np.arange(n, dtype=np.uint64) * _GOLDEN
            )
        for i in range(n - 1, 0, -1):
            j = int(us[i] % np.uint64(i + 1))
            out[i], out[j] = out[j], out[i]
    return out


def gather_rows(src: np.ndarray, idx: np.ndarray,
                n_threads: int = 4) -> np.ndarray:
    """out[k] = src[idx[k]] for a C-contiguous array — the per-batch row
    gather, multi-threaded memcpy on the native path.

    Internal API: indices must lie in [0, len(src)) — the native path does
    no bounds checking (it is fed only by ``permutation`` over the same
    array in ArrayDataLoader)."""
    lib = _load()
    idx = np.ascontiguousarray(idx, np.int64)
    if lib is None or not src.flags.c_contiguous or src.ndim < 1:
        return src[idx]
    row_bytes = src.dtype.itemsize * int(np.prod(src.shape[1:], dtype=np.int64))
    out = np.empty((len(idx),) + src.shape[1:], src.dtype)
    lib.tddl_gather_rows(
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        idx.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        len(idx), row_bytes,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        n_threads,
    )
    return out


def window_gather(stream: np.ndarray, seq_len: int, batch: int, seed: int,
                  n_threads: int = 4) -> "tuple[np.ndarray, np.ndarray]":
    """(inputs i32[batch, seq_len], targets i32[batch, seq_len]): random
    seq_len+1 windows of a contiguous token stream at splitmix-derived
    offsets — the nanoGPT-style sampler, multi-threaded memcpy on the
    native path.  Offsets are O(1) addressable (pure function of
    (seed, row)), so batches are reproducible and the Python fallback is
    bit-exact."""
    stream = np.ascontiguousarray(stream, np.int32)
    # A window consumes seq_len+1 tokens, so valid offsets are
    # [0, len - seq_len - 1] — span = len - seq_len of them.
    span = len(stream) - seq_len
    if span <= 0:
        raise ValueError(
            f"stream of {len(stream)} tokens too short for seq_len={seq_len}"
        )
    lib = _load()
    if lib is not None and batch:
        inputs = np.empty((batch, seq_len), np.int32)
        targets = np.empty((batch, seq_len), np.int32)
        i32p = ctypes.POINTER(ctypes.c_int32)
        lib.tddl_window_gather(
            stream.ctypes.data_as(i32p), len(stream), seq_len, batch,
            ctypes.c_uint64(seed),
            inputs.ctypes.data_as(i32p), targets.ctypes.data_as(i32p),
            n_threads,
        )
        return inputs, targets
    offs = (splitmix_fill(seed, batch) % np.uint64(span)).astype(np.int64)
    gather = offs[:, None] + np.arange(seq_len + 1, dtype=np.int64)[None, :]
    windows = stream[gather]
    return windows[:, :-1].copy(), windows[:, 1:].copy()


__all__ = [
    "build_library",
    "gather_rows",
    "native_available",
    "permutation",
    "splitmix_fill",
    "synthetic_tokens",
    "window_gather",
]


# ---------------------------------------------------------------------------
# Byte-level BPE encoder (hot path of data/tokenizer.py)
# ---------------------------------------------------------------------------


def bpe_load(lefts: np.ndarray, rights: np.ndarray, prods: np.ndarray
             ) -> bool:
    """Install the merge table (id pairs -> product id, rank = position)
    into the native encoder.  Returns False when the native tier is
    unavailable — the tokenizer then runs its bit-exact Python merge
    loop."""
    lib = _load()
    if lib is None:
        return False
    i32p = ctypes.POINTER(ctypes.c_int32)
    lefts = np.ascontiguousarray(lefts, np.int32)
    rights = np.ascontiguousarray(rights, np.int32)
    prods = np.ascontiguousarray(prods, np.int32)
    lib.tddl_bpe_load(
        lefts.ctypes.data_as(i32p), rights.ctypes.data_as(i32p),
        prods.ctypes.data_as(i32p), len(lefts),
    )
    return True


def bpe_encode(flat: np.ndarray, offsets: np.ndarray
               ) -> "tuple[np.ndarray, np.ndarray]":
    """Encode a flat batch of unit-id words (``offsets`` delimits each
    word) with the table installed by ``bpe_load``.  Returns
    (flat_out, out_offsets)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native tier unavailable; call bpe_load first")
    i32p = ctypes.POINTER(ctypes.c_int32)
    i64p = ctypes.POINTER(ctypes.c_int64)
    flat = np.ascontiguousarray(flat, np.int32)
    offsets = np.ascontiguousarray(offsets, np.int64)
    out = np.empty(max(len(flat), 1), np.int32)
    out_offsets = np.empty(len(offsets), np.int64)
    lib.tddl_bpe_encode(
        flat.ctypes.data_as(i32p), offsets.ctypes.data_as(i64p),
        len(offsets) - 1, out.ctypes.data_as(i32p),
        out_offsets.ctypes.data_as(i64p),
    )
    return out[: out_offsets[-1]], out_offsets
