"""ctypes bindings for the native runtime core (core.cc).

The reference ships its runtime as C++ shared libraries built by setup.py
and loaded with ctypes (byteps/common/__init__.py:52-139 BytePSBasics).
Same shape here: ``load()`` compiles core.cc once (g++, cached next to the
source keyed by content hash) and returns the CDLL; everything degrades to
the pure-Python implementations when the toolchain is unavailable or
BYTEPS_NATIVE=0.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import List, Optional, Tuple

import numpy as np

_SRC = os.path.join(os.path.dirname(__file__), "core.cc")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_load_failed = False
_load_error = ""  # one-line cause of the latched failure, for callers' logs


def _build_path() -> str:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return os.path.join(os.path.dirname(__file__),
                        f"_libbyteps_native_{digest}.so")


def _compile(out: str) -> None:
    # a temporary file of this builder's own: in a fresh checkout several
    # processes build at once (every test worker imports this while it
    # collects), and on ONE shared ".tmp" the first os.replace took the
    # file from under the others, whose own replace then failed and
    # latched the library unavailable in their process
    tmp = f"{out}.{os.getpid()}.{threading.get_ident()}.tmp"
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
           _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.replace(tmp, out)  # atomic: parallel builders race safely
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def load() -> Optional[ctypes.CDLL]:
    """Return the native core library, building it on first use; None when
    disabled or the build fails (callers fall back to Python)."""
    global _lib, _load_failed, _load_error
    if _lib is not None:
        return _lib
    if _load_failed:
        return None
    # one gate: Config parses BYTEPS_NATIVE (and programmatic
    # set_config(use_native=False) must win over the env)
    from ..common.config import get_config
    if not get_config().use_native:
        return None  # not latched: a later config may re-enable native
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        try:
            path = _build_path()
            if not os.path.exists(path):
                _compile(path)
            try:
                lib = ctypes.CDLL(path)
            except OSError:
                # existing binary from another platform/ABI: rebuild once
                _compile(path)
                lib = ctypes.CDLL(path)
            _declare_signatures(lib)
            if lib.bps_native_abi_version() != 5:
                raise RuntimeError("native ABI mismatch")
            _lib = lib
        except Exception as e:  # noqa: BLE001 — Python twins take over
            _load_failed = True
            _load_error = _describe_failure(e)
            from ..common.logging import get_logger
            get_logger().warning(
                "native core unavailable, using the pure-Python "
                "reducer/coder/CRC: %s", _load_error)
            return None
    return _lib


def _describe_failure(exc: Exception) -> str:
    """The build/load failure in one line: the compiler's own last
    message for a failed g++ run, the missing tool, or the loader error."""
    if isinstance(exc, subprocess.CalledProcessError):
        tail = (exc.stderr or "").strip().splitlines()
        return (f"g++ exited {exc.returncode} building core.cc: "
                f"{tail[-1] if tail else 'no compiler output'}")
    if isinstance(exc, FileNotFoundError):
        return f"g++ not found ({exc})"
    return f"{type(exc).__name__}: {exc}"


def available() -> bool:
    return load() is not None


def _declare_signatures(lib: ctypes.CDLL) -> None:
    i64, u64, f32, f64 = (ctypes.c_int64, ctypes.c_uint64, ctypes.c_float,
                          ctypes.c_double)
    lib.bps_make_key.restype = u64
    lib.bps_make_key.argtypes = [u64, u64]
    lib.bps_key_declared.restype = u64
    lib.bps_key_declared.argtypes = [u64]
    lib.bps_key_part.restype = u64
    lib.bps_key_part.argtypes = [u64]
    lib.bps_chunk_bounds.restype = i64
    lib.bps_chunk_bounds.argtypes = [i64, i64, i64, i64,
                                     ctypes.POINTER(i64),
                                     ctypes.POINTER(i64), i64]
    for name, ct in (("bps_reduce_sum_f32", f32), ("bps_reduce_sum_f64", f64)):
        fn = getattr(lib, name)
        fn.argtypes = [ctypes.POINTER(ct), ctypes.POINTER(ct), i64,
                       ctypes.c_int]
    lib.bps_reduce_sum_i32.argtypes = [ctypes.POINTER(ctypes.c_int32),
                                       ctypes.POINTER(ctypes.c_int32), i64,
                                       ctypes.c_int]
    lib.bps_reduce_sum_i64.argtypes = [ctypes.POINTER(i64),
                                       ctypes.POINTER(i64), i64,
                                       ctypes.c_int]
    lib.bps_reduce_scaled_f32.argtypes = [ctypes.POINTER(f32),
                                          ctypes.POINTER(f32), f32, i64,
                                          ctypes.c_int]
    lib.bps_reduce_sum_bf16.argtypes = [ctypes.POINTER(ctypes.c_uint16),
                                        ctypes.POINTER(ctypes.c_uint16),
                                        i64, ctypes.c_int]
    lib.bps_elias_encode.restype = i64
    lib.bps_elias_encode.argtypes = [ctypes.POINTER(ctypes.c_int8), i64,
                                     ctypes.POINTER(ctypes.c_uint32), i64]
    lib.bps_elias_decode.restype = i64
    lib.bps_elias_decode.argtypes = [ctypes.POINTER(ctypes.c_uint32), i64,
                                     ctypes.POINTER(ctypes.c_int8), i64]
    lib.bps_crc32c.restype = ctypes.c_uint32
    lib.bps_crc32c.argtypes = [ctypes.c_char_p, i64, ctypes.c_uint32]
    lib.bps_native_abi_version.restype = ctypes.c_int


# -------------------------------------------------------------- partitioner

def chunk_bounds(num_elems: int, itemsize: int, partition_bytes: int,
                 align_elems: int = 512) -> List[Tuple[int, int]]:
    """Native version of common.partitioner.chunk_bounds (same contract)."""
    lib = load()
    if lib is None:
        from ..common import partitioner as pp
        return pp.chunk_bounds(num_elems, itemsize, partition_bytes)
    # first call with a NULL buffer returns the exact chunk count (the
    # 512-element alignment shrink can make it much larger than the naive
    # bytes/partition_bytes estimate)
    n = lib.bps_chunk_bounds(num_elems, itemsize, partition_bytes,
                             align_elems, None, None, 0)
    if n < 0:
        raise ValueError(
            f"bps_chunk_bounds failed ({n}) for num_elems={num_elems}")
    off = (ctypes.c_int64 * n)()
    ln = (ctypes.c_int64 * n)()
    n = lib.bps_chunk_bounds(num_elems, itemsize, partition_bytes,
                             align_elems, off, ln, n)
    if n < 0:
        raise ValueError(
            f"bps_chunk_bounds failed ({n}) for num_elems={num_elems}")
    return [(int(off[i]), int(ln[i])) for i in range(n)]


# -------------------------------------------------------------- cpu reducer

_REDUCE_FNS = {
    np.dtype(np.float32): ("bps_reduce_sum_f32", ctypes.c_float),
    np.dtype(np.float64): ("bps_reduce_sum_f64", ctypes.c_double),
    np.dtype(np.int32): ("bps_reduce_sum_i32", ctypes.c_int32),
    np.dtype(np.int64): ("bps_reduce_sum_i64", ctypes.c_int64),
}


def inplace_add(dst: np.ndarray, src: np.ndarray,
                nthreads: int = 0) -> np.ndarray:
    """dst += src via the native multithreaded reducer; numpy fallback for
    unsupported dtypes/layouts.  Returns dst."""
    lib = load()
    if (lib is None or dst.dtype != src.dtype
            or dst.dtype not in _REDUCE_FNS
            or not dst.flags.c_contiguous or not src.flags.c_contiguous
            or dst.shape != src.shape):
        np.add(dst, src, out=dst)
        return dst
    if nthreads <= 0:
        nthreads = min(8, os.cpu_count() or 1)
    name, ct = _REDUCE_FNS[dst.dtype]
    fn = getattr(lib, name)
    fn(dst.ctypes.data_as(ctypes.POINTER(ct)),
       src.ctypes.data_as(ctypes.POINTER(ct)), dst.size, nthreads)
    return dst


def inplace_scaled_add(dst: np.ndarray, src: np.ndarray, alpha: float,
                       nthreads: int = 0) -> np.ndarray:
    """dst += alpha * src (f32 native path, numpy otherwise)."""
    lib = load()
    if (lib is None or dst.dtype != np.float32 or src.dtype != np.float32
            or not dst.flags.c_contiguous or not src.flags.c_contiguous
            or dst.shape != src.shape):
        dst += (alpha * src).astype(dst.dtype, copy=False)
        return dst
    if nthreads <= 0:
        nthreads = min(8, os.cpu_count() or 1)
    lib.bps_reduce_scaled_f32(
        dst.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        src.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        float(alpha), dst.size, nthreads)
    return dst


def make_key(declared: int, part: int) -> int:
    lib = load()
    if lib is None:
        return (declared << 16) | (part & 0xFFFF)
    return int(lib.bps_make_key(declared, part))


# --------------------------------------------------------- elias-delta coder

def elias_encode(codes: np.ndarray) -> Optional[Tuple[np.ndarray, int]]:
    """Entropy-code signed int8 level codes (gap/sign/|level| triplets,
    Elias-delta); returns (uint32 words, nbits) or None when the native
    core is unavailable (callers fall back to the numpy twin in
    compression.elias)."""
    lib = load()
    if lib is None:
        return None
    codes = np.ascontiguousarray(codes, dtype=np.int8)
    cap = max(4, codes.size + 64)
    while True:
        out = np.zeros(cap, np.uint32)
        nbits = lib.bps_elias_encode(
            codes.ctypes.data_as(ctypes.POINTER(ctypes.c_int8)), codes.size,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)), cap)
        if nbits == -2:
            cap *= 2
            continue
        nwords = (int(nbits) + 31) // 32
        return out[:nwords].copy(), int(nbits)


def elias_decode(words: np.ndarray, nbits: int,
                 n: int) -> Optional[np.ndarray]:
    """Inverse of :func:`elias_encode`; returns dense int8 codes or None
    when the native core is unavailable.  Raises on a malformed stream."""
    lib = load()
    if lib is None:
        return None
    words = np.ascontiguousarray(words, dtype=np.uint32)
    out = np.zeros(n, np.int8)
    rc = lib.bps_elias_decode(
        words.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)), int(nbits),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int8)), n)
    if rc != 0:
        raise ValueError("malformed elias-delta stream")
    return out


# ------------------------------------------------------------------- crc32c

def crc32c(data: bytes, crc: int = 0) -> Optional[int]:
    """CRC32C (Castagnoli) over ``data``, continuing ``crc``; None when
    the native core is unavailable (common/integrity.py falls back to
    google_crc32c or its pure-Python table)."""
    lib = load()
    if lib is None:
        return None
    mv = memoryview(data)
    if not mv.c_contiguous:
        mv = memoryview(bytes(mv))
    # np.frombuffer exposes the address of a READ-ONLY buffer (ctypes
    # from_buffer refuses those), so a memoryview of a 100 MB frame is
    # checksummed without an extra memcpy
    view = np.frombuffer(mv, dtype=np.uint8)
    ptr = view.ctypes.data_as(ctypes.c_char_p)
    return int(lib.bps_crc32c(ptr, view.nbytes, crc & 0xFFFFFFFF))
