"""Loader for the native C++ kernel library (native/daft_native.cpp).

Builds the shared library on first use when a compiler is available (the
image bakes g++); falls back to the numpy kernels otherwise, with a warning.
The artifact is named by a hash of the source, the compiler flags and the
CPU it was built for, so a library is only ever loaded by the checkout and
the machine that built it — a copied tree rebuilds instead of running code
compiled with ``-march=native`` for another CPU.
Disable with DAFT_NATIVE=0. Hash outputs are bit-identical across the native
and numpy paths (cross-host hash-partitioning requirement).
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import platform
import subprocess
import threading
from typing import Optional

import numpy as np

_log = logging.getLogger(__name__)

_lib: Optional[ctypes.CDLL] = None
_tried = False
_lock = threading.Lock()

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "native", "daft_native.cpp")
_FLAG_SETS = (["-O3", "-march=native"], ["-O3"])


def _cpu_identity() -> str:
    """What ``-march=native`` resolves against: the architecture plus the
    CPU's feature flags."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return platform.machine() + line
    except OSError:
        pass  # no procfs: the architecture and processor name must do
    return platform.machine() + platform.processor()


def _so_path() -> str:
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(repr(_FLAG_SETS).encode())
    h.update(_cpu_identity().encode())
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"_daft_native.{h.hexdigest()[:16]}.so")


def _build(so: str) -> bool:
    """Compile to a temp path and os.rename into place (atomic on POSIX), with
    an flock so concurrent worker processes never dlopen a half-written .so."""
    import fcntl

    lock_path = os.path.join(os.path.dirname(so), "_daft_native.so.lock")
    tmp_path = f"{so}.{os.getpid()}.tmp"
    try:
        with open(lock_path, "w") as lock_f:
            fcntl.flock(lock_f, fcntl.LOCK_EX)
            # Another process may have finished the build while we waited.
            if os.path.exists(so):
                return True
            for flags in _FLAG_SETS:
                try:
                    subprocess.run(
                        ["g++", *flags, "-shared", "-fPIC", "-std=c++17",
                         _SRC, "-o", tmp_path],
                        check=True, capture_output=True, timeout=120,
                    )
                    os.rename(tmp_path, so)
                    return True
                except Exception:
                    continue
            return False
    except Exception:
        return False
    finally:
        try:
            if os.path.exists(tmp_path):
                os.remove(tmp_path)
        except OSError:
            pass


def get_lib() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        from daft_tpu.config import daft_env_flag

        if not daft_env_flag("DAFT_NATIVE", True):
            return None
        if not os.path.exists(_SRC):
            _log.warning("native kernels off: %s is missing; numpy kernels "
                         "run instead", _SRC)
            return None
        so = _so_path()
        if not os.path.exists(so) and not _build(so):
            _log.warning("native kernels off: building %s failed; numpy "
                         "kernels run instead", so)
            return None
        try:
            lib = ctypes.CDLL(so)
            if lib.daft_native_abi_version() != 1:
                return None
            u8p = ctypes.POINTER(ctypes.c_uint8)
            i64p = ctypes.POINTER(ctypes.c_int64)
            u64p = ctypes.POINTER(ctypes.c_uint64)
            u32p = ctypes.POINTER(ctypes.c_uint32)
            lib.hash_bytes_batch.argtypes = [u8p, i64p, i64p, ctypes.c_int64, u64p]
            lib.hash_fixed_width.argtypes = [u8p, ctypes.c_int64, ctypes.c_int64, u64p]
            lib.combine_hashes.argtypes = [u64p, u64p, ctypes.c_int64, u64p]
            lib.minhash_rows.argtypes = [u64p, i64p, ctypes.c_int64, u64p, u64p,
                                         ctypes.c_int64, u32p]
            lib.hll_build.argtypes = [u64p, ctypes.c_int64, ctypes.c_int32, u8p]
            _lib = lib
        except Exception:
            _log.warning("native kernels off: loading %s failed; numpy "
                         "kernels run instead", so, exc_info=True)
            _lib = None
        return _lib


def _ptr(arr: np.ndarray, ctype):
    return arr.ctypes.data_as(ctypes.POINTER(ctype))


def native_hash_bytes(data: np.ndarray, starts: np.ndarray, lengths: np.ndarray) -> Optional[np.ndarray]:
    lib = get_lib()
    if lib is None:
        return None
    n = len(starts)
    out = np.empty(n, dtype=np.uint64)
    data = np.ascontiguousarray(data, dtype=np.uint8)
    starts = np.ascontiguousarray(starts, dtype=np.int64)
    lengths = np.ascontiguousarray(lengths, dtype=np.int64)
    lib.hash_bytes_batch(_ptr(data, ctypes.c_uint8), _ptr(starts, ctypes.c_int64),
                         _ptr(lengths, ctypes.c_int64), n, _ptr(out, ctypes.c_uint64))
    return out


def native_hash_fixed(raw: np.ndarray) -> Optional[np.ndarray]:
    """raw: (n, width) uint8 contiguous."""
    lib = get_lib()
    if lib is None:
        return None
    raw = np.ascontiguousarray(raw, dtype=np.uint8)
    n, width = raw.shape
    out = np.empty(n, dtype=np.uint64)
    lib.hash_fixed_width(_ptr(raw, ctypes.c_uint8), n, width, _ptr(out, ctypes.c_uint64))
    return out


def native_combine(a: np.ndarray, b: np.ndarray) -> Optional[np.ndarray]:
    lib = get_lib()
    if lib is None:
        return None
    a = np.ascontiguousarray(a, dtype=np.uint64)
    b = np.ascontiguousarray(b, dtype=np.uint64)
    out = np.empty(len(a), dtype=np.uint64)
    lib.combine_hashes(_ptr(a, ctypes.c_uint64), _ptr(b, ctypes.c_uint64),
                       len(a), _ptr(out, ctypes.c_uint64))
    return out


def native_minhash(token_hashes: np.ndarray, row_offsets: np.ndarray,
                   a: np.ndarray, b: np.ndarray, num_hashes: int) -> Optional[np.ndarray]:
    lib = get_lib()
    if lib is None:
        return None
    token_hashes = np.ascontiguousarray(token_hashes, dtype=np.uint64)
    row_offsets = np.ascontiguousarray(row_offsets, dtype=np.int64)
    a = np.ascontiguousarray(a, dtype=np.uint64)
    b = np.ascontiguousarray(b, dtype=np.uint64)
    n_rows = len(row_offsets) - 1
    out = np.zeros((n_rows, num_hashes), dtype=np.uint32)
    lib.minhash_rows(_ptr(token_hashes, ctypes.c_uint64), _ptr(row_offsets, ctypes.c_int64),
                     n_rows, _ptr(a, ctypes.c_uint64), _ptr(b, ctypes.c_uint64),
                     num_hashes, _ptr(out, ctypes.c_uint32))
    return out


def native_hll(hashes: np.ndarray, precision: int) -> Optional[np.ndarray]:
    lib = get_lib()
    if lib is None:
        return None
    hashes = np.ascontiguousarray(hashes, dtype=np.uint64)
    registers = np.zeros(1 << precision, dtype=np.uint8)
    lib.hll_build(_ptr(hashes, ctypes.c_uint64), len(hashes), precision,
                  _ptr(registers, ctypes.c_uint8))
    return registers
