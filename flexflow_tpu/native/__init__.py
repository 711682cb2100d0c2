"""Native (C++) runtime components with ctypes bindings.

The reference's runtime core is C++ (SURVEY §2 language note); this package
holds the pieces where native code genuinely pays on TPU hosts: the
prefetching data loader (src/dataloader.cc — GIL-free shuffled batch
gather, reference python/flexflow_dataloader.cc) and the task-graph
simulator + MCMC annealing loop (src/simulator.cc — reference
src/runtime/simulator.cc + model.cc mcmc_optimize).

The shared library is built on first use with g++ into a fixed path next
to the sources, with the sources' content hash beside it: a library whose
recorded hash is not the hash of the sources here is stale and is rebuilt
(file times say nothing — a copy of the tree can reorder them). Every
consumer has a pure-Python fallback so the framework works without a
toolchain; a failed build is logged, never silent.
"""
from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

logger = logging.getLogger(__name__)

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "src")
_LIB_PATH = os.path.join(_HERE, "libffnative.so")
_HASH_PATH = _LIB_PATH + ".sha256"
_BUILD_CMD = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread"]
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False


def _sources():
    return sorted(
        os.path.join(_SRC, f) for f in os.listdir(_SRC) if f.endswith(".cc")
    )


def _source_hash() -> str:
    """sha256 over the build command and every source's name + bytes."""
    h = hashlib.sha256(" ".join(_BUILD_CMD).encode())
    for src in _sources():
        h.update(os.path.basename(src).encode())
        with open(src, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _needs_build() -> bool:
    try:
        with open(_HASH_PATH) as f:
            recorded = f.read().strip()
    except OSError:
        return True
    return not os.path.exists(_LIB_PATH) or recorded != _source_hash()


def build(force: bool = False) -> Optional[str]:
    """Compile the native library. Returns its path or None on failure."""
    global _build_failed
    with _lock:
        if not force and not _needs_build():
            return _LIB_PATH
        try:
            # the hash goes first and comes back last, so an interrupted
            # build leaves a library that reads as stale
            if os.path.exists(_HASH_PATH):
                os.unlink(_HASH_PATH)
            subprocess.run([*_BUILD_CMD, "-o", _LIB_PATH, *_sources()],
                           check=True, capture_output=True, timeout=120)
            with open(_HASH_PATH, "w") as f:
                f.write(_source_hash() + "\n")
            _build_failed = False
            return _LIB_PATH
        except (OSError, subprocess.SubprocessError) as e:
            detail = getattr(e, "stderr", b"") or b""
            logger.warning(
                "native build failed (%r)%s — the Python simulator and "
                "data loader run instead", e,
                ": " + detail.decode(errors="replace")[-500:]
                if detail else "")
            _build_failed = True
            return None


def get_lib() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None if unavailable."""
    global _lib
    if _lib is not None:
        return _lib
    if _build_failed:
        return None
    path = build()
    if path is None:
        return None
    with _lock:
        if _lib is None:
            lib = ctypes.CDLL(path)
            _configure(lib)
            _lib = lib
    return _lib


def _configure(lib: ctypes.CDLL) -> None:
    i64 = ctypes.c_int64
    u64 = ctypes.c_uint64
    dbl = ctypes.c_double
    ptr = ctypes.c_void_p
    # dataloader
    lib.ffdl_create.restype = ptr
    lib.ffdl_create.argtypes = [ptr, i64, i64, i64, ctypes.c_int, u64, i64]
    lib.ffdl_next.restype = i64
    lib.ffdl_next.argtypes = [ptr, ptr]
    lib.ffdl_reset.argtypes = [ptr]
    lib.ffdl_batches_per_epoch.restype = i64
    lib.ffdl_batches_per_epoch.argtypes = [ptr]
    lib.ffdl_destroy.argtypes = [ptr]
    # simulator
    I64P = ctypes.POINTER(i64)
    DP = ctypes.POINTER(dbl)
    lib.ffsim_create.restype = ptr
    lib.ffsim_create.argtypes = [
        i64, i64, I64P, I64P, I64P, i64, I64P, I64P, i64, I64P, I64P, I64P,
        i64, DP, DP, DP, dbl, dbl,
    ]
    lib.ffsim_simulate.restype = dbl
    lib.ffsim_simulate.argtypes = [ptr, I64P]
    lib.ffsim_mcmc.restype = dbl
    lib.ffsim_mcmc.argtypes = [ptr, I64P, i64, dbl, u64]
    lib.ffsim_destroy.argtypes = [ptr]


def available() -> bool:
    return get_lib() is not None
