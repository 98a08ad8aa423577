"""ctypes bindings for the native C++ data-plane (native/fedloader.cpp).

Compiles the shared library on first use with g++ (no pybind11 in this
environment; pure C ABI + ctypes) for the baseline ISA of the host's
architecture — no ``-march=native``: ``native/build/`` is untracked, a
working tree gets copied between machines, and a binary tuned to the
build host's CPU dies with SIGILL on another. Falls back to the numpy
transforms when the build fails; :func:`unavailable_reason` says why
and the driver prints which path runs. ``COMMEFFICIENT_NATIVE=0``
forces the numpy path, ``COMMEFFICIENT_NATIVE=1`` makes a missing
native build an error.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "native", "fedloader.cpp")
_SO = os.path.join(_REPO_ROOT, "native", "build", "libfedloader.so")

_lib = None
_tried = False
_why_not: Optional[str] = None


def _build() -> Optional[str]:
    """Compile the library; returns None on success, else the reason."""
    os.makedirs(os.path.dirname(_SO), exist_ok=True)
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-std=c++17", "-pthread",
           _SRC, "-o", _SO]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    except FileNotFoundError:
        return "g++ not found"
    except subprocess.TimeoutExpired:
        return "g++ timed out after 120 s"
    except subprocess.CalledProcessError as e:
        tail = e.stderr.decode(errors="replace").strip().splitlines()[-1:]
        return f"g++ exited {e.returncode}: {' '.join(tail)}"
    return None


def get_lib() -> Optional[ctypes.CDLL]:
    global _lib, _tried, _why_not
    if _lib is not None or _tried:
        return _lib
    _tried = True
    if os.environ.get("COMMEFFICIENT_NATIVE") == "0":
        _why_not = "COMMEFFICIENT_NATIVE=0"
        return None
    if not os.path.exists(_SO) or (
            os.path.exists(_SRC)
            and os.path.getmtime(_SRC) > os.path.getmtime(_SO)):
        _why_not = _build()
        if _why_not is not None:
            if os.environ.get("COMMEFFICIENT_NATIVE") == "1":
                raise RuntimeError(
                    f"native fedloader build failed: {_why_not}")
            return None
    lib = ctypes.CDLL(_SO)
    u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
    i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
    lib.fedloader_gather_augment.argtypes = [
        u8p, i64p, f32p, ctypes.c_int64, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int, f32p, f32p,
        ctypes.c_uint64, ctypes.c_int]
    lib.fedloader_gather_normalize.argtypes = [
        u8p, i64p, f32p, ctypes.c_int64, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, f32p, f32p, ctypes.c_int]
    _lib = lib
    return _lib


def available() -> bool:
    return get_lib() is not None


def unavailable_reason() -> Optional[str]:
    """Why :func:`available` is False (None while it is True)."""
    get_lib()
    return _why_not


def gather_augment(images: np.ndarray, idx: np.ndarray, mean: np.ndarray,
                   std: np.ndarray, pad: int, flip: bool, seed: int,
                   num_threads: int = 0) -> np.ndarray:
    """Fused gather + crop/flip + normalize. ``images``: (N, H, W, C) uint8;
    ``idx``: any int shape; returns float32 with idx.shape + (H, W, C)."""
    lib = get_lib()
    assert lib is not None
    n_threads = num_threads or min(8, os.cpu_count() or 1)
    flat_idx = np.ascontiguousarray(idx.reshape(-1), np.int64)
    h, w, c = images.shape[1:]
    out = np.empty((flat_idx.size, h, w, c), np.float32)
    lib.fedloader_gather_augment(
        np.ascontiguousarray(images), flat_idx, out, flat_idx.size,
        h, w, c, pad, int(flip),
        np.ascontiguousarray(mean, np.float32),
        np.ascontiguousarray(std, np.float32),
        ctypes.c_uint64(seed), n_threads)
    return out.reshape(idx.shape + (h, w, c))


def gather_normalize(images: np.ndarray, idx: np.ndarray, mean: np.ndarray,
                     std: np.ndarray, num_threads: int = 0) -> np.ndarray:
    lib = get_lib()
    assert lib is not None
    n_threads = num_threads or min(8, os.cpu_count() or 1)
    flat_idx = np.ascontiguousarray(idx.reshape(-1), np.int64)
    h, w, c = images.shape[1:]
    out = np.empty((flat_idx.size, h, w, c), np.float32)
    lib.fedloader_gather_normalize(
        np.ascontiguousarray(images), flat_idx, out, flat_idx.size,
        h, w, c,
        np.ascontiguousarray(mean, np.float32),
        np.ascontiguousarray(std, np.float32), n_threads)
    return out.reshape(idx.shape + (h, w, c))
