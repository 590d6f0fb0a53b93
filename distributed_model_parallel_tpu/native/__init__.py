"""native/ — C++ runtime components for the input-pipeline hot loop.

The reference's input path rides PyTorch's native layer (torchvision C
image ops + the DataLoader C++ worker pool); this package is the
TPU-framework equivalent: `augment.cpp` implements the batched
RandomCrop+RandomHorizontalFlip+normalize transform with an internal
std::thread pool, compiled on first use with the image's g++ (no pip
deps; ctypes binding, no pybind11) and cached next to the source.

The cached library is git-ignored, so its NAME carries a hash of
`augment.cpp`: a binary is only ever loaded if it was built from this
tree's source (a copied checkout can reorder mtimes; it cannot forge
the hash), and an edit to the source builds a new file.

If the toolchain or the build is unavailable, `lib()` returns None,
says so once on stderr, and the Loader takes the vectorized NumPy
implementation with identical numerics (tests/test_native.py asserts
bit-exact parity between the two).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import threading
from typing import Optional

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "augment.cpp")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _so_path() -> str:
    with open(_SRC, "rb") as f:
        sha8 = hashlib.sha256(f.read()).hexdigest()[:8]
    return os.path.join(_DIR, f"libdmp_native-{sha8}.so")


def _compile(so: str) -> Optional[str]:
    """Build `so` from the source; None on success, else the reason."""
    tmp = f"{so}.{os.getpid()}.tmp"  # rename into place: never half-written
    cmd = ["g++", "-O3", "-shared", "-fPIC", "-pthread", "-o", tmp, _SRC]
    try:
        proc = subprocess.run(
            cmd, capture_output=True, text=True, timeout=120
        )
    except (OSError, subprocess.TimeoutExpired) as e:
        return f"{type(e).__name__}: {e}"
    if proc.returncode != 0 or not os.path.exists(tmp):
        return f"g++ exited {proc.returncode}: {proc.stderr.strip()[-200:]}"
    os.replace(tmp, so)
    return None


def lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, compiling it on first call; None when
    the native path is unavailable (missing toolchain, failed build) —
    reported once on stderr."""
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        so = _so_path()
        why = None if os.path.exists(so) else _compile(so)
        if why is None:
            try:
                cdll = ctypes.CDLL(so)
            except OSError as e:
                why = f"OSError: {e}"
        if why is not None:
            print(
                "[native] augment library unavailable, the Loader takes "
                f"the NumPy path ({why})",
                file=sys.stderr, flush=True,
            )
            return None
        u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
        i32p = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
        f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
        ci = ctypes.c_int
        cdll.dmp_augment_normalize.argtypes = [
            u8p, ci, ci, ci, ci, i32p, i32p, u8p, ci, f32p, f32p, f32p, ci
        ]
        cdll.dmp_augment_normalize.restype = None
        cdll.dmp_normalize.argtypes = [u8p, ci, ci, ci, ci, f32p, f32p,
                                       f32p, ci]
        cdll.dmp_normalize.restype = None
        _lib = cdll
        return _lib


def available() -> bool:
    return lib() is not None


def augment_normalize(
    images: np.ndarray,
    ys: np.ndarray,
    xs: np.ndarray,
    flips: np.ndarray,
    padding: int,
    mean: np.ndarray,
    std: np.ndarray,
    workers: int = 1,
) -> np.ndarray:
    """Batched crop+flip+normalize on uint8 NHWC via the native library.
    Caller guarantees `lib()` is not None. The ctypes call releases the
    GIL, so prefetch threads overlap this with the device step."""
    cdll = lib()
    n, h, w, c = images.shape
    out = np.empty((n, h, w, c), np.float32)
    cdll.dmp_augment_normalize(
        np.ascontiguousarray(images), n, h, w, c,
        ys.astype(np.int32), xs.astype(np.int32),
        flips.astype(np.uint8), padding,
        mean.astype(np.float32), std.astype(np.float32), out, workers,
    )
    return out


def normalize(
    images: np.ndarray,
    mean: np.ndarray,
    std: np.ndarray,
    workers: int = 1,
) -> np.ndarray:
    cdll = lib()
    n, h, w, c = images.shape
    out = np.empty((n, h, w, c), np.float32)
    cdll.dmp_normalize(
        np.ascontiguousarray(images), n, h, w, c,
        mean.astype(np.float32), std.astype(np.float32), out, workers,
    )
    return out
