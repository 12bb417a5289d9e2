"""ctypes bridge to the native image-geometry kernel.

Builds ``tpu_compressed_dp/native/image_ops.cpp`` on first use (g++ is part
of the toolchain; the .so is cached next to the source, keyed by a source
hash) and exposes :func:`crop_resize` — fused crop + PIL-BILINEAR resize +
horizontal flip on uint8 RGB arrays.  ctypes releases the GIL for the call,
so the loaders' thread pools parallelise across images.

:func:`available` is False when no compiler exists or the build fails, and
the loaders keep their pure-PIL path; the first load says on stderr which of
the two paths this process took, and why.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import sys
import threading
from typing import Optional, Tuple

import numpy as np

__all__ = ["available", "crop_resize", "build", "lib_path"]

_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "native", "image_ops.cpp")
_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None
_FAILED = False


def lib_path() -> str:
    with open(_SRC, "rb") as f:
        tag = hashlib.sha256(f.read()).hexdigest()[:12]
    return os.path.join(os.path.dirname(_SRC), f"libimageops_{tag}.so")


def build(verbose: bool = False) -> str:
    """Compile the kernel if the cached .so is stale; returns the .so path."""
    out = lib_path()
    if not os.path.exists(out):
        cmd = ["g++", "-O3", "-fPIC", "-shared", "-pthread", _SRC, "-o", out]
        res = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
        if res.returncode != 0:
            raise RuntimeError(f"native build failed: {res.stderr[-500:]}")
        if verbose:
            print(f"built {out}")
    return out


def _load() -> Optional[ctypes.CDLL]:
    global _LIB, _FAILED
    if _LIB is not None or _FAILED:
        return _LIB
    with _LOCK:
        if _LIB is not None or _FAILED:
            return _LIB
        try:
            lib = ctypes.CDLL(build())
        except (OSError, RuntimeError, subprocess.SubprocessError) as err:
            _FAILED = True
            print(f"image ops: PIL path (native kernel unavailable: {err})",
                  file=sys.stderr)
            return None
        print(f"image ops: native kernel {lib._name}", file=sys.stderr)
        lib.crop_resize_bilinear.restype = ctypes.c_int
        lib.crop_resize_bilinear.argtypes = [
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
            ctypes.c_float, ctypes.c_float, ctypes.c_float, ctypes.c_float,
            ctypes.c_void_p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ]
        _LIB = lib
    return _LIB


def available() -> bool:
    return _load() is not None


def crop_resize(src: np.ndarray, box: Tuple[float, float, float, float],
                out_h: int, out_w: int, flip: bool = False) -> np.ndarray:
    """Crop ``box`` (x0, y0, x1, y1) from an HWC uint8 RGB array, resize to
    (out_h, out_w) with PIL-BILINEAR semantics, optionally mirror."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native image ops unavailable (build failed?)")
    src = np.ascontiguousarray(src, dtype=np.uint8)
    if src.ndim != 3 or src.shape[2] != 3:
        raise ValueError(f"expected HWC RGB uint8, got {src.shape}")
    dst = np.empty((out_h, out_w, 3), np.uint8)
    rc = lib.crop_resize_bilinear(
        src.ctypes.data, src.shape[0], src.shape[1],
        float(box[0]), float(box[1]), float(box[2]), float(box[3]),
        dst.ctypes.data, out_h, out_w, int(bool(flip)),
    )
    if rc != 0:
        raise RuntimeError(f"crop_resize_bilinear failed with code {rc}")
    return dst
