"""Native (C++) host-side data-path kernels, bound via ctypes.

Built lazily with g++ on first use and cached next to the source (no
pybind11 in this image — plain C ABI + ctypes, per the environment
constraints). Everything has a pure-Python fallback: ``available()`` tells
you which path you're on (``require()`` turns the fallback into an error
that says why), and the public helpers raise nothing at import
time on machines without a toolchain.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional, Tuple

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "fastimage.cpp")
_LIB_PATH = os.path.join(_DIR, "_fastimage.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
_error: Optional[str] = None   # why the native path is off, once known


def build() -> str:
    """Compile ``fastimage.cpp`` to ``_fastimage.so`` and return its path.

    Compiles to a private temp path and renames into place: atomic on
    POSIX, so concurrent dataloader worker processes never dlopen a
    half-written .so. Raises ``RuntimeError`` carrying the compiler's
    own message when the toolchain is missing or the build fails."""
    tmp = f"{_LIB_PATH}.{os.getpid()}.tmp"
    cmd = [
        "g++", "-O3", "-shared", "-fPIC", "-std=c++17",
        _SRC, "-o", tmp, "-lz",
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _LIB_PATH)
    except (OSError, subprocess.SubprocessError) as exc:
        if os.path.exists(tmp):
            os.unlink(tmp)
        detail = getattr(exc, "stderr", b"") or b""
        raise RuntimeError(
            f"building {_LIB_PATH} failed: {exc!r} "
            f"{detail.decode(errors='replace')[-2000:]}") from exc
    return _LIB_PATH


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried, _error
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            if not os.path.exists(_LIB_PATH) or (
                os.path.getmtime(_LIB_PATH) < os.path.getmtime(_SRC)
            ):
                build()
            lib = ctypes.CDLL(_LIB_PATH)
        except (RuntimeError, OSError) as exc:
            # no toolchain / unloadable .so: the PIL path serves, and
            # require() says why
            _error = str(exc)
            return None
        lib.png_decode.restype = ctypes.c_int
        lib.png_decode.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int64),
        ]
        lib.normalize_f32.restype = None
        lib.normalize_f32.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64
        ]
        lib.quantize_u8.restype = None
        lib.quantize_u8.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64, ctypes.c_int
        ]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


def require() -> None:
    """Raise unless the native decoder is loaded — for callers (the chip
    smoke) that must not run on the PIL fallback unnoticed."""
    if _load() is None:
        raise RuntimeError(f"native fastimage unavailable: {_error}")


def png_decode(data: bytes) -> Optional[np.ndarray]:
    """Decode an 8-bit RGB/RGBA non-interlaced PNG to (H, W, 3) uint8.

    Returns None for unsupported inputs (caller falls back to PIL)."""
    lib = _load()
    if lib is None:
        return None
    w = ctypes.c_int64()
    h = ctypes.c_int64()
    rc = lib.png_decode(data, len(data), None, ctypes.byref(w), ctypes.byref(h))
    if rc != 0:
        return None
    out = np.empty((h.value, w.value, 3), np.uint8)
    rc = lib.png_decode(
        data, len(data), out.ctypes.data_as(ctypes.c_void_p),
        ctypes.byref(w), ctypes.byref(h),
    )
    if rc != 0:
        return None
    return out


def normalize_f32(img: np.ndarray) -> Optional[np.ndarray]:
    """uint8 HWC → float32 [-1,1] (ToTensor + Normalize(.5) semantics)."""
    lib = _load()
    if lib is None:
        return None
    img = np.ascontiguousarray(img, np.uint8)
    out = np.empty(img.shape, np.float32)
    lib.normalize_f32(
        img.ctypes.data_as(ctypes.c_void_p),
        out.ctypes.data_as(ctypes.c_void_p),
        img.size,
    )
    return out


def quantize_u8(img: np.ndarray, bits: int = 3) -> Optional[np.ndarray]:
    """Bit-depth quantizer on uint8 (compress_uint8 parity)."""
    lib = _load()
    if lib is None:
        return None
    img = np.ascontiguousarray(img, np.uint8)
    out = np.empty(img.shape, np.uint8)
    lib.quantize_u8(
        img.ctypes.data_as(ctypes.c_void_p),
        out.ctypes.data_as(ctypes.c_void_p),
        img.size, bits,
    )
    return out


def load_image_fast(
    path: str, expect_hw: Optional[Tuple[int, int]] = None
) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Read + decode + normalize a PNG entirely natively.

    ``expect_hw``: bail out after the cheap header probe (no inflate) when
    the stored size differs — the caller's PIL+resize path takes over
    without having paid for a full decode.

    Returns (uint8_hwc, float32_hwc_in_[-1,1]) or None (fallback)."""
    if not path.lower().endswith(".png"):
        return None
    lib = _load()
    if lib is None:
        return None
    with open(path, "rb") as f:
        data = f.read()
    if expect_hw is not None:
        w = ctypes.c_int64()
        h = ctypes.c_int64()
        rc = lib.png_decode(
            data, len(data), None, ctypes.byref(w), ctypes.byref(h)
        )
        if rc != 0 or (h.value, w.value) != tuple(expect_hw):
            return None
    u8 = png_decode(data)
    if u8 is None:
        return None
    f32 = normalize_f32(u8)
    return u8, f32
