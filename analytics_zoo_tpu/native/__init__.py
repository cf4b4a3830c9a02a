"""ctypes binding for the native host-side image pipeline (zoo_native.cc).

The reference delegated image decode to OpenCV through JNI
(feature/image/OpenCVMethod.scala); here the equivalent C++ library is
built on demand with the system toolchain and bound via ctypes (pybind11
is not available in this environment).  ``available()`` is False when
the toolchain or libjpeg/libpng are missing; callers then fall back to
PIL, and the reason is logged once (``build_error()`` keeps it).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import List, Optional, Sequence

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "zoo_native.cc")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_error: Optional[str] = None


def _lib_path() -> str:
    """The binary is named by its source's digest, so it is only ever
    reused for the exact source it was built from.  (An mtime
    comparison means nothing after the tree has been copied, and the
    binary is never committed: first use in a fresh checkout builds.)"""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    return os.path.join(_DIR, f"libzoo_native.{digest}.so")


def _build(lib_path: str) -> None:
    # build to a per-process temp path and rename atomically: concurrent
    # first-use builds from several worker processes must never leave a
    # torn .so at the final path
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    cmd = ["g++", "-O3", "-fPIC", "-shared", "-std=c++17", _SRC,
           "-o", tmp, "-ljpeg", "-lpng", "-lpthread"]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise RuntimeError(f"native build failed: {proc.stderr[-2000:]}")
    os.replace(tmp, lib_path)


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _build_error
    if _lib is not None or _build_error is not None:
        return _lib
    with _lock:
        if _lib is not None or _build_error is not None:
            return _lib
        try:
            lib_path = _lib_path()
            if not os.path.exists(lib_path):
                _build(lib_path)
            lib = ctypes.CDLL(lib_path)
            lib.zoo_decode_rgb.restype = ctypes.c_int
            lib.zoo_decode_rgb.argtypes = [
                ctypes.c_char_p, ctypes.c_size_t,
                ctypes.POINTER(ctypes.c_void_p),
                ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int)]
            lib.zoo_free.argtypes = [ctypes.c_void_p]
            lib.zoo_resize_bilinear.argtypes = [
                ctypes.c_char_p, ctypes.c_int, ctypes.c_int,
                ctypes.c_char_p, ctypes.c_int, ctypes.c_int]
            lib.zoo_decode_batch.restype = ctypes.c_int
            lib.zoo_decode_batch.argtypes = [
                ctypes.POINTER(ctypes.c_char_p),
                ctypes.POINTER(ctypes.c_size_t), ctypes.c_int,
                ctypes.c_int, ctypes.c_int,
                ctypes.POINTER(ctypes.c_float),
                ctypes.POINTER(ctypes.c_float),
                ctypes.c_float, ctypes.c_int,
                ctypes.POINTER(ctypes.c_float)]
            lib.zoo_native_abi_version.restype = ctypes.c_int
            if lib.zoo_native_abi_version() != 1:
                raise RuntimeError("native ABI mismatch")
            _lib = lib
        except Exception as e:  # toolchain/libs absent: PIL fallback
            _build_error = str(e)
            from ..observability.log import get_logger
            get_logger("zoo.native").warning(
                "native_unavailable_pil_fallback",
                error=f"{type(e).__name__}: {e}")
    return _lib


def available() -> bool:
    """True when the native library is (or can be) loaded."""
    return _load() is not None


def build_error() -> Optional[str]:
    _load()
    return _build_error


def decode_image(data: bytes) -> np.ndarray:
    """Decode a JPEG/PNG blob to an (H, W, 3) uint8 RGB array."""
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native library unavailable: {_build_error}")
    out = ctypes.c_void_p()
    w = ctypes.c_int()
    h = ctypes.c_int()
    rc = lib.zoo_decode_rgb(data, len(data), ctypes.byref(out),
                            ctypes.byref(w), ctypes.byref(h))
    if rc != 0:
        raise ValueError("image decode failed (not a valid JPEG/PNG?)")
    try:
        buf = ctypes.cast(out, ctypes.POINTER(
            ctypes.c_uint8 * (w.value * h.value * 3))).contents
        return np.frombuffer(buf, dtype=np.uint8).reshape(
            h.value, w.value, 3).copy()
    finally:
        lib.zoo_free(out)


def decode_resize_normalize_batch(
        blobs: Sequence[bytes], size, mean: Optional[Sequence[float]] = None,
        std: Optional[Sequence[float]] = None, scale: float = 1.0,
        num_threads: int = 0,
        errors: str = "raise") -> np.ndarray:
    """Decode + resize + normalize a batch of image blobs into float32 NHWC.

    Per pixel channel c: ``(pixel * scale - mean[c]) / std[c]`` (means/stds
    in the same 0-255 scale the reference's ChannelNormalize uses when
    scale=1).  ``errors='zero'`` zero-fills undecodable slots instead of
    raising.
    """
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native library unavailable: {_build_error}")
    h, w = (size, size) if isinstance(size, int) else tuple(size)
    n = len(blobs)
    out = np.empty((n, h, w, 3), dtype=np.float32)
    if n == 0:
        return out
    blob_arr = (ctypes.c_char_p * n)(*[bytes(b) for b in blobs])
    len_arr = (ctypes.c_size_t * n)(*[len(b) for b in blobs])
    mean_p = ((ctypes.c_float * 3)(*[float(v) for v in mean])
              if mean is not None else None)
    std_p = ((ctypes.c_float * 3)(*[float(v) for v in std])
             if std is not None else None)
    failures = lib.zoo_decode_batch(
        blob_arr, len_arr, n, h, w, mean_p, std_p,
        ctypes.c_float(scale), num_threads,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
    if failures and errors == "raise":
        raise ValueError(f"{failures}/{n} images failed to decode")
    return out


def resize_bilinear(img: np.ndarray, size) -> np.ndarray:
    """Bilinear-resize an (H, W, 3) uint8 array (half-pixel centers)."""
    lib = _load()
    if lib is None:
        raise RuntimeError(f"native library unavailable: {_build_error}")
    h, w = (size, size) if isinstance(size, int) else tuple(size)
    img = np.ascontiguousarray(img, dtype=np.uint8)
    sh, sw, c = img.shape
    if c != 3:
        raise ValueError("expected (H, W, 3) RGB input")
    dst = np.empty((h, w, 3), dtype=np.uint8)
    lib.zoo_resize_bilinear(
        img.ctypes.data_as(ctypes.c_char_p), sw, sh,
        dst.ctypes.data_as(ctypes.c_char_p), w, h)
    return dst
