"""NativeLoader — locate, (re)build, and bind the native runtime library.

Analog of the reference's NativeLoader
(ref: src/core/env/src/main/scala/NativeLoader.java:28,47-68): the
reference extracts per-OS .so files from jar resources to a temp dir and
System.load()s them; here the library lives next to the package (built
once by cmake) and binds through ctypes. Everything that calls into it
falls back to pure numpy when the library is unavailable — native is an
accelerator, never a requirement.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

from mmlspark_tpu.core.logging_utils import get_logger

log = get_logger("native")

_NATIVE_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB_PATH = os.path.join(_NATIVE_DIR, "lib", "libmml_native.so")
_SRC_PATH = os.path.join(_NATIVE_DIR, "src", "mml_native.cpp")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _stale() -> bool:
    """The library is missing, or older than the source it was built
    from: the bindings below assume the symbols of the source as
    committed."""
    if not os.path.exists(_LIB_PATH):
        return True
    return (os.path.exists(_SRC_PATH)
            and os.path.getmtime(_LIB_PATH) < os.path.getmtime(_SRC_PATH))


def _build() -> bool:
    """cmake build (the packaging-time step; done lazily here so source
    checkouts self-provision)."""
    build_dir = os.path.join(_NATIVE_DIR, "build")
    os.makedirs(build_dir, exist_ok=True)
    try:
        subprocess.run(["cmake", "-S", _NATIVE_DIR, "-B", build_dir,
                        "-DCMAKE_BUILD_TYPE=Release"],
                       check=True, capture_output=True, timeout=120)
        subprocess.run(["cmake", "--build", build_dir, "-j"],
                       check=True, capture_output=True, timeout=300)
        return os.path.exists(_LIB_PATH)
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired,
            FileNotFoundError) as e:
        out = getattr(e, "stderr", b"")
        log.warning("native build failed (%s); using numpy fallbacks: %s",
                    type(e).__name__,
                    out.decode()[-500:] if out else e)
        return False


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.mml_free.argtypes = [ctypes.c_void_p]
    lib.mml_decode_image.argtypes = [
        u8p, ctypes.c_int, ctypes.POINTER(u8p),
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int)]
    lib.mml_decode_image.restype = ctypes.c_int
    lib.mml_resize_bilinear_u8.argtypes = [
        u8p, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        u8p, ctypes.c_int, ctypes.c_int]
    lib.mml_resize_bilinear_u8.restype = ctypes.c_int
    lib.mml_unroll_chw.argtypes = [u8p, ctypes.c_int, ctypes.c_int,
                                   ctypes.c_int,
                                   ctypes.POINTER(ctypes.c_double)]
    lib.mml_unroll_chw.restype = ctypes.c_int
    lib.mml_apply_bins.argtypes = [
        ctypes.POINTER(ctypes.c_double), ctypes.c_long, ctypes.c_int,
        ctypes.POINTER(ctypes.c_double), ctypes.POINTER(ctypes.c_long),
        ctypes.POINTER(ctypes.c_int32)]
    lib.mml_apply_bins.restype = ctypes.c_int
    lib.mml_apply_bins_t_u8.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_long, ctypes.c_int,
        ctypes.POINTER(ctypes.c_double),
        ctypes.POINTER(ctypes.c_long),
        ctypes.POINTER(ctypes.c_uint8)]
    lib.mml_apply_bins_t_u8.restype = ctypes.c_int
    lib.mml_apply_bins_t_u8_range.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.c_long, ctypes.c_int,
        ctypes.c_int, ctypes.c_int,
        ctypes.POINTER(ctypes.c_double),
        ctypes.POINTER(ctypes.c_long),
        ctypes.POINTER(ctypes.c_uint8)]
    lib.mml_apply_bins_t_u8_range.restype = ctypes.c_int
    return lib


def get_lib(allow_build: bool = True) -> Optional[ctypes.CDLL]:
    """The loaded library, or None when unavailable. Thread-safe,
    attempts the build exactly once per process."""
    global _lib, _tried
    if _lib is not None:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("MMLSPARK_TPU_NO_NATIVE") == "1":
            return None  # kill-switch: force pure-numpy paths
        if _stale() and not (allow_build and _build()):
            return None
        try:
            _lib = _bind(ctypes.CDLL(_LIB_PATH))
            log.info("native library loaded from %s", _LIB_PATH)
        except OSError as e:
            log.warning("failed to load %s: %s", _LIB_PATH, e)
            _lib = None
    return _lib


def available() -> bool:
    return get_lib() is not None


# ---------------------------------------------------------------------------
# numpy-facing wrappers
# ---------------------------------------------------------------------------

import numpy as np  # noqa: E402


def decode_image(data: bytes) -> Optional[np.ndarray]:
    """JPEG/PNG bytes -> RGB uint8 HWC array, or None if undecodable."""
    lib = get_lib()
    if lib is None:
        return None
    u8p = ctypes.POINTER(ctypes.c_uint8)
    out = u8p()
    h = ctypes.c_int()
    w = ctypes.c_int()
    c = ctypes.c_int()
    buf = (ctypes.c_uint8 * len(data)).from_buffer_copy(data)
    rc = lib.mml_decode_image(buf, len(data), ctypes.byref(out),
                              ctypes.byref(h), ctypes.byref(w),
                              ctypes.byref(c))
    if rc != 0:
        return None
    n = h.value * w.value * c.value
    try:
        arr = np.ctypeslib.as_array(out, shape=(n,)).copy()
    finally:
        lib.mml_free(out)
    return arr.reshape(h.value, w.value, c.value)


def resize_u8(img: np.ndarray, oh: int, ow: int) -> Optional[np.ndarray]:
    lib = get_lib()
    if lib is None:
        return None
    img = np.ascontiguousarray(img, dtype=np.uint8)
    h, w, c = img.shape
    dst = np.empty((oh, ow, c), dtype=np.uint8)
    rc = lib.mml_resize_bilinear_u8(
        img.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), h, w, c,
        dst.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), oh, ow)
    return dst if rc == 0 else None


def unroll_chw(img: np.ndarray) -> Optional[np.ndarray]:
    lib = get_lib()
    if lib is None:
        return None
    img = np.ascontiguousarray(img, dtype=np.uint8)
    h, w, c = img.shape
    dst = np.empty(h * w * c, dtype=np.float64)
    rc = lib.mml_unroll_chw(
        img.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), h, w, c,
        dst.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
    return dst if rc == 0 else None


def apply_bins(X: np.ndarray, upper_bounds: list) -> Optional[np.ndarray]:
    """Parallel per-feature searchsorted (binning.BinMapper.transform
    fast path)."""
    lib = get_lib()
    if lib is None:
        return None
    X = np.ascontiguousarray(X, dtype=np.float64)
    n, f = X.shape
    bounds = (np.concatenate([np.asarray(u, dtype=np.float64)
                              for u in upper_bounds])
              if upper_bounds and any(len(u) for u in upper_bounds)
              else np.zeros(0))
    offsets = np.zeros(f + 1, dtype=np.int64)
    for j, u in enumerate(upper_bounds):
        offsets[j + 1] = offsets[j] + len(u)
    out = np.empty((n, f), dtype=np.int32)
    rc = lib.mml_apply_bins(
        X.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), n, f,
        bounds.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    return out if rc == 0 else None


def apply_bins_t_u8(X: np.ndarray, upper_bounds: list,
                    feature_range: Optional[tuple] = None,
                    ) -> Optional[np.ndarray]:
    """Fused bin+transpose+narrow: (n, f) f32/f64 features ->
    FEATURES-MAJOR (f, n) uint8 bins in one native pass (the GBDT
    engine's ship layout). ``feature_range=(j0, j1)`` bins only that
    column slice into a (j1-j0, n) block without copying X — the unit
    of the pipelined host-bin/device-ship overlap. Requires every
    feature's bin count <= 256."""
    lib = get_lib()
    if lib is None:
        return None
    if any(len(u) + 1 > 256 for u in upper_bounds):
        return None
    X = np.ascontiguousarray(X)
    if X.dtype == np.float32:
        is_f32 = 1
    elif X.dtype == np.float64:
        is_f32 = 0
    else:
        X = np.ascontiguousarray(X, dtype=np.float64)
        is_f32 = 0
    n, f = X.shape
    bounds = (np.concatenate([np.asarray(u, dtype=np.float64)
                              for u in upper_bounds])
              if upper_bounds and any(len(u) for u in upper_bounds)
              else np.zeros(0))
    offsets = np.zeros(f + 1, dtype=np.int64)
    for j, u in enumerate(upper_bounds):
        offsets[j + 1] = offsets[j] + len(u)
    if feature_range is None:
        out = np.empty((f, n), dtype=np.uint8)
        rc = lib.mml_apply_bins_t_u8(
            X.ctypes.data_as(ctypes.c_void_p), is_f32, n, f,
            bounds.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    else:
        j0, j1 = int(feature_range[0]), int(feature_range[1])
        if not 0 <= j0 < j1 <= f:
            raise ValueError(f"feature_range {feature_range} outside "
                             f"[0, {f})")
        out = np.empty((j1 - j0, n), dtype=np.uint8)
        rc = lib.mml_apply_bins_t_u8_range(
            X.ctypes.data_as(ctypes.c_void_p), is_f32, n, f, j0, j1,
            bounds.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
            offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    return out if rc == 0 else None
