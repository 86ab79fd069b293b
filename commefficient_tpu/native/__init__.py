"""Native (C++) host data plane — build-on-first-use ctypes bindings.

``lib()`` returns the loaded shared library, compiling ``fedio.cpp`` with
g++ on first use (cached next to the source, keyed by a source hash).
Returns ``None`` — and the callers fall back to pure numpy — when no
compiler is available or ``COMMEFFICIENT_NO_NATIVE=1`` is set, so the
framework stays importable everywhere. See fedio.cpp for what lives here
and why randomness stays in Python.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "fedio.cpp")
_ABI = 2

_lock = threading.Lock()
_cached = False
_handle = None


def _build() -> str | None:
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    so = os.path.join(_DIR, f"_fedio_{digest}.so")
    if os.path.exists(so):
        return so
    tmp = so + f".tmp{os.getpid()}"
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
           _SRC, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
    except (OSError, subprocess.SubprocessError):
        return None
    os.replace(tmp, so)  # atomic: concurrent builders race benignly
    for old in os.listdir(_DIR):
        if (old.startswith("_fedio_") and old.endswith(".so")
                and old != os.path.basename(so)):
            try:
                os.remove(os.path.join(_DIR, old))
            except OSError:
                pass
    return so


def _declare(h) -> None:
    i64, i32p, f32p, u8p = (ctypes.c_int64,
                            np.ctypeslib.ndpointer(np.int32, flags="C"),
                            np.ctypeslib.ndpointer(np.float32, flags="C"),
                            np.ctypeslib.ndpointer(np.uint8, flags="C"))
    h.fedio_rrc_batch.argtypes = [u8p, i64, i64, i64, i64, i32p, f32p, i64,
                                  f32p, f32p, ctypes.c_int]
    h.fedio_rrc_batch.restype = None
    h.fedio_pad_crop_batch.argtypes = [f32p, i64, i64, i64, i64, i32p, f32p,
                                       ctypes.c_int, ctypes.c_int,
                                       ctypes.c_float, ctypes.c_int]
    h.fedio_pad_crop_batch.restype = None
    i64p = np.ctypeslib.ndpointer(np.int64, flags="C")
    h.fedio_pad_crop_round.argtypes = [i64p, i64p, i64, i64, i64, i64, i32p,
                                       f32p, f32p, ctypes.c_int, ctypes.c_int,
                                       ctypes.c_float, ctypes.c_int]
    h.fedio_pad_crop_round.restype = None
    h.fedio_gather_rows.argtypes = [u8p, i64p, i64, i64, u8p, ctypes.c_int]
    h.fedio_gather_rows.restype = None
    h.fedio_abi_version.restype = ctypes.c_int


def lib():
    """The loaded fedio library, or None if native is unavailable."""
    global _cached, _handle
    if _cached:
        return _handle
    with _lock:
        if _cached:
            return _handle
        handle = None
        if os.environ.get("COMMEFFICIENT_NO_NATIVE") != "1":
            so = _build()
            if so is not None:
                try:
                    h = ctypes.CDLL(so)
                    _declare(h)
                    if h.fedio_abi_version() == _ABI:
                        handle = h
                except OSError:
                    handle = None
        _handle, _cached = handle, True
    return _handle


def default_threads() -> int:
    return max(1, min(os.cpu_count() or 1, 16))


def rrc_batch(src: np.ndarray, params: np.ndarray, size: int,
              scale: np.ndarray, bias: np.ndarray) -> np.ndarray:
    """Fused crop+resize+flip+affine; see fedio.cpp. src uint8 NHWC."""
    h = lib()
    assert h is not None
    B, H, W, C = src.shape
    src = np.ascontiguousarray(src)
    params = np.ascontiguousarray(params, np.int32)
    out = np.empty((B, size, size, C), np.float32)
    h.fedio_rrc_batch(src, B, H, W, C, params, out, size,
                      np.ascontiguousarray(scale, np.float32),
                      np.ascontiguousarray(bias, np.float32),
                      default_threads())
    return out


def pad_crop_batch(src: np.ndarray, params: np.ndarray, pad: int,
                   reflect: bool, fill: float) -> np.ndarray:
    """Fused pad+crop+flip on float NHWC; see fedio.cpp."""
    h = lib()
    assert h is not None
    B, H, W, C = src.shape
    src = np.ascontiguousarray(src, np.float32)
    params = np.ascontiguousarray(params, np.int32)
    out = np.empty_like(src)
    h.fedio_pad_crop_batch(src, B, H, W, C, params, out, pad,
                           int(reflect), float(fill), default_threads())
    return out


def pad_crop_round(src_addr: np.ndarray, slots: np.ndarray,
                   params: np.ndarray, table: np.ndarray, out: np.ndarray,
                   pad: int, reflect: bool, fill: float) -> None:
    """normalize + pad + crop + flip of a round's images in one pass, each
    written once into ``out``; see fedio.cpp. ``src_addr[i]`` is the address
    of image i's uint8 (H, W, C) block -- the caller keeps the arrays they
    point into alive and has checked the rows -- and ``slots[i]`` its place
    among the images of ``out`` (float32, (..., H, W, C), C-contiguous)."""
    h = lib()
    assert h is not None
    H, W, C = out.shape[-3:]
    n = len(src_addr)
    if out.dtype != np.float32 or not out.flags.c_contiguous:
        raise ValueError("pad_crop_round writes a C-contiguous float32 array")
    if not (len(slots) == len(params) == n and params.shape == (n, 3)
            and table.shape == (256, C)):
        raise ValueError("pad_crop_round: one source, slot and (y, x, flip) "
                         "an image, and a 256 x C table")
    if n == 0:
        return
    if slots.min() < 0 or slots.max() >= out.size // (H * W * C):
        raise IndexError(f"pad_crop_round: slot out of range for "
                         f"{out.size // (H * W * C)} images")
    if (not 0 <= pad < min(H, W) or params[:, :2].min() < 0
            or params[:, :2].max() > 2 * pad):
        raise ValueError(f"pad_crop_round: offsets outside [0, {2 * pad}]")
    h.fedio_pad_crop_round(
        np.ascontiguousarray(src_addr, np.int64),
        np.ascontiguousarray(slots, np.int64), n, H, W, C,
        np.ascontiguousarray(params, np.int32),
        np.ascontiguousarray(table, np.float32), out, pad, int(reflect),
        float(fill), default_threads())


def gather_rows(src: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """out[i] = src[idx[i]] with a threaded memcpy (GIL released); works on
    memory-mapped sources. Rows must be C-contiguous fixed-size. Indices
    are bounds-checked here — the C side is a raw memcpy and would read
    out-of-buffer memory where numpy fancy indexing raises."""
    h = lib()
    assert h is not None
    idx = np.ascontiguousarray(idx, np.int64)
    out = np.empty((len(idx),) + src.shape[1:], src.dtype)
    if len(idx) == 0 or src.size == 0:
        return src[idx]  # numpy raises on bad idx into empty src
    if idx.min() < 0 or idx.max() >= src.shape[0]:
        raise IndexError(
            f"gather_rows: index out of range for {src.shape[0]} rows "
            f"(min {idx.min()}, max {idx.max()})")
    row_bytes = int(np.prod(src.shape[1:], dtype=np.int64)) * src.itemsize
    h.fedio_gather_rows(
        src.reshape(src.shape[0], row_bytes // src.itemsize).view(np.uint8),
        idx, len(idx), row_bytes,
        out.reshape(len(idx), row_bytes // src.itemsize).view(np.uint8),
        default_threads())
    return out
