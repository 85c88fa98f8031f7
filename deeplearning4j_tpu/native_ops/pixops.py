"""ctypes bindings for the native pixel/hash kernels (native/pixops.cpp).

Reference parity:
  * ImagePreProcessingScaler / NormalizerStandardize: their elementwise
    loops are native in the reference (libnd4j legacy transform kernels).
    Here the HOST-side input pipeline normalizes uint8 image batches in C++
    before device_put, keeping byte-wrangling off Python; the device path
    stays XLA.
  * murmur3_32: nd4j-common HashUtil role — stable bytes/string hashing
    for vocab bucketing and shard assignment.

Numpy fallbacks mirror the C ABI exactly when no toolchain is available.
"""

from __future__ import annotations

import ctypes
from typing import Optional, Union

import numpy as np

from deeplearning4j_tpu.native_ops.threshold import _get_lib


def _pix_lib() -> Optional[ctypes.CDLL]:
    lib = _get_lib()
    if lib is None:
        return None
    if not getattr(lib, "_pixops_bound", False):
        lib.u8_normalize.restype = None
        lib.u8_normalize.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
            ctypes.c_float, ctypes.c_float,
            ctypes.POINTER(ctypes.c_float)]
        lib.u8_standardize.restype = None
        lib.u8_standardize.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
            ctypes.c_int64, ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_float)]
        lib.murmur3_32.restype = ctypes.c_uint32
        lib.murmur3_32.argtypes = [
            ctypes.POINTER(ctypes.c_uint8), ctypes.c_int64,
            ctypes.c_uint32]
        lib._pixops_bound = True
    return lib


def u8_normalize(img: np.ndarray, scale: float, shift: float = 0.0) -> np.ndarray:
    """float32 out = u8 in * scale + shift (ImagePreProcessingScaler path)."""
    img = np.ascontiguousarray(img, dtype=np.uint8)
    lib = _pix_lib()
    out = np.empty(img.shape, np.float32)
    if lib is not None:
        lib.u8_normalize(
            img.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), img.size,
            ctypes.c_float(scale), ctypes.c_float(shift),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        return out
    np.multiply(img, np.float32(scale), out=out)
    out += np.float32(shift)
    return out


def u8_standardize(img: np.ndarray, mean: np.ndarray,
                   std: np.ndarray) -> np.ndarray:
    """Channel-last z-score of a uint8 image batch (NormalizerStandardize
    path): out = (in - mean[c]) / std[c], c = trailing axis."""
    img = np.ascontiguousarray(img, dtype=np.uint8)
    c = img.shape[-1]
    mean = np.ascontiguousarray(np.broadcast_to(mean, (c,)), np.float32)
    inv = np.ascontiguousarray(
        1.0 / np.maximum(np.broadcast_to(std, (c,)).astype(np.float32), 1e-8))
    lib = _pix_lib()
    out = np.empty(img.shape, np.float32)
    if lib is not None:
        lib.u8_standardize(
            img.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)), img.size, c,
            mean.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            inv.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        return out
    return ((img.astype(np.float32) - mean) * inv).astype(np.float32)


def _murmur3_py(data: bytes, seed: int) -> int:
    """Numpy-free MurmurHash3 x86-32 fallback, bit-exact vs the C kernel."""
    c1, c2 = 0xCC9E2D51, 0x1B873593
    h = seed & 0xFFFFFFFF
    n = len(data)
    for i in range(0, n - (n & 3), 4):
        k = int.from_bytes(data[i:i + 4], "little")
        k = (k * c1) & 0xFFFFFFFF
        k = ((k << 15) | (k >> 17)) & 0xFFFFFFFF
        k = (k * c2) & 0xFFFFFFFF
        h ^= k
        h = ((h << 13) | (h >> 19)) & 0xFFFFFFFF
        h = (h * 5 + 0xE6546B64) & 0xFFFFFFFF
    k = 0
    tail = data[n - (n & 3):]
    if n & 3 >= 3:
        k ^= tail[2] << 16
    if n & 3 >= 2:
        k ^= tail[1] << 8
    if n & 3 >= 1:
        k ^= tail[0]
        k = (k * c1) & 0xFFFFFFFF
        k = ((k << 15) | (k >> 17)) & 0xFFFFFFFF
        k = (k * c2) & 0xFFFFFFFF
        h ^= k
    h ^= n
    h ^= h >> 16
    h = (h * 0x85EBCA6B) & 0xFFFFFFFF
    h ^= h >> 13
    h = (h * 0xC2B2AE35) & 0xFFFFFFFF
    h ^= h >> 16
    return h


def murmur3_32(data: Union[str, bytes], seed: int = 0) -> int:
    """Stable 32-bit hash (HashUtil analog). Strings hash as UTF-8."""
    if isinstance(data, str):
        data = data.encode("utf-8")
    lib = _pix_lib()
    if lib is not None:
        buf = (ctypes.c_uint8 * len(data)).from_buffer_copy(data) if data \
            else (ctypes.c_uint8 * 1)()
        return int(lib.murmur3_32(buf, len(data), ctypes.c_uint32(seed)))
    return _murmur3_py(bytes(data), seed)
