"""ctypes bindings for the native record loader (native/record_loader.cpp).

Reference parity: the reference's record readers bottom out in native
loaders (JavaCPP wrappers); here CSVRecordReader's all-numeric fast path
and the IDX (MNIST/EMNIST) readers delegate to C++ when the shared lib is
available, with a transparent numpy fallback otherwise.
"""

from __future__ import annotations

import ctypes
from typing import Optional, Tuple

import numpy as np

from deeplearning4j_tpu.native_ops.threshold import _get_lib


def _loader_lib() -> Optional[ctypes.CDLL]:
    lib = _get_lib()
    if lib is None:
        return None
    if not getattr(lib, "_record_loader_bound", False):
        lib.csv_parse_floats.restype = ctypes.c_int64
        lib.csv_parse_floats.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_char,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.POINTER(ctypes.c_float)]
        lib.idx_parse.restype = ctypes.c_int64
        lib.idx_parse.argtypes = [
            ctypes.POINTER(ctypes.c_ubyte), ctypes.c_int64, ctypes.c_int,
            ctypes.POINTER(ctypes.c_float), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64), ctypes.POINTER(ctypes.c_int)]
        lib._record_loader_bound = True
    return lib


def native_loader_available() -> bool:
    return _loader_lib() is not None


def csv_to_float_matrix(text: str, cols: int, *, delimiter: str = ",",
                        skip_rows: int = 0,
                        max_rows: Optional[int] = None) -> np.ndarray:
    """One-pass CSV → (rows, cols) float32; non-numeric/empty cells are NaN.
    Raises ValueError on ragged rows (same contract as the Python path)."""
    data = text.encode()
    cap = max_rows if max_rows is not None else \
        text.count("\n") + text.count("\r") + 1
    lib = _loader_lib()
    if lib is not None:
        out = np.empty((cap, cols), np.float32)
        n = lib.csv_parse_floats(
            data, len(data), delimiter.encode(), skip_rows, cols, cap,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        if n < 0:
            raise ValueError("ragged CSV: a row does not have "
                             f"{cols} fields")
        return out[:n]
    # numpy fallback — same semantics
    rows = []
    for i, line in enumerate(text.splitlines()):
        if i < skip_rows or not line.strip():
            continue
        parts = line.split(delimiter)
        if len(parts) != cols:
            raise ValueError(f"ragged CSV: a row does not have {cols} fields")
        vals = []
        for p in parts:
            # same accepted syntax as the native parser: plain
            # decimal/scientific (no hex, no underscore separators)
            if "_" in p or "x" in p.lower():
                vals.append(float("nan"))
                continue
            try:
                vals.append(float(p))
            except ValueError:
                vals.append(float("nan"))
        rows.append(vals)
        if max_rows is not None and len(rows) >= max_rows:
            break
    return np.asarray(rows, np.float32).reshape(-1, cols)


def idx_to_array(buf: bytes, *, scale: bool = True) -> np.ndarray:
    """IDX ubyte container → float32 array (optionally scaled to [0,1]).
    Raises ValueError for malformed/truncated buffers."""
    import struct

    if len(buf) < 4 or buf[0] or buf[1] or buf[2] != 0x08:
        raise ValueError("not an unsigned-byte IDX buffer")
    if len(buf) < 4 + 4 * buf[3]:
        raise ValueError("truncated IDX header")
    lib = _loader_lib()
    if lib is not None:
        ndim = buf[3]
        if 1 <= ndim <= 4:
            dims = struct.unpack(f">{ndim}I", buf[4:4 + 4 * ndim])
            total = int(np.prod(dims))
            out = np.empty((total,), np.float32)
            shape_out = (ctypes.c_int64 * 4)()
            ndim_out = ctypes.c_int()
            arr = (ctypes.c_ubyte * len(buf)).from_buffer_copy(buf)
            n = lib.idx_parse(arr, len(buf), 1 if scale else 0,
                              out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                              total, shape_out, ctypes.byref(ndim_out))
            if n == total:
                return out.reshape(dims)
    # numpy fallback
    ndim = buf[3]
    dims = struct.unpack(f">{ndim}I", buf[4:4 + 4 * ndim])
    if len(buf) < 4 + 4 * ndim + int(np.prod(dims)):
        raise ValueError("truncated IDX data")
    data = np.frombuffer(buf, np.uint8, offset=4 + 4 * ndim).astype(np.float32)
    if scale:
        data = data / 255.0
    return data.reshape(dims)
