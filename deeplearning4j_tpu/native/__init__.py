"""Native data-pipeline bindings (ref: the reference's C++ ETL/IO layer —
SURVEY.md §2.3: the JVM drops to native for record-parsing throughput; this
package is the same split: Python orchestrates, C++ parses).

ctypes over a single .so (pybind11 is not in this toolchain). The library
auto-builds on first import when a compiler is available; every entry point
has a pure-numpy fallback so the package works without a toolchain —
``native_available()`` reports which path is active.

Public surface:
- ``parse_csv(text | path)`` -> (rows, cols) float64 ndarray — multithreaded
  numeric CSV parsing.
- ``load_idx(path, scale=...)`` -> ndarray — IDX (MNIST container) decode.
- ``PrefetchIterator(iter, depth)`` — background-thread batch prefetcher
  (ref: AsyncDataSetIterator): overlaps host ETL with device compute.
"""
from __future__ import annotations

import ctypes
import os
import queue
import threading
from typing import Iterator, Optional

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_HERE, "libdl4j_native.so")
_SRC = os.path.join(_HERE, "dl4j_native.cpp")
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    _tried = True
    # *.so is git-ignored, so a checkout never carries one and a working
    # tree may carry a stale one: rebuild when the source is newer
    if not os.path.exists(_SO) \
            or os.path.getmtime(_SRC) > os.path.getmtime(_SO):
        try:
            from deeplearning4j_tpu.native.build import build
            build(verbose=False)
        except Exception:
            return None
    try:
        lib = ctypes.CDLL(_SO)
    except OSError:
        return None
    lib.csv_dims.argtypes = [ctypes.c_char_p, ctypes.c_int64, ctypes.c_char,
                             ctypes.POINTER(ctypes.c_int64),
                             ctypes.POINTER(ctypes.c_int64)]
    lib.csv_parse.argtypes = [ctypes.c_char_p, ctypes.c_int64, ctypes.c_char,
                              ctypes.c_int64, ctypes.c_int64,
                              ctypes.POINTER(ctypes.c_double), ctypes.c_int]
    lib.idx_header.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                               ctypes.POINTER(ctypes.c_int64),
                               ctypes.POINTER(ctypes.c_int)]
    lib.idx_decode.argtypes = [ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64,
                               ctypes.c_int64, ctypes.c_int, ctypes.c_int,
                               ctypes.POINTER(ctypes.c_double)]
    if lib.dl4j_native_abi_version() != 1:
        return None
    _lib = lib
    return _lib


def native_available() -> bool:
    return _load() is not None


# ------------------------------------------------------------------- CSV

def parse_csv(source: str, delimiter: str = ",", threads: int = 4,
              force_python: bool = False) -> np.ndarray:
    """Numeric CSV -> (rows, cols) float64. ``source`` is a path or raw text.
    Non-numeric fields become NaN (the caller's schema decides what that
    means — same contract as the reference's CSVRecordReader + Schema)."""
    if os.path.exists(source):
        with open(source, "rb") as f:
            data = f.read()
    else:
        data = source.encode()
    lib = None if force_python else _load()
    if lib is None:
        return _parse_csv_python(data.decode(), delimiter)
    rows = ctypes.c_int64()
    cols = ctypes.c_int64()
    rc = lib.csv_dims(data, len(data), delimiter.encode(), ctypes.byref(rows),
                      ctypes.byref(cols))
    if rc != 0 or rows.value == 0:
        return np.zeros((0, 0))
    out = np.empty((rows.value, cols.value), np.float64)
    rc = lib.csv_parse(data, len(data), delimiter.encode(), rows.value,
                       cols.value,
                       out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                       max(threads, 1))
    if rc != 0:
        raise ValueError(f"native csv parse failed rc={rc}")
    return out


def _parse_csv_python(text: str, delimiter: str) -> np.ndarray:
    rows = []
    for line in text.splitlines():
        if not line.strip():
            continue
        vals = []
        for f in line.split(delimiter):
            try:
                vals.append(float(f))
            except ValueError:
                vals.append(float("nan"))
        rows.append(vals)
    return np.asarray(rows, np.float64) if rows else np.zeros((0, 0))


# ------------------------------------------------------------------- IDX

def load_idx(path: str, scale: bool = False,
             force_python: bool = False) -> np.ndarray:
    """IDX container (MNIST images/labels) -> float64 ndarray; ``scale``
    divides uint8 payloads by 255 (image normalization in the decoder, one
    pass — ref: the reference's MnistManager does this in Java per pixel)."""
    with open(path, "rb") as f:
        data = f.read()
    lib = None if force_python else _load()
    if lib is None:
        return _load_idx_python(data, scale)
    dims = (ctypes.c_int64 * 8)()
    dtype = ctypes.c_int()
    nd = lib.idx_header(data, len(data), dims, ctypes.byref(dtype))
    if nd < 0:
        raise ValueError(f"malformed IDX file: {path}")
    shape = tuple(dims[i] for i in range(nd))
    count = int(np.prod(shape)) if shape else 1
    out = np.empty(count, np.float64)
    offset = 4 + 4 * nd
    rc = lib.idx_decode(data, len(data), offset, count, dtype.value,
                        1 if scale else 0,
                        out.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
    if rc != 0:
        raise ValueError(f"IDX decode failed rc={rc} dtype={dtype.value}")
    return out.reshape(shape)


_IDX_NP = {0x08: np.uint8, 0x09: np.int8, 0x0B: ">i2", 0x0C: ">i4",
           0x0D: ">f4", 0x0E: ">f8"}


def _load_idx_python(data: bytes, scale: bool) -> np.ndarray:
    if len(data) < 4 or data[0] != 0 or data[1] != 0:
        raise ValueError("malformed IDX header")
    dtype, nd = data[2], data[3]
    shape = tuple(int.from_bytes(data[4 + 4 * i:8 + 4 * i], "big")
                  for i in range(nd))
    arr = np.frombuffer(data, _IDX_NP[dtype], count=int(np.prod(shape)),
                        offset=4 + 4 * nd).reshape(shape).astype(np.float64)
    if scale and dtype == 0x08:
        arr = arr / 255.0
    return arr


# -------------------------------------------------------------- prefetch

class PrefetchIterator:
    """Background-thread prefetcher (ref: AsyncDataSetIterator — the
    reference's dedicated ETL thread + bounded queue). Wraps any iterator;
    ``depth`` bounds queued items so ETL cannot run unboundedly ahead."""

    _END = object()

    def __init__(self, iterable, depth: int = 2):
        self._iterable = iterable
        self.depth = depth
        self._q: Optional[queue.Queue] = None
        self._thread: Optional[threading.Thread] = None
        self._err: Optional[BaseException] = None

    def _worker(self, it):
        try:
            for item in it:
                self._q.put(item)
        except BaseException as e:  # surfaced on the consumer side
            self._err = e
        finally:
            self._q.put(self._END)

    def __iter__(self) -> Iterator:
        self._q = queue.Queue(maxsize=self.depth)
        self._err = None
        self._thread = threading.Thread(target=self._worker,
                                        args=(iter(self._iterable),),
                                        daemon=True)
        self._thread.start()
        return self

    def __next__(self):
        item = self._q.get()
        if item is self._END:
            if self._err is not None:
                raise self._err
            raise StopIteration
        return item
