"""ctypes bindings for native/recordio.cpp (built on demand with g++).

pybind11 isn't available in this image, so the native fast paths are plain C
symbols loaded via ctypes; everything degrades to the pure-python
implementation in tfrecord.py when the toolchain or .so is missing.
"""
from __future__ import annotations

import ctypes
import os
import typing

import numpy as np

from ._native import library_path, load_errors, load_library


def _declare(lib: ctypes.CDLL) -> None:
    lib.rio_scan.restype = ctypes.c_long
    lib.rio_scan.argtypes = [ctypes.c_char_p, ctypes.c_void_p,
                             ctypes.c_void_p, ctypes.c_long]
    lib.rio_read_file.restype = ctypes.c_long
    lib.rio_read_file.argtypes = [ctypes.c_char_p, ctypes.c_void_p, ctypes.c_long]
    lib.rio_decode_varints.restype = ctypes.c_long
    lib.rio_decode_varints.argtypes = [ctypes.c_void_p, ctypes.c_long,
                                       ctypes.c_void_p, ctypes.c_long]
    lib.rio_find_feature.restype = ctypes.c_long
    lib.rio_find_feature.argtypes = [ctypes.c_void_p, ctypes.c_long,
                                     ctypes.c_char_p, ctypes.c_void_p,
                                     ctypes.c_void_p]
    lib.rio_masked_crc.restype = ctypes.c_uint32
    lib.rio_masked_crc.argtypes = [ctypes.c_void_p, ctypes.c_int64]
    lib.rio_write_records.restype = ctypes.c_long
    lib.rio_write_records.argtypes = [ctypes.c_char_p, ctypes.c_void_p,
                                      ctypes.c_void_p, ctypes.c_void_p,
                                      ctypes.c_long, ctypes.c_int]


def _load() -> typing.Optional[ctypes.CDLL]:
    return load_library("recordio", _declare)


def available() -> bool:
    return _load() is not None


def describe() -> str:
    """Which record reader this process uses, for start-up logs."""
    if available():
        return f"native ({os.path.basename(library_path('recordio'))})"
    return f"python ({load_errors.get('recordio', 'native library absent')})"


def read_records(path: str) -> typing.Iterator[bytes]:
    lib = _load()
    assert lib is not None
    size = os.path.getsize(path)
    buf = np.empty(size, dtype=np.uint8)
    got = lib.rio_read_file(path.encode(), buf.ctypes.data, size)
    if got < 0:
        raise IOError(f"cannot read {path}")
    max_n = max(16, size // 16)
    offsets = np.empty(max_n, dtype=np.int64)
    lengths = np.empty(max_n, dtype=np.int64)
    n = lib.rio_scan(path.encode(), offsets.ctypes.data, lengths.ctypes.data, max_n)
    if n < 0:
        raise IOError(f"cannot scan {path} ({n})")
    data = buf.tobytes()
    for i in range(n):
        o, l = int(offsets[i]), int(lengths[i])
        if o + l + 4 > size:  # truncated trailing record (crash mid-write)
            return
        yield data[o:o + l]


def masked_crc(data: bytes) -> typing.Optional[int]:
    lib = _load()
    if lib is None:
        return None
    buf = np.frombuffer(data, dtype=np.uint8)
    return int(lib.rio_masked_crc(buf.ctypes.data if len(data) else None,
                                  len(data)))


def write_records(path: str, payloads: typing.Sequence[bytes],
                  append: bool = False) -> bool:
    """Bulk framed-record write (crc32c framing in C++)."""
    lib = _load()
    if lib is None:
        return False
    buf = np.frombuffer(b"".join(payloads), dtype=np.uint8)
    lengths = np.asarray([len(p) for p in payloads], dtype=np.int64)
    offsets = np.concatenate([[0], np.cumsum(lengths)[:-1]]) \
        if len(payloads) else np.zeros(0, dtype=np.int64)
    offsets = offsets.astype(np.int64)
    n = lib.rio_write_records(path.encode(), buf.ctypes.data,
                              offsets.ctypes.data, lengths.ctypes.data,
                              len(payloads), int(append))
    return n == len(payloads)


def feature_tokens(payload: bytes, name: str = "text"
                   ) -> typing.Optional[np.ndarray]:
    """Fast path: extract a bytes or int64 'text' feature as a token array
    (uint8 codepoints for bytes, int64 for token ids)."""
    lib = _load()
    if lib is None:
        return None
    buf = np.frombuffer(payload, dtype=np.uint8)
    offset = ctypes.c_long()
    kind = ctypes.c_int()
    ln = lib.rio_find_feature(buf.ctypes.data, len(payload), name.encode(),
                              ctypes.byref(offset), ctypes.byref(kind))
    if ln < 0:
        return None
    start = offset.value
    if kind.value == 1:  # bytes
        return buf[start:start + ln].copy()
    if kind.value == 3:  # packed int64 varints
        out = np.empty(ln, dtype=np.int64)
        n = lib.rio_decode_varints(buf.ctypes.data + start, ln,
                                   out.ctypes.data, ln)
        return out[:n].copy()
    return None
