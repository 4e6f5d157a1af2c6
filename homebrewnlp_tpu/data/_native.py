"""Shared loader for the C++ fast paths (native/*.cpp via ctypes).

pybind11 isn't available in this image, so native modules are plain C symbols
compiled with g++ on demand and loaded with ctypes; callers degrade to pure
python when the toolchain is missing.

The binary is built on the machine that runs it and named after what it was
built FROM: ``native/lib<name>.<key>.so`` with ``key`` a digest of the source
text and the compiler command.  A tree copied from another machine (or an
edited source) therefore never loads a stale or foreign binary — the key
does not match and the library is rebuilt here — and the build carries no
``-march=native``, so even a copied binary with a matching key runs on any
CPU of the same architecture (an illegal instruction cannot be caught).
"""
from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import platform
import subprocess
import typing

from ..utils import locks

NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native")
_lock = locks.named_lock("_native._lock")
_cache: typing.Dict[str, typing.Optional[ctypes.CDLL]] = {}
#: why each library that failed to load did (name -> message), for callers
#: that report which implementation ran
load_errors: typing.Dict[str, str] = {}

_CXX = ("g++", "-O3", "-shared", "-fPIC")


def _build(src: str, so: str, extra: typing.Sequence[str]) -> None:
    """Compile ``src`` to ``so`` (atomically: a concurrent loader never sees
    a half-written library).  Raises RuntimeError carrying g++'s stderr."""
    tmp = f"{so}.{os.getpid()}.tmp"
    try:
        subprocess.run([*_CXX, src, "-o", tmp, *extra], check=True,
                       capture_output=True, text=True, timeout=300)
        os.replace(tmp, so)
    except (OSError, subprocess.SubprocessError) as exc:
        detail = (getattr(exc, "stderr", "") or str(exc)).strip()
        raise RuntimeError(f"building {os.path.basename(src)} failed: "
                           f"{detail[-2000:]}") from exc
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def library_path(name: str, extra_flags: typing.Sequence[str] = ()) -> str:
    """``native/lib<name>.<key>.so`` for the CURRENT source + command."""
    with open(os.path.join(NATIVE_DIR, f"{name}.cpp"), "rb") as f:
        digest = hashlib.sha256(f.read())
    digest.update(" ".join((*_CXX, *extra_flags, platform.machine())).encode())
    return os.path.join(NATIVE_DIR, f"lib{name}.{digest.hexdigest()[:16]}.so")


def load_library(name: str,
                 declare: typing.Callable[[ctypes.CDLL], None],
                 extra_flags: typing.Sequence[str] = ()
                 ) -> typing.Optional[ctypes.CDLL]:
    """Load native/<name>.cpp, building it here unless a library keyed on
    exactly this source + command already exists.  `declare` sets
    restype/argtypes.  A failed build or load is printed once (with g++'s
    own message), recorded in :data:`load_errors`, and returns None so the
    caller takes its pure-python path; results are cached per module."""
    with _lock:
        if name in _cache:
            return _cache[name]
        _cache[name] = None
        try:
            so = library_path(name, extra_flags)
            if not os.path.exists(so):
                _build(os.path.join(NATIVE_DIR, f"{name}.cpp"), so,
                       extra_flags)
                for old in glob.glob(os.path.join(NATIVE_DIR,
                                                  f"lib{name}.*so")):
                    if old != so:  # binaries of other sources / machines
                        os.unlink(old)
            lib = ctypes.CDLL(so)
            declare(lib)
        except (OSError, AttributeError, RuntimeError) as exc:
            load_errors[name] = str(exc)
            print(f"WARNING: native {name} unavailable, using the pure-python "
                  f"path: {exc}", flush=True)
            return None
        _cache[name] = lib
        return lib
