"""ctypes bindings to the native runtime library (native/jt_native.cpp).

The image has no pybind11, so Python↔C++ crosses via ctypes on plain C
ABIs. The library is compiled on first use with g++ (toolchain is baked
into the image) and cached in native/build/; everything degrades to the
pure-Python paths when a compiler is unavailable.

Surface:
- ``load_native_splitter(path, params)`` — dlopen a splitter plugin .so
  implementing the jt_splitter_* ABI (the dlopen/create seam of the
  reference's fv_converter plugins, SURVEY.md §2.8) — the load-bearing
  native feature: tokenizer plugins run at C speed in the ingest path.
- ``hash_names(names, mask)`` — batch feature-name hashing, bit-identical
  to the zlib.crc32 FeatureHasher. Measured: NOT faster than the Python
  loop at realistic sizes (zlib is already C; ctypes marshalling eats the
  win), so FeatureHasher uses it only when JUBATUS_TPU_NATIVE=1.
- ``crc32(data)``             — zlib-compatible checksum (API parity).
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Callable, Dict, List, Optional

import numpy as np

log = logging.getLogger(__name__)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
BUILD_DIR = os.path.join(NATIVE_DIR, "build")
_CXX = ("g++", "-O3", "-Wall", "-fPIC", "-std=c++17", "-pthread", "-shared")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def build(stem: str) -> Optional[str]:
    """``native/<stem>.cpp`` compiled to ``native/build/lib<stem>-<key>.so``
    and the path returned; None when the source or toolchain is missing
    or the compile fails (logged). ``key`` hashes the source bytes and the
    compiler command, so a library is reused exactly when it was built
    from this source with these flags — file times say nothing after a
    checkout or a copy to another machine."""
    src = os.path.join(NATIVE_DIR, stem + ".cpp")
    try:
        with open(src, "rb") as f:
            body = f.read()
    except OSError:
        return None
    key = hashlib.sha256(" ".join(_CXX).encode() + b"\0" + body).hexdigest()[:16]
    out = os.path.join(BUILD_DIR, f"lib{stem}-{key}.so")
    if os.path.exists(out):
        return out
    # compile beside the target and rename: replicas booting together
    # each build their own copy and the last rename wins, whole
    tmp = f"{out}.{os.getpid()}.tmp"
    try:
        os.makedirs(BUILD_DIR, exist_ok=True)
        res = subprocess.run([*_CXX, "-o", tmp, src],
                             capture_output=True, timeout=300)
    except (OSError, subprocess.TimeoutExpired) as e:
        log.warning("native build of %s did not run: %s", stem, e)
        return None
    if res.returncode != 0:
        log.warning("native build of %s failed:\n%s", stem,
                    res.stderr.decode("utf-8", "replace")[-2000:])
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None
    os.replace(tmp, out)
    return out


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        path = os.environ.get("JUBATUS_TPU_NATIVE_LIB") or build("jt_native")
        if not path or not os.path.exists(path):
            return None
        try:
            lib = ctypes.CDLL(path)
        except OSError:
            return None
        lib.jt_crc32.restype = ctypes.c_uint32
        lib.jt_crc32.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        lib.jt_hash_names.restype = None
        lib.jt_hash_names.argtypes = [
            ctypes.c_char_p,
            np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
            ctypes.c_int64,
            ctypes.c_uint32,
            np.ctypeslib.ndpointer(np.uint32, flags="C_CONTIGUOUS"),
        ]
        _lib = lib
        return _lib


def available() -> bool:
    if os.environ.get("JUBATUS_TPU_NATIVE", "") in ("0", "false", "no"):
        return False
    return _load() is not None


def crc32(data: bytes) -> int:
    lib = _load()
    if lib is None:
        import zlib

        return zlib.crc32(data) & 0xFFFFFFFF
    return int(lib.jt_crc32(data, len(data)))


def hash_names(names: List[str], mask: int) -> np.ndarray:
    """Batch of utf-8 names → uint32 indices in [1, mask] (0 remapped to 1,
    matching FeatureHasher.index). Falls back to zlib per-name."""
    lib = _load() if available() else None
    if lib is None:
        import zlib

        out = np.empty(len(names), dtype=np.uint32)
        for i, name in enumerate(names):
            h = zlib.crc32(name.encode("utf-8")) & mask
            out[i] = h if h else 1
        return out
    encoded = [n.encode("utf-8") for n in names]
    offsets = np.zeros(len(encoded) + 1, dtype=np.int64)
    np.cumsum([len(e) for e in encoded], out=offsets[1:])
    buf = b"".join(encoded)
    out = np.empty(len(encoded), dtype=np.uint32)
    lib.jt_hash_names(buf, offsets, len(encoded), ctypes.c_uint32(mask), out)
    return out


# -- native splitter plugins (jt_splitter_* ABI) -----------------------------

_splitter_libs: Dict[str, ctypes.CDLL] = {}


def load_native_splitter(path: str, params: Dict[str, str]) -> Callable[[str], List[str]]:
    """dlopen a .so implementing the jt_splitter ABI and wrap it as a
    ``text -> [tokens]`` callable (see native/sample_ngram_splitter.cpp)."""
    from jubatus_tpu.core.fv.converter import ConverterError

    resolved = os.path.abspath(path)
    with _lock:
        lib = _splitter_libs.get(resolved)
        if lib is None:
            if not os.path.exists(resolved):
                raise ConverterError(f"native splitter not found: {path!r}")
            try:
                lib = ctypes.CDLL(resolved)
            except OSError as e:
                raise ConverterError(f"cannot dlopen {path!r}: {e}")
            lib.jt_splitter_create.restype = ctypes.c_void_p
            lib.jt_splitter_create.argtypes = [
                ctypes.POINTER(ctypes.c_char_p),
                ctypes.POINTER(ctypes.c_char_p), ctypes.c_int]
            lib.jt_splitter_split.restype = ctypes.c_int64
            lib.jt_splitter_split.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
                np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
                np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS"),
                ctypes.c_int64]
            lib.jt_splitter_destroy.restype = None
            lib.jt_splitter_destroy.argtypes = [ctypes.c_void_p]
            _splitter_libs[resolved] = lib

    items = [(k, v) for k, v in params.items()
             if k not in ("method", "path", "function")]
    keys = (ctypes.c_char_p * len(items))(*[k.encode() for k, _ in items])
    vals = (ctypes.c_char_p * len(items))(*[str(v).encode() for _, v in items])
    handle = lib.jt_splitter_create(keys, vals, len(items))
    if not handle:
        raise ConverterError(f"native splitter {path!r} rejected params")

    def split(text: str, _lib=lib, _h=handle) -> List[str]:
        data = text.encode("utf-8")
        cap = max(64, len(data) * 2)
        while True:
            begins = np.empty(cap, dtype=np.int64)
            ends = np.empty(cap, dtype=np.int64)
            n = _lib.jt_splitter_split(_h, data, len(data), begins, ends, cap)
            if n <= cap:
                break
            cap = int(n)
        return [data[begins[i]:ends[i]].decode("utf-8", "replace")
                for i in range(max(0, int(n)))]

    return split
