"""Loader for the native C++ host library (native/sptag_host.cpp).

The reference's host runtime is C++ end to end; here the TPU compute path is
XLA and the native library accelerates the host-side hot paths (parallel TSV
ingestion, wire codec, and the served query-vector parse:
`parse_query_vectors`, one call a batch group with the interpreter lock
free).  Built on demand with g++ (this toolchain has no
pybind11 — plain C ABI + ctypes), cached next to the source, and every
caller degrades gracefully to the pure-Python implementation when the
library is unavailable.  A server loads (or builds) it when its
`SearchExecutor` is constructed, never inside a batch.

The binary is compiled with -march=native, so it is only ever valid on the
machine that built it.  A stamp file next to it records what it was built
from (source, flags, this machine's CPU); a binary whose stamp is missing
or differs — a checkout copied from another machine, an edited source — is
rebuilt, never loaded.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import platform
import subprocess
import threading
from typing import Optional

log = logging.getLogger(__name__)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SRC = os.path.join(_REPO_ROOT, "native", "sptag_host.cpp")
_LIB = os.path.join(_REPO_ROOT, "native", "libsptag_host.so")
_STAMP = _LIB + ".stamp"
_FLAGS = ["-O3", "-march=native", "-std=c++17", "-shared", "-fPIC"]

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def _machine() -> str:
    """What -march=native resolved against: architecture plus the CPU's
    model and feature flags."""
    lines = [platform.machine()]
    try:
        with open("/proc/cpuinfo") as f:
            seen = set()
            for ln in f:
                key = ln.split(":", 1)[0].strip()
                if key in ("model name", "flags", "Features") \
                        and key not in seen:
                    seen.add(key)
                    lines.append(ln.strip())
    except OSError:
        pass
    return "\n".join(lines)


def _expected_stamp() -> str:
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    h.update(" ".join(_FLAGS).encode())
    h.update(_machine().encode())
    return h.hexdigest()


def _stamp_matches(stamp: str) -> bool:
    try:
        with open(_STAMP) as f:
            return os.path.exists(_LIB) and f.read().strip() == stamp
    except OSError:
        return False


def _build(stamp: str) -> bool:
    """Compile to a private name, then rename into place and stamp —
    concurrent builders (pytest workers) never see a half-written file."""
    tmp = f"{_LIB}.{os.getpid()}.tmp"
    cmd = ["g++", *_FLAGS, "-o", tmp, _SRC, "-lpthread"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        # stamp last: a binary without its stamp is rebuilt, not trusted
        if os.path.exists(_STAMP):
            os.remove(_STAMP)
        os.replace(tmp, _LIB)
        with open(tmp, "w") as f:
            f.write(stamp + "\n")
        os.replace(tmp, _STAMP)
        return True
    except (subprocess.SubprocessError, OSError) as e:
        log.info("native host library build skipped: %s", e)
        return False


def load() -> Optional[ctypes.CDLL]:
    """Return the native library, building it on first use; None if the
    toolchain or source is unavailable."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if not os.path.exists(_SRC):
            return None
        stamp = _expected_stamp()
        if not _stamp_matches(stamp) and not _build(stamp):
            return None
        try:
            lib = ctypes.CDLL(_LIB)
        except OSError as e:
            log.info("native host library load failed: %s", e)
            return None
        lib.sptag_count_lines.restype = ctypes.c_longlong
        lib.sptag_count_lines.argtypes = [ctypes.c_char_p,
                                          ctypes.c_longlong]
        lib.sptag_parse_tsv.restype = ctypes.c_longlong
        lib.sptag_parse_tsv.argtypes = [
            ctypes.c_char_p, ctypes.c_longlong, ctypes.c_char, ctypes.c_int,
            ctypes.c_int, ctypes.POINTER(ctypes.c_float), ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_longlong)]
        # addresses as plain integers (`ndarray.ctypes.data`): this one is
        # called once a served batch group
        lib.sptag_parse_query_vectors.restype = ctypes.c_int
        lib.sptag_parse_query_vectors.argtypes = [
            ctypes.c_char_p, ctypes.c_void_p, ctypes.c_longlong,
            ctypes.c_int, ctypes.c_char, ctypes.c_int, ctypes.c_void_p,
            ctypes.c_void_p]
        _lib = lib
        return _lib


def parse_tsv(blob: bytes, delimiter: str, dim: int, threads: int):
    """Native parallel TSV parse -> (float32 (rows, dim), list of metadata
    bytes), or None when the native library is unavailable or input is
    malformed (caller falls back to Python parsing)."""
    import numpy as np

    lib = load()
    if lib is None or dim <= 0:
        return None
    rows = lib.sptag_count_lines(blob, len(blob))
    if rows <= 0:
        return None
    out = np.empty((rows, dim), np.float32)
    meta_blob = ctypes.create_string_buffer(len(blob))
    meta_lens = (ctypes.c_longlong * rows)()
    got = lib.sptag_parse_tsv(
        blob, len(blob), delimiter.encode()[:1], dim, threads,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        meta_blob, meta_lens)
    if got < 0:
        return None
    out = out[:got]
    metas = []
    off = 0
    raw = meta_blob.raw
    for r in range(got):
        n = meta_lens[r]
        metas.append(raw[off:off + n])
        off += n
    return out, metas


def parse_query_vectors(texts, sep: str, dim: int, value_type):
    """The text vectors of one served batch group ("<v1><sep><v2>...", one
    str a query) -> ((len(texts), dim) array of `value_type`'s dtype, bool
    mask of the rows it holds), by ONE native call that runs with the
    interpreter lock released (ctypes.CDLL).  None when the library is
    unavailable or `sep` / `dim` / `value_type` is not something it takes.

    A row is accepted only in forms `ParsedQuery.extract_vector` accepts
    too, with the same value (native/sptag_host.cpp::parse_row); a row
    whose mask is False was not decided: hand it to `extract_vector`."""
    import numpy as np

    from sptag_tpu.core.types import dtype_of

    lib = load()
    if lib is None or dim <= 0 or len(sep) != 1 or ord(sep) > 127:
        return None
    try:
        dtype = dtype_of(value_type)
    except (KeyError, ValueError):
        return None
    rows = len(texts)
    offsets = np.zeros(rows + 1, np.int64)
    np.cumsum(np.fromiter(map(len, texts), np.int64, rows),
              out=offsets[1:])
    # one '?' a non-ASCII character: lengths stay the strs', and '?' is
    # no element the parser accepts
    buf = "".join(texts).encode("ascii", "replace")
    out = np.empty((rows, dim), dtype)
    ok = np.zeros(rows, np.bool_)
    if lib.sptag_parse_query_vectors(
            buf, offsets.ctypes.data, rows, dim, sep.encode(),
            int(value_type), out.ctypes.data, ok.ctypes.data) < 0:
        return None
    return out, ok
