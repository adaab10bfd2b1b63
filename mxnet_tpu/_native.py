"""Native (C++) runtime components, loaded via ctypes.

The reference's runtime around the compute path is C++ (engine, storage,
IO — SURVEY.md §2.1); on TPU the engine/storage layers are PJRT/XLA, and
the native layer that remains worthwhile is host-side IO.  This module
compiles ``src/*.cc`` with the system ``g++`` on first use (no pybind11
in this image; the ABI is plain C for ctypes) and caches the shared
object under the git-ignored ``mxnet_tpu/_build/``, keyed by a digest
of the sources it was built from (a copied tree need not keep mtimes).

If the build fails the callers use their pure-Python paths
(``native_recordio() is None``); the compiler's error is logged once
per library, so the slower path is never taken in silence.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

from .base import logger

_LOCK = threading.Lock()
_LIB = {}

_SRC_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")
_BUILD_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "_build")


def _python_embed_flags():
    """Compiler/linker flags for embedding CPython (the c_predict_api
    build); via python3-config --embed."""
    import sysconfig

    inc = "-I" + sysconfig.get_paths()["include"]
    libdir = sysconfig.get_config_var("LIBDIR") or ""
    ver = sysconfig.get_config_var("LDVERSION") or \
        sysconfig.get_config_var("VERSION")
    return [inc], ["-L" + libdir, "-lpython" + ver]


_EXTRA_FLAGS = {
    # name -> (extra compile flags, extra link flags)
    "c_predict_api": _python_embed_flags,
    "c_api": _python_embed_flags,
    "im2rec": lambda: (["-pthread"], ["-pthread"]),
}


def _sources_digest(paths):
    h = hashlib.sha256()
    for path in sorted(paths):
        h.update(os.path.basename(path).encode())
        with open(path, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _load(name):
    """Compile (if stale) and dlopen src/<name>.cc; returns CDLL or
    None."""
    with _LOCK:
        if name in _LIB:
            return _LIB[name]
        src = os.path.join(_SRC_DIR, name + ".cc")
        so = os.path.join(_BUILD_DIR, name + ".so")
        stamp = so + ".sha256"
        lib = None
        try:
            if os.path.exists(src):
                # stale unless built from exactly these bytes: the
                # source AND any src/*.h it may include (embed_common.h
                # is shared by the ABI libs)
                digest = _sources_digest(
                    [src] + [os.path.join(_SRC_DIR, f)
                             for f in os.listdir(_SRC_DIR)
                             if f.endswith(".h")])
                built = None
                if os.path.exists(so) and os.path.exists(stamp):
                    with open(stamp) as f:
                        built = f.read().strip()
                if built != digest:
                    os.makedirs(_BUILD_DIR, exist_ok=True)
                    cflags, ldflags = ([], [])
                    if name in _EXTRA_FLAGS:
                        cflags, ldflags = _EXTRA_FLAGS[name]()
                    # build beside, then rename: another process of
                    # this checkout never dlopens a half-written file
                    tmp = "%s.%d.tmp" % (so, os.getpid())
                    subprocess.run(
                        ["g++", "-O2", "-shared", "-fPIC", "-std=c++17"]
                        + cflags + ["-o", tmp, src] + ldflags,
                        check=True, capture_output=True, timeout=120)
                    os.replace(tmp, so)
                    with open(stamp, "w") as f:
                        f.write(digest)
                lib = ctypes.CDLL(so)
        except (OSError, subprocess.SubprocessError) as e:
            # once per library (_LIB caches the None)
            detail = getattr(e, "stderr", None)
            if isinstance(detail, bytes):
                detail = detail.decode("utf-8", "replace")
            logger.warning(
                "native library %r unavailable, using the pure-Python "
                "path: %s%s", name, e,
                ("\n" + detail.strip()[-2000:]) if detail else "")
            lib = None
        _LIB[name] = lib
        return lib


def native_im2rec():
    """The parallel image->RecordIO packer library, or None."""
    lib = _load("im2rec")
    if lib is None:
        return None
    if not getattr(lib, "_i2r_configured", False):
        lib.i2r_pack.restype = ctypes.c_long
        lib.i2r_pack.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                 ctypes.c_char_p, ctypes.c_char_p,
                                 ctypes.c_int]
        lib._i2r_configured = True
    return lib


def pack_recordio(list_path, root, rec_path, idx_path, nthreads=4):
    """Pack already-encoded image files listed in a .lst into .rec/.idx
    with the native parallel packer (the reference's ``tools/im2rec.cc``
    role).  Returns the record count, or None when the native library
    is unavailable; raises on unreadable inputs."""
    from .base import MXNetError

    lib = native_im2rec()
    if lib is None:
        return None
    n = lib.i2r_pack(str(list_path).encode(), str(root or "").encode(),
                     str(rec_path).encode(), str(idx_path).encode(),
                     int(nthreads))
    if n < 0:
        raise MXNetError(
            "native im2rec pack failed (code %d: %s)" % (n, {
                -1: "cannot open list file",
                -2: "unreadable image file",
                -3: "cannot open output",
                -4: "output write failed (disk full?)",
                -5: "image payload exceeds the 2^29-1 byte frame "
                    "limit (length field reserves top 3 bits for "
                    "cflag)"}.get(n, "?")))
    return int(n)


def native_recordio():
    """The recordio scanner library, or None (pure-Python fallback)."""
    lib = _load("recordio")
    if lib is None:
        return None
    if not getattr(lib, "_rio_configured", False):
        lib.rio_scan.restype = ctypes.c_long
        lib.rio_scan.argtypes = [ctypes.c_char_p,
                                 ctypes.POINTER(ctypes.c_uint64),
                                 ctypes.POINTER(ctypes.c_uint32),
                                 ctypes.c_long]
        lib.rio_count.restype = ctypes.c_long
        lib.rio_count.argtypes = [ctypes.c_char_p]
        lib._rio_configured = True
    return lib


def scan_recordio(path):
    """Index a .rec file natively: returns (offsets list, lengths list)
    or None when the native library is unavailable.  Raises on corrupt
    files (negative return codes from the scanner)."""
    from .base import MXNetError

    lib = native_recordio()
    if lib is None:
        return None
    n = lib.rio_count(path.encode())
    if n < 0:
        raise MXNetError("native recordio scan failed on %s (code %d: "
                         "%s)" % (path, n,
                                  {-1: "cannot open", -2: "bad magic",
                                   -3: "truncated",
                                   -4: "bad split framing"}.get(n, "?")))
    offsets = (ctypes.c_uint64 * max(n, 1))()
    lengths = (ctypes.c_uint32 * max(n, 1))()
    n2 = lib.rio_scan(path.encode(), offsets, lengths, n)
    if n2 != n:
        raise MXNetError("native recordio rescan mismatch on %s" % path)
    return list(offsets[:n]), list(lengths[:n])
