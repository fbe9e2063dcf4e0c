"""Native (C++) host-side components, loaded via ctypes.

Built automatically on first use (g++, ~1 s) from the tracked
``kv_index.cpp`` / ``slot_parser.cpp``. The flags include
``-march=native``, so a binary is only valid on the host that built it:
the artifact carries a build stamp (hash of sources + compiler + flags +
this boot of this host) and ``load_native()`` rebuilds whenever the
stamp differs — a ``.so`` that arrived with a directory copy from
another machine is never loaded.

``load_native()`` returns None when the toolchain is missing (tests that
ask for the Python index by name still run); the chip entry points
(chip_smoke.py, benchmarks/run.py) call ``require_native()``, which
raises — the native index is ~50x faster on the per-batch key→row hot
path and a silent fallback would hide that.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
import threading
from typing import Optional

from paddlebox_tpu.utils.logging import get_logger

log = get_logger(__name__)

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libpbox_native.so")
_STAMP = _SO + ".stamp"
_LOCK = threading.Lock()
_LIB: ctypes.CDLL | None = None
_TRIED = False
#: how this process got its library: "built" | "verified" | the failure
_STATUS = "not loaded"


_SRCS = ("kv_index.cpp", "slot_parser.cpp")


def _toolchain() -> tuple:
    """(cxx, flags) — CXX/CXXFLAGS override the defaults."""
    return (os.environ.get("CXX", "g++"),
            os.environ.get(
                "CXXFLAGS", "-O3 -march=native -std=c++17 -fPIC").split())


def _host_id() -> str:
    """Identity of this boot of this machine (``-march=native`` ties a
    binary to the CPU it was built on; a copied tree lands on another
    boot id)."""
    try:
        with open("/proc/sys/kernel/random/boot_id") as fh:
            return fh.read().strip()
    except OSError:
        return platform.node()


def _build_key() -> str:
    h = hashlib.sha256()
    for s in _SRCS:
        with open(os.path.join(_DIR, s), "rb") as fh:
            h.update(fh.read())
    h.update(repr((_toolchain(), platform.machine(),
                   _host_id())).encode())
    return h.hexdigest()


def _stamp_matches(key: str) -> bool:
    try:
        with open(_STAMP) as fh:
            return os.path.exists(_SO) and fh.read().strip() == key
    except OSError:
        return False


def _build(key: str) -> Optional[str]:
    """Compile to a temp file then atomically rename, so concurrent
    importers never CDLL a half-written .so; the stamp lands after the
    library. Returns the failure text, None on success."""
    srcs = [os.path.join(_DIR, s) for s in _SRCS]
    cxx, flags = _toolchain()
    tmp = _SO + f".tmp{os.getpid()}"
    try:
        subprocess.run([cxx, *flags, "-shared", *srcs, "-o", tmp],
                       check=True, capture_output=True, timeout=120)
        os.replace(tmp, _SO)
        with open(tmp, "w") as fh:
            fh.write(key)
        os.replace(tmp, _STAMP)
        return None
    except (subprocess.SubprocessError, FileNotFoundError, OSError) as e:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return f"native build failed ({e})"


def native_status() -> str:
    """How this process got its library: "built" (compiled here),
    "verified" (stamp matched this host's build key), or the failure
    text."""
    return _STATUS


def require_native() -> ctypes.CDLL:
    """The strict loader of the chip path: the library or an error."""
    lib = load_native()
    if lib is None:
        raise RuntimeError(
            f"native library required on the chip path: {_STATUS}")
    return lib


def load_native() -> ctypes.CDLL | None:
    """Load the native library, (re)building it unless its stamp says
    it was built from these sources on this host; None if unavailable."""
    global _LIB, _TRIED, _STATUS
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        key = _build_key()
        if _stamp_matches(key):
            _STATUS = "verified"
        else:
            err = _build(key)
            if err is not None:
                _STATUS = err
                log.warning("%s; using python fallbacks", err)
                return None
            _STATUS = "built"
        try:
            lib = ctypes.CDLL(_SO)
        except OSError as e:
            _STATUS = f"native load failed ({e})"
            log.warning("%s; using python fallbacks", _STATUS)
            return None
        lib.kv_create.restype = ctypes.c_void_p
        lib.kv_create.argtypes = [ctypes.c_int64, ctypes.c_int32]
        lib.kv_destroy.argtypes = [ctypes.c_void_p]
        lib.kv_size.restype = ctypes.c_int64
        lib.kv_size.argtypes = [ctypes.c_void_p]
        lib.kv_assign.restype = ctypes.c_int64
        lib.kv_assign.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                  ctypes.c_int64, ctypes.c_void_p]
        lib.kv_lookup.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                  ctypes.c_int64, ctypes.c_void_p]
        lib.kv_release.restype = ctypes.c_int64
        lib.kv_release.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                   ctypes.c_int64, ctypes.c_void_p]
        lib.kv_items.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                 ctypes.c_void_p]
        lib.kv_assign_unique.restype = ctypes.c_int64
        lib.kv_assign_unique.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                         ctypes.c_int64, ctypes.c_void_p,
                                         ctypes.c_void_p]
        lib.kv_lookup_unique.restype = ctypes.c_int64
        lib.kv_lookup_unique.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                         ctypes.c_int64, ctypes.c_int32,
                                         ctypes.c_void_p, ctypes.c_void_p]
        lib.kv_arena_enable.restype = ctypes.c_int32
        lib.kv_arena_enable.argtypes = [ctypes.c_void_p, ctypes.c_int32,
                                        ctypes.c_int32]
        lib.kv_assign_slotted.restype = ctypes.c_int64
        lib.kv_assign_slotted.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                          ctypes.c_void_p, ctypes.c_int64,
                                          ctypes.c_void_p, ctypes.c_void_p]
        lib.kv_dedup_first_seen.restype = ctypes.c_int64
        lib.kv_dedup_first_seen.argtypes = [ctypes.c_void_p, ctypes.c_int64,
                                            ctypes.c_void_p, ctypes.c_void_p,
                                            ctypes.c_void_p]
        lib.kv_dedup_slotted_first_seen.restype = ctypes.c_int64
        lib.kv_dedup_slotted_first_seen.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
        lib.kv_arena_chunk_count.restype = ctypes.c_int32
        lib.kv_arena_chunk_count.argtypes = [ctypes.c_void_p]
        lib.kv_arena_export.restype = ctypes.c_int32
        lib.kv_arena_export.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                        ctypes.c_void_p]
        lib.criteo_parse.restype = ctypes.c_int64
        lib.criteo_parse.argtypes = [ctypes.c_char_p, ctypes.c_int64,
                                     ctypes.c_int64, ctypes.c_void_p,
                                     ctypes.c_void_p, ctypes.c_void_p]
        lib.slot_text_parse.restype = ctypes.c_int64
        lib.slot_text_parse.argtypes = [
            ctypes.c_char_p, ctypes.c_int64, ctypes.c_void_p,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ctypes.c_void_p]
        _LIB = lib
        return _LIB
