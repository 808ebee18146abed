"""Build + load the native components (ctypes, on-demand g++ compile).

pybind11 is not available in this environment, so the native pieces expose
a plain C ABI consumed through ctypes.  Each artifact is compiled next to
its source on first use and NAMED BY A HASH OF ITS SOURCES
(`_native_wal.<hash>.so`, `_http_load.<hash>`): an object can be loaded
only if it was built from the source beside it.  Modification times
prove nothing — a fresh checkout gives every file the same one, and a
copied working tree carries ignored artifacts along.  Failures of any
kind (no compiler, read-only checkout) degrade to the pure-Python
implementations; `/healthz` says which one is serving (`native_wal`).

Set RAFTSQL_TPU_NATIVE=0 to force the Python fallbacks.
"""
from __future__ import annotations

import ctypes
import glob
import hashlib
import logging
import os
import subprocess
import tempfile
import threading

log = logging.getLogger("raftsql_tpu.native")

_DIR = os.path.dirname(os.path.abspath(__file__))
_lock = threading.Lock()
_cache: dict = {}


def _compile(src: str, dest: str, link_args: tuple) -> bool:
    """Compile `src` to `dest` atomically (tmp + rename, so concurrent
    processes never open a half-written artifact); True on success,
    warning + False on any failure, temp never leaked."""
    fd, tmp = tempfile.mkstemp(dir=_DIR)
    os.close(fd)
    try:
        cmd = ["g++", "-O2", "-std=c++17", *link_args, "-o", tmp, src]
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True,
                                  timeout=120)
        except (OSError, subprocess.TimeoutExpired) as e:
            log.warning("native build unavailable (%s); using Python "
                        "fallback", e)
            return False
        if proc.returncode != 0:
            log.warning("native build failed; using Python fallback:\n%s",
                        proc.stderr)
            return False
        os.chmod(tmp, 0o755)
        os.replace(tmp, dest)
        return True
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)


def _artifact(stem: str, srcs: list, flags: tuple, suffix: str = ""):
    """Path of `stem`'s artifact built from `srcs` (first is the
    translation unit handed to g++) with `flags`, compiling it when no
    object of exactly these sources exists; None when the build is
    unavailable.  The name carries a hash of the sources and flags, so
    a stale object beside changed source is never opened; superseded
    objects of the same stem are unlinked after a successful build."""
    h = hashlib.sha256(repr(flags).encode())
    for src in srcs:
        with open(src, "rb") as f:
            h.update(f.read())
    path = os.path.join(_DIR, f"{stem}.{h.hexdigest()[:16]}{suffix}")
    if os.path.isfile(path):
        return path
    if not _compile(srcs[0], path, flags):
        return None
    for old in glob.glob(os.path.join(_DIR, f"{stem}.*{suffix}")):
        if old != path:
            try:
                os.unlink(old)
            except OSError:
                pass
    return path


def _load(name: str):
    """Compile (if no object of this source exists) and dlopen
    native/<name>.cc -> CDLL or None."""
    if os.environ.get("RAFTSQL_TPU_NATIVE", "1") == "0":
        return None
    with _lock:
        if name in _cache:
            return _cache[name]
        lib = None
        try:
            so = _artifact(f"_native_{name}",
                           [os.path.join(_DIR, f"{name}.cc")],
                           ("-shared", "-fPIC"), suffix=".so")
            if so is not None:
                lib = ctypes.CDLL(so)
        except OSError as e:
            log.warning("native %s load failed (%s); Python fallback",
                        name, e)
            lib = None
        _cache[name] = lib
        return lib


def build_http_load():
    """Compile native/http_load.cc into a standalone load-generator
    binary (the bench harness's `wrk`); returns its path, or None when
    the toolchain is unavailable (callers fall back to the Python
    client threads)."""
    if os.environ.get("RAFTSQL_TPU_NATIVE", "1") == "0":
        return None
    with _lock:
        if "http_load" in _cache:
            return _cache["http_load"]
        try:
            path = _artifact("_http_load",
                             [os.path.join(_DIR, "http_load.cc")], ())
        except OSError as e:
            log.warning("http_load build unavailable (%s)", e)
            path = None
        _cache["http_load"] = path
        return path


# Sanitizer build variants for the native WAL stress harness
# (wal_stress.cc drives 4 threads of appends/hardstate/compact/
# snapshot/sync on one handle).  `make native-sanitize` builds + runs
# the asan and ubsan variants; the existing `make tsan` target covers
# thread.  -O1 keeps stacks honest in reports; -fno-omit-frame-pointer
# makes asan traces readable; -fno-sanitize-recover turns every ubsan
# diagnostic into a nonzero exit so CI cannot scroll past one.
SANITIZERS = {
    "asan": ("-pthread", "-fsanitize=address",
             "-fno-omit-frame-pointer"),
    "ubsan": ("-pthread", "-fsanitize=undefined",
              "-fno-sanitize-recover=all"),
    "tsan": ("-pthread", "-fsanitize=thread"),
}


def build_wal_stress(sanitizer: str):
    """Compile the WAL stress binary under `sanitizer` (a SANITIZERS
    key); returns the executable path, or None when the toolchain is
    unavailable (callers degrade to a skip — hosts without g++ are
    covered by the Python WAL backend)."""
    flags = SANITIZERS[sanitizer]
    srcs = [os.path.join(_DIR, "wal_stress.cc"),
            os.path.join(_DIR, "wal.cc")]
    with _lock:
        key = f"wal_stress_{sanitizer}"
        if key in _cache:
            return _cache[key]
        try:
            path = _artifact(f"_wal_stress_{sanitizer}", srcs,
                             ("-O1", "-g", *flags, "-fPIC", srcs[1]))
        except OSError as e:
            log.warning("wal_stress %s build unavailable (%s)",
                        sanitizer, e)
            path = None
        _cache[key] = path
        return path


def load_native_wal():
    """ctypes handle to the WAL fast path, or None."""
    lib = _load("wal")
    if lib is None:
        return None
    try:
        lib.wal_open.restype = ctypes.c_void_p
        lib.wal_open.argtypes = [ctypes.c_char_p]
        lib.wal_append_entry.restype = ctypes.c_int
        lib.wal_append_entry.argtypes = [
            ctypes.c_void_p, ctypes.c_uint32, ctypes.c_uint64,
            ctypes.c_uint64, ctypes.c_char_p, ctypes.c_uint32]
        lib.wal_append_entries.restype = ctypes.c_int
        lib.wal_append_entries.argtypes = [
            ctypes.c_void_p, ctypes.c_uint32,
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint32)]
        lib.wal_append_ranges.restype = ctypes.c_int
        lib.wal_append_ranges.argtypes = [
            ctypes.c_void_p, ctypes.c_uint32,
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.c_char_p, ctypes.POINTER(ctypes.c_uint32)]
        lib.wal_set_hardstate.restype = ctypes.c_int
        lib.wal_set_hardstate.argtypes = [
            ctypes.c_void_p, ctypes.c_uint32, ctypes.c_uint64,
            ctypes.c_int64, ctypes.c_uint64]
        lib.wal_set_hardstates.restype = ctypes.c_int
        lib.wal_set_hardstates.argtypes = [
            ctypes.c_void_p, ctypes.c_uint32,
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_uint64)]
        lib.wal_set_snapshot.restype = ctypes.c_int
        lib.wal_set_snapshot.argtypes = [
            ctypes.c_void_p, ctypes.c_uint32, ctypes.c_uint64,
            ctypes.c_uint64]
        lib.wal_epoch.restype = ctypes.c_int
        lib.wal_epoch.argtypes = [
            ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint8]
        lib.wal_set_compact.restype = ctypes.c_int
        lib.wal_set_compact.argtypes = [
            ctypes.c_void_p, ctypes.c_uint32, ctypes.c_uint64,
            ctypes.c_uint64]
        lib.wal_set_compacts.restype = ctypes.c_int
        lib.wal_set_compacts.argtypes = [
            ctypes.c_void_p, ctypes.c_uint32,
            ctypes.POINTER(ctypes.c_uint32),
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64)]
        lib.wal_sync.restype = ctypes.c_int
        lib.wal_sync.argtypes = [ctypes.c_void_p]
        lib.wal_close.restype = ctypes.c_int
        lib.wal_close.argtypes = [ctypes.c_void_p]
    except AttributeError as e:     # pragma: no cover - corrupt build
        log.warning("native wal ABI mismatch (%s); Python fallback", e)
        return None
    return lib
