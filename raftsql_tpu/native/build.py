"""Build + load the native components (ctypes, on-demand g++ compile).

pybind11 is not available in this environment, so the native pieces expose
a plain C ABI consumed through ctypes.  Each artifact is compiled next to
its source on first use and NAMED BY A HASH OF ITS SOURCES
(`_native_wal.<hash>.so`, `_http_load.<hash>`): an object can be loaded
only if it was built from the source beside it.  Modification times
prove nothing — a fresh checkout gives every file the same one, and a
copied working tree carries ignored artifacts along.  Failures of any
kind (no compiler, read-only checkout) degrade to the pure-Python
implementations; `/healthz` says which one is serving (`native_wal`,
`native_apply`).

Set RAFTSQL_TPU_NATIVE=0 to force the Python fallbacks.
"""
from __future__ import annotations

import ctypes
import glob
import hashlib
import logging
import os
import subprocess
import sys
import tempfile
import threading

log = logging.getLogger("raftsql_tpu.native")

_DIR = os.path.dirname(os.path.abspath(__file__))
_lock = threading.Lock()
_cache: dict = {}


def _compile(src: str, dest: str, link_args: tuple,
             libs: tuple = ()) -> bool:
    """Compile `src` to `dest` atomically (tmp + rename, so concurrent
    processes never open a half-written artifact); True on success,
    warning + False on any failure, temp never leaked.  `libs` follow
    the source on the command line, where the linker wants them."""
    fd, tmp = tempfile.mkstemp(dir=_DIR)
    os.close(fd)
    try:
        cmd = ["g++", "-O2", "-std=c++17", *link_args, "-o", tmp, src,
               *libs]
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


def _artifact(stem: str, srcs: list, flags: tuple, suffix: str = "",
              libs: tuple = ()):
    """Path of `stem`'s artifact built from `srcs` (first is the
    translation unit handed to g++) with `flags` and `libs`, compiling
    it when no object of exactly these sources exists; None when the
    build is unavailable.  The name carries a hash of the sources and
    flags, so a stale object beside changed source is never opened;
    superseded objects of the same stem are unlinked after a successful
    build."""
    h = hashlib.sha256(repr(flags + libs).encode())
    for src in srcs:
        with open(src, "rb") as f:
            h.update(f.read())
    path = os.path.join(_DIR, f"{stem}.{h.hexdigest()[:16]}{suffix}")
    if os.path.isfile(path):
        return path
    if not _compile(srcs[0], path, flags, libs):
        return None
    for old in glob.glob(os.path.join(_DIR, f"{stem}.*{suffix}")):
        if old != path:
            try:
                os.unlink(old)
            except OSError:
                pass
    return path


def _load(name: str, libs: tuple = ()):
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
                           ("-shared", "-fPIC"), suffix=".so", libs=libs)
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


# The interpreters on which taking the `sqlite3*` out of a
# `sqlite3.Connection` by its layout (models/sqlite_sm.py `_borrow`) was
# tested: (implementation, major, minor, size of a Connection object).
APPLY_TESTED_ON = frozenset({("cpython", 3, 12, 224)})


def _connection_layout() -> tuple:
    import sqlite3
    return (sys.implementation.name, *sys.version_info[:2],
            sqlite3.Connection.__basicsize__)


def load_native_apply():
    """ctypes handle to the one-call apply transaction (apply.cc), or
    None.  The object works on handles that CPython's `_sqlite3` made,
    so it is only of use bound to the SAME loaded SQLite: linked by
    soname against the library `_sqlite3` depends on, and checked here
    by the address of one of its symbols as both see it (a `_sqlite3`
    with a SQLite of its own inside, or none to look into, gives
    None).  And only on an interpreter in `APPLY_TESTED_ON`: the handle
    is a word read out of the connection object, and asking the library
    about a word that is no handle is a crash, not a fallback."""
    lib = _load("apply", libs=("-l:libsqlite3.so.0",))
    if lib is None:
        return None
    with _lock:
        if "apply_checked" in _cache:
            return _cache["apply_checked"]
        try:
            if _connection_layout() not in APPLY_TESTED_ON:
                raise OSError("a sqlite3.Connection's layout was not "
                              f"tested on {_connection_layout()}")
            import _sqlite3
            theirs = ctypes.cast(
                ctypes.CDLL(_sqlite3.__file__).sqlite3_libversion,
                ctypes.c_void_p).value
            lib.apply_sqlite_id.restype = ctypes.c_void_p
            lib.apply_sqlite_id.argtypes = []
            lib.apply_db_filename.restype = ctypes.c_char_p
            lib.apply_db_filename.argtypes = [ctypes.c_void_p]
            lib.apply_txn.restype = ctypes.c_int
            lib.apply_txn.argtypes = [
                ctypes.c_void_p, ctypes.c_int,
                ctypes.POINTER(ctypes.c_char_p),
                ctypes.POINTER(ctypes.c_int), ctypes.c_longlong]
            lib.apply_checkpoint_many.restype = None
            lib.apply_checkpoint_many.argtypes = [
                ctypes.POINTER(ctypes.c_void_p), ctypes.c_int, ctypes.c_int,
                ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_double)]
            lib.apply_reopen.restype = ctypes.c_int
            lib.apply_reopen.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
                ctypes.c_int, ctypes.POINTER(ctypes.c_longlong)]
            if lib.apply_sqlite_id() != theirs:
                raise OSError("bound to another SQLite than _sqlite3's")
        except (ImportError, AttributeError, OSError) as e:
            log.warning("native apply unusable (%s); Python fallback", e)
            lib = None
        _cache["apply_checked"] = lib
        return lib
