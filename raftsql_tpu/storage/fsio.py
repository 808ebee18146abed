"""Injectable filesystem layer for the durable write paths.

The reference's only storage-fault story is "trust etcd/wal"; SURVEY.md
§4 and the round-5 advisor findings (crash-window durability bugs that
no test could reach) call for systematic storage fault injection.  This
module is the seam: every durable-path write/fsync in storage/wal.py and
the epoch-commit file in runtime/fused.py flows through the functions
below, which are pass-throughs until a `StorageFaultInjector` is
installed (chaos/ scenarios install one; production never does, so the
cost is one None check per call).

Fault classes (the chaos harness's storage axis):
  * FAILED FSYNC — the Nth fsync matching a rule raises OSError,
    exercising the paths that must fail a tick loudly instead of
    acking unsynced data.  Counters are PER RULE (e.g. per peer WAL
    directory): each peer's fsyncs are sequential even when the fused
    barrier runs them from a worker pool, so rule counters are
    deterministic where a global counter would race.
  * SILENT FSYNC LOSS — from rule op N on, fsync reports success but
    syncs nothing; combined with a crash this models a disk that lied.
  * TORN WRITE / UNSYNCED LOSS — the injector records every write's
    (offset, length) and every file's last really-synced size, so a
    power-loss simulation can truncate files to exactly what a real
    crash could leave: everything synced, plus at most a torn prefix of
    one unsynced record (WAL._repair_tail's job to repair).
  * ENOSPC — the Nth write ATTEMPT matching a rule raises EnospcError
    (errno ENOSPC) BEFORE any byte reaches the file: the WAL record is
    refused whole, so the log tail stays a clean record boundary
    instead of a half-written frame.  The trigger is consumed when it
    fires (an operator freeing disk space), so a crash+restart retry
    of the same record succeeds.
  * FSYNC STALL — the Nth..(N+count-1)th fsyncs matching a rule sleep
    `stall_s` before completing (a saturated disk queue, not a failed
    one): data IS durable afterwards, just late — the tick slows, no
    invariant may break, and the stall count is exported so slow-disk
    incidents are visible in /metrics.
  * PROCESS EXIT AT FSYNC — the Nth fsync matching a rule hard-exits
    the WHOLE PROCESS (os._exit, EXIT_CODE_FSYNC_CRASH) before the
    real fsync runs: the process-plane chaos harness's crash point.
    The written-but-not-yet-synced tail sits in the page cache, the
    tick's ack never happens, and the restarted process must recover
    through WAL tail repair — the "machine died at the worst moment"
    scenario over a REAL server process, not an in-process simulation.

Faults cross the process boundary via RAFTSQL_FSIO_FAULTS: the server
entry point (server/main.py) parses the env spec with
`install_from_env` and installs the rules inside the child before the
node boots, so a nemesis that only controls argv/env can still inject
disk faults into real server processes.  Spec grammar (';'-separated
rules, ':'-separated fields, first field is the path substring):

    raftsql-2:enospc@12            ENOSPC on WAL write attempt #12
    raftsql-2:exit_fsync@9         hard process exit at fsync #9
    raftsql-1:fail_fsync@5         fsync #5 raises FsyncFaultError
    raftsql-3:stall@4x3x50         fsyncs #4..#6 stall 50 ms each
    raftsql-1:enospc@8:stall@2x2x20   clauses compose per rule

The injector also keeps an ordered event log (("write"|"fsync"|
"fsync_dir", path) tuples) so tests can assert durability ORDERING —
e.g. "the data_dir was fsynced after the EPOCHS file was created,
before the epoch was treated as committed".

An ACTIVE injector forces the Python WAL backend (storage/wal.py
_open_active checks `active()`): the C++ fast path does its framing and
fdatasync behind one ctypes call, invisible to this seam.  Chaos
scenarios trade the fast path for full observability; both backends
produce byte-identical files, so what the faults exercise is the real
on-disk format.
"""
from __future__ import annotations

import errno
import os
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple


class FsyncFaultError(OSError):
    """Injected fsync failure (distinguishable from real OS errors)."""


# Exit code of an injected process-exit-at-fsync crash point: the
# nemesis (chaos/proc.py) distinguishes "the scheduled disk crash
# fired" from a real bug in the child by this code.
EXIT_CODE_FSYNC_CRASH = 86


class EnospcError(OSError):
    """Injected disk-full write failure: raised BEFORE the write lands,
    so the refused record never reaches the file and the log tail stays
    a clean record boundary.  Carries errno.ENOSPC like the real one."""

    def __init__(self, msg: str):
        super().__init__(errno.ENOSPC, msg)


class CrashPointError(RuntimeError):
    """Injected mid-write power loss: the write reached the page cache
    (the injector writes through) and the machine died before any
    fsync.  Carries the rule's `tag` so the chaos runner knows which
    peer's record to tear."""

    def __init__(self, msg: str, tag=None):
        super().__init__(msg)
        self.tag = tag


class _FsyncRule:
    """One fault rule: matches paths by substring (`sub` in path + sep,
    so a directory matches its own fsync and its files'), counts the
    fsyncs and writes it sees, fails/skips/crashes at chosen ops."""

    def __init__(self, substring: str, fail_at=(), silent_from=None,
                 crash_write_at=(), tag=None, enospc_write_at=(),
                 stall_at=(), stall_s: float = 0.05, exit_at=()):
        self.substring = substring
        self.fail_at = set(fail_at)
        self.silent_from = silent_from
        self.crash_write_at = set(crash_write_at)
        self.tag = tag
        # ENOSPC triggers fire on the (write_ops + 1)th write ATTEMPT
        # and are consumed when they fire (see module doc).
        self.enospc_write_at = set(enospc_write_at)
        self.stall_at = set(stall_at)
        self.stall_s = stall_s
        # Process-exit crash points: fsync op numbers at which the
        # whole process hard-exits (os._exit, no cleanup).
        self.exit_at = set(exit_at)
        self.ops = 0
        self.write_ops = 0
        self.failures = 0
        self.lost = 0
        self.enospc_hits = 0
        self.stalls = 0

    def matches(self, path: str) -> bool:
        return self.substring in path + os.sep


class StorageFaultInjector:
    """Deterministic storage fault state, shared across all files.

    Thread-safe: the fused runtime fsyncs peers from a worker pool, so
    the write log and rule counters are lock-protected.
    """

    def __init__(self):
        self.rules: List[_FsyncRule] = []
        self.fsync_ops = 0
        self.write_ops = 0
        self.fsync_failures = 0
        self.enospc_hits = 0
        self.fsync_stalls = 0
        self.events: List[Tuple[str, str]] = []
        # path -> (offset before last write, bytes written) for torn-
        # write crash simulation.
        self.last_write: Dict[str, Tuple[int, int]] = {}
        # path -> durable size at last REAL fsync (for unsynced-loss
        # crash simulation; a path absent here was never synced).
        self.synced_size: Dict[str, int] = {}
        # Path substring whose next unlink is the crash point (WAL
        # compaction: the re-asserted records are durable, the doomed
        # segment still there), or None.  Consumed when it fires.
        self.crash_unlink: Optional[str] = None
        self._lock = threading.Lock()

    def add_rule(self, substring: str, fail_at=(),
                 silent_from: Optional[int] = None,
                 crash_write_at=(), tag=None, enospc_write_at=(),
                 stall_at=(), stall_s: float = 0.05,
                 exit_at=()) -> _FsyncRule:
        rule = _FsyncRule(substring, fail_at, silent_from,
                          crash_write_at, tag, enospc_write_at,
                          stall_at, stall_s, exit_at)
        with self._lock:
            self.rules.append(rule)
        return rule

    # -- hooks called by the I/O functions below -----------------------

    def check_write(self, path: str, nbytes: int) -> None:
        """Pre-write gate: raises EnospcError when a rule's next write
        attempt is scheduled to hit disk-full.  Runs BEFORE the caller
        writes anything, so the refused record never lands (the log
        tail cannot be corrupted by a half-written frame).  The trigger
        is consumed so a post-restart retry of the same record
        succeeds — the disk-was-freed recovery story."""
        with self._lock:
            for rule in self.rules:
                if not rule.matches(path):
                    continue
                attempt = rule.write_ops + 1
                if attempt in rule.enospc_write_at:
                    rule.enospc_write_at.discard(attempt)
                    rule.enospc_hits += 1
                    self.enospc_hits += 1
                    raise EnospcError(
                        f"injected ENOSPC (write attempt {attempt} of "
                        f"rule {rule.substring!r}) on {path}")

    def on_write(self, path: str, offset: int, nbytes: int) -> None:
        """Record one (already page-cache-visible) write; raises
        CrashPointError AFTER recording when a rule's write counter
        hits a crash point — the caller's write reached the file, the
        fsync never will."""
        with self._lock:
            self.write_ops += 1
            self.events.append(("write", path))
            self.last_write[path] = (offset, nbytes)
            for rule in self.rules:
                if not rule.matches(path):
                    continue
                rule.write_ops += 1
                if rule.write_ops in rule.crash_write_at:
                    raise CrashPointError(
                        f"injected mid-write power loss (write op "
                        f"{rule.write_ops} of rule {rule.substring!r}) "
                        f"on {path}", tag=rule.tag)

    def on_fsync(self, path: str, size: int, kind: str = "fsync") -> bool:
        """Count one fsync; returns False when the sync must be
        silently skipped; raises FsyncFaultError for a failed one.
        Stall rules sleep OUTSIDE the lock (a stalled disk must slow
        this fsync, not serialize every other peer's)."""
        stall_for = 0.0
        with self._lock:
            self.fsync_ops += 1
            self.events.append((kind, path))
            silent = False
            for rule in self.rules:
                if not rule.matches(path):
                    continue
                rule.ops += 1
                if rule.ops in rule.exit_at:
                    # Crash point: the machine dies AT the fsync — the
                    # record is in the page cache, the barrier never
                    # completes, nothing after this line runs.  stderr
                    # is best-effort (the nemesis reads the exit code).
                    try:
                        sys.stderr.write(
                            f"fsio: injected process exit at fsync "
                            f"{rule.ops} of rule {rule.substring!r} "
                            f"on {path}\n")
                        sys.stderr.flush()
                    finally:
                        os._exit(EXIT_CODE_FSYNC_CRASH)
                if rule.ops in rule.fail_at:
                    rule.failures += 1
                    self.fsync_failures += 1
                    raise FsyncFaultError(
                        f"injected fsync failure (op {rule.ops} of rule "
                        f"{rule.substring!r}) on {path}")
                if rule.ops in rule.stall_at:
                    rule.stalls += 1
                    self.fsync_stalls += 1
                    stall_for = max(stall_for, rule.stall_s)
                if rule.silent_from is not None \
                        and rule.ops >= rule.silent_from:
                    rule.lost += 1
                    silent = True
            if not silent and kind == "fsync":
                self.synced_size[path] = size
        if stall_for > 0.0:
            time.sleep(stall_for)
        return not silent

    # -- crash simulation ----------------------------------------------

    def tear_last_write(self, path: str,
                        keep_fraction: float = 0.5) -> bool:
        """Truncate `path` mid-way through its last recorded write —
        the torn-record shape a power loss leaves.  Never cuts below
        the last really-synced size (durable bytes cannot tear), and
        never extends the file (the write may still sit in a userspace
        buffer a simulated process kill already discarded).  Returns
        True when something was actually torn."""
        rec = self.last_write.get(path)
        if rec is None or not os.path.isfile(path):
            return False
        offset, nbytes = rec
        keep = offset + max(0, min(nbytes - 1,
                                   int(nbytes * keep_fraction)))
        keep = max(keep, self.synced_size.get(path, 0))
        if keep >= os.path.getsize(path):
            return False
        with open(path, "r+b") as f:
            f.truncate(keep)
        return True

    def drop_unsynced(self, path: str) -> bool:
        """Truncate `path` back to its last REALLY-synced size (0 when
        never synced) — what a power loss leaves on disk.  Returns True
        when bytes were dropped."""
        size = self.synced_size.get(path, 0)
        if not os.path.isfile(path) or os.path.getsize(path) <= size:
            return False
        with open(path, "r+b") as f:
            f.truncate(size)
        return True

    def tracked_paths(self) -> List[str]:
        with self._lock:
            return sorted(set(self.last_write) | set(self.synced_size))


_injector: Optional[StorageFaultInjector] = None


def install(inj: StorageFaultInjector) -> StorageFaultInjector:
    global _injector
    _injector = inj
    return inj


def uninstall() -> None:
    global _injector
    _injector = None


def active() -> bool:
    return _injector is not None


def injector() -> Optional[StorageFaultInjector]:
    return _injector


# -- env-injected faults (the process boundary) ------------------------

def parse_env_spec(spec: str) -> List[dict]:
    """Parse a RAFTSQL_FSIO_FAULTS value into add_rule kwargs dicts.

    Grammar (module doc): rules ';'-separated, fields ':'-separated,
    first field the path substring, then `clause@args` clauses with
    'x'-separated integer args.  Raises ValueError on anything
    malformed — a server booted with a broken fault spec must fail
    loudly, not run chaos with silently-dropped faults."""
    rules = []
    for rule_s in spec.split(";"):
        rule_s = rule_s.strip()
        if not rule_s:
            continue
        fields = rule_s.split(":")
        if len(fields) < 2 or not fields[0]:
            raise ValueError(f"fsio spec rule needs 'substring:clause', "
                             f"got {rule_s!r}")
        kw: dict = {"substring": fields[0]}
        for clause in fields[1:]:
            name, at, args_s = clause.partition("@")
            if at != "@":
                raise ValueError(f"fsio clause needs 'name@args', "
                                 f"got {clause!r}")
            args = [int(a) for a in args_s.split("x")]
            if name == "enospc" and len(args) == 1:
                kw.setdefault("enospc_write_at", []).append(args[0])
            elif name == "fail_fsync" and len(args) == 1:
                kw.setdefault("fail_at", []).append(args[0])
            elif name == "exit_fsync" and len(args) == 1:
                kw.setdefault("exit_at", []).append(args[0])
            elif name == "stall" and len(args) == 3:
                k, count, ms = args
                kw.setdefault("stall_at", []).extend(
                    range(k, k + count))
                kw["stall_s"] = ms / 1000.0
            else:
                raise ValueError(f"unknown fsio clause {clause!r}")
        rules.append(kw)
    return rules


def install_from_env(spec: Optional[str] = None) \
        -> Optional[StorageFaultInjector]:
    """Install an injector from a RAFTSQL_FSIO_FAULTS-style spec (reads
    the env var when `spec` is None).  Returns the installed injector,
    or None when the spec is absent/empty.  This is the server entry
    point's storage-fault seam: the nemesis sets the env var, the child
    installs the rules before its first WAL byte."""
    if spec is None:
        spec = os.environ.get("RAFTSQL_FSIO_FAULTS", "")
    rules = parse_env_spec(spec)
    if not rules:
        return None
    inj = StorageFaultInjector()
    for kw in rules:
        inj.add_rule(**kw)
    return install(inj)


class installed:
    """Context manager: `with fsio.installed(inj): ...` — uninstalls on
    exit even when the scenario raises (tests must never leak an
    injector into the next test's WAL traffic)."""

    def __init__(self, inj: StorageFaultInjector):
        self.inj = inj

    def __enter__(self) -> StorageFaultInjector:
        return install(self.inj)

    def __exit__(self, *exc) -> None:
        uninstall()


# -- the I/O seam ------------------------------------------------------

def write(f, data: bytes) -> None:
    """File write, recorded for torn-write simulation.

    Under an injector the write goes THROUGH to the file before the
    crash-point check runs — page-cache semantics: a process kill keeps
    what was written, a power loss keeps at most a torn prefix of it
    (the injector's tear/drop helpers cut it back to what a real crash
    could leave).  An ENOSPC rule fires BEFORE any byte lands (see
    StorageFaultInjector.check_write): the caller's record is refused
    whole and the file tail is untouched."""
    inj = _injector
    if inj is None:
        f.write(data)
        return
    path = getattr(f, "name", "")
    inj.check_write(path, len(data))     # may raise EnospcError
    offset = f.tell()
    f.write(data)
    f.flush()
    inj.on_write(path, offset, len(data))


def fsync_file(f) -> None:
    """flush + fsync an open file object through the fault layer."""
    f.flush()
    inj = _injector
    if inj is not None:
        if not inj.on_fsync(getattr(f, "name", ""), f.tell()):
            return                       # silent loss: report success
    os.fsync(f.fileno())


def unlink(path: str) -> None:
    """Remove a file (a WAL segment compaction has superseded) through
    the fault layer: an injector whose `crash_unlink` matches raises
    CrashPointError INSTEAD, once: the machine died before the
    unlink."""
    inj = _injector
    if inj is not None and inj.crash_unlink is not None \
            and inj.crash_unlink in path:
        inj.crash_unlink = None
        raise CrashPointError(f"crash before unlink of {path}")
    os.unlink(path)


def fsync_dir(path: str) -> None:
    """fsync a directory fd (dirent durability) through the fault layer."""
    inj = _injector
    if inj is not None:
        if not inj.on_fsync(path, 0, kind="fsync_dir"):
            return
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)
