"""SQLite state machine — reference-parity apply/query semantics.

Mirrors the reference's raftdb SQL handling (reference db.go):
  - the database file is DELETED on boot and rebuilt entirely from the
    replicated log — no snapshots yet (db.go:27-29);
  - writes are applied in commit order under a write lock (db.go:55-57);
  - reads run against the local replica only, never consulting the
    leader — stale reads are by design (db.go:128-130);
  - SELECT rows are rendered `|v1|v2|…|\n` with every column stringified
    via a byte-slice scan (db.go:137-156): NULL → empty cell, so the
    `||0|`-style strings the reference tests grep for fall out.

SQLite is C reached through CPython's `sqlite3` binding — the same
library the reference reaches through cgo (db.go:6), per SURVEY.md §2b V5.

A file-backed machine applies a batch in ONE call into that library
(native/apply.cc, on the connection's own handle) where it can; the
Python loop over `sqlite3` calls is the same transaction, the arm for
everything else and the only source of a failure's outcome.
"""
from __future__ import annotations

import ctypes
import functools
import os
import sqlite3
import threading
import time
from typing import Optional

from raftsql_tpu.native.build import load_native_apply
from raftsql_tpu.storage import fsio


def is_select(query: str) -> bool:
    """First-token SELECT check, case-insensitive — the reference's naive
    write/read split (db.go:98-104), preserved deliberately."""
    tokens = query.strip(" ").split(" ")
    return len(tokens) > 0 and tokens[0].upper() == "SELECT"


def _handle(conn: sqlite3.Connection):
    """The word after the head of `conn`, which CPython's connection
    object keeps its `sqlite3 *db` in (Modules/_sqlite/connection.h; the
    module has no accessor for it).  Only to be read where the library
    loaded (`load_native_apply`), which it does only on an interpreter
    whose layout was looked at (native/build.py `APPLY_TESTED_ON`):
    asking the library about a word that is no handle is a wild
    dereference, a crash, not a counted fallback."""
    return ctypes.c_void_p.from_address(
        id(conn) + object.__basicsize__).value


def _borrow(conn: sqlite3.Connection, path: str):
    """native/apply.cc's `apply_txn` bound to the `sqlite3*` inside
    `conn`, and the file name the library gave for that handle, so that
    a batch's transaction runs on the connection's OWN handle (a second
    connection would double the descriptors models/store.py budgets
    by, and make every query re-read the pages a write changed); None
    where the handle cannot be had or does not prove itself, and the
    machine then stays on the Python loop.

    The word `_handle` reads is believed only if the library, asked
    through it for the main database's file, names this machine's file,
    outside a transaction."""
    if path == ":memory:" or type(conn) is not sqlite3.Connection:
        return None
    lib = load_native_apply()
    if lib is None:
        return None
    db = _handle(conn)
    if not db:
        return None
    try:
        name = lib.apply_db_filename(db)
        if name and os.path.samefile(name, path) \
                and not conn.in_transaction:
            return functools.partial(lib.apply_txn, db), name
    except OSError:
        pass
    return None


def _cell(v) -> str:
    if v is None:
        return ""
    if isinstance(v, bytes):
        return v.decode("utf-8", "replace")
    if isinstance(v, float):
        return repr(v)
    return str(v)


class SQLiteStateMachine:
    """`resume=False` (default): reference parity — the DB file is deleted
    on boot and rebuilt from the log (db.go:29).

    `resume=True`: the DB file IS the snapshot.  Every apply writes the
    entry's log index into the `_raft_meta` table inside the SAME SQLite
    transaction as the command, so file-state and applied-index are
    crash-atomic; on reboot the engine skips entries at or below
    `applied_index()` instead of replaying from scratch."""

    def __init__(self, path: str, resume: bool = False):
        if not resume and path != ":memory:" and os.path.exists(path):
            os.remove(path)
        self.path = path
        self.resume = resume
        # WAL compaction may only trust applied_index() as a floor when it
        # survives a crash (models/base.py contract).
        self.has_durable_snapshot = resume and path != ":memory:"
        # Descriptors this machine holds while its connection is open
        # (models/store.py budgets by it): a WAL-journal database keeps
        # the file, its -wal and its -shm; an in-memory one none, and
        # can therefore never be released.
        self.open_files = 0 if path == ":memory:" else (
            3 if self.has_durable_snapshot else 1)
        # Whether the one native call committed the last batch (one
        # that fell back to the Python loop counts as that loop's):
        # runtime/db.py reads it after its call.
        self.last_native = False
        # The file name the library gave for this machine's last handle
        # that `_borrow` verified, None where the last was not: a reopen
        # takes a new handle naming it as verified (`_reconnect`).
        self._verified: Optional[bytes] = None
        self._connect()
        self._lock = threading.Lock()
        self._applied = 0
        self._dir_synced = False
        if resume:
            self._conn.execute(
                "CREATE TABLE IF NOT EXISTS _raft_meta "
                "(k TEXT PRIMARY KEY, v INTEGER)")
            self._conn.commit()
            self._applied = self._applied_on_file()

    def _applied_on_file(self) -> int:
        row = self._conn.execute(
            "SELECT v FROM _raft_meta WHERE k='applied_index'").fetchone()
        return int(row[0]) if row else 0

    def _connect(self, reopening: bool = False) -> None:
        """Open self.path configured for this state machine, as
        `self._conn`, and borrow its handle as `self._txn`: manual
        transaction control (apply_batch brackets its own BEGIN/COMMIT
        group commit — the module's implicit-BEGIN machinery would fight
        the explicit statements) and journaling matched to the upstream
        durability model.  Durability belongs to the raft WAL, not
        SQLite:
          - parity mode deletes and rebuilds this file from the log on
            every boot (db.go:27-29), so per-statement fsync buys
            nothing — memory journal, no syncs;
          - resume mode needs (commands, applied_index) ATOMIC, not
            durable-per-statement: SQLite-WAL + synchronous=NORMAL can
            lose a recent tail on power loss but always rolls the file
            back to a consistent point whose applied_index matches, and
            the raft log replays forward from there — exactly-once
            preserved at a fraction of the fsync cost.  The log must
            still BE there: a compaction sweep drops it only under an
            index `checkpoint()` has put on disk.
        A file this machine made or opened is in WAL mode for good (the
        file says so), so `reopening` it asks only for the syncs."""
        self._setup(self._open(), reopening)

    def _open(self) -> sqlite3.Connection:
        conn = sqlite3.connect(self.path, check_same_thread=False)
        conn.isolation_level = None
        return conn

    def _pragmas(self, reopening: bool) -> tuple:
        if not self.has_durable_snapshot:
            return ("PRAGMA journal_mode=MEMORY", "PRAGMA synchronous=OFF")
        if reopening:
            return ("PRAGMA synchronous=NORMAL",)
        return ("PRAGMA journal_mode=WAL", "PRAGMA synchronous=NORMAL")

    def _setup(self, conn: sqlite3.Connection, reopening: bool) -> None:
        """`_connect`'s work on the new connection through the module."""
        try:
            for pragma in self._pragmas(reopening):
                conn.execute(pragma)
        except sqlite3.Error:          # pragma: no cover - pragma support
            pass
        self._conn = conn
        self._txn, self._verified = _borrow(conn, self.path) or (None, None)

    def _reconnect(self) -> Optional[int]:
        """`_connect(reopening=True)` and, in resume mode, the applied
        index on file, in ONE native call on the new connection's own
        handle (native/apply.cc `apply_reopen`) where this machine's
        last handle was verified: the new one must name the same file,
        outside a transaction, so the `stat`s of `_borrow` are not made
        again.  The index read (0 in parity mode), or None where the
        module did the set-up and the caller reads the index there."""
        lib = load_native_apply() if self._verified else None
        if lib is None:
            self._connect(reopening=True)
            return None
        conn = self._open()
        db = _handle(conn)
        on_file = ctypes.c_longlong()
        if db and not lib.apply_reopen(
                db, self._verified,
                "; ".join(self._pragmas(True)).encode(), int(self.resume),
                ctypes.byref(on_file)):
            self._conn = conn
            self._txn = functools.partial(lib.apply_txn, db)
            return on_file.value
        self._setup(conn, reopening=True)
        return None

    def _disconnect(self) -> None:
        """Close the connection; the borrowed handle dies with it."""
        self._txn = None
        self._conn.close()
        self._conn = None

    def applied_index(self) -> int:
        return self._applied

    def release(self) -> int:
        """Close the connection and keep the machine (models/store.py:
        the least recently used handle gives its descriptors back);
        returns the applied index the close put on disk, or 0.  Closing
        the last connection of a WAL-journal database checkpoints it as
        `checkpoint` does, with the same syncs, and drops the `-wal`
        only where that checkpoint ran to its end: a `-wal` left behind
        says it did not.  The file stays, in either mode, and `reopen`
        finds it as it was left."""
        with self._lock:
            if self._conn is None:
                return 0
            self._disconnect()
            if not self.has_durable_snapshot \
                    or os.path.exists(self.path + "-wal"):
                return 0
            self._sync_dir()
            return self._applied

    def checkpoint(self) -> int:
        """Make everything applied so far survive a power loss, and
        return the applied index that now does (0 where this machine
        keeps no durable snapshot, or the checkpoint could not run to
        its end).  `synchronous=NORMAL` syncs nothing at a commit: the
        newest transactions live in the `-wal` file's unsynced tail
        until a checkpoint, which syncs the `-wal`, copies its pages
        into the database and syncs that.  The compaction sweep drops
        the raft log under a group's applied index, so it may only
        trust an index this has returned (models/store.py
        `durable`)."""
        with self._lock:
            if self._conn is None:
                return 0
            return self._checkpoint()

    def _checkpoint(self) -> int:
        """`checkpoint` with the lock held and the connection open."""
        if not self.has_durable_snapshot:
            return 0
        try:
            busy, in_log, moved = self._conn.execute(
                "PRAGMA wal_checkpoint(FULL)").fetchone()
        except sqlite3.Error:
            return 0                # disk full: the log stays
        if busy or in_log != moved:
            return 0
        self._sync_dir()
        return self._applied

    def _sync_dir(self) -> None:
        if not self._dir_synced:
            # The file's own directory entry, once.
            fsio.fsync_dir(os.path.dirname(self.path) or ".")
            self._dir_synced = True

    @staticmethod
    def checkpoint_many(machines: list, threads: int) -> list:
        """`checkpoint` each of `machines` (a compaction round's batch):
        [(the applied index now on disk or 0, the seconds its checkpoint
        took)], one a machine.  Every machine's lock is held for the
        whole batch.  Those on the native arm go through ONE call into
        SQLite, `threads` files at a time, with the interpreter given up
        once (native/apply.cc `apply_checkpoint_many`); the others one
        by one here.  A directory that holds a file put on disk for the
        first time is synced once for all of them."""
        out = [(0, 0.0)] * len(machines)
        held, native = [], []
        try:
            for i, sm in enumerate(machines):
                sm._lock.acquire()
                held.append(sm)
                if sm._conn is None or not sm.has_durable_snapshot:
                    continue
                if sm._txn is None:
                    t0 = time.monotonic()
                    out[i] = (sm._checkpoint(), time.monotonic() - t0)
                else:
                    native.append(i)
            if native:
                n = len(native)
                ok, secs = (ctypes.c_int * n)(), (ctypes.c_double * n)()
                # `_txn` is `apply_txn` bound to the borrowed handle.
                dbs = (ctypes.c_void_p * n)(
                    *(machines[i]._txn.args[0] for i in native))
                load_native_apply().apply_checkpoint_many(
                    dbs, n, threads, ok, secs)
                for d in {os.path.dirname(machines[i].path) or "."
                          for j, i in enumerate(native)
                          if ok[j] and not machines[i]._dir_synced}:
                    fsio.fsync_dir(d)
                for j, i in enumerate(native):
                    sm = machines[i]
                    if ok[j]:
                        sm._dir_synced = True
                    out[i] = (sm._applied if ok[j] else 0, secs[j])
        finally:
            for sm in held:
                sm._lock.release()
        return out

    def reopen(self) -> bool:
        """Connect again to the file `release` left; True where one
        native call did the set-up (`_reconnect`).  Never deletes:
        parity mode's delete-at-boot is the constructor's.  In resume
        mode the file's own `_raft_meta` must say what this machine
        remembers, or the file is not the one that was released."""
        with self._lock:
            if self._conn is not None:
                return False
            on_file = self._reconnect()
            native = on_file is not None
            if self.resume:
                if not native:
                    on_file = self._applied_on_file()
                if on_file != self._applied:
                    raise RuntimeError(
                        f"{self.path}: applied index {on_file} on file, "
                        f"{self._applied} remembered at release")
            return native

    def apply(self, command: str, index: int = 0) -> Optional[Exception]:
        return self.apply_batch([(command, index)])[0]

    def apply_batch(self, items) -> list:
        """Apply `[(command, index), ...]` in ONE durable transaction
        (group commit): per-statement outcomes are isolated with
        SAVEPOINTs, and the batch's statements plus the final
        applied_index land atomically — so a crash re-delivers the whole
        batch (exactly-once via the applied floor), never half of it.
        Returns one Optional[Exception] per item.

        The exactly-once check lives under the SAME lock install()
        takes: a snapshot install racing the applier thread bumps
        _applied before this runs, so a stale queued entry can never
        re-apply over the installed image."""
        with self._lock:
            errs = self._apply_native(items) if self._txn else None
            self.last_native = errs is not None
            if errs is None:
                errs = self._apply_python(items)
            return errs

    def _skip(self, index: int) -> bool:
        """Already applied before a restart or an install (resume mode):
        the entry is consumed, its statement not run again."""
        return bool(self.resume and index and index <= self._applied)

    def _apply_native(self, items) -> Optional[list]:
        """The batch's transaction as one call on the borrowed handle
        (native/apply.cc; lock held).  None where it did not commit:
        nothing of it landed, and `_apply_python` runs the same items."""
        todo = [(cmd, ix) for cmd, ix in items if not self._skip(ix)]
        last = max((ix for _, ix in todo), default=0)
        try:
            cmds = [cmd.encode("utf-8") for cmd, _ in todo]
        except UnicodeEncodeError:      # a lone surrogate
            return None
        n = len(cmds)
        if self._txn(n, (ctypes.c_char_p * n)(*cmds),
                     (ctypes.c_int * n)(*map(len, cmds)),
                     last if self.resume else 0):
            return None
        if last:
            self._applied = last
        return [None] * len(items)

    def _apply_python(self, items) -> list:
        """The batch's transaction statement by statement through the
        `sqlite3` module (lock held): the arm of an in-memory machine,
        of a process without the native library, and of every batch the
        native call gave back."""
        errs: list = []
        attempted: list = []     # False = skipped as already applied
        last = 0
        try:
            self._conn.execute("BEGIN")
        except sqlite3.Error:       # already in a transaction
            pass
        for command, index in items:
            if self._skip(index):
                errs.append(None)
                attempted.append(False)
                continue
            attempted.append(True)
            try:
                self._conn.execute("SAVEPOINT _apply")
                self._conn.execute(command)
                self._conn.execute("RELEASE _apply")
                errs.append(None)
            except sqlite3.Error as e:
                # A failed command still consumes its entry (the
                # error is its outcome, reference db.go:55-80): undo
                # only ITS effects, keep the batch.
                try:
                    self._conn.execute("ROLLBACK TO _apply")
                    self._conn.execute("RELEASE _apply")
                except sqlite3.Error:
                    pass
                errs.append(e)
            if index:
                last = max(last, index)
        meta = ("INSERT INTO _raft_meta (k, v) VALUES "
                "('applied_index', ?) ON CONFLICT(k) DO UPDATE "
                "SET v=excluded.v")
        try:
            if self.resume and last:
                self._conn.execute(meta, (last,))
            self._conn.commit()
            if last:
                self._applied = last
        except sqlite3.Error as e:
            # Commit failure (disk full): nothing of the batch
            # landed.  Report it on every entry attempted in THIS
            # transaction (skipped duplicates keep their None — they
            # are durable from an earlier boot), then try to advance
            # the durable floor alone so the entries stay consumed
            # ("the error is their outcome") — the applied floor may
            # only move when it is durable, because WAL compaction
            # and snapshot labeling trust it (models/base.py).
            try:
                self._conn.rollback()
            except sqlite3.Error:
                pass
            errs = [err if (err is not None or not att) else e
                    for err, att in zip(errs, attempted)]
            if last:
                try:
                    if self.resume:
                        self._conn.execute(meta, (last,))
                        self._conn.commit()
                    self._applied = last
                except sqlite3.Error:
                    pass            # floor stays; log re-delivers
        return errs

    def _image(self) -> bytes:
        """Serialize in DELETE journal mode: a WAL-mode image cannot be
        `deserialize`d by a receiver (in-memory databases reject WAL),
        and an image header should not advertise a -wal sidecar it does
        not carry.  Caller holds the lock; the mode flip checkpoints,
        which is fine at InstallSnapshot cadence.

        A database nothing has been written to has NO pages, and
        SQLite refuses to serialize it ("unable to serialize 'main'") —
        which is every group's state at boot in parity mode.  An empty
        write transaction allocates page 1 (the header and an empty
        schema) without changing what any query sees, so the image is
        a valid one-page database."""
        if not self._conn.execute("PRAGMA page_count").fetchone()[0]:
            self._conn.execute("BEGIN IMMEDIATE")
            self._conn.execute("COMMIT")
        wal = self.has_durable_snapshot
        if wal:
            self._conn.execute("PRAGMA journal_mode=DELETE")
        try:
            return self._conn.serialize()
        finally:
            if wal:
                self._conn.execute("PRAGMA journal_mode=WAL")

    def serialize(self) -> bytes:
        """Consistent point-in-time image of the database (the blob of an
        InstallSnapshot transfer)."""
        with self._lock:
            return self._image()

    def serialize_with_index(self):
        """(applied_index, image) captured atomically — the pair an
        InstallSnapshot sender needs (an apply sneaking between the two
        reads would mislabel the image's log position)."""
        with self._lock:
            return self._applied, self._image()

    def install(self, blob: bytes, index: int) -> None:
        """Replace all state with a serialized image applied up to
        `index` (receiver side of InstallSnapshot).

        With a real file, the image replaces the FILE (atomic tmp +
        rename, stale -wal/-shm sidecars dropped) and the connection
        reopens on it — `deserialize` would silently detach the
        connection onto an in-memory copy, so post-install applies
        never reached disk and a restart resurrected the pre-install
        file.  The in-memory path keeps deserialize."""
        with self._lock:
            if self.path != ":memory:":
                # Image lands in a tmp file BEFORE the live connection
                # closes: if the write fails (ENOSPC), the pre-install
                # state machine stays fully usable and the node just
                # drops the transfer.
                tmp = self.path + ".snap"
                with open(tmp, "wb") as f:
                    f.write(blob)
                    f.flush()
                    os.fsync(f.fileno())
                self._disconnect()
                try:
                    os.replace(tmp, self.path)
                    for suffix in ("-wal", "-shm"):
                        try:
                            os.remove(self.path + suffix)
                        except OSError:
                            pass
                finally:
                    self._connect()
            else:
                self._conn.deserialize(blob)
            if self.resume:
                self._conn.execute(
                    "CREATE TABLE IF NOT EXISTS _raft_meta "
                    "(k TEXT PRIMARY KEY, v INTEGER)")
                self._conn.execute(
                    "INSERT INTO _raft_meta (k, v) VALUES "
                    "('applied_index', ?) ON CONFLICT(k) DO UPDATE "
                    "SET v=excluded.v", (index,))
                self._conn.commit()
            self._applied = index

    def query(self, q: str) -> str:
        with self._lock:
            cur = self._conn.execute(q)
            rows = cur.fetchall()
        out = []
        for row in rows:
            out.append("|" + "|".join(_cell(v) for v in row) + "|\n")
        return "".join(out)

    def rows(self, q: str) -> list:
        """Structured read: the raw result tuples.  The reshard plane
        moves row values between groups verbatim, so it cannot use
        query()'s pipe-delimited rendering (a value containing '|'
        would be torn on re-parse)."""
        with self._lock:
            cur = self._conn.execute(q)
            return cur.fetchall()

    def close(self) -> None:
        with self._lock:
            if self._conn is not None:
                self._disconnect()
