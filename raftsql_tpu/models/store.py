"""StateMachineStore — state machines for the groups in use, not for all.

A multi-raft node leads tens of thousands of groups of which a few
hundred ever see a statement.  One open SQLite handle a group, made at
boot, costs one descriptor a group in parity mode and three in resume
mode (the file, its `-wal`, its `-shm`) whether or not the group was
ever written: 30,512 descriptors at G=10,000 where hosts give 20,000,
and the wall that keeps G=100,000 from starting at all.

The store makes a group's state machine on FIRST USE (`use(group)`, a
context manager: apply, query, snapshot, install) and keeps at most
`budget` of them OPEN: when one more would pass the budget, a handle
that nobody holds is released (RELEASE below) and reopened when the
group is next used.  The budget is what the process can observe: the
soft RLIMIT_NOFILE, less a reserve for the raft WALs, rings, sockets
and workers, divided by the descriptors one handle holds (`open_files`
of the first machine made: 3 in resume mode, 1 in parity mode, 0 for an
in-memory machine, which is then never released: it has nowhere to come
back from).

What every group has whether or not it is open, as int64 [G] arrays the
hot paths read without a lock or a file:

  `applied`  the index its state machine has applied (`applied_index`,
             /healthz, the read waits, the session watermark).  Written
             under the store's lock, from the machine itself, when a
             handle is opened and whenever a user lets go of it, and
             never downwards: a reader that leaves late cannot put an
             applier's newer index back.  A group whose file exists at
             boot is named to `seed()`, which reads the index off the
             file; any other group reads 0 until its first statement.
  `synced`   the applied index a POWER LOSS cannot take back: what
             `checkpoint(group)` or a release last put on disk.  A
             state machine commits without a sync (models/sqlite_sm.py
             `synchronous=NORMAL`), so `applied` runs ahead of the
             file; the compaction sweep, which unlinks the raft log
             under a group's index, is given this one.

RELEASE.  A use that finds its handle closed (`misses`) and no slot
free releases a victim on its own thread: the least recently used
handle nobody holds, the first whose file is already on disk where one
is among the VICTIM_SCAN least recently used (its release is then a
close, no fsync: the compaction round puts every written file on disk).  A release puts the file on disk
the way `checkpoint` does and only then closes it; a group whose
release got its checkpoint through counts as synced up to its applied
index, so no compaction round opens it again.  A slot counts until its
descriptors are closed.

THE ROUND (`checkpoint_round`, runtime/db.py's compaction thread) puts
every file with `applied > synced` on disk before the sweep, ROUND_BATCH
open files at a time in one call of the machines' `checkpoint_many`.

Concurrency: the apply workers (runtime/db.py), the read pool and the
compaction round use handles side by side.  A handle in use is PINNED
and never a victim; open and release happen outside the store's lock
with the entry marked busy, and a thread that wants a busy entry waits
for it.  So no read or apply is ever served from a handle
another thread is closing.
"""
from __future__ import annotations

import contextlib
import resource
import threading
import time
from collections import OrderedDict
from typing import Callable, Dict, Iterable, Optional

import numpy as np

# Descriptors left to everything that is not a state machine: the raft
# WAL segments, the propose/completion rings, the listening and scrape
# sockets, the device, the log (server/main.py asks RLIMIT_NOFILE for
# the same reserve).
RESERVED_FILES = 512
# The fewest handles a store will work with: more than the threads that
# can hold one at a time (the apply workers, both read pools, the
# reader, a snapshot, a compaction round's batch), so a victim always
# exists.
MIN_HANDLES = 64
# How far up the order of use a victim whose file is on disk is looked
# for before the least recently used one is taken as it is.
VICTIM_SCAN = 64
# Open files a round pins and puts on disk in one call, and the threads
# that call runs them on: a checkpoint is two fsyncs, the disk's time,
# so several go at once; a batch's groups wait for it to end.
ROUND_BATCH = 32
ROUND_THREADS = 8


def handle_budget(open_files: int, limit: Optional[int] = None) -> int:
    """How many handles of `open_files` descriptors each may be open at
    once under RLIMIT_NOFILE's soft limit (`limit` overrides it)."""
    if open_files <= 0:
        return 1 << 62                  # nothing to run out of
    if limit is None:
        limit = resource.getrlimit(resource.RLIMIT_NOFILE)[0]
        if limit == resource.RLIM_INFINITY:
            return 1 << 62
    return max(MIN_HANDLES, (limit - RESERVED_FILES) // open_files)


def files_needed(open_files: int) -> int:
    """The least RLIMIT_NOFILE a store of such handles can run under."""
    return RESERVED_FILES + MIN_HANDLES * max(open_files, 0)


class _Entry:
    __slots__ = ("sm", "pins", "busy")

    def __init__(self):
        self.sm = None
        self.pins = 0
        self.busy = True        # being made, reopened or released


class StateMachineStore:
    def __init__(self, factory: Callable[[int], object], num_groups: int,
                 budget: Optional[int] = None):
        self._factory = factory
        self.num_groups = num_groups
        # None: taken from RLIMIT_NOFILE when the first machine is made
        # (only then is `open_files` known).
        self._budget = budget
        self.applied = np.zeros(num_groups, np.int64)
        self.synced = np.zeros(num_groups, np.int64)
        # None until a machine was made; then whether `applied`
        # survives a crash (models/base.py has_durable_snapshot).
        self.durable: Optional[bool] = None
        self._cv = threading.Condition()
        # Every group that was ever used.  # raftlint: guarded-by=_cv
        self._entries: Dict[int, _Entry] = {}
        # Those of them whose handle is open and not being released,
        # least recently used first: a victim is looked for here, so
        # the search passes the few handles in use and never the
        # closed ones.  # raftlint: guarded-by=_cv
        self._open: "OrderedDict[int, _Entry]" = OrderedDict()
        # Handles that hold descriptors: the open ones, those being
        # opened and those being released.
        self._slots = 0
        # Whether handles count against the budget: the first machine
        # made says (it holds descriptors and can be released).
        self._counted = False
        # obs/prof.py's profiler, where the owner has one (runtime/db.py):
        # the stages `sm.miss`, `sm.reopen`, `sm.release` and
        # `compact.file`.
        self.prof = None
        self.opens = 0
        self.closes = 0
        self.evictions = 0
        self.uses = 0
        self.misses = 0
        # The misses' reopens by the arm that did the set-up: one native
        # call (a machine's `reopen` says True) or the module.
        self.native_reopens = 0
        self.python_reopens = 0

    # -- the one way to a state machine ---------------------------------

    @contextlib.contextmanager
    def use(self, group: int):
        """The group's state machine, open, for the length of the
        block; nobody releases it meanwhile."""
        e = self._pin(group)
        try:
            yield e.sm
        finally:
            self._unpin(group, e)

    def _unpin(self, group: int, e: _Entry) -> None:
        with self._cv:
            self._note_applied(group, e.sm)
            e.pins -= 1
            if e.pins == 0:
                self._cv.notify_all()

    def _note_applied(self, group: int, sm) -> None:
        """`applied[group]` follows the machine, upwards only (lock
        held: whoever reads the machine later also writes later)."""
        a = sm.applied_index()
        if a > self.applied[group]:
            self.applied[group] = a

    def applied_index(self, group: int) -> int:
        return int(self.applied[group])

    def seed(self, groups: Iterable[int]) -> None:
        """Read the applied index of groups whose file a former process
        left (resume mode; the caller knows where the files are).
        Until this has run such a group would read 0: nothing replays
        into a group whose log was compacted away, so nothing else
        would open it before its first request."""
        for g in groups:
            with self.use(g):
                pass

    def checkpoint(self, group: int) -> bool:
        """Put the group's applied statements on disk and say so in
        `synced`; False where there was nothing to put there (a release
        did it meanwhile).  The round's way to a file that is not open
        as it stands: closed by a release that could not put it on
        disk, or being opened or released; the group's applies and
        reads wait meanwhile."""
        with self._cv:
            while True:
                e = self._entries.get(group)
                if e is None or not e.busy:
                    break
                self._cv.wait()
            if self.applied[group] <= self.synced[group]:
                return False
        with self.use(group) as sm:
            done = _checkpoint_one(sm)
        with self._cv:
            if done > self.synced[group]:
                self.synced[group] = done
        return done > 0

    def checkpoint_round(self, stop: Callable[[], bool] = lambda: False
                         ) -> int:
        """Put every file with `applied > synced` on disk, until `stop()`
        says so; the files put there.  One round at a time (runtime/db.py
        `_compact_round`)."""
        order = np.flatnonzero(self.applied > self.synced).tolist()
        files = 0
        for at in range(0, len(order), ROUND_BATCH):
            if stop():
                break
            files += self._checkpoint_batch(order[at:at + ROUND_BATCH])
        return files

    def _checkpoint_batch(self, groups: list) -> int:
        """`checkpoint_round`'s ROUND_BATCH groups.  A file a release put
        on disk since the round began is passed over unopened; the open
        ones are pinned as they stand (not moved up the order of use)
        and go in one call; the others go one by one (`checkpoint`)."""
        pinned, rest = [], []
        with self._cv:
            for g in groups:
                if self.applied[g] <= self.synced[g]:
                    continue
                e = self._open.get(g)
                if e is None:
                    rest.append(g)
                else:
                    e.pins += 1
                    pinned.append((g, e))
        took = []
        if pinned:
            sms = [e.sm for _, e in pinned]
            try:
                many = getattr(type(sms[0]), "checkpoint_many", None)
                got = many(sms, ROUND_THREADS) if many is not None \
                    else [(_checkpoint_one(sm), 0.0) for sm in sms]
            finally:
                for g, e in pinned:
                    self._unpin(g, e)
            with self._cv:
                for (g, _), (done, _) in zip(pinned, got):
                    if done > self.synced[g]:
                        self.synced[g] = done
            took = [("compact.file", t) for done, t in got if done]
        for g in rest:
            t0 = time.monotonic()
            if self.checkpoint(g):
                took.append(("compact.file", time.monotonic() - t0))
        if took and self.prof is not None:
            self.prof.stage_many(took)
        return len(took)

    def _pin(self, group: int) -> _Entry:
        t0 = time.monotonic()
        with self._cv:
            self.uses += 1
            while True:
                e = self._entries.get(group)
                if e is None:
                    e = self._entries[group] = _Entry()
                    e.pins = 1
                    break                       # ours to make
                if e.busy:
                    self._cv.wait()
                    continue
                e.pins += 1
                if group in self._open:
                    self._open.move_to_end(group)
                    return e
                e.busy = True
                self.misses += 1
                break                           # ours to reopen
        took = False
        reopen_s, native = None, False     # None: no reopen
        try:
            self._take_slot()
            took = True
            if e.sm is None:
                sm = self._factory(group)
                if self.durable is None:
                    self.durable = bool(
                        getattr(sm, "has_durable_snapshot", False))
                    files = int(getattr(sm, "open_files", 0))
                    self._counted = files > 0 and hasattr(sm, "release")
                    if self._budget is None:
                        self._budget = handle_budget(
                            files if self._counted else 0)
                e.sm = sm
            else:
                t1 = time.monotonic()
                native = e.sm.reopen() is True
                reopen_s = time.monotonic() - t1
        except BaseException:
            with self._cv:
                if took:
                    self._slots -= 1
                e.pins -= 1
                if e.sm is None:
                    del self._entries[group]
                else:
                    e.busy = False
                self._cv.notify_all()
            raise
        with self._cv:
            self._open[group] = e
            e.busy = False
            self.opens += 1
            if reopen_s is not None:
                if native:
                    self.native_reopens += 1
                else:
                    self.python_reopens += 1
            self._note_applied(group, e.sm)
            self._cv.notify_all()
        if reopen_s is not None and self.prof is not None:
            self.prof.stage_many((("sm.miss", time.monotonic() - t0),
                                  ("sm.reopen", reopen_s)))
        return e

    def _take_slot(self) -> None:
        """Release victims (`_victim`) until one more handle fits the
        budget, and take its slot.  Called with the entry to open marked
        busy and the lock NOT held."""
        while True:
            with self._cv:
                if self._budget is None or not self._counted \
                        or self._slots < self._budget:
                    self._slots += 1
                    return
                group = self._victim()
                if group is None:
                    self._cv.wait()     # every open handle is in use
                    continue
                victim = self._open.pop(group)
                victim.busy = True
            t0 = time.monotonic()
            done = 0
            try:
                done = victim.sm.release() or 0
            finally:
                with self._cv:
                    victim.busy = False
                    self._slots -= 1
                    self.closes += 1
                    self.evictions += 1
                    if done > self.synced[group]:
                        self.synced[group] = done
                    self._cv.notify_all()
            if self.prof is not None:
                self.prof.stage("sm.release", time.monotonic() - t0)

    def _victim(self) -> Optional[int]:
        """The least recently used handle nobody holds, or the first
        such one whose file is on disk among the VICTIM_SCAN least
        recently used; None where every open handle is in use (lock
        held)."""
        first = None
        for n, (g, e) in enumerate(self._open.items()):
            if e.pins:
                continue
            if self.applied[g] <= self.synced[g]:
                return g
            if first is None:
                first = g
            if n >= VICTIM_SCAN:
                break
        return first

    # -- what the rest asks of the store --------------------------------

    def open_handles(self) -> int:
        return self._slots

    def close(self) -> None:
        deadline = time.monotonic() + 5.0
        with self._cv:
            # The caller has stopped the threads that apply; a read
            # still inside a SELECT gets a moment to finish.
            while any(e.busy or e.pins for e in self._entries.values()) \
                    and time.monotonic() < deadline:
                self._cv.wait(0.1)
            entries, self._entries = self._entries, {}
            self.closes += len(self._open)
            self._open = OrderedDict()
            self._slots = 0
        for e in entries.values():
            if e.sm is not None:
                e.sm.close()


def _checkpoint_one(sm) -> int:
    """A machine's `checkpoint`, 0 for one that has none."""
    fn = getattr(sm, "checkpoint", None)
    return fn() if fn is not None else 0
