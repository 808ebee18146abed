"""The long-lived node (PR 32): state machines for the groups in use, a
compaction sweep whose cost follows the groups that moved, and quiet
groups that no longer pin the oldest WAL segment.

  (i)   the deployment against the plain reference
        (benchmarks/lib/reference.py, imported as the benchmark's own
        tests do): a YCSB-shaped history on 64 groups of which 8 are
        written, through RaftDB on the fused path with resume on and a
        sweep every 32 applies, a stop mid-history and a restart on the
        same directory;
  (ii)  the store (models/store.py): a budget of 8 handles over 64
        groups under concurrent appliers and readers;
  (iii) the sweep against the function it replaced, which lives on here
        as the oracle (as tests/test_durable_path.py keeps the per-peer
        hard-state save), per WAL layout;
  (iv)  a crash between the re-assert and the unlink;
  (v)   the open-file limit as the entry point asks for it.
"""
import importlib
import json
import os
import random
import sqlite3
import threading
import time

import numpy as np
import pytest

from raftsql_tpu.config import RaftConfig
from raftsql_tpu.models import store as store_mod
from raftsql_tpu.models.sqlite_sm import SQLiteStateMachine
from raftsql_tpu.models.store import StateMachineStore
from raftsql_tpu.runtime.db import RaftDB
from raftsql_tpu.runtime.fused import FusedClusterNode, FusedPipe
from raftsql_tpu.runtime.hostplane import ClusterHostPlane
from raftsql_tpu.storage import fsio
from raftsql_tpu.storage.log import PayloadLog
from raftsql_tpu.storage.wal import WAL, GroupCommitWAL, _segment_paths

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
W = 16                      # the device ring of these tests
FIELDS = 10


def cfg_for(groups, **kw):
    kw.setdefault("log_window", W)
    kw.setdefault("max_entries_per_msg", 4)
    kw.setdefault("election_ticks", 10)
    kw.setdefault("heartbeat_ticks", 1)
    kw.setdefault("tick_interval_s", 0.0)
    return RaftConfig(num_groups=groups, num_peers=3, seed=7, **kw)


def elect(node, max_ticks=400):
    for t in range(max_ticks):
        node.tick()
        if t > 10 and (node._hints >= 0).all():
            return
    raise AssertionError("no full leadership within budget")


# -- (i) the deployment against the plain reference -----------------------

GROUPS, WRITTEN = 64, tuple(range(3, 64, 8))        # 8 of 64
SELECT = "SELECT * FROM usertable ORDER BY ycsb_key"


def ycsb_history(seed, keys_per_group=6, ops=360):
    """[(group, sql)]: YCSB's usertable in the 8 written groups, a few
    rows each, then one-field updates with a skew over the keys."""
    rnd = random.Random(seed)
    cols = ", ".join(f"field{i} TEXT" for i in range(FIELDS))
    out = [(g, f"CREATE TABLE usertable (ycsb_key TEXT PRIMARY KEY, "
               f"{cols})") for g in WRITTEN]
    keys = []
    for g in WRITTEN:
        for k in range(keys_per_group):
            key = f"user{g}x{k}"
            vals = ", ".join(f"'{rnd.getrandbits(64):016x}'"
                             for _ in range(FIELDS))
            out.append((g, f"INSERT INTO usertable VALUES "
                           f"('{key}', {vals})"))
            keys.append((g, key))
    for _ in range(ops):
        g, key = keys[int(len(keys) * rnd.random() ** 2)]
        out.append((g, f"UPDATE usertable SET field{rnd.randrange(FIELDS)}"
                       f" = '{rnd.getrandbits(64):016x}' "
                       f"WHERE ycsb_key = '{key}'"))
    return out


class Recording(SQLiteStateMachine):
    """Notes the log index of every statement it really executes."""
    executed = None         # {group: [index, ...]}, set by the test

    def apply_batch(self, items):
        self.executed.setdefault(self.group, []).extend(
            ix for _q, ix in items if ix > self._applied)
        return super().apply_batch(items)


def deployment(data_dir, executed, steps=1):
    """RaftDB over a fused node as `server.main --fused --resume
    --compact-every 32 --compact-keep 16` builds it, ticking on its own
    thread; small WAL segments so that some close."""
    node = FusedClusterNode(cfg_for(GROUPS, wal_segment_bytes=8192),
                            os.path.join(data_dir, "fused"),
                            group_commit=True, steps=steps)

    def factory(g):
        sm = Recording(os.path.join(data_dir, f"g{g}.db"), resume=True)
        sm.group, sm.executed = g, executed
        return sm

    rdb = RaftDB(factory, FusedPipe(node), num_groups=GROUPS, resume=True,
                 compact_every=32, compact_keep=W,
                 existing=[g for g in range(GROUPS) if os.path.exists(
                     os.path.join(data_dir, f"g{g}.db"))])
    node.start(0.001)
    return node, rdb


def put(rdb, g, sql, deadline=60.0):
    t_end = time.monotonic() + deadline
    while True:
        fut = rdb.propose(sql, g)
        try:
            err = fut.wait(5.0)
        except TimeoutError:
            rdb.abandon(sql, g, fut)
            err = TimeoutError()
        if err is None:
            return
        assert time.monotonic() < t_end, (g, sql, err)
        time.sleep(0.05)


def test_deployment_against_the_plain_reference(tmp_path, monkeypatch):
    monkeypatch.syspath_prepend(os.path.join(REPO, "benchmarks"))
    reference = importlib.import_module("lib.reference")
    ref = reference.Reference()
    history = ycsb_history(20261002)
    half = len(history) // 2
    executed = {}
    node, rdb = deployment(str(tmp_path), executed)
    try:
        for g, sql in history[:half]:
            put(rdb, g, sql)
            ref.apply(g, sql)
        for g in WRITTEN:
            assert rdb.query(SELECT, g) == ref.query(g, SELECT)
        doc = rdb.metrics()
        assert doc["compact"]["sweeps"] >= 3
        assert doc["compact"]["floors_advanced"] > 0
        # Closed segments went, though 56 groups never got a statement
        # (their election no-ops are in the first segment).
        assert doc["wal"]["segments_unlinked"] > 0
        assert not os.path.exists(os.path.join(
            str(tmp_path), "fused", "gc", "wal-0.log"))
        assert 0 < doc["wal"]["disk_bytes"] < doc["wal"]["bytes"]
        # Handles for the groups written, nobody else's.
        assert doc["sm"]["open_handles"] == len(WRITTEN)
        assert doc["sm"]["opens"] == len(WRITTEN)
        assert doc["sm"]["evictions"] == 0
        quiet = [g for g in range(GROUPS) if g not in WRITTEN]
        assert not any(os.path.exists(os.path.join(
            str(tmp_path), f"g{g}.db")) for g in quiet)
    finally:
        rdb.close()
    # The stop, mid-history.  What the files say was applied:
    on_file = {}
    for g in WRITTEN:
        db = sqlite3.connect(os.path.join(str(tmp_path), f"g{g}.db"))
        on_file[g] = db.execute("SELECT v FROM _raft_meta "
                                "WHERE k='applied_index'").fetchone()[0]
        db.close()
    executed.clear()
    node, rdb = deployment(str(tmp_path), executed)
    try:
        # Before any request, and whether or not the replay has reached
        # the group (a group whose log was swept away gets none): the
        # watermark is what the file says, off the file, so a session
        # read after the restart waits for no apply that cannot come.
        for g in WRITTEN:
            assert rdb.watermark(g) >= on_file[g] > 0, g
        # The restart re-executed nothing at or below an applied index
        # (the WAL keeps W entries under it, and those are replayed
        # and skipped), and the floors it found are the sweeps'.
        for g, idxs in executed.items():
            assert all(ix > on_file[g] for ix in idxs), (g, idxs)
        starts = node.plogs[0].starts
        assert (starts[list(WRITTEN)] > 0).all()
        assert (starts[[g for g in range(GROUPS)
                        if g not in WRITTEN]] > 0).all()
        for g in WRITTEN:
            assert rdb.query(SELECT, g) == ref.query(g, SELECT)
        for g, sql in history[half:]:
            put(rdb, g, sql)
            ref.apply(g, sql)
        for g in WRITTEN:
            for mode in ("local", "linear", "follower"):
                assert rdb.query(SELECT, g, mode=mode) \
                    == ref.query(g, SELECT), (g, mode)
    finally:
        rdb.close()
        ref.close()


def test_a_compaction_round_is_seen_only_once_it_runs(tmp_path,
                                                       monkeypatch):
    """`close()` joins the round it finds in `_compactor`, from another
    thread than the one that starts rounds: a thread put there before
    its `start()` cannot be joined ("cannot join thread before it is
    started": where the driver's run of PR 34's tree died, in the test
    above).  So a round is started first and published after."""
    from raftsql_tpu.runtime import db as db_mod
    box, published_early = [], []

    class Watched(threading.Thread):
        def start(self):
            if self.name == "raftdb-compact":
                published_early.append(box[0]._compactor is self)
            super().start()

    monkeypatch.setattr(db_mod.threading, "Thread", Watched)
    node, rdb = deployment(str(tmp_path), {})
    box.append(rdb)
    try:
        for g, sql in ycsb_history(20261004, ops=60):
            put(rdb, g, sql)
    finally:
        rdb.close()
    assert published_early and not any(published_early)


def test_power_loss_after_a_sweep_loses_no_acked_write(tmp_path,
                                                       monkeypatch):
    """A state machine commits without a sync (`synchronous=NORMAL`):
    after a loss of power its file is the last CHECKPOINT, the `-wal`
    tail gone.  A sweep unlinks the raft log, so it may drop only what
    a checkpoint has put on disk: the machine dies after sweeps ran,
    with every unsynced `-wal` lost, and every acknowledged statement
    is still read back."""
    import shutil
    monkeypatch.syspath_prepend(os.path.join(REPO, "benchmarks"))
    reference = importlib.import_module("lib.reference")
    ref = reference.Reference()
    history = ycsb_history(20261003, ops=300)
    live, lost = str(tmp_path / "live"), str(tmp_path / "lost")
    os.mkdir(live)
    node, rdb = deployment(live, {})
    try:
        for g, sql in history:
            put(rdb, g, sql)                    # acknowledged
            ref.apply(g, sql)
        rdb._compactor.join()           # no round in flight
        doc = rdb.metrics()
        assert doc["compact"]["sweeps"] >= 3
        assert doc["wal"]["segments_unlinked"] > 0
        assert doc["stages"]["compact"]["checkpoint"]["n"] >= 3
        applied = rdb.store.applied.copy()
        synced = rdb.store.synced.copy()
        assert (synced <= applied).all()
        # No floor above what is on disk, on any peer.
        starts = np.stack([pl.starts for pl in node.plogs])
        written = list(WRITTEN)
        assert (starts[:, written] <= synced[written][None, :]).all()
        # The power goes: the tick thread stops where it is, nothing is
        # closed (a close would checkpoint).  What the disk holds: the
        # raft WAL as fsynced before each ack, the database files as
        # their last checkpoint left them, no `-wal`, no `-shm`.
        node.stop()
        shutil.copytree(live, lost, ignore=shutil.ignore_patterns(
            "*.db-wal", "*.db-shm"))
    finally:
        rdb.close()
    on_disk = {}
    for g in WRITTEN:
        db = sqlite3.connect(os.path.join(lost, f"g{g}.db"))
        on_disk[g] = db.execute("SELECT v FROM _raft_meta "
                                "WHERE k='applied_index'").fetchone()[0]
        db.close()
        assert on_disk[g] >= synced[g]
    # The test bites: some file did roll back under what was applied
    # (and acknowledged) when the power went.
    assert any(on_disk[g] < applied[g] for g in WRITTEN), (on_disk, applied)
    node, rdb = deployment(lost, {})
    try:
        for g in WRITTEN:
            for mode in ("linear", "follower"):
                assert rdb.query(SELECT, g, mode=mode) \
                    == ref.query(g, SELECT), (g, mode)
    finally:
        rdb.close()
        ref.close()


def test_power_loss_inside_a_served_dispatch_loses_no_acked_write(
        tmp_path, monkeypatch):
    """The same loss of power under the served node's dispatch
    (PIPELINE_STEPS steps a launch, epoch-framed): the machine dies
    after sweeps ran AND between a dispatch's WAL barrier and its
    `EPOCHS` record, so every peer's log ends in a frame that no
    commit record covers.  The restart erases that dispatch on every
    peer (nothing of it was acknowledged) and reads every acknowledged
    statement back."""
    import shutil
    from raftsql_tpu.runtime.fused import (PIPELINE_STEPS,
                                           _read_committed_epoch)
    monkeypatch.syspath_prepend(os.path.join(REPO, "benchmarks"))
    reference = importlib.import_module("lib.reference")
    ref = reference.Reference()
    history = ycsb_history(20261004, ops=300)
    live, lost = str(tmp_path / "live"), str(tmp_path / "lost")
    os.mkdir(live)
    node, rdb = deployment(live, {}, steps=PIPELINE_STEPS)
    try:
        assert node._steps == PIPELINE_STEPS
        for g, sql in history:
            put(rdb, g, sql)                    # acknowledged
            ref.apply(g, sql)
        rdb._compactor.join()           # no round in flight
        ticks = node.metrics.ticks
        doc = rdb.metrics()
        assert doc["compact"]["sweeps"] >= 3
        assert doc["wal"]["segments_unlinked"] > 0
        assert doc["dispatch"]["steps"] >= PIPELINE_STEPS * ticks
        assert doc["dispatch"]["steps"] % PIPELINE_STEPS == 0
        applied = rdb.store.applied.copy()
        committed = node._epoch_no
        assert committed > 0            # the dispatches were framed
        # The power goes inside the next dispatch that writes: its
        # frames reach every peer's WAL and are fsynced, its commit
        # record never reaches EPOCHS.
        died = threading.Event()

        def power_off(no):
            died.no = no
            died.set()
            raise OSError("power lost before the EPOCHS record")
        monkeypatch.setattr(node, "_commit_epoch", power_off)
        in_flight = [rdb.propose(
            f"UPDATE usertable SET field0 = 'never acked' "
            f"WHERE ycsb_key = 'user{g}x0'", g) for g in WRITTEN]
        assert died.wait(30)
        node._thread.join(30)
        assert isinstance(node.error, OSError)
        assert died.no == committed + 1
        for fut in in_flight:           # refused, never acknowledged
            assert fut.wait(10) is not None
        shutil.copytree(live, lost, ignore=shutil.ignore_patterns(
            "*.db-wal", "*.db-shm"))
    finally:
        rdb.close()
    epochs = os.path.join(lost, "fused", "EPOCHS")
    assert _read_committed_epoch(epochs) == committed
    assert any(applied[g] > 0 for g in WRITTEN)
    # The test bites: the disk holds a frame above the commit record.
    probe = str(tmp_path / "probe")
    shutil.copytree(os.path.join(lost, "fused", "gc"), probe)
    assert GroupCommitWAL.repair_epochs(probe, committed)
    node, rdb = deployment(lost, {}, steps=PIPELINE_STEPS)
    try:
        # The frame was on disk and is gone: the boot dropped it.
        assert node._epoch_no == committed
        for g in WRITTEN:
            for mode in ("linear", "follower"):
                assert rdb.query(SELECT, g, mode=mode) \
                    == ref.query(g, SELECT), (g, mode)
        # The node serves on: the next dispatch takes the number the
        # lost one had.
        put(rdb, WRITTEN[0], history[-1][1])
        assert node._epoch_no > committed
    finally:
        rdb.close()
        ref.close()


def test_checkpoint_puts_the_applied_index_on_disk(tmp_path):
    path = str(tmp_path / "g.db")
    sm = SQLiteStateMachine(path, resume=True)
    sm.apply_batch([("CREATE TABLE t (v)", 1),
                    ("INSERT INTO t VALUES ('a')", 2)])

    def without_the_wal_tail():
        """What a power loss leaves: the database file alone."""
        import shutil
        copy = str(tmp_path / "copy.db")
        shutil.copyfile(path, copy)
        db = sqlite3.connect(copy)
        try:
            return db.execute("SELECT v FROM _raft_meta").fetchone()
        except sqlite3.Error:
            return None
        finally:
            db.close()

    assert without_the_wal_tail() in (None, (0,))   # committed, not synced
    assert sm.checkpoint() == 2
    assert without_the_wal_tail() == (2,)
    sm.apply_batch([("INSERT INTO t VALUES ('b')", 3)])
    assert without_the_wal_tail() == (2,)
    assert sm.checkpoint() == 3
    assert without_the_wal_tail() == (3,)
    sm.close()
    # Nothing to put on disk where no snapshot is kept.
    assert SQLiteStateMachine(str(tmp_path / "p.db")).checkpoint() == 0
    assert SQLiteStateMachine(":memory:").checkpoint() == 0


# -- (ii) the store --------------------------------------------------------

def fds():
    return len(os.listdir("/proc/self/fd"))


@pytest.mark.parametrize("resume,rounds", [(True, False), (False, False),
                                           (True, True)],
                         ids=["resume", "parity", "resume-with-rounds"])
def test_store_holds_a_budget_under_appliers_and_readers(tmp_path, resume,
                                                         rounds):
    """Appliers and readers (and, in the third case, compaction rounds
    under a budget of 4) never serve from a handle being closed: a
    statement or a query on a closed connection raises."""
    G, BUDGET, ROUNDS = 64, (4 if rounds else 8), 12
    per_handle = 3 if resume else 1

    def make(tag, budget):
        d = tmp_path / tag
        d.mkdir()
        return StateMachineStore(
            lambda g: SQLiteStateMachine(str(d / f"g{g}.db"),
                                         resume=resume),
            G, budget=budget)

    small, roomy = make("small", BUDGET), make("roomy", None)
    base = fds()
    peak = [0]
    stop = threading.Event()
    errors = []

    def statements(g, r):
        if r == 0:
            return [("CREATE TABLE t (k INTEGER PRIMARY KEY, v)", 1)]
        return [(f"INSERT INTO t VALUES ({r}, 'g{g}r{r}')", r + 1)]

    def applier(groups):
        try:
            for r in range(ROUNDS):
                for g in groups:
                    items = statements(g, r)
                    with small.use(g) as sm:
                        assert sm.apply_batch(items) == [None]
                    peak[0] = max(peak[0], fds())
        except Exception as e:                          # noqa: BLE001
            errors.append(e)

    def reader(seed):
        rnd = random.Random(seed)
        try:
            while not stop.is_set():
                g = rnd.randrange(G)
                if small.applied_index(g) < 1:
                    continue
                with small.use(g) as sm:
                    rows = sm.query("SELECT count(*) FROM t")
                assert rows.startswith("|"), rows
                peak[0] = max(peak[0], fds())
        except Exception as e:                          # noqa: BLE001
            errors.append(e)

    checkpointed = []

    def compactor():
        try:
            while not stop.wait(0.002):         # a round, then the next
                checkpointed.append(small.checkpoint_round(stop.is_set))
                # Never above what was applied: the sweep trusts it.
                assert (small.synced <= small.applied).all()
        except Exception as e:                          # noqa: BLE001
            errors.append(e)

    appliers = [threading.Thread(target=applier,
                                 args=(list(range(i, G, 4)),))
                for i in range(4)]
    readers = [threading.Thread(target=reader, args=(s,))
               for s in range(4)]
    others = readers + ([threading.Thread(target=compactor)]
                        if rounds else [])
    for t in appliers + others:
        t.start()
    for t in appliers:
        t.join()
    stop.set()
    for t in others:
        t.join()
    assert not errors, errors
    if rounds:
        assert sum(checkpointed) > 0
        # What is on disk is on disk: a release or a round put every
        # file there, and a last round finds nothing left to open.
        small.checkpoint_round()
        assert (small.synced[:G] == small.applied[:G]).all()
        opens = small.opens
        assert small.checkpoint_round() == 0 and small.opens == opens
    # Never more descriptors than the budget's, but for what a thread
    # holds in passing: the listing of /proc/self/fd that counts them,
    # and SQLite's own moment with a directory as it makes or drops a
    # journal (two a thread, eight threads).
    assert peak[0] - base <= BUDGET * per_handle + 16, (peak[0], base)
    # The same statements to a store that never closes a handle.
    for r in range(ROUNDS):
        for g in range(G):
            with roomy.use(g) as sm:
                assert sm.apply_batch(statements(g, r)) == [None]
    roomy_fds = per_handle * roomy.open_handles()
    assert roomy.open_handles() == G and roomy.evictions == 0
    assert small.open_handles() <= BUDGET
    assert small.evictions > G          # every group came and went
    assert small.opens - small.closes == small.open_handles()
    # A closed group's applied index is read off the array: no open.
    closed = [g for g in small._entries if g not in small._open]
    assert len(closed) >= G - BUDGET
    opens = small.opens
    for g in closed:
        assert small.applied_index(g) == ROUNDS
    assert small.opens == opens and small.open_handles() <= BUDGET
    # Evicted-and-reopened groups answer as never-closed ones.
    for g in range(G):
        with small.use(g) as a, roomy.use(g) as b:
            assert a.query("SELECT * FROM t ORDER BY k") \
                == b.query("SELECT * FROM t ORDER BY k")
            assert a.applied_index() == b.applied_index() == ROUNDS
    assert fds() - base <= BUDGET * per_handle + roomy_fds + 4
    small.close()
    roomy.close()
    assert fds() <= base + 2


class Slot:
    """A machine that holds one descriptor's worth and nothing else."""
    open_files = 1
    has_durable_snapshot = False
    fail_release = fail_reopen = False

    def __init__(self, group):
        self.group, self.index, self.is_open = group, 0, True

    def applied_index(self):
        return self.index

    def release(self):
        if Slot.fail_release:
            raise OSError("scripted: release")
        self.is_open = False

    def reopen(self):
        if Slot.fail_reopen:
            raise OSError("scripted: reopen")
        self.is_open = True

    def close(self):
        self.is_open = False


def test_store_at_scale_counts_its_slots_and_looks_at_open_handles_only(
        monkeypatch):
    """G far above the budget, the regime the store is for: a victim is
    looked for among the open handles (at most the budget), never among
    the closed ones; a fault while making room or opening leaves the
    count of slots what it was; `applied` never goes down."""
    G, BUDGET = 4096, 64
    store = StateMachineStore(Slot, G, budget=BUDGET)
    for sweep in range(3):
        for g in range(G):
            with store.use(g) as sm:
                assert sm.is_open and sm.group == g
                sm.index += 1
            assert len(store._open) <= BUDGET
    assert len(store._entries) == G and len(store._open) == BUDGET
    assert store.open_handles() == BUDGET
    assert store.evictions == 3 * G - BUDGET
    assert store.uses == 3 * G
    assert store.misses == 2 * G        # every use after the first pass
    assert (store.applied == 3).all()
    assert sum(e.sm.is_open for e in store._entries.values()) == BUDGET
    # A release that raises: the victim's slot is given back all the
    # same (its handle is in an unknown state, not counted twice), and
    # the opener, which had taken none yet, takes none with it.
    closed = next(g for g in range(G) if g not in store._open)
    monkeypatch.setattr(Slot, "fail_release", True)
    with pytest.raises(OSError, match="release"):
        with store.use(closed):
            pass
    monkeypatch.setattr(Slot, "fail_release", False)
    assert store.open_handles() == len(store._open) == BUDGET - 1
    # A reopen that raises gives its slot back.
    monkeypatch.setattr(Slot, "fail_reopen", True)
    with pytest.raises(OSError, match="reopen"):
        with store.use(closed):
            pass
    monkeypatch.setattr(Slot, "fail_reopen", False)
    assert store.open_handles() == len(store._open) == BUDGET - 1
    # A factory that raises leaves no entry and no slot.
    fresh = StateMachineStore(lambda g: 1 // 0, 4, budget=2)
    with pytest.raises(ZeroDivisionError):
        with fresh.use(1):
            pass
    assert fresh.open_handles() == 0 and not fresh._entries
    # The group is usable again, and the budget still holds.
    for g in (closed, closed + 1, closed + 2):
        with store.use(g) as sm:
            assert sm.is_open
    assert store.open_handles() == len(store._open) <= BUDGET
    # `applied` follows the machine upwards only: a user that leaves
    # after a newer index was noted cannot put an older one back.
    with store.use(7) as sm:
        sm.index = 2
    assert store.applied_index(7) == 3
    store.close()
    assert store.open_handles() == 0


def test_release_checkpoints_and_reopen_reads_the_meta_back(tmp_path):
    path = str(tmp_path / "g.db")
    sm = SQLiteStateMachine(path, resume=True)
    assert sm.open_files == 3
    sm.apply_batch([("CREATE TABLE t (v)", 1),
                    ("INSERT INTO t VALUES ('a')", 2)])
    assert os.path.getsize(path + "-wal") > 0
    sm.release()
    # The last connection closed: SQLite checkpointed and dropped the
    # journal; what the file says is what the machine remembers.
    assert not os.path.exists(path + "-wal") \
        or os.path.getsize(path + "-wal") == 0
    db = sqlite3.connect(path)
    assert db.execute("SELECT v FROM _raft_meta").fetchone()[0] \
        == sm.applied_index() == 2
    db.execute("UPDATE _raft_meta SET v = 1")
    db.commit()
    db.close()
    with pytest.raises(RuntimeError, match="applied index 1 on file"):
        sm.reopen()
    sm.release()
    db = sqlite3.connect(path)
    db.execute("UPDATE _raft_meta SET v = 2")
    db.commit()
    db.close()
    sm.reopen()
    assert sm.query("SELECT * FROM t") == "|a|\n"
    # Parity mode: a release keeps the file, a reopen does not delete.
    ppath = str(tmp_path / "p.db")
    pm = SQLiteStateMachine(ppath)
    assert pm.open_files == 1
    pm.apply_batch([("CREATE TABLE t (v)", 1)])
    pm.release()
    pm.reopen()
    assert pm.query("SELECT count(*) FROM t") == "|0|\n"
    assert pm.applied_index() == 1
    assert SQLiteStateMachine(":memory:").open_files == 0


def test_budget_comes_from_the_open_file_limit(monkeypatch):
    import resource
    monkeypatch.setattr(resource, "getrlimit", lambda _r: (20000, 20000))
    assert store_mod.handle_budget(3) == (20000 - 512) // 3     # 6,496
    assert store_mod.handle_budget(1) == 20000 - 512
    assert store_mod.handle_budget(0) > 10 ** 9     # nothing to run out of
    monkeypatch.setattr(resource, "getrlimit", lambda _r: (600, 600))
    assert store_mod.handle_budget(3) == store_mod.MIN_HANDLES
    monkeypatch.setattr(
        resource, "getrlimit",
        lambda _r: (resource.RLIM_INFINITY, resource.RLIM_INFINITY))
    assert store_mod.handle_budget(3) > 10 ** 9


# -- (v) the entry point's limit ------------------------------------------

def test_nofile_limit_fits_the_deployment_and_refuses_what_cannot(
        monkeypatch):
    import resource

    from raftsql_tpu.server import main as server_main
    set_to = []
    monkeypatch.setattr(resource, "setrlimit",
                        lambda _r, lim: set_to.append(lim))
    # The benchmark host: hard limit 20,000; the runner left 12,176.
    monkeypatch.setattr(resource, "getrlimit", lambda _r: (12176, 20000))
    server_main._raise_nofile_limit(resume=True)      # G=10,000 fits
    assert set_to == [(20000, 20000)]
    # ... and the store then has room for every table group.
    monkeypatch.setattr(resource, "getrlimit", lambda _r: (20000, 20000))
    assert store_mod.handle_budget(3) >= 256
    # What cannot fit is refused with a sentence, not in sqlite3.connect.
    monkeypatch.setattr(resource, "getrlimit", lambda _r: (600, 600))
    with pytest.raises(SystemExit) as e:
        server_main._raise_nofile_limit(resume=True)
    assert "RLIMIT_NOFILE's hard limit is 600" in str(e.value)
    assert "one SQLite database per group" not in str(e.value)
    server_main._raise_nofile_limit(resume=False)     # 576 <= 600


# -- (iii) the sweep against the function it replaced ----------------------

def parent_compact(self, applied=None, keep=1024):
    """ClusterHostPlane.compact as it stood before PR 32, statement for
    statement: the oracle."""
    keep = max(keep, self.cfg.log_window)
    G = self.cfg.num_groups
    any_changed = False
    for p in range(self.cfg.num_peers):
        plog = self.plogs[p]
        floors = {}
        changed = False
        for g in range(G):
            floor = int(self._applied[p][g]) - keep
            if applied is not None:
                floor = min(floor, applied.get(g, 0) - keep)
            if floor > plog.start(g):
                plog.compact(g, floor, plog.term_of(g, floor))
                changed = True
            s = plog.start(g)
            if s > 0:
                floors[g] = (s, plog.term_of(g, s))
        if changed:
            # The parent handed the WAL a dict of all G hard states
            # (and, under group commit, merged the peers' dicts in the
            # shared log); the WAL now asks for those it needs.
            if self._gcwal is not None:
                def hard(names):
                    ids = np.asarray(names, np.int64)
                    rows = self._hard[ids // G, ids % G]
                    return rows[:, 0], rows[:, 1], rows[:, 2]
                self._gcwal.compact(
                    {p * G + g: v for g, v in floors.items()}, hard)
            else:
                def hard(names, hp=self._hard[p]):
                    rows = hp[np.asarray(names, np.int64)]
                    return rows[:, 0], rows[:, 1], rows[:, 2]
                self.wals[p].compact(floors, hard)
            any_changed = True
    return any_changed


def drain(node, applied):
    """Take peer 0's stream as the apply plane would; `applied` follows
    the highest index delivered per group."""
    q = node.commit_q(0)
    while not q.empty():
        item = q.get_nowait()
        if item is None:
            continue
        for (g, base, datas) in (item[1] if len(item) == 2
                                 else [item[1:]]):
            applied[g] = max(applied[g], base + len(datas))


def wal_state(node):
    """What a restart would find, per peer: every group's floor, floor
    term, entries and hard state; and the segment files that exist."""
    out = []
    if node._gcwal is not None:
        flat = GroupCommitWAL.replay_flat(node._gc_dir)
        dirs = [node._gc_dir]
    else:
        flat = {}
        for p, d in enumerate(node.dirs):
            for g, gl in WAL.replay(d).items():
                flat[p * node.cfg.num_groups + g] = gl
        dirs = node.dirs
    for fg in sorted(flat):
        gl = flat[fg]
        out.append((fg, gl.start, gl.start_term, gl.entries,
                    (gl.hard.term, gl.hard.vote, gl.hard.commit)))
    return out, [[os.path.basename(p) for _, p in _segment_paths(d)]
                 for d in dirs]


@pytest.mark.parametrize("group_commit", [False, True],
                         ids=["per-peer", "group-commit"])
def test_sweep_equals_the_parent_where_the_keep_rule_decides(
        tmp_path, monkeypatch, group_commit):
    """Two nodes, one seed, one history; one compacts with the parent's
    function, the other with the sweep (its rule for what every peer
    holds, which the parent has not, taken out): same floors after
    every sweep, same markers and hard states on replay, same segments
    unlinked."""
    G = 6
    keep_rule = ClusterHostPlane._sweep_floors

    def keep_rule_alone(*a):
        floors, last = keep_rule(*a)
        return floors, np.zeros_like(last)

    nodes, applied = [], []
    for tag in ("parent", "sweep"):
        nodes.append(FusedClusterNode(
            cfg_for(G, wal_segment_bytes=2048), str(tmp_path / tag),
            seed=3, group_commit=group_commit))
        applied.append(np.zeros(G, np.int64))
        elect(nodes[-1])
    monkeypatch.setattr(ClusterHostPlane, "_sweep_floors",
                        staticmethod(keep_rule_alone))
    rnd = random.Random(5)
    unlinked = [0, 0]
    for round_no in range(14):
        batch = [(rnd.randrange(G),
                  [b"SET k%d v%d" % (round_no, i)
                   for i in range(rnd.randrange(1, 9))])
                 for _ in range(6)]
        for k, node in enumerate(nodes):
            for g, payloads in batch:
                node.propose_many(g, payloads)
            for _ in range(5):
                node.tick()
            node.publish_flush()
            drain(node, applied[k])
        assert (applied[0] == applied[1]).all()
        # The state machines lag the stream by a seeded amount.
        sm = np.maximum(applied[0] - rnd.randrange(0, 6), 0)
        before = [len(s) for s in wal_state(nodes[0])[1]], \
            [len(s) for s in wal_state(nodes[1])[1]]
        parent_compact(nodes[0], dict(enumerate(sm.tolist())), keep=W)
        nodes[1].compact(sm, keep=W)
        for p in range(3):
            assert (nodes[0].plogs[p].starts
                    == nodes[1].plogs[p].starts).all(), (round_no, p)
            assert nodes[0].plogs[p]._start_term \
                == nodes[1].plogs[p]._start_term
        sa, sb = wal_state(nodes[0]), wal_state(nodes[1])
        assert sa[0] == sb[0], round_no
        if not group_commit:
            # One log a peer: the same records at the same barriers
            # (the sweep writes a record type at a time where the
            # parent went group by group), so the same segments exist
            # and each is as long.
            assert sa[1] == sb[1], round_no
            for da, db in zip(nodes[0].dirs, nodes[1].dirs):
                for (_, pa), (_, pb) in zip(_segment_paths(da),
                                            _segment_paths(db)):
                    assert os.path.getsize(pa) == os.path.getsize(pb), \
                        (round_no, pa)
        for k in (0, 1):
            unlinked[k] += sum(before[k]) - sum(
                len(s) for s in (sa, sb)[k][1])
    # Segments closed and went on both; one shared log takes the three
    # peers' floors in one call where the parent made three, so its
    # segments can end a record or two apart.
    # (Every group has traffic here: under the parent's rule one quiet
    # group would pin the first segment for good.)
    assert (nodes[1].plogs[0].starts > 0).all()
    for k, node in enumerate(nodes):
        wals = [node._gcwal.base] if group_commit else node.wals
        assert sum(w.segments_unlinked for w in wals) > 0
    for node in nodes:
        node.stop()


def test_keep_rule_floors_are_the_parents_arithmetic():
    rnd = np.random.default_rng(11)
    P, G, keep = 3, 500, 16
    commit = rnd.integers(0, 200, (P, G))
    pub = np.maximum(commit - rnd.integers(0, 3, (P, G)), 0)
    lens = commit + rnd.integers(0, 4, (P, G))
    starts = np.maximum(pub - rnd.integers(10, 60, (P, G)), 0)
    applied = np.maximum(pub[0] - rnd.integers(0, 30, G), 0)
    floors, last = ClusterHostPlane._sweep_floors(
        pub, starts, lens, commit, applied, None, keep)
    for p in range(P):
        for g in range(G):
            assert floors[p, g] == min(int(pub[p, g]),
                                       int(applied[g])) - keep
    # What every peer holds: in every log, committed and published on
    # every peer, covered by the state machine.
    want = np.array([
        min(min(int(lens[p, g]), int(commit[p, g]), int(pub[p, g]))
            for p in range(P)) for g in range(G)])
    assert (last == np.minimum(want, applied)).all()
    covered = applied + rnd.integers(0, 3, G)
    _, held = ClusterHostPlane._sweep_floors(
        pub, starts, lens, commit, applied, covered, keep)
    assert (held == np.minimum(want, covered)).all()
    # No state machine gates (the chaos runners, the soak): the cursor.
    ungated, _ = ClusterHostPlane._sweep_floors(
        pub, starts, lens, commit, None, None, keep)
    assert (ungated == pub - keep).all()


def scripted_node(tmp_path, G, moved):
    """A node nobody ticked, with `moved` groups holding 40 committed,
    published entries on every peer (WAL and payload log)."""
    node = FusedClusterNode(cfg_for(G), str(tmp_path / f"g{G}"), seed=3,
                            group_commit=True)
    for p in range(3):
        for g in moved:
            datas = [b"x%d" % i for i in range(40)]
            node.wals[p].append_ranges([g], [1], [40], [1], datas)
            node.plogs[p].put(g, 1, datas, [1] * 40)
            node._hard[p, g] = (1, 0, 40)
            node._applied[p, g] = 40
        node.wals[p].sync()
    return node


def test_sweep_cost_follows_the_groups_that_moved(tmp_path, monkeypatch):
    """No walk over G: the payload logs' per-group accessors and the
    store's applied_index are called as often at G=4,096 as at G=256."""
    calls = {}

    def counted(cls, name):
        fn = getattr(cls, name)

        def wrapper(self, *a, **k):
            calls[name] = calls.get(name, 0) + 1
            return fn(self, *a, **k)
        monkeypatch.setattr(cls, name, wrapper)

    for name in ("start", "term_of", "length", "compact"):
        counted(PayloadLog, name)
    counted(StateMachineStore, "applied_index")
    seen = []
    for G in (256, 4096):
        moved = (5, G // 2, G - 1)
        node = scripted_node(tmp_path, G, moved)
        store = StateMachineStore(lambda g: None, G)
        store.applied[list(moved)] = 38
        calls.clear()
        assert node.compact(store.applied, keep=W)
        seen.append(dict(calls))
        for p in range(3):
            # Every peer holds all 40 and the state machine covers 38
            # of them: 38, where `keep` alone would say 22.
            assert node.plogs[p].starts[list(moved)].tolist() == [38] * 3
            assert int(node.plogs[p].starts.sum()) == 114
        node.stop()
    assert seen[0] == seen[1], seen
    assert seen[0].get("applied_index", 0) == 0
    assert sum(seen[0].values()) <= 16, seen


# -- quiet groups, and (iv) the crash before the unlink --------------------

def loaded_node(data_dir, group_commit=True, rounds=10):
    """Eight groups, two of them written (0 and 1), every commit
    published and taken; small segments."""
    node = FusedClusterNode(cfg_for(8, wal_segment_bytes=1024), data_dir,
                            seed=3, group_commit=group_commit)
    elect(node)
    applied = np.zeros(8, np.int64)
    for r in range(rounds):
        for g in (0, 1):
            node.propose_many(g, [b"SET r%d i%d" % (r, i)
                                  for i in range(6)])
        for _ in range(6):
            node.tick()
    for _ in range(4):
        node.tick()
    node.publish_flush()
    drain(node, applied)
    return node, applied


def in_memory(node):
    return [(p, g, node.plogs[p].start(g), node.plogs[p].length(g),
             node.plogs[p].slice_columns(
                 g, node.plogs[p].start(g) + 1,
                 node.plogs[p].length(g) - node.plogs[p].start(g)),
             tuple(node._hard[p, g].tolist()))
            for p in range(3) for g in range(8)]


@pytest.mark.parametrize("group_commit", [False, True],
                         ids=["per-peer", "group-commit"])
def test_quiet_groups_stop_pinning_segments(tmp_path, group_commit):
    node, applied = loaded_node(str(tmp_path / "d"), group_commit)
    segs = sum(len(s) for s in wal_state(node)[1])
    assert segs > 3 * (1 if group_commit else 3)
    # The parent's rule: six groups hold one no-op each, far under
    # `keep`: no floor for them, and the first segment holds them all.
    assert (applied[2:] == 1).all()
    assert node.compact(applied, keep=W)
    wals = [node._gcwal.base] if group_commit else node.wals
    assert sum(w.segments_unlinked for w in wals) > 0
    starts = np.stack([pl.starts for pl in node.plogs])
    # Every peer holds every group's whole log: its floor is its last
    # index.
    assert (starts == applied[None, :]).all(), starts
    assert all(w.segments_pinned == 0 for w in wals)
    assert sum(w.disk_bytes() for w in wals) == sum(
        os.path.getsize(p) for d in
        ([node._gc_dir] if group_commit else node.dirs)
        for _, p in _segment_paths(d))
    # The node goes on: writes to a compacted group commit and publish.
    held = in_memory(node)
    node.propose_many(2, [b"SET late 1"])
    for _ in range(8):
        node.tick()
    node.publish_flush()
    drain(node, applied)
    assert applied[2] == 2
    node.stop()
    # A restart finds every group at its floor, above it what was
    # written since, and delivers each floor to the apply plane.
    again = FusedClusterNode(cfg_for(8, wal_segment_bytes=1024),
                             str(tmp_path / "d"), seed=3,
                             group_commit=group_commit)
    try:
        for (p, g, start, length, cols, hard) in held:
            assert again.plogs[p].start(g) == start
            if g != 2:
                assert again.plogs[p].length(g) == length
                assert tuple(again._hard[p, g].tolist()) == hard
        assert again.plogs[0].length(2) == 2
        replayed = np.zeros(8, np.int64)
        drain(again, replayed)
        assert (replayed == applied).all(), replayed
        elect(again)
        again.propose_many(5, [b"SET after restart"])
        for _ in range(8):
            again.tick()
        again.publish_flush()
        drain(again, replayed)
        assert replayed[5] >= 3         # floor 1, a new no-op, the write
    finally:
        again.stop()


def test_a_lagging_peer_or_state_machine_holds_the_floor_down(tmp_path):
    node, applied = loaded_node(str(tmp_path / "d"))
    # Peer 2 has not published group 3's last index (a cursor a moment
    # old), and the state machine has not covered group 4's.
    node._applied[2, 3] -= 1
    covered = applied.copy()
    covered[4] = 0
    node.compact(applied, keep=W, covered=covered)
    starts = np.stack([pl.starts for pl in node.plogs])
    assert (starts[:, 3] == 0).all() and (starts[:, 4] == 0).all()
    assert (starts[:, 5] == 1).all()
    node.stop()


def test_a_batch_resent_below_the_floor_is_trimmed(tmp_path):
    """The one thing that can still name an index at or below a floor
    set to what every peer holds: a batch re-sent before its ack was seen.
    The receiver holds it; the mirror reads only what lies above the
    source's floor, and stops where the receiver does NOT hold it."""
    from raftsql_tpu.runtime.hostplane import _C
    node, applied = loaded_node(str(tmp_path / "d"))
    node.compact(applied, keep=W)
    g, last = 0, int(applied[0])
    assert node.plogs[1].start(g) == last
    leader = int(node._hints[g])
    dest = (leader + 1) % 3
    pinfo = np.zeros((3, 8, len(_C)), np.int32)
    pinfo[:, :, _C["term"]] = node._hard[:, :, 0]
    pinfo[:, :, _C["voted_for"]] = node._hard[:, :, 1]
    pinfo[:, :, _C["commit"]] = node._hard[:, :, 2]
    pinfo[:, :, _C["app_from"]] = -1
    row = pinfo[dest, g]
    row[_C["app_from"]], row[_C["app_start"]] = leader, last - 2
    row[_C["app_n"]], row[_C["new_log_len"]] = 3, last
    staged = [([], [], [], [], []) for _ in range(3)]
    wrote = node.wals[dest]._owner.base.written()[0]
    node._durable_phases(pinfo[None], [staged])
    assert node.plogs[dest].length(g) == last
    assert node.plogs[dest].start(g) == last
    # Nothing of the re-sent batch was written again.
    assert node.wals[dest]._owner.base.written()[0] == wrote
    # A receiver that does not hold the entries is a fault.
    node.plogs[dest].lengths[g] = 0
    node.plogs[dest]._start[g] = 0
    with pytest.raises(RuntimeError, match="below the source's floor"):
        node._durable_phases(pinfo[None], [staged])
    node.error = RuntimeError("scripted")       # stop() must not flush
    node.stop()


@pytest.mark.parametrize("group_commit", [False, True],
                         ids=["per-peer", "group-commit"])
def test_crash_between_reassert_and_unlink_replays_the_same(
        tmp_path, group_commit):
    from raftsql_tpu.chaos.scenarios import hard_crash_fused
    inj = fsio.StorageFaultInjector()
    with fsio.installed(inj):
        # The control: the same history, the sweep runs to its end.
        ctl, applied = loaded_node(str(tmp_path / "ctl"), group_commit)
        ctl.compact(applied, keep=W)
        want = in_memory(ctl)
        ctl.stop()
        node, applied = loaded_node(str(tmp_path / "d"), group_commit)
        names = wal_state(node)[1]
        inj.crash_unlink = "wal-"
        with pytest.raises(fsio.CrashPointError):
            node.compact(applied, keep=W)
        # Re-asserted and fsynced, nothing unlinked yet: every segment
        # is still there, and more records than before.
        assert [len(s) for s in wal_state(node)[1]] \
            >= [len(s) for s in names]
        assert wal_state(node)[1][0][0] == "wal-0.log"
        hard_crash_fused(node)
        again = FusedClusterNode(cfg_for(8, wal_segment_bytes=1024),
                                 str(tmp_path / "d"), seed=3,
                                 group_commit=group_commit)
        try:
            # The same logs and hard states.  (One log a peer: the
            # crash came in peer 0's sweep, before the other peers'
            # markers were written; they come back whole, which is the
            # same log with a lower floor.)
            for (p, g, start, length, cols, hard) in want:
                plog = again.plogs[p]
                assert plog.start(g) in (start, 0), (p, g)
                assert group_commit or p > 0 or plog.start(g) == start
                assert plog.length(g) == length
                assert plog.slice_columns(g, start + 1,
                                          length - start) == cols
                assert tuple(again._hard[p, g].tolist()) == hard
            if group_commit:
                assert in_memory(again) == want
            # The next sweep unlinks what the crash left.
            again.compact(applied, keep=W)
            assert not os.path.exists(os.path.join(
                again._gc_dir if group_commit else again.dirs[0],
                "wal-0.log"))
        finally:
            again.stop()


# -- the benchmark's readers (benchmarks/layers/COMPACT.md) ----------------

def scrape(k, sweeps=True, store=True, prof=True):
    doc = {"ticks": 100 * k}
    if prof:
        doc["stages"] = {"compact": {
            "sweep": {"total_ms": 12.0 * k if sweeps else 0.0,
                      "n": k if sweeps else 0, "max_ms": 30.0},
            "checkpoint": {"total_ms": 250.0 * k if sweeps else 0.0,
                           "n": k if sweeps else 0, "max_ms": 400.0}}}
        doc["compact"] = {"sweeps": k if sweeps else 0,
                          "floors_advanced": 90 * k if sweeps else 0}
        doc["wal"] = {"segments_unlinked": 3 * k if sweeps else 0,
                      "segments_pinned": 5 if sweeps else 0,
                      "disk_bytes": 48 * 2 ** 20, "bytes": 10 ** 9}
        if store:
            doc["sm"] = {"opens": 256, "closes": 0, "evictions": 0,
                         "open_handles": 256}
    return {"t": 9.0 * k, "engine": doc, "workers": [doc]}


@pytest.mark.parametrize("name,want,idle", [
    ("compact_sweep_ms", 12.0, None),
    ("compact_checkpoint_ms", 250.0, None),
    ("compact_floors_per_sweep", 90.0, None),
    ("wal_segments_unlinked_per_sweep", 3.0, None),
    ("wal_segments_pinned", 5, None),
    ("wal_disk_mb", 48.0, 48.0),
    ("sm_open_handles", 256, 256),
    ("sm_evictions", 0, 0),
])
def test_compaction_readers_on_a_pair_of_scrapes(monkeypatch, name, want,
                                                 idle):
    monkeypatch.syspath_prepend(os.path.join(REPO, "benchmarks"))
    reader = importlib.import_module("layers." + name)
    before, after = scrape(2), scrape(6)
    assert reader.read(before, after, {}, None) == pytest.approx(want)
    # No sweep in the window (every cell but the new one; or a window
    # between two sweeps): the per-sweep readers are silent.
    q0, q1 = scrape(2, sweeps=False), scrape(6, sweeps=False)
    got = reader.read(q0, q1, {}, None)
    assert got == (None if idle is None else pytest.approx(idle))
    # A program without the counters (the parent commit).
    old = [scrape(k, prof=False) for k in (2, 6)]
    assert reader.read(old[0], old[1], {}, None) is None
    if name.startswith("sm_"):
        bare = [scrape(k, store=False) for k in (2, 6)]
        assert reader.read(bare[0], bare[1], {}, None) is None


def store_scrape(k, has=True):
    """The engine's document after k rounds of the store and the
    compaction round; `has=False` is a program without their counters."""
    doc = {"ticks": 100 * k,
           "stages": {"compact": {"checkpoint": {
               "total_ms": 900.0 * k, "n": k, "max_ms": 1000.0}}},
           "compact": {"sweeps": k, "floors_advanced": 90 * k},
           "sm": {"opens": 300 * k, "closes": 280 * k,
                  "evictions": 280 * k, "open_handles": 6496}}
    if has:
        doc["stages"]["compact"]["file"] = {
            "total_ms": 4000.0 * k, "n": 250 * k, "max_ms": 90.0}
        doc["stages"]["sm"] = {
            "miss": {"total_ms": 600.0 * k, "n": 300 * k, "max_ms": 40.0},
            "release": {"total_ms": 5600.0 * k, "n": 280 * k,
                        "max_ms": 60.0}}
        doc["compact"].update(rounds=k, files=250 * k)
        doc["sm"].update(uses=1000 * k, misses=300 * k)
    return {"t": 9.0 * k, "engine": doc, "workers": [doc]}


@pytest.mark.parametrize("name,want", [
    ("sm_miss_pct", 30.0),
    ("sm_miss_ms", 2.0),
    ("sm_release_ms", 20.0),
    ("compact_files_per_round", 250.0),
    ("compact_file_ms", 16.0),
])
def test_store_and_round_readers_on_a_pair_of_scrapes(monkeypatch, name,
                                                      want):
    monkeypatch.syspath_prepend(os.path.join(REPO, "benchmarks"))
    reader = importlib.import_module("layers." + name)
    before, after = store_scrape(2), store_scrape(6)
    assert reader.read(before, after, {}, None) == pytest.approx(want)
    # Nothing used, missed, released or put on disk in the window.
    assert reader.read(after, after, {}, None) is None
    # A program without the counters (the parent commit): silent.
    old = [store_scrape(k, has=False) for k in (2, 6)]
    assert reader.read(old[0], old[1], {}, None) is None


def test_store_and_round_readers_are_in_the_manifest_for_their_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    new = "kv0-10ksplits-resume"
    for name, cells in (
            ("sm_miss_pct", [new]), ("sm_miss_ms", [new]),
            ("sm_release_ms", [new]),
            ("compact_files_per_round", [new, "ycsb-a-10kgroups-resume"]),
            ("compact_file_ms", [new, "ycsb-a-10kgroups-resume"])):
        m = by_name[name]
        assert m["workloads"] == cells, name
        assert m["moves"] == "write_p50_ms" and \
            m["source"] == "program_counter", name
        assert m["layer"] == by_name["compact_checkpoint_ms"]["layer"]
        assert os.path.exists(os.path.join(
            REPO, "benchmarks", "layers", name + ".py"))


def test_compaction_readers_are_in_the_manifest_for_their_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    cell = "ycsb-a-10kgroups-resume"
    every = [w["name"] for w in manifest["workloads"]]
    assert cell in every        # later cells are appended after it
    for name in ("compact_sweep_ms", "compact_checkpoint_ms"):
        assert by_name[name]["workloads"] == [cell], name
    # The long-lived kv0 cell sweeps too; it reports `setup_s`, not
    # `write_p95_ms`.
    for name in ("compact_floors_per_sweep",
                 "wal_segments_unlinked_per_sweep", "wal_segments_pinned",
                 "wal_disk_mb"):
        assert by_name[name]["workloads"] == [cell, "kv0-10ksplits-resume"], \
            name
    for name in ("sm_open_handles", "sm_evictions"):
        assert by_name[name]["workloads"] == every, name
    for name in ("read_p50_ms", "read_queue_ms", "read_wait_ms",
                 "read_sql_ms", "read_edge_wait_ms",
                 "wal_mirror_rows_per_tick", "wal_mirror_skipped_pct"):
        assert cell in by_name[name]["workloads"], name
    p95 = [m for m in manifest["end_to_end"]
           if m["name"] == "write_p95_ms"][0]
    assert cell in p95["workloads"]
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "multiraft-10k-resume.json")) as f:
        config = json.load(f)
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "multiraft-10k.json")) as f:
        pair = json.load(f)
    assert config["scale"] == pair["scale"]
    assert config["argv"] == pair["argv"] + [
        "--resume", "--compact-every", "1024", "--compact-keep", "256"]
    for k in ("write_ack", "linear_read", "session_read"):
        assert config["guarantees"][k] == pair["guarantees"][k]
    assert {"snapshot", "log_gc"} <= set(config["guarantees"])
