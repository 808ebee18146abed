"""Elastic keyspace (raftsql_tpu/reshard/): router, journal, fork,
coordinator, and the live serving plane.

The reshard plane's whole safety story reduces to three claims, and
this file pins each at the layer where it is decided:

  1. The router never holds truth the logs don't — `fold_records`
     rebuilds (keymap, active-verb) from journal entries in any order
     with duplicates, and a coordinator rebuilt mid-verb either
     resumes forward (copy fence journaled) or aborts cleanly (fence
     missing), never half-applies a flip.
  2. A snapshot fork is a partition — `fork_by_slots` yields two
     standalone SQLite files whose keyed-row union is exactly the
     source and whose intersection is empty, with the meta tables
     (applied floor, journal) carried on BOTH sides.
  3. Consumers fail closed on the mapping epoch — a /kv request
     pinned to a stale epoch is refused with the current mapping
     attached (409), frozen-slot intake is refused up front (503),
     the api client adopts only strictly newer mappings, and an shm
     worker whose cached epoch lags the publisher's falls back to the
     ring path until it revalidates.

The end-to-end test drives a real split and merge through POST
/reshard on a live single-node cluster (both serving planes) and then
re-folds the replicated journal into a FRESH plane to prove the
router state is fully log-derived.
"""
import http.client
import json
import sqlite3
import tempfile
import time

import pytest

from raftsql_tpu.reshard.coordinator import (ReshardCoordinator,
                                             ReshardRefused)
from raftsql_tpu.reshard.fork import fork_state_machine
from raftsql_tpu.reshard.journal import (decode_rdel, decode_record,
                                         encode_rdel, encode_record,
                                         fold_records)
from raftsql_tpu.reshard.keymap import KeyMap, slot_of

TIMEOUT = 30.0


# -- keymap -----------------------------------------------------------------


def test_slot_of_stable_and_bounded():
    assert all(0 <= slot_of(f"k{i}", 16) < 16 for i in range(200))
    assert slot_of("alpha", 16) == slot_of("alpha", 16)
    # The ring spreads keys: no single slot swallows the keyspace.
    slots = {slot_of(f"k{i}", 16) for i in range(200)}
    assert len(slots) > 8


def test_keymap_move_retire_epoch():
    km = KeyMap.initial(2, 8)
    assert km.epoch == 0 and km.slots == [0, 1] * 4
    assert km.live_groups() == [0, 1]
    assert km.move([0, 2], 1) == 1
    assert km.slots_of(0) == [4, 6]
    assert km.slots_of(1) == [0, 1, 2, 3, 5, 7]
    # Retiring a group that still owns slots is refused.
    with pytest.raises(ValueError):
        km.retire(0)
    km.move([4, 6], 1)
    assert km.retire(0) == 3
    assert km.live_groups() == [1] and 0 in km.retired
    # A later move back ONTO the retired group revives it.
    km.move([0], 0)
    assert 0 not in km.retired and km.live_groups() == [0, 1]


def test_keymap_freeze_is_not_a_routing_change():
    km = KeyMap.initial(2, 8)
    km.freeze([3, 5])
    assert km.epoch == 0          # hygiene, not a routing change
    assert km.frozen == {3, 5}
    frozen_key = next(k for k in (f"k{i}" for i in range(100))
                      if km.slot_of(k) == 3)
    assert km.is_frozen(frozen_key)
    km.unfreeze([3])
    assert not km.is_frozen(frozen_key) and km.frozen == {5}


def test_keymap_doc_roundtrip():
    km = KeyMap.initial(3, 16)
    km.move([1, 4, 7], 2)
    km.freeze([9])
    doc = km.to_doc()
    back = KeyMap.from_doc(json.loads(json.dumps(doc)))
    assert back.to_doc() == doc
    assert back.epoch == 1 and back.frozen == {9}


# -- journal ----------------------------------------------------------------


def _rec(vid, step, verb="split", src=0, dst=1, slots=(0, 2), nslots=8):
    return {"id": vid, "verb": verb, "step": step, "src": src,
            "dst": dst, "slots": sorted(slots), "nslots": nslots}


def test_record_encode_decode():
    rec = _rec(3, "copied")
    assert decode_record(encode_record(rec)) == rec
    assert decode_record(encode_record(rec).encode()) == rec
    for junk in ("", "RJ!not json", "INSERT INTO kv", b"\xff\xfe", None):
        assert decode_record(junk) is None
    rd = decode_rdel(encode_rdel([2, 0], 8, 5))
    assert rd == {"id": 5, "slots": [0, 2], "nslots": 8}
    assert decode_rdel("RD!{bad") is None


def test_fold_any_order_with_duplicates():
    """The journal fold must collapse re-proposed duplicates and sort
    by verb id: the coordinator re-journals idempotently whenever a
    proposal may have been lost at a deposed leader."""
    v1 = [_rec(1, s) for s in ("begin", "copied", "flip", "done")]
    # After v1, group 1 owns {0,1,2,3,5,7} — a merge moves ALL of it.
    v2 = [_rec(2, s, verb="merge", src=1, dst=0,
               slots=[0, 1, 2, 3, 5, 7])
          for s in ("begin", "copied", "flip", "done")]
    records = list(reversed(v1)) + v2 + v1 + [v2[0]]   # shuffled + dups
    km, active = fold_records(records, num_groups=2, nslots=8)
    assert active is None
    # v1 moved slots {0,2} to g1, then v2 merged g1's keyspace into g0
    # and retired g1: everything lands on g0.
    assert set(km.slots) == {0}
    assert km.retired == {1}
    assert km.epoch == 3          # move, move, retire
    assert km.frozen == set()


def test_fold_active_verb_freezes_until_flipped():
    km, active = fold_records([_rec(1, "begin")], num_groups=2, nslots=8)
    assert active is not None and active["id"] == 1
    assert "flip" not in active["steps"]
    assert km.frozen == {0, 2} and km.epoch == 0
    # Once the flip record is in the log the slots belong to dst and
    # are NOT frozen — only the cleanup half remains.
    km, active = fold_records(
        [_rec(1, "begin"), _rec(1, "copied"), _rec(1, "flip")],
        num_groups=2, nslots=8)
    assert active is not None
    assert km.slots[0] == 1 and km.slots[2] == 1
    assert km.frozen == set() and km.epoch == 1
    # A migrate in flight never freezes slots (keyspace doesn't move).
    km, active = fold_records(
        [_rec(2, "begin", verb="migrate", slots=[])],
        num_groups=2, nslots=8)
    assert active is not None and km.frozen == set()


# -- snapshot fork ----------------------------------------------------------


def _rows_of_image(image: bytes, sql: str):
    with tempfile.NamedTemporaryFile(suffix=".db") as f:
        f.write(image)
        f.flush()
        conn = sqlite3.connect(f.name)
        try:
            return conn.execute(sql).fetchall()
        finally:
            conn.close()


def test_fork_disjoint_union(tmp_path):
    """Key-range fork: two standalone DBs, keyed rows disjoint by
    slot, union exactly the source; meta tables on BOTH sides,
    non-keyed tables stay with the source shard.  Runs through
    `SQLiteStateMachine.serialize`.  resume=True so the `_raft_meta`
    applied floor exists — the meta table both forks must carry."""
    from raftsql_tpu.models.sqlite_sm import SQLiteStateMachine
    sm = SQLiteStateMachine(str(tmp_path / "src.db"), resume=True)
    try:
        sm.apply("CREATE TABLE kv (k TEXT PRIMARY KEY, v TEXT)", 1)
        src_rows = {}
        for i in range(40):
            k, v = f"key-{i}", f"val|{i}"     # '|' probes value safety
            src_rows[k] = v
            sm.apply("INSERT INTO kv VALUES "
                     f"('{k}', '{v}')", i + 2)
        sm.apply("CREATE TABLE sidecar (n INTEGER)", 42)
        sm.apply("INSERT INTO sidecar VALUES (7)", 43)
        nslots = 8
        moving_slots = [0, 3, 5]
        index, moving, staying = fork_state_machine(
            sm, moving_slots, nslots)
        assert index == 43
    finally:
        sm.close()
    got_m = dict(_rows_of_image(moving, "SELECT k, v FROM kv"))
    got_s = dict(_rows_of_image(staying, "SELECT k, v FROM kv"))
    # Disjoint...
    assert not set(got_m) & set(got_s)
    # ...partitioned exactly by slot...
    assert all(slot_of(k, nslots) in set(moving_slots) for k in got_m)
    assert all(slot_of(k, nslots) not in set(moving_slots)
               for k in got_s)
    # ...and the union IS the source, values intact.
    union = dict(got_m)
    union.update(got_s)
    assert union == src_rows
    assert got_m            # the slot choice actually moved something
    # Meta tables ride on both forks; non-keyed tables stay.
    for img in (moving, staying):
        names = {r[0] for r in _rows_of_image(
            img, "SELECT name FROM sqlite_master WHERE type='table'")}
        assert "_raft_meta" in names
    assert _rows_of_image(staying,
                          "SELECT n FROM sidecar") == [(7,)]
    assert not _rows_of_image(
        moving, "SELECT name FROM sqlite_master "
                "WHERE type='table' AND name='sidecar'") \
        or _rows_of_image(moving, "SELECT n FROM sidecar") == []


# -- coordinator ------------------------------------------------------------


class MemBackend:
    """In-memory coordinator backend: journal/copy/rdel apply
    instantly (the 'cluster' never starves), which makes each step()
    advance exactly one state — crash points are then just step
    counts.  `records` doubles as the durable journal a rebuilt
    coordinator folds."""

    def __init__(self, keymap: KeyMap):
        self.nslots = keymap.nslots
        self.kv = {g: {} for g in
                   range(len(set(keymap.slots) | keymap.retired))}
        self.records = []
        self.applied = set()
        self.published = []
        self.shipped = []
        self.cutover_outcome = "completed"

    def seed(self, keymap: KeyMap, n: int = 32):
        for i in range(n):
            k = f"k{i}"
            self.kv[keymap.group_of(k)][k] = f"v{i}"

    def journal(self, group, rec, want=True):
        self.records.append(dict(rec))
        self.applied.add((int(rec["id"]), rec["step"]))

    def journal_applied(self, vid, step):
        return (int(vid), step) in self.applied

    def drained(self, group, slots):
        return True

    def rows_of(self, group, slots):
        ss = set(int(s) for s in slots)
        return {k: v for k, v in self.kv[int(group)].items()
                if slot_of(k, self.nslots) in ss}

    def copy(self, dst, rows):
        self.kv[int(dst)].update(rows)

    def copy_settled(self, dst, rows):
        return all(self.kv[int(dst)].get(k) == v
                   for k, v in rows.items())

    def rdel(self, group, slots, vid):
        for k in list(self.rows_of(group, slots)):
            del self.kv[int(group)][k]

    def rdel_settled(self, group, slots, vid):
        return not self.rows_of(group, slots)

    def publish(self, km):
        self.published.append(km.epoch)

    def ship(self, src, dst):
        self.shipped.append((int(src), int(dst)))

    def cutover(self, src, dst, retry=False):
        return self.cutover_outcome


def _coord(num_groups=2, nslots=8):
    km = KeyMap.initial(num_groups, nslots)
    be = MemBackend(km)
    be.seed(km)
    return ReshardCoordinator(be, km, num_groups=num_groups), be, km


def _run(coord, max_steps=50):
    for _ in range(max_steps):
        if not coord.busy:
            return
        coord.step()
    raise AssertionError(f"verb did not finish: {coord.doc()}")


def test_split_moves_rows_and_bumps_epoch():
    coord, be, km = _coord()
    before = dict(be.kv[0])
    moving = [0, 2]
    moved = {k: v for k, v in before.items()
             if slot_of(k, 8) in set(moving)}
    assert moved                  # seed covered the moving slots
    coord.enqueue("split", 0, 1, moving)
    assert km.frozen == {0, 2}    # intake refused while in flight
    _run(coord)
    assert km.epoch == 1 and km.slots[0] == 1 and km.slots[2] == 1
    assert km.frozen == set()
    for k, v in moved.items():
        assert be.kv[1][k] == v           # arrived at dst...
        assert k not in be.kv[0]          # ...and cleaned off src
    assert coord.counters["splits"] == 1
    assert be.published and be.published[-1] == 1


def test_merge_retires_source_and_migrate_ships():
    coord, be, km = _coord()
    src_rows = dict(be.kv[1])
    coord.enqueue("merge", 1, 0)
    _run(coord)
    assert km.retired == {1} and set(km.slots) == {0}
    assert all(be.kv[0][k] == v for k, v in src_rows.items())
    assert not be.kv[1]
    assert coord.counters["merges"] == 1
    # A full-slot split IS a merge (enqueue normalizes the verb).
    coord2, be2, km2 = _coord()
    coord2.enqueue("split", 1, 0, km2.slots_of(1))
    _run(coord2)
    assert coord2.counters["merges"] == 1 and km2.retired == {1}
    # Migrate never touches the keyspace; it ships + cuts over.
    coord.enqueue("migrate", 0, 2)
    _run(coord)
    assert be.shipped == [(0, 2)]
    assert coord.counters["migrations"] == 1
    assert km.epoch == 2          # unchanged by the migrate


def test_enqueue_refusals():
    coord, be, km = _coord()
    with pytest.raises(ReshardRefused):
        coord.enqueue("rotate", 0, 1)              # unknown verb
    with pytest.raises(ReshardRefused):
        coord.enqueue("split", 0, 1, [1])          # slot owned by g1
    with pytest.raises(ReshardRefused):
        coord.enqueue("split", 0, 0, [0])          # src == dst
    coord.enqueue("split", 0, 1, [0])
    with pytest.raises(ReshardRefused):
        coord.enqueue("split", 0, 1, [2])          # one verb at a time


def _rebuilt(be, num_groups=2, nslots=8):
    """A coordinator restarted after SIGKILL: fresh object, fresh
    boot-time keymap, state rebuilt ONLY from the journal fold."""
    km = KeyMap.initial(num_groups, nslots)
    coord = ReshardCoordinator(be, km, num_groups=num_groups)
    coord.recover(be.records)
    return coord, km


def test_sigkill_before_copy_fence_aborts():
    """Crash after `begin` but before the `copied` fence reached the
    log: rows may be half-copied into dst.  Recovery must UNDO the
    partial copies, release the freeze, and leave the router exactly
    where it was — never guess forward past an unfenced copy."""
    coord, be, km = _coord()
    src_before = dict(be.kv[0])
    coord.enqueue("split", 0, 1, [0, 2])
    coord.step()                  # j:begin -> drain
    coord.step()                  # drain: rows copied into dst
    assert any(slot_of(k, 8) in (0, 2) for k in be.kv[1])
    del coord                     # SIGKILL: fence never journaled

    coord2, km2 = _rebuilt(be)
    assert coord2.busy
    _run(coord2)
    assert coord2.counters["aborted"] == 1
    assert coord2.counters["resumed"] == 1
    assert coord2.counters["splits"] == 0
    assert km2.epoch == 0 and km2.slots == KeyMap.initial(2, 8).slots
    assert km2.frozen == set()
    assert be.kv[0] == src_before              # src untouched
    assert not any(slot_of(k, 8) in (0, 2) for k in be.kv[1])


def test_sigkill_after_copy_fence_resumes_forward():
    """Crash once `copied` is journaled: dst durably holds the rows,
    so recovery must finish the verb FORWARD (flip + cleanup), not
    abort — an abort here would orphan the copies."""
    coord, be, km = _coord()
    moved = {k: v for k, v in be.kv[0].items()
             if slot_of(k, 8) in (0, 2)}
    coord.enqueue("split", 0, 1, [0, 2])
    coord.step()                  # j:begin -> drain
    coord.step()                  # drain -> copy
    coord.step()                  # copy settled -> journal 'copied'
    assert ("copied" in {r["step"] for r in be.records})
    del coord                     # SIGKILL mid-verb

    coord2, km2 = _rebuilt(be)
    _run(coord2)
    assert coord2.counters["splits"] == 1
    assert coord2.counters["resumed"] == 1
    assert coord2.counters["aborted"] == 0
    assert km2.epoch == 1 and km2.slots[0] == 1 and km2.slots[2] == 1
    for k, v in moved.items():
        assert be.kv[1][k] == v and k not in be.kv[0]


def test_sigkill_after_flip_finishes_cleanup():
    coord, be, km = _coord()
    coord.enqueue("split", 0, 1, [0, 2])
    for _ in range(5):            # through j:flip (router flipped)
        coord.step()
    assert "flip" in {r["step"] for r in be.records}
    del coord

    coord2, km2 = _rebuilt(be)
    assert km2.epoch == 1         # fold already applied the flip
    _run(coord2)
    assert coord2.counters["splits"] == 1
    assert not any(slot_of(k, 8) in (0, 2) for k in be.kv[0])
    assert "done" in {r["step"] for r in be.records}


def test_migrate_disk_fault_aborts_cleanly():
    coord, be, km = _coord()

    def bad_ship(src, dst):
        raise OSError("injected fork fault")
    be.ship = bad_ship
    coord.enqueue("migrate", 0, 2)
    _run(coord)
    assert coord.counters["aborted"] == 1
    assert coord.counters["fork_faults"] == 1
    assert km.epoch == 0          # keyspace untouched


def test_metrics_doc_always_carries_all_verbs():
    coord, be, km = _coord()
    doc = coord.metrics_doc()
    assert doc["active"] == 0 and doc["epoch"] == 0
    assert set(doc["duration"]) == {"split", "merge", "migrate"}
    for verb in doc["duration"]:
        h = doc["duration"][verb]
        assert h["count"] == 0 and "inf" in h["bucket"]
    coord.enqueue("split", 0, 1, [0])
    _run(coord)
    h = coord.metrics_doc()["duration"]["split"]
    assert h["count"] == 1 and h["bucket"]["inf"] == 1


# -- shm plane: mapping-epoch fail-closed -----------------------------------


def test_shm_reader_fails_closed_on_keymap_epoch(tmp_path):
    """A router flip publishes the new mapping epoch into the shm
    header; a worker whose cached epoch lags MUST fall back to the
    ring path (None) — recoverably, unlike an engine-epoch mismatch —
    until it refreshes and revalidates."""
    from raftsql_tpu.runtime.shm import (ShmSnapshotPublisher,
                                         ShmSnapshotReader)
    pub = ShmSnapshotPublisher(str(tmp_path), num_groups=1)
    pub.start(lambda g: None, lambda g: 0)
    rdr = ShmSnapshotReader(str(tmp_path))
    try:
        pub.publish_deltas({0: [("CREATE TABLE t (v TEXT)", 1),
                                ("INSERT INTO t VALUES ('x')", 2)]})
        got = rdr.try_read("local", 0, "SELECT count(*) FROM t")
        assert got is not None and got[0].strip() == "|1|"
        pub.set_keymap_epoch(1)   # reshard flip behind the worker
        assert rdr.try_read("local", 0,
                            "SELECT count(*) FROM t") is None
        assert rdr.keymap_epoch() == 1
        rdr.note_keymap_epoch(1)  # worker refreshed its mapping
        got = rdr.try_read("local", 0, "SELECT count(*) FROM t")
        assert got is not None and got[0].strip() == "|1|"
    finally:
        rdr.close()
        pub.close()


# -- api client: mapping-epoch adoption (satellite: unknown-group refresh) --


def _client():
    from raftsql_tpu.api.client import RaftSQLClient
    return RaftSQLClient([10001, 10002], timeout_s=0.2,
                         backoff_s=0.001, backoff_cap_s=0.002)


def _km_doc(epoch, nslots=8, groups=2):
    return KeyMap(nslots, [s % groups for s in range(nslots)],
                  epoch=epoch).to_doc()


def test_client_adopts_only_newer_keymaps():
    c = _client()
    assert c.keymap_epoch() is None
    assert c._note_keymap(_km_doc(2)) is True
    assert c.keymap_epoch() == 2
    # Stale and equal sweeps must NOT roll the router back.
    assert c._note_keymap(_km_doc(1)) is False
    assert c._note_keymap(_km_doc(2)) is False
    assert c.keymap_epoch() == 2
    assert c._note_keymap(_km_doc(3)) is True
    assert c._note_keymap("junk") is False
    assert c.keymap_epoch() == 3
    # The cached epoch is pinned onto every /kv request.
    assert c._kv_headers()["X-Raft-Keymap-Epoch"] == "3"


def test_client_put_kv_refreshes_on_409_and_retries():
    """The mapping-epoch bump path: a split moved the keyspace under
    this client, the server refuses the pinned epoch with 409 + the
    CURRENT mapping, and the client must adopt it and retry the same
    write immediately (breaking the node rotation, not backing off)."""
    c = _client()
    c._note_keymap(_km_doc(1))
    attempts = []

    def fake_raw(node, method, path="/", body="", headers=None,
                 timeout_s=None):
        attempts.append((node, (headers or {}).get(
            "X-Raft-Keymap-Epoch")))
        if (headers or {}).get("X-Raft-Keymap-Epoch") != "4":
            return 409, {}, json.dumps(
                {"error": "keymap epoch mismatch",
                 "keymap": _km_doc(4)})
        return 204, {"X-Raft-Session": "9",
                     "X-Raft-Keymap-Epoch": "4"}, ""

    c.raw = fake_raw
    assert c.put_kv("alpha", "1", deadline_s=5) == 9
    assert c.keymap_epoch() == 4
    # One refused probe at the stale epoch, then the retry pins the
    # adopted epoch — no second trip around the ring in between.
    assert attempts[0][1] == "1" and attempts[1][1] == "4"
    assert len(attempts) == 2


def test_client_epoch_echo_triggers_healthz_sweep():
    """A SUCCESSFUL /kv response that echoes a newer epoch than the
    cache means the keyspace moved without refusing us (the slot
    landed on the same group): the client must sweep /healthz so its
    NEXT request pins the current epoch."""
    c = _client()
    swept = []

    def fake_health(node, timeout_s=2.0):
        swept.append(node)
        return {"keymap": _km_doc(2)}

    c.health = fake_health
    c._note_kv_epoch({"X-Raft-Keymap-Epoch": "2"})   # cache empty
    assert swept and c.keymap_epoch() == 2
    swept.clear()
    # Echo of the SAME epoch: no sweep.  Stale echo: no sweep either
    # (epochs only move forward; an old server answer is not news).
    c._note_kv_epoch({"X-Raft-Keymap-Epoch": "2"})
    c._note_kv_epoch({"X-Raft-Keymap-Epoch": "1"})
    c._note_kv_epoch({"X-Raft-Keymap-Epoch": "junk"})
    assert not swept
    c._note_kv_epoch({"X-Raft-Keymap-Epoch": "5"})
    assert swept


def test_client_get_kv_404_is_none_not_error():
    c = _client()

    def fake_raw(node, method, path="/", body="", headers=None,
                 timeout_s=None):
        if path.endswith("/missing"):
            return 404, {"X-Raft-Keymap-Epoch": "0"}, "no such key"
        return 200, {"X-Raft-Keymap-Epoch": "0"}, "value"

    c.raw = fake_raw
    assert c.get_kv("missing", deadline_s=5) is None
    assert c.get_kv("present", deadline_s=5) == "value"


# -- the live serving plane (both HTTP planes) ------------------------------


@pytest.fixture(params=["threaded", "aio"])
def elastic(request, tmp_path):
    """Single-node 4-group cluster with the reshard plane attached and
    its coordinator thread running — the `--reshard` server wiring."""
    from raftsql_tpu.api.aio import AioSQLServer
    from raftsql_tpu.api.http import SQLServer
    from raftsql_tpu.config import RaftConfig
    from raftsql_tpu.models.sqlite_sm import SQLiteStateMachine
    from raftsql_tpu.reshard.plane import ReshardPlane
    from raftsql_tpu.runtime.db import RaftDB
    from raftsql_tpu.runtime.pipe import RaftPipe
    from raftsql_tpu.transport.loopback import (LoopbackHub,
                                                LoopbackTransport)
    cfg = RaftConfig(num_groups=4, num_peers=1, tick_interval_s=0.005,
                     log_window=64, max_entries_per_msg=4)
    pipe = RaftPipe.create(1, 1, cfg, LoopbackTransport(LoopbackHub()),
                           data_dir=str(tmp_path / "raftsql-1"))
    rdb = RaftDB(lambda g: SQLiteStateMachine(
        str(tmp_path / f"kv-g{g}.db")), pipe, num_groups=4)
    plane = ReshardPlane(rdb, nslots=16,
                         ship_dir=str(tmp_path / "ship"))
    plane.start()
    srv_cls = SQLServer if request.param == "threaded" else AioSQLServer
    srv = srv_cls(0, rdb, host="127.0.0.1", timeout_s=TIMEOUT)
    srv.start()
    yield srv, rdb, plane
    srv.stop()
    plane.stop()
    rdb.close()


def _raw_kv(srv, method, key, body=b"", headers=None):
    conn = http.client.HTTPConnection("127.0.0.1", srv.port,
                                      timeout=10)
    try:
        conn.request(method, f"/kv/{key}", body=body,
                     headers=headers or {})
        r = conn.getresponse()
        return r.status, dict(r.getheaders()), r.read()
    finally:
        conn.close()


def _await_idle(plane, deadline_s=20.0):
    deadline = time.monotonic() + deadline_s
    while plane.coord.busy:
        if time.monotonic() > deadline:
            raise AssertionError(f"verb stuck: {plane.doc()}")
        time.sleep(0.02)


@pytest.mark.slow
def test_elastic_keyspace_end_to_end(elastic):
    """The full serving-plane story on a live node: keyed writes over
    the hash ring, a split and a merge through POST /reshard, epoch
    fail-closed refusals, client-side mapping adoption, reshard
    metrics, and finally a journal re-fold into a FRESH plane proving
    the router state is entirely log-derived."""
    from raftsql_tpu.api.client import RaftSQLClient, SQLError
    from raftsql_tpu.reshard.plane import ReshardPlane
    srv, rdb, plane = elastic
    cli = RaftSQLClient([srv.port], timeout_s=5.0, backoff_s=0.01)

    kv = {f"k{i}": f"v{i}|{i}" for i in range(24)}   # '|' in values
    for k, v in kv.items():
        assert cli.put_kv(k, v, deadline_s=TIMEOUT) is not None
    for k, v in kv.items():
        assert cli.get_kv(k, deadline_s=TIMEOUT) == v
    assert cli.get_kv("never-written", deadline_s=TIMEOUT) is None

    # /healthz carries the mapping; the client swept it while probing.
    assert cli.refresh_keymap() == 0

    # SPLIT: move half of group 0's slots to group 2.
    owned = plane.keymap.slots_of(0)
    moving = owned[:len(owned) // 2]
    doc = cli.reshard("split", 0, 2, moving, deadline_s=TIMEOUT)
    assert doc["verb"] == "split" and doc["id"] >= 1
    _await_idle(plane)
    assert plane.keymap.epoch == 1
    assert all(plane.keymap.slots[s] == 2 for s in moving)

    # Every acked write survives the move, read back THROUGH the
    # client, which adopts the bumped epoch along the way (the 409
    # fail-closed path: its cached epoch 0 is now stale).
    for k, v in kv.items():
        assert cli.get_kv(k, deadline_s=TIMEOUT) == v, k
    assert cli.keymap_epoch() == plane.keymap.epoch
    # Writes route to the NEW owner after the flip.
    moved_key = next((k for k in kv
                      if plane.keymap.slot_of(k) in set(moving)), None)
    if moved_key is not None:
        assert cli.put_kv(moved_key, "rewritten",
                          deadline_s=TIMEOUT) is not None
        assert cli.get_kv(moved_key, deadline_s=TIMEOUT) == "rewritten"
        kv[moved_key] = "rewritten"

    # A request pinned to a stale epoch is refused with the CURRENT
    # mapping attached — the raw-HTTP view of what the client handled.
    status, hdrs, body = _raw_kv(srv, "GET", "k0",
                                 headers={"X-Raft-Keymap-Epoch": "0"})
    assert status == 409
    refused = json.loads(body)
    assert refused["keymap"]["epoch"] == plane.keymap.epoch
    assert int(hdrs.get("X-Raft-Keymap-Epoch")) == plane.keymap.epoch

    # Frozen-slot intake is refused up front with a retry hint.
    s0 = plane.keymap.slot_of("k0")
    plane.keymap.freeze([s0])
    try:
        status, hdrs, _ = _raw_kv(srv, "PUT", "k0", body=b"nope")
        assert status == 503 and hdrs.get("Retry-After")
    finally:
        plane.keymap.unfreeze([s0])

    # MERGE group 3 into group 1; group 3 retires from the router.
    cli.reshard("merge", 3, 1, deadline_s=TIMEOUT)
    _await_idle(plane)
    assert 3 in plane.keymap.retired
    assert 3 not in plane.keymap.live_groups()
    for k, v in kv.items():
        assert cli.get_kv(k, deadline_s=TIMEOUT) == v, k

    # Verb hygiene over the wire: unknown verb and busy-coordinator
    # are 409s, surfaced as SQLError by the client.
    with pytest.raises(SQLError):
        cli.reshard("rotate", 0, 1, deadline_s=TIMEOUT)

    # /metrics carries the reshard counters + per-verb histograms.
    m = rdb.metrics()
    assert m["reshard"]["splits"] == 1
    assert m["reshard"]["merges"] == 1
    assert m["reshard"]["epoch"] == plane.keymap.epoch
    assert m["reshard"]["duration"]["split"]["count"] == 1

    # The router never holds truth the logs don't: folding the
    # replicated journal tables into a FRESH plane rebuilds the exact
    # same mapping.
    want = plane.keymap.to_doc()
    rebuilt = ReshardPlane(rdb, nslots=plane.keymap.nslots,
                           ship_dir=plane.ship_dir)
    try:
        rebuilt.recover_from_db()
        got = rebuilt.keymap.to_doc()
        assert got == want
        assert not rebuilt.coord.busy     # no verb left in flight
    finally:
        rdb.reshard = plane               # restore the live plane
