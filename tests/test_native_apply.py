"""A group's transaction as one call into SQLite (native/apply.cc through
models/sqlite_sm.py `apply_batch`) against the Python loop it stands in
for: every case runs the same runs on a file-backed machine of each arm
(`native`: the borrowed handle; `python`: RAFTSQL_TPU_NATIVE=0, the tree
as it was) and on a `:memory:` machine, which is always the Python loop,
and the three must agree on every error (class and text), every table
and the applied index.  A reopen between runs sets the new connection up
in one native call on the native arm (`apply_reopen`) and through the
module on the other.  Then which arm committed what, the counters
`_apply_run` and the store hand the profiler, their readers, and
/healthz.

"Agree" is by construction for whatever the native call gives back, so
the cases that matter are the commands a bare prepare-and-step would
ACCEPT where the `sqlite3` module refuses or does otherwise: a second
statement, a row, a transaction-control statement, a parameter to bind.
"""
import importlib
import json
import os
import sqlite3

import pytest

from raftsql_tpu.models import sqlite_sm
from raftsql_tpu.models.sqlite_sm import SQLiteStateMachine
from raftsql_tpu.models.store import StateMachineStore
from raftsql_tpu.native import build
from raftsql_tpu.native.build import load_native_apply

BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                     "benchmarks")
T = "CREATE TABLE t (k INTEGER PRIMARY KEY, v TEXT NOT NULL)"


@pytest.fixture(params=["native", "python"])
def arm(request, monkeypatch):
    if request.param == "python":
        monkeypatch.setenv("RAFTSQL_TPU_NATIVE", "0")
    elif load_native_apply() is None:
        pytest.skip("no native apply here (g++ or libsqlite3.so.0 absent, "
                    "or an interpreter the borrow was not tested on)")
    return request.param


def ins(k, v="'v'"):
    return f"INSERT INTO t (k, v) VALUES ({k}, {v})"


def indexed(*runs):
    """Runs of commands -> runs of (command, index), indices from 1."""
    out, at = [], 0
    for run in runs:
        out.append([(cmd, at + i + 1) for i, cmd in enumerate(run)])
        at += len(run)
    return out


def bounce(sm):
    """Release and reopen; whether one native call set the new
    connection up."""
    sm.release()
    return sm.reopen()


# name -> (resume, runs of (command, index), runs the native call commits
# on the native arm, what happens between two runs)
CASES = {
    "one statement": (False, indexed([T], [ins(1)]), [1, 1], None),
    "18 statements": (
        False, indexed([T], [ins(k) for k in range(18)]), [1, 1], None),
    "a constraint failure in the middle": (
        False, indexed([T], [ins(1), ins(2), ins(1, "'again'"), ins(3)],
                       [ins(4)]), [1, 0, 1], None),
    "a NOT NULL failure in the middle": (
        False, indexed([T], [ins(1), ins(2, "NULL"), ins(3)]), [1, 0],
        None),
    "a syntax error in the middle": (
        False, indexed([T], [ins(1), "INSRT INTO t", ins(2)]), [1, 0],
        None),
    "a missing table in the middle": (
        False, indexed([T], [ins(1), "INSERT INTO nope VALUES (1)",
                             ins(2)]), [1, 0], None),
    "a two-statement command": (
        False, indexed([T], [ins(1), ins(2) + "; DELETE FROM t", ins(3)]),
        [1, 0], None),
    "a trailing semicolon and blanks": (
        False, indexed([T + ";"], [ins(1) + " ; \n\t", ins(2) + ";"]),
        [1, 1], None),
    "a trailing comment": (
        False, indexed([T], [ins(1) + " -- said the client"],
                       [ins(2) + "; -- after the statement"],
                       [ins(3) + "; /* and */ " + ins(4)]), [1, 1, 0, 0],
        None),
    "an empty command": (False, indexed([T], [ins(1), "", ins(2)]),
                         [1, 0], None),
    "unicode and blob literals": (
        False, indexed(["CREATE TABLE b (k TEXT PRIMARY KEY, v BLOB)"],
                       ["INSERT INTO b VALUES ('zürich ✓ 東京', x'00ff10')",
                        "INSERT INTO b VALUES ('q''uote\"', x'')",
                        "INSERT INTO b VALUES ('\U0001f600', x'e29c93')"]),
        [1, 1], None),
    "a statement that returns rows": (
        False, indexed([T], [ins(1), "SELECT k FROM t", ins(2)],
                       [ins(3) + " RETURNING k"]), [1, 0, 0], None),
    "a command that ends the transaction": (
        False, indexed([T], [ins(1), "COMMIT", ins(2)],
                       [ins(3), "ROLLBACK", ins(4)],
                       [ins(5), "RELEASE _apply", ins(6)], [ins(7)]),
        [1, 0, 0, 0, 1], None),
    "a parameter to bind": (
        # The module refuses each ("Incorrect number of bindings
        # supplied"); a bare step would write NULLs.
        False, indexed(["CREATE TABLE p (k INTEGER PRIMARY KEY, v TEXT)"],
                       ["INSERT INTO p VALUES (1, 'a')",
                        "INSERT INTO p VALUES (2, ?)",
                        "INSERT INTO p (v) VALUES (?1)",
                        "INSERT INTO p VALUES (3, 'c')"],
                       ["UPDATE p SET v = :x WHERE k = 1"],
                       ["UPDATE p SET v = @x"], ["DELETE FROM p WHERE k = $k"],
                       ["INSERT INTO p VALUES (4, '?'), (5, ':x') -- ?"]),
        [1, 0, 0, 0, 0, 1], None),
    "statements with a body or a query of their own": (
        False, indexed(
            [T, "CREATE TABLE log (k INTEGER, was TEXT)"],
            ["CREATE TRIGGER tr AFTER UPDATE ON t BEGIN "
             "INSERT INTO log VALUES (old.k, old.v); "
             "DELETE FROM log WHERE k < 0; END"],
            [ins(1), ins(2), "UPDATE t SET v = 'w' WHERE k = 1"],
            ["WITH s(k) AS (VALUES (7), (8)) "
             "INSERT INTO t SELECT k, 'cte' FROM s",
             "INSERT OR IGNORE INTO t VALUES (1, 'dup')",
             "REPLACE INTO t VALUES (2, 'r')"],
            ["CREATE INDEX tv ON t (v)", "ALTER TABLE t RENAME TO u",
             "DROP TABLE log", "PRAGMA user_version = 7"]),
        [1, 1, 1, 1, 1], None),
    "statements a transaction refuses or answers": (
        False, indexed([T], [ins(1), "VACUUM", ins(2)],
                       [ins(3), "BEGIN IMMEDIATE", ins(4)],
                       [ins(5), "ATTACH DATABASE ':memory:' AS aux"],
                       [ins(6), "PRAGMA journal_mode", ins(7)],
                       [ins(8), "EXPLAIN " + ins(9)],
                       [ins(10) + ";;"], [";"], ["-- nothing"]),
        [1, 0, 0, 0, 0, 0, 0, 0, 0], None),
    "resume: the index lands with the statements": (
        True, indexed([T], [ins(1), ins(2)], [ins(3)]), [1, 1, 1], None),
    "resume: indices at or under the floor are skipped": (
        True, [[(T, 1), (ins(1), 2), (ins(2), 3)],
               [(ins(2), 3), (ins(1), 2), (ins(3), 4)],     # re-delivered
               [(ins(3), 4)],                               # all of it
               [(ins(9), 0), (ins(4), 5)]],                 # no index
        [1, 1, 1, 1], None),
    "resume: an error in a run keeps the floor moving": (
        True, indexed([T], [ins(1), ins(1), ins(2)], [ins(3)]),
        [1, 0, 1], None),
    "release() then reopen() between runs": (
        False, indexed([T], [ins(1)], [ins(2), ins(3)]), [1, 1, 1], bounce),
    "resume: release() then reopen() between runs": (
        True, indexed([T], [ins(1)], [ins(2), ins(3)]), [1, 1, 1], bounce),
    "release() then reopen() after a failed run": (
        False, indexed([T], [ins(1), ins(1, "'again'")], [ins(2)]),
        [1, 0, 1], bounce),
    "resume: release() then reopen() after a failed run": (
        True, indexed([T], [ins(1), ins(1, "'again'")], [ins(2)]),
        [1, 0, 1], bounce),
    "resume: release() then reopen() between every run": (
        True, indexed([T], [ins(1)], [ins(2)], [ins(3), ins(4)]),
        [1, 1, 1, 1], bounce),
}


def dump(sm):
    """Every table, schema and rows."""
    schema = sm.rows("SELECT name, sql FROM sqlite_master ORDER BY name")
    return [(name, sql, sm.rows(f"SELECT * FROM {name} ORDER BY 1"))
            for name, sql in schema if sql and sql.startswith("CREATE TABLE")]


def drive(sm, runs, between):
    """Each run's outcomes, whether the native call committed it, and
    what each `between` said."""
    errs, native, said = [], [], []
    for i, run in enumerate(runs):
        if i and between is not None and sm.path != ":memory:":
            said.append(between(sm))
        errs.append([None if e is None else (type(e), str(e))
                     for e in sm.apply_batch(run)])
        native.append(int(sm.last_native))
    return errs, native, said


@pytest.mark.parametrize("case", list(CASES))
def test_both_arms_and_the_plain_loop_agree(arm, case, tmp_path):
    resume, runs, native_runs, between = CASES[case]
    sm = SQLiteStateMachine(str(tmp_path / "g.db"), resume=resume)
    ref = SQLiteStateMachine(":memory:", resume=resume)
    try:
        assert (sm._txn is not None) == (arm == "native")
        assert ref._txn is None
        got, by_native, reopened = drive(sm, runs, between)
        want, ref_by_native, _ = drive(ref, runs, between)
        assert got == want
        assert dump(sm) == dump(ref)
        assert sm.applied_index() == ref.applied_index()
        if resume:
            last = max(ix for run in runs for _c, ix in run)
            assert sm.applied_index() == last
            assert sm.rows("SELECT v FROM _raft_meta "
                           "WHERE k='applied_index'") == [(last,)]
        # A batch by the arm that committed it; what the native call
        # gives back, the Python loop commits and is counted for.
        assert by_native == (native_runs if arm == "native"
                             else [0] * len(runs))
        assert ref_by_native == [0] * len(runs)
        # A reopen's set-up: one native call wherever the machine's
        # handle was verified, the module everywhere else.
        assert reopened == [arm == "native"] * len(reopened)
        assert not sm._conn.in_transaction
        # And the file is what another connection finds.
        sm.close()
        other = sqlite3.connect(str(tmp_path / "g.db"))
        try:
            assert other.execute("PRAGMA integrity_check").fetchone() == \
                ("ok",)
        finally:
            other.close()
    finally:
        sm.close()
        ref.close()


def test_a_null_character_is_refused_the_same_way(arm, tmp_path):
    sm = SQLiteStateMachine(str(tmp_path / "g.db"))
    ref = SQLiteStateMachine(":memory:")
    try:
        for m in (sm, ref):
            assert m.apply(T, 1) is None
        run = [(ins(1), 2), (ins(2) + "\x00 ", 3), (ins(3), 4)]
        outcomes = []
        for m in (sm, ref):
            try:
                outcomes.append([e and (type(e), str(e))
                                 for e in m.apply_batch(run)])
            except Exception as e:          # noqa: BLE001 - compared
                outcomes.append((type(e), str(e)))
        assert outcomes[0] == outcomes[1]
        assert sm.last_native is False
    finally:
        sm.close()
        ref.close()


def test_query_sees_a_write_at_once_on_the_same_handle(arm, tmp_path):
    """One connection a file: the write's pages are in the cache the
    next SELECT reads, and a statement the Python connection prepared
    before a native schema change still runs."""
    sm = SQLiteStateMachine(str(tmp_path / "g.db"))
    try:
        assert sm.apply(T, 1) is None
        assert sm.query("SELECT count(*) FROM t") == "|0|\n"
        for k in range(1, 40):
            assert sm.apply(ins(k, f"'v{k}'"), k + 1) is None
            assert sm.last_native is (arm == "native")
            assert sm.query("SELECT count(*) FROM t") == f"|{k}|\n"
            assert sm.query(f"SELECT v FROM t WHERE k={k}") == f"|v{k}|\n"
        assert sm.apply("ALTER TABLE t ADD COLUMN w INTEGER", 99) is None
        assert sm.query("SELECT w FROM t WHERE k=1") == "||\n"
        assert sm.query("SELECT count(*) FROM t") == "|39|\n"
        descriptors = [os.readlink(f"/proc/self/fd/{fd}")
                       for fd in os.listdir("/proc/self/fd")
                       if os.path.exists(f"/proc/self/fd/{fd}")]
        assert descriptors.count(str(tmp_path / "g.db")) == 1
    finally:
        sm.close()


def test_install_and_image_keep_the_arm(arm, tmp_path):
    """`install` replaces the file and the connection, `_image` flips
    the journal mode on the one it has: the handle is re-taken with the
    connection, and the machine goes on applying on its arm."""
    src = SQLiteStateMachine(str(tmp_path / "src.db"), resume=True)
    dst = SQLiteStateMachine(str(tmp_path / "dst.db"), resume=True)
    try:
        assert src.apply_batch([(T, 1), (ins(1), 2)]) == [None, None]
        index, blob = src.serialize_with_index()
        assert src.apply(ins(2), 3) is None             # after _image
        dst.install(blob, index)
        assert (dst._txn is not None) == (arm == "native")
        assert dst.apply_batch([(ins(1), 2), (ins(2), 3)]) == [None, None]
        assert dst.applied_index() == src.applied_index() == 3
        assert dump(dst) == dump(src)
        assert dst.last_native is src.last_native is (arm == "native")
    finally:
        src.close()
        dst.close()


def test_a_handle_that_fails_verification_stays_on_the_python_arm(
        arm, tmp_path, monkeypatch):
    """What is read at the connection's address is believed only if it
    names the machine's own file: if not, the Python loop applies and
    is counted for it, and a later connection is asked again."""
    monkeypatch.setattr(sqlite_sm.os.path, "samefile", lambda a, b: False)
    sm = SQLiteStateMachine(str(tmp_path / "g.db"))
    try:
        assert sm._txn is None
        assert sm.apply_batch([(T, 1), (ins(1), 2)]) == [None, None]
        assert sm.last_native is False
        monkeypatch.undo()
        if arm == "python":
            monkeypatch.setenv("RAFTSQL_TPU_NATIVE", "0")
        bounce(sm)
        assert (sm._txn is not None) == (arm == "native")
        assert sm.apply(ins(2), 3) is None
        assert sm.last_native is (arm == "native")
        assert sm.query("SELECT k FROM t ORDER BY k") == "|1|\n|2|\n"
    finally:
        sm.close()


@pytest.mark.parametrize("layout", [
    ("cpython", 3, 13, 224),        # an interpreter nobody looked at
    ("cpython", 3, 12, 232),        # this one, another Connection struct
    ("pypy", 3, 12, 224),
])
def test_an_interpreter_that_was_not_tested_is_never_asked(
        arm, tmp_path, monkeypatch, layout):
    """The word after the object's head is read, and the library asked
    about it, only where that layout was looked at: anywhere else the
    question itself could crash, so the library does not load, /healthz
    says so and the machine stays on the Python loop."""
    monkeypatch.delitem(build._cache, "apply_checked", raising=False)
    monkeypatch.setattr(build, "_connection_layout", lambda: layout)
    assert load_native_apply() is None
    sm = SQLiteStateMachine(str(tmp_path / "g.db"))
    try:
        assert sm._txn is None
        assert sm.apply_batch([(T, 1), (ins(1), 2)]) == [None, None]
        assert sm.last_native is False
        assert sm.query("SELECT k FROM t") == "|1|\n"
    finally:
        sm.close()
        build._cache.pop("apply_checked", None)


def test_a_transaction_left_open_is_not_the_native_calls_to_end(arm,
                                                                tmp_path):
    """The Python loop tolerates a transaction already open (its BEGIN
    fails and it goes on inside it); the native call does not touch
    one, and gives the batch to that loop."""
    sm = SQLiteStateMachine(str(tmp_path / "g.db"))
    try:
        assert sm.apply(T, 1) is None
        sm._conn.execute("BEGIN")
        sm._conn.execute(ins(1))
        assert sm.apply(ins(2), 2) is None
        assert sm.last_native is False
        assert not sm._conn.in_transaction
        assert sm.query("SELECT k FROM t ORDER BY k") == "|1|\n|2|\n"
    finally:
        sm.close()


def test_memory_never_goes_native(arm):
    for resume in (False, True):
        sm = SQLiteStateMachine(":memory:", resume=resume)
        try:
            assert sm._txn is None
            assert sm.apply_batch([(T, 1), (ins(1), 2)]) == [None, None]
            assert sm.last_native is False
        finally:
            sm.close()


# -- a reopen in one call ---------------------------------------------------

@pytest.mark.parametrize("resume", [False, True], ids=["parity", "resume"])
def test_a_reopen_sets_the_connection_up_as_the_first_open_did(
        arm, tmp_path, resume):
    """A close forgets the connection's pragmas and the file keeps its
    journal mode: reopened on either arm, a parity machine is back on a
    memory journal without syncs, a resume machine on WAL with NORMAL
    syncs, and goes on applying on its arm."""
    sm = SQLiteStateMachine(str(tmp_path / "g.db"), resume=resume)
    want = [("wal",), (1,)] if resume else [("memory",), (0,)]
    try:
        assert sm.apply_batch([(T, 1), (ins(1), 2)]) == [None, None]
        assert [sm.rows("PRAGMA journal_mode")[0],
                sm.rows("PRAGMA synchronous")[0]] == want
        for k in (2, 3):
            sm.release()
            assert sm.reopen() is (arm == "native")
            assert sm.reopen() is False             # already open
            assert [sm.rows("PRAGMA journal_mode")[0],
                    sm.rows("PRAGMA synchronous")[0]] == want
            assert sm.apply(ins(k), k + 1) is None
            assert sm.last_native is (arm == "native")
        assert sm.applied_index() == 4
        assert sm.query("SELECT count(*) FROM t") == "|3|\n"
    finally:
        sm.close()


@pytest.mark.parametrize("change,on_file", [
    ("UPDATE _raft_meta SET v = 7", 7),
    ("DELETE FROM _raft_meta", 0),
])
def test_a_reopen_whose_raft_meta_differs_still_raises(arm, tmp_path,
                                                       change, on_file):
    """The file's `_raft_meta` must say what the machine remembers, on
    either arm, or the file is not the one that was released."""
    path = str(tmp_path / "g.db")
    sm = SQLiteStateMachine(path, resume=True)
    try:
        assert sm.apply_batch([(T, 1), (ins(1), 2)]) == [None, None]
        sm.release()
        other = sqlite3.connect(path)
        other.execute(change)
        other.commit()
        other.close()
        with pytest.raises(RuntimeError, match=(
                f"applied index {on_file} on file, 2 remembered at "
                "release")):
            sm.reopen()
    finally:
        sm.close()


def test_a_handle_naming_another_file_reopens_through_the_module(
        arm, tmp_path):
    """The new handle must name the file the verified one named: where
    it does not, the module sets the connection up, `_borrow` asks the
    new handle again, and the machine is back on its arm.  The store
    counts each reopen by the arm that did it."""
    store = StateMachineStore(lambda g: SQLiteStateMachine(
        str(tmp_path / f"g{g}.db"), resume=True), 2, budget=1)
    try:
        for g in (0, 1):                # 1 releases 0
            with store.use(g) as sm:
                assert sm.apply_batch([(T, 1), (ins(g), 2)]) == [None, None]
        zero = store._entries[0].sm
        assert (zero._verified is not None) == (arm == "native")
        zero._verified = str(tmp_path / "other.db").encode()
        with store.use(0) as sm:        # releases 1
            assert sm._verified != str(tmp_path / "other.db").encode()
            assert (sm._verified is not None) == (arm == "native")
            assert sm.apply(ins(5), 3) is None
            assert sm.last_native is (arm == "native")
        assert (store.native_reopens, store.python_reopens) == (0, 1)
        with store.use(1) as sm:
            assert sm.query("SELECT k FROM t") == "|1|\n"
        with store.use(0) as sm:
            assert sm.query("SELECT k FROM t ORDER BY k") == "|0|\n|5|\n"
        n = 2 if arm == "native" else 0
        assert (store.native_reopens, store.python_reopens) == (n, 3 - n)
        assert store.misses == 3
    finally:
        store.close()


def test_memory_reopens_through_the_module(arm):
    sm = SQLiteStateMachine(":memory:")
    try:
        assert sm.apply(T, 1) is None
        sm.release()
        assert sm.reopen() is False
        assert sm._txn is None and sm._verified is None
        assert sm.apply(T, 1) is None           # a new, empty database
    finally:
        sm.close()


@pytest.mark.parametrize("gone", ["RAFTSQL_TPU_NATIVE=0",
                                  "an untested interpreter"])
def test_a_library_gone_since_the_first_open_reopens_through_the_module(
        tmp_path, monkeypatch, gone):
    """A machine verified at its first open reopens through the module
    once the library does not load, and is counted for it."""
    if load_native_apply() is None:
        pytest.skip("no native apply here")
    store = StateMachineStore(lambda g: SQLiteStateMachine(
        str(tmp_path / f"g{g}.db"), resume=True), 2, budget=1)
    try:
        for g in (0, 1):
            with store.use(g) as sm:
                assert sm._txn is not None
                assert sm.apply_batch([(T, 1), (ins(g), 2)]) == [None, None]
        if gone == "RAFTSQL_TPU_NATIVE=0":
            monkeypatch.setenv("RAFTSQL_TPU_NATIVE", "0")
        else:
            monkeypatch.delitem(build._cache, "apply_checked")
            monkeypatch.setattr(build, "_connection_layout",
                                lambda: ("cpython", 3, 13, 224))
        for g in (0, 1):
            with store.use(g) as sm:
                assert sm._txn is None and sm._verified is None
                assert sm.query("SELECT k FROM t") == f"|{g}|\n"
                assert sm.apply(ins(5), 3) is None
                assert sm.last_native is False
        assert (store.native_reopens, store.python_reopens) == (0, 2)
    finally:
        store.close()
        build._cache.pop("apply_checked", None)


# -- the counters of a run, their reader, /healthz -------------------------

def test_a_run_counts_its_batches_by_the_arm_that_committed(arm, tmp_path):
    from test_apply_fanout import WAIT_S, Rig, counters
    rig = Rig(4, lambda g, _events: SQLiteStateMachine(
        str(tmp_path / f"g{g}.db")), shm=False)
    try:
        for writes in ([(g, T) for g in range(4)],
                       [(g, ins(1)) for g in range(4)],
                       [(0, ins(2)), (1, ins(1)), (2, ins(2))],  # 1 fails
                       [(3, ins(5))]):
            futs = rig.run(writes)
            rig.heard(len(writes))
            errs = [f.wait(WAIT_S) for f in futs]
        assert errs == [None]
        c = counters(rig)
        assert c["runs"] == 4 and c["groups"] == 12
        assert c["native_txns"] + c["python_txns"] == c["groups"]
        assert c["native_txns"] == (11 if arm == "native" else 0)
        assert rig.db.health_doc()["native_apply"] is (arm == "native")
    finally:
        rig.close()


def scrape(native, python, has=True):
    doc = {"apply": {"runs": 10, "groups": native + python}}
    if has:
        doc["apply"].update(native_txns=native, python_txns=python)
    return {"t": 1.0, "engine": doc, "workers": []}


@pytest.mark.parametrize("before,after,want", [
    ((0, 0), (300, 0), 100.0),
    ((100, 7), (400, 8), pytest.approx(100.0 * 300 / 301)),
    ((5, 5), (5, 45), 0.0),                 # RAFTSQL_TPU_NATIVE=0
    ((5, 5), (5, 5), None),                 # nothing applied
])
def test_apply_native_pct_on_hand_made_scrapes(monkeypatch, before, after,
                                               want):
    monkeypatch.syspath_prepend(BENCH)
    reader = importlib.import_module("layers.apply_native_pct")
    assert reader.read(scrape(*before), scrape(*after), {}, None) == want
    # A program from before this reader counted neither arm: silent.
    assert reader.read(scrape(*before, has=False),
                       scrape(*after, has=False), {}, None) is None
    with open(os.path.join(BENCH, "..", "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = [m for m in manifest["per_layer"]
             if m["name"] == "apply_native_pct"]
    assert len(entry) == 1 and entry[0]["moves"] == "write_p50_ms"
    assert entry[0]["workloads"] == [w["name"]
                                     for w in manifest["workloads"]]
    assert entry[0]["layer"] == \
        "ack routing + apply (runtime/db.py, models/sqlite_sm.py)"


def reopens(native, python, total_ms, has=True):
    doc = {"sm": {"uses": 1000, "misses": native + python}}
    if has:
        doc["sm"].update(native_reopens=native, python_reopens=python)
        doc["stages"] = {"sm": {"reopen": {
            "total_ms": total_ms, "n": native + python, "max_ms": 9.0}}}
    return {"t": 1.0, "engine": doc, "workers": []}


@pytest.mark.parametrize("before,after,pct,ms", [
    ((0, 0, 0.0), (300, 0, 900.0), 100.0, 3.0),
    ((100, 7, 50.0), (400, 8, 1253.0), pytest.approx(100.0 * 300 / 301),
     pytest.approx(1203.0 / 301)),
    ((5, 5, 10.0), (5, 45, 1410.0), 0.0, 35.0),      # the module's
    ((5, 5, 10.0), (5, 5, 10.0), None, None),        # nothing reopened
])
def test_sm_reopen_readers_on_hand_made_scrapes(monkeypatch, before, after,
                                                pct, ms):
    monkeypatch.syspath_prepend(BENCH)
    with open(os.path.join(BENCH, "..", "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for name, want in (("sm_reopen_native_pct", pct), ("sm_reopen_ms", ms)):
        reader = importlib.import_module("layers." + name)
        assert reader.read(reopens(*before), reopens(*after), {},
                           None) == want
        # A program from before these readers has neither: silent.
        assert reader.read(reopens(*before, has=False),
                           reopens(*after, has=False), {}, None) is None
        entry = [m for m in manifest["per_layer"] if m["name"] == name]
        assert len(entry) == 1
        assert entry[0]["moves"] == "write_p50_ms"
        assert entry[0]["source"] == "program_counter"
        assert entry[0]["workloads"] == ["kv0-10ksplits-resume"]
        assert entry[0]["layer"] == \
            "ack routing + apply (runtime/db.py, models/sqlite_sm.py)"
