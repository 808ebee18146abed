"""The long-lived node with every group written: CockroachDB's
`kv` workload at 0% reads over one range a raft group
(benchmarks/ops/kv_splits.py, traffic/kv0.json) on a node that keeps its
SQLite files as snapshots and sweeps its raft log
(configs/multiraft-10k-kv-splits-resume.json), small, on the CPU.

  (a) the configuration is kv-splits' node plus `--resume
      --compact-every 1024 --compact-keep 256`, with the snapshot and
      log-GC guarantees, and its cell is judged on what kv0's is;
  (b) the served node (64 groups, the store's budget forced to 16
      handles, a round every 24 applied entries) fed kv0's statements by
      concurrent keep-alive clients: every write 204, every key read
      back `linear` and `follower` equal to benchmarks/lib/reference.py;
      the store evicted, rounds put files on disk and sweeps moved
      floors, all on /metrics;
  (c) a restart of that node on its data directory answers the same;
  (d) the store: a release raises `synced` only where its checkpoint
      ran to its end, and a round over released groups opens nothing.
"""
import collections
import http.client
import importlib
import itertools
import json
import os
import signal
import socket
import sqlite3
import subprocess
import sys
import threading
import time

import pytest

from raftsql_tpu.models.sqlite_sm import SQLiteStateMachine
from raftsql_tpu.models.store import StateMachineStore

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
GROUPS, BUDGET, EVERY = 64, 16, 24
CLIENTS, OPS_EACH = 32, 12
SEED = 2**31 + 40
P = {"splits": GROUPS - 1, "load_connections": 16, "clients": CLIENTS,
     "read_percent": 0, "batch": 1, "min_block_bytes": 1,
     "max_block_bytes": 2}
CELL = "kv0-10ksplits-resume"
# server.main as the benchmark starts it, with the budget the store
# would derive from RLIMIT_NOFILE replaced by BUDGET handles.
SERVE = ("import sys\n"
         "from raftsql_tpu.models import store\n"
         f"store.handle_budget = lambda files, limit=None: {BUDGET}\n"
         "from raftsql_tpu.server.main import main\n"
         "main(sys.argv[1:])\n")


def bench_json(*path):
    with open(os.path.join(BENCH, *path)) as f:
        return json.load(f)


@pytest.fixture()
def kv(monkeypatch):
    monkeypatch.syspath_prepend(BENCH)
    return importlib.import_module("ops.kv_splits")


# -- (a) the configuration -------------------------------------------------

def test_configuration_is_kv_splits_node_kept_for_months():
    new = bench_json("configs", "multiraft-10k-kv-splits-resume.json")
    old = bench_json("configs", "multiraft-10k-kv-splits.json")
    resume = bench_json("configs", "multiraft-10k-resume.json")
    assert new["argv"] == old["argv"] + [
        "--resume", "--compact-every", "1024", "--compact-keep", "256"]
    for key in ("env", "groups", "chips", "platform", "scale"):
        assert new[key] == old[key], key
    for key in ("write_ack", "linear_read", "session_read"):
        assert new["guarantees"][key] == old["guarantees"][key], key
    assert {"snapshot", "log_gc"} <= set(new["guarantees"])
    assert new["guarantees"]["snapshot"].startswith(
        resume["guarantees"]["snapshot"])
    assert new["guarantees"]["flags"] == resume["guarantees"]["flags"]
    assert new["reduced"] == [] and len(new["source"]) <= 200
    assert "kv --splits 9999" in new["source"] and "kv0" in new["source"]
    assert "truncat" in new["source"]
    assert set(old["assumed"]) < set(new["assumed"])
    manifest = bench_json("..", "BENCHMARK.json")
    entry = [c for c in manifest["configs"] if c["name"] == new["name"]]
    assert entry[0]["source"] == new["source"] and entry[0]["reduced"] == []
    assert entry[0]["file"] == \
        "benchmarks/configs/multiraft-10k-kv-splits-resume.json"
    cell = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell == [dict(cell[0], config=new["name"], traffic="kv0",
                         chips=1)]
    names = [w["name"] for w in manifest["workloads"]]
    assert names[names.index("kv0-10ksplits") + 1] == CELL
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    # Judged as kv0-10ksplits is, for kv0's reasons (PERF.md section 2):
    # not `write_p95_ms` nor `ops_per_s`, which spread 18-37% there.
    for name in ("kv0-10ksplits", CELL):
        listed = {m["name"] for m in manifest["end_to_end"]
                  if name in m.get("workloads", [name])}
        assert listed == {"write_p50_ms", "setup_s"}, name
    # The cell is wherever kv0-10ksplits is listed, right after it (a
    # later cell comes after both), and in the sweep's readers that move
    # `setup_s`.
    for m in manifest["per_layer"]:
        cells = m.get("workloads", [])
        if "kv0-10ksplits" in cells:
            assert cells[cells.index("kv0-10ksplits") + 1] == CELL, \
                m["name"]
    for name in ("compact_sweep_ms", "compact_checkpoint_ms"):
        assert CELL not in [m for m in manifest["per_layer"]
                            if m["name"] == name][0]["workloads"]


# -- (b) and (c) the served node --------------------------------------------

class Client:
    """One keep-alive connection, as lib/loadgen.py's clients hold."""

    def __init__(self, port):
        self.conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)

    def request(self, method, group, sql, headers=None):
        h = {"X-Raft-Group": str(group)}
        h.update(headers or {})
        self.conn.request(method, "/", body=sql, headers=h)
        r = self.conn.getresponse()
        return r.status, r.read().decode()

    def metrics(self):
        self.conn.request("GET", "/metrics")
        r = self.conn.getresponse()
        assert r.status == 200
        return json.loads(r.read())


class Served:
    """The configuration's server.main (--groups GROUPS, a round every
    EVERY applied entries) in `cwd`, its data directory."""

    def __init__(self, cwd, tag):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        self.port = s.getsockname()[1]
        s.close()
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("RAFTSQL_PROF", None)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        argv = bench_json("configs",
                          "multiraft-10k-kv-splits-resume.json")["argv"]
        argv[argv.index("--groups") + 1] = str(GROUPS)
        argv[argv.index("--compact-every") + 1] = str(EVERY)
        self.log = open(os.path.join(cwd, f"server-{tag}.log"), "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-c", SERVE, *argv, "--port", str(self.port)],
            cwd=cwd, env=env, stdout=self.log, stderr=subprocess.STDOUT)
        from raftsql_tpu.api.client import RaftSQLClient
        client = RaftSQLClient([self.port], timeout_s=10)
        try:
            client.wait_healthy(0, deadline_s=180)
        except Exception:
            self.stop()
            raise
        finally:
            client.close()

    def stop(self):
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=10)
        self.log.close()
        return self.proc.returncode


def in_threads(port, per_client):
    """Each list of (group, sql) on a connection of its own, in order;
    [(t_answered, group, sql, status, body)]."""
    answered, mu = [], threading.Lock()

    def one(statements):
        c = Client(port)
        try:
            for g, sql in statements:
                status, body = c.request("PUT", g, sql)
                with mu:
                    answered.append((time.monotonic(), g, sql, status, body))
        finally:
            c.conn.close()

    ts = [threading.Thread(target=one, args=(sts,)) for sts in per_client]
    for t in ts:
        t.start()
    for t in ts:
        t.join(180)
        assert not t.is_alive()
    return answered


def read_back(port, kv, by_group, deadline_s=120.0):
    """{(group, mode): {key: row}} read `linear` and `follower`; a read
    that finds no leader yet (a restart's elections) is asked again."""
    c = Client(port)
    out = {}
    try:
        for g, keys in sorted(by_group.items()):
            for mode in ("linear", "follower"):
                t_end = time.monotonic() + deadline_s
                while True:
                    status, body = c.request(
                        "GET", g, kv.read_many_sql(keys),
                        {"X-Consistency": mode})
                    if status == 200:
                        break
                    assert time.monotonic() < t_end, (g, mode, status, body)
                    time.sleep(0.2)
                got = {line.split("|")[1]: line + "\n"
                       for line in body.splitlines()}
                out[(g, mode)] = {k: got.get(k, "") for k in keys}
    finally:
        c.conn.close()
    return out


@pytest.fixture(scope="module")
def written(tmp_path_factory):
    """The node served, set up and written as run.py does it; what the
    reference and /metrics say afterwards.  The node is stopped; its
    data directory stays for the restart."""
    mp = pytest.MonkeyPatch()
    mp.syspath_prepend(BENCH)
    try:
        kv = importlib.import_module("ops.kv_splits")
        from lib.reference import Reference
        cwd = str(tmp_path_factory.mktemp("kvresume"))
        node = Served(cwd, "first")
        try:
            schema = kv.schema(P)
            n = P["load_connections"]
            setup = in_threads(node.port, [schema[j::n] for j in range(n)])
            c = Client(node.port)
            before = c.metrics()
            writes, keys = [], collections.defaultdict(list)
            for cid in range(CLIENTS):
                stream = []
                for _k, key, _f, val in itertools.islice(
                        kv.client(P, SEED, cid), OPS_EACH):
                    g = kv.group_of(P, key)
                    stream.append((g, kv.write_sql(key, 0, val)))
                    keys[g].append(key)
                writes.append(stream)
            answered = in_threads(node.port, writes)
            for key in kv.sample_keys(P, SEED, 16):         # never written
                keys[kv.group_of(P, key)].append(key)
            ref = Reference()
            try:
                for _t, g, sql, _s, _b in sorted(setup + answered):
                    ref.apply(g, sql)
                want = {(g, mode): {k: ref.query(g, kv.read_sql(k))
                                    for k in ks}
                        for g, ks in keys.items()
                        for mode in ("linear", "follower")}
            finally:
                ref.close()
            got = read_back(node.port, kv, keys)
            after = c.metrics()
            c.conn.close()
        finally:
            rc = node.stop()
        yield {"kv": kv, "cwd": cwd, "setup": setup, "answered": answered,
               "keys": keys, "want": want, "got": got, "before": before,
               "after": after, "rc": rc}
    finally:
        mp.undo()


def test_served_node_evicts_sweeps_and_matches_the_reference(written):
    bad = [a for a in written["setup"] + written["answered"] if a[3] != 204]
    assert not bad, bad[:3]
    assert len(written["answered"]) == CLIENTS * OPS_EACH
    assert len(written["keys"]) > GROUPS * 3 // 4     # nearly every range
    assert written["got"] == written["want"]
    assert written["rc"] == 0
    before, after = written["before"], written["after"]
    # The store held BUDGET handles for GROUPS groups: it evicted and
    # the budget held.
    sm = after["sm"]
    assert 0 < sm["open_handles"] <= BUDGET
    assert sm["evictions"] - before["sm"]["evictions"] > 0
    assert sm["misses"] - before["sm"]["misses"] > 0
    assert sm["uses"] > sm["misses"]
    assert sm["opens"] - sm["closes"] == sm["open_handles"]
    st = after["stages"]
    # A stage's sample lands just after its counter moved: a release
    # or a round's batch may still be on its way at the scrape.
    assert 0 < sm["evictions"] - st["sm"]["release"]["n"] + 4 <= 4 + 4
    assert st["sm"]["miss"]["n"] == sm["misses"]
    assert st["sm"]["reopen"]["n"] == sm["misses"]
    assert sm["native_reopens"] + sm["python_reopens"] == sm["misses"]
    # Rounds put files on disk and sweeps moved floors.
    assert after["compact"]["rounds"] > 1
    assert 0 < after["compact"]["files"] <= st["compact"]["file"]["n"]
    assert after["compact"]["sweeps"] - before["compact"]["sweeps"] > 0
    assert after["compact"]["floors_advanced"] \
        - before["compact"]["floors_advanced"] > 0
    # The benchmark's readers find what they read.
    for name in ("sm_miss_pct", "sm_miss_ms", "sm_release_ms",
                 "sm_reopen_ms", "compact_files_per_round", "compact_file_ms",
                 "sm_evictions", "compact_floors_per_sweep"):
        reader = importlib.import_module("layers." + name)
        value = reader.read({"engine": before}, {"engine": after}, {}, None)
        assert value is not None and value > 0, name
    share = importlib.import_module("layers.sm_reopen_native_pct").read(
        {"engine": before}, {"engine": after}, {}, None)
    assert share is not None and 0 <= share <= 100


def test_a_restart_answers_the_same(written):
    node = Served(written["cwd"], "again")
    try:
        got = read_back(node.port, written["kv"], written["keys"])
        doc = Client(node.port).metrics()
    finally:
        rc = node.stop()
    assert got == written["want"]
    assert rc == 0
    # The files were found and read at boot, not rebuilt: every group
    # has one, and each was opened to read its applied index (under a
    # budget of BUDGET handles, so the store evicted on the way).
    assert doc["sm"]["opens"] >= GROUPS
    assert doc["sm"]["evictions"] > 0


# -- (d) the store -----------------------------------------------------------

def on_disk(path):
    """The applied index a loss of power would leave: the database file
    alone, without its `-wal`."""
    import shutil
    copy = path + ".copy"
    shutil.copyfile(path, copy)
    db = sqlite3.connect(copy)
    try:
        row = db.execute("SELECT v FROM _raft_meta").fetchone()
        return row[0] if row else 0
    except sqlite3.Error:
        return 0
    finally:
        db.close()
        os.remove(copy)


def test_a_release_counts_as_synced_only_when_its_checkpoint_completed(
        tmp_path):
    store = StateMachineStore(
        lambda g: SQLiteStateMachine(str(tmp_path / f"g{g}.db"),
                                     resume=True), 8, budget=2)
    for g in (0, 1):
        with store.use(g) as sm:
            sm.apply_batch([("CREATE TABLE t (v)", 1),
                            (f"INSERT INTO t VALUES ({g})", 2)])
    assert store.evictions == 0 and (store.synced == 0).all()
    # Room for a third: the least recently used handle, group 0, is put
    # on disk and closed on the thread that wanted the room.
    with store.use(2) as sm:
        sm.apply_batch([("CREATE TABLE t (v)", 1)])
    assert store.evictions == 1 and set(store._open) == {1, 2}
    assert store.synced[0] == store.applied[0] == 2
    assert on_disk(str(tmp_path / "g0.db")) == 2
    assert store.synced[1] == 0 < store.applied[1]
    # A handle whose file is on disk goes before a less recently used
    # one whose file is not: its release is a close.
    assert store.checkpoint(2) and store.synced[2] == 1
    with store.use(3) as sm:
        sm.apply_batch([("CREATE TABLE t (v)", 1)])
    assert store.evictions == 2 and set(store._open) == {1, 3}
    assert store.synced[1] == 0
    # A close that cannot checkpoint (another connection reads the
    # file): the file is closed all the same, its `-wal` stays and the
    # group stays unsynced, so the next round reopens it to try again.
    reader = sqlite3.connect(str(tmp_path / "g1.db"))
    reader.execute("BEGIN")
    assert reader.execute("SELECT count(*) FROM t").fetchone() == (1,)
    with store.use(4) as sm:
        sm.apply_batch([("CREATE TABLE t (v)", 1)])
    assert store.evictions == 3 and set(store._open) == {3, 4}
    assert store.synced[1] == 0 < store.applied[1] == 2
    assert os.path.exists(str(tmp_path / "g1.db-wal"))
    reader.execute("COMMIT")
    opens = store.opens
    # Groups 3 and 4 go on disk as they stand, group 1 is reopened for
    # it (and 3, on disk by then, makes the room).
    assert store.checkpoint_round() == 3
    assert store.opens == opens + 1 and set(store._open) == {1, 4}
    assert (store.synced == store.applied).all()
    for g in range(5):
        assert on_disk(str(tmp_path / f"g{g}.db")) == store.applied[g]
    reader.close()
    store.close()


@pytest.mark.parametrize("native", [True, False], ids=["native", "python"])
def test_a_round_over_released_groups_opens_nothing(tmp_path, monkeypatch,
                                                    native):
    """Both arms of a round's batch: ONE native call over the borrowed
    handles, and the module's `PRAGMA wal_checkpoint(FULL)` a file where
    no handle could be borrowed."""
    from raftsql_tpu.models import sqlite_sm
    if not native:
        monkeypatch.setattr(sqlite_sm, "_borrow", lambda conn, path: None)
    G, budget = 40, 8
    store = StateMachineStore(
        lambda g: SQLiteStateMachine(str(tmp_path / f"g{g}.db"),
                                     resume=True), G, budget=budget)
    for r in range(3):
        for g in range(G):
            with store.use(g) as sm:
                assert (sm._txn is not None) == native \
                    or sqlite_sm.load_native_apply() is None
                stmt = ("CREATE TABLE t (v)" if r == 0
                        else f"INSERT INTO t VALUES ({r})")
                assert sm.apply_batch([(stmt, r + 1)]) == [None]
    released = [g for g in range(G) if g not in store._open]
    assert len(released) == G - budget
    assert (store.synced[released] == 3).all()
    opens = store.opens
    files = store.checkpoint_round()
    # Only the handles still open were put on disk; none was reopened.
    assert files == G - len(released)
    assert store.opens == opens
    assert (store.synced == store.applied).all()
    assert store.checkpoint_round() == 0 and store.opens == opens
    for g in range(G):
        assert on_disk(str(tmp_path / f"g{g}.db")) == 3
    # Every miss reopened on the arm of the machine's first open: one
    # native call where that handle was verified.
    assert store.misses > 0
    if native and sqlite_sm.load_native_apply() is not None:
        assert (store.native_reopens, store.python_reopens) == \
            (store.misses, 0)
    else:
        assert (store.native_reopens, store.python_reopens) == \
            (0, store.misses)
    store.close()


def test_a_stopped_round_leaves_the_rest_to_the_next(tmp_path, monkeypatch):
    from raftsql_tpu.models import store as store_mod
    monkeypatch.setattr(store_mod, "ROUND_BATCH", 3)
    G = 8
    store = StateMachineStore(
        lambda g: SQLiteStateMachine(str(tmp_path / f"g{g}.db"),
                                     resume=True), G)
    written = [5, 2, 7, 0, 4]
    for g in written:
        with store.use(g) as sm:
            sm.apply_batch([("CREATE TABLE t (v)", 1)])
    asked = iter((False, True))
    # Stopped after its first batch (the engine closing): three files
    # on disk, two left, and none counted as on disk that is not.
    assert store.checkpoint_round(lambda: next(asked)) == 3
    assert sorted(g for g in range(G) if store.synced[g]) == [0, 2, 4]
    for g in written:
        assert on_disk(str(tmp_path / f"g{g}.db")) == store.synced[g]
    assert store.checkpoint_round() == 2
    assert (store.synced == store.applied).all()
    assert store.checkpoint_round() == 0
    store.close()
