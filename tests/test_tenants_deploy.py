"""A node of 100,000 resident groups (configs/multiraft-100k-tenants.json,
cell `ycsb-a-100ktenants`), small, on the CPU.

  (a) the configuration is multiraft-10k's node at `--groups 100000`,
      its 256 table groups spread over all of them, and its cell and
      readers are in the manifest; the runner's descriptor step takes
      its clients under the chip host's hard limit;
  (b) a served node (1,000 groups) whose rings are 32 KiB, so that its
      /healthz document is three rings: /healthz answers with
      every row and every group led, /metrics answers, and YCSB-A
      traffic driven by the benchmark's own generators and checker reads
      back `linear` equal to benchmarks/lib/reference.py;
  (c) the shm plane's reader unpacks the ONE row a read needs, under the
      same seqlock as the header, and still retries a torn sequence;
  (d) the shm plane's bulk refresh writes the bytes the per-group loop
      wrote, from a node's [G] columns.
"""
import http.client
import importlib
import json
import math
import os
import resource
import signal
import socket
import struct
import subprocess
import sys
import time
import types

import numpy as np
import pytest

from raftsql_tpu.runtime import shm
from raftsql_tpu.runtime.shm import (ShmSnapshotPublisher,
                                     ShmSnapshotReader)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
CONFIG = "multiraft-100k-tenants"
CELL = "ycsb-a-100ktenants"
GROUPS, RING = 1000, 1 << 15
SEED = 2**31 + 43
# The configuration's table groups and traffic at this node's size; an
# INSERT of 8 rows (~9 KB) fits half the request ring of RING bytes.
SCALE = {"table_groups": 16, "group_stride": 62, "recordcount": 2048,
         "rows_per_insert": 8, "load_connections": 8}
CLIENTS, WINDOW_S = 8, 4.0
# server.main as the benchmark starts it, with rings of RING bytes.
SERVE = ("import sys\n"
         "from raftsql_tpu.runtime import ring\n"
         f"ring.DEFAULT_RING_BYTES = {RING}\n"
         "from raftsql_tpu.server.main import main\n"
         "main(sys.argv[1:])\n")


def bench_json(*path):
    with open(os.path.join(BENCH, *path)) as f:
        return json.load(f)


# -- (a) the configuration -------------------------------------------------

def test_configuration_is_multiraft_10k_at_100000_groups():
    new = bench_json("configs", CONFIG + ".json")
    old = bench_json("configs", "multiraft-10k.json")
    argv = list(old["argv"])
    argv[argv.index("--groups") + 1] = "100000"
    assert new["argv"] == argv and new["groups"] == 100000
    for key in ("env", "chips", "platform", "guarantees", "reduced"):
        assert new[key] == old[key], key
    assert set(new["reduced_why"]) == set(old["reduced_why"])
    assert new["scale"] == dict(old["scale"], group_stride=390)
    assert set(new["assumed"]) == set(old["assumed"])
    assert "TiKV" in new["assumed"]["table_groups"]
    assert "99,744" in new["deployment"]
    assert len(new["source"]) <= 200 and "config 4" in new["source"]
    # Every table group is a group of the node, and they are distinct.
    sys.path.insert(0, BENCH)
    try:
        ycsb = importlib.import_module("ops.ycsb")
    finally:
        sys.path.remove(BENCH)
    tables = ycsb.table_groups(new["scale"])
    assert len(set(tables)) == 256 and max(tables) < 100000
    manifest = bench_json("..", "BENCHMARK.json")
    entry = [c for c in manifest["configs"] if c["name"] == CONFIG]
    assert len(entry) == 1 and entry[0]["source"] == new["source"]
    assert entry[0]["reduced"] == ["recordcount"]
    assert entry[0]["file"] == f"benchmarks/configs/{CONFIG}.json"
    cell = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert cell == [dict(cell[0], config=CONFIG, traffic="ycsb-a",
                         chips=1)]
    assert len(cell[0]["why"]) <= 200
    # Judged on the median write and the set-up at least; the readers
    # of what grows with G are there, and `None` at a program without
    # their keys.
    e2e = {m["name"] for m in manifest["end_to_end"]
           if CELL in m.get("workloads", [CELL])}
    assert {"write_p50_ms", "setup_s"} <= e2e
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in ("shm_refresh_ms", "read_shm_snapshot_ms",
                 "boot_all_led_s"):
        m = by_name[name]
        assert m["moves"] in e2e, name
        assert "workloads" not in m or CELL in m["workloads"], name
        sys.path.insert(0, BENCH)
        try:
            reader = importlib.import_module("layers." + name)
        finally:
            sys.path.remove(BENCH)
        empty = {"engine": {}, "workers": [{}, {}]}
        assert reader.read(empty, empty, {}, None) is None, name
    assert by_name["read_shm_snapshot_ms"]["workloads"][-1] == CELL


def test_the_runners_descriptor_step_takes_the_tenants_node(monkeypatch):
    """run.py asks for its clients and a spare, not a descriptor a
    group: the chip host's hard limit of 20,000 holds the 100,000-group
    node's cell."""
    monkeypatch.syspath_prepend(BENCH)
    run = importlib.import_module("run")
    traffic = bench_json("traffic", "ycsb-a.json")
    assert bench_json("configs", CONFIG + ".json")["groups"] > 20000
    limits = {"soft": 1024, "hard": 20000}

    def get(which):
        assert which == resource.RLIMIT_NOFILE
        return limits["soft"], limits["hard"]

    def set_(which, pair):
        assert which == resource.RLIMIT_NOFILE and pair[1] == 20000
        limits["soft"] = pair[0]

    monkeypatch.setattr(resource, "getrlimit", get)
    monkeypatch.setattr(resource, "setrlimit", set_)
    assert run.raise_nofile(traffic["clients"]) == 20000
    assert limits["soft"] == 20000


# -- (b) the served node ---------------------------------------------------

def get(port, path, conn=None):
    own = conn is None
    if own:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        return r.status, r.read()
    finally:
        if own:
            conn.close()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """The configuration's server.main at GROUPS groups with RING-byte
    rings, up until every group is led; the benchmark's run.py as a
    module, for its generators."""
    mp = pytest.MonkeyPatch()
    mp.syspath_prepend(BENCH)
    cwd = str(tmp_path_factory.mktemp("tenants"))
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("RAFTSQL_PROF", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    argv = bench_json("configs", CONFIG + ".json")["argv"]
    argv[argv.index("--groups") + 1] = str(GROUPS)
    log = open(os.path.join(cwd, "server.log"), "w")
    proc = subprocess.Popen(
        [sys.executable, "-c", SERVE, *argv, "--port", str(port)],
        cwd=cwd, env=env, stdout=log, stderr=subprocess.STDOUT)
    try:
        deadline = time.monotonic() + 240
        health = None
        while time.monotonic() < deadline:
            assert proc.poll() is None, "the server exited"
            try:
                status, body = get(port, "/healthz")
            except OSError:
                status = 0
            if status == 200:
                health = json.loads(body)
                if all(r["leader"] > 0 for r in health["groups"].values()):
                    break
            time.sleep(0.5)
        else:
            pytest.fail("no /healthz with every group led")
        yield {"port": port, "health": health, "health_bytes": len(body),
               "run": importlib.import_module("run"), "cwd": cwd}
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=10)
        log.close()
        mp.undo()


def test_healthz_and_metrics_answer_beyond_the_ring(served):
    health = served["health"]
    rows = health["groups"]
    assert served["health_bytes"] > 2 * RING
    assert health["ready"] and sorted(map(int, rows)) == list(range(GROUPS))
    for row in rows.values():
        assert {"role", "leader", "term", "commit", "applied",
                "lease_s"} <= set(row)
        assert row["role"] == "leader" and 1 <= row["leader"] <= 3
    conn = http.client.HTTPConnection("127.0.0.1", served["port"],
                                      timeout=60)
    try:
        status, body = get(served["port"], "/metrics", conn)
        assert status == 200
        before = json.loads(body)
        assert get(served["port"], "/healthz", conn)[0] == 200
        status, body = get(served["port"], "/metrics", conn)
        after = json.loads(body)
    finally:
        conn.close()
    # The engine made the documents and a /healthz took several records.
    renders = after["stages"]["doc"]["render"]["n"] \
        - before["stages"]["doc"]["render"]["n"]
    records = after["doc"]["records"] - before["doc"]["records"]
    assert renders >= 2 and records >= renders + 2
    assert after["stages"]["shm"]["refresh"]["n"] \
        > before["stages"]["shm"]["refresh"]["n"]
    assert 0 < after["boot"]["replay_s"] <= after["boot"]["all_led_s"]
    sys.path.insert(0, BENCH)
    try:
        for name in ("shm_refresh_ms", "boot_all_led_s"):
            reader = importlib.import_module("layers." + name)
            value = reader.read({"engine": before}, {"engine": after}, {},
                                None)
            assert value is not None and value > 0, name
    finally:
        sys.path.remove(BENCH)


def test_ycsb_a_reads_back_equal_to_the_reference(served):
    """The cell's pipeline as run.py runs it (schema, load, the mix's
    closed-loop clients over a window, the read-back), judged by the
    cell's checker against lib/reference.py."""
    run, port = served["run"], served["port"]
    ops = importlib.import_module("ops.ycsb")
    checker = importlib.import_module("ops.registers")
    traffic = dict(bench_json("traffic", "ycsb-a.json"), clients=CLIENTS,
                   processes=1)
    p = dict(SCALE, **traffic)
    schema = [["PUT", g, sql, ""] for g, sql in ops.schema(p)]
    load = [["PUT", g, sql, ""] for g, sql in ops.load(p, SEED)]
    gens = run.Generators(served["cwd"])
    try:
        for what, reqs in (("schema", schema), ("load", load)):
            run.require_204(reqs, run.run_list(
                gens, port, reqs, p["load_connections"], what), what)
        gens.spawn_mix(port, traffic, p, SEED)
        time.sleep(2.0)
        t0 = time.monotonic() + 0.25
        t1 = t0 + WINDOW_S
        gens.tell(f"window {t0!r} {t1!r}")
        time.sleep(max(0.0, t1 - time.monotonic()))
        docs = gens.collect(run.GENERATOR_EXIT_S)
        log = [r for d in docs for r in d["ops"]]
        client = run.client_numbers(log, t0, t1)
        readback = run.read_back(gens, port, ops, p, SEED, log)
    finally:
        gens.destroy()
    assert client["failed"] == 0 and client["writes"] > 10 \
        and client["reads"] > 10, client
    verdict = checker.check(ops, p, SEED, [(r[1], r[2]) for r in
                                           schema + load],
                            log, readback, "linear")
    assert verdict["correct"] and verdict["mismatched"] == 0, verdict
    written = {r[2] for r in log if r[1] == "w" and r[7] == 204}
    assert written and written <= set(readback)


# -- (c) and (d) the shm plane -------------------------------------------

SCHEMA = "CREATE TABLE t (k INTEGER PRIMARY KEY, v TEXT)"


def mapping_rows(pub):
    """Every row of the mapped table, unpacked from its bytes."""
    raw = bytes(pub._mm[pub._table_off:pub._log_off])
    return [shm._ROW.unpack_from(raw, i * shm._ROW_SIZE)
            for i in range(pub.num_groups)]


def pair(tmp_path, groups):
    pub = ShmSnapshotPublisher(str(tmp_path), num_groups=groups,
                               size=2 << 20)
    pub.start(lambda g: None, lambda g: 0)
    return pub, ShmSnapshotReader(str(tmp_path))


def test_a_read_snapshots_its_own_row_under_the_seqlock(tmp_path):
    G = 64
    pub, rdr = pair(tmp_path, G)
    try:
        for g in range(0, G, 3):
            pub.publish_deltas({g: [(SCHEMA, 1), (
                f"INSERT INTO t VALUES ({g}, 'x')", 2 + g % 5)]})
        pub.refresh(lambda g: 2 + g % 7, lambda g: g % 3 - 1,
                    lambda g: 1e6 + g if g % 2 else 0.0)
        rows = mapping_rows(pub)
        for g in range(G):
            hdr, row = rdr._snapshot_row(g)
            assert row == rows[g]
            assert hdr[5] % 2 == 0 and hdr[4] == pub.epoch
            assert rdr.leader_of(g) == rows[g][4]
        assert rdr._snapshot_row(G)[1] is None          # outside the map
        assert rdr.try_read("local", G, "SELECT 1") is None
        assert rdr.last_miss() == "group_range"
        # A writer in its critical section: bounded retries, fail closed.
        with pub._lock:
            pub._seq += 1
            pub._write_header(time.monotonic_ns())
        assert rdr._snapshot_row(0) is None
        with pub._lock:
            pub._seq += 1
            pub._write_header(time.monotonic_ns())
        assert rdr._snapshot_row(0)[1] == rows[0]
    finally:
        rdr.close()
        pub.close()


def test_a_torn_sequence_is_read_again(tmp_path, monkeypatch):
    """The header read after the row names another sequence than the one
    before it: the row is read again, and the second, untorn pair is
    what the read gets."""
    pub, rdr = pair(tmp_path, 8)
    try:
        pub.refresh(lambda g: 5, lambda g: 0, lambda g: 0.0)
        real = rdr._read_header_raw
        seqs = iter([2, 4, 4, 4])
        reads = []

        def header():
            h = list(real())
            h[5] = next(seqs)
            reads.append(h[5])
            return tuple(h)
        monkeypatch.setattr(rdr, "_read_header_raw", header)
        hdr, row = rdr._snapshot_row(3)
        assert reads == [2, 4, 4, 4] and hdr[5] == 4
        assert row == mapping_rows(pub)[3] and row[1] == 5
    finally:
        rdr.close()
        pub.close()


class CountingRow:
    """shm._ROW with a count of the rows unpacked."""

    def __init__(self, real):
        self.real, self.n = real, 0
        self.size = real.size

    def unpack_from(self, *a, **kw):
        self.n += 1
        return self.real.unpack_from(*a, **kw)

    def pack(self, *a):
        return self.real.pack(*a)


@pytest.mark.parametrize("groups", [16, 4096])
def test_a_read_unpacks_one_row_whatever_the_groups(tmp_path, monkeypatch,
                                                    groups):
    pub, rdr = pair(tmp_path, groups)
    try:
        g = groups - 1
        pub.publish_deltas({g: [(SCHEMA, 1),
                                ("INSERT INTO t VALUES (1, 'a')", 2)]})
        pub.refresh(lambda x: 2, lambda x: 0, lambda x: 0.0)
        counting = CountingRow(shm._ROW)
        monkeypatch.setattr(shm, "_ROW", counting)
        for mode in ("local", "follower", "session"):
            got = rdr.try_read(mode, g, "SELECT count(*) FROM t")
            assert got is not None and got[0].strip() == "|1|", mode
        assert rdr.try_read("linear", g, "SELECT 1") is None   # no lease
        assert rdr.leader_of(g) == 1
        assert counting.n == 5
    finally:
        rdr.close()
        pub.close()


def test_the_bulk_refresh_writes_the_loops_bytes(tmp_path):
    """refresh_columns and refresh, fed the same values, leave the same
    table: the commit column never decreases, the leader is 1-based with
    0 unknown, a lease is monotonic ns with 0 for none, and a lease that
    cannot be read (NaN in a column, a raise in the loop) is 0."""
    G = 300
    for side in ("loop", "bulk"):
        os.makedirs(tmp_path / side)
    loop = ShmSnapshotPublisher(str(tmp_path / "loop"), G, size=2 << 20)
    bulk = ShmSnapshotPublisher(str(tmp_path / "bulk"), G, size=2 << 20)
    rng = np.random.default_rng(7)
    try:
        for pub in (loop, bulk):
            pub.start(lambda g: None, lambda g: 0)
            pub.publish_deltas({5: [(SCHEMA, 1)], 9: [(SCHEMA, 3)]})
        for round_ in range(4):
            commit = rng.integers(0, 50, G)
            leader = rng.integers(-1, 3, G)
            lease = np.where(rng.random(G) < 0.5,
                             time.monotonic() + rng.random(G), 0.0)
            lease[rng.random(G) < 0.1] = -1.0
            broken = rng.random(G) < 0.05
            lease[broken] = math.nan

            def lease_of(g):
                if broken[g]:
                    raise RuntimeError("no lease column")
                return float(lease[g])
            loop.refresh(lambda g: int(commit[g]), lambda g: int(leader[g]),
                         lease_of)
            bulk.refresh_columns(commit, leader, lease)
            assert mapping_rows(loop) == mapping_rows(bulk), round_
        rows = mapping_rows(bulk)
        assert rows[5][0] == 1 and rows[9][0] == 3      # applied kept
        assert all(r[3] == 0 for r, b in zip(rows, broken) if b)
        assert any(r[3] > 0 for r in rows)
        assert bulk.table_snapshot()[3] == [r[:5] for r in rows]
    finally:
        loop.close()
        bulk.close()


def test_the_nodes_columns_are_its_per_group_answers(monkeypatch):
    """ClusterHostPlane.shm_columns (the served engine's bulk refresh)
    and its per-group commit_watermark / leader_of / lease_deadline_s
    read the same arrays the same way."""
    from raftsql_tpu.runtime import hostplane
    P, G = 3, 50
    rng = np.random.default_rng(11)
    node = hostplane.ClusterHostPlane.__new__(hostplane.ClusterHostPlane)
    node.cfg = types.SimpleNamespace(num_groups=G, num_peers=P,
                                     lease_ticks=4, max_clock_skew=1,
                                     tick_interval_s=0.01)
    node._steps = 4
    node._hints = rng.integers(-1, P, G).astype(np.int32)
    node._hard = rng.integers(0, 100, (P, G, 3)).astype(np.int32)
    node._lease_col = rng.integers(0, 40, (P, G)).astype(np.int32)
    node._device_steps = 20
    monkeypatch.setattr(hostplane.time, "monotonic", lambda: 1000.0)
    commit, leader, lease = node.shm_columns()
    assert commit.tolist() == [node.commit_watermark(g) for g in range(G)]
    assert leader.tolist() == [node.leader_of(g) for g in range(G)]
    assert lease.tolist() == [node.lease_deadline_s(g) for g in range(G)]
    assert (lease > 0).any() and (lease == 0).any()
    node.cfg.lease_ticks = 0
    assert not node.shm_columns()[2].any()
    status = node.status()
    for g in range(G):
        p = int(node._hints[g])
        assert status[str(g)]["leader"] == p + 1
        assert status[str(g)]["commit"] == (int(node._hard[p, g, 2])
                                            if p >= 0 else 0)
    assert struct.calcsize("<QQQQIi") == shm._ROW_DTYPE.itemsize


class BigReplies:
    """A RaftDB stand-in whose reads and documents are as long as asked."""

    num_groups = 1

    def __init__(self):
        self.health = "h" * (5 * RING)

    def query(self, sql, group, **kw):
        n = int(sql.split()[1])
        return "x" * n + sql

    def watermark(self, group):
        return 7

    def render_health(self):
        return self.health

    render_metrics = render_members = render_trace = render_events = \
        render_health


def test_replies_of_any_length_arrive_whole(tmp_path, monkeypatch):
    """Concurrent replies on one worker's completion ring of RING bytes,
    from a few bytes to five rings: each arrives whole, with its
    watermark.  A record longer than half the ring could find no room
    at an unlucky position of the region even in an empty ring, so such
    replies go in pieces."""
    import threading

    from raftsql_tpu.runtime.ring import RingClient, RingServer
    monkeypatch.setenv("RAFTSQL_SHM_READS", "0")
    srv = RingServer(BigReplies(), str(tmp_path), 1, ring_bytes=RING)
    srv.start()
    rc = RingClient(str(tmp_path), 0)
    sizes = [10, RING // 3, RING // 2 - 40, RING // 2 + 1000,
             RING - 100, 3 * RING]
    errors = []

    def reads(i):
        for k in range(6):
            n = sizes[(i + k) % len(sizes)]
            sql = f"SELECT {n} -- {i}.{k}"
            try:
                got = rc.query(sql, timeout=20)
                assert got == "x" * n + sql and rc.watermark(0) == 7
            except Exception as e:                     # noqa: BLE001
                errors.append(repr(e)[:200])
    try:
        threads = [threading.Thread(target=reads, args=(i,))
                   for i in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not errors, errors[:3]
        assert rc.render_health() == srv.rdb.health
    finally:
        rc.close()
        srv.stop()
