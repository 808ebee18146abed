"""The four-chip deployment (`--mesh --group-shards 4`), supported and
guarded at a small size on the 8 forced host devices of conftest.py:

  (a) SERVED: the mesh deployment and the `--fused` one take the same
      seeded YCSB-A-shaped statements over HTTP; every acknowledged
      write is read back `linear` and `follower`, before and after
      SIGKILL + restart on the same data directory, equal to a plain
      `sqlite3` per group fed the acknowledged statements;
  (b) PLACEMENT: after construction and after replay every leaf of
      `states` / `inboxes` lies on four distinct devices, each shard its
      quarter, and the constructor never lays a whole [P, G, ...] array
      committed to one device over the mesh;
  (c) the READERS of what the mesh adds (benchmarks/layers/), against
      hand-made scrapes and a hand-made trace;
  (d) the COUNTERS `wal.shard_syncs`, `wal.mirror_rows`,
      `wal.mirror_fallback_rows` and `wal.mirror_skipped_rows` against
      what a scripted tick wrote, on a MeshClusterNode and on a
      FusedClusterNode.
"""
import http.client
import importlib
import json
import os
import random
import signal
import socket
import sqlite3
import subprocess
import sys
import time
import zlib

import numpy as np
import pytest

import jax

from raftsql_tpu.config import RaftConfig
from raftsql_tpu.runtime.fused import FusedClusterNode
from raftsql_tpu.runtime.mesh import MeshClusterNode, MeshConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GROUPS, PEERS, SHARDS = 8, 3, 4
FIELDS = 10
SERVED_LIMIT_S = 300.0          # each served test's own time limit
SELECT = "SELECT * FROM usertable ORDER BY ycsb_key"


def cfg_for(groups=GROUPS, **kw):
    kw.setdefault("log_window", 32)
    kw.setdefault("max_entries_per_msg", 4)
    kw.setdefault("election_ticks", 10)
    kw.setdefault("heartbeat_ticks", 1)
    kw.setdefault("tick_interval_s", 0.0)
    return RaftConfig(num_groups=groups, num_peers=PEERS, seed=7, **kw)


def mesh4():
    return MeshConfig(peer_shards=1, group_shards=SHARDS).build()


def elect(node, max_ticks=300):
    for t in range(max_ticks):
        node.tick()
        if t > 10 and (node._hints >= 0).all():
            return
    raise AssertionError("no full leadership within budget")


# -- (a) the served deployments ------------------------------------------

def ycsb_a_statements(seed, keys_per_group=4, ops=64):
    """(schema, load, mix): YCSB's usertable (a key, ten fields) in every
    group, `keys_per_group` rows each, then `ops` operations, half reads
    of one row and half updates of one field, keys drawn with a skew.
    A key's group is a hash of the key, as the benchmark's clients
    route.  Each item is (kind, group, key, sql)."""
    rnd = random.Random(seed)
    cols = ", ".join(f"field{i} TEXT" for i in range(FIELDS))
    schema = [("w", g, None, f"CREATE TABLE usertable "
               f"(ycsb_key TEXT PRIMARY KEY, {cols})")
              for g in range(GROUPS)]
    keys, load = [], []
    n = 0
    while len(keys) < keys_per_group * GROUPS:
        key = f"user{n}"
        n += 1
        g = zlib.crc32(key.encode()) % GROUPS
        if sum(1 for _k, kg in keys if kg == g) >= keys_per_group:
            continue
        keys.append((key, g))
        vals = ", ".join(f"'{rnd.getrandbits(64):016x}'"
                         for _ in range(FIELDS))
        load.append(("w", g, key, f"INSERT INTO usertable VALUES "
                     f"('{key}', {vals})"))
    mix = []
    for _ in range(ops):
        key, g = keys[int(len(keys) * rnd.random() ** 3)]
        if rnd.random() < 0.5:
            mix.append(("r", g, key, f"SELECT * FROM usertable "
                        f"WHERE ycsb_key = '{key}'"))
        else:
            mix.append(("w", g, key, f"UPDATE usertable SET "
                        f"field{rnd.randrange(FIELDS)} = "
                        f"'{rnd.getrandbits(64):016x}' "
                        f"WHERE ycsb_key = '{key}'"))
    return schema, load, mix


def render(rows):
    return ["|" + "|".join(str(v) for v in row) + "|" for row in rows]


class Reference:
    """A plain sqlite3 per group, fed what the server acknowledged."""

    def __init__(self):
        self.dbs = {g: sqlite3.connect(":memory:") for g in range(GROUPS)}

    def apply(self, group, sql):
        self.dbs[group].execute(sql)

    def rows(self, group, sql=SELECT):
        return render(self.dbs[group].execute(sql).fetchall())

    def close(self):
        for db in self.dbs.values():
            db.close()


class Deployment:
    """`server.main <deploy flags> --workers 2 --groups 8 --peers 3` in
    `data_dir`, in a session of its own."""

    def __init__(self, flags, data_dir, deadline):
        self.flags, self.data_dir, self.deadline = flags, data_dir, deadline
        self.proc = self.conn = self.log = None
        self.port = 0

    def left(self):
        left = self.deadline - time.monotonic()
        assert left > 0, "the served test ran out of its time limit"
        return left

    def start(self):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        self.port = s.getsockname()[1]
        s.close()
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        self.log = open(os.path.join(self.data_dir, "server.log"), "ab")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "raftsql_tpu.server.main", *self.flags,
             "--workers", "2", "--groups", str(GROUPS), "--peers",
             str(PEERS), "--port", str(self.port), "--tick", "0.004"],
            cwd=self.data_dir, env=env, stdout=self.log,
            stderr=subprocess.STDOUT, start_new_session=True)
        while True:                     # ready, every group led
            assert self.proc.poll() is None, "the server exited at boot"
            try:
                doc = self.doc("/healthz")
                rows = doc.get("groups", {})
                if doc.get("ready") and len(rows) == GROUPS and all(
                        r.get("leader", 0) > 0 for r in rows.values()):
                    break
            except (OSError, http.client.HTTPException, ValueError):
                pass
            self.left()
            time.sleep(0.3)
        self.conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                               timeout=30)
        return doc

    def doc(self, path):
        c = http.client.HTTPConnection("127.0.0.1", self.port, timeout=10)
        try:
            c.request("GET", path)
            r = c.getresponse()
            body = r.read()
            assert r.status == 200, (path, r.status)
            return json.loads(body)
        finally:
            c.close()

    def request(self, method, group, sql, headers=None):
        h = {"X-Raft-Group": str(group)}
        h.update(headers or {})
        self.conn.request(method, "/", body=sql.encode(), headers=h)
        r = self.conn.getresponse()
        return r.status, {k.lower(): v for k, v in r.getheaders()}, \
            r.read().decode()

    def kill(self, sig):
        if self.conn is not None:
            self.conn.close()
            self.conn = None
        if sig == signal.SIGKILL:       # the workers with the engine
            os.killpg(self.proc.pid, sig)
        else:
            self.proc.send_signal(sig)
        rc = self.proc.wait(timeout=min(90.0, self.left()))
        self.log.close()
        return rc

    def destroy(self):
        if self.conn is not None:
            self.conn.close()
        if self.proc is not None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait(timeout=30)
        if self.log is not None and not self.log.closed:
            self.log.close()


def read_back(dep, ref, marks):
    """Every group's table, `linear` and `follower` (at the highest
    watermark a write to the group was acknowledged with), against the
    reference."""
    for g in range(GROUPS):
        for mode, extra in (("linear", {}), ("follower", {
                "X-Raft-Session": str(marks.get(g, 0))})):
            status, _h, body = dep.request(
                "GET", g, SELECT, dict(extra, **{"X-Consistency": mode}))
            assert status == 200, (mode, g, status, body)
            assert body.splitlines() == ref.rows(g), (mode, g)


@pytest.mark.parametrize("flags", [
    ("--mesh", "--group-shards", str(SHARDS)), ("--fused",)],
    ids=["mesh4", "fused"])
def test_served_deployment_keeps_every_acknowledged_write(tmp_path, flags):
    """(a): the same seeded statements, the same plain reference, through
    SIGKILL and restart, whatever the layout of the cluster over the
    devices."""
    dep = Deployment(flags, str(tmp_path),
                     time.monotonic() + SERVED_LIMIT_S)
    ref = Reference()
    marks = {}
    schema, load, mix = ycsb_a_statements(seed=2147483999)

    def write(g, sql):
        status, h, body = dep.request("PUT", g, sql)
        assert status == 204, (g, sql[:60], status, body)
        ref.apply(g, sql)
        marks[g] = max(marks.get(g, 0), int(h.get("x-raft-session", 0)))

    try:
        health = dep.start()
        if flags[0] == "--mesh":
            assert len(set(health["mesh"]["mesh_devices"])) == SHARDS
        for kind, g, key, sql in schema + load + mix:
            dep.left()
            if kind == "w":
                write(g, sql)
                continue
            # One client, so a linear read sees exactly the writes
            # acknowledged before it.
            status, _h, body = dep.request(
                "GET", g, sql, {"X-Consistency": "linear"})
            assert status == 200 and body.splitlines() == ref.rows(g, sql)
        assert sum(1 for k, *_ in mix if k == "w") >= 16
        read_back(dep, ref, marks)
        if flags[0] == "--mesh":
            mesh = dep.doc("/healthz")["mesh"]
            assert len(set(mesh["state_devices"])) == SHARDS
            assert mesh["state_shard_shape"] == [PEERS, GROUPS // SHARDS]
        assert dep.kill(signal.SIGKILL) == -signal.SIGKILL

        dep.start()                     # the same data directory
        read_back(dep, ref, marks)
        write(3, "UPDATE usertable SET field0 = 'after-restart'")
        read_back(dep, ref, marks)
        assert dep.kill(signal.SIGTERM) == 0
    finally:
        dep.destroy()
        ref.close()


# -- (b) where the boot state lies ---------------------------------------

def assert_quartered(node):
    """Every leaf on four distinct devices, each addressable shard the
    [P, G/4, ...] quarter of its leaf (the per-peer leaves `rng` and
    `tick`, which have no groups axis, whole on each)."""
    leaves = jax.tree.leaves((node.states, node.inboxes))
    assert len(leaves) > 30
    for x in leaves:
        assert len({s.device.id for s in x.addressable_shards}) == SHARDS
        want = list(x.shape)
        if x.ndim >= 2 and x.shape[1] == GROUPS:
            want[1] = GROUPS // SHARDS
        for s in x.addressable_shards:
            assert list(s.data.shape) == want, (x.shape, s.data.shape)
    assert list(node.states.commit.addressable_shards[0].data.shape) \
        == [PEERS, GROUPS // SHARDS]


@pytest.fixture
def whole_arrays_put(monkeypatch):
    """Every array handed to `jax.device_put` that is a whole
    [P, G, ...] leaf COMMITTED TO ONE DEVICE (what a constructor that
    builds the cluster on the default device and then re-lays it hands
    over); host arrays, which go straight to their shards, do not
    count."""
    seen = []
    real = jax.device_put

    def spy(x, *a, **k):
        for leaf in jax.tree.leaves(x):
            if isinstance(leaf, jax.Array) and leaf.ndim >= 2 \
                    and leaf.shape[:2] == (PEERS, GROUPS) \
                    and len(leaf.sharding.device_set) == 1:
                seen.append(leaf.shape)
        return real(x, *a, **k)

    monkeypatch.setattr(jax, "device_put", spy)
    return seen


def test_boot_state_is_born_sharded_fresh_and_replayed(tmp_path,
                                                       whole_arrays_put):
    """(b), and that the replayed state is the state that was written:
    the hard states and logs of the first life, on their shards."""
    node = MeshClusterNode(cfg_for(), str(tmp_path), mesh4())
    try:
        assert_quartered(node)
        elect(node)
        for g in range(GROUPS):
            node.propose_many(g, [f"SET k{g} v".encode()])
        for _ in range(12):
            node.tick()
        node.publish_flush()
        assert_quartered(node)
        commit = np.asarray(node.states.commit)
        log_len = np.asarray(node.states.log_len)
        term = np.asarray(node.states.term)
        assert (commit >= 2).all()      # the no-op and the entry
    finally:
        node.stop()
    again = MeshClusterNode(cfg_for(), str(tmp_path), mesh4())
    try:
        assert_quartered(again)
        assert (np.asarray(again.states.commit) == commit).all()
        assert (np.asarray(again.states.log_len) == log_len).all()
        assert (np.asarray(again.states.term) == term).all()
        elect(again)                    # and it runs from there
        assert_quartered(again)
    finally:
        again.stop()
    assert whole_arrays_put == []


def test_fused_boot_state_is_unchanged_by_the_seam(tmp_path):
    """The seam's default (one device) builds what it built before: the
    fresh cluster, and after a restart the replayed leaves over it."""
    from raftsql_tpu.core.cluster import init_cluster_state

    cfg = cfg_for(groups=4)
    node = FusedClusterNode(cfg, str(tmp_path))
    try:
        fresh = init_cluster_state(cfg, None)
        for a, b in zip(jax.tree.leaves(node.states),
                        jax.tree.leaves(fresh)):
            assert a.shape == b.shape and (np.asarray(a)
                                           == np.asarray(b)).all()
        elect(node)
        node.propose_many(1, [b"SET a b"])
        for _ in range(8):
            node.tick()
        node.publish_flush()
        commit = np.asarray(node.states.commit)
        timeout = np.asarray(node.states.timeout)
    finally:
        node.stop()
    again = FusedClusterNode(cfg, str(tmp_path))
    try:
        assert (np.asarray(again.states.commit) == commit).all()
        # What no replay decides is the fresh cluster's (same seed).
        assert (np.asarray(again.states.timeout)
                == np.asarray(fresh.timeout)).all()
        assert timeout.shape == (PEERS, 4)
    finally:
        again.stop()


# -- (c) the readers -----------------------------------------------------

def _scrape(k, mesh=True, prof=True):
    """A scrape after 10 k ticks: `mesh` a MeshClusterNode's document,
    else a FusedClusterNode's (no mesh_put pair; the counters there and
    zero); `prof=False` a program from before the pairs and counters."""
    doc = {"ticks": 10 * k, "phase_profile": {
        "launch": {"total_ms": 300.0 * k, "n": 10 * k}}}
    if prof:
        doc["wal"] = {"shard_syncs": 120 * k if mesh else 0,
                      "mirror_rows": 4000 * k,
                      "mirror_fallback_rows": 4000 * k if mesh else 1000 * k}
        doc["stages"] = {"publish": {"queue": {
            "total_ms": 6.0 * k, "n": 40 * k, "max_ms": 3.0}}}
        if mesh:
            doc["phase_profile"]["mesh_put"] = {"total_ms": 25.0 * k,
                                                "n": 10 * k}
    return {"t": 3.0 * k, "engine": doc, "workers": [doc]}


TRACE = {"devices": 4, "window_s": 3.0, "busy_s": 0.02,
         "collective_s": 0.0005,
         "device_ops": [["select_reduce_fusion", 0.012], ["pmax.6", 0.0004],
                        ["copy.300", 0.0003]]}


@pytest.mark.parametrize("name,want,fused", [
    ("mesh_collective_ms", 0.05, 0.0),   # 0.5 ms over the trace's 10 ticks
    ("tick_mesh_put_ms", 2.5, None),     # no such phase under --fused
    ("wal_shard_syncs_per_tick", 12.0, 0.0),
    ("wal_mirror_fallback_pct", 100.0, 25.0),
    ("publish_queue_ms", 0.15, 0.15),
])
def test_mesh_readers_on_a_pair_of_scrapes(monkeypatch, name, want, fused):
    monkeypatch.syspath_prepend(os.path.join(REPO, "benchmarks"))
    reader = importlib.import_module("layers." + name)
    before, after = _scrape(20), _scrape(60)        # 400 ticks, 120 s
    assert reader.read(before, after, {}, TRACE) == pytest.approx(want)
    # A FusedClusterNode's document, and its one-device trace.
    f0, f1 = _scrape(20, mesh=False), _scrape(60, mesh=False)
    got = reader.read(f0, f1, {}, dict(
        TRACE, devices=1, collective_s=0.0,
        device_ops=[["select_reduce_fusion", 0.05]]))
    assert got == (None if fused is None else pytest.approx(fused))
    # A program without the pairs and counters (the parent commit).
    old = [_scrape(k, prof=False) for k in (20, 60)]
    if name != "mesh_collective_ms":    # the trace's, not the program's
        assert reader.read(old[0], old[1], {}, TRACE) is None
        assert reader.read(old[0], after, {}, TRACE) is None
    # Nothing counted in the window; no trace; no ticks.
    if name == "mesh_collective_ms":
        assert reader.read(before, after, {}, None) is None
        assert reader.read(before, after, {}, {"window_s": 3.0,
                                               "busy_s": 0.0}) is None
        # The reducer saw no collective by name (the chip's trace names
        # the all-reduce `pmax.6`): the longest operations that bear a
        # collective primitive's name, 0.4 ms over 10 ticks.
        assert reader.read(before, after, {}, dict(
            TRACE, collective_s=0.0)) == pytest.approx(0.04)
    elif name != "wal_shard_syncs_per_tick":
        assert reader.read(after, after, {}, TRACE) is None
    assert reader.read(after, after, {}, None) is None


def test_new_readers_are_in_the_manifest_for_their_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    cell = "ycsb-a-10kgroups-mesh4"
    for name in ("mesh_collective_ms", "tick_mesh_put_ms",
                 "wal_shard_syncs_per_tick", "wal_mirror_fallback_pct"):
        assert by_name[name]["workloads"] == [cell]
        assert by_name[name]["moves"] == "write_p50_ms"
    assert "workloads" not in by_name["publish_queue_ms"]
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert cells[cell]["chips"] == 4
    assert cells[cell]["config"] == "multiraft-10k-mesh4"
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "multiraft-10k-mesh4.json")) as f:
        mesh_cfg = json.load(f)
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "multiraft-10k.json")) as f:
        one_cfg = json.load(f)
    assert mesh_cfg["argv"] == ["--mesh", "--group-shards", "4",
                                "--workers", "2", "--groups", "10000",
                                "--peers", "3"]
    assert mesh_cfg["env"] == {} and mesh_cfg["chips"] == 4
    for key in ("scale", "reduced", "reduced_why"):
        assert mesh_cfg[key] == one_cfg[key]
    # The guarantees are the one-chip deployment's but for the WAL's
    # layout.
    for key in ("linear_read", "session_read"):
        assert mesh_cfg["guarantees"][key] == one_cfg["guarantees"][key]
    assert "every dirty one fsynced before the ack" \
        in mesh_cfg["guarantees"]["flags"]
    assert "group-commit" not in json.dumps(mesh_cfg["guarantees"])


# -- (d) the counters ----------------------------------------------------

def _scripted(node):
    """Elect, settle, then one entry on each of groups 0 (shard 0) and
    5 (shard 2) and nothing else: what the counters counted for it."""
    elect(node)
    for _ in range(6):
        node.tick()
    node.publish_flush()
    base = node.prof.counters_doc()["wal"]
    ticks0 = node.metrics.ticks
    node.propose_many(0, [b"SET a 1"])
    node.propose_many(5, [b"SET b 2"])
    for _ in range(8):
        node.tick()
    node.publish_flush()
    now = node.prof.counters_doc()["wal"]
    return {k: now[k] - base[k] for k in now}, node.metrics.ticks - ticks0


def _mirror_counts_as_scripted(d, ticks):
    """Two followers a group accept an append every tick, a heartbeat's
    if nothing else.  The mirror is handed only those that carry an
    entry: the script's two entries, each into two followers' logs.
    The empty acks are counted and dropped before they are listed."""
    assert d["mirror_rows"] + d["mirror_skipped_rows"] \
        == 2 * GROUPS * ticks
    assert d["mirror_rows"] == 2 * 2


def test_mesh_counters_count_what_a_scripted_tick_wrote(tmp_path):
    node = MeshClusterNode(cfg_for(), str(tmp_path), mesh4())
    try:
        d, ticks = _scripted(node)
        snap = node.prof.snapshot()
        stages = node.prof.stages_doc()
    finally:
        node.stop()
    # Two entries, each in its leader's log and mirrored into two
    # followers': six records, in two of the four shards of each peer.
    assert d["records"] == 6
    # A barrier flushes only the streams that are dirty: per peer the
    # two shards that took an entry, and the same two again when the
    # commit index moved (a hard state) — never the two idle shards.
    assert 6 <= d["shard_syncs"] <= 2 * 2 * PEERS
    assert d["shard_syncs"] == d["fsyncs"]      # a shard is a WAL
    _mirror_counts_as_scripted(d, ticks)
    # One mirror: the rows it wrote are the rows it was handed.
    assert d["mirror_fallback_rows"] == d["mirror_rows"]
    # The mesh's own phase and the publish workers' stamp.
    assert snap["mesh_put"]["n"] == snap["launch"]["n"] > 0
    assert abs(snap["dispatch"]["total_ms"] - snap["launch"]["total_ms"]
               - snap["readback"]["total_ms"]
               - snap["mesh_put"]["total_ms"]) < 0.01
    assert stages["publish"]["queue"]["n"] > 0
    assert stages["publish"]["queue"]["n"] % SHARDS == 0    # a worker each


def test_fused_counters_count_what_a_scripted_tick_wrote(tmp_path):
    """The fused runtime: no shard streams, no `mesh_put`; the mirror
    counts as on the mesh."""
    node = FusedClusterNode(cfg_for(), str(tmp_path))
    try:
        d, ticks = _scripted(node)
        snap = node.prof.snapshot()
    finally:
        node.stop()
    assert d["records"] == 6
    assert d["shard_syncs"] == 0 and d["fsyncs"] > 0
    _mirror_counts_as_scripted(d, ticks)
    assert d["mirror_fallback_rows"] == d["mirror_rows"]
    assert "mesh_put" not in snap
    assert abs(snap["dispatch"]["total_ms"] - snap["launch"]["total_ms"]
               - snap["readback"]["total_ms"]) < 0.01
