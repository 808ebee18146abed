"""The telemetry plane's stages and counters (raftsql_tpu/obs/prof.py):
a write's and a read's stages from the socket to the socket, the host
plane's intake, the WAL's work, the shm plane's fallback reasons, the
finer tick phases and the named scopes of the device step.

The served checks run against ONE `--fused --workers 2 --groups 8`
server on the CPU (module fixture): every write goes down one keep-alive
connection, so one worker carries them all and that connection's
/metrics is that worker's document.
"""
import http.client
import importlib.util
import json
import os
import signal
import socket
import subprocess
import sys
import time

import pytest

from raftsql_tpu.config import RaftConfig
from raftsql_tpu.obs import prof as prof_mod
from raftsql_tpu.runtime.fused import FusedClusterNode

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GROUPS = 8
WRITES = 48                     # after the 8 CREATEs


def _load_check_prom():
    spec = importlib.util.spec_from_file_location(
        "check_prom", os.path.join(REPO, "scripts", "check_prom.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def mkcfg(groups=4):
    return RaftConfig(num_groups=groups, num_peers=3, log_window=32,
                      max_entries_per_msg=4, election_ticks=10,
                      heartbeat_ticks=1, tick_interval_s=0.0)


def elect(node, max_ticks=200):
    for t in range(max_ticks):
        node.tick()
        if t > 10 and (node._hints >= 0).all():
            return
    raise AssertionError("no full leadership within budget")


# -- the served path ---------------------------------------------------

class Served:
    """The server, the one connection the writes went down, and what it
    was told."""

    def __init__(self, proc, port):
        self.proc, self.port = proc, port
        self.conn = http.client.HTTPConnection("127.0.0.1", port,
                                               timeout=30)
        self.acked = 0

    def request(self, method, body="", headers=None, path="/", conn=None):
        conn = conn or self.conn
        conn.request(method, path, body=body, headers=headers or {})
        r = conn.getresponse()
        return r.status, dict(r.getheaders()), r.read().decode()

    def put(self, sql, group):
        status, _h, text = self.request(
            "PUT", sql, {"X-Raft-Group": str(group)})
        assert status == 204, (status, text)
        self.acked += 1

    def metrics(self, conn=None):
        status, _h, text = self.request("GET", path="/metrics", conn=conn)
        assert status == 200
        return json.loads(text)

    def settled(self):
        """The writing worker's document once every stage has counted
        every acknowledged write (edge_out is stamped just after the
        response is handed to the transport)."""
        deadline = time.monotonic() + 10
        while True:
            doc = self.metrics()
            if doc["worker_stages"]["put"]["edge_out"]["n"] >= self.acked \
                    or time.monotonic() > deadline:
                return doc
            time.sleep(0.05)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("stages")
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("RAFTSQL_PROF", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    log = open(os.path.join(str(tmp), "server.log"), "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "raftsql_tpu.server.main", "--fused",
         "--workers", "2", "--groups", str(GROUPS), "--peers", "3",
         "--port", str(port), "--tick", "0.004"],
        cwd=str(tmp), env=env, stdout=log, stderr=subprocess.STDOUT)
    sv = None
    try:
        from raftsql_tpu.api.client import RaftSQLClient
        client = RaftSQLClient([port], timeout_s=10)
        client.wait_healthy(0, deadline_s=120)
        client.close()
        sv = Served(proc, port)
        for g in range(GROUPS):
            sv.put("CREATE TABLE t (k text primary key, v text)", g)
        for i in range(WRITES):
            sv.put(f"INSERT OR REPLACE INTO t (k, v) VALUES "
                   f"('k{i}', 'v{i}')", i % GROUPS)
        yield sv
    finally:
        if sv is not None:
            sv.conn.close()
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=10)
        log.close()


def test_metrics_document_holds_the_new_keys(served):
    doc = served.settled()
    assert set(doc["stages"]["put"]) == {"engine", "propose_commit",
                                         "apply", "apply_batch"}
    assert set(doc["stages"]["get"]) == {"queue", "wait", "sql"}
    assert set(doc["apply"]) == {"runs", "groups", "fanout_runs",
                                 "native_txns", "python_txns"}
    assert set(doc["intake"]) == {"backlog", "offered", "accepted",
                                  "groups", "committed_in_dispatch"}
    assert set(doc["dispatch"]) == {"steps"}
    assert set(doc["wal"]) == {"records", "bytes", "hardstates",
                               "groups_written", "fsyncs", "shard_syncs",
                               "mirror_rows", "mirror_fallback_rows",
                               "mirror_skipped_rows", "segments_unlinked",
                               "segments_pinned", "disk_bytes"}
    assert set(doc["compact"]) == {"sweeps", "floors_advanced", "rounds",
                                   "files"}
    assert set(doc["stages"]["compact"]) == {"sweep", "checkpoint", "file"}
    assert set(doc["sm"]) == {"opens", "closes", "evictions",
                              "open_handles", "uses", "misses",
                              "native_reopens", "python_reopens"}
    assert set(doc["stages"]["sm"]) == {"miss", "reopen", "release"}
    assert set(doc["stages"]["publish"]) == {"queue"}
    assert "mesh_put" not in doc["phase_profile"]   # the mesh's alone
    assert {"launch", "readback", "wal_plan", "wal_append",
            "wal_hardstate", "dispatch", "wal_write", "epoch_commit"} \
        <= set(doc["phase_profile"])
    assert set(doc["worker_stages"]["put"]) == {"edge_in", "ring_rtt",
                                                "edge_out"}
    assert set(doc["worker_stages"]["get"]) == {"ring_rtt", "shm_snapshot"}
    assert "shm_fallback_reasons" in doc["reads"]
    # The serving plane's own: the shm refresh, the documents the
    # engine made and the ring records their replies took, and the
    # engine's boot on its own clock.
    assert set(doc["stages"]["shm"]) == {"refresh"}
    assert set(doc["stages"]["doc"]) == {"render"}
    assert doc["stages"]["doc"]["render"]["n"] >= 1
    assert doc["doc"]["records"] >= doc["stages"]["doc"]["render"]["n"]
    assert doc["stages"]["shm"]["refresh"]["n"] > 0
    assert 0 < doc["boot"]["replay_s"] <= doc["boot"]["all_led_s"]
    for pair in doc["stages"]["put"].values():
        assert set(pair) == {"total_ms", "n", "max_ms"}
    assert doc["phase_profile"]["sample"] == 1


def test_put_stage_pairs_tile_and_count_every_write(served):
    doc = served.settled()
    put, wput = doc["stages"]["put"], doc["worker_stages"]["put"]
    for pair in (put["engine"], put["propose_commit"], put["apply"],
                 wput["edge_in"], wput["ring_rtt"], wput["edge_out"]):
        assert pair["n"] == served.acked, (pair, served.acked)
        assert 0 < pair["total_ms"] and pair["max_ms"] <= pair["total_ms"]
    # The engine's residence holds its two inner legs; a worker's round
    # trip holds the engine's residence (each request's stamps nest, so
    # the sums do; 1 us of slack per request for the rounding to ms).
    slack = 1e-3 * served.acked
    assert put["engine"]["total_ms"] + slack >= \
        put["propose_commit"]["total_ms"] + put["apply"]["total_ms"]
    assert wput["ring_rtt"]["total_ms"] + slack >= put["engine"]["total_ms"]


def test_intake_counts_every_accepted_entry(served):
    doc = served.settled()
    intake = doc["intake"]
    assert intake["accepted"] == served.acked       # queues are empty
    assert intake["accepted"] <= intake["offered"] <= intake["backlog"]
    assert 0 < intake["groups"] <= intake["offered"]
    assert doc["proposals"] == intake["accepted"]
    # The served node dispatches the pipeline's depth, so what a
    # dispatch accepts at its first step commits inside it.
    assert doc["dispatch"]["steps"] in (4 * doc["ticks"],
                                        4 * doc["ticks"] + 4)
    assert 0 < intake["committed_in_dispatch"] <= intake["accepted"]
    assert doc["phase_profile"]["epoch_commit"]["n"] > 0


def _wrote(wal):
    """The wal.* counters of what was WRITTEN (mirror_skipped_rows
    counts the empty heartbeat acks, which idle ticks have too)."""
    return {k: v for k, v in wal.items() if k != "mirror_skipped_rows"}


def test_wal_counters_grow_only_on_ticks_that_write(served):
    a = served.settled()
    # Every entry lands in 3 peers' logs (+ one no-op a group).
    assert a["wal"]["records"] == 3 * (served.acked + GROUPS)
    assert a["wal"]["bytes"] > 100 * served.acked
    assert a["wal"]["hardstates"] >= 3 * GROUPS
    assert 0 < a["wal"]["fsyncs"] < a["ticks"]
    time.sleep(0.5)                     # idle: heartbeats only
    b = served.metrics()
    assert b["ticks"] > a["ticks"]
    assert _wrote(b["wal"]) == _wrote(a["wal"])
    assert b["intake"] == a["intake"]
    # An idle tick hands the mirror nothing: every follower's (empty)
    # heartbeat ack is counted and dropped before it is listed.  What
    # the mirror was handed (the same after the idle ticks, as _wrote
    # held) were the entries on their way into two followers' logs,
    # and the served deployment's payload log is the Python one, so
    # each took the Python mirror.
    assert b["wal"]["mirror_skipped_rows"] > a["wal"]["mirror_skipped_rows"]
    assert b["wal"]["mirror_rows"] > 0
    assert b["wal"]["mirror_fallback_rows"] == b["wal"]["mirror_rows"]
    assert b["wal"]["shard_syncs"] == 0     # one stream, no shards


def test_apply_counters_count_runs_and_their_groups(served):
    doc = served.settled()
    apply, batch = doc["apply"], doc["stages"]["put"]["apply_batch"]
    # One apply_batch a group a run; every acknowledged write was in one.
    assert batch["n"] == apply["groups"]
    assert 0 <= apply["fanout_runs"] <= apply["runs"] <= apply["groups"] \
        <= served.acked
    # A group's batch by the arm that committed it: the one native call
    # wherever the library loaded (no statement of this file fails).
    assert apply["native_txns"] + apply["python_txns"] == apply["groups"]
    status, _h, text = served.request("GET", path="/healthz")
    assert status == 200
    if json.loads(text)["native_apply"]:
        assert apply["python_txns"] == 0
    assert 0 < batch["total_ms"] and batch["max_ms"] <= batch["total_ms"]


def test_read_stages_and_fallback_reasons(served):
    before = served.settled()
    # A session read whose watermark is ahead of what is applied: the
    # mapping cannot prove it, so the ring path waits (and times out).
    status, _h, _t = served.request(
        "GET", "SELECT count(*) FROM t",
        {"X-Raft-Group": "1", "X-Consistency": "session",
         "X-Raft-Session": "999999", "X-Raft-Deadline-Ms": "200"})
    assert status == 503
    for mode in ("linear", "session", "follower", "local"):
        status, _h, text = served.request(
            "GET", "SELECT count(*) FROM t",
            {"X-Raft-Group": "2", "X-Consistency": mode})
        assert status == 200 and text.strip() == "|6|", (mode, text)
    after = served.metrics()
    reasons = after["reads"]["shm_fallback_reasons"]
    assert reasons["behind_watermark"] == \
        before["reads"]["shm_fallback_reasons"]["behind_watermark"] + 1
    assert sum(reasons.values()) == after["reads"]["shm_fallbacks"]
    assert after["reads"]["shm_hits"] + after["reads"]["shm_fallbacks"] \
        == before["reads"]["shm_hits"] \
        + before["reads"]["shm_fallbacks"] + 5
    # What fell back went over the ring: queue counts each, wait and
    # sql the ones that were answered.
    fell = after["reads"]["shm_fallbacks"] \
        - before["reads"]["shm_fallbacks"]
    get_b, get_a = before["stages"]["get"], after["stages"]["get"]
    assert fell >= 1
    assert get_a["queue"]["n"] - get_b["queue"]["n"] == fell
    assert get_a["sql"]["n"] - get_b["sql"]["n"] == fell - 1
    assert get_a["wait"]["n"] == get_a["sql"]["n"]
    wget_b = before["worker_stages"]["get"]["ring_rtt"]
    wget_a = after["worker_stages"]["get"]["ring_rtt"]
    assert wget_a["n"] - wget_b["n"] == fell
    assert wget_a["total_ms"] - wget_b["total_ms"] >= 150   # the timeout


def test_prom_round_trips_through_a_worker(served):
    check_prom = _load_check_prom()
    status, _h, text = served.request("GET", path="/metrics?format=prom")
    assert status == 200
    samples = check_prom.parse_prom(text)
    assert not check_prom.check_round_trip(served.metrics(), samples)
    names = {name for name, _labels in samples}
    assert {"raftsql_stages_put_engine_total_ms",
            "raftsql_worker_stages_put_edge_in_n",
            "raftsql_intake_accepted", "raftsql_wal_bytes",
            "raftsql_stages_put_apply_batch_total_ms",
            "raftsql_stages_put_apply_batch_n", "raftsql_apply_runs",
            "raftsql_apply_groups", "raftsql_apply_fanout_runs",
            "raftsql_reads_shm_fallback_reasons_log_full"} <= names
    assert ("raftsql_tick_phase_ms_count",
            frozenset({("phase", "wal_hardstate")})) in samples


def test_worker_processes_never_import_jax(served):
    """The workers record their stages without loading JAX: no jaxlib
    object is mapped into either worker process (and the engine's own
    maps show that the probe can see one)."""
    def maps(pid):
        with open(f"/proc/{pid}/maps") as f:
            return f.read()

    workers = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/stat") as f:
                ppid = int(f.read().rsplit(")", 1)[1].split()[1])
            with open(f"/proc/{pid}/cmdline") as f:
                cmd = f.read()
        except OSError:
            continue
        if ppid == served.proc.pid and "raftsql_tpu.server.worker" in cmd:
            workers.append(int(pid))
    assert len(workers) == 2, workers
    assert "jaxlib" in maps(served.proc.pid)
    for pid in workers:
        assert "jaxlib" not in maps(pid), f"worker {pid} loaded jaxlib"
    # And by sys.modules, for everything a worker's main() imports.
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys\n"
         "import raftsql_tpu.server.worker\n"
         "from raftsql_tpu.api.aio import AioSQLServer\n"
         "from raftsql_tpu.runtime.ring import RingClient\n"
         "from raftsql_tpu.runtime.shm import ShmSnapshotReader\n"
         "from raftsql_tpu.obs.export import TraceSegmentWriter\n"
         "from raftsql_tpu.utils.metrics import prom_render\n"
         "print('jax' in sys.modules)\n"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        stdout=subprocess.PIPE, text=True, timeout=60)
    assert r.stdout.strip() == "False", r.stdout


# -- in process ----------------------------------------------------------

def test_apply_series_are_in_the_document_from_boot(tmp_path):
    """Before the first write: the run counters and the apply_batch pair
    are there and zero, in the JSON and in the exposition."""
    from raftsql_tpu.models.sqlite_sm import SQLiteStateMachine
    from raftsql_tpu.runtime.db import RaftDB
    from raftsql_tpu.runtime.fused import FusedPipe

    node = FusedClusterNode(mkcfg(groups=2), str(tmp_path / "data"))
    rdb = RaftDB(lambda g: SQLiteStateMachine(
        str(tmp_path / f"g{g}.db")), FusedPipe(node), num_groups=2)
    try:
        doc = json.loads(rdb.render_metrics())
        assert doc["apply"] == {"runs": 0, "groups": 0, "fanout_runs": 0,
                                "native_txns": 0, "python_txns": 0}
        assert doc["stages"]["put"]["apply_batch"] == {
            "total_ms": 0.0, "n": 0, "max_ms": 0.0}
        check_prom = _load_check_prom()
        samples = check_prom.parse_prom(rdb.render_metrics_prom())
        assert not check_prom.check_round_trip(doc, samples)
        for name in ("raftsql_apply_runs", "raftsql_apply_groups",
                     "raftsql_apply_fanout_runs",
                     "raftsql_stages_put_apply_batch_n"):
            assert samples[(name, frozenset())] == 0
    finally:
        rdb.close()


def test_prof_off_leaves_the_new_keys_out(tmp_path, monkeypatch):
    """RAFTSQL_PROF=0: no profiler in the engine, nothing recorded, no
    new key in the engine's document nor in what a worker folds in."""
    from raftsql_tpu.models.sqlite_sm import SQLiteStateMachine
    from raftsql_tpu.runtime.db import RaftDB
    from raftsql_tpu.runtime.fused import FusedPipe
    from raftsql_tpu.runtime.ring import RingClient, RingServer

    monkeypatch.setenv("RAFTSQL_PROF", "0")
    node = FusedClusterNode(mkcfg(groups=2), str(tmp_path / "data"))
    assert node.prof is None
    node.start(interval_s=0.0005)
    rdb = RaftDB(lambda g: SQLiteStateMachine(
        str(tmp_path / f"g{g}.db")), FusedPipe(node), num_groups=2)
    srv = RingServer(rdb, str(tmp_path / "rings"), workers=1)
    srv.start()
    rc = RingClient(str(tmp_path / "rings"), 0)
    try:
        assert rc.stages is None
        assert rc.propose("CREATE TABLE t (v text)").wait(30) is None
        assert rc.query("SELECT count(*) FROM t",
                        mode="linear").strip() == "|0|"
        doc = json.loads(rc.render_metrics())
        for key in ("stages", "intake", "wal", "apply", "phase_profile",
                    "worker_stages"):
            assert key not in doc, key
        assert "shm_fallback_reasons" not in doc["reads"]
        assert doc["reads"]["shm_hits"] + doc["reads"]["shm_fallbacks"] \
            == 1
        assert doc["proposals"] == 1
    finally:
        rc.close()
        srv.stop()
        rdb.close()


def test_finer_phases_keep_dispatch_attribution(tmp_path):
    """launch/readback belong to the tick that dispatched; wal_plan/
    wal_append/wal_hardstate to the tick that OWNS the durable phase,
    as wal_write and fsync do, which since PR 39 is the tick that
    dispatched it too; each part inside its whole."""
    node = FusedClusterNode(mkcfg(groups=2), str(tmp_path / "d"))
    try:
        elect(node)
        for i in range(6):
            node.propose_many(0, [f"SET a{i} v".encode()])
            node.tick()
        for _ in range(6):
            node.tick()
        node.publish_flush()
        p = node.prof
        got = {ph: p.phase_ticks(ph) for ph in (
            "launch", "readback", "wal_write", "wal_plan",
            "wal_append", "wal_hardstate", "fsync")}
        snap = p.snapshot()
        ticks = node.metrics.ticks
    finally:
        node.stop()
    assert got["launch"] == got["readback"] == list(range(ticks))
    assert got["wal_plan"] == got["wal_append"] \
        == got["wal_hardstate"] == got["wal_write"] == got["fsync"]
    assert got["wal_write"] and set(got["wal_write"]) <= set(range(ticks))
    assert snap["launch"]["n"] == snap["readback"]["n"] == ticks
    assert snap["dispatch"]["n"] == 2 * ticks
    assert abs(snap["dispatch"]["total_ms"] - snap["launch"]["total_ms"]
               - snap["readback"]["total_ms"]) < 0.01
    parts = sum(snap[ph]["total_ms"] for ph in (
        "wal_plan", "wal_append", "wal_hardstate"))
    assert 0 < parts <= snap["wal_write"]["total_ms"] * 1.05 + 0.5
    assert "launch_share" not in p.shares()


def test_intake_and_wal_counters_in_process(tmp_path):
    """accepted <= offered on every tick, equal to the entries proposed
    once the queues drain; wal.* moves only with a tick that writes."""
    node = FusedClusterNode(mkcfg(groups=4), str(tmp_path))
    try:
        elect(node)

        def counters():
            doc = node.prof.counters_doc()
            return doc["intake"], _wrote(doc["wal"])

        for _ in range(5):              # the no-ops reach every peer
            node.tick()
        node.publish_flush()
        base_i, base_w = counters()
        assert base_i["accepted"] == 0 and base_w["records"] == 3 * 4
        # Idle ticks: heartbeats only, nothing to count.
        for _ in range(5):
            node.tick()
        node.publish_flush()
        assert counters() == (base_i, base_w)
        # 11 entries on one group at E=4 an offer: three ticks' worth.
        node.propose_many(2, [f"SET k{i} v".encode() for i in range(11)])
        seen = []
        for _ in range(8):
            node.tick()
            i, _w = counters()
            assert i["accepted"] <= i["offered"] <= i["backlog"]
            seen.append(i["accepted"])
        node.publish_flush()
        i, w = counters()
        assert i["accepted"] == 11 and seen[0] == 4
        assert i["offered"] == 11 and i["backlog"] == 11 + 7 + 3
        assert i["groups"] == 3
        assert w["records"] == base_w["records"] + 3 * 11
        assert w["bytes"] > base_w["bytes"] + 3 * 11 * len(b"SET k0 v")
        assert w["groups_written"] > base_w["groups_written"]
        assert w["hardstates"] > base_w["hardstates"]
        assert w["fsyncs"] > base_w["fsyncs"]
    finally:
        node.stop()


def test_lowered_cluster_step_holds_every_scope_name():
    import jax.numpy as jnp

    from raftsql_tpu.core import cluster
    from raftsql_tpu.core.step import STEP_SCOPES

    cfg = mkcfg(groups=2)
    text = cluster.cluster_step_host.lower(
        cfg, cluster.init_cluster_state(cfg),
        cluster.empty_cluster_inbox(cfg),
        jnp.zeros((3, 2), jnp.int32)).as_text(debug_info=True)
    for name in STEP_SCOPES + ("cluster_step", "raft.deliver",
                               "raft.pack"):
        assert f"/{name}" in text or f"({name})" in text, name


def test_compiled_step_ops_join_their_scopes():
    """obs/scopes.py: the map a trace's operation names are looked up
    in.  Every phase of the step owns instructions of the COMPILED
    program (compiled anew: a cached one carries the metadata of
    whoever compiled it first), and a trace's operations sum by it."""
    from raftsql_tpu.core.step import STEP_SCOPES
    from raftsql_tpu.obs import scopes

    text = """
HloModule jit_cluster_step_host
%fused_computation.3 (p: s32[3,2]) -> s32[3,2] {
  ROOT %add.7 = s32[3,2]{1,0} add(%p, %p), metadata={op_name="jit(cluster_step_host)/cluster_step/vmap(raft.commit)/add" stack_frame_id=4}
}
ENTRY %main.9 (a: s32[3,2]) -> s32[3,2] {
  %a = s32[3,2]{1,0} parameter(0), metadata={op_name="states.term"}
  %fusion.44 = s32[3,2]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(cluster_step_host)/cluster_step/vmap(raft.outbox)/reduce_max"}
  %copy.1 = s32[3,2]{1,0} copy(%fusion.44), metadata={op_name="jit(cluster_step_host)/cluster_step/raft.deliver/transpose"}
  ROOT %select_reduce_fusion = s32[3,2]{1,0} fusion(%copy.1), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(cluster_step_host)/cluster_step/mul"}
}
"""
    assert scopes.op_scopes(text) == {
        "add.7": "raft.commit", "fusion.44": "raft.outbox",
        "copy.1": "raft.deliver", "select_reduce_fusion": "cluster_step"}
    assert scopes.scope_of("jit(cluster_step_host)/jit(main)/mul") is None
    got = scopes.step_scopes(2, 3)
    assert set(got.values()) >= set(STEP_SCOPES) | {"raft.deliver",
                                                    "raft.pack"}
    ops = [["jit_cluster_step_host(1)", 0.5]] + [
        [name, 0.001] for name in list(got)[:40]]
    sums = dict(scopes.by_scope(ops, got))
    assert sums.pop("jit_cluster_step_host(1)") == 0.5
    assert set(sums) <= set(got.values())
    assert sum(sums.values()) == pytest.approx(0.040)


# -- the readers of the apply counters (benchmarks/layers/) -------------

def _apply_scrape(k, groups, fanned, batch_ms, has=True):
    """A scrape after k runs of `groups` groups each, `fanned` of every
    four fanned out; `has=False` is a program from before the counters."""
    doc = {"ticks": 10 * k, "stages": {"put": {"apply": {
        "total_ms": 50.0 * k, "n": 5 * k, "max_ms": 20.0}}}}
    if has:
        doc["apply"] = {"runs": k, "groups": groups * k,
                        "fanout_runs": fanned * k // 4}
        doc["stages"]["put"]["apply_batch"] = {
            "total_ms": batch_ms * groups * k, "n": groups * k,
            "max_ms": 2 * batch_ms}
    return {"t": float(k), "engine": doc, "workers": [doc]}


@pytest.mark.parametrize("name,want", [
    ("apply_batch_ms", 12.5),           # 12.5 ms a group's batch
    ("apply_groups_per_run", 7.0),      # 280 groups / 40 runs
    ("apply_fanout_pct", 75.0),         # 30 of the window's 40 runs
])
def test_apply_readers_on_a_pair_of_scrapes(monkeypatch, name, want):
    monkeypatch.syspath_prepend(os.path.join(REPO, "benchmarks"))
    reader = importlib.import_module("layers." + name)
    before, after = (_apply_scrape(k, 7, 3, 12.5) for k in (20, 60))
    assert reader.read(before, after, {}, None) == pytest.approx(want)
    # Nothing applied in the window; a parent's scrapes (no such keys).
    assert reader.read(after, after, {}, None) is None
    old = [_apply_scrape(k, 7, 3, 12.5, has=False) for k in (20, 60)]
    assert reader.read(old[0], old[1], {}, None) is None
    assert reader.read(old[0], after, {}, None) is None


# -- the mechanism -------------------------------------------------------

def test_stage_set_pairs():
    s = prof_mod.StageSet(("put.engine", "get.sql", "get.wait"))
    assert s.stages_doc()["put"] == {"engine": {
        "total_ms": 0.0, "n": 0, "max_ms": 0.0}}
    s.stage("put.engine", 0.002)
    s.stage("put.engine", 0.005)
    s.stage("get.sql", 0.001)
    s.stage_many((("get.sql", 0.003), ("get.wait", 0.004)))
    doc = s.stages_doc()
    assert doc["put"]["engine"] == {"total_ms": 7.0, "n": 2,
                                    "max_ms": 5.0}
    assert doc["get"]["sql"] == {"total_ms": 4.0, "n": 2, "max_ms": 3.0}
    assert doc["get"]["wait"]["n"] == 1
    for _ in range(prof_mod.FOLD_AT + 5):   # folds on the way, loses none
        s.stage("put.later", 0.001)
    assert s.stages_doc()["put"]["later"]["n"] == prof_mod.FOLD_AT + 5
    assert len(s._new_stages) == 0


def test_stage_set_loses_no_update_under_contention():
    """More recording threads than cores, a short switch interval: every
    stage and count must land (a lost read-modify-write would leave
    n or the counter short)."""
    import threading

    s = prof_mod.TickPhaseProfiler(cap=64)
    threads, per = 4 * (os.cpu_count() or 2), 2000
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(per):
                s.stage("put.engine", 0.001)
                s.stage_many((("put.apply", 0.001),))
                s.record_tick(0, (), (("intake.accepted", 2),
                                      ("wal.bytes", 2)))

        ts = [threading.Thread(target=work) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    total = threads * per
    for name in ("engine", "apply"):
        pair = s.stages_doc()["put"][name]
        assert pair["n"] == total
        assert pair["total_ms"] == pytest.approx(total * 1.0, rel=1e-6)
    doc = s.counters_doc()
    assert doc["intake"]["accepted"] == doc["wal"]["bytes"] == 2 * total


def test_record_tick_takes_samples_and_counts_in_one_call():
    p = prof_mod.TickPhaseProfiler(cap=64)
    p.record_tick(7, (("dispatch", 1.0, 0.002), ("launch", 1.0, 0.002),
                      ("dispatch", 1.5, 0.001), ("readback", 1.5, 0.001)),
                  (("intake.backlog", 5), ("intake.accepted", 3)))
    p.record_tick(8, [("wal_write", 2.0, 0.004)], ())
    p.record_tick(9, [], (("wal.bytes", 100),))     # counts alone
    p.record("publish", 8, 2.5, 0.003, tid=2)
    snap = p.snapshot()
    assert snap["dispatch"]["n"] == 2 and snap["launch"]["n"] == 1
    assert snap["dispatch"]["total_ms"] == 3.0
    assert snap["wal_write"]["total_ms"] == 4.0
    assert "wal_plan" not in snap and "pop" not in snap
    assert p.phase_ticks("dispatch") == [7]
    assert p.phase_ticks("wal_write") == [8]
    doc = p.counters_doc()
    assert doc["intake"]["backlog"] == 5 and doc["intake"]["accepted"] == 3
    assert doc["wal"]["bytes"] == 100 and doc["wal"]["records"] == 0
    evs = p.events()
    assert [e["phase"] for e in evs] == ["dispatch", "launch", "dispatch",
                                         "readback", "wal_write",
                                         "publish"]
    assert evs[-1] == {"phase": "publish", "tick": 8, "t0": 2.5,
                       "dur": 0.003, "tid": 2}


def test_a_record_never_waits_for_a_scrape():
    """Records are deque appends: with the lock held (a scrape copying
    the rings) every recording call returns at once, and the next
    export holds what they brought."""
    import threading

    p = prof_mod.TickPhaseProfiler(cap=64)

    def work():
        p.record("pop", 1, 0.0, 0.001)
        p.record_tick(1, (("launch", 0.0, 0.002),),
                      (("wal.bytes", 7),))
        p.stage("put.engine", 0.003)
        p.stage_many([("put.apply", 0.004)])

    with p._mu:
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
    assert p.snapshot()["launch"]["total_ms"] == 2.0
    assert p.counters_doc()["wal"]["bytes"] == 7
    assert p.stages_doc()["put"]["apply"]["n"] == 1
    p.count((("apply.runs", 1), ("apply.groups", 3)))   # owned by no tick
    assert p.counters_doc()["apply"] == {
        "runs": 1, "groups": 3, "fanout_runs": 0, "native_txns": 0,
        "python_txns": 0}
    assert p.phase_ticks("launch") == [1]


def test_rings_wrap_and_exports_see_only_filled_slots():
    p = prof_mod.TickPhaseProfiler(cap=8)
    for i in range(3):
        p.record("pop", i, float(i), 0.001 * (i + 1))
    assert p.snapshot()["pop"]["max_ms"] == 3.0     # no empty slot's 0.0
    assert p.snapshot()["pop"]["p50_ms"] == 2.0
    assert len(p.events()) == 3
    for i in range(3, 20):
        p.record("pop", i, float(i), 0.001)
    snap = p.snapshot()["pop"]
    assert snap["n"] == 20 and snap["max_ms"] == 1.0
    assert p.phase_ticks("pop") == list(range(12, 20))
    assert len(p.events()) == 8


def test_span_annotates_only_while_a_session_runs():
    opened = []

    class Ann:
        on = True

        def __init__(self, name, **kw):
            self.name, self.kw = name, kw

        @classmethod
        def is_enabled(cls):
            return cls.on

        def __enter__(self):
            opened.append((self.name, self.kw, "in"))

        def __exit__(self, *exc):
            opened.append((self.name, self.kw, "out"))

    p = prof_mod.TickPhaseProfiler(cap=64)
    assert p.annotation() is None       # a bare profiler annotates nothing
    p._ann_cls = Ann
    ann = p.annotation()                # a tick's one test
    assert ann is Ann
    with prof_mod.span(ann, "tick.launch", 7):
        pass
    Ann.on = False                      # no profiler session
    assert p.annotation() is None
    with prof_mod.span(p.annotation(), "tick.pop", 8):
        pass
    assert prof_mod.span(None, "tick.pop", 8) is prof_mod.NO_SPAN
    assert opened == [("tick.launch", {"tick": 7}, "in"),
                      ("tick.launch", {"tick": 7}, "out")]


def test_engine_profiler_annotates_with_jax():
    p = prof_mod.TickPhaseProfiler.from_env()
    from jax.profiler import TraceAnnotation
    assert p._ann_cls is TraceAnnotation
    assert p.snapshot() == {"sample": 1}    # every tick is recorded
    assert not TraceAnnotation.is_enabled()     # no profiler session
    assert p.annotation() is None               # ... so nothing opens


def test_wal_written_only_grows_across_rotation(tmp_path):
    from raftsql_tpu.storage.wal import WAL

    w = WAL(str(tmp_path / "w"), segment_bytes=256)
    seen = [w.written()]
    for i in range(12):
        w.append_entry(0, i + 1, 1, b"x" * 40)
        w.sync()
        seen.append(w.written())
    w.sync()                            # nothing pending: not a barrier
    assert w.written() == seen[-1]
    w.close()
    assert len(os.listdir(str(tmp_path / "w"))) > 1     # it rotated
    assert all(b[0] > a[0] and b[1] == a[1] + 1
               for a, b in zip(seen, seen[1:]))
    assert seen[-1][0] - seen[0][0] >= 12 * 40


def test_a_reader_out_for_good_keeps_giving_its_cause(tmp_path):
    """Once the delta log has overflowed the reader is dead; every later
    miss is still counted `log_full`, so a window that opens after the
    overflow names the cause.  A region re-created by a restarted engine
    (another epoch) counts as `no_snapshot`."""
    from raftsql_tpu.runtime import shm

    pub = shm.ShmSnapshotPublisher(str(tmp_path), num_groups=2)
    pub.start(lambda g: None, lambda g: 0)
    reader = shm.ShmSnapshotReader(str(tmp_path))
    try:
        assert reader.try_read("local", 0, "SELECT 1") is not None
        with pub._lock:                     # as an overflowing append
            pub._full = True                # leaves it: flag in the header
            pub._publish_locked(lambda: None)
        for _ in range(3):
            assert reader.try_read("local", 0, "SELECT 1") is None
            assert reader.last_miss() == "log_full"
        assert reader.try_read("local", 0, "INSERT") is None
        assert reader.last_miss() == "not_select"
    finally:
        reader.close()
        pub.close()
