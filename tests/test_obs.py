"""Observability subsystem (raftsql_tpu/obs/): device-plane event
ring, host-plane lifecycle spans, Chrome-trace (Perfetto) export, the
/trace and /events HTTP endpoints, the propose→commit histograms in
/metrics, and the chaos flight recorder — plus the PR 8 production
telemetry plane: the tick-phase profiler (overlap-aware attribution),
per-group traffic accounting (top-K hot groups), the Prometheus text
exposition on both HTTP planes, and the cross-process /trace merge of
a --workers deployment.

The schema checks here ARE the acceptance gate for "Perfetto accepts
the emitted JSON": validate_chrome_trace enforces the trace-event
object form (name/ph/ts/pid, X needs dur, C needs numeric args) that
both Perfetto and chrome://tracing require; scripts/check_prom.py's
parse_prom is the same gate for the Prometheus exposition.
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
from raftsql_tpu.obs.device_ring import EVENT_FIELDS
from raftsql_tpu.obs.export import chrome_trace, validate_chrome_trace
from raftsql_tpu.obs.spans import SpanTracer
from raftsql_tpu.runtime.fused import FusedClusterNode


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


@pytest.fixture
def traced_node(tmp_path):
    node = FusedClusterNode(mkcfg(), str(tmp_path))
    node.enable_tracing(ring_depth=16)
    yield node
    node.stop()


# -- device plane ------------------------------------------------------

def test_device_ring_records_every_tick(traced_node):
    node = traced_node
    elect(node)
    for g in range(node.cfg.num_groups):
        node.propose_many(g, [f"SET k{g} v{i}".encode()
                              for i in range(6)])
    for _ in range(20):
        node.tick()
    node.publish_flush()
    node.ring.drain()
    rows = node.ring.rows()
    assert len(rows) == node.metrics.ticks
    # Tick-indexed, in order, with a batch drain every ring_depth ticks.
    assert [r["tick"] for r in rows] == list(range(len(rows)))
    assert node.ring.drains >= len(rows) // 16
    last = rows[-1]
    assert set(EVENT_FIELDS) - {"tick"} <= set(last)
    P, G = node.cfg.num_peers, node.cfg.num_groups
    assert len(last["term"]) == P and len(last["term"][0]) == G
    # Post-election, post-commit state is visible per (peer, group).
    assert all(t >= 1 for row in last["term"] for t in row)
    assert all(c >= 6 for row in last["commit"] for c in row)
    # An elected leader holds a vote quorum for its group somewhere.
    assert any(v >= 2 for row in last["votes"] for v in row)


def test_ring_disabled_by_default(tmp_path):
    node = FusedClusterNode(mkcfg(1), str(tmp_path))
    try:
        assert node.ring is None and node.tracer is None
        for _ in range(5):
            node.tick()     # no tracing machinery runs
    finally:
        node.stop()


# -- host plane (spans) ------------------------------------------------

def test_span_lifecycle_fused(traced_node):
    node = traced_node
    elect(node)
    node.propose_many(1, [b"SET k1 v1", b"SET k1 v2"])
    for _ in range(15):
        node.tick()
    node.publish_flush()
    snap = node.tracer.snapshot()
    spans = [s for s in snap["spans"] if s["group"] == 1
             and s["key"].startswith("SET k1")]
    assert len(spans) == 2
    for s in spans:
        ph = s["phases"]
        # The fused runner has no apply/ack layer on the raw node; the
        # pipeline up to commit must be stamped and ordered.
        assert ph["propose"] <= ph["append"] <= ph["replicate"] \
            <= ph["commit"]
        assert s["index"] >= 1
    # WAL fsync events landed on the timeline ring.
    assert any(e["name"] == "wal.fsync" for e in snap["events"])


def test_span_tracer_bounded_and_threadsafe():
    tr = SpanTracer(max_pending=8, max_live=8, max_done=16)
    for i in range(100):
        tr.begin(0, f"q{i}")
    assert tr.dropped == 100 - 8
    tr.note_append(0, 1, [f"q{i}" for i in range(92, 100)])
    tr.note_commit(0, 8)
    for i in range(92, 100):
        tr.note_ack(0, f"q{i}")
    snap = tr.snapshot()
    assert len(snap["spans"]) <= 16
    done = [s for s in snap["spans"] if "ack" in s["phases"]]
    assert len(done) == 8


def test_span_unknown_keys_are_skipped():
    """Forwarded/replayed payloads with no local span must not crash or
    mis-bind (tracing is an observer)."""
    tr = SpanTracer()
    tr.note_append(0, 5, ["never-proposed"])
    tr.note_commit(0, 10)
    tr.note_apply(0, 5)
    tr.note_ack(0, "never-proposed")
    assert tr.snapshot()["spans"] == []


# -- chrome trace export ----------------------------------------------

def test_chrome_trace_schema_from_live_run(traced_node):
    node = traced_node
    elect(node)
    node.propose_many(0, [b"SET k0 v0"])
    for _ in range(10):
        node.tick()
    node.publish_flush()
    node.ring.drain()
    doc = chrome_trace(node.tracer.snapshot(), node.ring.rows())
    validate_chrome_trace(doc)
    # Round-trips through JSON (what GET /trace and make trace emit).
    doc2 = json.loads(json.dumps(doc))
    validate_chrome_trace(doc2)
    evs = doc["traceEvents"]
    assert any(e["ph"] == "X" and "→" in e["name"] for e in evs)
    assert any(e["ph"] == "C" for e in evs)


def test_validate_rejects_malformed():
    validate_chrome_trace({"traceEvents": []})      # empty is valid
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [{"ph": "X"}]})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [
            {"name": "x", "ph": "Z", "pid": 1, "ts": 0}]})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [
            {"name": "x", "ph": "X", "pid": 1, "ts": -1, "dur": 1}]})
    with pytest.raises(ValueError):
        validate_chrome_trace({"traceEvents": [
            {"name": "x", "ph": "C", "pid": 1, "ts": 0,
             "args": {"value": "not-a-number"}}]})


def test_trace_demo_writes_valid_perfetto_json(tmp_path):
    """`make trace` end to end: the demo runs a traced cluster and the
    emitted file passes the Perfetto schema check."""
    from raftsql_tpu.obs.trace_demo import run_demo
    out = str(tmp_path / "trace.json")
    run_demo(out, groups=2, ticks=60)
    with open(out) as f:
        doc = json.load(f)
    validate_chrome_trace(doc)
    assert len(doc["traceEvents"]) > 10


# -- HTTP endpoints + /metrics histograms ------------------------------

@pytest.fixture(params=["threaded", "aio"])
def server(request, tmp_path):
    from raftsql_tpu.api.aio import AioSQLServer
    from raftsql_tpu.api.http import SQLServer
    from raftsql_tpu.models.sqlite_sm import SQLiteStateMachine
    from raftsql_tpu.runtime.db import RaftDB
    from raftsql_tpu.runtime.pipe import RaftPipe
    from raftsql_tpu.transport.loopback import (LoopbackHub,
                                                LoopbackTransport)

    cfg = RaftConfig(num_groups=2, num_peers=1, tick_interval_s=0.005,
                     log_window=64, max_entries_per_msg=4)
    pipe = RaftPipe.create(1, 1, cfg, LoopbackTransport(LoopbackHub()),
                           data_dir=str(tmp_path / "raftsql-1"))
    pipe.node.enable_tracing()
    rdb = RaftDB(lambda g: SQLiteStateMachine(
        str(tmp_path / f"obs-g{g}.db")), pipe, num_groups=2)
    srv_cls = SQLServer if request.param == "threaded" else AioSQLServer
    srv = srv_cls(0, rdb, host="127.0.0.1", timeout_s=30.0)
    srv.start()
    yield srv
    srv.stop()
    rdb.close()


def _get(srv, path):
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=10)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        return r.status, r.read()
    finally:
        conn.close()


def _put(srv, body):
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=30)
    try:
        conn.request("PUT", "/", body=body)
        r = conn.getresponse()
        r.read()
        return r.status
    finally:
        conn.close()


def test_http_trace_and_events_endpoints(server):
    assert _put(server, b"CREATE TABLE main.o (v text)") == 204
    assert _put(server, b'INSERT INTO main.o (v) VALUES ("a")') == 204

    status, data = _get(server, "/trace")
    assert status == 200
    doc = json.loads(data)
    validate_chrome_trace(doc)
    assert any(e.get("ph") == "X" for e in doc["traceEvents"])

    status, data = _get(server, "/events")
    assert status == 200
    ev = json.loads(data)
    assert ev["tracing"] is True
    spans = ev["host"]["spans"]
    full = [s for s in spans if {"propose", "append", "commit",
                                 "apply", "ack"} <= set(s["phases"])]
    assert full, spans
    ph = full[0]["phases"]
    assert ph["propose"] <= ph["append"] <= ph["commit"] \
        <= ph["apply"] <= ph["ack"]


def test_metrics_has_propose_commit_histogram(server):
    for i in range(3):
        code = _put(server, b"CREATE TABLE IF NOT EXISTS main.h (v text)"
                    if i == 0 else
                    f'INSERT INTO main.h (v) VALUES ("{i}")'.encode())
        assert code == 204
    status, data = _get(server, "/metrics")
    assert status == 200
    m = json.loads(data)
    for k in ("propose_commit_p50_ms", "propose_commit_p95_ms",
              "propose_commit_p99_ms", "propose_ack_p50_ms",
              "propose_ack_p99_ms"):
        assert k in m, k
        assert isinstance(m[k], float), (k, m[k])
    # Commit is observed before apply+ack resolves.
    assert m["propose_commit_p50_ms"] <= m["propose_ack_p99_ms"]


def test_metrics_exports_membership_state(server):
    """Membership observability (raftsql_tpu/membership/): /metrics
    carries the live per-cluster voter/learner slot totals and the
    applied conf-change counter — the operator's view of the active
    configuration's shape without scraping /members."""
    status, data = _get(server, "/metrics")
    assert status == 200
    m = json.loads(data)
    # 1 voter slot x 2 groups, no learners, nothing churned yet.
    assert m["members_voters"] == 2
    assert m["members_learners"] == 0
    assert m["conf_changes_applied"] == 0


# -- production telemetry plane (PR 8) ---------------------------------


def _load_check_prom():
    """scripts/check_prom.py as a module: the tests and the CI lint
    must enforce the exact same exposition grammar."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "check_prom", os.path.join(repo, "scripts", "check_prom.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_prom_exposition_parses_and_round_trips(server):
    """GET /metrics?format=prom (and Accept negotiation) on both HTTP
    planes: parses under the strict parser, and every numeric field of
    the JSON document appears as a sample (name + labels)."""
    for i in range(3):
        code = _put(server, b"CREATE TABLE IF NOT EXISTS main.p (v text)"
                    if i == 0 else
                    f'INSERT INTO main.p (v) VALUES ("{i}")'.encode())
        assert code == 204
    check_prom = _load_check_prom()
    status, data = _get(server, "/metrics")
    assert status == 200
    json_doc = json.loads(data)
    status, prom = _get(server, "/metrics?format=prom")
    assert status == 200
    samples = check_prom.parse_prom(prom.decode())
    assert samples
    missing = check_prom.check_round_trip(json_doc, samples)
    assert not missing, missing[:10]
    # Accept-header negotiation returns the exposition with the prom
    # content type; the bare GET stays JSON.
    conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                      timeout=10)
    try:
        conn.request("GET", "/metrics",
                     headers={"Accept": "application/openmetrics-text"})
        r = conn.getresponse()
        body = r.read().decode()
        assert r.status == 200
        assert (r.getheader("Content-Type") or "").startswith(
            "text/plain")
        check_prom.parse_prom(body)
    finally:
        conn.close()
    json.loads(_get(server, "/metrics")[1])     # default unchanged


def test_per_group_traffic_ranks_hot_group_first(tmp_path):
    """A deliberately skewed workload: the hot group must rank first
    in the top-K table with matching counters and its live leader."""
    node = FusedClusterNode(mkcfg(groups=4), str(tmp_path))
    try:
        elect(node)
        node.propose_many(2, [f"SET h{i} v".encode()
                              for i in range(40)])
        node.propose_many(0, [b"SET cold 1"])
        for _ in range(40):
            node.tick()
        node.publish_flush()
        doc = node.traffic.doc(leader_of=node.leader_of)
        assert doc["proposed"] == 41
        hot = doc["hot_groups"]
        assert hot[0]["group"] == 2, hot
        assert hot[0]["proposed"] == 40
        assert hot[0]["committed"] >= 40        # +fresh-leader no-op
        assert hot[0]["leader"] == node.leader_of(2) + 1
        assert hot[0]["propose_rate"] >= hot[-1]["propose_rate"]
        cold = [r for r in hot if r["group"] == 0]
        assert cold and cold[0]["proposed"] == 1
    finally:
        node.stop()


def test_profiler_attribution_matches_the_dispatching_tick(tmp_path):
    """A durable phase's samples belong to the tick that dispatched it
    (since PR 39 the tick that also runs it): the fsync- and
    wal_write-owning ticks are exactly the ticks whose durable phase
    wrote something, and every tick that carried a proposal is one of
    them."""
    node = FusedClusterNode(mkcfg(groups=2), str(tmp_path / "d"))
    wrote = []
    real = node._durable_phases

    def spy(step_infos, staged):
        active = real(step_infos, staged)
        if active:
            wrote.append(node._tick_no)
        return active

    node._durable_phases = spy
    try:
        assert node.prof is not None        # default ON
        elect(node)
        carried = []
        for i in range(6):
            node.propose_many(0, [f"SET a{i} v".encode()])
            carried.append(node._tick_no)
            node.tick()
        for _ in range(6):
            node.tick()
        node.publish_flush()
        fsync = node.prof.phase_ticks("fsync")
        wal = node.prof.phase_ticks("wal_write")
    finally:
        node.stop()
    assert fsync == wal == sorted(set(wrote))
    assert set(carried) <= set(fsync)


def test_phase_tracks_in_trace_doc(traced_node):
    """The profiler's phase events land as pid-4 Perfetto tracks next
    to the span/device tracks, on one shared time axis."""
    node = traced_node
    elect(node)
    node.propose_many(0, [b"SET k v"])
    for _ in range(10):
        node.tick()
    node.publish_flush()
    doc = chrome_trace(node.tracer.snapshot(),
                       phase_events=node.prof.events(),
                       base_monotonic=node.tracer.t0)
    validate_chrome_trace(doc)
    phases = [e for e in doc["traceEvents"]
              if e.get("pid") == 4 and e.get("ph") == "X"]
    assert {e["name"] for e in phases} >= {"dispatch", "fsync"}
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in phases)


def test_flight_bundle_carries_serving_state(tmp_path):
    """Flight bundles now carry the PR 7 serving-plane state: the
    group-commit batch histogram and per-worker ring cursors/depths."""
    from raftsql_tpu.obs.flight import FlightRecorder
    from raftsql_tpu.runtime.ring import RingServer

    node = FusedClusterNode(mkcfg(groups=2), str(tmp_path / "d"),
                            group_commit=True)
    rs = None
    try:
        elect(node)
        node.propose_many(0, [b"SET x 1", b"SET y 2"])
        node.tick()

        class _Rdb:
            serving_metrics = None

        rs = RingServer(_Rdb(), str(tmp_path / "rings"), workers=2)
        rs.start()
        path = FlightRecorder(str(tmp_path / "flights")).dump(
            "serving-unit", "unit-test", node=node, ring_server=rs)
        with open(path) as f:
            doc = json.load(f)
        s = doc["serving"]
        assert "overlap" not in s
        assert s["wal_group_commit"]["group_commits"] >= 1
        assert isinstance(s["wal_group_commit"]["batch_hist"], dict)
        assert "phase_profile" in s and "group_traffic" in s
        rings = s["rings"]["rings"]
        assert len(rings) == 2
        assert all(r["req_tail"] >= r["req_head"] for r in rings)
    finally:
        if rs is not None:
            rs.stop()
        node.stop()


def test_workers_trace_merge_multiprocess(tmp_path):
    """--fused --workers 2 --trace: the engine's GET /trace is ONE
    merged Perfetto timeline carrying spans from all three pids (the
    engine plus both worker processes), and the prom exposition works
    through a worker's ring facade."""
    from raftsql_tpu.api.client import RaftSQLClient

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.Popen(
        [sys.executable, "-m", "raftsql_tpu.server.main", "--fused",
         "--workers", "2", "--groups", "2", "--port", str(port),
         "--tick", "0.004", "--trace"],
        cwd=str(tmp_path), env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
    client = RaftSQLClient([port], timeout_s=10)

    def healthz_fresh_conn():
        # A FRESH connection per request: SO_REUSEPORT hashes the
        # 4-tuple, so new ephemeral ports spread across both workers.
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=10)
        try:
            conn.request("GET", "/healthz")
            assert conn.getresponse().status == 200
        finally:
            conn.close()

    try:
        client.wait_healthy(0, deadline_s=90)
        for g in range(2):
            client.put("CREATE TABLE t (v text)", group=g,
                       deadline_s=60)
        for i in range(10):
            client.put(f"INSERT INTO t (v) VALUES ('w{i}')",
                       group=i % 2, deadline_s=30)
        for _ in range(15):
            healthz_fresh_conn()
        # Segment flush cadence is 0.5 s after a completion batch:
        # wait it out, then drive one more round so both workers flush
        # everything above.
        time.sleep(0.8)
        for _ in range(15):
            healthz_fresh_conn()
        status, _, text = client.raw(0, "GET", "/trace")
        assert status == 200
        doc = json.loads(text)
        validate_chrome_trace(doc)
        evs = doc["traceEvents"]
        worker_pids = {e["pid"] for e in evs
                       if e.get("ph") == "M"
                       and e.get("name") == "process_name"
                       and "http worker" in e["args"].get("name", "")}
        assert len(worker_pids) == 2, worker_pids
        for pid in worker_pids:
            assert any(e.get("pid") == pid and e.get("ph") == "X"
                       for e in evs), f"no spans from worker pid {pid}"
        # Engine-side tracks on the same timeline: proposal spans
        # (pid 1) and the profiler's phase tracks (pid 4).
        assert any(e.get("pid") == 1 and e.get("ph") == "X"
                   for e in evs)
        assert any(e.get("pid") == 4 and e.get("ph") == "X"
                   for e in evs)
        # Prom exposition through a worker's RingClient facade.
        status, _, prom = client.raw(0, "GET", "/metrics?format=prom")
        assert status == 200
        _load_check_prom().parse_prom(prom)
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=30) == 0
    finally:
        client.close()
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)


# -- flight recorder ---------------------------------------------------

def test_flight_recorder_dumps_on_invariant_failure(tmp_path,
                                                    monkeypatch):
    """A chaos run that trips an invariant must leave a post-mortem
    artifact holding BOTH planes: device-plane tick events and
    host-plane spans."""
    from raftsql_tpu.chaos.invariants import InvariantViolation
    from raftsql_tpu.chaos.scenarios import FusedChaosRunner
    from raftsql_tpu.chaos.schedule import ChaosSchedule

    monkeypatch.setenv("RAFTSQL_FLIGHT_DIR", str(tmp_path / "flights"))
    sched = ChaosSchedule(seed=7, ticks=60)
    runner = FusedChaosRunner(sched, str(tmp_path / "data"))
    # Poison the commit-monotonicity matrix MID-run (after elections and
    # real traffic, so the trace has history): the next observation
    # reads as a regression — a forced invariant failure.
    orig_observe = FusedChaosRunner._observe

    def poisoned(self, t):
        if t == 40:
            self.monotonic._hi[:, :] = 10 ** 6
        orig_observe(self, t)

    monkeypatch.setattr(FusedChaosRunner, "_observe", poisoned)
    with pytest.raises(InvariantViolation):
        runner.run()
    path = tmp_path / "flights" / "flight-fused-seed7.json"
    assert path.exists()
    doc = json.loads(path.read_text())
    assert "commit regressed" in doc["reason"]
    assert doc["meta"]["schedule_digest"] == sched.digest()
    rows = doc["device_events"]
    assert rows, "flight dump must carry device-plane tick events"
    assert set(EVENT_FIELDS) - {"tick"} <= set(rows[-1])
    spans = doc["host_spans"]["spans"]
    assert spans, "flight dump must carry host-plane spans"
    assert any("commit" in s["phases"] for s in spans)


def test_chaos_runs_remain_deterministic_with_tracing(tmp_path):
    """Tracing is an observer: two runs of one seed must still produce
    identical schedule AND result digests (the `make chaos` gate)."""
    from raftsql_tpu.chaos.scenarios import FusedChaosRunner
    from raftsql_tpu.chaos.schedule import generate

    sched = generate(11, ticks=100)
    reports = []
    for i in range(2):
        d = tmp_path / f"run{i}"
        os.makedirs(d)
        reports.append(FusedChaosRunner(sched, str(d)).run())
    assert reports[0]["schedule_digest"] == reports[1]["schedule_digest"]
    assert reports[0]["result_digest"] == reports[1]["result_digest"]
