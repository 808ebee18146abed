"""Pod runtime tests: the multi-host break of the single-controller
assumption (raftsql_tpu/pod/).

The equivalence contract mirrors tests/test_mesh.py's fused<->mesh
pins one level up: a pod of N processes driven through a seeded global
workload must land bit-for-bit on the same hard states, publish
cursors, leader hints and applied KV stream as one MeshClusterNode
driven through the SAME workload.  Fast tests run the procs == 1
degenerate pod in-process (every pod code path except the TCP hop);
the `slow`-marked test spawns two real `python -m
raftsql_tpu.pod.dryrun` processes and compares their dumps against an
in-process mesh reference — the dry-run rung of the pod ladder.
"""
import json
import os
import queue
import subprocess
import sys
import threading

import numpy as np
import pytest

from tests.conftest import free_port

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _child_env():
    """Env for pod child processes: the CPU platform by name (the
    device rule never falls back to it) and 8 virtual devices."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = REPO_ROOT + os.pathsep + env.get("PYTHONPATH", "")
    return env


# -- PodConfig ----------------------------------------------------------


def test_pod_config_validation():
    from raftsql_tpu.pod import PodConfig
    with pytest.raises(ValueError, match="process"):
        PodConfig(procs=0)
    with pytest.raises(ValueError, match="outside"):
        PodConfig(procs=2, proc_id=2, coordinator="h:1")
    with pytest.raises(ValueError, match="coordinator"):
        PodConfig(procs=2, proc_id=0)
    with pytest.raises(ValueError, match="hosts"):
        PodConfig(procs=2, proc_id=0, coordinator="h:1",
                  hosts=("http://a",))
    pod = PodConfig(procs=2, proc_id=1, coordinator="h:1")
    with pytest.raises(ValueError, match="shard"):
        pod.validate(group_shards=1)
    pod.validate(group_shards=4)
    assert pod.owned_shards(4) == [1, 3]
    assert PodConfig(procs=2, proc_id=0,
                     coordinator="h:1").owned_shards(4) == [0, 2]
    assert pod.seq_origin(3) == 1 and pod.seq_origin(4) == 0


def test_pod_meta_refuses_reassignment(tmp_path):
    """The PODMETA check — a host restarted with a shard assignment
    that disagrees with its on-disk layout is refused (the cross-host
    analogue of the mesh re-shard refusal)."""
    from raftsql_tpu.pod import PodConfig
    d = str(tmp_path / "h0")
    PodConfig(procs=2, proc_id=0, coordinator="h:1").check_meta(d, 4)
    # Same assignment reopens fine.
    PodConfig(procs=2, proc_id=0, coordinator="h:1").check_meta(d, 4)
    # A different pod size, proc id, or shard count is refused.
    with pytest.raises(ValueError, match="shard assignment"):
        PodConfig(procs=3, proc_id=0, coordinator="h:1").check_meta(d, 4)
    with pytest.raises(ValueError, match="shard assignment"):
        PodConfig(procs=2, proc_id=1, coordinator="h:1").check_meta(d, 4)
    with pytest.raises(ValueError, match="shard assignment"):
        PodConfig(procs=2, proc_id=0, coordinator="h:1").check_meta(d, 8)
    assert PodConfig.read_meta(d)["owned"] == [0, 2]
    assert PodConfig.read_meta(str(tmp_path / "none")) is None


# -- the collective -----------------------------------------------------


def test_tcp_pod_transport_gather():
    """Three threads form a pod over localhost and run a few
    collectives; every process must see every contribution in proc-id
    order, and a mismatched tag must fail loudly."""
    from raftsql_tpu.pod import PodPeerLost, TcpPodTransport
    procs = 3
    coord = f"127.0.0.1:{free_port()}"
    results = [None] * procs
    errors = []

    def run(pid):
        try:
            t = TcpPodTransport(procs, pid, coord, connect_timeout_s=10.0)
            try:
                out = []
                for tag in ("a", "b"):
                    out.append(t.gather(tag, f"{tag}{pid}".encode()))
                t.barrier("end")
                results[pid] = out
            finally:
                t.close()
        except Exception as e:  # surfaced below
            errors.append((pid, e))

    threads = [threading.Thread(target=run, args=(p,)) for p in range(procs)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=30)
    assert not errors, errors
    for pid in range(procs):
        assert results[pid] == [[b"a0", b"a1", b"a2"],
                                [b"b0", b"b1", b"b2"]]

    with pytest.raises(ValueError):
        TcpPodTransport(1, 0, "x:1")
    t = __import__("raftsql_tpu.pod.transport",
                   fromlist=["make_transport"]).make_transport(1, 0, "")
    assert t.gather("x", b"p") == [b"p"]
    assert isinstance(PodPeerLost("x"), RuntimeError)


# -- equivalence (procs == 1 pod vs MeshClusterNode, in-process) --------


def _mesh_pair(tmp_path, num_groups=8, num_peers=3, group_shards=4):
    from raftsql_tpu.config import RaftConfig
    from raftsql_tpu.pod import PodClusterNode, PodConfig
    from raftsql_tpu.runtime.mesh import MeshClusterNode, MeshConfig
    cfg = RaftConfig(num_groups=num_groups, num_peers=num_peers,
                     log_window=32, max_entries_per_msg=4,
                     election_ticks=10, heartbeat_ticks=1,
                     tick_interval_s=0.0, seed=7)
    mesh = MeshConfig(peer_shards=1, group_shards=group_shards).build()
    pod = PodClusterNode(PodConfig(), cfg, str(tmp_path / "pod"), mesh,
                         seed=3)
    ref = MeshClusterNode(cfg, str(tmp_path / "ref"), mesh, seed=3)
    return pod, ref, cfg


def _drain(node):
    from raftsql_tpu.runtime.db import _expand_commit_item
    out = []
    q = node.commit_q(0)
    while True:
        try:
            item = q.get_nowait()
        except queue.Empty:
            break
        if item is None or not isinstance(item, tuple):
            continue
        out.extend(_expand_commit_item(item))
    return out


def _assert_equal_state(pod, ref, pod_applied, ref_applied):
    from raftsql_tpu.pod.dryrun import state_doc
    np.testing.assert_array_equal(np.asarray(pod._hard),
                                  np.asarray(ref._hard))
    np.testing.assert_array_equal(np.asarray(pod._applied),
                                  np.asarray(ref._applied))
    pd = state_doc(pod, pod_applied)
    rd = state_doc(ref, ref_applied)
    assert pd["digest"] == rd["digest"]
    assert pd["kv_stream"] == rd["kv_stream"]


def test_pod_single_proc_equivalence(tmp_path):
    """A procs == 1 pod is bit-for-bit the single controller: same
    hard states, same hints, same applied stream — through the full
    pod tick (gather merge, strided seqs, ack plane)."""
    from raftsql_tpu.pod.dryrun import seeded_workload
    pod, ref, cfg = _mesh_pair(tmp_path)
    pod_applied, ref_applied = [], []
    try:
        wl = seeded_workload(0, 60, cfg.num_groups)
        for t in range(60):
            for _i, g, payload in wl[t]:
                seqs = pod.pod_propose(g, [payload])
                assert len(seqs) == 1
                ref.propose_many(g, [payload])
            pod.tick()
            ref.tick()
            ref.publish_flush()
            pod_applied.extend(_drain(pod))
            ref_applied.extend(_drain(ref))
            if t % 20 == 19:
                _assert_equal_state(pod, ref, pod_applied, ref_applied)
        _assert_equal_state(pod, ref, pod_applied, ref_applied)
        assert len(pod_applied) > 0
        # The ack plane: the owner acks a committed seq, and the next
        # collective carries it back to the origin.
        pod.pod_send_ack([5, 9])
        pod.tick()
        assert pod.pod_take_acked() == {5, 9}
        assert pod.pod_take_acked() == set()
        assert pod.metrics.pod_gathers >= 60
    finally:
        pod.stop()
        ref.stop()


def test_pod_restart_replays_from_disk(tmp_path):
    """Stop a pod, reopen over the same dirs: the replay exchange must
    rebuild the identical state (PodShardedWAL replay + PODMETA
    second-open acceptance)."""
    from raftsql_tpu.config import RaftConfig
    from raftsql_tpu.pod import PodClusterNode, PodConfig
    from raftsql_tpu.pod.dryrun import seeded_workload, state_doc
    from raftsql_tpu.runtime.mesh import MeshConfig
    cfg = RaftConfig(num_groups=8, num_peers=3, log_window=32,
                     max_entries_per_msg=4, election_ticks=10,
                     heartbeat_ticks=1, tick_interval_s=0.0, seed=7)
    mesh = MeshConfig(peer_shards=1, group_shards=4).build()
    d = str(tmp_path / "pod")
    node = PodClusterNode(PodConfig(), cfg, d, mesh, seed=3)
    applied = []
    try:
        wl = seeded_workload(0, 40, cfg.num_groups)
        for t in range(40):
            for _i, g, payload in wl[t]:
                node.pod_propose(g, [payload])
            node.tick()
            applied.extend(_drain(node))
        before = state_doc(node, applied)
    finally:
        node.stop()
    node2 = PodClusterNode(PodConfig(), cfg, d, mesh, seed=3)
    try:
        np.testing.assert_array_equal(
            np.asarray(node2._hard)[:, :, :2],
            np.frombuffer(__import__("base64").b64decode(before["hard"]),
                          dtype=np.asarray(node._hard).dtype).reshape(
                              np.asarray(node._hard).shape)[:, :, :2])
        replayed = []
        for _ in range(3):
            node2.tick()
            replayed.extend(_drain(node2))
        rows = sorted([int(g), int(i),
                       d2.decode() if isinstance(d2, (bytes, bytearray))
                       else str(d2)] for (g, i, d2) in replayed)
        assert rows == before["kv_stream"]
    finally:
        node2.stop()


def test_pod_rejects_bad_shapes(tmp_path):
    from raftsql_tpu.config import RaftConfig
    from raftsql_tpu.pod import PodClusterNode, PodConfig
    from raftsql_tpu.runtime.mesh import MeshConfig
    cfg = RaftConfig(num_groups=8, num_peers=3, log_window=32,
                     max_entries_per_msg=4, tick_interval_s=0.0)
    mesh = MeshConfig(peer_shards=1, group_shards=2).build()
    with pytest.raises(ValueError, match="shard"):
        PodClusterNode(PodConfig(procs=4, proc_id=0, coordinator="h:1"),
                       cfg, str(tmp_path / "x"), mesh)


# -- the dry-run rung: two real processes over TCP ----------------------


@pytest.mark.slow
def test_pod_dryrun_two_process_equivalence(tmp_path):
    """Rungs 1+2 of the pod ladder: two `raftsql_tpu.pod.dryrun`
    processes form a pod over localhost, run the seeded workload, and
    both dumps must match each other AND an in-process procs == 1
    reference bit-for-bit."""
    coord = f"127.0.0.1:{free_port()}"
    procs = []
    for pid in range(2):
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "raftsql_tpu.pod.dryrun",
             "--procs", "2", "--proc-id", str(pid),
             "--coord", coord,
             "--data-dir", str(tmp_path / f"h{pid}"),
             "--ticks", "60", "--seed", "0",
             "--out", str(tmp_path / f"h{pid}.json")],
            env=_child_env(), cwd=REPO_ROOT,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    logs = [p.communicate(timeout=280)[0] for p in procs]
    for pid, p in enumerate(procs):
        assert p.returncode == 0, logs[pid].decode(errors="replace")
    docs = [json.loads((tmp_path / f"h{i}.json").read_text())
            for i in range(2)]
    assert docs[0]["digest"] == docs[1]["digest"]
    assert docs[0]["kv_stream"] == docs[1]["kv_stream"]
    assert len(docs[0]["kv_stream"]) > 0

    # The single-controller reference over the same workload.
    from raftsql_tpu.pod.dryrun import (build_pod_node, drain_commits,
                                        seeded_workload, state_doc)

    class _A:
        procs = 1
        proc_id = 0
        coord = ""
        data_dir = str(tmp_path / "ref")
        groups = 8
        peers = 3
        group_shards = 0
        connect_timeout = 30.0

    node, cfg = build_pod_node(_A)
    applied = []
    try:
        wl = seeded_workload(0, 60, cfg.num_groups)
        for t in range(60):
            for _i, g, payload in wl[t]:
                node.pod_propose(g, [payload])
            node.tick()
            applied.extend(drain_commits(node))
        ref = state_doc(node, applied)
    finally:
        node.stop()
    assert docs[0]["digest"] == ref["digest"]
    # Durability is sharded: each host materialized only its own
    # shards' WAL dirs, disjoint and jointly exhaustive.
    owned = [sorted(x.name for x in (tmp_path / f"h{i}" / "p1").iterdir())
             for i in range(2)]
    assert not set(owned[0]) & set(owned[1])


# -- the serving plane: client routing + the --pod server ---------------


def test_client_pod_hint_merge(monkeypatch):
    """refresh_hints over a pod: the sweep adopts the /healthz hosts
    table (a client pointed at ONE host learns them all) and routes
    each group to its OWNER host — engine role is ignored on pod rows
    (every host truthfully reports every group; only owners serve)."""
    from raftsql_tpu.api.client import RaftSQLClient
    hosts = ["127.0.0.1:18000", "127.0.0.1:18001"]
    docs = {
        0: {"id": 0, "ready": True,
            "pod": {"procs": 2, "proc_id": 0, "hosts": hosts},
            "groups": {"0": {"role": "leader", "pod_owned": True},
                       "1": {"role": "leader", "pod_owned": False,
                             "lease_s": 9.0}}},
        1: {"id": 0, "ready": True,
            "pod": {"procs": 2, "proc_id": 1, "hosts": hosts},
            "groups": {"0": {"pod_owned": False},
                       "1": {"pod_owned": True, "lease_s": 5.0}}},
    }
    monkeypatch.setattr(RaftSQLClient, "health",
                        lambda self, idx, timeout_s=1.0: docs.get(idx))
    cli = RaftSQLClient([hosts[0]])
    try:
        assert cli.refresh_hints() == 2
        assert [p for (_h, p) in cli.nodes] == [18000, 18001]
        assert cli._leader == {0: 0, 1: 1}
        # The lease hint comes from the OWNER's row, never the
        # non-owner's (whose identical engine lease is not servable).
        assert cli._lease_target(1) == 1
        # A second sweep is stable (no duplicate adoption).
        assert cli.refresh_hints() == 2
        assert len(cli.nodes) == 2
    finally:
        cli.close()


@pytest.mark.slow
def test_pod_server_two_hosts(tmp_path):
    """The --pod serving rung end to end: two `server.main --pod`
    processes on one box, a client pointed at host 0 only.  The sweep
    adopts host 1 and routes by ownership; a deliberately misdirected
    write 421s with X-Raft-Leader naming the owner host; reads land on
    the owner's durable SQLite shard."""
    from raftsql_tpu.api.client import RaftSQLClient
    from raftsql_tpu.server.main import EXIT_CODE_FATAL
    deadline = 120.0
    p0, p1 = free_port(), free_port()
    coord = f"127.0.0.1:{free_port()}"
    hosts = f"127.0.0.1:{p0},127.0.0.1:{p1}"
    procs = [subprocess.Popen(
        [sys.executable, "-m", "raftsql_tpu.server.main",
         "--pod", "--pod-id", str(i), "--pod-coord", coord,
         "--pod-hosts", hosts, "--port", str(p), "--groups", "4",
         "--group-shards", "2", "--peers", "3", "--tick", "0.02"],
        env=_child_env(), cwd=str(tmp_path),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for i, p in enumerate((p0, p1))]
    cli = RaftSQLClient([f"127.0.0.1:{p0}"], timeout_s=15.0)
    try:
        cli.wait_healthy(0, deadline_s=deadline)
        doc = cli.health(0)
        assert doc["pod"]["procs"] == 2
        assert doc["pod"]["owned_shards"] == [0]
        # group_shards=2 over 4 groups: host 0 owns groups 0-1 (shard
        # 0), host 1 owns 2-3 — every host reports all four rows.
        assert doc["groups"]["0"]["pod_owned"] is True
        assert doc["groups"]["2"]["pod_owned"] is False
        assert cli.refresh_hints(timeout_s=5.0) == 4
        assert len(cli.nodes) == 2          # host 1 adopted
        assert cli._leader == {0: 0, 1: 0, 2: 1, 3: 1}
        # A write for a host-1 group routes there via the merged hints.
        cli.put("CREATE TABLE t (v text)", group=2, deadline_s=deadline)
        cli.put("INSERT INTO t (v) VALUES ('x')", group=2,
                deadline_s=deadline)
        cli.get_until("SELECT v FROM t", "|x|\n", group=2,
                      deadline_s=deadline)
        # And host 0's own groups serve locally.
        cli.put("CREATE TABLE s (v text)", group=0, deadline_s=deadline)
        # Misdirected write: host 0 refuses a host-1 group up front
        # with 421 + the owner host (1-based hosts-table slot).
        status, hdrs, _ = cli.raw(
            0, "PUT", "/", "INSERT INTO t (v) VALUES ('y')",
            headers={"X-Raft-Group": "2"})
        assert status == 421
        assert hdrs.get("X-Raft-Leader") == "2"
        # Misdirected read: same refusal on the query path.
        status, hdrs, _ = cli.raw(0, "GET", "/", "SELECT v FROM t",
                                  headers={"X-Raft-Group": "2"})
        assert status == 421
    except BaseException:
        for p in procs:
            p.terminate()
        logs = [p.communicate(timeout=30)[0] for p in procs]
        for i, log in enumerate(logs):
            print(f"--- pod host {i} ---\n" + log.decode(errors="replace"))
        raise
    finally:
        cli.close()
        for p in procs:
            if p.poll() is None:
                p.terminate()
    # Fail-stop teardown: whichever host's collective dies first may
    # exit EXIT_CODE_FATAL (pod-wide fail-stop), a clean stop exits 0.
    for p in procs:
        p.communicate(timeout=60)
        assert p.returncode in (0, EXIT_CODE_FATAL), p.returncode
