"""Mesh runtime subsystem (runtime/mesh.py) — 8 virtual CPU devices.

The acceptance story for the mesh scale-out:

  * mesh ↔ fused EQUIVALENCE: the MeshClusterNode under forced host
    devices reproduces the single-device FusedClusterNode bit-for-bit —
    hard states, commit indexes, applied KV — on full-voter-mask and
    masked-membership configs, with and without per-peer skew
    (sharding is an execution detail, never a semantics change);
  * acked writes with G sharded over >= 2 devices, through the full
    product stack (RaftDB + FusedPipe over the mesh node);
  * the per-shard durable layout (ShardedWAL): routing, replay merge,
    restart equivalence, re-shard refusal;
  * skew on the mesh (the closed MeshLockstepOnlyError frontier):
    lockstep vs skewed elections diverge, and the mesh-skew chaos
    family reproduces digests.
"""
import queue

import numpy as np
import pytest

from raftsql_tpu.config import RaftConfig
from raftsql_tpu.runtime.db import _expand_commit_item
from raftsql_tpu.runtime.fused import FusedClusterNode
from raftsql_tpu.runtime.mesh import (MeshClusterNode, MeshConfig,
                                      ShardedWAL)


def cfg_for(num_peers=4, num_groups=8, seed=7, **kw):
    kw.setdefault("log_window", 32)
    kw.setdefault("max_entries_per_msg", 4)
    kw.setdefault("election_ticks", 10)
    kw.setdefault("heartbeat_ticks", 1)
    kw.setdefault("tick_interval_s", 0.0)
    return RaftConfig(num_groups=num_groups, num_peers=num_peers,
                      seed=seed, **kw)


def drain(node, peer=0):
    out = []
    q = node.commit_q(peer)
    while True:
        try:
            item = q.get_nowait()
        except queue.Empty:
            break
        if item is None or not isinstance(item, tuple):
            continue
        out.extend(_expand_commit_item(item))
    return out


def by_group(rows):
    """Applied (group, index, cmd) rows as {group: [(index, cmd), ...]},
    each group's list in stream order."""
    out = {}
    for g, idx, cmd in rows:
        out.setdefault(g, []).append((idx, cmd))
    return out


# -- MeshConfig ---------------------------------------------------------

def test_mesh_config_validation():
    with pytest.raises(ValueError, match="positive"):
        MeshConfig(peer_shards=0, group_shards=4)
    mc = MeshConfig(peer_shards=2, group_shards=4)
    assert mc.total_devices == 8
    with pytest.raises(ValueError, match="not divisible"):
        mc.validate(cfg_for(num_peers=3, num_groups=8))
    with pytest.raises(ValueError, match="not divisible"):
        mc.validate(cfg_for(num_peers=4, num_groups=6))
    mc.validate(cfg_for(num_peers=4, num_groups=8))
    with pytest.raises(ValueError, match="devices"):
        MeshConfig(peer_shards=4, group_shards=4).build()


def test_mesh_config_for_groups_picks_widest_divisor():
    # 8 devices, 12 groups: the widest divisor of 12 that fits is 6.
    mc = MeshConfig.for_groups(cfg_for(num_groups=12))
    assert mc.group_shards == 6 and mc.peer_shards == 1
    # Reserving 2 peer shards halves the device budget per group shard.
    mc = MeshConfig.for_groups(cfg_for(num_groups=12), peer_shards=2)
    assert mc.group_shards == 4 and mc.peer_shards == 2


# -- ShardedWAL ---------------------------------------------------------

def test_sharded_wal_routes_and_replays(tmp_path):
    d = str(tmp_path / "p1")
    w = ShardedWAL(d, num_shards=4, groups_per_shard=2)
    # Ranges spanning three shards in one call (groups 0, 3, 6).
    w.append_ranges([0, 3, 6], [1, 1, 1], [2, 1, 1], [1, 1, 1],
                    [b"a", b"b", b"c", b"d"])
    w.set_hardstates(np.array([0, 3, 6]), np.array([1, 1, 1]),
                     np.array([0, 1, 2]), np.array([2, 1, 1]))
    w.sync()
    w.close()
    # Each touched shard got exactly its own groups' records.
    per_shard = [ShardedWAL.replay(d, 4, 2)]
    from raftsql_tpu.storage.wal import WAL, wal_exists
    assert wal_exists(str(tmp_path / "p1" / "s0"))
    assert wal_exists(str(tmp_path / "p1" / "s1"))
    assert wal_exists(str(tmp_path / "p1" / "s3"))
    # Untouched shard: its active segment exists but replays empty.
    assert WAL.replay(str(tmp_path / "p1" / "s2")) == {}
    s0 = WAL.replay(str(tmp_path / "p1" / "s0"))
    assert set(s0) == {0}
    assert [dt for (_, dt) in s0[0].entries] == [b"a", b"b"]
    merged = per_shard[0]
    assert set(merged) == {0, 3, 6}
    assert merged[3].hard.vote == 1
    assert [dt for (_, dt) in merged[6].entries] == [b"d"]


def test_sharded_wal_refuses_reshard(tmp_path):
    d = str(tmp_path / "p1")
    w = ShardedWAL(d, num_shards=2, groups_per_shard=4)
    w.append_ranges([5], [1], [1], [1], [b"x"])   # shard 1 under gl=4
    w.sync()
    w.close()
    with pytest.raises(ValueError, match="different group-shard"):
        ShardedWAL.replay(d, 2, 2)   # gl=2 would put group 5 in shard 2


def test_mesh_node_refuses_reshard(tmp_path):
    cfg = cfg_for()
    mesh4 = MeshConfig(group_shards=4).build()
    node = MeshClusterNode(cfg, str(tmp_path), mesh4)
    node.stop()
    mesh2 = MeshConfig(group_shards=2).build()
    with pytest.raises(ValueError, match="re-sharding"):
        MeshClusterNode(cfg, str(tmp_path), mesh2)


# -- mesh <-> fused equivalence (the property test) ---------------------

def _run_pair(tmp_path, ticks, membership=None, skew_windows=(),
              group_shards=4, peer_shards=1, num_peers=4):
    """Drive a FusedClusterNode and a MeshClusterNode through the SAME
    seeded workload (+ optional identical skew schedule) and assert
    bit-for-bit equal hard states, commit indexes, and applied KV
    stream after every check interval."""
    cfg = cfg_for(num_peers=num_peers)
    mesh = MeshConfig(peer_shards=peer_shards,
                      group_shards=group_shards).build()
    fused = FusedClusterNode(cfg, str(tmp_path / "fused"), seed=3)
    meshn = MeshClusterNode(cfg, str(tmp_path / "mesh"), mesh, seed=3)
    if membership is not None:
        fused.enable_membership(initial_voters=membership)
        meshn.enable_membership(initial_voters=membership)
    rng = np.random.default_rng(0)
    seq = 0
    applied_f, applied_m = [], []
    try:
        for t in range(ticks):
            for g in range(cfg.num_groups):
                if rng.random() < 0.4:
                    payload = f"SET k{g} v{seq}".encode()
                    seq += 1
                    # Same routing state on both sides (asserted below),
                    # so the same propose lands at the same peer.
                    fused.propose_many(g, [payload])
                    meshn.propose_many(g, [payload])
            ti = None
            for (s, e, incs) in skew_windows:
                if s <= t < e:
                    ti = np.asarray(incs, np.int32)
            fused.timer_inc = ti
            meshn.timer_inc = ti
            fused.tick()
            meshn.tick()
            if t % 20 == 19 or t == ticks - 1:
                fused.publish_flush()
                meshn.publish_flush()
                np.testing.assert_array_equal(
                    fused._hard, meshn._hard,
                    err_msg=f"hard state diverged at tick {t}")
                np.testing.assert_array_equal(
                    fused._hints, meshn._hints,
                    err_msg=f"leader hints diverged at tick {t}")
                np.testing.assert_array_equal(
                    fused._applied, meshn._applied,
                    err_msg=f"publish cursors diverged at tick {t}")
                applied_f.extend(drain(fused))
                applied_m.extend(drain(meshn))
                # Order WITHIN a group is the contract.  How the streams
                # of different groups interleave is not: with the
                # host-parallel plane on (any host with >= 4 cores, the
                # chip host among them) each publish shard has its own
                # worker, and their relative progress is scheduling.
                assert by_group(applied_f) == by_group(applied_m), \
                    f"KV stream at tick {t}"
        assert (fused._hard[:, :, 2] > 0).any(), "nothing ever committed"
        assert applied_f, "no applied KV to compare"
    finally:
        fused.stop()
        meshn.stop()
    return applied_f


def test_mesh_fused_equivalence_full_voters(tmp_path):
    applied = _run_pair(tmp_path, ticks=100)
    assert len(applied) > 20


def test_mesh_fused_equivalence_peer_sharded(tmp_path):
    # The peers x groups mesh: message exchange rides the all_to_all
    # route; the host contract must not notice.
    applied = _run_pair(tmp_path, ticks=80, group_shards=4,
                        peer_shards=2)
    assert applied


def test_mesh_fused_equivalence_masked_membership(tmp_path):
    # Boot a 3-of-4 voter config over provisioned slot capacity: every
    # quorum kernel runs mask-weighted, and the mesh must reproduce the
    # fused runtime's masked elections and commits exactly.
    applied = _run_pair(tmp_path, ticks=100, membership=(0, 1, 2))
    assert applied


def test_mesh_fused_equivalence_under_skew(tmp_path):
    # The SAME per-peer skew schedule on both runtimes: the sharded
    # step's [P] timer vector must be semantically identical to the
    # fused step's — the closed MeshLockstepOnlyError frontier.
    windows = ((20, 50, (2, 0, 1, 1)), (60, 80, (1, 3, 1, 0)))
    applied = _run_pair(tmp_path, ticks=100, skew_windows=windows)
    assert applied


# -- acked writes over the product stack --------------------------------

def test_mesh_acked_writes_sharded_groups(tmp_path):
    """Acceptance: under forced host devices the mesh runtime commits
    ACKED writes with G sharded over >= 2 devices, through the full
    RaftDB product stack (propose -> device step -> per-shard WAL fsync
    -> publish workers -> SQLite apply -> ack)."""
    import jax

    from raftsql_tpu.models.sqlite_sm import SQLiteStateMachine
    from raftsql_tpu.runtime.db import RaftDB
    from raftsql_tpu.runtime.fused import FusedPipe

    assert len(jax.devices()) >= 2
    cfg = cfg_for(num_peers=3, num_groups=4)
    mesh = MeshConfig(group_shards=2).build()
    assert mesh.shape["groups"] >= 2
    node = MeshClusterNode(cfg, str(tmp_path / "data"), mesh)
    node.start(interval_s=0.001)
    rdb = RaftDB(lambda g: SQLiteStateMachine(":memory:"),
                 FusedPipe(node), num_groups=4)
    try:
        futs = [rdb.propose("CREATE TABLE t (k TEXT, v TEXT)", group=g)
                for g in range(4)]
        errs = [f.wait(30) for f in futs]
        futs = [rdb.propose(f"INSERT INTO t VALUES ('k', 'g{g}')",
                            group=g) for g in range(4)]
        errs += [f.wait(30) for f in futs]
        assert all(e is None for e in errs), errs
        for g in range(4):
            assert rdb.query("SELECT v FROM t WHERE k='k'",
                             group=g) == f"|g{g}|\n"
    finally:
        rdb.close()


# -- skew on the mesh (replaces the PR-4 lockstep regression) -----------

def test_mesh_skew_changes_elections(tmp_path):
    """Same seed, lockstep vs per-peer skew on the MESH runtime: the
    election outcomes must demonstrably differ — proof the sharded
    timer vector actually reaches every peer block's clocks (and not,
    say, only shard 0's)."""
    import dataclasses as dc

    from raftsql_tpu.chaos.scenarios import MeshChaosRunner
    from raftsql_tpu.chaos.schedule import generate_skew

    sk = generate_skew(0, ticks=120)
    lock = dc.replace(sk, skews=())
    ra = MeshChaosRunner(lock, str(tmp_path / "lock"))
    rep_a = ra.run()
    rb = MeshChaosRunner(sk, str(tmp_path / "skew"))
    rep_b = rb.run()
    assert rep_b["skew_ticks"] > 0 and rep_a["skew_ticks"] == 0
    assert rep_a["result_digest"] != rep_b["result_digest"]
    # Skew fault counters export through NodeMetrics (the /metrics
    # surface), from the mesh runtime too.
    assert rb.final_metrics.faults_skew_ticks == rep_b["skew_ticks"]


def test_mesh_skew_chaos_reproduces(tmp_path):
    from raftsql_tpu.chaos.scenarios import MeshChaosRunner
    from raftsql_tpu.chaos.schedule import generate_skew

    sk = generate_skew(4, ticks=100)
    r1 = MeshChaosRunner(sk, str(tmp_path / "a")).run()
    r2 = MeshChaosRunner(sk, str(tmp_path / "b")).run()
    assert (r1["schedule_digest"], r1["result_digest"]) \
        == (r2["schedule_digest"], r2["result_digest"])
    assert r1["skew_ticks"] > 0 and r1["crashes"] >= 1


def test_mesh_skew_matches_fused_chaos(tmp_path):
    """The SAME skew schedule through the fused and the mesh chaos
    runners must produce the SAME result digest: the chaos harness is
    another witness that sharding never changes semantics — crashes,
    per-shard WAL replay and all."""
    from raftsql_tpu.chaos.scenarios import FusedChaosRunner, MeshChaosRunner
    from raftsql_tpu.chaos.schedule import generate_skew

    sk = generate_skew(2, ticks=100)
    rf = FusedChaosRunner(sk, str(tmp_path / "fused")).run()
    rm = MeshChaosRunner(sk, str(tmp_path / "mesh")).run()
    assert rf["result_digest"] == rm["result_digest"], (rf, rm)
