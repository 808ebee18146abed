"""Dynamic membership tests (raftsql_tpu/membership/).

Four layers, mirroring the subsystem's planes:

  * mask-weighted quorum kernels (ops/quorum.py, ops/commit_scan.py,
    ops/pallas_quorum.py): a FULL voter mask must reproduce the static
    fixed-quorum kernels bit for bit (property-tested across all three
    commit rules), plus the degenerate configs — single voter,
    even-size joint C_old,new, all-learner group that can never elect
    or commit;
  * the host manager (membership/manager.py): change validation, the
    one-in-flight latch, two-phase joint flow, idempotent apply,
    restart restore;
  * the wire/durability planes: conf-entry codec framing, WAL REC_CONF
    baselines surviving replay AND segment compaction;
  * the runtimes: the fused cluster's full add-learner -> promote
    (joint) -> remove lifecycle with per-group configs inside one
    dispatch + restart recovery; the lockstep RaftNode cluster's
    node-replacement story under chaos (SIGKILL a voter, boot a fresh
    machine, add/promote/remove) — digest-reproducible across two runs
    of one plan with zero lost acked writes; TCP-plane crash/restart
    with port rebinding; the admin HTTP API on both serving planes.
"""
import http.client
import json
import time

import numpy as np
import pytest

import jax.numpy as jnp

from raftsql_tpu.config import RaftConfig
from raftsql_tpu.membership import MembershipError, MembershipManager
from raftsql_tpu.ops.commit_scan import (masked_windowed_commit_index,
                                         windowed_commit_index)
from raftsql_tpu.ops.pallas_quorum import (pallas_masked_quorum_commit_index,
                                           pallas_quorum_commit_index)
from raftsql_tpu.ops.quorum import (mask_majority, masked_quorum_commit_index,
                                    masked_quorum_match_index,
                                    masked_vote_win, quorum_commit_index,
                                    quorum_match_index, vote_count)
from raftsql_tpu.storage import fsio
from raftsql_tpu.storage.wal import WAL
from raftsql_tpu.transport.codec import (CONF_KIND_ENTER_JOINT,
                                         CONF_KIND_LEARNER,
                                         CONF_KIND_LEAVE_JOINT,
                                         decode_conf_entry,
                                         encode_conf_entry, is_conf_entry)


def _rand_state(rng, G, P, W):
    """A plausible random per-group consensus snapshot for the commit
    kernels (both kernel families compute the same function of these,
    so consistency beyond index ranges is not required)."""
    log_len = rng.integers(0, W + 1, G)
    match = (rng.random((G, P)) * (log_len[:, None] + 1)).astype(np.int64)
    commit = (rng.random(G) * (log_len + 1)).astype(np.int64)
    ring = rng.integers(1, 4, (G, W))
    term = rng.integers(1, 4, G)
    leader = rng.random(G) < 0.7
    j = lambda x: jnp.asarray(x, jnp.int32)
    return (j(match), j(ring), j(log_len), j(commit), j(term),
            jnp.asarray(leader))


# -- full voter mask == static quorum, bit for bit ---------------------

@pytest.mark.parametrize("P", [3, 4, 5])
def test_masked_kernels_match_static_full_mask(P):
    """The acceptance property: with every slot a voter, all three
    mask-weighted commit rules and the vote tally reproduce the static
    fixed-quorum kernels exactly (CPU point, windowed, AND Pallas)."""
    G, W = 16, 8
    q = P // 2 + 1
    rng = np.random.default_rng(100 + P)
    full = jnp.ones((G, P), bool)
    for trial in range(8):
        match, ring, log_len, commit, term, leader = \
            _rand_state(rng, G, P, W)
        assert (quorum_match_index(match, q)
                == masked_quorum_match_index(match, full)).all()
        want = quorum_commit_index(match, ring, log_len, commit, term,
                                   leader, quorum=q, window=W)
        got = masked_quorum_commit_index(
            match, ring, log_len, commit, term, leader,
            voters=full, voters_joint=full, window=W)
        assert (want == got).all(), trial
        want_w = windowed_commit_index(match, ring, log_len, commit,
                                       term, leader, quorum=q, window=W)
        got_w = masked_windowed_commit_index(
            match, ring, log_len, commit, term, leader,
            voters=full, voters_joint=full, window=W)
        assert (want_w == got_w).all(), trial
        want_p = pallas_quorum_commit_index(
            match, ring, log_len, commit, term, leader,
            quorum=q, window=W)
        got_p = pallas_masked_quorum_commit_index(
            match, ring, log_len, commit, term, leader,
            voters=full, voters_joint=full, window=W)
        assert (want_p == got_p).all(), trial
        votes = jnp.asarray(rng.random((G, P)) < 0.5)
        assert (masked_vote_win(votes, full, full)
                == (vote_count(votes) >= q)).all(), trial


def test_mask_majority_thresholds():
    m = jnp.asarray([[1, 1, 1, 0], [1, 1, 1, 1], [1, 0, 0, 0],
                     [0, 0, 0, 0]], bool)
    assert mask_majority(m).tolist() == [2, 3, 1, 1]


def test_masked_quorum_degenerate_configs():
    """Single voter, even-size joint C_old,new, and the all-learner
    group that must never commit."""
    W = 8
    ring = jnp.ones((3, W), jnp.int32)
    log_len = jnp.asarray([5, 5, 5], jnp.int32)
    commit = jnp.zeros(3, jnp.int32)
    term = jnp.ones(3, jnp.int32)
    leader = jnp.asarray([True, True, True])
    match = jnp.asarray([[5, 0, 0, 0],
                         [5, 4, 1, 0],
                         [5, 5, 5, 5]], jnp.int32)
    # g0: single voter (slot 0) — its own match IS the quorum index.
    # g1: joint config mid-promote of slot 3: C_new {0,1,2,3} needs 3,
    #     C_old {0,1,2} needs 2 — the commit candidate is the MIN of
    #     the two quorum indexes (3rd of [5,4,1,0] = 1; 2nd of [5,4,1]
    #     = 4) = 1.
    # g2: all-learner group: empty masks, no quorum can ever form.
    voters = jnp.asarray([[1, 0, 0, 0],
                          [1, 1, 1, 1],
                          [0, 0, 0, 0]], bool)
    jvot = jnp.asarray([[1, 0, 0, 0],
                        [1, 1, 1, 0],
                        [0, 0, 0, 0]], bool)
    got = masked_quorum_commit_index(
        match, ring, log_len, commit, term, leader,
        voters=voters, voters_joint=jvot, window=W)
    assert got.tolist() == [5, 1, 0]
    got_p = pallas_masked_quorum_commit_index(
        match, ring, log_len, commit, term, leader,
        voters=voters, voters_joint=jvot, window=W)
    assert got_p.tolist() == [5, 1, 0]
    got_w = masked_windowed_commit_index(
        match, ring, log_len, commit, term, leader,
        voters=voters, voters_joint=jvot, window=W)
    assert got_w.tolist() == [5, 1, 0]
    # The all-learner group can never elect either: every vote granted
    # still loses under an empty mask.
    votes = jnp.ones((3, 4), bool)
    win = masked_vote_win(votes, voters, jvot)
    assert win.tolist() == [True, True, False]


# -- conf-entry codec --------------------------------------------------

def test_conf_entry_codec_roundtrip():
    e = encode_conf_entry(CONF_KIND_ENTER_JOINT, 0b1110, 0b0111, 0b0001)
    assert is_conf_entry(e)
    assert decode_conf_entry(e) == (CONF_KIND_ENTER_JOINT, 0b1110,
                                    0b0111, 0b0001)
    # Discriminates against the other payload shapes on the wire.
    for other in (b"", b"SET k v", b"\x01envelope", e + b"x", e[:-1]):
        assert not is_conf_entry(other)
        assert decode_conf_entry(other) is None


# -- the host manager --------------------------------------------------

def test_manager_change_validation_and_one_in_flight():
    mm = MembershipManager(4, 1, initial_voters=(0, 1, 2))
    with pytest.raises(MembershipError):
        mm.make_change(0, "add_learner", 0)     # already a voter
    with pytest.raises(MembershipError):
        mm.make_change(0, "promote", 3)         # not a learner yet
    with pytest.raises(MembershipError):
        mm.make_change(0, "bogus", 3)
    with pytest.raises(MembershipError):
        mm.make_change(0, "add_learner", 9)     # slot out of range
    e = mm.make_change(0, "add_learner", 3)
    assert decode_conf_entry(e)[3] == 0b1000
    with pytest.raises(MembershipError):        # one in flight per group
        mm.make_change(0, "add_learner", 3)
    mm.abort_pending(0)
    mm.make_change(0, "add_learner", 3)         # latch released


def test_manager_joint_promote_flow_and_idempotent_apply():
    mm = MembershipManager(4, 1, initial_voters=(0, 1, 2))
    assert mm.apply(0, 1, mm.make_change(0, "add_learner", 3)) \
        is not None
    c = mm.config(0)
    assert c.learners == 0b1000 and not c.is_joint
    enter = mm.make_change(0, "promote", 3)
    assert mm.apply(0, 2, enter).is_joint
    assert mm.voter_mask(0) == 0b1111           # both masks count
    # While joint: no new change may start, but the leader drives the
    # LEAVE_JOINT (rate-limited re-propose).
    with pytest.raises(MembershipError):
        mm.make_change(0, "remove", 0)
    leave = mm.maybe_leave(0, tick_no=10, cooldown=40)
    assert leave is not None
    assert mm.maybe_leave(0, tick_no=20, cooldown=40) is None
    c = mm.apply(0, 3, leave)
    assert c.voters == 0b1111 and not c.is_joint
    # Replay/redelivery below the applied baseline is a no-op.
    assert mm.apply(0, 2, enter) is None
    assert mm.config(0).voters == 0b1111
    assert mm.conf_changes_applied == 3
    # A voter-less entry is hostile/corrupt: refused.
    assert mm.apply(0, 9, encode_conf_entry(1, 0, 0, 0)) is None


def test_manager_remove_keeps_a_voter_and_counts():
    mm = MembershipManager(3, 2)
    assert mm.counts() == (6, 0)
    mm.apply(0, 1, encode_conf_entry(CONF_KIND_LEAVE_JOINT, 0b001,
                                     0b001, 0b110))
    assert mm.counts() == (4, 2)
    with pytest.raises(MembershipError):
        mm.make_change(0, "remove", 0)          # last voter of g0
    # Group 1 untouched: per-group configs are independent.
    assert mm.config(1).voters == 0b111


def test_manager_restore_baseline_entries_and_pending():
    """WAL-replay restore: REC_CONF baseline, committed entries above
    it re-applied, appended-but-uncommitted ones back in the pending
    list (applied later when their commit passes)."""
    mm = MembershipManager(4, 1, initial_voters=(0, 1, 2))
    e_committed = encode_conf_entry(CONF_KIND_LEARNER, 0b0111, 0b0111,
                                    0b1000)
    e_pending = encode_conf_entry(CONF_KIND_ENTER_JOINT, 0b1111, 0b0111,
                                  0b0000)
    entries = [(1, b"SET k v"), (1, e_committed), (1, e_pending)]
    changed = mm.restore(0, (3, 0, 0b0111, 0b0111, 0b0000), entries,
                         start=4, commit=6)
    assert changed
    c = mm.config(0)
    assert c.index == 6 and c.learners == 0b1000
    assert mm.appended_list(0) == [(7, e_pending)]
    # The pending entry commits later: the live publish path applies it.
    got = mm.take_committed(0, 6, 7)
    assert got == [(7, e_pending)]
    assert mm.apply(0, 7, e_pending).is_joint


def test_manager_note_truncated_discards_clobbered_suffix():
    mm = MembershipManager(3, 1)
    e = encode_conf_entry(CONF_KIND_LEARNER, 0b111, 0b111, 0)
    mm.note_appended(0, 5, e)
    mm.note_appended(0, 8, e)
    mm.note_truncated(0, 6)
    assert mm.appended_list(0) == [(5, e)]
    assert mm.take_committed(0, 0, 4) == []


# -- WAL durability (REC_CONF) -----------------------------------------

def test_wal_conf_baseline_replays(tmp_path):
    with fsio.installed(fsio.StorageFaultInjector()):
        w = WAL(str(tmp_path / "w"))
        w.append_entry(0, 1, 1, b"x")
        assert w.set_conf(0, 5, 0, 0b011, 0b011, 0b100)
        w.set_conf(0, 7, 0, 0b111, 0b111, 0b000)   # last wins
        w.sync()
        w.close()
    logs = WAL.replay(str(tmp_path / "w"))
    assert logs[0].conf == (7, 0, 0b111, 0b111, 0b000)


def test_wal_conf_baseline_survives_compaction(tmp_path):
    """Segment compaction may unlink the segment holding both the conf
    ENTRY and its REC_CONF baseline: compact() must re-assert the
    latest baseline into the active segment (the hard-state survival
    contract) so a restart cannot boot on a stale voter set."""
    with fsio.installed(fsio.StorageFaultInjector()):
        w = WAL(str(tmp_path / "w"), segment_bytes=512)
        for i in range(1, 11):
            w.append_entry(0, i, 1, b"x" * 24)
        w.set_conf(0, 4, 0, 0b011, 0b011, 0b100)
        w.sync()
        for i in range(11, 41):
            w.append_entry(0, i, 1, b"x" * 24)
        w.sync()
        n = len  # one hard state for whatever the segments name
        w.compact({0: (30, 1)}, lambda names: (
            [1] * n(names), [-1] * n(names), [35] * n(names)))
        w.close()
    logs = WAL.replay(str(tmp_path / "w"))
    assert logs[0].start == 30
    assert logs[0].conf == (4, 0, 0b011, 0b011, 0b100)


# -- config validation -------------------------------------------------

def test_config_initial_voters_validation():
    RaftConfig(num_peers=4, initial_voters=(0, 2))
    with pytest.raises(ValueError):
        RaftConfig(num_peers=4, initial_voters=())
    with pytest.raises(ValueError):
        RaftConfig(num_peers=4, initial_voters=(0, 4))
    with pytest.raises(ValueError):
        RaftConfig(num_peers=4, initial_voters=(1, 1))


# The PR-4 "mesh ticks lockstep only" regression test
# (MeshLockstepOnlyError) is gone with the error itself: the mesh
# runtime now takes the per-peer timer vector through the sharded step
# (parallel/sharded.py timer_spec).  Skew-on-mesh coverage lives in
# tests/test_mesh.py (lockstep vs skewed elections diverge; mesh-skew
# chaos family digests reproduce) and `make chaos-mesh`.


# -- fused runtime lifecycle -------------------------------------------

def _tick_until(node, pred, limit=600, drain=None):
    for _ in range(limit):
        if pred():
            return True
        node.tick()
        node.publish_flush()
        if drain is not None:
            drain()
    return pred()


def test_fused_membership_lifecycle_and_restart(tmp_path):
    """The fused plane end to end: a 4-slot cluster booted on voters
    {0,1,2} (slot 3 a live spare) adds slot 3 as a learner, promotes
    it through joint consensus (auto LEAVE_JOINT), then removes slot 0
    — group 1 stays on the boot config throughout (per-group device
    configs inside one dispatch) — and a restart recovers the active
    config from the WAL REC_CONF baselines."""
    from raftsql_tpu.chaos.scenarios import _drain_fused_q
    from raftsql_tpu.runtime.fused import FusedClusterNode

    cfg = RaftConfig(num_groups=2, num_peers=4, log_window=32,
                     max_entries_per_msg=4, election_ticks=10,
                     heartbeat_ticks=1, tick_interval_s=0.0,
                     initial_voters=(0, 1, 2))
    node = FusedClusterNode(cfg, str(tmp_path), seed=7)
    node.publish_peers = {0}
    node.enable_membership()
    drain = lambda: _drain_fused_q(node.commit_q(0))
    try:
        assert _tick_until(node, lambda: node.leader_of(0) >= 0
                           and node.leader_of(1) >= 0, drain=drain)
        mm = node.membership
        assert mm.config(0).voters == 0b0111

        node.member_change(0, "add_learner", 3)
        assert _tick_until(node, lambda: mm.config(0).learners == 0b1000,
                           drain=drain)
        # The learner receives AppendEntries: its payload log follows
        # the leader's.
        node.propose_many(0, [b"SET a 1", b"SET b 2"])
        lead = node.leader_of(0)
        assert _tick_until(
            node, lambda: node.plogs[3].length(0)
            == node.plogs[lead].length(0) > 0, drain=drain)

        node.member_change(0, "promote", 3)
        # ENTER_JOINT applies, then the leader auto-proposes the
        # LEAVE_JOINT (rate-limited): the group must come out stable
        # on voters {0,1,2,3} without any further admin op.
        assert _tick_until(node, lambda: mm.config(0).voters == 0b1111
                           and not mm.config(0).is_joint, drain=drain)

        node.member_change(0, "remove", 0)
        assert _tick_until(node, lambda: mm.config(0).voters == 0b1110
                           and not mm.config(0).is_joint, drain=drain)

        # Group 1 never left the boot config: per-group independence.
        assert mm.config(1).voters == 0b0111 and mm.config(1).index == 0
        # The new configuration still commits (quorum of {1,2,3}).
        c0 = int(node._hard[node.leader_of(0), 0, 2])
        node.propose_many(0, [b"SET c 3"])
        assert _tick_until(
            node, lambda: int(node._hard[
                max(node.leader_of(0), 0), 0, 2]) > c0, drain=drain)
        doc = node.members_doc()
        assert doc["groups"]["0"]["voters"] == [1, 2, 3]
        assert doc["groups"]["1"]["voters"] == [0, 1, 2]
        assert node.metrics.conf_changes_applied >= 5
    finally:
        node.stop()

    # Restart: the active per-group configs come back from the WAL.
    node2 = FusedClusterNode(cfg, str(tmp_path), seed=7)
    node2.publish_peers = {0}
    node2.enable_membership()
    try:
        mm2 = node2.membership
        assert mm2.config(0).voters == 0b1110
        assert not mm2.config(0).is_joint
        assert mm2.config(1).voters == 0b0111
    finally:
        node2.stop()


# -- the node-replacement acceptance story -----------------------------

def _replacement_plan(seed=1):
    from raftsql_tpu.chaos import (DropWindow, MemberEvent,
                                   MembershipChaosPlan, NodeBoot,
                                   NodeCrash)
    return MembershipChaosPlan(
        seed=seed, ticks=120, peers=4,
        initial_voters=(0, 1, 2), initial_down=(3,),
        boots=(NodeBoot(30, 3),),
        events=(MemberEvent(34, "add_learner", 3),
                MemberEvent(60, "promote", 3),
                MemberEvent(85, "remove", 1)),
        crashes=(NodeCrash(26, 1, down=10 * 120),),   # permanent SIGKILL
        drops=(DropWindow(45, 60, 0.08),),
        heal_ticks=50, final_voters=(0, 2, 3))


def test_node_replacement_survives_and_reproduces(tmp_path):
    """The acceptance scenario as a tier-1 test: SIGKILL one voter of a
    3-voter cluster, boot a fresh machine into the spare slot, add it
    as a learner, promote it once caught up (joint consensus), remove
    the dead member — under a drop window — with ZERO lost acked
    writes (the runner's durability + log-matching invariants check
    every tick, and the final check proves the post-churn voter set
    still commits).  Two runs of the same plan produce identical
    result digests."""
    from raftsql_tpu.chaos import MembershipChaosRunner

    plan = _replacement_plan()
    r1 = MembershipChaosRunner(plan, str(tmp_path / "a")).run()
    assert r1["crashes"] == 1 and r1["restarts"] == 0   # kill is final
    assert r1["boots"] == 1
    # add_learner + promote + remove, applied on BOTH groups.
    assert r1["member_ops_applied"] == 6
    assert r1["commits"] > 20
    r2 = MembershipChaosRunner(plan, str(tmp_path / "b")).run()
    assert r1["result_digest"] == r2["result_digest"]
    assert r1 == r2


def test_tcp_rebind_crash_restart_catchup(tmp_path):
    """ROADMAP chaos-frontier closure: stop a node under the REAL TCP
    transport (listener closes, port released), rebind the SAME port
    on restart, and require peer reconnect + log catch-up (post-heal
    commit spread bounded by one append batch)."""
    from raftsql_tpu.chaos import (NodeCrash, TcpRebindChaosRunner,
                                   TcpRebindPlan)

    plan = TcpRebindPlan(seed=2, ticks=100,
                         restarts=(NodeCrash(40, -2, down=20),),
                         heal_ticks=60)
    r = TcpRebindChaosRunner(plan, str(tmp_path)).run()
    assert r["stops"] == 1 and r["rebinds"] == 1
    assert r["commits"] > 10


# -- admin HTTP API (both serving planes) ------------------------------

TIMEOUT = 30.0


@pytest.fixture(params=["threaded", "aio"])
def member_server(request, tmp_path):
    """Single live node owning voter slot 0 of a 2-slot cluster (slot 1
    is provisioned spare capacity): self-elects with quorum {0} and can
    legally add/remove slot 1 as a learner."""
    from raftsql_tpu.api.aio import AioSQLServer
    from raftsql_tpu.api.http import SQLServer
    from raftsql_tpu.models.sqlite_sm import SQLiteStateMachine
    from raftsql_tpu.runtime.db import RaftDB
    from raftsql_tpu.runtime.pipe import RaftPipe
    from raftsql_tpu.transport.loopback import LoopbackHub, \
        LoopbackTransport

    cfg = RaftConfig(num_groups=2, num_peers=2, tick_interval_s=0.005,
                     log_window=64, max_entries_per_msg=4,
                     initial_voters=(0,))
    pipe = RaftPipe.create(1, 2, cfg, LoopbackTransport(LoopbackHub()),
                           data_dir=str(tmp_path / "raftsql-1"))
    rdb = RaftDB(lambda g: SQLiteStateMachine(
        str(tmp_path / f"m-g{g}.db")), pipe, num_groups=2)
    srv_cls = SQLServer if request.param == "threaded" else AioSQLServer
    srv = srv_cls(0, rdb, host="127.0.0.1", timeout_s=TIMEOUT)
    srv.start()
    yield srv
    srv.stop()
    rdb.close()


def _req(srv, method, path, body=b""):
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=10)
    try:
        conn.request(method, path, body=body)
        r = conn.getresponse()
        return r.status, r.read()
    finally:
        conn.close()


def _members(srv):
    status, data = _req(srv, "GET", "/members")
    assert status == 200
    return json.loads(data)


def test_members_api_read_change_and_validation(member_server):
    srv = member_server
    doc = _members(srv)
    assert doc["num_peers"] == 2
    assert doc["groups"]["0"]["voters"] == [0]
    assert doc["groups"]["0"]["learners"] == []

    # Admin write: add slot 1 as a learner of group 0; the change is a
    # log entry applied at commit — poll the read side.  Changes are
    # leader-only (421 + retry hint until the node self-elects).
    deadline = time.monotonic() + TIMEOUT
    while True:
        status, data = _req(srv, "POST", "/members", json.dumps(
            {"group": 0, "op": "add_learner", "peer": 1}).encode())
        if status != 421 or time.monotonic() >= deadline:
            break
        time.sleep(0.02)
    assert status == 200, data
    deadline = time.monotonic() + TIMEOUT
    while time.monotonic() < deadline:
        if _members(srv)["groups"]["0"]["learners"] == [1]:
            break
        time.sleep(0.02)
    doc = _members(srv)
    assert doc["groups"]["0"]["learners"] == [1]
    assert doc["groups"]["1"]["learners"] == []     # per-group config

    # Validation errors surface as 400s.
    for bad in ({"group": 0, "op": "remove", "peer": 0},   # last voter
                {"group": 0, "op": "promote", "peer": 0},  # not learner
                {"group": 0, "op": "bogus", "peer": 1},
                {"group": 9, "op": "add_learner", "peer": 1}):
        status, _ = _req(srv, "POST", "/members",
                         json.dumps(bad).encode())
        assert status == 400, bad

    # And back out: remove the learner.
    status, _ = _req(srv, "POST", "/members", json.dumps(
        {"group": 0, "op": "remove_learner", "peer": 1}).encode())
    assert status == 200
    deadline = time.monotonic() + TIMEOUT
    while time.monotonic() < deadline:
        if _members(srv)["groups"]["0"]["learners"] == []:
            break
        time.sleep(0.02)
    assert _members(srv)["groups"]["0"]["learners"] == []


# -- slow sweeps -------------------------------------------------------

@pytest.mark.slow
def test_membership_seed_sweep(tmp_path):
    """Acceptance-scale sweep: seeded generator plans (permanent kill,
    fresh boot, add/promote/remove under drops + a transient crash),
    each seed run twice and digest-compared."""
    from raftsql_tpu.chaos import (MembershipChaosRunner,
                                   generate_membership_plan)
    for seed in range(3):
        plan = generate_membership_plan(seed)
        r1 = MembershipChaosRunner(plan,
                                   str(tmp_path / f"s{seed}a")).run()
        r2 = MembershipChaosRunner(plan,
                                   str(tmp_path / f"s{seed}b")).run()
        assert r1["result_digest"] == r2["result_digest"], seed
        assert r1["member_ops_applied"] == 6, seed


@pytest.mark.slow
def test_tcp_rebind_seed_sweep(tmp_path):
    from raftsql_tpu.chaos import (TcpRebindChaosRunner,
                                   generate_tcp_rebind_plan)
    for seed in range(3):
        plan = generate_tcp_rebind_plan(seed)
        r = TcpRebindChaosRunner(plan, str(tmp_path / f"s{seed}")).run()
        assert r["rebinds"] == 2, seed
        assert r["commits"] > 20, seed
