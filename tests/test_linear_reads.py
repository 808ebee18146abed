"""Linearizable reads (ReadIndex, raft §6.4) — beyond reference parity.

The reference serves GETs from the local replica and documents the
staleness (db.go:128-130, raftsql_test.go:150-158).  `query(...,
linear=True)` upgrades a read: only the group's current leader serves
it, after a quorum re-confirms its leadership on a round started after
the call and the local apply catches up to the read point.  These tests
pin the three behaviors that make that linearizable:

  - read-your-writes at the leader, immediately after the ack;
  - non-leaders refuse with the leader's identity (no silent staleness);
  - a leader cut off from its quorum cannot serve (no stale reads from
    a deposed leader that doesn't know it yet).
"""
import os
import time

import pytest

from raftsql_tpu.config import LEADER, RaftConfig
from raftsql_tpu.models.sqlite_sm import SQLiteStateMachine
from raftsql_tpu.runtime.db import NotLeaderError, RaftDB
from raftsql_tpu.runtime.errors import ReadTimeout
from raftsql_tpu.runtime.pipe import RaftPipe
from raftsql_tpu.transport.loopback import (FaultPlan, LoopbackHub,
                                            LoopbackTransport)

TICK = 0.005
TIMEOUT = 30.0


@pytest.fixture
def cluster(tmp_path):
    faults = FaultPlan()
    hub = LoopbackHub(faults=faults)
    cfg = RaftConfig(num_groups=1, num_peers=3, tick_interval_s=TICK,
                     election_ticks=10, log_window=64,
                     max_entries_per_msg=4)
    dbs = []
    for i in range(3):
        pipe = RaftPipe.create(
            i + 1, 3, cfg, LoopbackTransport(hub),
            data_dir=os.path.join(str(tmp_path), f"raftsql-{i + 1}"))
        dbs.append(RaftDB(
            lambda g, i=i: SQLiteStateMachine(
                os.path.join(str(tmp_path), f"db-{i}.db")),
            pipe, num_groups=1))
    yield dbs, faults
    for db in dbs:
        try:
            db.close()
        except Exception:
            pass


def leader_index(dbs, timeout=TIMEOUT) -> int:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        for i, db in enumerate(dbs):
            node = db.pipe.node
            if node._last_role[0] == LEADER:
                return i
        time.sleep(0.02)
    raise AssertionError("no leader elected")


def test_linear_read_your_writes_at_leader(cluster):
    dbs, _ = cluster
    assert dbs[0].propose("CREATE TABLE t (v text)").wait(TIMEOUT) is None
    lead = leader_index(dbs)
    for k in range(5):
        assert dbs[lead].propose(
            f"INSERT INTO t (v) VALUES ('k{k}')").wait(TIMEOUT) is None
        # Immediately after the ack, a linear read at the leader must see
        # the write (the ack already implies local apply; the quorum
        # round proves the leader is still current).  The election
        # timeout is 50 ms: on a loaded box the leadership can move
        # between two lines, the old leader then refuses, as it must,
        # and the read goes to whoever leads now, where the
        # acknowledged write must show all the same.
        deadline = time.monotonic() + TIMEOUT
        while True:
            try:
                got = dbs[lead].query("SELECT count(*) FROM t", linear=True,
                                      timeout=TIMEOUT)
                break
            except NotLeaderError:
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.05)
                lead = leader_index(dbs)
        assert got == f"|{k + 1}|\n", got


def test_linear_read_rejected_at_follower(cluster):
    dbs, _ = cluster
    assert dbs[0].propose("CREATE TABLE t (v text)").wait(TIMEOUT) is None
    lead = leader_index(dbs)
    follower = (lead + 1) % 3
    # Followers must refuse rather than serve a possibly-stale answer,
    # and must say who the leader is.
    with pytest.raises(NotLeaderError) as ei:
        dbs[follower].query("SELECT count(*) FROM t", linear=True,
                            timeout=5.0)
    assert ei.value.leader == lead + 1
    # Plain (reference-parity) reads still work on followers — but they
    # are STALE by design, so poll until the follower's replica has
    # applied the schema (reference raftsql_test.go:159-170).
    deadline = time.monotonic() + TIMEOUT
    while True:
        try:
            assert dbs[follower].query(
                "SELECT count(*) FROM t").startswith("|")
            break
        except Exception:
            if time.monotonic() > deadline:
                raise
            time.sleep(0.05)


def test_linear_read_blocked_without_quorum(cluster):
    """A leader partitioned from its quorum must NOT serve a linear read
    — that is the exact staleness window ReadIndex closes (the deposed
    leader may not know a new leader committed past it)."""
    dbs, faults = cluster
    assert dbs[0].propose("CREATE TABLE t (v text)").wait(TIMEOUT) is None
    lead = leader_index(dbs)
    faults.isolate(lead + 1, range(1, 4))
    # Allow in-flight quorum confirmations to drain past reg_tick + 2.
    time.sleep(20 * TICK)
    t0 = time.monotonic()
    with pytest.raises((TimeoutError, NotLeaderError)):
        dbs[lead].query("SELECT count(*) FROM t", linear=True, timeout=1.5)
    assert time.monotonic() - t0 < 10.0
    faults.heal()


def test_reads_do_not_wait_on_an_entry_that_carries_no_command(cluster):
    """Right after an election the newest committed entry is the new
    leader's no-op.  The state machine applies nothing for it, so its
    applied index stays one short of the commit index — and a linear or
    follower read, whose target IS the commit index, used to wait for an
    apply that could not happen (503 until the group's next write).
    The commit stream now delivers the no-op's index too."""
    dbs, _ = cluster
    deadline = time.monotonic() + TIMEOUT
    while True:
        lead = leader_index(dbs)
        node = dbs[lead].pipe.node
        while int(node._hard_np[0, 2]) < 1:         # the no-op committed
            assert time.monotonic() < deadline
            time.sleep(0.02)
        assert dbs[lead].watermark(0) == 0          # nothing was applied
        try:
            assert dbs[lead].query("SELECT 1", linear=True,
                                   timeout=5) == "|1|\n"
            break
        except (NotLeaderError, ReadTimeout) as e:
            # The election timer is 50 ms: on a loaded host the node
            # `leader_index` named can be deposed, or be a deposed
            # leader that has not heard yet, and then it refuses or
            # cannot re-confirm.  Ask again who leads.  The wait this
            # test is about is the one for the APPLY: never retried.
            if getattr(e, "phase", None) == "apply" \
                    or time.monotonic() > deadline:
                raise
    assert dbs[lead].query("SELECT 1", mode="follower",
                           timeout=TIMEOUT) == "|1|\n"
    assert dbs[lead].watermark(0) == 0      # served over no-ops alone


def test_fused_reads_after_restart_do_not_wait_on_the_new_noop(
        tmp_path, monkeypatch):
    """The same on the co-located runtime, where it bit hardest: after a
    restart EVERY group elects again, so every group's newest entry is a
    no-op, and no linear read could be served until each group was
    written to (chip_smoke.py's restart read-back found it)."""
    monkeypatch.chdir(tmp_path)
    from raftsql_tpu.server.main import build_fused_node

    rdb = build_fused_node(groups=2, peers=3, tick=0.002)
    try:
        assert rdb.propose("CREATE TABLE t (v text)", 0).wait(30) is None
        assert rdb.propose("INSERT INTO t (v) VALUES ('x')",
                           0).wait(30) is None
        # Group 1 was never written to: its only entry is a no-op.
        assert rdb.query("SELECT 1", 1, linear=True, timeout=10) == "|1|\n"
    finally:
        rdb.close()
    rdb = build_fused_node(groups=2, peers=3, tick=0.002)   # WAL replay
    try:
        assert rdb.query("SELECT v FROM t", 0, linear=True,
                         timeout=10) == "|x|\n"
        assert rdb.query("SELECT v FROM t", 0, mode="follower",
                         timeout=10) == "|x|\n"
    finally:
        rdb.close()
