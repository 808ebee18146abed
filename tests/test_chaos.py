"""Chaos harness tests (raftsql_tpu/chaos/).

Fast tier-1 scenarios: seeded drops/delays/partitions, crash+restart
of the fused runtime AND the lockstep RaftNode cluster, injected fsync
failures and mid-record power loss — with the four invariants
(durability, single leader per term, log matching, KV linearizability)
checked inside the runners (a violation raises and fails the test).
The full acceptance-scale sweeps are `slow`-marked; `make chaos
SEED=...` drives the same runner from the CLI, twice, and compares
digests.
"""
import dataclasses
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raftsql_tpu.chaos import (ChaosSchedule, FsyncFault, FusedChaosRunner,
                               NodeClusterChaosRunner, SkewWindow,
                               SnapshotChaosRunner, TcpClusterChaosRunner,
                               TornWriteFault, generate, generate_asym,
                               generate_compact, generate_corrupt_plan,
                               generate_enospc, generate_node_plan,
                               generate_skew, generate_snapshot_plan,
                               generate_stall, generate_tcp_plan)
from raftsql_tpu.config import RaftConfig
from raftsql_tpu.core.cluster import empty_cluster_inbox
from raftsql_tpu.runtime.fused import PIPELINE_STEPS
from raftsql_tpu.storage import fsio
from raftsql_tpu.transport.faults import hold_messages, release_messages


# -- schedules ---------------------------------------------------------

def test_schedule_generation_deterministic_and_meets_floors():
    a = generate(12, ticks=240)
    b = generate(12, ticks=240)
    assert a == b and a.digest() == b.digest()
    assert a.ticks >= 200
    assert len(a.partitions) >= 2
    assert len(a.crashes) >= 2
    assert len(a.fsync_faults) >= 1
    assert len(a.torn_writes) >= 1
    assert generate(13, ticks=240).digest() != a.digest()


# -- the storage fault seam (storage/fsio.py) --------------------------

def test_fsio_fail_silent_tear_and_drop(tmp_path):
    inj = fsio.StorageFaultInjector()
    inj.add_rule(str(tmp_path), fail_at=(2,))
    p = str(tmp_path / "f.log")
    with fsio.installed(inj):
        f = open(p, "ab")
        fsio.write(f, b"A" * 10)
        fsio.fsync_file(f)                       # op 1: real sync
        fsio.write(f, b"B" * 10)
        with pytest.raises(fsio.FsyncFaultError):
            fsio.fsync_file(f)                   # op 2: injected fail
        f.close()
    assert inj.synced_size[p] == 10
    # A tear cuts into the unsynced record but never below the synced
    # prefix; dropping unsynced bytes restores exactly the synced size.
    assert inj.tear_last_write(p)
    assert 10 <= os.path.getsize(p) < 20
    inj.drop_unsynced(p)
    assert os.path.getsize(p) == 10


def test_fsio_crash_point_fires_after_the_write_lands(tmp_path):
    inj = fsio.StorageFaultInjector()
    inj.add_rule(str(tmp_path), crash_write_at=(2,), tag=7)
    p = str(tmp_path / "g.log")
    with fsio.installed(inj):
        f = open(p, "ab")
        fsio.write(f, b"first|")
        with pytest.raises(fsio.CrashPointError) as ei:
            fsio.write(f, b"second")
        assert ei.value.tag == 7
        f.close()
    # Page-cache semantics: the crashing write reached the file; the
    # power-loss simulation then tears it mid-record.
    assert os.path.getsize(p) == len(b"first|second")
    assert inj.tear_last_write(p)
    assert len(b"first|") <= os.path.getsize(p) < len(b"first|second")


def test_fsio_active_forces_python_wal_backend(tmp_path):
    from raftsql_tpu.storage.wal import WAL

    with fsio.installed(fsio.StorageFaultInjector()):
        w = WAL(str(tmp_path / "w"))
        assert not w.is_native
        w.append_entry(0, 1, 1, b"x")
        w.sync()
        w.close()
    logs = WAL.replay(str(tmp_path / "w"))
    assert [d for (_, d) in logs[0].entries] == [b"x"]


# -- message-plane delay masks -----------------------------------------

def test_hold_release_messages_roundtrip():
    cfg = RaftConfig(num_groups=2, num_peers=3, log_window=32,
                     max_entries_per_msg=4)
    ones = jax.tree.map(lambda x: jnp.ones_like(x),
                        empty_cluster_inbox(cfg))
    mask = np.zeros(ones.v_type.shape, bool)
    mask[0] = True                       # delay everything sent to peer 0
    delivered, held = hold_messages(ones, jnp.asarray(mask))
    assert int(np.asarray(delivered.v_type)[0].sum()) == 0
    assert int(np.asarray(held.v_type)[1:].sum()) == 0
    merged = release_messages(delivered, held)
    for a, b in zip(jax.tree.leaves(merged), jax.tree.leaves(ones)):
        assert (np.asarray(a) == np.asarray(b)).all()


# -- fused-runtime scenarios (fast tier) -------------------------------

def test_fused_scenario_fast_invariants(tmp_path):
    """Seeded drops + delays + partitions (one leader-targeted) +
    crashes + a failed fsync + a torn write, 150 ticks.  Invariants
    are enforced inside the runner every tick."""
    sched = generate(5, ticks=150)
    r = FusedChaosRunner(sched, str(tmp_path / "a")).run()
    assert r["committed_entries"] > 0
    assert r["reads_checked"] > 0
    assert r["crashes"] >= len(sched.crashes)
    assert r["partitions"] >= 2
    assert r["safety_observations"] > 100


def test_fused_scenario_reproduces_bit_for_bit(tmp_path):
    """Same seed, fresh data dirs: the entire run — schedule, fault
    firings, committed history, reads — reproduces identically."""
    sched = generate(9, ticks=120)
    r1 = FusedChaosRunner(sched, str(tmp_path / "a")).run()
    r2 = FusedChaosRunner(sched, str(tmp_path / "b")).run()
    assert r1 == r2
    assert r1["result_digest"] == r2["result_digest"]


def test_torn_write_power_loss_repairs(tmp_path):
    """A mid-record power loss alone: the torn record is dropped by
    WAL._repair_tail on restart and every published entry survives
    (the durability ledger is verified at the restart)."""
    sched = ChaosSchedule(seed=3, ticks=100,
                          torn_writes=(TornWriteFault(1, 40),))
    r = FusedChaosRunner(sched, str(tmp_path)).run()
    assert r["torn_write_faults"] == 1
    assert r["torn_writes"] >= 1
    assert r["committed_entries"] > 0


def test_fsync_fault_is_fatal_and_recovers(tmp_path):
    """An injected fsync failure crashes the process (etcd posture)
    and the restart serves on from the durable prefix."""
    sched = ChaosSchedule(seed=4, ticks=100,
                          fsync_faults=(FsyncFault(0, 20),))
    r = FusedChaosRunner(sched, str(tmp_path)).run()
    assert r["fsync_faults"] == 1
    assert r["committed_entries"] > 0


@pytest.mark.parametrize("steps", [2, PIPELINE_STEPS])
def test_fused_scenario_multistep_epoch_framing(tmp_path, steps):
    """The same chaos under multi-step dispatch (the served node's
    depth among them): crashes now interact with epoch framing
    (repair_epochs drops uncommitted dispatch frames on restart)."""
    sched = ChaosSchedule(seed=6, ticks=100,
                          torn_writes=(TornWriteFault(0, 50),))
    r = FusedChaosRunner(sched, str(tmp_path), steps=steps).run()
    assert r["committed_entries"] > 0
    assert r["crashes"] >= 1


# -- the extended fault matrix (one fast seed per family) --------------

def test_fsio_enospc_fires_once_before_the_write(tmp_path):
    """ENOSPC raises BEFORE any byte lands (clean tail) and the trigger
    is consumed: the post-restart retry of the same record succeeds."""
    inj = fsio.StorageFaultInjector()
    inj.add_rule(str(tmp_path), enospc_write_at=(2,))
    p = str(tmp_path / "e.log")
    with fsio.installed(inj):
        f = open(p, "ab")
        fsio.write(f, b"A" * 10)
        with pytest.raises(fsio.EnospcError):
            fsio.write(f, b"B" * 10)
        assert os.path.getsize(p) == 10        # nothing landed
        fsio.write(f, b"B" * 10)               # consumed: retry lands
        f.close()
    assert os.path.getsize(p) == 20
    assert inj.enospc_hits == 1


def test_fsio_stall_counts_and_still_syncs(tmp_path):
    import time as _time
    inj = fsio.StorageFaultInjector()
    inj.add_rule(str(tmp_path), stall_at=(1,), stall_s=0.05)
    p = str(tmp_path / "s.log")
    with fsio.installed(inj):
        f = open(p, "ab")
        fsio.write(f, b"X")
        t0 = _time.monotonic()
        fsio.fsync_file(f)
        assert _time.monotonic() - t0 >= 0.05   # it stalled ...
        f.close()
    assert inj.fsync_stalls == 1
    assert inj.synced_size[p] == 1              # ... but synced for real


def test_family_asym_partition(tmp_path):
    """One-directional partitions (leader-deafness + a random link cut)
    + a crash: all invariants in-run, counters reported."""
    r = FusedChaosRunner(generate_asym(2, ticks=110),
                         str(tmp_path)).run()
    assert r["asym_partitions"] == 2
    assert r["crashes"] >= 1
    assert r["committed_entries"] > 0


def test_family_clock_skew_changes_elections(tmp_path):
    """The lockstep-timer assumption is the suspect one (ROADMAP): the
    SAME seed run lockstep vs with per-peer timer skew must elect
    DIFFERENT leaders somewhere — proof the per-peer timer_inc really
    reaches the device step — while both runs keep every invariant."""
    sk = generate_skew(0, ticks=120)
    lock = dataclasses.replace(sk, skews=())
    ra = FusedChaosRunner(lock, str(tmp_path / "lock"))
    rep_a = ra.run()
    rb = FusedChaosRunner(sk, str(tmp_path / "skew"))
    rep_b = rb.run()
    assert rep_b["skew_ticks"] > 0 and rep_a["skew_ticks"] == 0
    # Election behavior diverges: some (group, term) elected a
    # different leader (both runs' ElectionSafety maps are complete
    # run histories, so comparing them compares every election).
    assert ra.safety._leader_of_term != rb.safety._leader_of_term
    assert rep_a["result_digest"] != rep_b["result_digest"]
    # And the skewed run's fault counters export through NodeMetrics.
    assert rb.final_metrics.faults_skew_ticks == rep_b["skew_ticks"]
    assert rb.final_metrics.snapshot()["faults"]["skew_ticks"] \
        == rep_b["skew_ticks"]


def test_family_skew_reproduces(tmp_path):
    sk = generate_skew(4, ticks=100)
    r1 = FusedChaosRunner(sk, str(tmp_path / "a")).run()
    r2 = FusedChaosRunner(sk, str(tmp_path / "b")).run()
    assert r1 == r2


def test_family_enospc(tmp_path):
    """Disk-full on WAL append is fatal (etcd posture), restart serves
    on from a clean tail, and the counter exports."""
    runner = FusedChaosRunner(generate_enospc(1, ticks=110),
                              str(tmp_path))
    r = runner.run()
    assert r["enospc_hits"] == 2
    assert r["crashes"] >= 2
    assert r["committed_entries"] > 0
    assert runner.final_metrics.faults_enospc == 2
    assert runner.final_metrics.snapshot()["faults"]["enospc"] == 2


def test_family_fsync_stall(tmp_path):
    """Slow-disk fsync stalls: latency, never corruption — the run
    completes with every invariant and counts each stall."""
    runner = FusedChaosRunner(generate_stall(1, ticks=100),
                              str(tmp_path))
    r = runner.run()
    assert r["fsync_stalls"] > 0
    assert r["committed_entries"] > 0
    assert runner.final_metrics.faults_fsync_stalls == r["fsync_stalls"]


def test_family_compact_crash_interleaving(tmp_path):
    """Aggressive compaction under crashes (one a torn-write power
    loss): restart replays COMPACT-marked WALs, the durability audit
    and log matching run floor-aware, and the KV state survives through
    the ledger's snapshot stand-in."""
    r = FusedChaosRunner(generate_compact(3, ticks=160),
                         str(tmp_path)).run()
    assert r["compactions"] > 0
    assert r["crashes"] >= 2
    assert r["torn_write_faults"] >= 1
    assert r["committed_entries"] > 40


def test_family_corrupt_frames_node_plane(tmp_path):
    """Byzantine frame corruption on the lockstep wire plane: every
    mangled frame is CRC-dropped (counted into the receiving node's
    metrics), consensus rides out the loss, and the run reproduces."""
    plan = generate_corrupt_plan(1, ticks=200)
    r1 = NodeClusterChaosRunner(plan, str(tmp_path / "a")).run()
    assert r1["corrupt_frames"] > 0
    assert r1["commits"] > 20
    r2 = NodeClusterChaosRunner(plan, str(tmp_path / "b")).run()
    assert r1["result_digest"] == r2["result_digest"]


def test_family_skew_node_plane(tmp_path):
    """Per-peer timer skew on the lockstep RaftNode plane: each node
    ticks with its own timer_inc (0 = stalled clock, 2 = fast) while a
    crash interleaves — invariants hold, counters export."""
    plan = dataclasses.replace(generate_node_plan(2, ticks=240),
                               skews=(SkewWindow(60, 120, (2, 1, 0)),))
    r = NodeClusterChaosRunner(plan, str(tmp_path)).run()
    assert r["skew_ticks"] > 0
    assert r["commits"] > 20


def test_family_snapshot_install_convergence(tmp_path):
    """Compaction + InstallSnapshot + crash interleaving: a follower
    crashed past every retained floor is rebuilt by a full state
    transfer, a second (leader-targeted) crash lands later, and after
    the heal window the survivors CONVERGE (the new invariant)."""
    plan = generate_snapshot_plan(0)
    r = SnapshotChaosRunner(plan, str(tmp_path)).run()
    assert r["snapshots_installed"] > 0
    assert r["compactions"] > 0
    assert r["crashes"] == 2
    assert r["commits"] > 100


def test_family_tcp_transport(tmp_path):
    """Chaos under the REAL TCP transport: send-side drops, asymmetric
    blocks, frame corruption, delays.  Invariants hold on every run
    (this plane is not bit-reproducible — kernel-scheduled arrival);
    every corrupt frame is dropped + counted at the receivers."""
    plan = generate_tcp_plan(1, ticks=140)
    r = TcpClusterChaosRunner(plan, str(tmp_path)).run()
    assert r["sent_corrupted"] > 0
    assert r["corrupt_frames_dropped"] > 0
    assert r["sent_dropped"] > 0
    assert r["asym_partitions"] == 1
    assert r["commits"] > 20


# -- leadership-transfer nemesis (PR 11) -------------------------------

def test_family_transfer_under_nemesis(tmp_path):
    """Graceful transfers racing drops, a leader-targeted partition, an
    asym cut, skew and a crash under acked-PUT load — every transfer
    resolves, at least one completes, post-transfer probes commit, and
    the run reproduces bit-for-bit."""
    from raftsql_tpu.chaos import TransferChaosRunner, generate_transfers
    plan = generate_transfers(0)
    r1 = TransferChaosRunner(plan, str(tmp_path / "a")).run()
    r2 = TransferChaosRunner(plan, str(tmp_path / "b")).run()
    assert r1 == r2
    assert r1["transfers_requested"] >= 6
    assert r1["transfers_completed"] >= 1
    assert r1["transfer_probes_confirmed"] >= 1
    assert r1["partitions"] >= 1 and r1["crashes"] >= 1
    assert r1["plan_digest"] == plan.digest()


def test_transfer_falsification_pair(tmp_path, monkeypatch):
    """The robustness headline: the SAME directed lagging-target
    schedule must CATCH the deliberately broken transfer kernel
    (unsafe_transfer: depose the leader before the target caught up —
    the target cannot win the election, the transfer aborts) and PASS
    the correct kernel (catch-up gate holds the TimeoutNow until the
    target's match_index is current, then it wins immediately)."""
    from raftsql_tpu.chaos import (TransferChaosRunner,
                                   falsification_transfer_plan)
    from raftsql_tpu.chaos.invariants import InvariantViolation
    monkeypatch.setenv("RAFTSQL_FLIGHT_DIR", str(tmp_path / "flight"))
    with pytest.raises(InvariantViolation,
                       match="TRANSFER-AVAILABILITY"):
        TransferChaosRunner(falsification_transfer_plan(0, broken=True),
                            str(tmp_path / "broken")).run()
    r = TransferChaosRunner(falsification_transfer_plan(0, broken=False),
                            str(tmp_path / "ok")).run()
    assert r["transfers_completed"] == 1
    assert r["max_transfer_stall"] <= 60


# -- threaded RaftNode cluster scenarios -------------------------------

def test_node_cluster_partition_leader_kill_restart(tmp_path):
    """Lockstep 3-node RaftNode cluster: a partition window, a
    leader-targeted kill and a follower kill (hard crashes), each
    restarted from its WAL.  Election safety, per-node durability
    across restart, and cross-node log matching are enforced in-run."""
    plan = generate_node_plan(7, ticks=280)
    r = NodeClusterChaosRunner(plan, str(tmp_path)).run()
    assert r["crashes"] == 2
    assert r["restarts"] == 2
    assert r["partitions"] == 1
    assert r["commits"] > 20


# -- deep sweeps (slow tier) -------------------------------------------

@pytest.mark.slow
def test_chaos_seed_sweep_deep(tmp_path):
    """Acceptance-scale sweep: several seeds at >= 240 ticks, each run
    twice — every run must pass all invariants and reproduce
    bit-for-bit."""
    for seed in range(4):
        sched = generate(seed, ticks=240)
        r1 = FusedChaosRunner(sched, str(tmp_path / f"s{seed}a")).run()
        r2 = FusedChaosRunner(sched, str(tmp_path / f"s{seed}b")).run()
        assert r1 == r2, f"seed {seed} diverged"
        assert r1["fsync_faults"] >= 1
        assert r1["torn_writes"] >= 1


@pytest.mark.slow
def test_node_cluster_seed_sweep(tmp_path):
    for seed in range(3):
        plan = generate_node_plan(seed, ticks=400)
        r = NodeClusterChaosRunner(plan,
                                   str(tmp_path / f"s{seed}")).run()
        assert r["commits"] > 20, f"seed {seed} starved"


@pytest.mark.slow
def test_matrix_seed_sweep(tmp_path):
    """Acceptance-scale matrix sweep: several seeds through every
    family via the `make chaos-matrix` entry point (deterministic
    families digest-compared inside)."""
    from raftsql_tpu.chaos.run import run_matrix
    for seed in range(3):
        assert run_matrix(seed) == 0, f"seed {seed} failed"


@pytest.mark.slow
def test_snapshot_family_seed_sweep(tmp_path):
    for seed in range(3):
        plan = generate_snapshot_plan(seed)
        r = SnapshotChaosRunner(plan, str(tmp_path / f"s{seed}")).run()
        assert r["snapshots_installed"] > 0, f"seed {seed}: no install"
