"""CLI entry point — the reference's server/main.go composition.

Wires proposeC→raftPipe→raftdb→HTTP exactly as the reference does
(reference server/main.go:24-38), with the TPU-native pieces underneath:

    python -m raftsql_tpu.server.main \
        --cluster http://127.0.0.1:12379,http://127.0.0.1:22379,... \
        --id 1 --port 12380

Flag parity: --cluster / --id / --port match the reference (main.go:25-27);
the DB file is `raftsql-<id>.db` (main.go:37) and the WAL dir
`raftsql-<id>` (raft.go:69).  New knobs expose the batched engine:
--groups (raft groups served by this cluster), --tick (seconds per device
step; the reference hard-codes 100ms, raft.go:207).
"""
from __future__ import annotations

import argparse
import logging
import os
import signal
import sys
import threading
import time


def _raise_nofile_limit(resume: bool) -> None:
    """The state-machine store (models/store.py) holds SQLite handles
    for the groups in use and budgets them by what RLIMIT_NOFILE
    leaves: so raise the soft limit to the hard one, which is the
    largest budget this process can have, whatever --groups says.  A
    hard limit under what the store needs to work at all (its least
    number of handles, of three descriptors each in resume mode and
    one in parity mode, beside the reserve for WALs, rings and
    sockets) stops here, with a sentence that names the limit,
    instead of in `sqlite3.connect` halfway through a run."""
    import resource

    from raftsql_tpu.models.store import MIN_HANDLES, files_needed
    need = files_needed(3 if resume else 1)
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if hard != resource.RLIM_INFINITY and hard < need:
        raise SystemExit(
            f"raftsql: this deployment needs at least {need} open files "
            f"({MIN_HANDLES} SQLite handles for the groups in use, "
            f"besides the WALs, rings and sockets) but RLIMIT_NOFILE's "
            f"hard limit is {hard}; raise it (ulimit -n / LimitNOFILE)")
    if soft != hard:
        resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))


def _groups_on_file(path_of, groups: int, resume: bool) -> list:
    """The groups whose database a former process left, for RaftDB's
    `existing` (under --resume their applied indexes are read off the
    files at boot; without it every file is rebuilt and none counts)."""
    import os
    if not resume:
        return []
    return [g for g in range(groups) if os.path.exists(path_of(g))]


from raftsql_tpu.api.http import SQLServer
from raftsql_tpu.config import RaftConfig
from raftsql_tpu.models.sqlite_sm import SQLiteStateMachine
from raftsql_tpu.runtime.db import RaftDB
from raftsql_tpu.runtime.pipe import RaftPipe
from raftsql_tpu.transport.tcp import TcpTransport


def build_node(cluster: str, node_id: int, groups: int = 1,
               tick: float = 0.01, election_ticks: int | None = None,
               data_prefix: str = "raftsql", resume: bool = False,
               compact_every: int = 0, compact_keep: int = 1024,
               wal_segment_bytes: int = 4 << 20,
               trace: bool = False, lease_ticks: int = 0,
               max_clock_skew: int = 1,
               write_quorum: int | None = None,
               election_quorum: int | None = None,
               witnesses: tuple = ()) -> RaftDB:
    peers = cluster.split(",")
    # Default election/heartbeat timing is REAL-TIME parity with the
    # reference (~1 s election timeout, ~100 ms heartbeat at its 100 ms
    # tick — raft.go:154-155, 207), whatever the tick interval: timers
    # advance only on interval-paced steps (core/step.py timer_inc), so
    # a fast tick must mean "fine timer resolution", not "20x shorter
    # election timeout".  A 5 ms tick with the raw 10-tick default gave
    # a 50-100 ms election window — OS scheduling jitter alone fired
    # constant spurious elections under load.
    if tick > 0:
        if election_ticks is None:
            election_ticks = max(10, round(1.0 / tick))
        heartbeat_ticks = max(1, round(0.1 / tick))
        if election_ticks <= 2 * heartbeat_ticks:
            heartbeat_ticks = max(1, election_ticks // 3)
    else:
        # Untimed (tick <= 0: step-per-loop): real-time scaling is
        # meaningless — keep the reference's tick counts (raft.go:154-155).
        election_ticks = election_ticks or 10
        heartbeat_ticks = 1
    # Leader leases (config.py lease_ticks): clamp to the safe bound
    # for a rate-bounded deployment — an operator-supplied lease can
    # never exceed what the election timeout can protect.
    if lease_ticks:
        lease_ticks = min(lease_ticks,
                          max(1, election_ticks - max_clock_skew - 1))
    cfg = RaftConfig(num_groups=groups, num_peers=len(peers),
                     tick_interval_s=tick, election_ticks=election_ticks,
                     heartbeat_ticks=heartbeat_ticks,
                     wal_segment_bytes=wal_segment_bytes,
                     lease_ticks=lease_ticks,
                     max_clock_skew=max_clock_skew,
                     write_quorum=write_quorum,
                     election_quorum=election_quorum,
                     witnesses=tuple(witnesses) or None)
    transport = TcpTransport(peers, node_id - 1)
    pipe = RaftPipe.create(node_id, len(peers), cfg, transport,
                           data_dir=f"{data_prefix}-{node_id}")
    if trace:
        pipe.node.enable_tracing()

    def path_of(g: int) -> str:
        return (f"{data_prefix}-{node_id}.db" if g == 0
                else f"{data_prefix}-{node_id}-g{g}.db")

    def sm_factory(g: int) -> SQLiteStateMachine:
        return SQLiteStateMachine(path_of(g), resume=resume)

    return RaftDB(sm_factory, pipe, num_groups=groups, resume=resume,
                  compact_every=compact_every, compact_keep=compact_keep,
                  existing=_groups_on_file(path_of, groups, resume))


def _start_ticking(node, tick: float) -> None:
    """Start a co-located engine's tick thread — AFTER the RaftDB over
    it is built.  The constructor opens every group's SQLite database
    and applies the WAL replay, which needs nothing from the tick loop
    (the replay was queued when the node was constructed); with the
    loop already running, each of those tens of thousands of short
    SQLite calls has to win the interpreter back from a tick that, at
    --groups 10000, holds it for most of its ~0.1 s (boot took 80 s on
    an 8-core CPU and 305 s on the chip host that way, ~2 s of it
    SQLite's own)."""
    node.start(interval_s=max(tick, 0.0005))


def build_fused_node(groups: int = 1, peers: int = 3,
                     tick: float = 0.002,
                     data_prefix: str = "raftsql",
                     resume: bool = False,
                     compact_every: int = 0, compact_keep: int = 1024,
                     wal_segment_bytes: int = 4 << 20,
                     trace: bool = False,
                     wal_group_commit: bool = True,
                     lease_ticks: int = 0,
                     max_clock_skew: int = 1,
                     write_quorum: int | None = None,
                     election_quorum: int | None = None,
                     witnesses: tuple = ()) -> RaftDB:
    """The --fused single-process deployment: all P peers of every
    group co-located in THIS process, consensus advanced by ONE fused
    device program per tick (runtime/fused.py), per-peer WALs on disk,
    SQLite applied from peer 0's commit stream.  The TPU-native answer
    to the reference's 3-process Procfile cluster: same durability
    (fsync-per-peer between dispatches = save-before-send), no
    cross-process hops on the propose→commit path."""
    from raftsql_tpu.runtime.fused import (PIPELINE_STEPS,
                                           FusedClusterNode, FusedPipe)

    # Leader leases on the fused plane: same safety clamp as
    # build_node — an operator-supplied lease can never exceed what
    # the (default) election timeout protects.
    if lease_ticks:
        election_default = RaftConfig.__dataclass_fields__[
            "election_ticks"].default
        lease_ticks = min(lease_ticks,
                          max(1, election_default - max_clock_skew - 1))
    if 0 in tuple(witnesses):
        # The fused deployment applies SQLite from peer 0's publish
        # stream (FusedPipe publish_peers={0}); a witness publishes
        # nothing, so slot 0 as witness would serve an empty database.
        raise ValueError("--fused applies from peer slot 0; pick a "
                         "different --witness slot")
    cfg = RaftConfig(num_groups=groups, num_peers=peers,
                     tick_interval_s=tick,
                     wal_segment_bytes=wal_segment_bytes,
                     lease_ticks=lease_ticks,
                     max_clock_skew=max_clock_skew,
                     write_quorum=write_quorum,
                     election_quorum=election_quorum,
                     witnesses=tuple(witnesses) or None)
    # WAL group commit is the serving default: one write+fsync per tick
    # for all P peers (storage/wal.py GroupCommitWAL).  An existing
    # per-peer data dir keeps its layout (the host plane refuses to
    # mix them); --wal-group-commit=off restores per-peer files.
    # A dispatch carries the whole propose -> replicate -> commit ->
    # learn pipeline: a write commits inside the launch that accepted
    # it instead of waiting four of them.
    node = FusedClusterNode(cfg, f"{data_prefix}-fused",
                            group_commit=wal_group_commit,
                            steps=PIPELINE_STEPS)
    if trace:
        node.enable_tracing()
    pipe = FusedPipe(node)

    def path_of(g: int) -> str:
        return (f"{data_prefix}-fused.db" if g == 0
                else f"{data_prefix}-fused-g{g}.db")

    def sm_factory(g: int) -> SQLiteStateMachine:
        return SQLiteStateMachine(path_of(g), resume=resume)

    rdb = RaftDB(sm_factory, pipe, num_groups=groups, resume=resume,
                 compact_every=compact_every, compact_keep=compact_keep,
                 existing=_groups_on_file(path_of, groups, resume))
    _start_ticking(node, tick)
    return rdb


def build_mesh_node(groups: int = 8, peers: int = 3,
                    tick: float = 0.002,
                    data_prefix: str = "raftsql",
                    group_shards: int = 0, peer_shards: int = 1,
                    resume: bool = False,
                    compact_every: int = 0, compact_keep: int = 1024,
                    wal_segment_bytes: int = 4 << 20,
                    trace: bool = False) -> RaftDB:
    """The --mesh deployment (runtime/mesh.py): the fused cluster with
    its consensus step SPMD over a real device mesh — G sharded over
    the `groups` axis — and the durable host plane sharded to match:
    per-shard WAL dirs under <prefix>-mesh/p<i>/s<j>, per-shard publish
    workers, and the SQLite state machines laid out per group shard
    under <prefix>-mesh-db/s<j>/.  `group_shards=0` auto-picks the
    widest mesh the visible devices allow (on a dev box: force
    devices with XLA_FLAGS=--xla_force_host_platform_device_count=8
    JAX_PLATFORMS=cpu)."""
    import os as _os

    from raftsql_tpu.runtime.fused import FusedPipe
    from raftsql_tpu.runtime.mesh import MeshClusterNode, MeshConfig

    cfg = RaftConfig(num_groups=groups, num_peers=peers,
                     tick_interval_s=tick,
                     wal_segment_bytes=wal_segment_bytes)
    mc = (MeshConfig.for_groups(cfg, peer_shards=peer_shards)
          if group_shards <= 0
          else MeshConfig(peer_shards=peer_shards,
                          group_shards=group_shards))
    mc.validate(cfg)
    logging.getLogger("raftsql.server").info(
        "mesh deployment: %dx%d devices, %d groups (%d per shard)",
        mc.peer_shards, mc.group_shards, groups,
        groups // mc.group_shards)
    node = MeshClusterNode(cfg, f"{data_prefix}-mesh", mc.build())
    if trace:
        node.enable_tracing()
    pipe = FusedPipe(node)
    g_loc = groups // mc.group_shards

    def path_of(g: int) -> str:
        return f"{data_prefix}-mesh-db/s{g // g_loc}/g{g}.db"

    def sm_factory(g: int) -> SQLiteStateMachine:
        _os.makedirs(_os.path.dirname(path_of(g)), exist_ok=True)
        return SQLiteStateMachine(path_of(g), resume=resume)

    rdb = RaftDB(sm_factory, pipe, num_groups=groups, resume=resume,
                 compact_every=compact_every, compact_keep=compact_keep,
                 existing=_groups_on_file(path_of, groups, resume))
    _start_ticking(node, tick)
    return rdb


class PodRaftDB(RaftDB):
    """RaftDB over a PodClusterNode: every group-scoped verb is served
    ONLY by the group's owner host.

    Ownership is the ack-soundness boundary, not a routing nicety:
    (a) an HTTP write ack fires when the commit reaches THIS host's
    publish stream, which follows THIS host's WAL fsync — on the owner
    that is exactly "durable where the group's whole P-peer history
    lives"; on any other host it would ack a write whose only durable
    copy is still crossing the pod (the premature-ack hazard
    chaos/pod.py falsifies); (b) pending-ack matching is
    (group, query)-keyed (RaftDB._q2cb), so two hosts holding futures
    for the same group could cross-resolve each other's writes off the
    replicated publish stream.  Non-owners answer 421 + X-Raft-Leader
    naming the owner host (1-based slot in the pod hosts table) and
    the client chases, exactly like a non-leader peer in the
    multi-process deployment (api/client.py merges ownership from the
    /healthz sweep so steady state has no 421s at all)."""

    def _pod_check(self, group: int) -> None:
        node = self.pipe.node
        if not node.owns_group(int(group)):
            from raftsql_tpu.runtime.db import NotLeaderError
            raise NotLeaderError(int(group),
                                 node.group_owner(int(group)) + 1)

    def propose(self, query, group: int = 0, *a, **kw):
        self._pod_check(group)
        return super().propose(query, group, *a, **kw)

    def query(self, query, group: int = 0, *a, **kw):
        self._pod_check(group)
        return super().query(query, group, *a, **kw)

    def member_change(self, group: int, op: str, peer: int) -> dict:
        self._pod_check(group)
        return super().member_change(group, op, peer)

    def transfer(self, group: int, target: int) -> dict:
        self._pod_check(group)
        return super().transfer(group, target)


def build_pod_node(groups: int = 8, peers: int = 3, tick: float = 0.01,
                   data_prefix: str = "raftsql",
                   group_shards: int = 0,
                   pod_procs: int = 1, pod_id: int = 0,
                   pod_coord: str = "", pod_hosts: tuple = (),
                   resume: bool = False,
                   compact_every: int = 0, compact_keep: int = 1024,
                   wal_segment_bytes: int = 4 << 20,
                   trace: bool = False) -> RaftDB:
    """The --pod deployment (raftsql_tpu/pod/): N host PROCESSES
    jointly own one cluster.  Every host runs the identical replicated
    device step; the durable plane is sharded — this host materializes
    WAL dirs and SQLite files only for the group shards it OWNS
    (round-robin, pod/config.py), and the per-tick collective carries
    cross-host proposals and is the tick+fsync barrier.  Construction
    BLOCKS until all pod_procs processes join (the pod is one
    program); a lost peer is pod-wide fail-stop — the engine error
    surfaces through _watch_fatal as EXIT_CODE_FATAL, and a supervisor
    restarts the whole pod, which rebuilds from the merged cross-host
    replay.  Set RAFTSQL_POD_JAX_DISTRIBUTED=1 on real multi-host
    fleets to run the device step as one jax.distributed SPMD program
    (the dry-run default replicates it per host instead)."""
    import os as _os

    from raftsql_tpu.pod.config import PodConfig
    from raftsql_tpu.pod.node import PodClusterNode
    from raftsql_tpu.runtime.fused import FusedPipe
    from raftsql_tpu.runtime.mesh import MeshConfig

    pod = PodConfig(procs=pod_procs, proc_id=pod_id,
                    coordinator=pod_coord, hosts=tuple(pod_hosts))
    if _os.environ.get("RAFTSQL_POD_JAX_DISTRIBUTED") == "1":
        pod.init_distributed()
    cfg = RaftConfig(num_groups=groups, num_peers=peers,
                     tick_interval_s=tick,
                     wal_segment_bytes=wal_segment_bytes)
    mc = (MeshConfig.for_groups(cfg, peer_shards=1)
          if group_shards <= 0
          else MeshConfig(peer_shards=1, group_shards=group_shards))
    mc.validate(cfg)
    logging.getLogger("raftsql.server").info(
        "pod deployment: host %d/%d, %d groups over %d shards, "
        "coordinator %s", pod_id, pod.procs, groups, mc.group_shards,
        pod_coord or "(local)")
    node = PodClusterNode(pod, cfg, f"{data_prefix}-pod{pod_id}",
                          mc.build())
    if trace:
        node.enable_tracing()
    node.start(interval_s=max(tick, 0.0005))
    pipe = FusedPipe(node)
    owned = {int(g) for g in node.owned_groups()}
    db_dir = f"{data_prefix}-pod{pod_id}-db"

    def sm_factory(g: int) -> SQLiteStateMachine:
        if g not in owned:
            # Replicated compute applies every group on every host, but
            # this host is not the durable authority for g: fold into a
            # throwaway in-memory replica (keeps watermarks and status
            # truthful for /healthz) — reads and writes for g are
            # owner-served (PodRaftDB), so no file may exist here.
            return SQLiteStateMachine(":memory:", resume=False)
        _os.makedirs(db_dir, exist_ok=True)
        return SQLiteStateMachine(path_of(g), resume=resume)

    def path_of(g: int) -> str:
        return _os.path.join(db_dir, f"g{g}.db")

    return PodRaftDB(sm_factory, pipe, num_groups=groups, resume=resume,
                     compact_every=compact_every,
                     compact_keep=compact_keep,
                     existing=_groups_on_file(path_of, groups, resume))


# Exit code when the consensus engine dies of a fatal error (failed
# fsync, injected ENOSPC, transport teardown): the etcd posture — a
# server that can no longer participate must CRASH, visibly, rather
# than keep answering HTTP with a dead engine behind it.  The chaos
# nemesis (chaos/proc.py) keys on this code.
EXIT_CODE_FATAL = 70


def _install_graceful_shutdown(rdb, srv_stop,
                               stopping: threading.Event) -> None:
    """SIGTERM/SIGINT → clean stop: stop the HTTP plane (threaded or
    aio — whichever `srv_stop` closes), then close the pipe, which
    flushes and fsyncs the WAL and closes both the consensus transport
    and the SQLite state machines (RaftDB.close → RaftPipe.close →
    RaftNode.stop → WAL.close).  Exit code 0 distinguishes a clean stop
    from a crash — `kill -TERM` is "stop", SIGKILL is "crash" — and is
    given only when the engine closed without an error: a final flush
    that failed exits EXIT_CODE_FATAL like any other engine failure.

    `stopping` is set first, so _watch_fatal leaves the verdict on the
    engine to this path while a stop is in progress.

    The handler only spawns a worker thread: the main thread is inside
    serve_forever(), and running a blocking shutdown inside the signal
    frame would deadlock against it.  A second signal while the first
    shutdown runs hard-exits (an operator's double Ctrl-C must win)."""

    def _graceful(signum, frame):
        if stopping.is_set():
            os._exit(0)
        stopping.set()

        def _work():
            try:
                srv_stop()
            except Exception:                       # noqa: BLE001
                logging.getLogger("raftsql.server").exception(
                    "HTTP plane did not stop cleanly")
            try:
                err = rdb.close()
            except Exception as e:                  # noqa: BLE001
                err = e
            if err is not None:
                logging.getLogger("raftsql.server").error(
                    "engine did not close cleanly, exiting: %s", err)
                os._exit(EXIT_CODE_FATAL)
            os._exit(0)

        # Non-daemon: when srv_stop() unblocks serve_forever and main()
        # returns, interpreter shutdown must WAIT for the WAL flush in
        # rdb.close() instead of killing it mid-write (the worker ends
        # the process itself via os._exit).
        threading.Thread(target=_work, daemon=False,
                         name="graceful-shutdown").start()

    signal.signal(signal.SIGTERM, _graceful)
    signal.signal(signal.SIGINT, _graceful)


def _watch_fatal(rdb, stopping: threading.Event) -> None:
    """Exit the process (EXIT_CODE_FATAL) when the consensus engine
    records a terminal error — see EXIT_CODE_FATAL above.  Once a
    graceful stop is in progress the shutdown path owns the exit code
    (it reports what RaftDB.close returned)."""
    def _work():
        while not stopping.is_set():
            if rdb.pipe.error is not None:
                logging.getLogger("raftsql.server").error(
                    "consensus engine failed, exiting: %s",
                    rdb.pipe.error)
                os._exit(EXIT_CODE_FATAL)
            time.sleep(0.2)

    threading.Thread(target=_work, daemon=True,
                     name="fatal-watch").start()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description="TPU-native replicated SQL")
    ap.add_argument("--cluster", default="http://127.0.0.1:9021",
                    help="comma separated cluster peers")
    ap.add_argument("--id", type=int, default=1, help="node ID (1-based)")
    ap.add_argument("--port", type=int, default=9121,
                    help="sql server port")
    ap.add_argument("--groups", type=int, default=1,
                    help="number of raft groups")
    ap.add_argument("--tick", type=float, default=0.01,
                    help="seconds per consensus tick")
    ap.add_argument("--resume", action="store_true",
                    help="snapshot-resume: keep the SQLite files across "
                         "restarts and skip re-applying the replayed "
                         "prefix (default: reference delete-and-replay)."
                         "  A group's file is opened on first use; a "
                         "handle holds three descriptors (one without "
                         "--resume) and the open ones are kept within "
                         "RLIMIT_NOFILE, least recently used closed first")
    ap.add_argument("--compact-every", type=int, default=0,
                    help="with --resume: one compaction sweep every N "
                         "applied entries, node-wide: the SQLite files "
                         "written since the last one are checkpointed "
                         "and fsynced, floors move to what is on disk "
                         "- --compact-keep (to its last index "
                         "for a group every peer has caught up on), "
                         "floor markers are appended, and closed WAL "
                         "segments under every floor are unlinked, "
                         "oldest first; without --resume it never acts "
                         "and the log says so")
    ap.add_argument("--compact-keep", type=int, default=1024,
                    help="entries retained above the compaction floor "
                         "of a group with traffic (never less than the "
                         "device ring, 256)")
    ap.add_argument("--wal-segment-bytes", type=int, default=4 << 20,
                    help="rotate WAL segments at this size; compaction "
                         "unlinks whole covered segments")
    ap.add_argument("--fused", action="store_true",
                    help="single-process cluster: all --peers raft "
                         "peers co-located on one device, one fused "
                         "step per tick (no --cluster/--id needed)")
    ap.add_argument("--peers", type=int, default=3,
                    help="with --fused/--mesh: peers per group")
    ap.add_argument("--mesh", action="store_true",
                    help="single-process cluster SPMD over a device "
                         "MESH (runtime/mesh.py): G sharded over the "
                         "'groups' axis, per-shard WAL dirs + publish "
                         "workers + SQLite shards (no --cluster/--id)")
    ap.add_argument("--group-shards", type=int, default=0,
                    help="with --mesh: devices on the groups axis "
                         "(0 = widest fit for the visible devices)")
    ap.add_argument("--peer-shards", type=int, default=1,
                    help="with --mesh: devices on the peers axis (the "
                         "message exchange then rides all_to_all)")
    ap.add_argument("--pod", action="store_true",
                    help="multi-host pod (raftsql_tpu/pod/): this "
                         "process is ONE of --pod-procs hosts jointly "
                         "owning the cluster — replicated device step, "
                         "durability sharded by group shard, one "
                         "cross-host collective per tick.  Boot blocks "
                         "until every host joins; a lost host is "
                         "pod-wide fail-stop (restart the whole pod)")
    ap.add_argument("--pod-procs", type=int, default=1,
                    help="with --pod: total host processes in the pod "
                         "(overridden by the length of --pod-hosts)")
    ap.add_argument("--pod-id", type=int, default=0,
                    help="with --pod: this host (0-based; 0 runs the "
                         "collective coordinator)")
    ap.add_argument("--pod-coord", default="",
                    help="with --pod: host:port the pod collective "
                         "coordinator (host 0) listens on")
    ap.add_argument("--pod-hosts", default="",
                    help="with --pod: comma separated host:port HTTP "
                         "addresses of EVERY pod host in --pod-id "
                         "order — published at /healthz so a client "
                         "pointed at one host sweeps the whole pod")
    ap.add_argument("--wal-group-commit", choices=("on", "off"),
                    default="on",
                    help="with --fused: coalesce every peer's per-tick "
                         "WAL records into ONE shared log + ONE fsync "
                         "(storage/wal.py GroupCommitWAL)")
    ap.add_argument("--workers", type=int, default=0,
                    help="N HTTP worker PROCESSES sharing this engine "
                         "through mmap propose/completion rings "
                         "(runtime/ring.py), all binding --port via "
                         "SO_REUSEPORT.  0 = serve HTTP in-process "
                         "(the classic single-process deployment)")
    ap.add_argument("--lease-ticks", type=int, default=0,
                    help="leader-lease duration in ticks (0 = off): "
                         "linearizable reads at a leader whose lease "
                         "covers now + --max-clock-skew skip the "
                         "ReadIndex quorum round.  Clamped below the "
                         "election timeout; requires bounded relative "
                         "clock rates (config.py lease_ticks)")
    ap.add_argument("--write-quorum", type=int, default=None,
                    help="flexible quorum geometry (config.py): size "
                         "of the append/commit/lease quorum; default "
                         "majority.  W + E must exceed the peer count")
    ap.add_argument("--election-quorum", type=int, default=None,
                    help="size of the vote/prevote quorum; default "
                         "majority (2E must also exceed the peer "
                         "count)")
    ap.add_argument("--witness", type=int, action="append", default=[],
                    metavar="SLOT",
                    help="0-based peer slot to run as a WITNESS: "
                         "votes, appends and fsyncs its WAL but owns "
                         "no SQLite shard and serves no reads "
                         "(repeatable)")
    ap.add_argument("--max-clock-skew", type=int, default=1,
                    help="clock-skew slack (ticks) subtracted from "
                         "every lease validity check")
    ap.add_argument("--http-engine", choices=("aio", "threaded"),
                    default="aio",
                    help="HTTP plane: single-thread event loop with "
                         "batched commit acks (aio, default) or the "
                         "thread-per-connection stdlib port (threaded)")
    ap.add_argument("--trace", action="store_true",
                    help="enable the observability planes "
                         "(raftsql_tpu/obs/): per-proposal lifecycle "
                         "spans + the on-device event ring, exported "
                         "at GET /trace (Perfetto) and GET /events")
    ap.add_argument("--placement", action="store_true",
                    help="traffic-aware leadership placement "
                         "(raftsql_tpu/placement/): a controller "
                         "thread watches the per-group traffic feed "
                         "and issues graceful leadership transfers "
                         "(POST /transfer machinery, thesis §3.10) to "
                         "balance hot groups across peers; fused/mesh "
                         "runtimes only")
    ap.add_argument("--placement-interval", type=float, default=0.5,
                    help="seconds between placement passes")
    ap.add_argument("--placement-imbalance", type=float, default=2.0,
                    help="hottest/coldest per-peer load ratio that "
                         "triggers a transfer")
    ap.add_argument("--reshard", action="store_true",
                    help="elastic keyspace (raftsql_tpu/reshard/): a "
                         "coordinator thread executes SPLIT / MERGE / "
                         "MIGRATE verbs (POST /reshard) journaled "
                         "through the raft logs, and the keyed "
                         "PUT/GET /kv/<key> surface routes by the "
                         "versioned hash-slot keymap (clients fail "
                         "closed on X-Raft-Keymap-Epoch mismatch)")
    ap.add_argument("--reshard-nslots", type=int, default=64,
                    help="hash slots in the key->group map (crc32 "
                         "%% nslots; fixed for the cluster's life)")
    ap.add_argument("--replica-listen", type=int, default=0,
                    help="publish the read-replica delta stream on "
                         "this TCP port (raftsql_tpu/replica/): "
                         "replicas subscribe with `python -m "
                         "raftsql_tpu.replica --upstream host:PORT` "
                         "and serve the read ladder remotely; 0 = off")
    ap.add_argument("--overload-cap", type=int, default=0,
                    help="bounded admission: max queued-but-unstaged "
                         "proposals per ENGINE (raftsql_tpu/overload/;"
                         " excess answers 429 + Retry-After on every "
                         "serving surface); 0 = no engine budget")
    ap.add_argument("--overload-group-cap", type=int, default=0,
                    help="bounded admission: max queued-but-unstaged "
                         "proposals per GROUP; 0 = no group budget")
    ap.add_argument("--brownout-hi", type=float, default=None,
                    help="queue-depth EWMA above which linear reads "
                         "degrade to lease-only (the brownout ladder; "
                         "default 0.75 x --overload-cap)")
    ap.add_argument("--brownout-lo", type=float, default=None,
                    help="queue-depth EWMA below which the brownout "
                         "ladder disengages (default brownout-hi / 3)")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)
    # Env-injected storage faults (RAFTSQL_FSIO_FAULTS): the chaos
    # nemesis's seam across the process boundary.  Installed before the
    # node boots so the very first WAL byte flows through the rules;
    # a malformed spec must kill the boot, not silently drop faults.
    from raftsql_tpu.storage import fsio
    fsio.install_from_env()
    # The serving process is ~30 cooperating threads (tick loop, HTTP
    # handlers, commit consumer, transport); CPython's default 5 ms GIL
    # switch interval makes every cross-thread handoff on the
    # propose→commit→ack path cost up to 5 ms × runnable threads.  1 ms
    # trades a little throughput for a large latency cut on small hosts.
    sys.setswitchinterval(
        float(os.environ.get("RAFTSQL_GIL_SWITCH_S", "0.001")))
    logging.basicConfig(
        level=logging.DEBUG if args.verbose else logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s")
    # The one device rule (utils/device.py): the platform JAX_PLATFORMS
    # names, or an accelerator — never a silent CPU.  Before anything
    # below can compute.
    from raftsql_tpu.utils.device import select_device
    select_device()
    # SQLite keeps -wal/-shm sidecars open per database in resume mode.
    _raise_nofile_limit(args.resume)
    t_boot = time.monotonic()

    # RAFTSQL_PROFILE=<dir>: cProfile of the consensus tick thread,
    # dumped periodically to <dir>/raftsql-node<id>-tick.prof
    # (runtime/node.py _run; SURVEY.md §5.1 — host-side profiling of
    # the serving process, the complement of the JAX profiler's device
    # traces in bench.py).
    if args.pod:
        pod_hosts = tuple(h for h in args.pod_hosts.split(",") if h)
        if (args.write_quorum is not None
                or args.election_quorum is not None or args.witness):
            ap.error("--write-quorum/--election-quorum/--witness are "
                     "not supported with --pod (the pod extends the "
                     "mesh runtime, which refuses them too)")
        if args.mesh or args.fused:
            ap.error("--pod is its own deployment; drop --mesh/--fused")
        if args.placement or args.reshard or args.workers:
            # Replicated controllers: N hosts each running a placement/
            # reshard controller would issue the same verbs N times;
            # the ring worker plane has no pod story yet.  Refuse
            # loudly rather than boot something subtly double-driven.
            ap.error("--placement/--reshard/--workers are not "
                     "supported with --pod yet")
        rdb = build_pod_node(groups=args.groups, peers=args.peers,
                             tick=args.tick,
                             group_shards=args.group_shards,
                             pod_procs=(len(pod_hosts) or args.pod_procs),
                             pod_id=args.pod_id,
                             pod_coord=args.pod_coord,
                             pod_hosts=pod_hosts,
                             resume=args.resume,
                             compact_every=args.compact_every,
                             compact_keep=args.compact_keep,
                             wal_segment_bytes=args.wal_segment_bytes,
                             trace=args.trace)
    elif args.mesh:
        if (args.write_quorum is not None
                or args.election_quorum is not None or args.witness):
            # The mesh runtime shards the GROUP axis; its geometry
            # plumbing is untested — refuse loudly rather than boot a
            # cluster whose quorums silently differ from the flags.
            ap.error("--write-quorum/--election-quorum/--witness are "
                     "not supported with --mesh (use --fused or the "
                     "multi-process deployment)")
        rdb = build_mesh_node(groups=args.groups, peers=args.peers,
                              tick=args.tick,
                              group_shards=args.group_shards,
                              peer_shards=args.peer_shards,
                              resume=args.resume,
                              compact_every=args.compact_every,
                              compact_keep=args.compact_keep,
                              wal_segment_bytes=args.wal_segment_bytes,
                              trace=args.trace)
    elif args.fused:
        rdb = build_fused_node(groups=args.groups, peers=args.peers,
                               tick=args.tick, resume=args.resume,
                               compact_every=args.compact_every,
                               compact_keep=args.compact_keep,
                               wal_segment_bytes=args.wal_segment_bytes,
                               trace=args.trace,
                               wal_group_commit=args.wal_group_commit
                               == "on",
                               lease_ticks=args.lease_ticks,
                               max_clock_skew=args.max_clock_skew,
                               write_quorum=args.write_quorum,
                               election_quorum=args.election_quorum,
                               witnesses=tuple(args.witness))
    else:
        rdb = build_node(args.cluster, args.id, groups=args.groups,
                         tick=args.tick, resume=args.resume,
                         compact_every=args.compact_every,
                         compact_keep=args.compact_keep,
                         wal_segment_bytes=args.wal_segment_bytes,
                         trace=args.trace,
                         lease_ticks=args.lease_ticks,
                         max_clock_skew=args.max_clock_skew,
                         write_quorum=args.write_quorum,
                         election_quorum=args.election_quorum,
                         witnesses=tuple(args.witness))
    logging.getLogger("raftsql.server").info(
        "engine built in %.1fs (%d groups, replay applied, tick loop "
        "running)", time.monotonic() - t_boot, args.groups)
    stopping = threading.Event()
    _watch_fatal(rdb, stopping)
    if args.overload_cap or args.overload_group_cap \
            or args.brownout_hi is not None:
        if not (args.fused or args.mesh):
            # The admission plane guards the co-located engine's
            # propose queues; the pod and distributed deployments have
            # no overload story yet — refuse loudly rather than boot a
            # server whose knobs silently do nothing.
            ap.error("--overload-cap/--overload-group-cap/--brownout-* "
                     "require --fused or --mesh")
        from raftsql_tpu.overload import OverloadController
        rdb.pipe.node.overload = OverloadController(
            args.groups, group_cap=args.overload_group_cap,
            total_cap=args.overload_cap, seed=0,
            tick_interval_s=args.tick,
            brownout_hi=args.brownout_hi,
            brownout_lo=args.brownout_lo)
    if args.placement:
        if not (args.fused or args.mesh):
            ap.error("--placement requires --fused or --mesh (the "
                     "co-located runtimes own the traffic feed)")
        from raftsql_tpu.placement import PlacementController
        pc = PlacementController(
            rdb.pipe.node, interval_s=args.placement_interval,
            imbalance=args.placement_imbalance)
        rdb.placement = pc
        pc.start()
    if args.reshard:
        from raftsql_tpu.reshard.plane import ReshardPlane
        plane = ReshardPlane(rdb, nslots=args.reshard_nslots)
        plane.start()        # recovers the journal fold, then drives
        if rdb.placement is not None:
            # split-hottest / merge-coldest verbs ride the controller.
            rdb.placement.reshard = plane
    if args.replica_listen and args.pod:
        ap.error("--replica-listen is not supported with --pod yet "
                 "(the stream tee rides the single-engine shm "
                 "publisher)")
    if args.workers > 0:
        _serve_workers(rdb, args, stopping)   # replica plane attaches,
        return                                # reusing the ring's shm
    if args.replica_listen:
        from raftsql_tpu.replica.publisher import attach_replica_plane
        attach_replica_plane(rdb, args.replica_listen)
    if args.http_engine == "aio":
        from raftsql_tpu.api.aio import AioSQLServer
        srv = AioSQLServer(args.port, rdb)
    else:
        srv = SQLServer(args.port, rdb)
    _install_graceful_shutdown(rdb, srv.stop, stopping)
    srv.serve_forever()
    # serve_forever returns once a graceful stop closed the HTTP plane;
    # the shutdown thread is still flushing the WAL and ends the process
    # itself.  Falling off main() here would start interpreter
    # finalization under it, and finalization tears down every
    # ThreadPoolExecutor first — the WAL sync pool among them — while the
    # engine's last tick may still be in flight (at --groups 10000 it
    # always is): that turned SIGTERM into EXIT_CODE_FATAL.
    while stopping.is_set():
        time.sleep(1.0)


def _serve_workers(rdb, args, stopping: threading.Event) -> None:
    """The --workers N deployment: this process runs ONLY the engine
    (consensus tick + WAL + SQLite apply) and the ring drain
    (runtime/ring.py RingServer); N child processes each run the
    asyncio HTTP plane over a RingClient, all bound to --port via
    SO_REUSEPORT.  HTTP parsing/ack serialization then spends other
    GILs, not the engine's.

    A worker that dies is respawned (it holds no state); the engine
    dying is fatal for everyone (EXIT_CODE_FATAL via _watch_fatal)."""
    import subprocess

    from raftsql_tpu.runtime.ring import RingServer

    log = logging.getLogger("raftsql.server")
    ring_dir = f"raftsql-rings-{os.getpid()}"
    t_ring = time.monotonic()
    ring = RingServer(rdb, ring_dir, args.workers)
    ring.start()
    log.info("rings and shm snapshot plane up in %.1fs",
             time.monotonic() - t_ring)
    if getattr(args, "replica_listen", 0):
        # The ring attached the shm publisher already; the stream tee
        # rides the same one (replica/publisher.py reuses rdb.shm).
        from raftsql_tpu.replica.publisher import attach_replica_plane
        attach_replica_plane(rdb, args.replica_listen)

    def spawn(i: int) -> "subprocess.Popen":
        # The chip belongs to the engine: a worker must never initialise
        # an accelerator backend, whatever the operator exported for the
        # engine (JAX_PLATFORMS=tpu would otherwise be inherited).  No
        # preexec_fn — that forks a process whose JAX threads are
        # already running; the worker arms PR_SET_PDEATHSIG on itself
        # (server/worker.py) against --engine-pid.
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        return subprocess.Popen(
            [sys.executable, "-m", "raftsql_tpu.server.worker",
             "--rings", ring_dir, "--index", str(i),
             "--port", str(args.port),
             "--engine-pid", str(os.getpid())]
            + (["--trace"] if args.trace else [])
            + (["--verbose"] if args.verbose else []),
            env=env)

    procs = [spawn(i) for i in range(args.workers)]

    def _stop_all():
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(timeout=5)
            except Exception:                           # noqa: BLE001
                p.kill()
        ring.stop()

    _install_graceful_shutdown(rdb, _stop_all, stopping)
    log.info("engine up; %d HTTP workers on port %d (rings in %s)",
             args.workers, args.port, ring_dir)
    while True:
        for i, p in enumerate(procs):
            rc = p.poll()
            if rc is not None and not stopping.is_set():
                log.warning("worker %d exited rc=%s; respawning", i, rc)
                procs[i] = spawn(i)
        time.sleep(0.5)


if __name__ == "__main__":
    main()
