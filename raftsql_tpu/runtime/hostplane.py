"""ClusterHostPlane — the durable host phase shared by every
single-controller runtime.

runtime/fused.py (one chip) and runtime/mesh.py (a device mesh) run the
same per-tick contract (reference raft.go:227-235: wal.Save →
transport.Send → publish, with the dispatch itself as the send barrier):

  messages composed at tick t are OBSERVED by their receivers only
  inside step t+1 — and the host does not dispatch step t+1 until every
  peer's tick-t appends and hard states are fsynced.  Within a tick a
  peer's entry records precede its hard states, which are found by one
  compare for all peers of the step's (term, vote, commit) columns
  against what the WALs hold, and written whatever the history.

This module is the host half of that contract, factored out of the
original ~1400-line runtime/fused.py so both runtimes share ONE codepath
for propose queues and leader routing, WAL + payload-log writes, the
per-peer fsync barrier, epoch-framed multi-step dispatch, commit
publish, and membership apply-at-commit.  The device half — how one
tick's consensus math is dispatched — is the single abstract method
`_device_step`, implemented by:

  * FusedClusterNode (runtime/fused.py): core/cluster.py
    cluster_step_host / cluster_multistep_host on one device;
  * MeshClusterNode (runtime/mesh.py): the shard_map'd SPMD step
    (parallel/sharded.py) over a `Mesh`, G sharded over a `groups`
    axis and the peer exchange riding all_to_all.

Subclass seams (all default to the single-device layout):

  _new_wal / _wal_exists / _wal_replay / _wal_repair_epochs — how a
    peer's durable log is laid out on disk.  The mesh runtime shards
    each peer's WAL per group shard (runtime/mesh.py ShardedWAL) so the
    durable plane gets one directory — and one fsync stream — per local
    device shard.
  _pub_shard_count / _pub_shard_groups — how many ordered publish
    workers drain commits to the apply plane and which group block each
    owns.  The fused runtime keeps the single FIFO worker; the mesh
    runtime runs one worker per group shard (disjoint groups, so
    per-group commit order is preserved without any cross-worker
    coordination).

Payload plane: entry BYTES never touch the device (the step moves
counts, terms and indexes).  Each peer owns a host PayloadLog + WAL;
a follower that accepts entries mirrors the bytes from the SOURCE
peer's payload log.  Within one host phase all mirror READS happen
before any payload-log WRITES: the reads then see exactly the
end-of-previous-tick state the device composed those appends from, so
a same-tick truncation on the source cannot tear a mirror.
"""
from __future__ import annotations

import os
import queue
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from raftsql_tpu.config import NO_XFER, RaftConfig
from raftsql_tpu.core.cluster import (empty_cluster_inbox,
                                      init_cluster_state)
from raftsql_tpu.core.state import (restored_leaves,
                                    set_group_config_stacked,
                                    set_transfer_target_stacked)
from raftsql_tpu.core.step import INFO_FIELDS
from raftsql_tpu.transport.codec import (CONF_PREFIX as _CONF_PREFIX,
                                         decode_conf_entry,
                                         is_conf_entry)
from raftsql_tpu.runtime.node import (CLOSED, RAW_MANY, RAW_PLAIN,
                                      TransferRefused)
from raftsql_tpu.storage import fsio
from raftsql_tpu.storage.log import PayloadLog
from raftsql_tpu.obs.prof import TickPhaseProfiler, span
from raftsql_tpu.storage.wal import WAL, split_uniform_runs, wal_exists
from raftsql_tpu.utils.metrics import GroupTraffic, NodeMetrics

_C = {n: i for i, n in enumerate(INFO_FIELDS)}
# What a hard state is: these columns of the packed info, in the column
# order of ClusterHostPlane._hard (and of a WAL hard-state record).
_HARD_COLS = [_C["term"], _C["voted_for"], _C["commit"]]


def _read_committed_epoch(path: str) -> int:
    """Last valid (u64 no, u32 crc) record of the epoch-commit file; 0
    when missing/empty.  A torn trailing record (crash mid-append)
    falls back to the previous one — the dispatch it would have
    committed is dropped by WAL.repair_epochs, which is exactly the
    uncommitted-dispatch semantics."""
    import struct
    import zlib
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError:
        return 0
    no = 0
    for off in range(0, len(blob) - 11, 12):
        n, crc = struct.unpack_from("<QI", blob, off)
        if zlib.crc32(blob[off:off + 8]) == crc:
            no = n
    return no


class ClusterHostPlane:
    """P peers × G groups, one device program per tick, durable WALs.

    Abstract over `_device_step` (see module docstring).  Public
    surface mirrors the distributed runtime where it overlaps:
    `propose_many(group, payloads)` routes to the current leader peer,
    `tick()` advances the whole cluster one step, `commit_q(peer)` is
    that peer's totally-ordered commit stream (same item protocol as
    RaftNode: any replayed (RAW_PLAIN, g, base, [bytes...]) batches
    first, then the None replay-complete sentinel, then live ticks as
    (RAW_MANY, [(g, base, [bytes...]), ...]) batch-of-batches items;
    CLOSED ends the stream), `leader_of(group)` reports the last hint.
    """

    # Epoch-commit file rotation threshold (12 bytes/dispatch; only the
    # last record matters — see _commit_epoch).
    _EPOCH_ROTATE_BYTES = 1 << 20

    # WAL group commit (storage/wal.py GroupCommitWAL) is a per-data-dir
    # layout choice; the mesh runtime's ShardedWAL seams supersede it.
    supports_group_commit = True

    # Which mesh shard owns a group (the hot-groups table's `shard`
    # column); None on unsharded runtimes, a method on MeshClusterNode.
    _group_shard_of = None

    # (prop_n, timer_inc) -> the same two as the step takes them, where
    # the host's inputs have to be laid over several devices before the
    # program can start: a method on MeshClusterNode, timed apart from
    # `launch` as the `mesh_put` phase.  None where the step takes host
    # arrays as they are (the fused runtime).
    _put_inputs = None

    def __init__(self, cfg: RaftConfig, data_dir: str,
                 seed: Optional[int] = None,
                 group_commit: Optional[bool] = None,
                 steps: int = 1):
        P, G = cfg.num_peers, cfg.num_groups
        # The engine's boot clock: boot.replay_s (runtime/db.py) and
        # boot.all_led_s (tick()) count from here.
        self.t_born = time.monotonic()
        self._all_led_s = 0.0
        self.cfg = cfg
        self.metrics = NodeMetrics()
        # Telemetry plane (raftsql_tpu/obs/prof.py), DEFAULT ON — both
        # are pure observers (pre-allocated buffers, no allocation on
        # the hot path, never any control-flow influence: chaos digests
        # are pinned identical with RAFTSQL_PROF on and off).
        #   prof: per-phase tick wall-time rings -> /metrics
        #     phase_profile + Perfetto phase tracks in /trace, each
        #     leaf phase also a `tick.<phase>` span on the JAX
        #     profiler's timeline while a profiler session runs; the
        #     request stages (stages.*) and
        #     the intake.* / wal.* counters ride the same object
        #     (RAFTSQL_PROF=0 turns all of it off);
        #   traffic: [G] propose/commit/ack counters + EWMA rates ->
        #     /metrics group_traffic top-K hot-groups table.
        self.prof = TickPhaseProfiler.from_env(G)
        if self.prof is not None:
            self.prof.gauge_fn("boot.all_led_s", lambda: self._all_led_s)
        self.traffic = GroupTraffic(G)
        # Phase attribution: the tick that OWNS the durable/publish
        # work currently running (a serial host's deferred publish,
        # delivered inside tick t+1's dispatch window, is tick t's).
        # _pending_tick tags the deferred-publish pinfo.
        self._prof_tick = 0
        self._pending_tick = 0
        self._fsync_span: Optional[tuple] = None    # (t0, dur) last tick
        # The durable phase's parts for the profiler: seconds of
        # [wal_plan, wal_append, wal_hardstate] summed over a
        # dispatch's steps, and what the wal.* counters count.
        self._wal_split = [0.0, 0.0, 0.0]
        self._wal_records = 0
        # Follower ranges listed for the mirror, those the mirror
        # wrote, and the accepted appends that never became a range
        # because they could change no log (wal.mirror_rows,
        # .mirror_fallback_rows, .mirror_skipped_rows).  The second is
        # the first by construction: benchmarks/layers/
        # wal_mirror_fallback_pct.py reads it, and it goes when a
        # `benchmark` PR retires that reader (ROADMAP.md).
        self._wal_mirror = [0, 0, 0]
        self._wal_shard_syncs = 0       # last seen (sharded WALs only)
        # A compaction sweep asked for by another thread (the apply
        # thread, runtime/db.py) and not yet run: the newest
        # (applied, covered, keep).  The TICK THREAD runs it, between
        # two durable phases (see compact()).
        self._compact_req: Optional[tuple] = None
        self._wal_hard: List[Optional[np.ndarray]] = [None] * P
        self._wal_groups: set = set()
        self._wal_wrote: Optional[Tuple[int, int]] = None   # last seen
        # This tick's [backlog, offered, groups, accepted, committed
        # in the dispatch] (intake.*), and whether a profiler session
        # ran at the tick's start.
        self._intake = [0, 0, 0, 0, 0]
        self._ann = None
        self.dirs = [os.path.join(data_dir, f"p{i + 1}") for i in range(P)]
        # WAL group commit: multiplex all P peers' records into ONE
        # physical log (flat group id peer*G+g) so the durable barrier
        # is one write+fsync per tick instead of P fsyncs in flight.
        # None = env RAFTSQL_WAL_GROUP_COMMIT (the serving deployment
        # and the durable bench turn it on); an existing per-peer
        # layout wins over the flag — never mix layouts in one dir.
        if group_commit is None:
            group_commit = os.environ.get(
                "RAFTSQL_WAL_GROUP_COMMIT") == "1"
        self._gc_dir = os.path.join(data_dir, "gc")
        self._gcwal = None
        self._gc_mode = False
        self._gc_replay: Optional[dict] = None
        self._gc_repaired = False
        if group_commit and self.supports_group_commit:
            from raftsql_tpu.storage.wal import GroupCommitWAL
            legacy = any(wal_exists(d) for d in self.dirs)
            if legacy and not GroupCommitWAL.exists(self._gc_dir):
                import logging
                logging.getLogger("raftsql.hostplane").warning(
                    "%s: per-peer WAL layout exists; group commit "
                    "disabled for this data dir", data_dir)
            else:
                self._gc_mode = True
        self.wals: List[WAL] = []
        self.plogs: List[PayloadLog] = []
        self._commit_qs: List["queue.Queue"] = [queue.Queue()
                                                for _ in range(P)]
        self._applied = np.zeros((P, G), np.int64)
        # What every peer's WAL holds, [P, G, (term, vote, commit)], in
        # the packed info's dtype (core/step.py pack_info: every column
        # is int32) and kept column by column, as the TPU hands the
        # packed info over (G is its minor axis there): the tick's
        # compare then runs over contiguous columns and casts nothing.
        self._hard_cols = np.zeros((P, 3, G), np.int32)
        self._hard = self._hard_cols.transpose(0, 2, 1)
        self._hard[:, :, 1] = -1
        self._hard_ne = np.zeros((P, 3, G), bool)   # the compare's flags
        # Per-(peer, group) proposal queues as plain lists: the tick
        # pops a whole batch with one C-level slice + del, vs a Python
        # popleft per entry on a deque.  _prop_lock covers _props and
        # _queued: under the threaded --fused deployment (start()),
        # HTTP client threads propose concurrently with the tick
        # thread's routing and batch pops.
        # raftlint: guarded-by=_prop_lock
        self._props: List[List[list]] = [
            [[] for _ in range(G)] for _ in range(P)]
        self._queued: set = set()  # raftlint: guarded-by=_prop_lock
        self._prop_lock = threading.Lock()
        self._hints = np.full(G, -1, np.int64)
        self._tick_no = 0
        # Leader-lease host cache (config.lease_ticks): the device
        # lease phase (core/step.py Phase 8b) returns each peer row's
        # [G] lease-expiry vector in device-STEP units; `_lease_col`
        # is the last dispatch's [P, G] slice and `_device_steps` the
        # host's running step count (ticks x steps-per-dispatch), the
        # "now" the validity check compares against.  Sound here
        # because the fused/mesh plane steps every peer once per host
        # step — per-peer skew only scales timer_inc, which is exactly
        # the rate bound cfg.max_clock_skew/lease_ticks must cover.
        self._lease_col: Optional[np.ndarray] = None
        self._device_steps = 0
        # Last tick's packed info, published at the START of the next
        # tick (overlapped with the device dispatch) — its entries are
        # already durable by then.
        self._pending_pinfo: Optional[np.ndarray] = None
        # Optional apply-plane work to run INSIDE the dispatch window,
        # right after the overlapped publish: dispatch is asynchronous,
        # so until the readback the device's compute time is idle host
        # time, and draining/applying the commit stream there is free.
        # The hook must only consume the commit queues (anything else
        # races the tick).
        self.overlap_hook = None
        # Which peers' commit queues receive live publishes (None =
        # all).  Deployments that consume a single peer's stream (the
        # --fused server and the durable bench drain peer 0) set {0}
        # and skip 2/3 of the publish slicing + queue traffic.
        self.publish_peers: Optional[set] = None
        # Witness peers (config.py quorum geometry): they vote, append
        # and fsync — full quorum citizens on the durability plane —
        # but own no state machine: their commit streams are never
        # materialized (cursor-advance only in _publish_shard) and
        # placement/transfer refuse them as leadership targets.
        self.witness_peers: frozenset = cfg.witness_set
        # Overload-control plane (raftsql_tpu/overload/), attachment-
        # gated like tracer/membership: None keeps propose_many and the
        # staging path byte-identical to the pre-overload code (the
        # chaos digest-neutrality pin).  When attached, propose_many
        # charges its budgets under _prop_lock and the staging path
        # sheds expired-deadline entries before any WAL cost.
        self.overload = None
        # True once any deadline-carrying proposal entered the queues:
        # only then does staging pay the per-entry deadline strip
        # (queue entries become (payload, deadline_step) pairs).
        self._deadlines_live = False  # raftlint: guarded-by=_prop_lock
        # Observability (raftsql_tpu/obs/, OFF by default): a host-plane
        # span tracer and the on-device event ring.  Every hook below is
        # gated on these being non-None, so the disabled tick pays one
        # attribute test and the step signatures are untouched.
        self.tracer = None
        self.ring = None
        # Dynamic membership (raftsql_tpu/membership/), opt-in via
        # enable_membership(): None keeps the static tick byte-identical
        # (every hook gates on one attribute test).
        self.membership = None
        # Leadership-transfer plane (thesis §3.10, PR 11): one latch
        # per group.  Client threads VALIDATE and enqueue into
        # _xfer_req; the tick thread arms the device latch (self.states
        # is donated every dispatch) and drives completion/abort in
        # _transfer_advance.  _xfer_events is the recent-outcome log
        # flight bundles attach for attribution.
        from collections import deque as _deque
        self._xfer_lock = threading.Lock()
        self._xfer_req: List[Tuple[int, int, int]] = []  # raftlint: guarded-by=_xfer_lock
        self._xfers: Dict[int, dict] = {}  # raftlint: guarded-by=_xfer_lock
        self._xfer_events = _deque(maxlen=256)
        self._conf_pending: List[list] = []      # per group [(idx, data)]
        self._conf_scrub: List[set] = []         # per group conf indexes
        self._conf_cursor: Optional[np.ndarray] = None   # [P, G]
        self._replayed_conf: List[Dict[int, tuple]] = [
            {} for _ in range(P)]
        self.error: Optional[Exception] = None
        self._work_evt = threading.Event()
        self._stop_evt = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._tick_active = True
        self._spin_hot = True
        # One worker per peer for the end-of-tick durable barrier: the
        # P per-peer fsyncs overlap (independent files; fsync releases
        # the GIL), so the barrier costs max not sum of the fsyncs.
        from concurrent.futures import ThreadPoolExecutor
        self._sync_pool = ThreadPoolExecutor(
            max_workers=P, thread_name_prefix="wal-sync")
        # Host-plane parallelism (the async publishers): only pays
        # when the host has cores to run them on — on a 1-core host the
        # same threads just time-slice the tick thread's core.
        # RAFTSQL_FUSED_PARALLEL=1/0 overrides the autodetect.
        par_env = os.environ.get("RAFTSQL_FUSED_PARALLEL", "")
        self._host_parallel = (par_env == "1"
                               or (par_env != "0"
                                   and (os.cpu_count() or 1) >= 4))
        # Serial hosts deliver a LIGHT tick's commits inline at tick end
        # (≤ this many entries) instead of deferring a whole tick for
        # dispatch overlap — ~0.4us/entry of publish against a full
        # tick of ack latency.  Saturated ticks keep the deferral.
        self._inline_publish_max = int(os.environ.get(
            "RAFTSQL_PUBLISH_INLINE_MAX", "4096"))
        # Steps per dispatch: run S consensus steps inside one device
        # program and replay the durable phases per step on return
        # (core/cluster.py cluster_multistep_host).  The fixed cost of
        # a dispatch (launch, readback, hard states, the barrier) is
        # paid once for S steps, and at S = PIPELINE_STEPS a proposal
        # commits within the dispatch that accepted it.  The bare
        # default is one step a dispatch; the deployment that serves
        # (server/main.py) passes the pipeline's depth.  Election /
        # heartbeat timers advance once per STEP, so election_ticks
        # continue to mean steps, not dispatches.
        if steps < 1:
            raise ValueError(f"steps per dispatch must be >= 1: {steps}")
        self._steps = steps
        # Publish workers (parallel hosts): delivering a tick's
        # (already durable) commits to the apply plane costs ~40% of a
        # saturated tick's wall time; ordered workers take it off the
        # tick thread entirely.  The fused runtime runs ONE worker; the
        # mesh runtime runs one per group shard, each owning a disjoint
        # group block (per-group commit order needs no cross-worker
        # coordination).  maxsize=2 bounds the lag to one tick —
        # enqueueing tick t's publish blocks until tick t-1's delivery
        # started, so memory and commit-ack latency stay bounded.
        import queue as _queue
        self._metrics_mu = threading.Lock()
        self._shard_groups = self._pub_shard_groups()
        self._pub_qs: List["_queue.Queue"] = [
            _queue.Queue(maxsize=2) for _ in range(len(self._shard_groups))]
        self._pub_threads: List[threading.Thread] = []
        for j, q in enumerate(self._pub_qs):
            th = threading.Thread(
                target=self._pub_run, args=(q, j), daemon=True,
                name=f"publish-{j}")
            th.start()
            self._pub_threads.append(th)
        # Per-peer timer skew seam: None = lockstep (every peer's timers
        # advance 1 per step).  A [P] i32 array makes peers drift — the
        # chaos harness's clock-skew schedules set it, modeling the real
        # world where deployments never tick in lockstep.  Applied on
        # the next tick(); plumbed through the runtime's per-peer
        # timer_inc (core/cluster.py, parallel/sharded.py).
        self.timer_inc: Optional[np.ndarray] = None

        # Multi-step dispatch epoch state (see tick()): the committed
        # epoch lives in data_dir/EPOCHS (12-byte records, fsynced once
        # per multi-step dispatch AFTER every peer's WAL barrier — the
        # cluster-atomic commit point).  Before any replay, drop every
        # peer's trailing UNCOMMITTED dispatch: within a dispatch peers
        # observe each other's un-fsynced messages, and the per-peer
        # barrier is not atomic, so a crash mid-barrier must erase the
        # whole dispatch everywhere or a vote/append observed by one
        # peer could survive while its sender's record did not (two
        # leaders in one term after replay).
        self._epoch_path = os.path.join(data_dir, "EPOCHS")
        self._epoch_no = _read_committed_epoch(self._epoch_path)
        self._epoch_f = None
        self._ep_active = False
        self._ep_begun = [False] * P
        self._ep_no_this: Optional[int] = None
        # Repair runs whenever any peer WAL exists — even when EPOCHS is
        # missing (committed epoch 0): EPOCHS is created lazily by the
        # FIRST _commit_epoch, so a crash mid-barrier during the
        # first-ever multi-step dispatch leaves epoch-1 BEGIN-framed
        # records durable on some peers with no EPOCHS file at all, and
        # skipping repair would replay exactly the non-atomic dispatch
        # (e.g. a durable vote grant whose sender's term bump was lost)
        # this mechanism exists to drop.
        for d in self.dirs:
            if self._wal_exists(d):
                self._wal_repair_epochs(d, self._epoch_no)

        replayed: List[Optional[dict]] = []
        for p in range(P):
            d = self.dirs[p]
            if self._wal_exists(d):
                replayed.append(self._replay_peer(p, d))
            else:
                os.makedirs(d, exist_ok=True)
                self.wals.append(self._new_wal(d))
                self.plogs.append(PayloadLog(G))
                replayed.append(None)
            # Replay-complete sentinel, replayed-or-not (the reference's
            # nil on commitC, raft.go:131-132).
            self._commit_qs[p].put(None)
        restored = None
        if any(r is not None for r in replayed):
            # A peer without a WAL beside peers with one restores from
            # nothing: the leaves a fresh peer has.
            blank = restored_leaves(cfg, {}, {})
            restored = {k: np.stack([(r or blank)[k] for r in replayed])
                        for k in blank}
        self.states, self.inboxes = self._build_cluster_arrays(
            restored, seed)
        self._E = cfg.max_entries_per_msg
        self._gc_replay = None          # free the boot replay cache
        if self.prof is not None:
            self.prof.gauge_fn("wal.disk_bytes",
                               lambda: self.wal_gauges()[0])
            self.prof.gauge_fn("wal.segments_pinned",
                               lambda: self.wal_gauges()[1])

    # -- subclass seams -------------------------------------------------

    def _device_step(self, prop_n: np.ndarray,
                     timer_inc: Optional[np.ndarray] = None):
        """Dispatch one cluster step; returns (packed-info device array,
        device busy bit or None).  `timer_inc` is the per-peer [P]
        timer advance (None = lockstep 1s, the steady-state fast path).
        Implemented by the concrete runtime — the durable host plane in
        this class is identical either way."""
        raise NotImplementedError

    def _build_cluster_arrays(self, restored: Optional[dict], seed):
        """The boot (states, inboxes) where the step will run them:
        the fresh cluster, with the leaves a replay decides (`restored`:
        {PeerState field: HOST array [P, G, ...]}, None on a first
        boot) laid over it.  Here: the default device.  The mesh
        runtime builds every leaf under its NamedSharding instead, so
        that no chip ever holds the whole cluster."""
        states = init_cluster_state(self.cfg, seed)
        if restored is not None:
            states = states._replace(
                **{k: jnp.asarray(v) for k, v in restored.items()})
        return states, empty_cluster_inbox(self.cfg)

    def _new_wal(self, dirname: str) -> WAL:
        """Construct a peer's durable log handle.  The mesh runtime
        overrides this with a per-group-shard layout (ShardedWAL); the
        group-commit mode hands out per-peer views of ONE shared log."""
        if self._gc_mode:
            if self._gcwal is None:
                from raftsql_tpu.storage.wal import GroupCommitWAL
                self._gcwal = GroupCommitWAL(
                    self._gc_dir, self.cfg.num_peers,
                    self.cfg.num_groups,
                    segment_bytes=self.cfg.wal_segment_bytes)
            return self._gcwal.view(self.dirs.index(dirname))
        return WAL(dirname, segment_bytes=self.cfg.wal_segment_bytes)

    def _wal_exists(self, dirname: str) -> bool:
        if self._gc_mode:
            from raftsql_tpu.storage.wal import GroupCommitWAL
            return GroupCommitWAL.exists(self._gc_dir)
        return wal_exists(dirname)

    def _wal_replay(self, dirname: str):
        if self._gc_mode:
            from raftsql_tpu.storage.wal import GroupCommitWAL
            if self._gc_replay is None:
                self._gc_replay = GroupCommitWAL.replay_flat(self._gc_dir)
            return GroupCommitWAL.split_replay(
                self._gc_replay, self.dirs.index(dirname),
                self.cfg.num_groups)
        return WAL.replay(dirname)

    def _wal_repair_epochs(self, dirname: str, committed: int) -> None:
        if self._gc_mode:
            if not self._gc_repaired:
                self._gc_repaired = True
                from raftsql_tpu.storage.wal import GroupCommitWAL
                GroupCommitWAL.repair_epochs(self._gc_dir, committed)
            return
        WAL.repair_epochs(dirname, committed)

    def _pub_shard_groups(self) -> List[Optional[np.ndarray]]:
        """One entry per ordered publish worker: the group-id block it
        owns (None = all groups).  Workers' blocks MUST be disjoint —
        each group's commit stream is then FIFO through exactly one
        worker, which is what preserves per-group publish order."""
        return [None]

    def _note_commits(self, n: int) -> None:
        """Commit-counter increment, safe from concurrent publish
        workers (disjoint groups, shared counter)."""
        with self._metrics_mu:
            self.metrics.commits += n

    # -- boot -----------------------------------------------------------

    def _replay_peer(self, p: int, d: str) -> dict:
        """Rebuild peer p from its WAL (RestartNode, raft.go:122-134):
        payload log, the replayed committed prefix published to its
        commit stream, and — returned, as host arrays — the leaves of
        its device state that the replay decides
        (core/state.py restored_leaves)."""
        logs = self._wal_replay(d)
        self._replayed_conf[p] = {g: gl.conf for g, gl in logs.items()
                                  if gl.conf is not None}
        self.wals.append(self._new_wal(d))
        plog = PayloadLog(self.cfg.num_groups)
        self.plogs.append(plog)
        log_terms: Dict[int, list] = {}
        hard: Dict[int, tuple] = {}
        starts: Dict[int, tuple] = {}
        g_peer_publishes = p not in self.cfg.witness_set
        for g, gl in logs.items():
            log_terms[g] = [t for (t, _) in gl.entries]
            hard[g] = (gl.hard.term, gl.hard.vote, gl.hard.commit)
            if gl.start:
                starts[g] = (gl.start, gl.start_term)
                plog.set_start(g, gl.start, gl.start_term)
            plog.put(g, gl.start + 1, [dt for (_, dt) in gl.entries],
                     [t for (t, _) in gl.entries])
            self._hard[p, g] = hard[g]
            commit = gl.hard.commit
            self._applied[p, g] = commit
            datas = plog.try_slice(g, gl.start + 1,
                                   max(commit - gl.start, 0))
            # A witness replays its WAL for votes/terms/log only — it
            # has no apply plane, so nothing is re-published (the live
            # path in _publish_shard advances its cursor the same way).
            # A group compacted up to its commit index has nothing to
            # replay, but the consumer must still learn that the stream
            # stands at its floor (runtime/db.py RaftDB._delivered), or
            # a read at that index waits for the next election's no-op.
            if (datas or gl.start) and g_peer_publishes:
                self._commit_qs[p].put((RAW_PLAIN, g, gl.start, datas))
        if starts:
            # The WAL handle is told once what the replay found; the
            # sweeps then tell it only what moves (storage/wal.py).
            self.wals[p].seed_floors(starts)
        return restored_leaves(self.cfg, log_terms, hard,
                               starts=starts or None)

    # -- client plane ---------------------------------------------------

    def commit_q(self, peer: int) -> "queue.Queue":
        return self._commit_qs[peer]

    def leader_of(self, group: int) -> int:
        """Last known leader peer (0-based), -1 unknown."""
        return int(self._hints[group])

    def enable_tracing(self, ring_depth: int = 64,
                       keep: int = 4096) -> None:
        """Turn on both observability planes (raftsql_tpu/obs/): the
        host span tracer and the on-device event ring.  Safe to call
        before the tick loop starts; idempotent."""
        from raftsql_tpu.obs.device_ring import DeviceEventRing
        from raftsql_tpu.obs.spans import SpanTracer
        if self.tracer is None:
            self.tracer = SpanTracer()
        if self.ring is None:
            self.ring = DeviceEventRing(self.cfg.num_peers,
                                        self.cfg.num_groups,
                                        depth=ring_depth, keep=keep)
        for w in self.wals:
            w.obs = self.tracer

    # -- dynamic membership (raftsql_tpu/membership/) -------------------

    def enable_membership(self, initial_voters=None) -> None:
        """Attach the membership plane: per-group voter masks as device
        state, conf entries applied per PEER ROW as that row's commit
        passes them, durable REC_CONF baselines per peer WAL.  Restores
        each peer's active config from its replayed WAL (baseline +
        retained conf entries).  Call before the tick loop; idempotent."""
        from raftsql_tpu.membership import MembershipManager
        if self.membership is not None:
            return
        # Leave the static-full-voter fast path (config.py
        # dynamic_membership): the device program must start reading the
        # per-group masks BEFORE any of them can change.  One recompile.
        import dataclasses as _dc
        if self.cfg.static_full_voters:
            self.cfg = _dc.replace(self.cfg, dynamic_membership=True)
        P, G = self.cfg.num_peers, self.cfg.num_groups
        iv = initial_voters if initial_voters is not None \
            else self.cfg.initial_voters
        geo = dict(write_quorum=self.cfg.write_quorum,
                   election_quorum=self.cfg.election_quorum,
                   witnesses=self.cfg.witnesses or (),
                   unsafe_geometry=self.cfg.unsafe_quorum_geometry)
        mm = MembershipManager(P, G, initial_voters=iv, **geo)
        self._conf_pending = [[] for _ in range(G)]
        self._conf_scrub = [set() for _ in range(G)]
        self._conf_cursor = np.zeros((P, G), np.int64)
        pend: List[Dict[int, bytes]] = [dict() for _ in range(G)]
        for p in range(P):
            view = MembershipManager(P, G, initial_voters=iv, **geo)
            for g in range(G):
                base = self._replayed_conf[p].get(g)
                plog = self.plogs[p]
                start, ln = plog.start(g), plog.length(g)
                datas = plog.try_slice(g, start + 1, ln - start) \
                    if ln > start else []
                entries = [(0, d) for d in (datas or [])]
                if view.restore(g, base, entries, start,
                                int(self._hard[p, g, 2])):
                    c = view.config(g)
                    self._patch_conf_row(p, g, c.entry(0))
                    self._conf_cursor[p, g] = c.index
                    # The cluster authority adopts the most advanced
                    # per-group view (full-picture entries make this a
                    # plain superseding apply).
                    mm.apply(g, c.index, c.entry(0))
                for idx, d in view.appended_list(g):
                    pend[g].setdefault(idx, d)
        self.membership = mm
        for g in range(G):
            for idx in sorted(pend[g]):
                self._conf_note(g, idx, pend[g][idx])

    def _conf_note(self, g: int, idx: int, data: bytes) -> None:
        """A conf entry entered some peer's log at `idx` (tick thread)."""
        lst = self._conf_pending[g]
        lst[:] = [(i, d) for (i, d) in lst if i != idx]
        lst.append((idx, data))
        lst.sort()
        # New set object (not in-place add): the publisher thread scrubs
        # from whatever reference it grabbed — no concurrent mutation.
        self._conf_scrub[g] = self._conf_scrub[g] | {idx}

    def _patch_conf_row(self, p: int, g: int, data: bytes) -> None:
        got = decode_conf_entry(data)
        if got is None:
            return
        _, v, j, _l = got
        P = self.cfg.num_peers
        vrow = np.array([bool(v >> i & 1) for i in range(P)])
        jrow = np.array([bool(j >> i & 1) for i in range(P)])
        self.states = set_group_config_stacked(
            self.states, p, g, vrow, jrow, bool((v | j) >> p & 1))

    def _membership_advance(self, pinfo: np.ndarray) -> None:
        """Apply pending conf entries to each peer row whose commit
        passed them, drive the auto LEAVE_JOINT, and keep the cluster
        authority in sync.  Tick thread, after the durable phases."""
        mm = self.membership
        P = self.cfg.num_peers
        commit = pinfo[:, :, _C["commit"]]
        for g, lst in enumerate(self._conf_pending):
            if not lst:
                continue
            drop: List[int] = []
            for (idx, data) in list(lst):
                all_done = True
                superseded = False
                for p in range(P):
                    if self._conf_cursor[p, g] >= idx:
                        continue
                    if commit[p, g] < idx:
                        all_done = False
                        continue
                    got = self.plogs[p].try_slice(g, idx, 1)
                    if got is None:
                        continue          # compacted under us: settled
                    if got[0] != data:
                        # Conflict truncation rewrote the slot before
                        # commit: this conf never happened.
                        superseded = True
                        break
                    self._patch_conf_row(p, g, data)
                    self._conf_cursor[p, g] = idx
                    # Per-peer durable baseline: THIS entry's masks (the
                    # cluster authority may already be ahead).
                    _k, cv, cj, cl = decode_conf_entry(data)
                    self.wals[p].set_conf(g, idx, _k, cv, cj, cl)
                    if mm.apply(g, idx, data) is not None:
                        self.metrics.conf_changes_applied += 1
                if superseded:
                    mm.abort_pending(g)      # the change never happened
                if superseded or all_done:
                    drop.append(idx)
            if drop:
                lst[:] = [(i, d) for (i, d) in lst if i not in drop]
        # Whichever peer leads a joint group finishes the transition.
        for g in list(mm.joint_groups):
            if self._hints[g] >= 0:
                entry = mm.maybe_leave(g, self._tick_no,
                                       4 * self.cfg.election_ticks)
                if entry is not None:
                    self.propose_many(g, [entry])

    def members_doc(self) -> dict:
        if self.membership is None:
            return {"error": "membership plane not enabled "
                             "(enable_membership())"}
        out = {}
        for g in range(self.cfg.num_groups):
            d = self.membership.describe(g)
            d["leader"] = self.leader_of(g) + 1
            out[str(g)] = d
        return {"num_peers": self.cfg.num_peers, "groups": out,
                "witnesses": sorted(self.witness_peers), "node": 0}

    def member_change(self, group: int, op: str, peer: int) -> dict:
        """Admin plane for the co-located cluster: every peer lives in
        this process, so routing goes through propose_many's leader
        hint instead of a wire forward."""
        from raftsql_tpu.membership import MembershipLagError
        if self.membership is None:
            raise RuntimeError("membership plane not enabled "
                               "(enable_membership())")
        if op == "promote":
            lead = int(self._hints[group])
            commit = int(self._hard[max(lead, 0), group, 2])
            have = self.plogs[peer].length(group)
            if commit - have > self.cfg.max_entries_per_msg:
                raise MembershipLagError(
                    f"group {group}: learner {peer} is "
                    f"{commit - have} entries behind; retry after "
                    "catch-up")
        entry = self.membership.make_change(group, op, peer)
        self.propose_many(group, [entry])
        return self.membership.describe(group)

    # -- leadership transfer (raft thesis §3.10, PR 11) -----------------

    def transfer_leadership(self, group: int, target: int,
                            deadline_ticks: Optional[int] = None) -> dict:
        """Arm a graceful leadership transfer of `group` to peer slot
        `target` (0-based).  The device latch stops proposal intake for
        the group, waits for the target's match_index to catch up, then
        fires the TimeoutNow grant (core/step.py Phase 9); queued
        proposals re-route to the new leader automatically once the
        hint moves.  One in flight per group; past `deadline_ticks` of
        device steps (default 4 election timeouts) the host clears the
        latch and the group resumes serving under the old leader.
        Client-thread safe — the tick thread patches device state."""
        cfg = self.cfg
        if not 0 <= group < cfg.num_groups:
            raise ValueError(f"group {group} out of range")
        if not 0 <= target < cfg.num_peers:
            raise ValueError(f"target {target} out of peer-slot range")
        lead = int(self._hints[group])
        if lead < 0:
            self.metrics.transfers_refused += 1
            raise TransferRefused(group, "group has no leader yet")
        if target == lead:
            self.metrics.transfers_refused += 1
            raise TransferRefused(group, "target already leads")
        if self.membership is not None \
                and not self.membership.is_voter(group, target):
            self.metrics.transfers_refused += 1
            raise TransferRefused(
                group, f"peer {target} is a learner/non-voter")
        if target in self.witness_peers:
            # A witness never campaigns or applies (core/step.py Phase
            # 8 gate): arming the latch would stall the group until the
            # transfer deadline aborts it.
            self.metrics.transfers_refused += 1
            raise TransferRefused(group, f"peer {target} is a witness")
        dl = int(deadline_ticks) if deadline_ticks \
            else 4 * cfg.election_ticks
        with self._xfer_lock:
            if group in self._xfers:
                self.metrics.transfers_refused += 1
                raise TransferRefused(group, "transfer already in flight")
            self._xfers[group] = {"target": target, "from": lead,
                                  "start_tick": self._tick_no,
                                  "deadline_ticks": dl, "deadline": None,
                                  "armed": False}
            self._xfer_req.append((lead, group, target))
        self.metrics.transfers_initiated += 1
        self._work_evt.set()          # wake a parked tick loop
        return {"group": group, "from": lead + 1, "target": target + 1,
                "deadline_ticks": dl}

    def _transfer_arm(self) -> None:
        """Apply queued transfer requests to device state (tick thread,
        before the dispatch so this tick's step sees the latch)."""
        with self._xfer_lock:
            reqs, self._xfer_req = self._xfer_req, []
            for (p, g, tgt) in reqs:
                self.states = set_transfer_target_stacked(
                    self.states, p, g, tgt)
                tr = self._xfers.get(g)
                if tr is not None:
                    tr["armed"] = True
                    tr["deadline"] = (self._device_steps
                                      + tr["deadline_ticks"])

    def _transfer_advance(self, pinfo: np.ndarray) -> None:
        """Completion/abort driver (tick thread, right after the hint
        refresh).  Completed: the hint names the target.  Aborted: the
        deadline passed, or leadership settled on a third peer — either
        way the latch is cleared so the group keeps serving."""
        xcol = pinfo[:, :, _C["xfer"]]
        now = self._device_steps
        with self._xfer_lock:
            for g, tr in list(self._xfers.items()):
                if not tr["armed"]:
                    continue
                outcome = None
                h = int(self._hints[g])
                frm = tr["from"]
                armed_dev = int(xcol[frm, g]) == tr["target"]
                if h == tr["target"]:
                    outcome = "completed"
                elif now >= tr["deadline"]:
                    if armed_dev:
                        self.states = set_transfer_target_stacked(
                            self.states, frm, g, NO_XFER)
                    outcome = "aborted"
                elif not armed_dev and 0 <= h != frm:
                    outcome = "aborted"    # settled elsewhere
                if outcome is None:
                    continue
                del self._xfers[g]
                stall = self._tick_no - tr["start_tick"]
                if outcome == "completed":
                    self.metrics.transfers_completed += 1
                else:
                    self.metrics.transfers_aborted += 1
                self.metrics.note_transfer_stall(stall)
                self._xfer_events.append(
                    {"group": g, "from": frm + 1,
                     "to": tr["target"] + 1, "outcome": outcome,
                     "stall_ticks": int(stall), "tick": self._tick_no})

    def transferring_groups(self) -> set:
        """Groups with a transfer in flight (hot-groups `transferring`
        flag)."""
        with self._xfer_lock:
            return set(self._xfers)

    def transfers_doc(self) -> dict:
        """In-flight latches + the recent-outcome log (flight bundles,
        placement-controller feedback)."""
        with self._xfer_lock:
            inflight = {str(g): {"target": tr["target"] + 1,
                                 "from": tr["from"] + 1,
                                 "start_tick": tr["start_tick"]}
                        for g, tr in self._xfers.items()}
            recent = list(self._xfer_events)
        return {"in_flight": inflight, "recent": recent}

    def propose_many(self, group: int, payloads,
                     deadline_step: Optional[int] = None) -> None:
        """Queue payloads at the group's current leader peer (host-side
        routing — all peers share this process; the distributed
        runtime's forward-over-transport becomes a list move).

        `deadline_step` (absolute device-step deadline, overload plane
        only) rides each entry as a (payload, deadline) pair; staging
        strips it and sheds entries already past it BEFORE any WAL
        cost.  With no overload controller attached and no deadline,
        this path is byte-identical to the pre-overload code."""
        if self.tracer is not None:
            for d in payloads:
                self.tracer.begin(group,
                                  d.decode("utf-8", "replace"))
        ov = self.overload
        if deadline_step is not None:
            payloads = [(d, int(deadline_step)) for d in payloads]
        p = int(self._hints[group])
        if p < 0:
            p = 0
        with self._prop_lock:
            if ov is not None:
                ov.admit(group, len(payloads))   # raises Overloaded
            if deadline_step is not None:
                self._deadlines_live = True
            self._props[p][group].extend(payloads)
            self._queued.add((p, group))
        self._work_evt.set()

    # -- threaded serving (single-process deployments) ------------------

    def start(self, interval_s: float = 0.002) -> None:
        """Run the tick loop on a background thread: wake immediately
        on proposals; tick at `interval_s` while consensus is active;
        PARK at a 0.5 s safety heartbeat once the cluster is quiet
        (nothing queued, committed-but-unpublished, leaderless, written
        this tick, or busy on-device — see the runtime's busy bit).
        Pausing a quiet cluster is safe precisely because it is
        single-controller: ALL peers pause together, so no peer can
        observe missed heartbeats, no timer skews, and elections fire
        only when a group actually lacks a leader."""
        def _run():
            while not self._stop_evt.is_set():
                self._work_evt.clear()
                try:
                    self.tick()
                except Exception as e:   # pragma: no cover - defensive
                    self.error = e
                    for q in self._commit_qs:
                        q.put(CLOSED)
                    return
                # Idle parking: a QUIET single-controller cluster can
                # pause consensus outright — every peer pauses with it,
                # so no election can fire spuriously and nothing is
                # missed; the next proposal (work event) resumes it.
                # The 0.5 s cap is a safety heartbeat.  While HOT
                # (client work in flight), loop back-to-back: the
                # tick's own wall time is the pacing, and relative
                # timer safety (heartbeat period < election timeout)
                # holds at any wall rate because all peers step
                # together — each saved interval_s is a propose→commit
                # pipeline hop clients don't wait.  ACTIVE-but-not-hot
                # (e.g. leaderless warmup) paces at interval_s.
                if not self._tick_active:
                    self._work_evt.wait(0.5)
                elif not self._spin_hot:
                    self._work_evt.wait(interval_s)

        self._thread = threading.Thread(target=_run, daemon=True,
                                        name="cluster-tick")
        self._thread.start()

    # -- linearizable reads (single-controller cluster) -----------------

    def commit_watermark(self, group: int) -> int:
        """Replicated read-index watermark for follower/session reads
        (X-Raft-Session): the hinted leader's commit index — in the
        co-located cluster that IS the global commit point."""
        p = max(int(self._hints[group]), 0)
        return int(self._hard[p, group, 2])

    def lease_read(self, group: int) -> Optional[int]:
        """Serve a linearizable read from the device-computed leader
        lease: the read's target commit index while the hinted
        leader's lease covers `now + max_clock_skew`, else None (the
        caller degrades to read_index — never a silent stale read).
        The §6.4 current-term-commit precondition is folded into the
        device lease value (0 while pending)."""
        cfg = self.cfg
        if cfg.lease_ticks <= 0:
            return None
        lc = self._lease_col
        p = int(self._hints[group])
        if lc is None or p < 0:
            return None
        until = int(lc[p, group])
        if until > 0 \
                and self._device_steps + cfg.max_clock_skew < until:
            self.metrics.lease_grants += 1
            return int(self._hard[p, group, 2])
        if until > 0:
            self.metrics.lease_expiries += 1
        return None

    def read_index(self, group: int):
        """ReadIndex for the co-located cluster: every peer of the
        group lives in THIS process, so no other process can hold a
        newer leadership — the leader's current commit index IS the
        linearization point, no quorum round needed.  Returns () while
        the group has no leader yet (caller polls)."""
        p = int(self._hints[group])
        if p < 0:
            return ()
        return int(self._hard[p, group, 2]), 0

    def read_ready(self, group: int, reg_tick: int) -> bool:
        return True

    def status(self) -> dict:
        """Per-group consensus status for GET /healthz (same shape as
        runtime/node.py status()): in the co-located cluster the
        process's role for a group is "leader" once a leader is known
        — every peer lives here — and "unknown" while leaderless.
        Host caches only (hints + hard-state mirror); never touches
        device arrays."""
        hints = self._hints
        lead = np.maximum(hints, 0)
        groups = np.arange(self.cfg.num_groups)
        terms = self._hard[lead, groups, 0].tolist()
        commits = self._hard[lead, groups, 2].tolist()
        out = {}
        for g, p in enumerate(hints.tolist()):
            if p >= 0:
                out[str(g)] = {"role": "leader", "leader": p + 1,
                               "term": terms[g], "commit": commits[g]}
            else:
                out[str(g)] = {"role": "unknown", "leader": 0,
                               "term": 0, "commit": 0}
        return out

    def shm_columns(self):
        """commit_watermark, leader_of and lease_deadline_s of every
        group at once, as [G] columns (runtime/shm.py refresh_columns):
        the shm publisher's refresh without a Python call a group."""
        hints = self._hints
        commit = self._hard[0, :, 2].copy()      # peer 0 where unknown
        for p in range(1, self.cfg.num_peers):
            np.copyto(commit, self._hard[p, :, 2], where=hints == p)
        return commit, hints, self.lease_deadlines_s(hints)

    def lease_deadlines_s(self, hints=None) -> np.ndarray:
        """lease_deadline_s of every group as one [G] float column (0.0
        where no live lease), from the same arrays and bound."""
        cfg = self.cfg
        G = cfg.num_groups
        lc = self._lease_col
        if hints is None:
            hints = self._hints
        if cfg.lease_ticks <= 0 or lc is None:
            return np.zeros(G)
        until = lc[np.maximum(hints, 0), np.arange(G)].astype(np.int64)
        remaining = until - (self._device_steps + cfg.max_clock_skew)
        live = (hints >= 0) & (until > 0) & (remaining > 0)
        ahead = np.minimum(remaining * self.step_interval_s,
                           self._LEASE_HORIZON_S)
        return np.where(live, time.monotonic() + ahead, 0.0)

    # Published-deadline horizon: see runtime/node.py — the shm
    # publisher refreshes every millisecond or two, so capping how far
    # ahead a deadline reaches bounds staleness when the tick loop
    # hot-spins device steps faster than the wall interval.
    _LEASE_HORIZON_S = 0.05

    @property
    def step_interval_s(self) -> float:
        """The wall time one consensus step is taken to last wherever a
        count of steps becomes a time or a time a count of steps (the
        published lease deadline below, the overload plane's
        deadline_steps): the loop paces DISPATCHES at tick_interval_s
        and a dispatch carries `_steps` steps at once.  An untimed
        engine (tick_interval_s == 0) converts at 0.1 ms a step."""
        return max(self.cfg.tick_interval_s / self._steps, 1e-4)

    def lease_deadline_s(self, group: int) -> float:
        """The time.monotonic() instant until which a lease read for
        `group` stays provably safe, 0.0 when no live lease — the
        shm-snapshot / routing-hint surface (runtime/shm.py).  The
        remaining lease is measured in DEVICE steps against the same
        `_device_steps + max_clock_skew` bound lease_read enforces, so
        a mis-sized max_clock_skew propagates verbatim into the
        published deadline (the chaos falsification pair still
        catches it on the shm plane).  No metric side effects."""
        cfg = self.cfg
        if cfg.lease_ticks <= 0:
            return 0.0
        lc = self._lease_col
        p = int(self._hints[group])
        if lc is None or p < 0:
            return 0.0
        until = int(lc[p, group])
        remaining = until - (self._device_steps + cfg.max_clock_skew)
        if until <= 0 or remaining <= 0:
            return 0.0
        return time.monotonic() + min(remaining * self.step_interval_s,
                                      self._LEASE_HORIZON_S)

    # -- the tick -------------------------------------------------------

    def _build_prop_n(self, steps: int = 1) -> np.ndarray:
        """Per-dispatch proposal counts.  steps == 1: [P, G], up to E
        per group.  steps > 1 (multi-step dispatch): [S, P, G] — each
        step gets its own ≤E chunk of the backlog, so one dispatch can
        accept (and commit) up to S×E per group.  The device may accept
        less at any step (window pressure); the host pops exactly what
        each step REPORTS accepted, in step order, and offers were cut
        from one backlog snapshot — so pops never outrun the queue and
        payloads stay aligned with the device's assigned indexes."""
        P, G = self.cfg.num_peers, self.cfg.num_groups
        E = self._E
        cap = E * steps
        # Filled entry by entry, a dozen of 30,000 a step: nothing of
        # [P, G] size is computed to cut the offers into steps.
        prop_n = np.zeros((steps, P, G), np.int32)
        dead = []
        ov = self.overload
        now_step = self._device_steps
        # intake.* (obs/prof.py): what the queues hold and what is
        # offered at the moment of this pop; tick() counts them with
        # what the device accepted, so a scrape never sees one without
        # the other.
        backlog = offered = groups = 0
        with self._prop_lock:
            for (p, g) in list(self._queued):  # snapshot: re-routes mutate
                q = self._props[p][g]
                if not q:
                    dead.append((p, g))
                    continue
                h = int(self._hints[g])
                if 0 <= h != p:
                    # Re-route a backlog stranded at a deposed/wrong peer.
                    self._props[h][g].extend(q)
                    q.clear()
                    self._queued.add((h, g))
                    dead.append((p, g))
                    continue
                if self._deadlines_live:
                    # Shed queued entries whose device-step deadline
                    # already passed — BEFORE they are offered to the
                    # device, so no WAL write, fsync or publish is ever
                    # paid for work the client has given up on
                    # (overload plane; entries are (payload, deadline)
                    # pairs only when a deadline was supplied).
                    live = [e for e in q
                            if type(e) is not tuple or e[1] >= now_step]
                    n_shed = len(q) - len(live)
                    if n_shed:
                        q[:] = live
                        if ov is not None:
                            ov.stage_shed(g, n_shed)
                        if not q:
                            dead.append((p, g))
                            continue
                n = len(q)
                offer = min(n, cap)
                for s in range(-(-offer // E)):
                    prop_n[s, p, g] = min(offer - s * E, E)
                backlog += n
                offered += offer
                groups += 1
            for k in dead:
                self._queued.discard(k)
        self._intake = [backlog, offered, groups, 0, 0]
        return prop_n if steps > 1 else prop_n[0]

    def _pub_run(self, q: "queue.Queue", shard: int) -> None:
        """Ordered publish worker (see __init__): per worker one queue,
        one disjoint group block, FIFO — publishes retire in tick
        order.  `_applied` and the commit queues for a given group are
        touched only by its owning worker after construction, so the
        cursor needs no lock; compact() reads _applied from other
        threads but a stale (lower) value only makes its floor more
        conservative."""
        import time as _t
        while True:
            item = q.get()
            try:
                # After a publish fault, keep draining (so flush/stop
                # never hang) but publish nothing more: the CLOSED
                # sentinel must stay the queues' last item.
                if item is not None and self.error is None:
                    pinfo, ptick, t_enq = item
                    # Per-shard publish workers tag their shard id —
                    # the mesh runtime's N workers each get their own
                    # Perfetto phase track.
                    prof = self.prof
                    ann = prof.annotation() if prof is not None else None
                    t0 = _t.monotonic()
                    if prof is not None:
                        # Handed over by the tick -> taken up by this
                        # worker (the wait for room in a full queue
                        # included): inside a write's propose_commit.
                        prof.stage("publish.queue", t0 - t_enq)
                    with span(ann, "tick.publish", ptick):
                        n_groups = self._publish_shard(pinfo, shard)
                    dur = _t.monotonic() - t0
                    with self._metrics_mu:
                        self.metrics.t_publish_ms += dur * 1e3
                    if prof is not None:
                        # publish.groups: once a dispatch a worker,
                        # with the phase, never once a group.
                        prof.record_tick(
                            ptick, (("publish", t0, dur),),
                            (("publish.groups", n_groups),), tid=shard)
            except Exception as e:
                self.error = e
                for cq in self._commit_qs:
                    cq.put(CLOSED)
            finally:
                q.task_done()
            if item is None:
                return

    def _enqueue_publish(self, pinfo: np.ndarray) -> None:
        """Hand a durable tick's packed info to every publish worker
        (each delivers only its own group block).  The owning tick id
        (`self._prof_tick`, set by the caller) rides the queue item so
        the workers' publish phases attribute to the right tick, and
        the hand-over's time so each worker can say how long the item
        waited for it (`stages.publish.queue`)."""
        import time as _t
        item = (pinfo, self._prof_tick, _t.monotonic())
        for q in self._pub_qs:
            q.put(item)

    def publish_flush(self) -> None:
        """Block until every enqueued publish has been delivered (the
        bench and tests read apply-plane state right after a tick
        loop).  Re-raises a publish fault — the async path must fail as
        loudly as the inline one did."""
        for q in self._pub_qs:
            q.join()
        if self.error is not None:
            raise self.error

    def _ensure_epoch_begin(self, p: int) -> None:
        """Lazily open peer p's dispatch frame: the BEGIN marker is
        written only when the dispatch actually writes to that peer's
        WAL (an idle multi-step tick costs zero records and zero epoch
        fsyncs)."""
        if not self._ep_active or self._ep_begun[p]:
            return
        if self._ep_no_this is None:
            self._ep_no_this = self._epoch_no + 1
        self._ep_begun[p] = True
        self.wals[p].epoch_mark(self._ep_no_this, end=False)

    def _commit_epoch(self, no: int) -> None:
        """The multi-step dispatch's atomic commit point: append the
        epoch number to data_dir/EPOCHS and fsync it — AFTER every
        peer's WAL barrier, BEFORE publish.  Recovery drops any
        dispatch whose number never made it here."""
        import struct
        import zlib
        created = False
        if self._epoch_f is None:
            created = not os.path.exists(self._epoch_path)
            self._epoch_f = open(self._epoch_path, "ab")
        rec = struct.pack("<Q", no)
        fsio.write(self._epoch_f,
                   rec + struct.pack("<I", zlib.crc32(rec)))
        fsio.fsync_file(self._epoch_f)
        if created:
            # Dirent durability for the just-created file, BEFORE the
            # epoch counts as committed: the record fsync above makes
            # the bytes durable but not the directory entry — a crash
            # could drop the whole file, and recovery would then
            # misclassify committed (already published/acked)
            # dispatches as uncommitted.  Mirrors the rotation path.
            fsio.fsync_dir(os.path.dirname(self._epoch_path) or ".")
        if self._epoch_f.tell() >= self._EPOCH_ROTATE_BYTES:
            # Rotate: only the LAST record matters for recovery.  Write
            # a one-record replacement beside the live file, fsync it,
            # atomically swap (rename is the commit), fsync the dir.
            tmp = self._epoch_path + ".tmp"
            with open(tmp, "wb") as f:
                fsio.write(f, rec + struct.pack("<I", zlib.crc32(rec)))
                fsio.fsync_file(f)
            os.replace(tmp, self._epoch_path)
            fsio.fsync_dir(os.path.dirname(self._epoch_path) or ".")
            self._epoch_f.close()
            self._epoch_f = open(self._epoch_path, "ab")

    def _hard_changed(self, pinfo: np.ndarray):
        """(peers, groups, rows): the (peer, group) whose hard state in
        this step differs from what the WALs hold, peer-major and
        group-ascending, and their (term, vote, commit) [n, 3].  ONE
        exact compare for all peers in three numpy calls of [P, G] size
        (the gather, the compare, the nonzero), each over contiguous
        columns where the packed info comes column-major.  The count
        matters more than the arithmetic: on a served engine every such
        call lets go of the interpreter, and the tick thread then
        stands in line for it behind the engine's other threads."""
        G = pinfo.shape[1]
        hs = pinfo.transpose(0, 2, 1)[:, _HARD_COLS]        # [P, 3, G]
        ne = np.not_equal(hs, self._hard_cols, out=self._hard_ne)
        at = np.flatnonzero(ne)             # (peer, column, group)
        if at.size:
            # A row is found once a changed column: few from here on.
            at = np.unique(at // (3 * G) * G + at % G)
        peers, groups = np.divmod(at, G)
        return peers, groups, hs[peers, :, groups]

    def _save_hard(self, pinfo: np.ndarray) -> bool:
        """Write every peer's changed hard states (term/vote/commit) to
        its WAL, AFTER the tick's entry records (etcd wal.Save order: a
        torn tail can then never leave a hard state referencing lost
        entries): one compare for all peers, then one set_hardstates a
        peer that has any.  True when anything changed."""
        peers, groups, rows = self._hard_changed(pinfo)
        if not peers.size:
            return False
        ends = np.searchsorted(peers, np.arange(self.cfg.num_peers),
                               side="right").tolist()
        lo = 0
        for p, hi in enumerate(ends):
            if hi > lo:
                changed, r = groups[lo:hi], rows[lo:hi]
                self._ensure_epoch_begin(p)
                self.wals[p].set_hardstates(changed, r[:, 0], r[:, 1],
                                            r[:, 2])
                self._hard[p][changed] = r
                self._wal_hard[p] = changed     # -> wal.* (_count_wal)
            lo = hi
        return True

    def tick(self) -> None:
        """One device step + the durable host phase.

        Order (the contract in the module docstring): offer → dispatch
        → read packed info → stage → WAL + payload-log writes → fsync
        every peer (→ the epoch commit) → publish.  The NEXT dispatch
        cannot happen before this method returns, so every message
        composed this tick is durable on its sender before any receiver
        observes it, and a dispatch's commits reach the publish workers
        before the next offer; publish always runs after the save of
        the tick it publishes.  (A serial host may defer a heavy
        publish into the next dispatch window: `_pending_pinfo`.)
        """
        import time as _t
        # The telemetry plane (obs/prof.py): the clock is read in place
        # and the tick's samples go over in one record_tick() below;
        # `ann` is this tick's one test for a running profiler session,
        # under which each leaf phase is also a `tick.<phase>` span.
        prof = self.prof
        ann = self._ann = prof.annotation() if prof is not None else None
        tick_no = self._tick_no
        t0 = _t.monotonic()
        with span(ann, "tick.pop", tick_no):
            if self._xfer_req:
                self._transfer_arm()     # latch visible to THIS dispatch
            # Snapshot _queued: _build_prop_n may re-route into the set.
            prop_n = self._build_prop_n(self._steps)
        tb = tl = _t.monotonic()
        ti = self.timer_inc
        put = self._put_inputs
        if put is not None:
            # A leaf of its own, not inside tick.launch: a gap goes to
            # the event that covers most of it (obs/prof.py).
            with span(ann, "tick.mesh_put", tick_no):
                dev_in = put(prop_n, ti)
            tl = _t.monotonic()
        else:
            dev_in = (prop_n, ti)
        with span(ann, "tick.launch", tick_no):
            if ti is not None:
                # Skew accounting: how far this tick's timer advances
                # deviate from lockstep, per peer, summed.
                self.metrics.faults_skew_ticks += int(
                    np.abs(np.asarray(ti, np.int64) - 1).sum())
            pinfo_dev, busy_dev = self._device_step(*dev_in)
            if self.ring is not None:
                # Device-plane event ring: one extra small fused program
                # over arrays already resident (tracing-on cost only);
                # the ring stays on device and drains to host in
                # batches.  A multi-step dispatch records its final
                # step — the ring is tick-indexed at dispatch
                # granularity, like the runtime.
                self.ring.record(self._tick_no,
                                 pinfo_dev if self._steps == 1
                                 else pinfo_dev[-1],
                                 self.states.votes, self.inboxes.v_type,
                                 self.inboxes.a_type, self._applied)
        t1 = _t.monotonic()
        # Overlap: tick t-1's commits are durable (fsynced last tick).
        # Parallel hosts hand them to the publish workers (the apply
        # plane runs concurrently with this whole tick); a 1-core host
        # delivers inline while the device computes.
        if self._pending_pinfo is not None:
            self._prof_tick = self._pending_tick
            if self._host_parallel:
                self._enqueue_publish(self._pending_pinfo)
            else:
                self._publish_inline(self._pending_pinfo,
                                     self._pending_tick)
            self._pending_pinfo = None
        if self._compact_req is not None:
            # Between two durable phases: the previous tick's finished
            # before this dispatch, this tick's starts after the
            # readback below.
            self._run_compact_request()
        t2 = _t.monotonic()
        if self.overlap_hook is not None:
            # Hook wall time is the caller's (apply-plane) cost, not a
            # tick phase: charge it to neither publish nor device.
            self.overlap_hook()
            t2b = _t.monotonic()
        else:
            t2b = t2
        with span(ann, "tick.readback", tick_no):
            if busy_dev is not None:
                pinfo, dev_busy = jax.device_get((pinfo_dev, busy_dev))
                pinfo = np.asarray(pinfo)
                dev_busy = bool(dev_busy)
            else:
                pinfo = np.asarray(jax.device_get(pinfo_dev))  # [P,G,NCOLS]
                dev_busy = True
        t3 = _t.monotonic()

        # The dispatch's packed info as ONE block [S, P, G, C], a
        # single-step dispatch's as S = 1; the host replays its durable
        # phases in step order — every step's entries land before the
        # ONE hard-state save + fsync barrier of the dispatch, which
        # preserves the etcd wal.Save order (entries-then-hardstate)
        # at dispatch granularity.
        step_infos = pinfo if pinfo.ndim == 4 else pinfo[None]
        pinfo = step_infos[-1]
        self._hints = pinfo[0, :, _C["leader_hint"]]
        self._lease_col = pinfo[:, :, _C["lease"]]
        self._device_steps += len(step_infos)
        if not self._all_led_s and not (self._hints < 0).any():
            self._all_led_s = _t.monotonic() - self.t_born
        if self._xfers:
            self._transfer_advance(pinfo)
        # Stage the 2a ranges (this pops the device-accepted proposals
        # off the queues) before the durable phase that writes them.
        ts0 = _t.monotonic()
        with span(ann, "tick.pop", tick_no):
            staged = self._stage_ranges(step_infos)
        if prof is not None:
            # `dispatch` is launch + readback (the host blocks on the
            # device completing this tick's program): a sample each,
            # and each half under its own name too; on a mesh the
            # inputs' way to their shards is a third part, `mesh_put`.
            # dispatch.steps: the consensus steps this launch carried.
            # pop's second sample, the staging, also under a name of
            # its own (pop_stage).
            # intake.*: what _build_prop_n found queued and offered,
            # what _stage_ranges popped as accepted, and how much of
            # that the publishing peer saw committed before the
            # dispatch ended.
            ld, rd = t1 - tl, t3 - t2b
            sd = _t.monotonic() - ts0
            it = self._intake
            samples = [("pop", t0, tb - t0), ("dispatch", tl, ld),
                       ("launch", tl, ld), ("dispatch", t2b, rd),
                       ("readback", t2b, rd),
                       ("pop", ts0, sd), ("pop_stage", ts0, sd)]
            if put is not None:
                samples += (("dispatch", tb, tl - tb),
                            ("mesh_put", tb, tl - tb))
            counts = (("dispatch.steps", len(step_infos)),)
            if it[2]:
                counts += (("intake.backlog", it[0]),
                           ("intake.offered", it[1]),
                           ("intake.groups", it[2]),
                           ("intake.accepted", it[3]),
                           ("intake.committed_in_dispatch", it[4]))
            prof.record_tick(tick_no, samples, counts)
        if self.overload is not None:
            # Overload plane tick feed: drain-rate EWMA (Retry-After)
            # + queue-depth EWMA (the brownout governor's hysteresis).
            self.overload.note_tick()
        # Content-derived activity signals: any append staged or
        # mirrored, or any hard state due to change.
        tick_active = any(
            bool(st_p[0]) for st in staged for st_p in st)
        if not tick_active:
            tick_active = bool(
                (step_infos[..., _C["app_from"]] >= 0).any())
        if not tick_active:
            tick_active = bool(self._hard_changed(pinfo)[0].size)
        # Quiescence signal for the threaded loop: anything written,
        # any group leaderless, or any proposal backlog means "keep
        # ticking at full pace".
        base_active = (tick_active
                       or dev_busy
                       or bool((self._hints < 0).any())
                       or bool(self._queued)
                       or bool(self._xfers))
        # HOT means real client work is flowing (writes this tick, a
        # device dispatch still in flight, or a proposal backlog): the
        # threaded loop then ticks back-to-back.  Merely-leaderless
        # groups keep the loop ACTIVE (elections must advance) but not
        # hot — warmup paces at interval_s instead of starving the
        # host core the cluster shares with its clients.
        self._spin_hot = tick_active or dev_busy or bool(self._queued)
        # The durable phase runs here, in the tick that dispatched, and
        # its commits go to the publish workers before the next offer:
        # a launch costs tens of ms of host time to start ~3 ms of
        # device, so nothing is left to overlap it with (PERF.md, PR 39).
        t4 = _t.monotonic()
        self._prof_tick = self._tick_no
        tick_active = self._finish_durable(step_infos, staged) \
            or tick_active
        base_active = base_active or tick_active
        if base_active:
            if self._host_parallel:
                # The publish workers ARE the overlap: hand the tick's
                # commits over right after the durable barrier instead
                # of deferring to the next tick's dispatch window —
                # one whole tick less propose→ack latency.
                self._enqueue_publish(pinfo)
            else:
                # Serial host: defer-and-overlap pays only when the
                # publish is expensive.  A light tick's batch (a few
                # serving requests) costs far less to deliver NOW than
                # the whole tick of ack latency the deferral adds.
                delta = int(np.clip(
                    pinfo[0][:, _C["commit"]] - self._applied[0],
                    0, None).sum())
                if delta <= self._inline_publish_max:
                    self._publish_inline(pinfo, self._tick_no)
                    self._pending_pinfo = None
                else:
                    self._pending_pinfo = pinfo  # next tick overlaps
                    self._pending_tick = self._tick_no
        else:
            # About to go quiet: deliver this tick's commits NOW (they
            # are fsynced above) instead of deferring to a next tick
            # that may be a parked 0.5s away — the deferral only pays
            # when another dispatch immediately follows to overlap.
            if self._host_parallel:
                self._enqueue_publish(pinfo)
            else:
                self._publish_inline(pinfo, self._tick_no)
            self._pending_pinfo = None
        self._tick_active = base_active
        self.metrics.t_device_ms += ((t1 - t0) + (t3 - t2b)) * 1e3
        self.metrics.t_wal_ms += (_t.monotonic() - t4) * 1e3
        self._tick_no += 1
        self.metrics.ticks += 1

    def _publish_inline(self, pinfo: np.ndarray, tick_no: int) -> None:
        """Deliver on the tick thread (serial hosts), timed as the
        `publish` phase of the tick that owns the commits."""
        import time as _t
        tp = _t.monotonic()
        with span(self._ann, "tick.publish", tick_no):
            n_groups = self._publish(pinfo)
        pdur = _t.monotonic() - tp
        self.metrics.t_publish_ms += pdur * 1e3
        if self.prof is not None:
            self.prof.record_tick(tick_no, (("publish", tp, pdur),),
                                  (("publish.groups", n_groups),))

    def _finish_durable(self, step_infos, staged) -> bool:
        """The whole durable back half for one dispatch, from its
        packed info [S, P, G, C] and its S staged write plans: the
        durable phases (epoch-framed when multi-step), the epoch
        commit, and membership apply-at-commit.  Returns tick_active
        (anything written).  Attributed to `self._prof_tick` (set by
        the caller: the live tick)."""
        import time as _t
        pinfo = step_infos[-1]
        prof = self.prof
        ptick = self._prof_tick
        td0 = _t.monotonic()
        self._fsync_span = None
        split = self._wal_split
        split[0] = split[1] = split[2] = 0.0
        if prof is not None and self._wal_wrote is None:
            self._wal_wrote = self._wal_written()   # wal.* start here
        # Multi-step dispatches are epoch-framed (see _ensure_epoch_
        # begin / _commit_epoch): BEGIN lazily wraps each peer's first
        # write, END lands before its fsync, and the dispatch commits
        # atomically below.
        self._ep_active = len(step_infos) > 1
        if self._ep_active:
            self._ep_begun = [False] * self.cfg.num_peers
            self._ep_no_this = None
        tick_active = self._durable_phases(step_infos, staged)
        ep_span = None
        if self._ep_active and self._ep_no_this is not None:
            # Every peer's barrier is down; this fsync is the
            # dispatch's atomic commit point (before any publish).
            # Clocked where it runs, apart from the WAL barrier: a
            # leaf span and a phase of its own (epoch_commit).
            self._epoch_no = self._ep_no_this
            te0 = _t.monotonic()
            with span(self._ann, "tick.epoch_commit", ptick):
                self._commit_epoch(self._epoch_no)
            ep_span = (te0, _t.monotonic() - te0)
        self._ep_active = False
        if self.membership is not None:
            # Apply-at-commit for conf entries: patch each peer row
            # whose commit passed a pending entry, BEFORE this tick's
            # publish enqueue (the scrub set must cover the batch).
            self._membership_advance(pinfo)
        if self._gcwal is not None:
            self.metrics.wal_group_commits = self._gcwal.group_commits
        if prof is not None:
            samples: list = []
            if tick_active:
                # wal_write = the durable back half minus the fsync
                # barrier and the epoch commit (each clocked where it
                # ran: _durable_phases fills _fsync_span); its parts
                # are _durable_phases'.
                t_tot = _t.monotonic() - td0
                fs = self._fsync_span
                fdur = fs[1] if fs is not None else 0.0
                edur = ep_span[1] if ep_span is not None else 0.0
                samples = [("wal_write", td0,
                            max(t_tot - fdur - edur, 0.0)),
                           ("wal_plan", td0, split[0]),
                           ("wal_append", td0, split[1]),
                           ("wal_hardstate", td0, split[2])]
                if fs is not None:
                    samples.append(("fsync", fs[0], fdur))
                if ep_span is not None:
                    samples.append(("epoch_commit",) + ep_span)
            prof.record_tick(ptick, samples, self._wal_counts())
        return tick_active

    def _wal_written(self) -> Tuple[int, int]:
        """(bytes, flushing barriers) of every WAL this plane writes,
        cumulative (storage/wal.py WAL.written; the group-commit views
        share one log, counted once)."""
        if self._gcwal is not None:
            return self._gcwal.base.written()
        got = [w.written() for w in self.wals]
        return sum(b for b, _ in got), sum(n for _, n in got)

    def _wal_counts(self) -> tuple:
        """The wal.* increments of the durable phase that just ran:
        what it handed to the WALs, or () where it handed them
        nothing."""
        hard = 0
        groups = self._wal_groups
        for p, changed in enumerate(self._wal_hard):
            if changed is not None:
                hard += changed.size
                groups.update(changed.tolist())
                self._wal_hard[p] = None
        records, self._wal_records = self._wal_records, 0
        mirror = self._wal_mirror
        rows, fell_back, skipped = mirror
        mirror[0] = mirror[1] = mirror[2] = 0
        wrote0, wrote1 = self._wal_wrote, self._wal_written()
        if not (records or hard or rows or skipped or wrote1 != wrote0):
            return ()
        self._wal_wrote = wrote1
        n_groups = len(groups)
        groups.clear()
        # A sharded WAL (runtime/mesh.py ShardedWAL) counts the shard
        # streams its barriers flushed; a WAL of one stream has none.
        shard0 = self._wal_shard_syncs
        shard1 = self._wal_shard_syncs = sum(
            getattr(w, "shard_syncs", 0) for w in self.wals)
        return (("wal.records", records),
                ("wal.bytes", wrote1[0] - wrote0[0]),
                ("wal.hardstates", hard),
                ("wal.groups_written", n_groups),
                ("wal.fsyncs", wrote1[1] - wrote0[1]),
                ("wal.shard_syncs", shard1 - shard0),
                ("wal.mirror_rows", rows),
                ("wal.mirror_fallback_rows", fell_back),
                ("wal.mirror_skipped_rows", skipped))

    def _stage_ranges(self, infos: np.ndarray) -> list:
        """Build a dispatch's phase-2a write plans — per step, per peer
        the (r_g, r_start, r_count, r_term, w_d) uniform-term ranges of
        fresh-leader no-ops + accepted proposals — POPPING the accepted
        payloads off the proposal queues.  `infos` is the dispatch's
        packed info [S, P, G, C].  Runs at stage time, in the tick that
        read it: the pops must settle before the next tick's
        _build_prop_n snapshot (offer counts and re-routes read queue
        lengths).  Side effects that ride
        the pop (conf-entry notes, tracer append stamps, the proposals
        counter) happen here too, in step order.

        The (step, peer, group) with anything to stage are found in ONE
        pass over the block, not one a step a peer: a dozen rows of
        30,000 a step, and every [G]-sized numpy call costs the tick
        thread of a served engine a turn at the interpreter (PERF.md,
        PR 31), which S steps a dispatch would pay S times over."""
        S, P = infos.shape[:2]
        out = [[([], [], [], [], []) for _ in range(P)]
               for _ in range(S)]
        at = np.flatnonzero(infos[..., _C["noop"]]
                            | infos[..., _C["prop_accepted"]])
        if not at.size:
            return out
        ss, ps, gs = np.unravel_index(at, infos.shape[:3])
        rows = infos[ss, ps, gs]                    # [n, C], few
        bases = rows[:, _C["prop_base"]]
        accs = rows[:, _C["prop_accepted"]]
        took = accs > 0
        # Of what this dispatch accepts, the entries the publishing
        # peer's final commit index already covers: those commit inside
        # the dispatch that accepted them (intake.committed_in_dispatch).
        done = np.clip(infos[-1, 0, gs, _C["commit"]] - bases, 0, accs)
        self._intake[4] += int(done[took].sum())
        noops = rows[:, _C["noop"]] != 0
        terms = rows[:, _C["term"]]
        traced = [] if self.tracer is not None else None
        confs = [] if self.membership is not None else None
        ov = self.overload
        strip = self._deadlines_live
        # One step's rows of one peer at a time, as they come (groups
        # ascending).
        cuts = (np.flatnonzero(np.diff(ss * P + ps)) + 1).tolist()
        for lo, hi in zip([0] + cuts, cuts + [at.size]):
            p = int(ps[lo])
            r_g, r_start, r_count, r_term, w_d = out[int(ss[lo])][p]
            nz = noops[lo:hi]
            if nz.any():
                # One empty record at prop_base per fresh leader
                # (ordered before any accepted proposals of the same
                # group — base < base+1, both pure tail appends).
                ngs = gs[lo:hi][nz].tolist()
                r_g.extend(ngs)
                r_start.extend(bases[lo:hi][nz].tolist())
                r_count.extend([1] * len(ngs))
                r_term.extend(terms[lo:hi][nz].tolist())
                w_d.extend([b""] * len(ngs))
            tk = took[lo:hi]
            if not tk.any():
                continue
            props_p = self._props[p]
            with self._prop_lock:   # pops race client-thread extends
                for g, n, b0, tm in zip(gs[lo:hi][tk].tolist(),
                                        accs[lo:hi][tk].tolist(),
                                        (bases[lo:hi][tk] + 1).tolist(),
                                        terms[lo:hi][tk].tolist()):
                    q = props_p[g]
                    batch = q[:n]
                    del q[:n]
                    if strip:
                        # Deadline-carrying entries are (payload,
                        # deadline_step) pairs — strip to plain bytes
                        # before WAL/trace/conf consumers.
                        batch = [e[0] if type(e) is tuple else e
                                 for e in batch]
                    if ov is not None:
                        ov.drained(g, n)
                    w_d.extend(batch)
                    r_g.append(g)
                    r_start.append(b0)
                    r_count.append(n)
                    r_term.append(tm)
                    if traced is not None:
                        traced.append((g, b0, batch))
                    if confs is not None:
                        # Conf entries entering the cluster log — one
                        # leading-byte test per accepted proposal, only
                        # with membership enabled.
                        for off, d in enumerate(batch):
                            if d[:1] == _CONF_PREFIX \
                                    and is_conf_entry(d):
                                confs.append((g, b0 + off, d))
        if confs:
            for (cg, cidx, cd) in confs:
                self._conf_note(cg, cidx, cd)
        if took.any():
            n_acc = int(accs[took].sum())
            self.metrics.proposals += n_acc
            self._intake[3] += n_acc
            # Per-group traffic: the accepted counts are already in
            # hand per group — one add, no new walks.
            self.traffic.add_propose(gs[took], accs[took])
        if traced:
            # Append stamp + index binding, outside the lock.
            for g, b0, batch in traced:
                self.tracer.note_append(
                    g, b0, [d.decode("utf-8", "replace")
                            for d in batch])
        return out

    def _mirror_keep(self, infos: np.ndarray
                     ) -> Tuple[np.ndarray, Tuple[np.ndarray, ...]]:
        """A dispatch's accepted appends, from its packed info
        [S, P, G, C]: how many `(peer, group)` took one at each step
        ([S]), and the (steps, peers, groups) index arrays of those
        that go to the mirror, step-major, then peer-major, groups
        ascending: the ones that carry an entry.  An EMPTY accepted
        append (a heartbeat's ack: every follower of a steady group,
        every step) can change no log.  On the device (core/step.py
        Phase 4) `a_n == 0` gives no overlap, so no conflict, and
        `log_len = max(log_len, prev)` with `prev <= log_len` by
        `prev_ok`: its `new_log_len` is the length the log had, which
        is the payload log's (the mirror of the device's log).  On the
        host such a row writes no record, puts nothing and truncates
        to the length that is there.  tests/test_mirror_rows.py holds
        that, row by dropped row, step by step.  `app_n` is 0 wherever
        no append was accepted.  Nothing more of [P, G] size than
        this, and ONE pass over the block for all its steps: what a
        numpy call on a column costs the tick thread of a served
        engine is in PERF.md (PR 29), and a dispatch of S steps would
        pay a pass a step S times (found flat: a 2-D `np.nonzero` over
        [P, G] costs five times a flat one)."""
        shape = infos.shape[:3]
        n_took = np.count_nonzero(
            (infos[..., _C["app_from"]] >= 0).reshape(shape[0], -1),
            axis=1)
        at = np.flatnonzero(infos[..., _C["app_n"]])
        return n_took, np.unravel_index(at, shape)

    def _durable_phases(self, step_infos, staged: list) -> bool:
        """The durable host phases of ONE dispatch, from its packed
        info [S, P, G, C] (or a sequence of S [P, G, C]) and the S
        write plans `_stage_ranges` made, one straight run.  Phase 1
        (plan), once for all the steps, collects mirror METADATA
        (step, peer, src, group, start, count, new_len) with no reads,
        of the accepted appends that can change a log (_mirror_keep).
        Then step by step: phase 2a writes leader appends
        (fresh-leader no-ops + accepted proposals, pre-popped into
        `staged` by _stage_ranges) as uniform-term RANGES into each
        leader's WAL and payload log, and phase 2b mirrors follower
        appends in two passes: ALL source reads, then one batched
        payload-log write and one WAL append per destination peer.

        The source reads happen inside 2b AFTER 2a's appends — safe
        because 2a writes are pure TAIL appends strictly above any
        mirrored range (mirror ranges were composed from the source's
        ring at the end of the PREVIOUS step), and the only same-step
        writes that can truncate or overwrite a mirrored range are
        OTHER MIRRORS (a group's old leader accepts from its new one,
        with truncation, in the step in which another peer still
        mirrors from it), which 2b reads in full before it writes any.
        Any future 2a change that is not a pure tail append breaks this
        argument and must move 2a after 2b's reads.

        After the last step's entries, phase 2c (hard states: one
        compare for all peers of the FINAL step's info against what
        the WALs hold, then one batched record write a peer that has a
        changed row) and the per-peer fsync barrier run — a multi-step
        dispatch saves every step's entries, then one hard state, then
        one fsync, which is the etcd wal.Save order at dispatch
        granularity.
        Returns tick_active (entries or hard states written)."""
        P = self.cfg.num_peers
        import time as _t
        # The phase's parts, each a span on the profiler's timeline
        # while a session runs and, summed into _wal_split, a phase of
        # phase_profile (recorded by _finish_durable): wal_plan,
        # wal_append, wal_hardstate.
        ann = self._ann
        counting = self.prof is not None
        ptick = self._prof_tick
        split = self._wal_split
        ta = _t.monotonic()
        with span(ann, "tick.wal_plan", ptick):
            # Only the accepted appends that can change a log become
            # rows, picked out BEFORE anything is listed.
            infos = np.asarray(step_infos)
            n_took, (k_step, k_peer, k_g) = self._mirror_keep(infos)
            a_peer, a_g = k_peer.tolist(), k_g.tolist()
            sub = infos[k_step, k_peer, k_g]            # [rows, C]
            a_src: List[int] = sub[:, _C["app_from"]].tolist()
            a_start: List[int] = sub[:, _C["app_start"]].tolist()
            a_count: List[int] = sub[:, _C["app_n"]].tolist()
            a_newlen: List[int] = sub[:, _C["new_log_len"]].tolist()
            # The rows of step s: [step_at[s], step_at[s + 1]).
            step_at = np.searchsorted(
                k_step, np.arange(len(infos) + 1)).tolist()
            took = int(n_took.sum())
            if counting and took:
                # wal.records / wal.groups_written: mirrored entries
                # (an empty heartbeat ack mirrors none); the ranges the
                # mirror is handed, and the appends that made none.
                self._wal_mirror[0] += len(a_peer)
                self._wal_mirror[2] += took - len(a_peer)
                self._wal_records += sum(a_count)
                self._wal_groups.update(
                    [g for g, c in zip(a_g, a_count) if c])

            if self.tracer is not None and a_peer:
                # Replicate stamp: the mirrored range lands in a
                # follower's log this dispatch (first stamp wins per
                # index).
                for g, st, c in zip(a_g, a_start, a_count):
                    if c:
                        self.tracer.note_replicate(g, st + c - 1)

            if self.witness_peers and a_peer:
                # Witnesses never lead, so every entry they persist
                # arrives here as a mirrored follower append.
                self.metrics.witness_appends += sum(
                    c for p, c in zip(a_peer, a_count)
                    if c and p in self.witness_peers)

        # Any accepted append, empty or not, keeps the tick active.
        tick_active = bool(took)
        tb = _t.monotonic()
        split[0] += tb - ta
        with span(ann, "tick.wal_append", ptick):
            for si, st_plan in enumerate(staged):
                # Phase 2a: leader appends as uniform-term RANGE
                # records — one framed record per (group, start, term)
                # run, not one per entry.  The write plan was staged
                # (and the payloads popped) by _stage_ranges.
                for p in range(P):
                    r_g, r_start, r_count, r_term, w_d = st_plan[p]
                    if not r_g:
                        continue
                    tick_active = True
                    if counting:
                        self._wal_records += sum(r_count)
                        self._wal_groups.update(r_g)
                    self._ensure_epoch_begin(p)
                    self.wals[p].append_ranges(r_g, r_start, r_count,
                                               r_term, w_d)
                    puts = []
                    pos = 0
                    for g, s, c, tm in zip(r_g, r_start, r_count,
                                           r_term):
                        puts.append((g, s, w_d[pos: pos + c], [tm] * c,
                                     None))
                        pos += c
                    self.plogs[p].put_ranges(puts)
                # Phase 2b: the step's mirror.  ALL source reads first
                # (the staging contract), then one batched write per
                # peer.
                lo, hi = step_at[si], step_at[si + 1]
                if hi > lo:
                    self._mirror(a_peer[lo:hi], a_src[lo:hi],
                                 a_g[lo:hi], a_start[lo:hi],
                                 a_count[lo:hi], a_newlen[lo:hi])
        tc = _t.monotonic()
        split[1] += tc - tb
        # Phase 2c: hard states after every ENTRY record of the
        # dispatch (etcd wal.Save order: a torn tail can then never
        # leave a hard state referencing lost entries), found by one
        # compare for all peers, then the per-peer fsync that is the
        # durable barrier before the next dispatch.
        with span(ann, "tick.wal_hardstate", ptick):
            tick_active = self._save_hard(step_infos[-1]) or tick_active
            if self._ep_active:
                for p in range(P):
                    if self._ep_begun[p]:
                        self.wals[p].epoch_mark(self._ep_no_this,
                                                end=True)
        # The durable barrier: every peer fsynced before this tick's
        # messages can be observed (the next dispatch).  The P fsyncs
        # are independent files — run them concurrently (os.fsync and
        # the native wal_sync both release the GIL), so the barrier
        # costs one fsync wall-time, not P.  A peer with nothing
        # pending returns immediately.
        tf0 = _t.monotonic()
        split[2] += tf0 - tc
        with span(ann, "tick.fsync", ptick):
            list(self._sync_pool.map(lambda w: w.sync(), self.wals))
        self._fsync_span = (tf0, _t.monotonic() - tf0)
        return tick_active

    def _mirror(self, m_peer, m_src, m_g, m_start, m_count,
                m_newlen) -> None:
        """Phase 2b for one step's mirror rows (parallel lists, peer-
        major): every source range read before any destination is
        written, then one batched payload-log write and one WAL append
        per destination peer."""
        if self.prof is not None:
            self._wal_mirror[1] += len(m_peer)
        for p in sorted(set(m_peer)):
            self._ensure_epoch_begin(p)
        floor_of = [pl.starts for pl in self.plogs]
        if any(c and st <= floor_of[s][g]
               for (s, g, st, c) in zip(m_src, m_g, m_start, m_count)):
            self._trim_mirror(m_peer, m_src, m_g, m_start, m_count)
        reads = [self.plogs[s].slice_columns(g, st, c)
                 if c else ([], [])
                 for (s, g, st, c) in zip(m_src, m_g, m_start, m_count)]
        for p in range(self.cfg.num_peers):
            b_g: List[int] = []
            b_start: List[int] = []
            b_count: List[int] = []
            b_terms: List[int] = []
            b_d: List[bytes] = []
            puts = []
            for (mp, g, st, c, nl), (terms, datas) in zip(
                    zip(m_peer, m_g, m_start, m_count, m_newlen), reads):
                if mp != p:
                    continue
                puts.append((g, st, datas, terms, nl))
                if c:
                    b_g.append(g)
                    b_start.append(st)
                    b_count.append(c)
                    b_terms.extend(terms)
                    b_d.extend(datas)
            if puts:
                self.plogs[p].put_ranges(puts)
            if b_g:
                # Mirrored batches may cross term boundaries; RANGE
                # records are uniform-term, so split each mirror at its
                # term changes (rare: elections).
                s_g: List[int] = []
                s_start: List[int] = []
                s_count: List[int] = []
                s_term: List[int] = []
                pos = 0
                for g, st0, c in zip(b_g, b_start, b_count):
                    for (rs, rc, rt) in split_uniform_runs(
                            st0, b_terms[pos: pos + c]):
                        s_g.append(g)
                        s_start.append(rs)
                        s_count.append(rc)
                        s_term.append(rt)
                    pos += c
                self.wals[p].append_ranges(s_g, s_start, s_count,
                                           s_term, b_d)

    def _scrub_conf(self, g: int, base: int, datas: list) -> list:
        """Blank conf entries out of a publish batch (entries at
        base+1..): the apply plane sees an empty slot where the
        membership change sat.  Index-driven off the scrub set — zero
        per-entry work; `_conf_scrub[g]` is replaced (never mutated) so
        the async publish workers can read it lock-free."""
        scrub = self._conf_scrub[g]
        if scrub:
            top = base + len(datas)
            for idx in scrub:
                if base < idx <= top:
                    datas[idx - base - 1] = b""
        return datas

    def _publish(self, pinfo: np.ndarray) -> int:
        """Deliver a saved tick's newly committed entries to every
        peer's commit stream, across ALL group shards (the inline /
        serial-host path; the async path fans the same pinfo out to the
        per-shard workers instead).  Returns what the shards return,
        summed."""
        return sum(self._publish_shard(pinfo, shard)
                   for shard in range(len(self._shard_groups)))

    def _publish_shard(self, pinfo: np.ndarray, shard: int) -> int:
        """Deliver one group shard's newly committed entries to each
        peer's commit stream (they were fsynced before this runs) — the
        whole tick's block as ONE RAW_MANY queue item per peer.
        Returns the groups of the shard in which peer 0, the
        client-facing stream, had commits to deliver (publish.groups)."""
        gsel = self._shard_groups[shard]
        n_groups = 0
        for p in range(self.cfg.num_peers):
            col = pinfo[p]
            commit = col[:, _C["commit"]]
            if gsel is None:
                ready = np.nonzero(commit > self._applied[p])[0]
            else:
                ready = gsel[commit[gsel] > self._applied[p][gsel]]
            if not ready.size:
                continue
            if p == 0:
                n_groups = ready.size
                if self.tracer is not None:
                    # Quorum/commit stamp on the client-facing stream.
                    for g, c in zip(ready.tolist(),
                                    commit[ready].tolist()):
                        self.tracer.note_commit(g, int(c))
            if (self.publish_peers is not None
                    and p not in self.publish_peers) \
                    or p in self.witness_peers:
                # Nobody consumes this peer's stream (or it is a
                # witness, which never applies): advance the cursor
                # without materializing anything.
                if p == 0:
                    deltas = commit[ready] - self._applied[p][ready]
                    self.traffic.add_commit(ready, deltas)
                    self._note_commits(int(deltas.sum()))
                self._applied[p][ready] = commit[ready]
                continue
            plog = self.plogs[p]
            gl = ready.tolist()
            cl = commit[ready].tolist()
            al = self._applied[p][ready].tolist()
            items = []
            sl = plog.slice
            for g, a, c in zip(gl, al, cl):
                datas = sl(g, a + 1, c - a)
                if len(datas) != c - a:
                    raise RuntimeError(
                        f"peer {p} g{g}: payload log shorter than "
                        f"commit ({a}+{len(datas)} < {c})")
                if self.membership is not None:
                    datas = self._scrub_conf(g, a, datas)
                items.append((g, a, datas))
            # All-empty ranges (a fresh leader's no-op, a scrubbed conf
            # entry) are delivered too: the consumer applies nothing
            # for them but must learn that the stream passed their
            # index (runtime/db.py RaftDB._delivered), or a read whose
            # target is that index waits for ever.
            self._commit_qs[p].put((RAW_MANY, items))
            self._applied[p][ready] = commit[ready]
            if p == 0:
                deltas = commit[ready] - np.asarray(al)
                self.traffic.add_commit(ready, deltas)
                self._note_commits(int(deltas.sum()))
        return n_groups

    # -- log compaction (SURVEY §5.4) -----------------------------------

    def compact(self, applied=None, keep: int = 1024,
                covered=None) -> bool:
        """One compaction sweep over every peer: payload-log prefixes
        drop, COMPACT markers land in the WALs, and fully-superseded
        closed segments unlink (storage/wal.py compact) — the
        memory-bound story for sustained load (the reference's
        MemoryStorage grows forever, raft.go:129).  True if a floor
        moved.

        WHO RUNS IT.  The tick thread, between two durable phases: it
        alone writes the WALs, the payload logs and `_hard`, so the
        sweep takes no lock against it and needs none.  A caller that
        drives `tick()` itself (tests, the chaos runners, the soak)
        calls this between two ticks.  Any other thread (the apply
        thread, runtime/db.py `_maybe_compact`) calls
        `request_compact`, and the next tick runs the sweep inside its
        dispatch window, where the previous tick's durable phase is
        down and its own has not begun.  The publish workers and the
        state machines move their cursors meanwhile: the sweep reads
        each once, as arrays, and a value a moment old is only a lower
        floor.

        THE FLOORS, one pass over [P, G] arrays, no walk over groups:
        a group's floor on peer p is `min(publish cursor, applied) -
        keep`.  `keep` is clamped to >= log_window so every index the
        device ring can still reference stays servable (mirror reads
        and in-window resends).  The publish cursor gates the floor:
        only entries already delivered to the apply plane are dropped.
        `applied` ([G] array) tightens it to the indexes the state
        machines have applied AND put on disk (models/store.py
        `synced`: a commit alone is not synced, and what this sweep
        unlinks no replay brings back) — the calling convention
        RaftDB's snapshot-driven compaction uses, so the --fused
        --resume --compact-every deployment works.

        WHAT EVERY PEER HOLDS GOES, whatever `keep` says: a group's
        floor is at least the highest index F that is in every peer's
        durable log, at or below every peer's durable commit index and
        publish cursor, and covered by the state machine (`covered`,
        [G]: what the apply plane has on disk, or has been delivered
        through where it has all it applied on disk; the publish
        cursor where no state machine gates).
        For a quiet group that is its last index.  Nothing is left
        that a later step could ask this host for at or below F: every
        peer holds the same committed prefix through F durably, so by
        log matching no append with a previous index at or below F is
        ever rejected, a reject's hint is never below the rejecting
        peer's length, a leader's next index never walks below its
        match index + 1, and a new leader starts at its own length + 1;
        the one thing that can still name an index at or below F is a
        batch re-sent before its ack was seen, which the receiver
        already holds: the mirror trims such a read at the source's
        floor (_trim_mirror).  A peer that lags holds F down to its
        own length: nothing it lacks is ever dropped.  Without this
        rule a node of mostly quiet groups never unlinks a segment: a
        group with fewer than `keep` entries has no floor, the first
        segment holds every group's election no-op, and the WAL stops
        at the first segment it cannot delete.  CockroachDB truncates a
        range's log to its committed index once every follower has it;
        this is that rule.
        """
        import time as _t
        t0 = _t.monotonic()
        P, G = self.cfg.num_peers, self.cfg.num_groups
        keep = max(keep, self.cfg.log_window)
        starts = np.stack([pl.starts for pl in self.plogs])      # [P, G]
        lens = np.stack([pl.lengths for pl in self.plogs])
        floors, held = self._sweep_floors(
            self._applied, starts, lens, self._hard[:, :, 2], applied,
            covered, keep)
        floors = np.maximum(floors, held[None, :])
        pp, gg = np.nonzero(floors > starts)
        moved = int(pp.size)
        ff = floors[pp, gg]
        by_peer = np.searchsorted(pp, np.arange(P + 1))
        marks: List[Dict[int, Tuple[int, int]]] = []
        for p in range(P):
            lo, hi = by_peer[p], by_peer[p + 1]
            gl, fl = gg[lo:hi].tolist(), ff[lo:hi].tolist()
            terms = self.plogs[p].compact_many(gl, fl)
            marks.append({g: (f, t) for g, f, t in zip(gl, fl, terms)})
        # The WALs are asked every sweep, floors moved or not: a
        # segment can have become deletable by CLOSING (the one that
        # holds a sweep's re-asserted markers is, the moment it does),
        # and a crash may have left doomed segments behind.
        deleted = self._wal_compact(marks)
        self.metrics.compactions += 1           # = compact.sweeps
        if self.prof is not None:
            self.prof.stage("compact.sweep", _t.monotonic() - t0)
            self.prof.count((("compact.sweeps", 1),
                             ("compact.floors_advanced", moved),
                             ("wal.segments_unlinked", deleted)))
        return bool(moved or deleted)

    @staticmethod
    def _sweep_floors(pub, starts, lens, commit, applied, covered,
                      keep: int):
        """([P, G] floors by the keep rule, [G] highest index every
        peer holds, committed, published and covered) from the arrays
        a sweep reads: the publish cursors, the payload logs' floors
        and lengths, the durable commit indexes, all [P, G], and the
        state machines' [G] `applied` / `covered` (None: no state
        machine gates, the publish cursor does)."""
        if applied is None:
            gate = pub
        else:
            gate = np.minimum(pub, applied[None, :])
            if covered is None:
                covered = applied
        held = np.minimum(np.minimum(lens, commit), pub).min(axis=0)
        if covered is not None:
            held = np.minimum(held, covered)
        return gate - keep, held

    def _wal_compact(self, marks: List[Dict[int, Tuple[int, int]]]
                     ) -> int:
        """Hand each peer's moved floors {group: (floor, term)} to the
        WALs; returns the segments unlinked.  The hard states a WAL
        must re-assert are looked up for the groups its doomed
        segments name, nobody else's."""
        G = self.cfg.num_groups
        if self._gcwal is not None:
            # One shared log: every peer's floors in one call, by flat
            # group id (peer * G + group).
            flat = {p * G + g: v for p, m in enumerate(marks)
                    for g, v in m.items()}
            hard = self._hard

            def hard_of(names):
                flat_ids = np.asarray(names, np.int64)
                rows = hard[flat_ids // G, flat_ids % G]
                return rows[:, 0], rows[:, 1], rows[:, 2]
            return self._gcwal.compact(flat, hard_of)
        deleted = 0
        for p, m in enumerate(marks):
            hp = self._hard[p]

            def hard_of(names, hp=hp):
                rows = hp[np.asarray(names, np.int64)]
                return rows[:, 0], rows[:, 1], rows[:, 2]
            deleted += self.wals[p].compact(m, hard_of)
        return deleted

    def request_compact(self, applied, covered, keep: int) -> None:
        """Ask the tick thread for a sweep (any thread; the newest
        request wins).  `applied` and `covered` are the [G] arrays of
        compact(); they are read when the sweep runs."""
        self._compact_req = (applied, covered, keep)

    def _run_compact_request(self) -> None:
        applied, covered, keep = self._compact_req
        self._compact_req = None
        with span(self._ann, "tick.compact", self._tick_no):
            self.compact(applied, keep, covered)

    def _trim_mirror(self, m_peer, m_src, m_g, m_start, m_count) -> None:
        """Cut the mirror rows that reach at or below their SOURCE's
        compaction floor back to what lies above it (in place).  Only a
        floor set to what every peer holds can be reached (compact():
        the keep rule stays a ring window below any index the device
        can name), and every peer held the log through that floor
        before it was set: such a row is a batch re-sent before its
        ack was seen, and its receiver has the entries.  A receiver
        that has not is a fault: stop, as the slice's own floor check
        did."""
        for i, (p, s, g, st, c) in enumerate(zip(
                m_peer, m_src, m_g, m_start, m_count)):
            floor = self.plogs[s].start(g)
            if not c or st > floor:
                continue
            have = self.plogs[p].length(g)
            if have < min(st + c - 1, floor):
                raise RuntimeError(
                    f"peer {p} g{g}: append from peer {s} at {st} "
                    f"reaches below the source's floor {floor} and "
                    f"the receiver's log ends at {have}")
            m_start[i] = floor + 1
            m_count[i] = max(st + c - 1 - floor, 0)

    def wal_gauges(self) -> Tuple[int, int]:
        """(bytes of the WAL segments that exist, closed segments the
        last sweep had to leave), over every WAL this plane writes;
        kept by the WALs as they rotate and unlink (no directory is
        listed for a scrape)."""
        ws = [self._gcwal.base] if self._gcwal is not None else self.wals
        return (sum(w.disk_bytes() for w in ws),
                sum(w.segments_pinned for w in ws))

    # -- teardown -------------------------------------------------------

    def stop(self) -> None:
        if self._thread is not None:
            self._stop_evt.set()
            self._work_evt.set()
            # No timeout: until the tick thread has returned it owns the
            # WALs, the sync pool and the publish queues, and tearing
            # any of them down under a running tick turns a clean stop
            # into an engine failure (at G=10k one tick outlasts any
            # small grace).  The loop re-checks _stop_evt every tick.
            self._thread.join()
            self._thread = None
        if self._pending_pinfo is not None:
            self._prof_tick = self._pending_tick
            self._enqueue_publish(self._pending_pinfo)  # already durable
            self._pending_pinfo = None
        for q in self._pub_qs:
            q.put(None)                       # drain, then retire
        for th in self._pub_threads:
            th.join(timeout=10)
        self._sync_pool.shutdown(wait=True)
        if self._epoch_f is not None:
            self._epoch_f.close()
            self._epoch_f = None
        for w in self.wals:
            w.close()
        for q in self._commit_qs:
            q.put(CLOSED)

    # -- introspection (tests) -----------------------------------------

    def roles(self) -> np.ndarray:
        """[P, G] role matrix from the live device state."""
        return np.asarray(self.states.role)
