"""RaftDB — apply-side state machine driver with ack routing.

Re-design of the reference's `raftdb` (reference db.go:13-167), batched
over groups:

  - consumes the commit stream and applies each committed command to the
    group's state machine in commit order (db.go:45-57);
  - routes per-proposal acks back to waiting clients by *query identity*:
    a FIFO of callbacks per (group, query); duplicate identical queries
    queue multiple callbacks and the first commit acks the head — the
    reference's exact quirk, preserved (db.go:63-76, 112-118, SURVEY.md
    §2d.3).  Commits originating from replay or other nodes have no
    callback and are skipped (db.go:64-69);
  - write/read split: Propose rejects SELECT, Query requires SELECT
    (db.go:98-110, 123-126);
  - local non-linearizable reads (db.go:128-130);
  - on consensus error, every pending ack receives the error and the DB
    shuts down (db.go:83-95);
  - the constructor consumes the replay stream synchronously until the
    `None` sentinel before returning, so the state machine is caught up to
    the WAL before serving (db.go:40, SURVEY.md §3.1 handshake), then a
    reader thread consumes live commits (db.go:41).

The optional commit listener mirrors every applied commit (and the replay
sentinel) to tests — the reference's `commitListenerC` observability hook
(db.go:19, 48-50, 59-61), which its restart tests depend on.
"""
from __future__ import annotations

import json
import logging
import os
import queue
import threading
import time
from collections import defaultdict, deque
from concurrent.futures import ThreadPoolExecutor, wait
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from raftsql_tpu.models.base import StateMachine
from raftsql_tpu.models.store import StateMachineStore
from raftsql_tpu.models.sqlite_sm import is_select
from raftsql_tpu.native.build import load_native_apply
from raftsql_tpu.overload import (Overloaded, deadline_steps,
                                  zero_metrics_doc)
from raftsql_tpu.runtime.envelope import unwrap
from raftsql_tpu.runtime.errors import (NotLeaderError,  # noqa: F401
                                        ReadTimeout)
from raftsql_tpu.transport.codec import is_conf_entry
from raftsql_tpu.runtime.node import (CLOSED, RAW_BATCH, RAW_MANY,
                                      RAW_PLAIN)
from raftsql_tpu.runtime.pipe import RaftPipe
from raftsql_tpu.utils.device import device_doc
from raftsql_tpu.utils.metrics import LatencyTimer

log = logging.getLogger("raftsql_tpu.db")

# Width of the apply pool: the groups of one drained run apply side by
# side on this many threads (RaftDB._apply_run).  A file-backed SQLite
# machine's transaction is one native call that drops the interpreter
# once (models/sqlite_sm.py): 3.8-5.3 ms in a served engine, the file
# system's system calls and one wait to win the interpreter back from
# the tick, WAL and ring threads (PERF.md, PR 35; 30-48 ms while it was
# a dozen `sqlite3` calls, PR 27).  The waits of different groups
# overlap; the statements' own work is small.
APPLY_WORKERS = min(8, os.cpu_count() or 1)


def iter_plain_entries(base, datas):
    """Yield (index, decoded_command) for each non-empty entry of one
    plain-payload sub-batch (entries at base+1..).  Lives next to
    _expand_commit_item so the plain wire contract (index base,
    empty-entry skip, utf-8 payloads) has exactly one owner; hot
    consumers (the durable benchmark's drain) use this instead of
    building per-entry (group, index, str) tuples."""
    idx = base
    for d in datas:
        idx += 1
        if d:
            yield idx, d.decode("utf-8")


def iter_plain_batches(item):
    """Yield (group, base_idx, [raw_bytes, ...]) sub-batches of a
    plain-payload commit item — one batch for RAW_PLAIN, the whole
    tick's batches for RAW_MANY.  Same single-owner rationale as
    iter_plain_entries; payloads follow the plain contract (no
    envelopes, empty bytes = no-op entries the consumer skips)."""
    if item[0] is RAW_PLAIN:
        yield item[1], item[2], item[3]
    elif item[0] is RAW_MANY:
        yield from item[1]


def _expand_commit_item(item, node=None, dups=None):
    """Normalize a commit_q item to per-entry (group, index, sql) tuples.

    `dups` (optional list) collects (group, index, sql) for committed
    entries the dedup window SKIPPED — a client-retried or
    forward-retried duplicate that already applied.  The caller must
    still ACK those by query identity (the retry's client is waiting on
    this very commit; without the ack a PUT retried across a crash
    would hang forever even though its first copy applied).

    Four forms, discriminated explicitly:
      - (RAW_BATCH, group, base_idx, [raw_bytes, ...]) — the live
        publish phase's tagged batch (entries at base_idx+1..): one
        queue put per group per tick, with the per-entry envelope
        unwrap / dedup / utf-8 decode done HERE, on the consumer
        thread, off the tick's critical path (`node.dedup_for(g)`
        supplies the per-group DedupWindow — forward-retried
        duplicates apply exactly once);
      - (RAW_PLAIN, group, base_idx, [raw_bytes, ...]) — same shape,
        but payloads are PLAIN (never enveloped): only producers whose
        proposals bypass the wrap/forward path may emit it (the
        fused/mesh runtimes, which route proposals on the host).
        Tagging wrapped payloads RAW_PLAIN would apply entries with
        envelope header bytes prepended;
      - (RAW_MANY, [(group, base_idx, [raw_bytes, ...]), ...]) — a
        whole fused tick's RAW_PLAIN batches in one queue item (same
        plain-payload contract);
      - (group, index, sql_str) — WAL replay per-entry items (the
        nil-sentinel counting protocol must stay item-accurate there);
      - (group, [(index, sql), ...]) — decoded per-group batches (older
        producers/tests).
    """
    if item[0] is RAW_BATCH:
        _, g, base, datas = item
        dedup = node.dedup_for(g) if node is not None else None
        out = []
        for off, data in enumerate(datas):
            if not data or is_conf_entry(data):
                continue                    # no-op/conf entry
            pid, payload = unwrap(data)
            if pid is not None and dedup is not None \
                    and dedup.seen(pid, base + 1 + off):
                if dups is not None:        # retry duplicate: ack, no apply
                    dups.append((g, base + 1 + off,
                                 payload.decode("utf-8")))
                continue
            out.append((g, base + 1 + off, payload.decode("utf-8")))
        return out
    if item[0] is RAW_PLAIN:
        _, g, base, datas = item
        return [(g, base + 1 + off, data.decode("utf-8"))
                for off, data in enumerate(datas)
                if data and not is_conf_entry(data)]
    if item[0] is RAW_MANY:
        return [(g, base + 1 + off, data.decode("utf-8"))
                for (g, base, datas) in item[1]
                for off, data in enumerate(datas)
                if data and not is_conf_entry(data)]
    if len(item) == 2:
        g = item[0]
        return [(g, i, s) for (i, s) in item[1]]
    if len(item) == 3 and isinstance(item[2], str):
        return [item]
    raise TypeError(f"unrecognized commit_q item shape: {item!r:.120}")


def _commit_item_tops(item):
    """Yield (group, highest_log_index) for every batch of a commit_q
    item, COUNTING the entries _expand_commit_item drops (no-ops,
    scrubbed conf entries): the index up to which the stream has
    delivered the group's log — see RaftDB._delivered."""
    if item[0] is RAW_BATCH or item[0] is RAW_PLAIN:
        yield item[1], item[2] + len(item[3])
    elif item[0] is RAW_MANY:
        for (g, base, datas) in item[1]:
            yield g, base + len(datas)
    elif len(item) == 2:
        if item[1]:
            yield item[0], item[1][-1][0]
    else:
        yield item[0], item[1]


def _apply_group(store: StateMachineStore, group: int,
                 items: list) -> Tuple[list, float, bool]:
    """One group's batch of a run on its state machine: the error list
    (one Optional[Exception] per item), the wall time it took, measured
    inside the thread that ran it, the way to the group's handle
    included (its first use opens it), and whether the machine's one
    native call committed it (models/sqlite_sm.py `last_native`).  Runs
    on the reader thread or on an apply worker, so it touches nothing
    but the store."""
    t0 = time.monotonic()
    with store.use(group) as sm:
        batch_fn = getattr(sm, "apply_batch", None)
        if batch_fn is not None:
            errs = batch_fn(items)
        else:
            errs = [sm.apply(qy, ix) for (qy, ix) in items]
        native = getattr(sm, "last_native", False)
    return errs, time.monotonic() - t0, native


class AckFuture:
    """The reference's buffered `chan error` (db.go:107): one result,
    delivered once, awaited by one client."""

    def __init__(self):
        self._evt = threading.Event()
        self._err: Optional[Exception] = None
        self._cb = None
        self._cb_mu = threading.Lock()
        self.created = time.monotonic()

    def set(self, err: Optional[Exception]) -> None:
        self._err = err
        self._evt.set()
        with self._cb_mu:
            cb, self._cb = self._cb, None
        if cb is not None:
            cb(err)

    def wait(self, timeout: Optional[float] = None) -> Optional[Exception]:
        if not self._evt.wait(timeout):
            raise TimeoutError("proposal not committed in time")
        return self._err

    def add_done_callback(self, cb) -> None:
        """Deliver the result to `cb(err)` instead of (or in addition
        to) a blocking wait() — the async API plane's bridge.  At most
        one callback; runs on the resolver's thread (the commit
        consumer), or immediately here if already resolved.  Called
        exactly once."""
        with self._cb_mu:
            if not self._evt.is_set():
                self._cb = cb
                return
        cb(self._err)


class RaftDB:
    def __init__(self, sm_factory: Callable[[int], StateMachine],
                 pipe: RaftPipe, num_groups: int = 1,
                 listener=None, resume: bool = False,
                 compact_every: int = 0, compact_keep: int = 1024,
                 existing=()):
        """resume=True enables snapshot-resume (SURVEY.md §5.4
        improvement): state machines that persist applied_index (see
        SQLiteStateMachine resume mode) skip re-apply of already-applied
        replayed entries, and — when compact_every > 0 — the WAL prefix
        covered by every group's snapshot is compacted away after every
        `compact_every` applies (retaining `compact_keep` entries for
        follower catch-up).  Default off: reference delete-and-replay
        parity (db.go:27-29).  `existing`: with resume, the groups
        whose state-machine file a former process left; their applied
        indexes are read off the files here, before anything asks
        (a group whose log was compacted away gets no replay that
        would open it)."""
        t_built = time.monotonic()
        self.pipe = pipe
        self.num_groups = num_groups
        self.listener = listener            # queue-like or None
        self.resume = resume
        self._compact_every = compact_every if resume else 0
        if compact_every and not resume:
            log.warning(
                "compact_every=%d can never act without resume: in "
                "parity mode the state machine is rebuilt from the "
                "whole log at boot, so no prefix of it can go",
                compact_every)
        self._compact_keep = compact_keep
        self._applies_since_compact = 0
        self._compactor: Optional[threading.Thread] = None
        # Witness replica (config.py quorum geometry): this node votes,
        # appends and fsyncs — but owns no SQLite shard.  The real
        # sm_factory is never invoked, so no shard file or directory is
        # ever created; committed payloads are discarded at apply time
        # (they are already durable in the WAL, which is all a witness
        # owes the cluster) and every read is refused up front.
        self.witness_self = bool(getattr(pipe.node, "witness_self",
                                         False))
        if self.witness_self:
            from raftsql_tpu.models.witness import WitnessStateMachine
            sm_factory = WitnessStateMachine
        # The state machines, made on first use and held open within
        # what RLIMIT_NOFILE allows (models/store.py): a group nobody
        # writes or reads costs a slot in an array, not a database.
        self.store = StateMachineStore(sm_factory, num_groups)
        if resume:
            self.store.seed(existing)
        prof = self._prof()
        if prof is not None:
            store = self.store
            prof.gauge_fn("sm.opens", lambda: store.opens)
            prof.gauge_fn("sm.closes", lambda: store.closes)
            prof.gauge_fn("sm.evictions", lambda: store.evictions)
            prof.gauge_fn("sm.open_handles", store.open_handles)
            prof.gauge_fn("sm.uses", lambda: store.uses)
            prof.gauge_fn("sm.misses", lambda: store.misses)
            prof.gauge_fn("sm.native_reopens", lambda: store.native_reopens)
            prof.gauge_fn("sm.python_reopens", lambda: store.python_reopens)
            store.prof = prof
        if resume:
            # Full state transfer for followers beyond the compaction
            # floor (InstallSnapshot) is only sound when re-apply is
            # snapshot-aware, so it rides the resume flag.
            pipe.node.snapshot_provider = self._snapshot_of
            pipe.node.snapshot_installer = self._install_snapshot
        self._mu = threading.Lock()
        # Highest log index the commit stream has delivered per group,
        # advanced only AFTER the run that carried it was applied.  A
        # no-op or a scrubbed conf entry carries no command, so the
        # state machine's applied index never covers it — but the
        # target of a linear or follower read (the commit index) does.
        # Without this mark a read on a group whose newest committed
        # entry is a fresh leader's no-op — every group, right after a
        # restart — waits for an apply that cannot happen.  Written by
        # the reader thread only; readers tolerate a stale (lower) value.
        self._delivered = np.zeros(num_groups, np.int64)
        self._q2cb: Dict[Tuple[int, str], deque] = defaultdict(deque)  # raftlint: guarded-by=_mu
        self._failed: Optional[Exception] = None
        self._closed = False
        self.latency = LatencyTimer()   # propose→ack, the p50 north star
        # Serving-plane gauge hook (runtime/ring.py RingServer): a
        # callable whose dict is merged into metrics() — ring depth,
        # proposed/completed counts of the multi-worker deployment.
        self.serving_metrics = None
        # Shared-memory snapshot publisher (runtime/shm.py), attached
        # by RingServer when the worker read fast path is on: every
        # applied run is mirrored into the worker-mapped snapshot log
        # (publish_deltas), snapshot installs republish the group's
        # base image.  None keeps the apply path untouched.
        self.shm = None
        # Read-replica stream server (raftsql_tpu/replica/), attached
        # by the server's --replica-listen flag: the shm publisher's
        # tee framed onto TCP for remote replicas.  None keeps the
        # engine inert; metrics() still exports the zeroed `replica`
        # section so the series exist from boot (scripts/check_prom.py
        # requires them).
        self.replica_plane = None
        # Placement controller (raftsql_tpu/placement/), attached by
        # the server's --placement flag; None keeps metrics() and
        # flight bundles unchanged.
        self.placement = None
        # Reshard plane (raftsql_tpu/reshard/plane.py), attached by the
        # server's --reshard flag: the elastic-keyspace coordinator +
        # keymap router.  None keeps /kv, /healthz and metrics()
        # unchanged (the plane compiles in but stays idle).
        self.reshard = None
        # propose→commit (stamped when the committed entry reaches the
        # apply consumer — commit + publish, before apply): the
        # histogram /metrics exports as propose_commit_p50/p95/p99_ms.
        self.latency_commit = LatencyTimer()
        # The apply workers (see _apply_run); threads start with the
        # first run that holds two groups, so a one-group deployment
        # never has any.
        self._apply_pool = ThreadPoolExecutor(
            max_workers=APPLY_WORKERS, thread_name_prefix="raftdb-apply")

        # Synchronous replay consumption (db.go:40): apply until the
        # sentinel so reads see the replayed state before we return.
        self._read_commits(replay=True)
        if prof is not None:
            # boot.replay_s: the node's constructor (where the WAL was
            # read) to here, the replay applied.
            replay_s = time.monotonic() - getattr(pipe.node, "t_born",
                                                  t_built)
            prof.gauge_fn("boot.replay_s", lambda: replay_s)
        self._reader = threading.Thread(target=self._read_commits,
                                        daemon=True, name="raftdb-reader")
        self._reader.start()

    # ------------------------------------------------------------------

    def _node_tracer(self):
        """The engine's span tracer, or None (tracing may be enabled
        after construction — resolve per use, it is one getattr)."""
        return getattr(getattr(self.pipe, "node", None), "tracer", None)

    def _prof(self):
        """The engine's telemetry plane (obs/prof.py: stages.put.*,
        stages.get.*), or None where RAFTSQL_PROF=0 or the engine has
        none."""
        return getattr(getattr(self.pipe, "node", None), "prof", None)

    def _ack_one(self, group: int, query: str, err,
                 commit_ts: Optional[float] = None,
                 acked: Optional[list] = None) -> None:
        if self.listener is not None:
            self.listener.put((group, query))
        tracer = self._node_tracer()
        if tracer is not None:
            tracer.note_ack(group, query)
        # Per-group traffic accounting (utils/metrics.py GroupTraffic):
        # the ack leg — proposes/commits are stamped in the host plane.
        traffic = getattr(self.pipe.node, "traffic", None)
        if traffic is not None:
            traffic.add_ack(group)
        with self._mu:
            cbs = self._q2cb.get((group, query))
            if not cbs:
                return                  # replayed or proposed elsewhere
            cb = cbs.popleft()
            if not cbs:
                del self._q2cb[(group, query)]
        cb.set(err)
        now = time.monotonic()
        self.latency.record(now - cb.created)
        if commit_ts is not None:
            # commit_ts is when this run was drained off the commit
            # queue — the commit observation point, before apply.
            self.latency_commit.record(commit_ts - cb.created)
            if acked is not None:
                # The same stamps as cumulative pairs, which a reader
                # can confine to a window (the rings above cannot be):
                # proposed -> commit observed -> applied and acked.
                # The caller hands its run's pairs over in one call.
                acked.append(("put.propose_commit",
                              commit_ts - cb.created))
                acked.append(("put.apply", now - commit_ts))

    def _apply_run(self, run) -> None:
        """Apply a drained run of commits with GROUP COMMIT: entries are
        batched per state machine and applied in one durable transaction
        each (models apply_batch; per-item fallback otherwise), then
        acks/listeners fire in original commit order.  In resume mode
        the state machine itself skips entries at or below its durable
        applied index (atomically under its own lock, racing snapshot
        installs safely) and returns None — so skipped-but-committed
        entries still resolve their acks.

        The groups of a run apply SIDE BY SIDE on the apply workers and
        are joined before anything else of the run happens: every group
        has its own state machine and lock, a run holds one batch a
        group and runs never overlap, so nothing orders them but this
        loop.  A run of one group (every run of a one-group deployment,
        and of the replay pass) applies here, with no hop."""
        commit_ts = time.monotonic()    # commit observation point
        per_g: Dict[int, list] = defaultdict(list)
        for (group, index, query) in run:
            per_g[group].append((query, index))
        fanout = len(per_g) > 1
        if fanout:
            futs = [self._apply_pool.submit(_apply_group, self.store, g,
                                            items)
                    for g, items in per_g.items()]
            wait(futs)                  # the barrier: all, then the rest
            done = [f.result() for f in futs]   # a worker's raise, here
        else:
            done = [_apply_group(self.store, g, items)
                    for g, items in per_g.items()]
        errs: Dict[int, list] = {
            g: d[0] for g, d in zip(per_g, done)}
        if self.shm is not None:
            # Mirror the applied run into the worker-mapped snapshot
            # log BEFORE acks fire: a client whose PUT just acked may
            # immediately session-read at a worker, and the worker's
            # replica must be able to reach that watermark.  Statements
            # that errored are published too — workers re-apply them
            # under the same SAVEPOINT semantics, so replica state
            # stays bit-identical to the engine's.
            try:
                self.shm.publish_deltas(per_g)
            except Exception:                           # noqa: BLE001
                log.exception("shm delta publish failed; disabling")
                self.shm = None
        tracer = self._node_tracer()
        prof = self._prof()
        acked: Optional[list] = None if prof is None else [
            ("put.apply_batch", d[1]) for d in done]
        pos = {g: 0 for g in per_g}
        for (group, index, query) in run:
            err = errs[group][pos[group]]
            pos[group] += 1
            if tracer is not None:
                tracer.note_apply(group, index)
            self._ack_one(group, query, err, commit_ts, acked)
        if acked:
            prof.stage_many(acked)
            native = sum(d[2] for d in done)
            prof.count((("apply.runs", 1), ("apply.groups", len(per_g)),
                        ("apply.fanout_runs", int(fanout)),
                        ("apply.native_txns", native),
                        ("apply.python_txns", len(done) - native)))
        for _ in run:
            self._maybe_compact()

    def _read_commits(self, replay: bool = False) -> None:
        q = self.pipe.commit_q
        while True:
            item = q.get()
            if item is None:
                if self.listener is not None:
                    self.listener.put(None)
                if replay:
                    return
                continue
            if item is CLOSED:
                break
            # Greedy drain (live loop only): everything already queued
            # joins this item's group-committed batch.  The replay pass
            # must stay strictly item-at-a-time — draining could swallow
            # live entries beyond the nil sentinel it returns at.
            # Items arrive per-entry (group, index, sql) from replay, or
            # as per-group RAW batches (group, base_idx, [bytes, ...])
            # from the live publish phase (runtime/node.py) — expanded
            # (unwrap/dedup/decode) HERE so the tick thread pays one
            # queue put per group and none of the per-entry Python.
            dups: list = []
            run = _expand_commit_item(item, self.pipe.node, dups)
            tops = list(_commit_item_tops(item))
            stop = False
            if not replay:
                while len(run) < 256:
                    try:
                        nxt = q.get_nowait()
                    except queue.Empty:
                        break
                    if nxt is None:
                        # Preserve the sentinel's position in the
                        # listener protocol relative to this run.
                        self._apply_run(run)
                        run = []
                        if self.listener is not None:
                            self.listener.put(None)
                        continue
                    if nxt is CLOSED:
                        stop = True
                        break
                    run.extend(_expand_commit_item(nxt, self.pipe.node,
                                                   dups))
                    tops.extend(_commit_item_tops(nxt))
            if run:
                self._apply_run(run)
            for g, top in tops:         # everything <= top is applied
                if top > self._delivered[g]:
                    self._delivered[g] = top
            if dups:
                # A committed RETRY duplicate: its first copy applied
                # (this run or earlier), so the retrying client's PUT
                # succeeded — ack success without re-applying.
                prof = self._prof()
                acked: Optional[list] = None if prof is None else []
                for (group, index, query) in dups:
                    self._ack_one(group, query, None, time.monotonic(),
                                  acked)
                if acked:
                    prof.stage_many(acked)
            if stop:
                break

        # Stream closed: clean shutdown or error teardown (db.go:83-95).
        err = self.pipe.error
        if err is not None:
            with self._mu:
                pending = [cb for cbs in self._q2cb.values() for cb in cbs]
                self._q2cb.clear()
                self._failed = err
            for cb in pending:
                cb.set(err)

    # ------------------------------------------------------------------

    def _snapshot_of(self, group: int):
        if self.store.applied_index(group) <= 0:
            # Nothing applied: there is no snapshot to hand out, so do
            # not open a database to find that out (at boot that is
            # every one of --groups groups, asked under the shm plane's
            # start while the tick thread competes for the interpreter).
            return None
        with self.store.use(group) as sm:
            fn = getattr(sm, "serialize_with_index", None)
            if fn is None:
                return None
            idx, blob = fn()
        return (idx, blob) if idx > 0 else None

    # Grace before failing acks orphaned by a snapshot install: commits
    # ABOVE the snapshot still publish normally and must keep their acks.
    SNAPSHOT_ACK_GRACE_S = 5.0

    def _install_snapshot(self, group: int, index: int,
                          blob: bytes) -> None:
        with self.store.use(group) as sm:
            sm.install(blob, index)
        if self.shm is not None:
            # A state transfer skipped the delta stream: workers must
            # rebuild their replica from the installed image, so the
            # group's base is republished into the snapshot log.
            try:
                self.shm.publish_base(group, blob, index)
            except Exception:                           # noqa: BLE001
                log.exception("shm base publish failed; disabling")
                self.shm = None
        # A state transfer SKIPS the log: proposals whose commits sit
        # INSIDE the snapshot are never published here, so their acks
        # would wait forever (the reference never snapshots and inherits
        # the hang only for lost proposals).  But a pending ack may also
        # belong to a commit ABOVE the snapshot — about to stream in and
        # ack normally — and the two are indistinguishable by (group,
        # query) key.  So: snapshot the exact callbacks pending NOW, give
        # the post-install catch-up a grace window to drain them, and
        # fail only the leftovers with a retriable error.  Hazard,
        # documented: a flushed write may in fact be inside the installed
        # state — a client retrying a non-idempotent statement should
        # verify first (same duplicate exposure as the reference's
        # content-keyed FIFO, db.go:112-118).
        with self._mu:
            stale = [(k, cb) for k, cbs in self._q2cb.items()
                     if k[0] == group for cb in cbs]
        if not stale:
            return
        err = RuntimeError(
            f"group {group}: pending proposal superseded by snapshot "
            f"install at index {index}; state may include the write — "
            "verify before retrying")

        def flush():
            victims = []
            with self._mu:
                for k, cb in stale:
                    cbs = self._q2cb.get(k)
                    if cbs and cb in cbs:
                        cbs.remove(cb)
                        if not cbs:
                            self._q2cb.pop(k, None)
                        victims.append(cb)
            for cb in victims:
                cb.set(err)

        t = threading.Timer(self.SNAPSHOT_ACK_GRACE_S, flush)
        t.daemon = True
        t.start()

    def _maybe_compact(self) -> None:
        if not self._compact_every:
            return
        self._applies_since_compact += 1
        if self._applies_since_compact < self._compact_every:
            return
        self._applies_since_compact = 0
        store = self.store
        if store.durable is None:
            return                      # no state machine made yet
        if not store.durable:
            # Volatile applied indexes must not gate WAL compaction:
            # compacting against state lost on restart would be silent
            # data loss (models/base.py contract).  Every floor would
            # be 0, for good: say so once and stop asking.
            log.warning(
                "compact_every=%d can never act: the state machines "
                "keep no durable applied index (no snapshot to compact "
                "the log under); compaction is off",
                self._compact_every)
            self._compact_every = 0
            return
        if self._closed or (self._compactor is not None
                            and self._compactor.is_alive()):
            return              # the last round is still on its way
        # Started before it is published: close(), on another thread,
        # joins whatever it finds here, and a thread can only be joined
        # once it has been started.
        round_ = threading.Thread(
            target=self._compact_round, name="raftdb-compact",
            daemon=True)
        round_.start()
        self._compactor = round_

    def _compact_round(self) -> None:
        """One compaction round, on a thread of its own (started by the
        apply thread, one at a time): put on disk what the state
        machines applied since the last round, then ask for the sweep.

        A sweep unlinks the raft log under a group's applied index, and
        a state machine commits without a sync: what a loss of power
        leaves of a file is its last checkpoint.  So every group with
        `applied > synced` is checkpointed first (models/store.py; its
        own applies and reads wait for that file's batch, nobody else's:
        the apply thread never waits for an fsync) and the sweep is
        handed `synced`, the indexes no power loss takes back, never
        `applied`.  A file a release put on disk is not opened again;
        the open ones go several at a time in one call
        (`StateMachineStore.checkpoint_round`)."""
        try:
            store = self.store
            prof = self._prof()
            t0 = time.monotonic()
            files = store.checkpoint_round(lambda: self._closed)
            if self._closed:
                return
            if prof is not None:
                prof.stage("compact.checkpoint", time.monotonic() - t0)
                prof.count((("compact.rounds", 1), ("compact.files", files)))
            # The apply thread moves on meanwhile.  `_delivered` is
            # written after the applies it stands for: read it FIRST,
            # so that where `applied` (read after) still equals
            # `synced`, every statement at or below what was delivered
            # is on disk, and what lies above `synced` there carries
            # none.
            delivered = self._delivered.copy()
            applied = store.applied.copy()
            synced = store.synced.copy()
            node = self.pipe.node
            ask = getattr(node, "request_compact", None)
            if ask is not None:
                # The co-located runtimes: the tick thread runs the
                # sweep (runtime/hostplane.py compact) with these and,
                # for what every peer already holds, how far the state
                # machine covers the log.
                covered = np.where(synced == applied,
                                   np.maximum(synced, delivered), synced)
                ask(synced, covered, self._compact_keep)
            else:
                written = np.flatnonzero(synced)
                node.compact(dict(zip(written.tolist(),
                                      synced[written].tolist())),
                             keep=self._compact_keep)
        except Exception:                               # noqa: BLE001
            # Nothing was dropped: the floors stay where they were.
            log.exception("compaction round failed; the log stays")

    def propose(self, query: str, group: int = 0,
                token: Optional[int] = None,
                deadline_ms: Optional[float] = None) -> AckFuture:
        """Submit a write; the future resolves after commit + local apply
        (the reference's blocking-PUT contract, httpapi.go:45-49).

        `token` (a client retry token, X-Raft-Retry-Token) pins the
        proposal's envelope id: a client re-sending the same logical
        PUT — after a timeout, a dropped connection, or a crashed
        leader — passes the same token and the publish-time dedup
        window applies whichever copies commit exactly once (the
        duplicate's commit still ACKS, it just doesn't re-apply).

        `deadline_ms` (remaining client budget, X-Raft-Deadline-Ms) is
        converted ONCE here from wall budget to a device-step deadline
        (raftsql_tpu/overload/ discipline) and carried with the queue
        entry, so work already expired at staging time is shed before
        WAL/fsync cost is paid.  Raises `Overloaded` (HTTP 429) when an
        attached admission controller refuses the enqueue; no-op when
        no overload plane is attached."""
        fut = AckFuture()
        if is_select(query):
            fut.set(ValueError("expected non-SELECT"))
            return fut
        if not 0 <= group < self.num_groups:
            fut.set(ValueError(f"group {group} out of range "
                               f"[0, {self.num_groups})"))
            return fut
        node = self.pipe.node
        dstep = None
        if deadline_ms is not None \
                and getattr(node, "overload", None) is not None:
            dstep = deadline_steps(node._device_steps, deadline_ms,
                                   node.step_interval_s)
        with self._mu:
            if self._failed is not None:
                fut.set(self._failed)
                return fut
            if self._closed:
                fut.set(RuntimeError("db is closed"))
                return fut
            self._q2cb[(group, query)].append(fut)
        try:
            if dstep is not None:
                self.pipe.propose(group, query.encode("utf-8"), token,
                                  deadline_step=dstep)
            else:
                self.pipe.propose(group, query.encode("utf-8"), token)
        except Overloaded:
            # Refused at the admission edge: nothing was enqueued, so
            # the ack callback must not linger in _q2cb.
            self.abandon(query, group, fut)
            raise
        return fut

    def abandon(self, query: str, group: int, fut: AckFuture) -> None:
        """Deregister a timed-out proposal's callback so it cannot leak in
        `_q2cb` forever (the proposal itself may still commit later; its
        apply is unaffected — only the ack is orphaned)."""
        with self._mu:
            cbs = self._q2cb.get((group, query))
            if cbs is None:
                return
            try:
                cbs.remove(fut)
            except ValueError:
                return
            if not cbs:
                del self._q2cb[(group, query)]

    def pending_for(self, group: int) -> int:
        """Acks still outstanding for `group` — the reshard drain gate:
        a frozen slot's verb may not copy rows until every write that
        was in flight at freeze time either acked or errored."""
        with self._mu:
            return sum(len(d) for (g, _q), d in self._q2cb.items()
                       if g == group)

    def watermark(self, group: int = 0) -> int:
        """This replica's applied index for `group` — the session
        watermark echoed as X-Raft-Session on both HTTP planes.  A
        client that carries the largest watermark it has seen and
        presents it on `mode="session"` reads gets read-your-writes
        and monotonic reads from ANY replica."""
        return self.store.applied_index(group)

    def _wait_applied(self, group: int, target: int, deadline: float,
                      tick: float, phase: str) -> None:
        """Block until the local apply reaches `target` (bounded):
        the state machine applied it, or the commit stream delivered
        past it (entries that carry no command — see _delivered)."""
        while max(self.store.applied_index(group),
                  self._delivered[group]) < target:
            if self._failed is not None:
                raise self._failed
            now = time.monotonic()
            if now > deadline:
                raise ReadTimeout(
                    group, phase,
                    f"apply (at {self.store.applied_index(group)}) "
                    f"did not reach read point {target} in time")
            time.sleep(min(tick, max(deadline - now, 0.0005)))

    def query(self, query: str, group: int = 0,
              linear: bool = False, timeout: float = 10.0,
              mode: Optional[str] = None, watermark: int = 0,
              deadline_ms: Optional[float] = None,
              brownout: bool = False,
              info: Optional[dict] = None) -> str:
        """Read path, five consistency modes (README read-modes table):

          - "local" (default): the reference's stale local read —
            never touches consensus (db.go:123-130);
          - "session": local read AFTER the replica's apply reaches the
            client-provided `watermark` (X-Raft-Session echo from a
            previous write/read) — read-your-writes + monotonic reads
            at any replica;
          - "follower": local read at the replicated read-index
            watermark — this node's CURRENT commit index — so the
            answer reflects everything this replica knows committed at
            request arrival (fresher than local, no leader round);
          - "linear" (or linear=True): LINEARIZABLE.  Served from the
            leader LEASE when one covers now + max_clock_skew (no
            quorum round, config.lease_ticks), degrading to the
            ReadIndex quorum round (raft §6.4), degrading to
            NotLeaderError (421 + leader hint) off-leader — each
            degradation explicit, never a silent stale read.

        Bounded: every wait raises typed, retryable ReadTimeout (503)
        within `timeout`; leadership lost mid-round surfaces
        NotLeaderError on the next poll, never an unbounded spin."""
        if not is_select(query):
            raise ValueError("expected SELECT")
        if self.witness_self:
            # Refuse up front: a witness applies nothing, so any wait
            # on its applied index would just spin to ReadTimeout.
            raise ValueError(
                "witness replica serves no reads (it owns no shard); "
                "route the query to a full voter")
        if not 0 <= group < self.num_groups:
            raise ValueError(f"group {group} out of range "
                             f"[0, {self.num_groups})")
        if mode is None:
            mode = "linear" if linear else "local"
        node = self.pipe.node
        m = getattr(node, "metrics", None)
        tick = node.cfg.tick_interval_s or 0.001
        if deadline_ms is not None:
            # The client's end-to-end budget bounds every wait below;
            # a tighter server-side timeout still wins.
            timeout = min(timeout, max(float(deadline_ms) / 1000.0, 0.0))
        t_in = time.monotonic()
        deadline = t_in + timeout
        if info is not None:
            info["served"] = mode
        if mode == "local":
            if m is not None:
                m.reads_local += 1
        elif mode == "session":
            if m is not None:
                m.reads_session += 1
            if watermark > 0:
                self._wait_applied(group, watermark, deadline, tick,
                                   "session")
        elif mode == "follower":
            if m is not None:
                m.reads_follower += 1
            wm_fn = getattr(node, "commit_watermark", None)
            target = wm_fn(group) if wm_fn is not None \
                else max(watermark, 0)
            self._wait_applied(group, target, deadline, tick, "follower")
        elif mode == "linear":
            self._linear_wait(node, group, deadline, tick,
                              brownout=brownout, info=info)
        else:
            raise ValueError(f"unknown read mode {mode!r}")
        # stages.get.wait: arrival -> this mode's freshness established;
        # stages.get.sql: the SELECT on SQLite (all modes together).
        t_fresh = time.monotonic()
        with self.store.use(group) as sm:
            rows = sm.query(query)
        prof = self._prof()
        if prof is not None:
            prof.stage_many((("get.wait", t_fresh - t_in),
                             ("get.sql", time.monotonic() - t_fresh)))
        return rows

    def _linear_wait(self, node, group: int, deadline: float,
                     tick: float, brownout: bool = False,
                     info: Optional[dict] = None) -> None:
        """The linearizable read protocol: lease fast path, then the
        ReadIndex round, each wait bounded by `deadline`.

        Brownout ladder (raftsql_tpu/overload/): when an attached
        governor reports sustained queue pressure, the ReadIndex
        fallback is withheld — the lease fast path still serves full
        linearizability for free, but a lease miss refuses (429) unless
        the client opted in via `brownout=True` (X-Raft-Brownout:
        allow), in which case the read degrades to a session read at
        this replica's current applied point and `info["served"]`
        names the mode actually served.  Never a silent downgrade."""
        m = getattr(node, "metrics", None)
        lease_fn = getattr(node, "lease_read", None)
        lease_on = node.cfg.lease_ticks > 0 and lease_fn is not None
        if lease_on:
            target = lease_fn(group)
            if target is not None:
                if m is not None:
                    m.reads_lease += 1
                self._wait_applied(group, target, deadline, tick,
                                   "lease_apply")
                return
            # Lease unavailable (expired / not leader / precondition
            # pending): degrade to the full quorum round.
            if m is not None:
                m.lease_degrades += 1
        ov = getattr(node, "overload", None)
        if ov is not None:
            path = ov.brownout_read_path(brownout)  # may raise Overloaded
            if path == "session":
                # Opted-in degradation: serve at whatever this replica
                # has applied, skipping the quorum round entirely.
                if info is not None:
                    info["served"] = "session"
                return
        if m is not None:
            m.reads_read_index += 1
        join_fn = getattr(node, "read_join", None)
        if join_fn is not None:
            # Batched ReadIndex (runtime/node.py): join the group's
            # shared per-tick round and sleep on its event — N
            # concurrent readers cost one quorum round per tick, and
            # nobody poll-spins at tick cadence.
            while True:
                b = join_fn(group)
                if b is None:
                    raise NotLeaderError(group,
                                         node.leader_of(group) + 1)
                # A spurious wake on a still-pending batch must keep
                # waiting on the SAME batch — re-joining would bump its
                # count again and double-count this reader in
                # reads_read_index_batched and the batch-size histogram.
                while not b.status:
                    if time.monotonic() > deadline:
                        raise ReadTimeout(
                            group, "confirm",
                            "leadership not re-confirmed "
                            "(no quorum reachable?)")
                    b.evt.wait(max(deadline - time.monotonic(), 0.0))
                if b.status == "ok":
                    self._wait_applied(group, b.target, deadline,
                                       tick, "apply")
                    return
                if time.monotonic() > deadline:
                    raise ReadTimeout(
                        group, "confirm",
                        "leadership not re-confirmed "
                        "(no quorum reachable?)")
                # "not_leader": re-join — once the role cache reflects
                # the loss, join returns None and the typed redirect
                # surfaces.
        while True:
            got = node.read_index(group)
            if got is None:
                raise NotLeaderError(group, node.leader_of(group) + 1)
            if got != ():
                break
            # Leader without a committed current-term entry yet
            # (raft §6.4 precondition) — its no-op is in flight.
            if time.monotonic() > deadline:
                raise ReadTimeout(group, "read_index",
                                  "no current-term commit yet")
            time.sleep(tick)
        target, reg = got
        while not node.read_ready(group, reg):
            # Leadership lost mid-round: surface the typed redirect on
            # the NEXT poll — the round can never confirm and spinning
            # it out to the deadline would stall the client for
            # nothing (the leader hint names where to retry).
            if node.read_index(group) is None:
                raise NotLeaderError(group, node.leader_of(group) + 1)
            if time.monotonic() > deadline:
                raise ReadTimeout(
                    group, "confirm",
                    "leadership not re-confirmed "
                    "(no quorum reachable?)")
            time.sleep(tick)
        self._wait_applied(group, target, deadline, tick, "apply")

    def metrics(self) -> dict:
        def ms(v):
            return round(v * 1e3, 3) if v == v else None   # NaN -> null

        m = self.pipe.node.metrics.snapshot()
        # propose→commit (stamped at the commit observation point,
        # before apply) and propose→ack (after apply, the full
        # blocking-PUT latency the client sees).
        c50, c95, c99 = self.latency_commit.percentiles(
            (0.5, 0.95, 0.99))
        m["propose_commit_p50_ms"] = ms(c50)
        m["propose_commit_p95_ms"] = ms(c95)
        m["propose_commit_p99_ms"] = ms(c99)
        a50, a99 = self.latency.percentiles((0.5, 0.99))
        m["propose_ack_p50_ms"] = ms(a50)
        m["propose_ack_p99_ms"] = ms(a99)
        # Membership observability (raftsql_tpu/membership/): active
        # voter/learner slot totals across groups + applied conf-change
        # count.  Engines without a manager report the static shape.
        node = self.pipe.node
        mm = getattr(node, "membership", None)
        if mm is not None:
            v, l = mm.counts()
        else:
            v, l = node.cfg.num_peers * node.cfg.num_groups, 0
        m["members_voters"] = v
        m["members_learners"] = l
        # Quorum geometry (config.py flexible quorums + witnesses):
        # the per-phase thresholds this deployment runs under and the
        # provisioned witness count — static per config, exported so an
        # operator can read the geometry off any node's /metrics.
        cfg = node.cfg
        m["quorum"] = {
            "write_size": cfg.write_size,
            "election_size": cfg.election_size,
            "witnesses": len(cfg.witness_set),
        }
        # Telemetry plane (PR 8, default on): per-phase tick wall-time
        # histograms and the per-group traffic table with its top-K
        # hot-groups rows — the feed the placement controller consumes.
        prof = getattr(node, "prof", None)
        if prof is not None:
            m["phase_profile"] = prof.snapshot()
            # The request's stages and the host plane's intake.* and
            # wal.* counters (obs/prof.py), all cumulative.
            m["stages"] = prof.stages_doc()
            m.update(prof.counters_doc())
        traffic = getattr(node, "traffic", None)
        if traffic is not None:
            xg = getattr(node, "transferring_groups", None)
            m["group_traffic"] = traffic.doc(
                leader_of=getattr(node, "leader_of", None),
                shard_of=getattr(node, "_group_shard_of", None),
                transferring=xg() if callable(xg) else None)
        # Placement controller (raftsql_tpu/placement/): balance gauges
        # + issue counters, when a controller is attached.
        if self.placement is not None:
            m["placement"] = self.placement.metrics_doc()
        # Reshard plane (raftsql_tpu/reshard/): verb counters, per-verb
        # duration histogram, mapping epoch + active-verb gauge.
        if self.reshard is not None:
            m["reshard"] = self.reshard.metrics_doc()
        # Read-replica tier (raftsql_tpu/replica/): stream-server
        # counters when --replica-listen attached a plane; zeros
        # otherwise, so the raftsql_replica_* series exist from boot
        # on every deployment (scripts/check_prom.py requires them).
        if self.replica_plane is not None:
            m["replica"] = self.replica_plane.metrics_doc()
        else:
            m["replica"] = {"subscribers": 0, "deltas_tx": 0,
                            "bases_tx": 0, "resyncs": 0,
                            "refusals": 0, "lag_ms": 0}
        # Overload plane (raftsql_tpu/overload/): admission, per-phase
        # shed, and brownout counters when a controller is attached;
        # zeros otherwise so the raftsql_overload_* series exist from
        # boot on every deployment (scripts/check_prom.py requires
        # them), same contract as the replica section above.
        ovc = getattr(node, "overload", None)
        m["overload"] = (ovc.metrics_doc() if ovc is not None
                         else zero_metrics_doc())
        # Which device the engine computes on (utils/device.py): the
        # numeric fields (count, compile-cache hits/misses) also reach
        # the Prometheus exposition.
        m["device"] = device_doc()
        gcw = getattr(node, "_gcwal", None)
        if gcw is not None:
            # Group-commit batch histogram: peers coalesced per fsync
            # -> count (how well the one-fsync-per-tick lever engages).
            m["wal_gc_batch_hist"] = {
                str(k): v for k, v in sorted(gcw.batch_hist.items())}
        if self.serving_metrics is not None:
            try:
                m.update(self.serving_metrics())
            except Exception:                           # noqa: BLE001
                pass        # a gauge must never break the scrape
        return m

    def render_metrics(self) -> str:
        return json.dumps(self.metrics(), sort_keys=True) + "\n"

    def render_metrics_prom(self) -> str:
        """GET /metrics?format=prom: the same document in the
        Prometheus text exposition (utils/metrics.py prom_render —
        every JSON counter/gauge/histogram becomes a sample; validated
        by scripts/check_prom.py)."""
        from raftsql_tpu.utils.metrics import prom_render
        return prom_render(self.metrics())

    # -- membership admin (raftsql_tpu/membership/) ---------------------

    def members(self) -> dict:
        """GET /members: per-group active configuration + leader."""
        node = self.pipe.node
        fn = getattr(node, "members_doc", None)
        if fn is None:
            return {"error": "engine has no membership plane"}
        return fn()

    def member_change(self, group: int, op: str, peer: int) -> dict:
        """POST /members: propose add/remove/promote of a peer slot.
        Maps the membership plane's not-leader error onto the API's
        NotLeaderError so both HTTP planes answer 421 + the hint."""
        from raftsql_tpu.membership import NotLeaderForChange
        node = self.pipe.node
        fn = getattr(node, "member_change", None)
        if fn is None:
            raise ValueError("engine has no membership plane")
        try:
            return fn(group, op, peer)
        except NotLeaderForChange as e:
            raise NotLeaderError(e.group, e.leader) from e

    def transfer(self, group: int, target: int) -> dict:
        """POST /transfer: arm a graceful leadership transfer of
        `group` to peer slot `target` (0-based, like /members' `peer`;
        thesis §3.10 TimeoutNow — the device plane stalls intake, waits
        for catch-up, fires the grant).  Not-leader maps onto
        NotLeaderError so both HTTP planes answer 421 + the hint;
        validation refusals (in-flight transfer, learner target)
        surface as 400s."""
        from raftsql_tpu.membership import NotLeaderForChange
        node = self.pipe.node
        fn = getattr(node, "transfer_leadership", None)
        if fn is None:
            raise ValueError("engine has no leadership-transfer plane")
        try:
            return fn(group, target)
        except NotLeaderForChange as e:
            raise NotLeaderError(e.group, e.leader) from e

    def render_members(self) -> str:
        return json.dumps(self.members(), sort_keys=True) + "\n"

    # -- readiness (GET /healthz) ---------------------------------------

    def health_doc(self) -> dict:
        """GET /healthz: node id, per-group role / leader hint / term /
        commit (from the engine's host-side status caches) plus each
        group's APPLIED index from the state machines.  Answering at
        all means the process is up and replay finished (the
        constructor blocks on replay); the nemesis and operators read
        role/leader to detect restart completion without a write
        probe."""
        node = self.pipe.node
        status_fn = getattr(node, "status", None)
        groups = status_fn() if status_fn is not None else {
            str(g): {"role": "unknown",
                     "leader": int(node.leader_of(g)) + 1
                     if hasattr(node, "leader_of") else 0}
            for g in range(self.num_groups)}
        # Routing hints (PR 12, api/client.py front router): per-group
        # remaining lease seconds — a client routes linearizable reads
        # to the node reporting a live lease, writes to the leader.
        # Columns in bulk where the node keeps them: at 100,000 groups
        # this document is ~9.6 MB, made on every readiness poll.
        lease_fn = getattr(node, "lease_deadline_s", None)
        leases_fn = getattr(node, "lease_deadlines_s", None)
        now = time.monotonic()
        applied = self.store.applied.tolist()
        leases = leases_fn().tolist() if leases_fn is not None else None
        for g in range(self.num_groups):
            row = groups.get(str(g))
            if row is not None:
                row["applied"] = applied[g]
                if lease_fn is not None:
                    d = leases[g] if leases is not None else lease_fn(g)
                    row["lease_s"] = round(max(d - now, 0.0), 4)
        doc = {"id": int(getattr(node, "node_id", 0)),
               "ready": True, "groups": groups}
        # Where this engine runs: the device as JAX reports it plus the
        # JAX version and compile-cache traffic (utils/device.py), and
        # whether the WAL writes through the native fast path or fell
        # back to Python, and whether a SQLite file's transaction has
        # its one native call to take — so nothing outside the process
        # has to guess whether it measured the chip and the native
        # plane.
        doc["device"] = device_doc()
        wals = getattr(node, "wals", None) or [getattr(node, "wal", None)]
        doc["native_wal"] = all(getattr(w, "is_native", False)
                                for w in wals)
        doc["native_apply"] = load_native_apply() is not None
        # Mesh deployment: the observed shard placement of the step's
        # carry and output (runtime/mesh.py mesh_doc).
        mesh_fn = getattr(node, "mesh_doc", None)
        if mesh_fn is not None:
            doc["mesh"] = mesh_fn()
        # Pod deployment (raftsql_tpu/pod/): topology + ownership.  The
        # `hosts` table lets a client pointed at ONE pod host discover
        # the sweep set; `pod_owned` on each group row names which rows
        # THIS host serves (compute is replicated, so every host
        # truthfully reports every group — ownership, not role, is the
        # routing key; api/client.py refresh_hints merges the sweep).
        pod_fn = getattr(node, "pod_doc", None)
        if pod_fn is not None:
            doc["pod"] = pod_fn()
            for g in range(self.num_groups):
                row = groups.get(str(g))
                if row is not None:
                    row["pod_owned"] = bool(node.owns_group(g))
        if self.witness_self:
            # Routers and the chaos harness key off this: witnesses
            # accept writes (forwarded like any follower) but must
            # never be picked as a read target.
            doc["witness"] = True
        # Elastic keyspace (raftsql_tpu/reshard/): the versioned
        # key->group mapping.  Clients cache this and fail closed when
        # a /kv response reports a newer epoch.
        if self.reshard is not None:
            doc["keymap"] = self.reshard.keymap.to_doc()
        # Read-replica tier (raftsql_tpu/replica/): stream listen port,
        # per-subscriber applied/lag tails and — the client sweep's
        # hook — the advertised replica HTTP endpoints, which
        # api/client.py adopts and routes read-mode traffic to.
        if self.replica_plane is not None:
            try:
                doc["replica"] = self.replica_plane.health_doc()
            except Exception:                           # noqa: BLE001
                pass        # readiness must never break on a gauge
        return doc

    def render_health(self) -> str:
        return json.dumps(self.health_doc(), sort_keys=True) + "\n"

    # -- observability exports (raftsql_tpu/obs/) ----------------------

    def trace_doc(self) -> dict:
        """Chrome trace-event JSON of the engine's span tracer + device
        event ring + tick-phase profiler tracks + any worker-process
        trace segments (GET /trace; Perfetto-loadable).  A `--workers N`
        deployment's document is ONE multi-process timeline: the
        engine's spans/phases plus each worker's pid-tagged request
        segment (runtime/ring.py RingServer points
        `trace_segments_dir` at the ring directory the workers flush
        into).  Always a valid (possibly empty) document — tracing off
        just yields no span events."""
        from raftsql_tpu.obs.export import chrome_trace, collect_segments
        node = self.pipe.node
        tracer = self._node_tracer()
        ring = getattr(node, "ring", None)
        prof = getattr(node, "prof", None)
        if ring is not None:
            ring.drain()
        seg_dir = getattr(self, "trace_segments_dir", None)
        segs = collect_segments(seg_dir) if seg_dir else None
        # One time axis for every track family: the tracer's epoch when
        # tracing is on, else the profiler's.
        base = tracer.t0 if tracer is not None else (
            prof.epoch if prof is not None else 0.0)
        # Cap the counter window: a long-lived ring (keep=4096 ticks)
        # would emit ~20 counter events per tick per (peer, group) —
        # the last 1024 ticks keep the document loadable.
        return chrome_trace(
            tracer.snapshot() if tracer is not None else None,
            ring.rows(last=1024) if ring is not None else None,
            phase_events=prof.events() if prof is not None else None,
            process_segments=segs,
            base_monotonic=base)

    def events_doc(self, last: int = 256) -> dict:
        """Raw observability state (GET /events): the device ring's
        drained per-tick rows plus the host tracer's snapshot."""
        node = self.pipe.node
        tracer = self._node_tracer()
        ring = getattr(node, "ring", None)
        if ring is not None:
            ring.drain()
        return {
            "tracing": tracer is not None or ring is not None,
            "device": ring.rows(last=last) if ring is not None else [],
            "host": tracer.snapshot() if tracer is not None else {},
        }

    def render_trace(self) -> str:
        return json.dumps(self.trace_doc(), sort_keys=True) + "\n"

    def render_events(self) -> str:
        return json.dumps(self.events_doc(), sort_keys=True) + "\n"

    def close(self) -> Optional[Exception]:
        """Shut down, failing (not leaking) any still-pending acks.

        The reference fatals on pending acks (db.go:159-161); failing them
        with an error instead is the conscious improvement — a node with
        in-flight proposals at shutdown (e.g. lost quorum) must still be
        able to close its WAL and state machines cleanly."""
        with self._mu:
            if self._closed:
                return None
            self._closed = True
            pending = [cb for cbs in self._q2cb.values() for cb in cbs]
            self._q2cb.clear()
        for cb in pending:
            cb.set(RuntimeError("db closing with proposal outstanding"))
        if self.replica_plane is not None:
            try:
                self.replica_plane.stop()
            except Exception:                           # noqa: BLE001
                pass
            self.replica_plane = None
        if self._compactor is not None:
            # A round in flight ends before the logs and the state
            # machines it works on are closed (none starts after
            # `_closed`).
            self._compactor.join(timeout=30)
        err = self.pipe.close()
        self._reader.join(timeout=10)
        self._apply_pool.shutdown()
        if self._compactor is not None:     # one the reader just started
            self._compactor.join(timeout=30)
        self.store.close()
        return err
