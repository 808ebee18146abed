"""The telemetry plane: where a tick's wall time goes, where a request's
latency goes, and what the host plane and the WAL were asked to do.

`NodeMetrics.phase_ms_per_tick` is a running AVERAGE — it can say "wal
is 40% of the tick" but not "fsync p99 spiked 20x for 50 ticks while
p50 held", which is exactly the shape a serving regression takes.  This
module is the layer between that average and the full span tracer.
Three kinds of record, all taken with `time.monotonic()` at the boundary
where the work happens, all cumulative, all on `GET /metrics`:

PHASES — monotonic-clock stamps around each phase of the host plane's
tick:

    pop        proposal pop/stage (_build_prop_n + _stage_ranges), of
               which the second is also recorded apart as
      pop_stage  _stage_ranges alone: the accepted payloads popped,
                 group by group, into the durable phase's write plans
    dispatch   device dispatch + packed-info readback, which are also
               recorded apart as
      launch     the dispatch half: stage inputs, launch the program
      readback   the half that blocks on the device's packed info
      mesh_put   (mesh runtime only; absent elsewhere) the host's
                 inputs laid over the mesh's shards, before launch
    wal_write  WAL entry/hardstate writes (the durable phase minus
               fsync), whose parts are also recorded apart as
      wal_plan      mirror metadata + the parallel-path plan
      wal_append    leader appends + follower mirror appends
      wal_hardstate changed hard states (+ epoch END marks)
    fsync      the per-peer fsync barrier
    epoch_commit  a multi-step dispatch's commit record: the write and
               fsync of data_dir/EPOCHS after the barrier (absent
               where a dispatch is one step)
    publish    commit delivery to the apply plane
    ring_drain the serving plane's propose-ring drain batches

— ring-buffered per phase (pre-allocated) and exported as p50/p95/p99
phase histograms plus the cumulative `total_ms`/`n` in `GET /metrics`
(`phase_profile`, and as a Prometheus summary
`raftsql_tick_phase_ms{phase=...}` under `?format=prom`) plus per-phase
Perfetto tracks in `GET /trace`.  A reader that wants a window takes
the difference of `total_ms` between two scrapes: the ring percentiles
cover the newest `cap` samples, whenever they were taken.

STAGES — named cumulative pairs `{total_ms, n, max_ms}` for the legs of
a request (`stages.put.*`, `stages.get.*` on the engine;
`worker_stages.*` in an HTTP worker, which folds its own into the
document it relays) and of a tick's commits on their way to the apply
plane (`stages.publish.queue`: handed to a publish worker -> taken up
by it, one sample a worker a tick), and of a compaction
(`stages.compact.checkpoint`: the state machines written since the
last sweep put on disk, on a thread of its own, runtime/db.py, with
`stages.compact.file` for each file, its checkpoint as the thread that
ran it timed it; then `stages.compact.sweep`: the tick thread's sweep,
one sample each a sweep), and of the state-machine store
(models/store.py: `stages.sm.miss`, a use that found its handle
closed, from the pin to the file open again; `stages.sm.release`, one
victim released, on the thread that needed its slot), and of the
serving plane (runtime/ring.py: `stages.shm.refresh`, one restamp of
the shm table's commit/leader/lease columns on the engine's refresh
thread; `stages.doc.render`, one document the engine made for a worker,
/healthz or /metrics among them; `worker_stages.get.shm_snapshot`, a
worker's seqlock read of the header and its one row inside
runtime/shm.py `try_read`).  No ring, no percentile, no per-request
object.

COUNTERS — plain cumulative integers (`dispatch.steps`: the consensus
steps the launches carried, one tick a launch; `intake.*`: what the
host plane's queues held, offered to the device and got accepted, per
tick summed, and `intake.committed_in_dispatch`: of the accepted, the
entries the publishing peer saw committed before that dispatch ended;
`wal.*`: records, bytes, hard states, groups and fsyncs of the durable
phase, the shard streams a sharded WAL flushed, the follower ranges
handed to the mirror, those of them that took the Python mirror, and
`wal.mirror_skipped_rows`: the accepted appends that could change no
log, empty heartbeat acks, and were dropped before any was listed;
`publish.groups`: the groups in which a publish worker found commits of
the client-facing peer to deliver, one count a dispatch a worker;
`compact.sweeps`, `compact.floors_advanced` (groups x peers whose floor
a sweep moved) and `wal.segments_unlinked`, one count() a sweep;
`compact.rounds` and `compact.files`, the files a round put on disk,
one count() a round; `doc.records`, the completion-ring records the
documents' replies took, one count() a reply, so a document larger than
the ring reads as more records than `stages.doc.render.n`).

GAUGES — values that are read where they live when a document is made
(`gauge_fn`): `wal.disk_bytes` and `wal.segments_pinned` (the WALs keep
both as they rotate and unlink), and the state-machine store's
`sm.opens`, `sm.closes`, `sm.evictions`, `sm.open_handles`,
`sm.uses`, `sm.misses` and `sm.native_reopens`, `sm.python_reopens`
(models/store.py), and two seconds on the engine's clock from the host
plane's constructor: `boot.replay_s` to the end of the WAL replay's
apply (runtime/db.py) and `boot.all_led_s` to the first tick at which
every group had a leader (0 until then).  They sit beside the counters
in the document; a float gauge keeps three decimals.

ON THE PROFILER'S CLOCK: while a JAX profiler session runs, the engine
opens each LEAF phase of the tick (pop, mesh_put, launch, readback,
wal_plan, wal_append, wal_hardstate, fsync, epoch_commit, publish, and
compact where a sweep runs: `stages.compact.sweep` holds its time) as a
`jax.profiler.TraceAnnotation` named `tick.<phase>` carrying `tick=<n>`
(`annotation()` is the one flag test a tick makes; `span()` gives the
shared no-op context when it says no), so a device trace names the
device's idle gaps after the tick phase that covered them.  No
annotation encloses another, a whole tick, or work that can outlast one
(an apply run, a read waiting for its freshness): a gap is named after
the host event that covers most of it, whichever thread it is on, and
such an event would take the name of every gap it spans (measured on
the chip, PERF.md PR 26).  `StageSet` alone never touches JAX: it is
what an HTTP worker process uses.

TICK ATTRIBUTION: the publish workers (and a serial host's deferred
publish) run a tick's publish while a later tick runs.  Every sample
carries the tick that OWNS the work — the publish queue items carry
theirs — so a phase histogram keyed by tick counts each phase once per
tick that owns it (pinned by tests/test_obs.py's attribution test).

Default **on**.  The hot paths read `time.monotonic()` in place and
hand a tick's samples and counts over in ONE call (`record_tick`: the
tick thread makes two a tick, one for the tick and one for the durable
phase it retires), an apply run's stage pairs in one (`stage_many`).
A call only APPENDS what it was handed to a deque (atomic, no lock);
the rings, totals and counters are brought up to date in batches —
every FOLD_AT appends, and before every export.  In a served engine
five threads take turns at the interpreter and a call's code runs
cold: measured there, the same stores made call by call cost five
times what they cost in a batch (PERF.md, PR 26).  RAFTSQL_PROF=0
disables all of it, in the engine and in its workers: the callers
then hold `None` and skip the calls, and the new keys are absent from
`/metrics`.
"""
from __future__ import annotations

import contextlib
import os
import threading
import time
from collections import deque
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

# Phases that partition the tick thread's wall time; ring_drain runs on
# the serving plane's drain threads and is reported but excluded from
# the tick-share denominators, as are the finer phases that lie INSIDE
# pop (pop_stage), dispatch (launch, readback) and wal_write (wal_*),
# and epoch_commit.
PROF_PHASES = ("pop", "pop_stage", "dispatch", "launch", "readback",
               "mesh_put",
               "wal_write", "wal_plan", "wal_append", "wal_hardstate",
               "fsync", "epoch_commit", "publish", "ring_drain")
_TICK_PHASES = ("pop", "dispatch", "wal_write", "fsync", "publish")

# The names every document carries from boot (a series that appears
# only after its first sample cannot be told from one that was lost).
ENGINE_STAGES = ("put.engine", "put.propose_commit", "put.apply",
                 "put.apply_batch", "get.queue", "get.wait", "get.sql",
                 "publish.queue", "compact.sweep", "compact.checkpoint",
                 "compact.file", "sm.miss", "sm.reopen", "sm.release",
                 "shm.refresh", "doc.render")
WORKER_STAGES = ("put.edge_in", "put.ring_rtt", "put.edge_out",
                 "get.ring_rtt", "get.shm_snapshot")
ENGINE_COUNTERS = ("dispatch.steps", "intake.backlog", "intake.offered",
                   "intake.accepted", "intake.groups",
                   "intake.committed_in_dispatch", "wal.records",
                   "wal.bytes",
                   "wal.hardstates", "wal.groups_written", "wal.fsyncs",
                   "wal.shard_syncs", "wal.mirror_rows",
                   "wal.mirror_fallback_rows", "wal.mirror_skipped_rows",
                   "publish.groups", "apply.runs", "apply.groups",
                   "apply.fanout_runs", "apply.native_txns",
                   "apply.python_txns",
                   "compact.sweeps", "compact.floors_advanced",
                   "wal.segments_unlinked", "compact.rounds",
                   "compact.files", "doc.records")
# Read where they live, at export (gauge_fn); 0 until somebody says.
ENGINE_GAUGES = ("wal.disk_bytes", "wal.segments_pinned", "sm.opens",
                 "sm.closes", "sm.evictions", "sm.open_handles", "sm.uses",
                 "sm.misses", "sm.native_reopens", "sm.python_reopens",
                 "boot.replay_s", "boot.all_led_s")


# Appends a deque may hold before the appending thread folds them in
# (~20 ticks' worth in the engine: a fraction of a millisecond's work,
# twice a second).
FOLD_AT = 256


def enabled() -> bool:
    """The one switch of the telemetry plane (engine and workers)."""
    return os.environ.get("RAFTSQL_PROF", "1") != "0"


def _nest(flat: Dict[str, object]) -> dict:
    """{"put.engine": v} -> {"put": {"engine": v}}."""
    out: dict = {}
    for name, v in flat.items():
        d = out
        *head, leaf = name.split(".")
        for k in head:
            d = d.setdefault(k, {})
        d[leaf] = v
    return out


class StageSet:
    """Named cumulative (total, n, max) pairs; the names given at
    construction are in the document from the start.

    Safe from any thread: a record is one deque append; `_mu` guards
    the folded state."""

    def __init__(self, stages: Iterable[str] = ()):
        self._mu = threading.Lock()
        # name -> [total_s, n, max_s]
        self._stages: Dict[str, list] = {s: [0.0, 0, 0.0] for s in stages}
        self._new_stages: deque = deque()   # stage_many()s not folded in

    def stage(self, name: str, dur_s: float) -> None:
        self.stage_many(((name, dur_s),))

    def stage_many(self, pairs: Sequence[Tuple[str, float]]) -> None:
        """Several samples in one call (an apply run's acks); `pairs`
        is the profiler's from here on."""
        self._new_stages.append(pairs)
        if len(self._new_stages) >= FOLD_AT:
            with self._mu:
                self._fold()

    def _fold(self) -> None:
        """Bring the folded state up to date (caller holds `_mu`)."""
        new, st = self._new_stages, self._stages
        while new:
            for name, dur_s in new.popleft():
                s = st.get(name)
                if s is None:
                    s = st[name] = [0.0, 0, 0.0]
                s[0] += dur_s
                s[1] += 1
                if dur_s > s[2]:
                    s[2] = dur_s

    def stages_doc(self) -> dict:
        """{"put": {"engine": {"total_ms", "n", "max_ms"}, ...}, ...}"""
        with self._mu:
            self._fold()
            flat = {k: {"total_ms": round(v[0] * 1e3, 3), "n": v[1],
                        "max_ms": round(v[2] * 1e3, 3)}
                    for k, v in self._stages.items()}
        return _nest(flat)


# What span() gives while no profiler session runs.
NO_SPAN = contextlib.nullcontext()


def span(ann, name: str, tick_no: int):
    """`with span(ann, "tick.pop", n):` — the phase on the JAX
    profiler's timeline where `ann` is what `annotation()` returned
    for this tick, and nothing where that was None."""
    return NO_SPAN if ann is None else ann(name, tick=tick_no)


class TickPhaseProfiler(StageSet):
    """Per-phase duration rings + totals, and the engine's stages and
    counters (see module docstring).

    record()/record_tick() are safe from any thread (tick thread,
    publish workers, ring drains); the rings are pre-allocated at
    construction."""

    def __init__(self, cap: int = 4096, annotate: bool = False):
        super().__init__(ENGINE_STAGES)
        self._counters: Dict[str, int] = dict.fromkeys(ENGINE_COUNTERS, 0)
        self._gauges: Dict[str, object] = {}    # name -> () -> number
        self._new_ticks: deque = deque()    # record_tick()s not folded in
        n = len(PROF_PHASES)
        self.cap = cap
        self.epoch = time.monotonic()
        self._i: Dict[str, int] = {p: k for k, p in enumerate(PROF_PHASES)}
        # Per-phase rings as plain pre-allocated lists: an element store
        # into a list costs a tenth of one into a numpy array, and a
        # sample makes four; the exports copy rows under the lock and
        # convert outside it.
        self._dur = [[0.0] * cap for _ in range(n)]     # seconds
        self._t0 = [[0.0] * cap for _ in range(n)]      # raw monotonic s
        self._tick = [[-1] * cap for _ in range(n)]     # owning tick
        self._tid = [[0] * cap for _ in range(n)]       # worker/shard id
        self._pos = [0] * n
        self._count = [0] * n
        self._total = [0.0] * n
        # jax.profiler.TraceAnnotation in the engine; None elsewhere
        # (tests that build a bare profiler, processes without JAX).
        self._ann_cls = None
        if annotate:
            from jax.profiler import TraceAnnotation
            self._ann_cls = TraceAnnotation

    @classmethod
    def from_env(cls, num_groups: int = 0) -> Optional["TickPhaseProfiler"]:
        """The default-on constructor the host plane uses (so its spans
        annotate the JAX profiler's timeline).  RAFTSQL_PROF=0 turns the
        telemetry plane off; RAFTSQL_PROF_CAP sizes the per-phase rings."""
        if not enabled():
            return None
        cap = int(os.environ.get("RAFTSQL_PROF_CAP", "4096"))
        return cls(cap=max(64, cap), annotate=True)

    def annotation(self):
        """The annotation class while a JAX profiler session runs (and
        this profiler annotates), else None: a thread asks once per
        tick or batch and hands the answer to span()."""
        ann = self._ann_cls
        return ann if ann is not None and ann.is_enabled() else None

    def record(self, phase: str, tick_no: int, t_start: float,
               dur_s: float, tid: int = 0) -> None:
        self.record_tick(tick_no, ((phase, t_start, dur_s),), tid=tid)

    def record_tick(self, tick_no: int,
                    samples: Sequence[Tuple[str, float, float]],
                    counts: Sequence[Tuple[str, int]] = (),
                    tid: int = 0) -> None:
        """What tick `tick_no` owns, in one call: its (phase, t_start,
        dur_s) samples and (counter, n) increments, both the profiler's
        from here on."""
        self._new_ticks.append((tick_no, samples, counts, tid))
        if len(self._new_ticks) >= FOLD_AT:
            with self._mu:
                self._fold()

    def count(self, counts: Sequence[Tuple[str, int]]) -> None:
        """(counter, n) increments that no tick owns (an apply run's),
        in one call."""
        self.record_tick(-1, (), counts)

    def gauge_fn(self, name: str, fn) -> None:
        """`fn()` is the value of gauge `name` whenever a document is
        made; it runs on the scraping thread and must only read."""
        self._gauges[name] = fn

    def _fold(self) -> None:
        super()._fold()
        new, index, cap, c = self._new_ticks, self._i, self.cap, \
            self._counters
        while new:
            tick_no, samples, counts, tid = new.popleft()
            for phase, t_start, dur_s in samples:
                k = index[phase]
                j = self._pos[k]
                self._dur[k][j] = dur_s
                self._t0[k][j] = t_start
                self._tick[k][j] = tick_no
                self._tid[k][j] = tid
                self._pos[k] = (j + 1) % cap
                self._count[k] += 1
                self._total[k] += dur_s
            for name, n in counts:
                c[name] += n

    # -- export ---------------------------------------------------------

    def counters_doc(self) -> dict:
        """{"intake": {"backlog": n, ...}, "wal": {...}}"""
        with self._mu:
            self._fold()
            flat = dict(self._counters)
        for name in ENGINE_GAUGES:
            flat[name] = 0
        for name, fn in list(self._gauges.items()):
            try:
                v = fn()
                flat[name] = round(v, 3) if isinstance(v, float) \
                    else int(v)
            except Exception:                           # noqa: BLE001
                pass            # a gauge must never break the scrape
        return _nest(flat)

    def _window(self, rows: List[list]) -> List[list]:
        """Copies of the filled part of each phase's ring (caller holds
        the lock): a ring fills from 0, so that is the first
        min(count, cap) slots."""
        return [r[:min(c, self.cap)] for r, c in zip(rows, self._count)]

    def snapshot(self) -> dict:
        """JSON-ready per-phase histograms over the ring window:
        {phase: {p50_ms, p95_ms, p99_ms, max_ms, n, total_ms}} (`n` and
        `total_ms` are cumulative since boot) plus `"sample": 1`, kept
        for readers of the exposition: every tick is recorded.  A
        record never takes the lock unless it is the one in FOLD_AT
        that folds; the scrape holds it for the fold and the row
        copies, and sorts outside it."""
        with self._mu:
            self._fold()
            durs = self._window(self._dur)
            counts = list(self._count)
            totals = list(self._total)
        out: dict = {"sample": 1}
        for p, k in self._i.items():
            if not counts[k]:
                continue
            valid = np.array(durs[k], np.float64)
            valid.sort()
            n = valid.size

            def q(f):
                return round(float(valid[min(int(f * n), n - 1)]) * 1e3,
                             4)

            out[p] = {"p50_ms": q(0.5), "p95_ms": q(0.95),
                      "p99_ms": q(0.99),
                      "max_ms": round(float(valid[-1]) * 1e3, 4),
                      "n": counts[k],
                      "total_ms": round(totals[k] * 1e3, 3)}
        return out

    def shares(self) -> dict:
        """Each tick phase's share of the total profiled tick time —
        the one-line "why did this rung move" summary the durable bench
        records (fsync-share vs dispatch-share vs publish-share)."""
        with self._mu:
            self._fold()
            totals = {p: self._total[self._i[p]] for p in _TICK_PHASES}
        denom = sum(totals.values())
        if denom <= 0:
            return {f"{p}_share": 0.0 for p in _TICK_PHASES}
        return {f"{p}_share": round(v / denom, 4)
                for p, v in totals.items()}

    def phase_ticks(self, phase: str) -> List[int]:
        """Sorted distinct tick ids holding samples of `phase` (the
        attribution test's probe)."""
        k = self._i[phase]
        with self._mu:
            self._fold()
            t = self._tick[k][:min(self._count[k], self.cap)]
        return sorted(set(t))

    def events(self, last: int = 2048) -> List[dict]:
        """The ring window as Perfetto-ready phase events (newest-last,
        RAW monotonic start seconds — the caller rebases to its trace
        epoch): {"phase", "tick", "t0", "dur", "tid"}."""
        with self._mu:
            self._fold()
            durs = self._window(self._dur)
            t0s = self._window(self._t0)
            ticks = self._window(self._tick)
            tids = self._window(self._tid)
        evs: List[dict] = []
        for p, k in self._i.items():
            for t0, d, tk, td in zip(t0s[k], durs[k], ticks[k], tids[k]):
                evs.append({"phase": p, "tick": tk, "t0": t0, "dur": d,
                            "tid": td})
        evs.sort(key=lambda e: e["t0"])
        return evs[-last:]
