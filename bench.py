"""Benchmark harness — the five BASELINE.json configs on one chip.

Headline (default, what the driver records): committed log entries per
second across N raft groups, using the fused whole-cluster step
(core/cluster.py) — P peers x G groups advanced per device tick, proposals
flowing at the flow-control limit, commits counted on device so only one
scalar crosses the host boundary per timed run.

Latency is MEASURED, not estimated: the commit trajectory [T, G] is kept on
device, `ops.commit_scan.commit_latency_ticks` finds the first tick at
which each group commits the batch appended on tick 0, and p50/p99 ticks x
measured tick wall-time give propose→commit milliseconds (stderr + README).
Groups that never commit the target inside the run are excluded from the
percentiles and reported as a censored count.

Prints one JSON line on stdout per result:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
   "platform": ..., "device_kind": ..., "devices": N}
Every result names the device it ran on, and a run that found no chip
prints NO result: the exit code is non-zero and stderr says why.

Process model: the process runs as a PARENT that never imports a jax
backend (a chip belongs to one process at a time).  Every measurement is
a CHILD subprocess under a hard timeout, one at a time.  The parent
first PROBES the default platform (raftsql_tpu/utils/device.py: the
platform JAX_PLATFORMS names, or an accelerator — never a silent CPU);
unless the probe reports `tpu` it exits non-zero.  Then it runs a
single-shape G-ladder (1k → 10k → 32k → 100k) smallest-first with
per-shape fault capture and a second pass over failed shapes, keeping
the BEST-value rung as the headline, and in budget priority order: a
durable-path child on the chip, host-runtime children pinned to the cpu
(labelled with their own platform), a latency child (G=1024/E=16, the
<2 ms p50 shape), and the commit-rule race.  A child that failed, timed
out or reported a faulted rung makes the parent's exit code non-zero.
`BENCH_PLATFORM=cpu` is the explicit development path: one pinned child,
its JSON says `"platform": "cpu"`.

The reference (chzchzchz/raftsql) publishes no numbers (BASELINE.md); the
baseline used for `vs_baseline` is the driver-set north star of 1e8
commits/sec (100k groups x 1k proposals/sec each, BASELINE.json).

Environment knobs:
  BENCH_CONFIG   headline | quorum | elections | commit_scan | multichip
                 | rules | latency | durable | georeads | all
                 (default headline)
  BENCH_GEO_SECONDS / BENCH_GEO_RTT_MS / BENCH_GEO_THINK_MS
                 georeads rung length, injected upstream RTT and the
                 closed-loop client think time (defaults 5s, 60, 50)
  BENCH_GROUPS / BENCH_PEERS / BENCH_TICKS / BENCH_REPEATS
  BENCH_E        append batch size (headline default 32; latency sweeps
                 pin 16 via BENCH_LAT_E; BENCH_LAT_GROUPS sets their G)
  BENCH_LADDER   comma-separated group counts
                 (default 1000,10000,32768,100000)
  BENCH_DURABLE_ACTIVE  N groups carrying load in the durable bench
  BENCH_PLATFORM cpu|tpu        (parent: single attempt on this platform)
  BENCH_ATTEMPT_TIMEOUT_S       (default 420, per child attempt)
  BENCH_PROBE_TIMEOUT_S         (default 150, platform probe)
  BENCH_TOTAL_BUDGET_S          (default 1800, whole-parent wall budget)
  BENCH_SKIP_DURABLE=1 / BENCH_SKIP_SWEEP=1 / BENCH_SKIP_RULES=1
  BENCH_PROFILE  <dir>          (wrap timed runs in jax.profiler.trace)
  BENCH_POD_PROCS=N  with BENCH_CONFIG=multichip: add the multi-host
                 pod rung — N real `pod.dryrun --mode bench` processes
                 over the TCP collective, reporting commits/s plus the
                 per-host cross-host hop cost (pod_wait_ms_per_tick)
                 next to the phase shares (BENCH_POD_TICKS overrides)
"""
from __future__ import annotations

import contextlib
import functools
import json
import os
import subprocess
import sys
import threading
import time

NORTH_STAR_COMMITS_PER_SEC = 1.0e8


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Child: one measurement attempt on one platform.
# ---------------------------------------------------------------------------


def _profiled():
    import jax
    d = os.environ.get("BENCH_PROFILE")
    return jax.profiler.trace(d) if d else contextlib.nullcontext()


def make_bench_run(cfg, num_ticks: int):
    """Jitted: scan `num_ticks` cluster ticks; returns device scalars
    (commit delta, [p50, p99] latency ticks, number of groups that
    committed the tick-0 batch).

    Latency: the proposals appended during tick 0 of the run define a
    per-group target index (max log_len after tick 0); the commit
    trajectory's first crossing of that target is the measured
    propose→commit tick count (ops/commit_scan.py).  Groups that never
    cross inside the run are right-censored: excluded from percentiles,
    counted separately.
    """
    import jax
    import jax.numpy as jnp

    from raftsql_tpu.core.cluster import cluster_step
    from raftsql_tpu.ops.commit_scan import (commit_latency_ticks,
                                             running_commit)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def run(states, inboxes, prop_n):
        commit0 = jnp.max(states.commit, axis=0)                    # [G]

        def body(carry, _):
            st, ib = carry
            st, ib, _ = cluster_step(cfg, st, ib, prop_n)
            return (st, ib), (jnp.max(st.commit, axis=0),
                              jnp.max(st.log_len, axis=0))

        (states, inboxes), (ctraj, ltraj) = jax.lax.scan(
            body, (states, inboxes), None, length=num_ticks)
        committed = jnp.sum(ctraj[-1] - commit0)
        first = commit_latency_ticks(running_commit(ctraj), ltraj[0])
        ok = first < num_ticks                                      # [G]
        n_ok = jnp.sum(ok)
        lats = jnp.sort(jnp.where(ok, (first + 1).astype(jnp.float32),
                                  jnp.inf))
        G = lats.shape[0]

        def q(p):
            i = (p * (n_ok.astype(jnp.float32) - 1.0)).astype(jnp.int32)
            return lats[jnp.clip(i, 0, G - 1)]

        pct = jnp.where(n_ok > 0, jnp.stack([q(0.5), q(0.99)]),
                        jnp.full((2,), jnp.inf))
        return states, inboxes, committed, pct, n_ok

    return run


def bench_throughput(groups: int, peers: int, ticks: int, repeats: int,
                     load: int | None = None, commit_rule: str = "point",
                     stats: dict | None = None, e: int | None = None):
    """Commits/sec + measured latency for a G x P fused cluster.

    `load` = proposals submitted per group per tick (None = saturating,
    i.e. max_entries_per_msg).  `e` = append batch size override
    (default env BENCH_E, else 32: throughput is G x E per tick and the
    measured TPU sweep gives E=32 +55% over E=16 at ~1.7 ms/tick, while
    E=16 keeps the tick at 0.3-0.5 ms — the latency sweep pins it).
    Returns best commits/s; if `stats` is given, records {"p50_ms",
    "p99_ms", "tick_ms"} of the best repeat.
    """
    import jax
    import jax.numpy as jnp

    from raftsql_tpu.config import RaftConfig
    from raftsql_tpu.core.cluster import (empty_cluster_inbox,
                                          init_cluster_state)

    # With pipelined replication throughput is G x E per tick; the
    # measured TPU sweep (README) picks E=32/W=256 for throughput runs
    # and E=16/W=128 for latency runs.
    E = e if e is not None else int(os.environ.get("BENCH_E", "32"))
    cfg = RaftConfig(num_groups=groups, num_peers=peers,
                     log_window=max(8 * E, 64), max_entries_per_msg=E,
                     tick_interval_s=0.0, commit_rule=commit_rule,
                     # The windowed/pallas rules scan the [G, W] term
                     # ring; the point rule reads only the transition
                     # table, so the ring (write fills ~40% of the
                     # remaining tick) is dropped.
                     keep_ring=commit_rule != "point")
    # Build the initial state ON device in one compiled program — at 100k
    # groups the eager per-leaf host->device transfers are the slow path.
    states, inboxes = jax.jit(
        lambda: (init_cluster_state(cfg), empty_cluster_inbox(cfg)))()
    saturate = load is None
    load = cfg.max_entries_per_msg if saturate else min(
        load, cfg.max_entries_per_msg)
    full = jnp.full((cfg.num_peers, cfg.num_groups), load, jnp.int32)

    run = make_bench_run(cfg, ticks)

    # Warmup (elect leaders everywhere) reuses the RUN program at zero
    # load — a separate shorter-scan warmup program would cost a second
    # full compile inside the child's time budget.  Repeat for short
    # runs so every group gets at
    # least ~4 election intervals to settle.
    for _ in range(max(1, -(-4 * cfg.election_ticks // ticks))):
        states, inboxes, _, _, _ = run(states, inboxes, full * 0)
    states, inboxes, c, _, _ = run(states, inboxes, full)
    jax.block_until_ready(c)

    best, best_p50, best_p99, best_tick = 0.0, float("inf"), float("inf"), 0.0
    total_committed = 0
    repeat_rates: list = []
    label = "saturated" if saturate else f"load={load}/group/tick"
    for _ in range(repeats):
        t0 = time.perf_counter()
        with _profiled():
            states, inboxes, committed, pct, n_ok = run(
                states, inboxes, full)
            committed = int(jax.block_until_ready(committed))
        dt = time.perf_counter() - t0
        total_committed += committed
        rate = committed / dt
        tick_ms = dt / ticks * 1e3
        n_ok = int(n_ok)
        if n_ok:
            p50, p99 = float(pct[0]) * tick_ms, float(pct[1]) * tick_ms
            lat_msg = (f"measured propose->commit p50={p50:.3f} ms "
                       f"p99={p99:.3f} ms ({float(pct[0]):.0f}/"
                       f"{float(pct[1]):.0f} ticks x {tick_ms:.4f} ms/tick, "
                       f"{groups - n_ok} censored)")
            if p50 < best_p50:
                best_p50, best_p99, best_tick = p50, p99, tick_ms
        else:
            lat_msg = "latency n/a (no group committed the marked batch)"
        _log(f"  {committed} commits in {dt:.3f}s -> {rate:,.0f} commits/s "
             f"({rate / groups:,.1f}/group/s); {lat_msg}")
        best = max(best, rate)
        repeat_rates.append(round(rate, 1))
    if saturate and total_committed == 0:
        raise RuntimeError("benchmark committed nothing — engine stalled")
    if best_p50 < float("inf"):
        _log(f"  best: {best:,.0f} commits/s, measured propose->commit "
             f"p50={best_p50:.3f} ms p99={best_p99:.3f} ms ({label})")
    if stats is not None:
        # None, not inf: json.dumps would emit the non-RFC token
        # `Infinity` and break strict parsers of the one-JSON-line
        # contract exactly on the degenerate (nothing committed) run.
        got_lat = best_p50 < float("inf")
        stats["p50_ms"] = round(best_p50, 3) if got_lat else None
        stats["p99_ms"] = round(best_p99, 3) if got_lat else None
        stats["tick_ms"] = round(best_tick, 4) if got_lat else None
        stats["repeat_rates"] = repeat_rates
        if len(repeat_rates) > 1 and max(repeat_rates) > 0:
            stats["repeat_spread"] = round(
                (max(repeat_rates) - min(repeat_rates))
                / max(repeat_rates), 3)
    return best


def _light_row(sweep: dict) -> dict:
    """The light-load row of a latency sweep (labels carry a G suffix)."""
    return next((v for k, v in sweep.items() if k.startswith("light_1")),
                {})


def bench_reads(peers: int = 3, seconds: float = 2.0) -> tuple:
    """BENCH_CONFIG=reads: the read-plane ladder on the DISTRIBUTED
    runtime (3 RaftNodes over loopback — the plane where a ReadIndex
    round actually costs a quorum round trip, unlike the co-located
    fused cluster where leadership is process-local):

      local       stale local read (reference parity)
      lease       linearizable via the leader lease (no quorum round)
      read_index  linearizable via the full ReadIndex round
      session     watermark read at the leader (applied >= wm)
      follower    replicated-watermark read at a follower

    Headline = lease reads/s (the optimization under test); the whole
    ladder rides the extras.  One serial client — this measures
    per-read PATH cost, not parallel throughput."""
    import tempfile

    from raftsql_tpu.config import RaftConfig
    from raftsql_tpu.models.sqlite_sm import SQLiteStateMachine
    from raftsql_tpu.runtime.db import RaftDB
    from raftsql_tpu.runtime.pipe import RaftPipe
    from raftsql_tpu.transport.loopback import (LoopbackHub,
                                                LoopbackTransport)

    cfg = RaftConfig(num_groups=1, num_peers=peers,
                     tick_interval_s=0.0005, election_ticks=40,
                     heartbeat_ticks=4, log_window=64,
                     max_entries_per_msg=8,
                     lease_ticks=20, max_clock_skew=2)
    rates: dict = {}
    with tempfile.TemporaryDirectory(prefix="raftsql-bench-reads-") as d:
        hub = LoopbackHub()
        dbs = []
        for i in range(peers):
            pipe = RaftPipe.create(
                i + 1, peers, cfg, LoopbackTransport(hub),
                data_dir=os.path.join(d, f"raftsql-{i + 1}"))
            dbs.append(RaftDB(
                lambda g, i=i: SQLiteStateMachine(
                    os.path.join(d, f"db-{i}.db")),
                pipe, num_groups=1))
        try:
            assert dbs[0].propose(
                "CREATE TABLE t (v text)").wait(30.0) is None
            assert dbs[0].propose(
                "INSERT INTO t (v) VALUES ('x')").wait(30.0) is None
            deadline = time.monotonic() + 30.0
            lead = None
            while lead is None and time.monotonic() < deadline:
                lead = next((i for i, db in enumerate(dbs)
                             if db.pipe.node._last_role[0] == 2), None)
                if lead is None:
                    time.sleep(0.02)
            if lead is None:
                raise RuntimeError("no leader elected")
            ldb = dbs[lead]
            fdb = dbs[(lead + 1) % peers]
            sel = "SELECT count(*) FROM t"
            wm = ldb.watermark(0)

            def timed(fn) -> float:
                fn()                      # warm (lease round, caches)
                n = 0
                t0 = time.monotonic()
                while time.monotonic() - t0 < seconds:
                    fn()
                    n += 1
                return n / (time.monotonic() - t0)

            rates["local"] = round(timed(lambda: ldb.query(sel)), 1)
            rates["lease"] = round(timed(
                lambda: ldb.query(sel, mode="linear")), 1)
            # Same path with the lease fast path disabled (the seam the
            # engine itself uses when cfg.lease_ticks == 0): every read
            # pays the full quorum round.
            node = ldb.pipe.node
            saved = node.lease_read
            node.lease_read = lambda g: None
            try:
                rates["read_index"] = round(timed(
                    lambda: ldb.query(sel, mode="linear")), 1)
            finally:
                node.lease_read = saved
            rates["session"] = round(timed(
                lambda: ldb.query(sel, mode="session", watermark=wm)),
                1)
            rates["follower"] = round(timed(
                lambda: fdb.query(sel, mode="follower")), 1)

            # --- PR 12 rung: batched ReadIndex under concurrency.
            # Every pending linear read of a tick shares ONE quorum
            # round (runtime/node.py read_join; lease disabled so each
            # read takes the §6.4 path) — the serial read_index rung
            # above is the before number.
            node.lease_read = lambda g: None
            try:
                nthreads = 128
                counts = [0] * nthreads
                stop_at = time.monotonic() + seconds

                def rloop(i: int) -> None:
                    while time.monotonic() < stop_at:
                        ldb.query(sel, mode="linear")
                        counts[i] += 1
                threads = [threading.Thread(target=rloop, args=(i,),
                                            daemon=True)
                           for i in range(nthreads)]
                t0 = time.monotonic()
                for t in threads:
                    t.start()
                for t in threads:
                    t.join()
                dt = time.monotonic() - t0
                rates["read_index_mt128"] = round(sum(counts) / dt, 1)
            finally:
                node.lease_read = saved

            # --- PR 12 rungs: the shm worker plane vs the ring.  One
            # RingServer over the leader's RaftDB, two worker slots:
            # slot 0 maps the shared-memory snapshot (zero-round-trip
            # fast path), slot 1 runs with the plane off so every GET
            # pays the full ring round trip — same engine, same query,
            # the pair is the before/after of runtime/shm.py.
            from raftsql_tpu.runtime.ring import RingClient, RingServer
            ring = RingServer(ldb, os.path.join(d, "rings"), 2)
            ring.start()
            shm_c = ring_c = None
            try:
                shm_c = RingClient(os.path.join(d, "rings"), 0)
                os.environ["RAFTSQL_SHM_READS"] = "0"
                try:
                    ring_c = RingClient(os.path.join(d, "rings"), 1)
                finally:
                    del os.environ["RAFTSQL_SHM_READS"]
                rates["ring_local"] = round(timed(
                    lambda: ring_c.query(sel)), 1)
                rates["shm_local"] = round(timed(
                    lambda: shm_c.query(sel)), 1)
                rates["ring_session"] = round(timed(
                    lambda: ring_c.query(sel, mode="session",
                                         watermark=wm)), 1)
                rates["shm_session"] = round(timed(
                    lambda: shm_c.query(sel, mode="session",
                                        watermark=wm)), 1)
                rates["shm_linear"] = round(timed(
                    lambda: shm_c.query(sel, mode="linear")), 1)
                shm_stats = {"shm_hits": shm_c._shm_hits,
                             "shm_fallbacks": shm_c._shm_fallbacks}
            finally:
                for c in (shm_c, ring_c):
                    if c is not None:
                        c.close()
                ring.stop()

            m = node.metrics
            extras = {"reads_ladder": rates,
                      "lease_grants": m.lease_grants,
                      "lease_expiries": m.lease_expiries,
                      "lease_degrades": m.lease_degrades,
                      "read_index_batched": m.reads_read_index_batched,
                      "read_batch_hist": dict(m.read_batch_hist)}
            extras.update(shm_stats)
            _log(f"reads ladder: {rates}")
            return float(rates["lease"]), extras
        finally:
            for db in dbs:
                try:
                    db.close()
                except Exception:                   # noqa: BLE001
                    pass


def bench_latency_sweep(groups: int, peers: int, repeats: int) -> dict:
    """Propose→commit latency at light / half / saturating load.

    VERDICT r2 task 3: the <2ms p50 target (BASELINE.md) is a latency
    target, and a saturated-only benchmark measures queueing, not the
    engine floor.  Reports {load_label: {p50_ms, p99_ms, tick_ms}}.
    """
    sweep = {}
    # Long scans so tick_ms reflects the DEVICE tick cadence (the fixed
    # per-execution dispatch cost would otherwise inflate a short call's
    # apparent tick time); the commit crossing still lands in the first
    # few ticks and p50 = crossing_ticks x tick_ms.
    ticks = 256
    # Latency is a best-case target (<2 ms p50, BASELINE.md): measure at
    # a modest group count where the tick is fastest, and again at the
    # headline shape so the queueing story at scale is also on record.
    lat_groups = min(groups, int(os.environ.get("BENCH_LAT_GROUPS", "1024")))
    # BENCH_LAT_E > BENCH_E > 16: an explicitly-set BENCH_E still governs
    # the sweep (small-machine runs set it); only the *default* differs
    # from the headline's (which favors E=32 throughput).
    E = int(os.environ.get("BENCH_LAT_E",
                           os.environ.get("BENCH_E", "16")))
    for label, load in ((f"light_1_G{lat_groups}", 1),
                        (f"sat_{E}_G{lat_groups}", None),
                        (f"sat_{E}_G{groups}", "headline")):
        g = groups if load == "headline" else lat_groups
        ld = None if load in (None, "headline") else load
        if load == "headline" and groups == lat_groups:
            continue        # same shape as the sat_G{lat_groups} row
        _log(f"== latency @ {label} ==")
        st: dict = {}
        bench_throughput(g, peers, ticks, repeats, load=ld, stats=st, e=E)
        sweep[label] = st
    # p50-vs-G curve (VERDICT r4 task 4): sustained (saturating) load at
    # each rung of BENCH_LAT_CURVE — the scaling story for the <2 ms
    # target, not just one shape.  Off by default (costly on the cpu
    # development path); the parent's latency child turns it on for the device.
    curve_spec = os.environ.get("BENCH_LAT_CURVE", "")
    if curve_spec:
        curve = {}
        for g in (int(x) for x in curve_spec.split(",") if x):
            st = {}
            _log(f"== latency curve @ G={g} (sat, E={E}) ==")
            bench_throughput(g, peers, ticks, repeats, stats=st, e=E)
            curve[str(g)] = {k: st.get(k)
                             for k in ("p50_ms", "p99_ms", "tick_ms")}
        sweep["p50_vs_G"] = curve
    return sweep


def bench_elections(groups: int, peers: int, repeats: int) -> float:
    """BASELINE config 3: randomized leader election at G x P.

    Measures cold-start elections/sec: from a fresh (all-follower) state,
    ticks until every group has a leader, repeated; value = groups elected
    per second of device time.
    """
    import jax
    import jax.numpy as jnp

    from raftsql_tpu.config import LEADER, RaftConfig
    from raftsql_tpu.core.cluster import (cluster_step, empty_cluster_inbox,
                                          init_cluster_state)

    cfg = RaftConfig(num_groups=groups, num_peers=peers, log_window=64,
                     max_entries_per_msg=8, tick_interval_s=0.0)
    T = 4 * cfg.election_ticks

    @jax.jit
    def elect(seed):
        states = init_cluster_state(cfg, seed=0)
        # Re-randomize timers per repeat by folding the seed into rng.
        states = states._replace(tick=states.tick + seed)
        inboxes = empty_cluster_inbox(cfg)
        prop = jnp.zeros((cfg.num_peers, cfg.num_groups), jnp.int32)

        def body(carry, _):
            st, ib = carry
            st, ib, _ = cluster_step(cfg, st, ib, prop)
            return (st, ib), None

        (states, _), _ = jax.lax.scan(body, (states, inboxes), None,
                                      length=T)
        return jnp.sum(jnp.any(states.role == LEADER, axis=0))

    elected = int(elect(jnp.asarray(0, jnp.int32)))  # compile + check
    best = 0.0
    for r in range(repeats):
        t0 = time.perf_counter()
        elected = int(jax.block_until_ready(elect(jnp.asarray(r, jnp.int32))))
        dt = time.perf_counter() - t0
        _log(f"  elected {elected}/{groups} leaders in {dt:.3f}s "
             f"({T} ticks) -> {elected / dt:,.0f} elections/s")
        best = max(best, elected / dt)
    return best


def bench_commit_scan(groups: int, repeats: int) -> float:
    """BASELINE config 4: the commit-index kernel alone at 100k groups.

    Measures group-commit-scans/sec of `windowed_commit_index` (the full
    masked prefix scan over the term ring) on random match/ring state.
    """
    import jax
    import jax.numpy as jnp

    from raftsql_tpu.ops.commit_scan import windowed_commit_index

    W, P = 64, 5
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 4)
    log_len = jax.random.randint(ks[0], (groups,), 0, W, dtype=jnp.int32)
    match = jnp.minimum(
        jax.random.randint(ks[1], (groups, P), 0, W, dtype=jnp.int32),
        log_len[:, None])
    log_term = jax.random.randint(ks[2], (groups, W), 1, 4, dtype=jnp.int32)
    commit = jnp.maximum(log_len - 8, 0)
    term = jnp.full((groups,), 3, jnp.int32)
    is_leader = jnp.ones((groups,), bool)

    @jax.jit
    def kernel(match, log_term, log_len, commit, term):
        return windowed_commit_index(match, log_term, log_len, commit,
                                     term, is_leader, quorum=3, window=W)

    out = jax.block_until_ready(
        kernel(match, log_term, log_len, commit, term))
    iters = 50
    best = 0.0
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = kernel(match, log_term, log_len, commit, term)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        rate = groups * iters / dt
        _log(f"  {iters} x {groups}-group commit scans in {dt:.3f}s -> "
             f"{rate:,.0f} scans/s")
        best = max(best, rate)
    return best


def bench_multichip(ticks: int, repeats: int,
                    groups: int | None = None) -> float:
    """BASELINE config 5: groups sharded over the device mesh, peer
    message exchange riding `all_to_all` (parallel/sharded.py).
    `groups` overrides the shape for the G-scale ladder rungs."""
    import jax
    import jax.numpy as jnp

    from raftsql_tpu.config import RaftConfig
    from raftsql_tpu.core.cluster import (empty_cluster_inbox,
                                          init_cluster_state)
    from raftsql_tpu.parallel.sharded import (make_mesh,
                                              make_sharded_cluster_run,
                                              shard_cluster_arrays)

    n = len(jax.devices())
    pp = 2 if n % 2 == 0 and n > 1 else 1
    gg = n // pp
    if groups is None:
        groups = int(os.environ.get("BENCH_GROUPS", 8192 * gg))
    groups -= groups % gg
    cfg = RaftConfig(num_groups=groups, num_peers=2 * pp if pp > 1 else 3,
                     log_window=64, max_entries_per_msg=8,
                     tick_interval_s=0.0)
    mesh = make_mesh(pp, gg)
    _log(f"  mesh {pp}x{gg} over {n} devices, {groups} groups x "
         f"{cfg.num_peers} peers")
    states = init_cluster_state(cfg)
    inboxes = empty_cluster_inbox(cfg)
    full = jnp.full((ticks, cfg.num_peers, cfg.num_groups),
                    cfg.max_entries_per_msg, jnp.int32)
    states, inboxes = shard_cluster_arrays(mesh, states, inboxes)

    run = make_sharded_cluster_run(cfg, mesh, ticks)
    states, inboxes, c = run(states, inboxes, full * 0)   # warmup/elect
    states, inboxes, c = run(states, inboxes, full)
    jax.block_until_ready(c)

    best = 0.0
    for _ in range(repeats):
        t0 = time.perf_counter()
        states, inboxes, committed = run(states, inboxes, full)
        committed = int(jax.block_until_ready(committed))
        dt = time.perf_counter() - t0
        _log(f"  {committed} commits in {dt:.3f}s -> "
             f"{committed / dt:,.0f} commits/s")
        best = max(best, committed / dt)
    return best


def bench_pod_rung(procs: int, ticks: int) -> dict:
    """BENCH_POD_PROCS=N rung of BENCH_CONFIG=multichip: N real
    `raftsql_tpu.pod.dryrun --mode bench` processes form a pod on this
    box (the dry-run rung — each process replicates the device step on
    forced host CPU devices; the sharded durability and the per-tick
    TCP collective are real).  Throughput is host 0's commits/s —
    compute is replicated, so hosts don't sum — and pod_wait_ms_per_tick
    is the CROSS-HOST HOP COST: collective wait per tick, reported
    per host next to the device/durable phase shares, so the profile
    attributes what the pod barrier adds at N hosts."""
    import json as _json
    import shutil
    import socket as _socket
    import subprocess
    import sys as _sys
    import tempfile

    with _socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord = f"127.0.0.1:{s.getsockname()[1]}"
    tmp = tempfile.mkdtemp(prefix="bench-pod-")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = (os.path.dirname(os.path.abspath(__file__))
                         + os.pathsep + env.get("PYTHONPATH", ""))
    ticks = int(os.environ.get("BENCH_POD_TICKS", str(max(ticks, 60))))
    outs = [os.path.join(tmp, f"h{i}.json") for i in range(procs)]
    try:
        children = [subprocess.Popen(
            [_sys.executable, "-m", "raftsql_tpu.pod.dryrun",
             "--mode", "bench", "--procs", str(procs),
             "--proc-id", str(i),
             "--coord", coord if procs > 1 else "",
             "--data-dir", os.path.join(tmp, f"h{i}"),
             "--ticks", str(ticks), "--out", outs[i]],
            env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL) for i in range(procs)]
        for c in children:
            c.wait(timeout=600)
        docs = []
        for i, (c, o) in enumerate(zip(children, outs)):
            if c.returncode != 0 or not os.path.exists(o):
                return {"procs": procs,
                        "error": f"host {i} rc={c.returncode}"}
            with open(o, encoding="utf-8") as f:
                docs.append(_json.load(f))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    d0 = docs[0]
    rung = {"procs": procs, "ticks": ticks,
            "commits_per_s": d0["commits_per_s"],
            "pod_wait_ms_per_tick": [d["pod_wait_ms_per_tick"]
                                     for d in docs],
            "phase_ms_per_tick": d0["phase_ms_per_tick"],
            "bytes_tx": sum(d["pod"]["bytes_tx"] for d in docs)}
    if "phase_shares" in d0:
        rung["phase_shares"] = d0["phase_shares"]
    _log(f"  pod rung: {procs} hosts, {d0['commits_per_s']:,.0f} "
         f"commits/s, gather wait {rung['pod_wait_ms_per_tick']} ms/tick")
    return rung


def bench_durable(groups: int, peers: int, ticks: int, repeats: int):
    """The DURABLE product path: a real in-process RaftNode cluster —
    WAL fsync before send before publish (reference raft.go:227-235),
    loopback transport, KV apply — manually ticked in lockstep.

    VERDICT r2 task 2: the device-only headline skips the host runtime;
    this config measures what a user of the full framework gets.  Load
    is pre-queued (E per group per tick) so the feeder isn't timed.
    """
    import shutil
    import tempfile

    from raftsql_tpu.config import RaftConfig
    from raftsql_tpu.models.kv_sm import KVStateMachine
    from raftsql_tpu.models.sqlite_sm import SQLiteStateMachine
    from raftsql_tpu.runtime.node import RaftNode
    from raftsql_tpu.transport.loopback import LoopbackHub, LoopbackTransport

    E = 8
    cfg = RaftConfig(num_groups=groups, num_peers=peers, log_window=64,
                     max_entries_per_msg=E, tick_interval_s=0.0)
    tmp = tempfile.mkdtemp(prefix="bench-durable-")
    # BENCH_TRANSPORT=tcp: peer traffic rides real localhost sockets
    # through the binary codec — the DCN product path — instead of the
    # in-process loopback.
    if os.environ.get("BENCH_TRANSPORT") == "tcp":
        import socket as _socket

        from raftsql_tpu.transport.tcp import TcpTransport
        socks, urls = [], []
        for _ in range(peers):
            s = _socket.socket()
            s.bind(("127.0.0.1", 0))
            socks.append(s)
            urls.append(f"http://127.0.0.1:{s.getsockname()[1]}")
        for s in socks:
            s.close()
        transports = [TcpTransport(urls, i) for i in range(peers)]
    else:
        hub = LoopbackHub(codec=False)
        transports = [LoopbackTransport(hub) for _ in range(peers)]
    nodes = [RaftNode(i + 1, peers, cfg, transports[i],
                      os.path.join(tmp, f"n{i + 1}")) for i in range(peers)]
    # BENCH_SM=sqlite: the reference-parity apply engine (one SQLite
    # database per group, group-committed) instead of the in-memory KV —
    # the number then covers the FULL product stack.
    sm_kind = os.environ.get("BENCH_SM", "kv")
    if sm_kind == "sqlite":
        sms = [SQLiteStateMachine(os.path.join(tmp, f"sm-{g}.db"))
               for g in range(groups)]
        for g, sm in enumerate(sms):
            err = sm.apply("CREATE TABLE t (v text)", 0)
            assert err is None, err
        mk_cmd = "INSERT INTO t (v) VALUES ('x')"
    else:
        sms = [KVStateMachine() for _ in range(groups)]
        mk_cmd = "SET k v"

    def drain(n0: "RaftNode", apply: bool, t0q=None, lats=None) -> int:
        """Consume node 0's commit stream; apply; record wall-clock
        propose→apply latency by matching each group's applies (commit
        order) against its FIFO of propose timestamps (t0q)."""
        cnt = 0
        per_g: dict = {}
        while True:
            try:
                item = n0.commit_q.get_nowait()
            except Exception:
                break
            if item is None or not isinstance(item, tuple):
                continue
            from raftsql_tpu.runtime.db import _expand_commit_item
            for g, idx, cmd in _expand_commit_item(item, n0):
                if apply:
                    per_g.setdefault(g, []).append((cmd, idx))
                cnt += 1
        for g, items in per_g.items():
            fn = getattr(sms[g], "apply_batch", None)
            if fn is not None:
                errs = fn(items)
            else:
                errs = [sms[g].apply(cmd, idx) for cmd, idx in items]
            bad = [e for e in errs if e is not None]
            if bad:     # a commits/s number for failed applies is a lie
                raise RuntimeError(f"apply failed in group {g}: {bad[0]}")
        if t0q is not None and per_g:
            now = time.perf_counter()
            for g, items in per_g.items():
                q = t0q[g]
                for _ in range(min(len(items), len(q))):
                    lats.append(now - q.popleft())
        return cnt

    try:
        for n in nodes:
            n.start(threaded=False)
        # Elect every group: tick all nodes until each has a leader.
        import numpy as np
        for t in range(40 * cfg.election_ticks):
            for n in nodes:
                n.tick()
            hints = np.asarray(nodes[0].state.leader_hint)
            if t > cfg.election_ticks and (hints >= 0).all():
                break
        for n in nodes:
            if n.error is not None:   # e.g. a TCP bind lost to a racer
                raise RuntimeError(f"node {n.node_id} died during "
                                   f"warmup: {n.error}")
        hints = np.asarray(nodes[0].state.leader_hint)
        _log(f"  elected: {int((hints >= 0).sum())}/{groups} groups "
             f"after warmup")
        for n in nodes:     # drop compile/warmup skew from phase averages
            m = n.metrics
            m.ticks = 0
            m.t_stage_ms = m.t_device_ms = m.t_wal_ms = 0.0
            m.t_send_ms = m.t_publish_ms = 0.0
        best = 0.0
        repeat_rates: list = []
        # BENCH_DURABLE_ACTIVE=N: queue load at only the first N groups.
        # The durable tick's Python cost is proportional to ACTIVE groups
        # (vectorized masks give idle groups ~zero work, runtime/node.py
        # _wal_phase/_publish_phase); this knob separates "how many groups
        # can the host carry" (G) from "how many proposals/tick can it
        # push" (active * E) — at G=10k the saturated-everywhere point
        # measures Python object handling, not the runtime's scaling.
        active = int(os.environ.get("BENCH_DURABLE_ACTIVE", "0")) or groups
        active = min(active, groups)
        for _ in range(repeats):
            # Pre-queue ticks*E proposals per group at its leader.
            # kv keeps the original unique-key workload (comparable to
            # earlier recorded runs); sqlite uses one INSERT shape.
            if sm_kind == "sqlite":
                cmds = [mk_cmd.encode()] * (ticks * E)
            else:
                cmds = [f"SET k{i} v".encode() for i in range(ticks * E)]
            for g in range(active):
                h = int(hints[g])
                nodes[h if h >= 0 else 0].propose_many(g, cmds)
            drain(nodes[0], apply=False)        # discard warmup commits
            t0 = time.perf_counter()
            committed = 0
            for _ in range(ticks):
                for n in nodes:
                    n.tick()
                committed += drain(nodes[0], apply=True)
            dt = time.perf_counter() - t0
            rate = committed / dt
            m = nodes[0].metrics.snapshot()
            _log(f"  {committed} durable commits in {dt:.3f}s -> "
                 f"{rate:,.0f} commits/s ({dt / ticks * 1e3:.2f} ms/tick); "
                 f"phase_ms={m['phase_ms_per_tick']}")
            best = max(best, rate)
            repeat_rates.append(round(rate, 1))
        phase = nodes[0].metrics.snapshot()["phase_ms_per_tick"]

        # -- Latency phase (VERDICT r3 task 3): REAL wall-clock
        # propose→commit+apply per proposal, measured end to end on the
        # durable stack.  Load arrives at the service rate (E per group
        # per tick, the flow-control ceiling) instead of pre-queued, so
        # the number is pipeline latency, not backlog drain; the feeder
        # is the client and its cost is honestly on the clock.  The
        # active set is bounded so feeding doesn't dominate the tick.
        from collections import deque as _deque
        lat_active = min(active, int(os.environ.get(
            "BENCH_DURABLE_LAT_ACTIVE", "256")))
        lat_ticks = max(ticks, 16)
        t0q = [_deque() for _ in range(groups)]
        lats: list = []
        # Flush the throughput phase's in-flight pipeline tail BEFORE
        # arming timestamps: leftover commits would otherwise be matched
        # FIFO against the new t0s, shifting every sample early by the
        # pipeline depth.
        for _ in range(8):
            for n in nodes:
                n.tick()
            if drain(nodes[0], apply=True) == 0:
                break
        for t in range(lat_ticks):
            now = time.perf_counter()
            if sm_kind == "sqlite":
                cmds = [mk_cmd.encode()] * E
            else:
                cmds = [f"SET lat{t}_{i} v".encode() for i in range(E)]
            for g in range(lat_active):
                h = int(hints[g])
                nodes[h if h >= 0 else 0].propose_many(g, cmds)
                t0q[g].extend([now] * E)
            for n in nodes:
                n.tick()
            drain(nodes[0], apply=True, t0q=t0q, lats=lats)
        for _ in range(6):          # resolve the in-flight pipeline tail
            for n in nodes:
                n.tick()
            drain(nodes[0], apply=True, t0q=t0q, lats=lats)
        censored = sum(len(q) for q in t0q)
        lat_stats = None
        if lats:
            lats.sort()
            lat_stats = {
                "p50_ms": round(lats[int(0.5 * (len(lats) - 1))] * 1e3, 3),
                "p99_ms": round(lats[int(0.99 * (len(lats) - 1))] * 1e3, 3),
                "n": len(lats), "censored": censored,
                "active": lat_active, "load_per_tick": E}
            _log(f"  durable wall-clock latency ({lat_active} active, "
                 f"{E}/group/tick): p50={lat_stats['p50_ms']} ms "
                 f"p99={lat_stats['p99_ms']} ms over {len(lats)} acks, "
                 f"{censored} censored")
        return best, {"durable_phase_ms": phase,
                      "durable_tick_ms": round(sum(phase.values()), 3),
                      "durable_lat": lat_stats,
                      "repeat_rates": repeat_rates}
    finally:
        for n in nodes:
            try:
                n.stop()
            except Exception:
                pass
        shutil.rmtree(tmp, ignore_errors=True)


def bench_http(groups: int, seconds: float, clients: int,
               fused: bool = False, device: bool = False,
               workers: int = 0):
    """BASELINE config 1: the real cluster driven over HTTP.

    The reference's observable unit of work is HTTP PUT -> 204 after
    commit + apply (/root/reference/httpapi.go:38-49); this is the one
    configuration the reference actually ships (Procfile), measured end
    to end with concurrent keep-alive HTTP clients.  Two deployments:
      - fused=False: three server/main.py OS processes, TCP raft
        transport (the reference's literal shape);
      - fused=True: ONE --fused process — all peers co-located, one
        device program per tick, same per-peer WAL durability (the
        TPU-native shape; no cross-process hops on the commit path).
    device=True (fused only): the server child is started WITHOUT a
    platform pin, so it requires an accelerator (utils/device.py) — the
    FULL stack (HTTP -> consensus device step on TPU -> WAL fsync ->
    SQLite apply -> 204) in one process.  Only valid while nothing else
    holds the chip: this bench child itself must be pinned to the cpu.
    Reports req/s and true per-request wall-clock latency percentiles.
    """
    import http.client
    import shutil
    import socket
    import subprocess as sp
    import tempfile
    import threading

    def free_port() -> int:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    n_procs = 1 if fused else 3
    raft_ports = [free_port() for _ in range(3)]
    api_ports = [free_port() for _ in range(n_procs)]
    cluster = ",".join(f"http://127.0.0.1:{p}" for p in raft_ports)
    tmp = tempfile.mkdtemp(prefix="bench-http-")
    env = dict(os.environ)
    if device and fused:
        env.pop("JAX_PLATFORMS", None)     # unpinned: the chip or nothing
    else:
        env["JAX_PLATFORMS"] = "cpu"
    repo = os.path.dirname(os.path.abspath(__file__))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    logf = open(os.path.join(tmp, "servers.log"), "w")
    procs = []
    try:
        tick = os.environ.get("BENCH_HTTP_TICK", "0.005")
        engine = os.environ.get("BENCH_HTTP_ENGINE", "aio")
        if fused:
            procs.append(sp.Popen(
                [sys.executable, "-m", "raftsql_tpu.server.main",
                 "--fused", "--port", str(api_ports[0]),
                 "--groups", str(groups), "--tick", tick,
                 "--http-engine", engine]
                + (["--workers", str(workers)] if workers else []),
                cwd=tmp, env=env, stdout=logf, stderr=logf))
        else:
            for i in range(3):
                procs.append(sp.Popen(
                    [sys.executable, "-m", "raftsql_tpu.server.main",
                     "--cluster", cluster, "--id", str(i + 1),
                     "--port", str(api_ports[i]),
                     "--groups", str(groups), "--tick", tick,
                     "--http-engine", engine],
                    cwd=tmp, env=env, stdout=logf, stderr=logf))
        # Readiness: PUT blocks until commit+apply, so the first 204
        # proves election + full pipeline.  Schema per group.
        # Device servers pay chip init + one compile before the first
        # 204 can happen; triple the bring-up budget for that rung.
        deadline = time.monotonic() + (360 if device else 120)
        for g in range(groups):
            while True:
                if time.monotonic() > deadline:
                    with open(os.path.join(tmp, "servers.log")) as f:
                        tail = f.read()[-800:]
                    raise RuntimeError(
                        "cluster not ready in 120s; servers.log tail: "
                        + tail)
                try:
                    c = http.client.HTTPConnection("127.0.0.1",
                                                   api_ports[0], timeout=10)
                    try:
                        c.request("PUT", "/",
                                  body=b"CREATE TABLE t (v text)",
                                  headers={"X-Raft-Group": str(g)})
                        # 204 = created; 400 "already exists" = an
                        # earlier attempt (whose ack we missed to a
                        # client timeout) committed + applied — either
                        # way the full pipeline answered, i.e. the
                        # cluster is serving.
                        if c.getresponse().status in (204, 400):
                            break
                    finally:
                        c.close()
                except OSError:
                    pass
                time.sleep(0.5)
        _log(f"  cluster of {n_procs} ready ({groups} groups) on api "
             f"ports {api_ports}")

        # Load plane: the C++ epoll generator when the toolchain is up
        # (BENCH_HTTP_LOADGEN=python forces the thread-per-client
        # fallback).  The Python clients cost ~120-250us of interpreter
        # time per request ON THE SERVER'S CORES — at 192 clients they
        # are half the measured ceiling (3.9k vs 7.1k req/s, fused).
        loadgen = None
        if os.environ.get("BENCH_HTTP_LOADGEN", "native") == "native":
            from raftsql_tpu.native.build import build_http_load
            loadgen = build_http_load()
        if loadgen is not None:
            out = sp.run(
                [loadgen, str(seconds), str(clients), str(groups)]
                + [str(p) for p in api_ports],
                capture_output=True, text=True, timeout=seconds + 60)
            if out.returncode != 0:
                raise RuntimeError(f"http_load rc={out.returncode}: "
                                   f"{out.stderr[-400:]}")
            j = json.loads(out.stdout.strip())
            if not j["n"]:
                raise RuntimeError(
                    f"no successful PUTs ({j['errors']} errors)")
            got = None
            for p in api_ports:
                c = http.client.HTTPConnection("127.0.0.1", p, timeout=10)
                c.request("GET", "/", body=b"SELECT count(*) FROM t")
                r = c.getresponse()
                got = r.read().decode()
                assert r.status == 200, (r.status, got)
                c.close()
            rate = j["n"] / j["secs"]
            stats = {"p50_ms": j["p50_ms"], "p99_ms": j["p99_ms"],
                     "n": j["n"], "errors": j["errors"],
                     "clients": clients, "groups": groups,
                     "replica_rows": got.strip(),
                     "deploy": "fused-1proc" if fused else "3proc",
                     "loadgen": "native",
                     "req_per_s": round(rate, 1)}
            _log(f"  {j['n']} HTTP PUTs (native loadgen) in "
                 f"{j['secs']:.1f}s -> {rate:,.0f} req/s; "
                 f"p50={j['p50_ms']} ms p99={j['p99_ms']} ms, "
                 f"{j['errors']} errors")
            return rate, {"http_lat": stats}

        stop_at = time.monotonic() + seconds
        lats: list = []
        errs = [0]
        mu = threading.Lock()

        def client(ci: int) -> None:
            port = api_ports[ci % n_procs]
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
            my_lats = []
            my_errs = 0
            k = 0
            while time.monotonic() < stop_at:
                g = (ci + k) % groups
                k += 1
                t0 = time.perf_counter()
                try:
                    conn.request(
                        "PUT", "/",
                        body=f"INSERT INTO t (v) VALUES ('c{ci}_{k}')"
                        .encode(),
                        headers={"X-Raft-Group": str(g)})
                    ok = conn.getresponse()
                    ok.read()
                    if ok.status != 204:
                        my_errs += 1
                        continue
                except OSError:
                    my_errs += 1
                    conn = http.client.HTTPConnection("127.0.0.1", port,
                                                      timeout=30)
                    continue
                my_lats.append(time.perf_counter() - t0)
            with mu:
                lats.extend(my_lats)
                errs[0] += my_errs
            conn.close()

        threads = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        # Read-side spot check: every replica serves the (stale-ok) read.
        got = None
        for p in api_ports:
            c = http.client.HTTPConnection("127.0.0.1", p, timeout=10)
            c.request("GET", "/", body=b"SELECT count(*) FROM t")
            r = c.getresponse()
            got = r.read().decode()
            assert r.status == 200, (r.status, got)
            c.close()
        if not lats:
            raise RuntimeError(f"no successful PUTs ({errs[0]} errors)")
        lats.sort()

        def pct(p):
            return round(lats[int(p * (len(lats) - 1))] * 1e3, 3)

        rate = len(lats) / dt
        stats = {"p50_ms": pct(0.5), "p99_ms": pct(0.99),
                 "n": len(lats), "errors": errs[0], "clients": clients,
                 "groups": groups, "replica_rows": got.strip(),
                 "deploy": "fused-1proc" if fused else "3proc",
                 "req_per_s": round(rate, 1)}
        _log(f"  {len(lats)} HTTP PUTs in {dt:.1f}s -> {rate:,.0f} req/s; "
             f"p50={stats['p50_ms']} ms p99={stats['p99_ms']} ms, "
             f"{errs[0]} errors")
        return rate, {"http_lat": stats}
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                p.kill()
        logf.close()
        shutil.rmtree(tmp, ignore_errors=True)


def bench_georeads(seconds: float = 5.0, rtt_ms: float = 60.0,
                   sites: int = 4, threads_per_site: int = 2,
                   think_ms: float = 50.0):
    """BENCH_CONFIG=georeads: the read-replica tier scaling ladder.

    The geo model: `sites` client sites, each `rtt_ms` away from the
    write tier.  One fused engine publishes the shm delta stream
    (--replica-listen); up to 4 `python -m raftsql_tpu.replica`
    processes subscribe.  A site with a LOCAL replica reads session
    mode at zero injected latency; a site without one pays the
    upstream RTT per read (injected client-side — the engine is on
    this box).  Rungs N=1/2/4 replicas measure aggregate session
    reads/s across all sites with a fixed watermark workload: every
    rung converts far sites into near ones, so the ladder is the
    read-scaling story the tier exists for.  Clients are CLOSED-LOOP
    with a per-request think time — the geo win is latency avoided
    per read, and an open-loop hammer on a small shared box would
    measure CPU contention instead of it.  A replica REFUSAL (421)
    falls back to the write tier (paying the RTT) and is counted —
    fail-closed never subtracts from correctness, only from the rate.
    Headline = reads/s at the 4-replica rung.
    """
    import http.client
    import shutil
    import socket
    import subprocess as sp
    import tempfile
    import threading

    def free_port() -> int:
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        p = s.getsockname()[1]
        s.close()
        return p

    groups = int(os.environ.get("BENCH_GROUPS", "2"))
    max_replicas = 4
    api_port = free_port()
    stream_port = free_port()
    http_ports = [free_port() for _ in range(max_replicas)]
    tmp = tempfile.mkdtemp(prefix="bench-georeads-")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    repo = os.path.dirname(os.path.abspath(__file__))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    logf = open(os.path.join(tmp, "servers.log"), "w")
    procs = []
    rtt_s = rtt_ms / 1e3
    try:
        procs.append(sp.Popen(
            [sys.executable, "-m", "raftsql_tpu.server.main", "--fused",
             "--port", str(api_port), "--groups", str(groups),
             "--tick", "0.02", "--lease-ticks", "40",
             "--replica-listen", str(stream_port)],
            cwd=tmp, env=env, stdout=logf, stderr=logf))
        deadline = time.monotonic() + 120
        for g in range(groups):
            while True:
                if time.monotonic() > deadline:
                    with open(os.path.join(tmp, "servers.log")) as f:
                        tail = f.read()[-800:]
                    raise RuntimeError("engine not ready in 120s: " + tail)
                try:
                    c = http.client.HTTPConnection(
                        "127.0.0.1", api_port, timeout=10)
                    try:
                        c.request("PUT", "/",
                                  body=b"CREATE TABLE t (v text)",
                                  headers={"X-Raft-Group": str(g)})
                        if c.getresponse().status in (204, 400):
                            break
                    finally:
                        c.close()
                except OSError:
                    pass
                time.sleep(0.5)
        # The dataset + the session watermark each reader will carry.
        wm = ["0"] * groups
        for n in range(groups * 25):
            g = n % groups
            c = http.client.HTTPConnection("127.0.0.1", api_port,
                                           timeout=10)
            c.request("PUT", "/", body=f"INSERT INTO t VALUES ('v{n}')"
                      .encode(), headers={"X-Raft-Group": str(g)})
            r = c.getresponse()
            assert r.status == 204, (r.status, r.read())
            wm[g] = r.headers.get("X-Raft-Session", wm[g])
            c.close()
        # All four replicas boot once; each rung reads from a subset.
        for i in range(max_replicas):
            procs.append(sp.Popen(
                [sys.executable, "-m", "raftsql_tpu.replica",
                 "--upstream", f"127.0.0.1:{stream_port}",
                 "--port", str(http_ports[i]),
                 "--advertise", f"127.0.0.1:{http_ports[i]}"],
                cwd=tmp, env=env, stdout=logf, stderr=logf))
        deadline = time.monotonic() + 120
        for i in range(max_replicas):
            while True:
                if time.monotonic() > deadline:
                    with open(os.path.join(tmp, "servers.log")) as f:
                        tail = f.read()[-800:]
                    raise RuntimeError(
                        f"replica {i} not serving in 120s: " + tail)
                try:
                    c = http.client.HTTPConnection(
                        "127.0.0.1", http_ports[i], timeout=5)
                    try:
                        c.request("GET", "/",
                                  body=b"SELECT count(*) FROM t",
                                  headers={"X-Consistency": "session",
                                           "X-Raft-Session": wm[0],
                                           "X-Raft-Group": "0"})
                        if c.getresponse().status == 200:
                            break
                    finally:
                        c.close()
                except OSError:
                    pass
                time.sleep(0.3)
        _log(f"  engine + {max_replicas} replicas serving "
             f"({groups} groups, rtt={rtt_ms}ms)")

        think_s = think_ms / 1e3

        def site_reader(site: int, idx: int, n_replicas: int,
                        stop: list, out: list) -> None:
            near = site < n_replicas
            port = http_ports[site] if near else api_port
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=10)
            near_reads = far_reads = fallbacks = refusals = 0
            it = 0
            try:
                while not stop:
                    g = it % groups
                    it += 1
                    if not near:
                        time.sleep(rtt_s)   # the injected upstream hop
                    try:
                        conn.request(
                            "GET", "/", body=b"SELECT count(*) FROM t",
                            headers={"X-Consistency": "session",
                                     "X-Raft-Session": wm[g],
                                     "X-Raft-Group": str(g)})
                        st = conn.getresponse()
                        st.read()
                        status = st.status
                    except OSError:
                        conn.close()
                        conn = http.client.HTTPConnection(
                            "127.0.0.1", port, timeout=10)
                        continue
                    if status == 200:
                        if near:
                            near_reads += 1
                        else:
                            far_reads += 1
                    elif near and status == 421:
                        # Fail-closed replica: pay the trip upstream.
                        refusals += 1
                        time.sleep(rtt_s)
                        ec = http.client.HTTPConnection(
                            "127.0.0.1", api_port, timeout=10)
                        try:
                            ec.request(
                                "GET", "/",
                                body=b"SELECT count(*) FROM t",
                                headers={"X-Consistency": "session",
                                         "X-Raft-Session": wm[g],
                                         "X-Raft-Group": str(g)})
                            er = ec.getresponse()
                            er.read()
                            if er.status == 200:
                                fallbacks += 1
                        finally:
                            ec.close()
                    time.sleep(think_s)     # closed-loop client pacing
            finally:
                conn.close()
            out[idx] = (near_reads, far_reads, fallbacks, refusals)

        ladder: dict = {}
        detail: dict = {}
        best = 0.0
        for n_replicas in (1, 2, 4):
            stop: list = []
            out: list = [None] * (sites * threads_per_site)
            ts = []
            for site in range(sites):
                for k in range(threads_per_site):
                    idx = site * threads_per_site + k
                    ts.append(threading.Thread(
                        target=site_reader,
                        args=(site, idx, n_replicas, stop, out),
                        daemon=True))
            t0 = time.monotonic()
            for t in ts:
                t.start()
            time.sleep(seconds)
            stop.append(True)
            for t in ts:
                t.join(timeout=30)
            dt = time.monotonic() - t0
            rows = [r for r in out if r is not None]
            near_reads = sum(r[0] for r in rows)
            far_reads = sum(r[1] for r in rows)
            fallbacks = sum(r[2] for r in rows)
            refusals = sum(r[3] for r in rows)
            rate = (near_reads + far_reads + fallbacks) / dt
            best = max(best, rate)
            ladder[str(n_replicas)] = round(rate, 1)
            detail[str(n_replicas)] = {
                "reads_per_s": round(rate, 1),
                "replica_hits": near_reads, "upstream_reads": far_reads,
                "engine_fallbacks": fallbacks, "refusals": refusals,
                "near_sites": min(n_replicas, sites)}
            _log(f"  georeads rung N={n_replicas}: "
                 f"{rate:,.0f} reads/s ({near_reads} replica, "
                 f"{far_reads} upstream, {fallbacks} fallbacks, "
                 f"{refusals} refusals)")
        extras = {"georeads_ladder": ladder, "georeads": detail,
                  "injected_rtt_ms": rtt_ms, "think_ms": think_ms,
                  "sites": sites,
                  "threads_per_site": threads_per_site,
                  "cpu_count": os.cpu_count()}
        return float(ladder["4"]), extras
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:                       # noqa: BLE001
                p.kill()
        logf.close()
        shutil.rmtree(tmp, ignore_errors=True)


def bench_durable_fused(groups: int, peers: int, ticks: int, repeats: int,
                        runtime: str = "fused"):
    """The durable path on the FUSED runtime (runtime/fused.py): all P
    peers advance in ONE device program per tick, per-peer WAL fsync is
    the inter-dispatch barrier (save-before-send), KV apply off peer 0's
    commit stream.

    This is the TPU-shaped durable deployment: the per-node runtime pays
    one dispatch (and one readback) per peer per tick; the fused runtime
    pays one per CLUSTER per tick, so durable throughput scales with
    G x E per dispatch instead of with per-peer overhead.

    runtime="mesh" runs the SAME bench on the MESH runtime
    (runtime/mesh.py MeshClusterNode): the device step shard_map'd with
    G sharded over the widest groups-only mesh the visible devices
    allow, per-shard WAL dirs and per-shard publish workers — the
    multi-chip G-scale durable rung (groups is rounded down to a
    multiple of the shard count).
    """
    import shutil
    import tempfile
    from collections import deque as _deque

    from raftsql_tpu.config import RaftConfig
    from raftsql_tpu.models.kv_sm import KVStateMachine
    from raftsql_tpu.models.sqlite_sm import SQLiteStateMachine
    from raftsql_tpu.runtime.db import _expand_commit_item
    from raftsql_tpu.runtime.fused import PIPELINE_STEPS, FusedClusterNode

    E = int(os.environ.get("BENCH_E", "8"))
    mesh_cfg = None
    if runtime == "mesh":
        from raftsql_tpu.runtime.mesh import MeshConfig
        import jax as _jax
        gg = min(len(_jax.devices()), groups)
        groups -= groups % gg           # divisibility for the mesh
        mesh_cfg = MeshConfig(group_shards=gg)
    cfg = RaftConfig(num_groups=groups, num_peers=peers,
                     log_window=max(64, 4 * E),
                     max_entries_per_msg=E, tick_interval_s=0.0)
    tmp = tempfile.mkdtemp(prefix=f"bench-{runtime}-")
    # BENCH_SM=sqlite: the reference-parity apply engine (one SQLite
    # database per group, group-committed transactions) — the FULL
    # product stack on the fused runtime.  Default: the Python KV
    # state machine.  Either way the commit stream is drained off peer
    # 0's queue and applied here, as server.main's RaftDB does.
    sm_kind = os.environ.get("BENCH_SM", "kv")
    if sm_kind == "sqlite":
        sms = [SQLiteStateMachine(os.path.join(tmp, f"sm-{g}.db"))
               for g in range(groups)]
        for g, sm in enumerate(sms):
            err = sm.apply("CREATE TABLE t (v text)", 0)
            assert err is None, err
        mk_cmd = b"INSERT INTO t (v) VALUES ('x')"
    else:
        sms = [KVStateMachine() for _ in range(groups)]
        mk_cmd = None                      # kv: unique keys per batch

    from raftsql_tpu.runtime.db import (iter_plain_batches,
                                        iter_plain_entries)
    from raftsql_tpu.runtime.node import RAW_MANY, RAW_PLAIN

    def drain(node, apply: bool, t0q=None, lats=None) -> int:
        cnt = 0
        per_g: dict = {}
        q = node.commit_q(0)
        while True:
            try:
                item = q.get_nowait()
            except Exception:
                break
            if item is None or not isinstance(item, tuple):
                continue
            if item[0] is RAW_PLAIN or item[0] is RAW_MANY:
                # The fused publish batches per group (RAW_PLAIN) or per
                # tick (RAW_MANY): decode in place (runtime/db.py owns
                # the plain-payload contract) instead of expanding to
                # per-entry tuples first.
                for g, base, datas in iter_plain_batches(item):
                    if apply:
                        lst = per_g.setdefault(g, [])
                        for idx, cmd in iter_plain_entries(base, datas):
                            lst.append((cmd, idx))
                            cnt += 1
                    else:
                        cnt += sum(1 for d in datas if d)
                continue
            for g, idx, cmd in _expand_commit_item(item):
                if apply:
                    per_g.setdefault(g, []).append((cmd, idx))
                cnt += 1
        for g, items in per_g.items():
            for err in sms[g].apply_batch(items):
                if err is not None:
                    raise RuntimeError(f"apply failed g{g}: {err}")
        if t0q is not None and per_g:
            now = time.perf_counter()
            for g, items in per_g.items():
                fifo = t0q[g]
                for _ in range(min(len(items), len(fifo))):
                    lats.append(now - fifo.popleft())
        return cnt

    if mesh_cfg is not None:
        from raftsql_tpu.runtime.mesh import MeshClusterNode
        mesh = mesh_cfg.build()
        _log(f"  mesh durable: 1x{mesh_cfg.group_shards} devices, "
             f"{groups} groups ({groups // mesh_cfg.group_shards} per "
             f"shard), per-shard WAL dirs + publish workers")
        node = MeshClusterNode(cfg, tmp, mesh)
    else:
        # WAL group commit (PR 7): one shared log + one fsync per tick
        # for all P peers — the durable rung's default; 0 restores the
        # per-peer-file layout for A/Bs.
        # The dispatch the served node makes (server/main.py): the
        # pipeline's depth in one launch.
        node = FusedClusterNode(
            cfg, tmp,
            group_commit=os.environ.get(
                "BENCH_WAL_GROUP_COMMIT", "1") == "1",
            steps=PIPELINE_STEPS)
    node.publish_peers = {0}       # the drain consumes peer 0's stream
    try:
        for t in range(40 * cfg.election_ticks):
            node.tick()
            if t > cfg.election_ticks and (node._hints >= 0).all():
                break
        elected = int((node._hints >= 0).sum())
        _log(f"  fused: elected {elected}/{groups} groups "
             f"({node.metrics.ticks} warmup ticks)")
        m = node.metrics
        m.ticks = 0
        m.t_device_ms = m.t_wal_ms = m.t_publish_ms = 0.0
        active = int(os.environ.get("BENCH_DURABLE_ACTIVE", "0")) or groups
        active = min(active, groups)
        best = 0.0
        repeat_rates: list = []
        for _ in range(repeats):
            # Flush the previous repeat's in-flight tail (publish is
            # deferred one tick, commits lag ~3) so it cannot leak into
            # this repeat's timed window — then drop the idle flush
            # ticks from the phase averages (they would dilute
            # durable_tick_ms by ~20%).
            for _ in range(6):
                node.tick()
                drain(node, apply=False)
            m = node.metrics
            m.ticks = 0
            m.t_device_ms = m.t_wal_ms = m.t_publish_ms = 0.0
            # Backlog for the whole run: each multi-step dispatch
            # drains S x E per group, so scale by steps or the later
            # dispatches run empty and dilute the rate.
            per_g = ticks * E * node._steps
            cmds = ([mk_cmd] * per_g if mk_cmd is not None else
                    [f"SET k{i} v".encode() for i in range(per_g)])
            for g in range(active):
                node.propose_many(g, cmds)
            drain(node, apply=False)
            # Drain+apply rides the runtime's overlap hook: while the
            # device computes, the dispatch window is idle host time,
            # so the apply plane runs there for free (on a synchronous
            # backend it's equivalent to draining after tick()).
            applied = 0

            def hook():
                nonlocal applied
                applied += drain(node, apply=True)

            node.overlap_hook = hook
            t0 = time.perf_counter()
            for _ in range(ticks):
                node.tick()
            node.overlap_hook = None
            # Retire the async publisher's backlog: the rate counts a
            # commit only once it reached the apply plane.
            node.publish_flush()
            committed = applied + drain(node, apply=True)
            dt = time.perf_counter() - t0
            rate = committed / dt
            _log(f"  {committed} fused durable commits in {dt:.3f}s -> "
                 f"{rate:,.0f} commits/s ({dt / ticks * 1e3:.2f} ms/tick)")
            best = max(best, rate)
            repeat_rates.append(round(rate, 1))
        snap = node.metrics.snapshot()["phase_ms_per_tick"]
        phase = {k: snap[k] for k in ("device", "wal", "publish")}

        # Wall-clock propose→apply latency at the service rate.
        lat_active = min(active, int(os.environ.get(
            "BENCH_DURABLE_LAT_ACTIVE", "256")))
        lat_ticks = max(ticks, 16)
        t0q = [_deque() for _ in range(groups)]
        lats: list = []
        for _ in range(8):
            node.tick()
            if drain(node, apply=True) == 0:
                break

        for t in range(lat_ticks):
            now = time.perf_counter()
            cmds = ([mk_cmd] * E if mk_cmd is not None else
                    [f"SET lat{t}_{i} v".encode() for i in range(E)])
            for g in range(lat_active):
                node.propose_many(g, cmds)
                t0q[g].extend([now] * E)
            node.tick()
            drain(node, apply=True, t0q=t0q, lats=lats)
        for _ in range(6):
            node.tick()
            node.publish_flush()    # acks land via the async publisher
            drain(node, apply=True, t0q=t0q, lats=lats)
        censored = sum(len(q) for q in t0q)
        lat_stats = None
        if lats:
            lats.sort()
            lat_stats = {
                "p50_ms": round(lats[int(0.5 * (len(lats) - 1))] * 1e3, 3),
                "p99_ms": round(lats[int(0.99 * (len(lats) - 1))] * 1e3, 3),
                "n": len(lats), "censored": censored,
                "active": lat_active, "load_per_tick": E}
            _log(f"  fused durable latency: p50={lat_stats['p50_ms']} ms "
                 f"p99={lat_stats['p99_ms']} ms over {len(lats)} acks, "
                 f"{censored} censored")
        # On parallel hosts publish runs on its own worker, overlapped
        # with the next tick's device+wal phases — summing it into the
        # tick would double-count wall time the tick thread never spent.
        overlapped = node._host_parallel
        tick_ms = sum(v for k, v in phase.items()
                      if not (overlapped and k == "publish"))
        out = {"durable_mode": runtime, "durable_sm": sm_kind,
               "durable_steps": node._steps,
               "durable_phase_ms": phase,
               "durable_phase_overlap": overlapped,
               "durable_tick_ms": round(tick_ms, 3),
               "durable_lat": lat_stats,
               "repeat_rates": repeat_rates}
        # Tick-phase profile (PR 8, obs/prof.py, default on —
        # RAFTSQL_PROF=0 for the A/B): per-phase shares of tick time
        # (fsync vs dispatch vs publish) + the p50/p95/p99 window, so
        # the BENCH_*.json trajectory shows WHY a rung moved, not just
        # that it did.
        prof = getattr(node, "prof", None)
        if prof is not None:
            out["phase_profile"] = {**prof.shares(),
                                    "phases": prof.snapshot()}
        gcw = getattr(node, "_gcwal", None)
        if gcw is not None:
            out["wal_group_commits"] = gcw.group_commits
            out["wal_gc_batch_hist"] = {
                str(k): v for k, v in sorted(gcw.batch_hist.items())}
        if mesh_cfg is not None:
            out["mesh_group_shards"] = mesh_cfg.group_shards
            out["mesh_groups"] = groups
        return best, out
    finally:
        node.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def bench_rules_race(groups: int, peers: int, ticks: int, repeats: int
                     ) -> dict:
    """Race the three commit-advance kernels, small-P AND large-P.

    VERDICT r2 task 6 / r4 task 7: `point` (etcd maybeCommit shortcut),
    `windowed` (masked ring scan) and `pallas` (hand-written kernel),
    each its own jit (commit_rule is static config).  The pallas
    kernel's claimed regime is large peer counts (its O(P^2) comparison
    network vs XLA's sort, ops/pallas_quorum.py) — so the race runs the
    requested P and a P=15 shape; the large-P winner is the evidence
    for (or against) keeping the kernel as the large-P default.
    """
    out: dict = {}
    shapes = [(f"P{peers}", groups, peers)]
    big_p = int(os.environ.get("BENCH_RULES_BIG_P", "15"))
    if big_p > peers:
        # Same total work scale: G x P stays comparable.
        shapes.append((f"P{big_p}", max(groups * peers // big_p, 64),
                       big_p))
    # BENCH_RULES_SET splits the race across child processes: the
    # parent runs point+windowed in one child and pallas in another so
    # a pallas compile hang (observed: P=15 on the device) costs only
    # its own child's timeout, never the XLA rules' JSON.
    rules_set = tuple(
        r for r in os.environ.get(
            "BENCH_RULES_SET", "point,windowed,pallas").split(",") if r)
    for label, g, p in shapes:
        row = {}
        for rule in rules_set:
            _log(f"== commit_rule={rule} (G={g}, P={p}) ==")
            try:
                row[rule] = round(
                    bench_throughput(g, p, ticks, repeats,
                                     commit_rule=rule), 1)
            except Exception as e:                  # noqa: BLE001
                _log(f"  commit_rule={rule} FAILED: "
                     f"{type(e).__name__}: {e}")
                row[rule] = f"fault: {type(e).__name__}"
        out[label] = row
        _log(f"rules race {label}: {row}")
    return out


def run_config(config: str, cpu: bool):
    """Dispatch one BENCH_CONFIG; defaults scale down on cpu so the
    development path (BENCH_PLATFORM=cpu) finishes quickly.

    Returns (headline_value, extras_dict) — extras are merged into the
    child's JSON line for the driver/judge to record.  A rung that
    raised is named in extras["rung_faults"]; child_main turns a
    non-empty list into a non-zero exit code.
    """
    # cpu default 2048: measured 6.7M commits/s vs 5.4M at 4096 (E=32) —
    # the best CPU point, not a scaled copy of the TPU shape.
    groups = int(os.environ.get("BENCH_GROUPS", 2048 if cpu else 100_000))
    peers = int(os.environ.get("BENCH_PEERS", 3))
    ticks = int(os.environ.get("BENCH_TICKS", 120 if cpu else 400))
    repeats = int(os.environ.get("BENCH_REPEATS", 2 if cpu else 3))
    egroups = int(os.environ.get("BENCH_GROUPS", 2048 if cpu else 10_000))

    if config == "all":
        results = {}
        _log("== config 2: 1k x 3 quorum replication ==")
        results["quorum_1k_x3"] = bench_throughput(1000, 3, ticks, repeats)
        _log("== config 3: elections ==")
        results["elections"] = bench_elections(egroups, 5, repeats)
        _log("== config 4: commit scan ==")
        results["commit_scan"] = bench_commit_scan(
            20_000 if cpu else 100_000, repeats)
        _log("== config 5: mesh-sharded cluster ==")
        results["multichip"] = bench_multichip(ticks, repeats)
        _log("== headline: G x P saturated throughput ==")
        results["headline"] = bench_throughput(groups, peers, ticks, repeats)
        for k, v in results.items():
            _log(f"{k}: {v:,.0f}/s")
        return results["headline"], {}
    if config == "quorum":
        return bench_throughput(1000, 3, ticks, repeats), {}
    if config == "elections":
        return bench_elections(egroups, 5, repeats), {}
    if config == "commit_scan":
        return bench_commit_scan(groups, repeats), {}
    if config == "multichip":
        # Multi-chip G-scale ladder (MULTICHIP-style JSON): sweep total
        # group counts over the mesh, smallest first, and headline the
        # best rung — how far the pod takes G past the one-chip shape.
        import jax as _jax
        gg = max(1, len(_jax.devices()) // (
            2 if len(_jax.devices()) % 2 == 0
            and len(_jax.devices()) > 1 else 1))
        default = ",".join(str(g * gg) for g in (1024, 8192, 32768))
        rungs = [int(x) for x in os.environ.get(
            "BENCH_MESH_LADDER", default).split(",") if x]
        ladder: dict = {}
        faults: list = []
        best = 0.0
        for g in rungs:
            _log(f"== multichip rung G={g} ==")
            try:
                r = bench_multichip(ticks, repeats, groups=g)
                ladder[str(g)] = round(r, 1)
                best = max(best, r)
            except Exception as e:                  # noqa: BLE001
                _log(f"  multichip G={g} FAILED: "
                     f"{type(e).__name__}: {e}")
                ladder[str(g)] = f"fault: {type(e).__name__}"
                faults.append(f"multichip-G{g}")
        extras = {"mesh_ladder": ladder,
                  "mesh_devices": len(_jax.devices())}
        if faults:
            extras["rung_faults"] = faults
        # BENCH_POD_PROCS=N: the multi-host pod rung — N real dry-run
        # processes over the TCP collective, attributing the cross-host
        # hop cost per tick (bench_pod_rung).
        pod_procs = int(os.environ.get("BENCH_POD_PROCS", "0"))
        if pod_procs > 0:
            _log(f"== pod rung: {pod_procs} host processes ==")
            extras["pod"] = bench_pod_rung(pod_procs, ticks)
        return best, extras
    if config == "rules":
        out = bench_rules_race(groups, peers, ticks, repeats)
        vals = [v for row in out.values() for v in row.values()
                if isinstance(v, float)]
        extras = {"rules": out}
        faults = [f"rules-{label}-{rule}" for label, row in out.items()
                  for rule, v in row.items() if isinstance(v, str)]
        if faults:
            extras["rung_faults"] = faults
        return (max(vals) if vals else 0.0), extras
    if config == "latency":
        sweep = bench_latency_sweep(groups, peers, repeats)
        return (_light_row(sweep).get("p50_ms") or 0.0, {"lat": sweep})
    if config == "reads":
        return bench_reads(
            peers, seconds=float(os.environ.get("BENCH_READ_SECONDS",
                                                "2")))
    if config == "georeads":
        return bench_georeads(
            seconds=float(os.environ.get("BENCH_GEO_SECONDS", "5")),
            rtt_ms=float(os.environ.get("BENCH_GEO_RTT_MS", "60")),
            think_ms=float(os.environ.get("BENCH_GEO_THINK_MS", "50")))
    if config == "http":
        # Two rungs: 16 clients (the reference's concurrency scale,
        # raftsql_test.go:79-90 — a LATENCY point) and a high-concurrency
        # rung (throughput point: concurrent proposals amortize into one
        # tick batch; on a small host the bench clients share the
        # server's cores, so this is a lower bound).  Headline = the
        # better req/s; both rungs + cpu count ride the extras JSON.
        g = int(os.environ.get("BENCH_GROUPS", "8"))
        secs = float(os.environ.get("BENCH_HTTP_SECONDS", "10"))
        c16 = int(os.environ.get("BENCH_HTTP_CLIENTS", "16"))
        chi = int(os.environ.get("BENCH_HTTP_CLIENTS_HI", "192"))
        extras = {"cpu_count": os.cpu_count()}
        best = 0.0
        if c16 > 0:       # 0 skips a rung (engine/deployment A/Bs)
            rate16, ex16 = bench_http(g, secs, c16)
            extras["http_lat"] = ex16["http_lat"]
            best = rate16
        # Further rungs: high concurrency on the 3-process cluster,
        # then the --fused single-process deployment (the TPU-native
        # shape) at both client counts.  Each is caught so the others
        # still report; a caught rung is a rung fault (non-zero exit).
        rungs = [("http_lat_hi", chi, False, False, 0),
                 ("http_lat_fused", c16, True, False, 0),
                 ("http_lat_fused_hi", chi, True, False, 0)]
        # Multi-worker serving ladder (PR 7, runtime/ring.py): the
        # fused engine behind 1/2/4/8 SO_REUSEPORT HTTP worker
        # processes at high concurrency — the req/s-vs-workers scaling
        # story.  BENCH_HTTP_WORKERS_LADDER= (empty) skips it.
        for w in (int(x) for x in os.environ.get(
                "BENCH_HTTP_WORKERS_LADDER", "1,2,4,8").split(",")
                if x):
            rungs.append((f"http_workers_{w}", chi, True, False, w))
        if os.environ.get("BENCH_HTTP_DEVICE") == "1":
            # config-1 ON THE DEVICE: the fused server child starts
            # unpinned (it requires the chip), the full
            # HTTP -> device step -> WAL -> SQLite -> 204 stack.
            rungs.append(("http_lat_fused_tpu",
                          int(os.environ.get("BENCH_HTTP_CLIENTS_TPU",
                                             "192")), True, True, 0))
        ladder: dict = {}
        faults: list = []
        for key, clients, fused, device, workers in rungs:
            if clients <= 0:
                continue
            try:
                r, ex = bench_http(g, secs, clients, fused=fused,
                                   device=device, workers=workers)
                best = max(best, r)
                extras[key] = ex["http_lat"]
                if workers:
                    ladder[str(workers)] = round(r, 1)
            except Exception as e:                  # noqa: BLE001
                _log(f"  http rung {key} FAILED: {e}")
                extras[key] = {"error": str(e)}
                faults.append(key)
                if workers:
                    ladder[str(workers)] = f"fault: {e}"
        if ladder:
            extras["http_workers_ladder"] = ladder
        if faults:
            extras["rung_faults"] = faults
        return best, extras
    if config == "durable":
        # sqlite keeps one DB file (3 fds with -wal/-shm) per group: stay
        # well under the default open-files rlimit.
        default_g = (256 if os.environ.get("BENCH_SM") == "sqlite"
                     else 1000 if cpu else 10_000)
        dg = int(os.environ.get("BENCH_GROUPS", default_g))
        dticks = int(os.environ.get("BENCH_TICKS", 24))
        # Mode: "node" = 3 RaftNodes (per-peer dispatch, the distributed
        # runtime), "fused" = FusedClusterNode (one dispatch per cluster
        # tick).  Default: fused on an accelerator, node on cpu (keeps
        # the historical CPU rung comparable).
        mode = os.environ.get("BENCH_DURABLE_MODE",
                              "node" if cpu else "fused")
        if mode == "mesh":
            # The multi-chip durable rung: MeshClusterNode over the
            # widest groups-only mesh, per-shard WAL + publish workers.
            return bench_durable_fused(dg, peers, dticks,
                                       min(repeats, 2), runtime="mesh")
        if mode == "fused":
            return bench_durable_fused(dg, peers, dticks,
                                       min(repeats, 2))
        return bench_durable(dg, peers, dticks, min(repeats, 2))
    # headline: saturated throughput + the latency/load sweep.
    stats: dict = {}
    value = bench_throughput(groups, peers, ticks, repeats, stats=stats)
    extras = {"p50_sat_ms": stats.get("p50_ms"),
              "tick_ms": stats.get("tick_ms"),
              "repeat_rates": stats.get("repeat_rates"),
              "repeat_spread": stats.get("repeat_spread")}
    if os.environ.get("BENCH_SKIP_SWEEP") != "1":
        sweep = bench_latency_sweep(groups, peers, max(1, repeats - 1))
        extras["lat"] = sweep
        extras["p50_light_ms"] = _light_row(sweep).get("p50_ms")
    return value, extras


def _select_device() -> dict:
    """The one device rule (raftsql_tpu/utils/device.py), with the
    bench's explicit pin on top: BENCH_PLATFORM names the platform
    (development: `cpu`); unset, JAX_PLATFORMS decides; both unset, an
    accelerator is required and a CPU-only machine is an error."""
    want = os.environ.get("BENCH_PLATFORM", "")
    if want:
        os.environ["JAX_PLATFORMS"] = want      # before jax is imported
    from raftsql_tpu.utils.device import select_device
    return select_device()


def child_main() -> None:
    """One attempt: select the device, measure, print JSON.  Exit code
    1 when a rung raised (the JSON still names it in `rung_faults`)."""
    dev = _select_device()
    config = os.environ.get("BENCH_CONFIG", "headline")
    platform = dev["platform"]
    _log(f"bench[{config}]: platform={platform} "
         f"device_kind={dev['device_kind']} devices={dev['count']}")
    got = run_config(config, cpu=platform == "cpu")
    value, extras = got if isinstance(got, tuple) else (got, {})
    if config == "latency":
        # Latency headline: ms, lower is better; vs_baseline is the
        # ratio to the <2ms p50 north star (>=1 means target met).
        out = {
            "metric": "raft_propose_commit_p50_ms",
            "value": round(value, 3),
            "unit": "ms",
            "vs_baseline": round(2.0 / value, 4) if value > 0 else 0.0,
        }
    else:
        out = {
            "metric": "raft_commits_per_sec",
            "value": round(value, 1),
            "unit": "commits/s",
            "vs_baseline": round(value / NORTH_STAR_COMMITS_PER_SEC, 4),
        }
    from raftsql_tpu.utils.device import device_doc
    out.update({"platform": platform, "device_kind": dev["device_kind"],
                "devices": dev["count"],
                "peak_bytes_in_use": device_doc()["peak_bytes_in_use"]})
    out.update(extras)
    print(json.dumps(out))
    if out.get("rung_faults"):
        _log(f"bench[{config}]: rung faults: {out['rung_faults']}")
        sys.exit(1)


def probe_main() -> None:
    """Tiny child: report the device the rule selects (and that it can
    compute).  With no pin and no accelerator the rule exits non-zero
    with its sentence and this prints nothing."""
    dev = _select_device()
    import jax
    import jax.numpy as jnp

    jax.block_until_ready(jnp.ones((8, 8)) @ jnp.ones((8, 8)))
    print(json.dumps({"probe": dev["platform"],
                      "device_kind": dev["device_kind"],
                      "devices": dev["count"]}))


# ---------------------------------------------------------------------------
# Parent: bounded attempts, one child at a time; no chip, no result.
# ---------------------------------------------------------------------------


def _attempt(platform: str, timeout_s: float, extra_env: dict | None = None,
             label: str = "", mode: str = "1") -> dict | None:
    """Run one child attempt; return its parsed JSON dict or None.

    A failure is RECORDED (returncode / timeout / missing JSON is logged
    per attempt, so a device fault at one ladder shape localizes) and
    returned as None — the caller counts it against the exit code.  A
    child that printed its JSON but exited non-zero (a faulted rung)
    keeps its JSON, `rung_faults` included."""
    env = dict(os.environ, BENCH_CHILD=mode)
    if platform:
        env["BENCH_PLATFORM"] = platform
        env["JAX_PLATFORMS"] = platform
    if extra_env:
        env.update({k: str(v) for k, v in extra_env.items()})
    label = label or platform or "default"
    _log(f"bench parent: attempt[{label}] (timeout {timeout_s:.0f}s)")
    try:
        r = subprocess.run([sys.executable, os.path.abspath(__file__)],
                           env=env, stdout=subprocess.PIPE, text=True,
                           timeout=timeout_s)
    except subprocess.TimeoutExpired:
        _log(f"bench parent: attempt[{label}] TIMED OUT after "
             f"{timeout_s:.0f}s")
        return None
    for line in reversed((r.stdout or "").strip().splitlines()):
        try:
            parsed = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(parsed, dict) and ("metric" in parsed
                                         or "probe" in parsed):
            return parsed
    _log(f"bench parent: attempt[{label}] rc={r.returncode}, no JSON")
    return None


def _emit(parsed: dict) -> None:
    print(json.dumps(parsed))


def main() -> int:
    """Parent: fault-localizing attempt ladder; returns the exit code.

    Plan:
      1. Probe the device the rule selects, under a short timeout.
         Unless it reports `tpu`, stop: exit non-zero, print no result
         (BENCH_PLATFORM=cpu is the explicit development path).
      2. Run the G-ladder smallest-first (1k → 10k → 32k → 100k), each
         shape its own bounded child; retry failed shapes in a second
         pass; headline = best rung.
      3. Durable, HTTP, latency and rules children, one at a time, in
         budget priority order.  Host-runtime children are pinned to the
         cpu and their numbers carry their own platform label.
    Any child that failed, timed out or reported a faulted rung makes
    the exit code non-zero; the JSON of what did run is still printed.
    """
    timeout_s = float(os.environ.get("BENCH_ATTEMPT_TIMEOUT_S", "420"))
    failed: list = []           # labels of children that did not succeed

    def attempt(platform, t_s, extra_env=None, label=""):
        got = _attempt(platform, t_s, extra_env=extra_env, label=label)
        if got is None or got.get("rung_faults"):
            failed.append(label)
        return got

    def finish(parsed: dict) -> int:
        if failed:
            parsed["failed_attempts"] = failed
            _log(f"bench parent: FAILED attempts: {failed}")
        _emit(parsed)
        return 1 if failed else 0

    pinned = os.environ.get("BENCH_PLATFORM", "")
    if pinned:
        parsed = attempt(pinned, timeout_s, label=f"pinned-{pinned}")
        if parsed is None:
            _log("bench parent: pinned attempt failed")
            return 1
        return finish(parsed)

    # Overall wall budget: without it a run in which EVERY ladder child
    # times out would stretch the serial plan past the driver's own
    # deadline.
    budget_s = float(os.environ.get("BENCH_TOTAL_BUDGET_S", "1800"))
    t_start = time.monotonic()
    reserve = 90.0

    def remaining() -> float:
        return budget_s - (time.monotonic() - t_start)

    # -- 1. device probe: no chip, no result.
    probe_timeout = float(os.environ.get("BENCH_PROBE_TIMEOUT_S", "150"))
    probe = _attempt("", probe_timeout, label="probe", mode="probe")
    platform = (probe or {}).get("probe", "none")
    _log(f"bench parent: device probe = {probe}")
    if platform != "tpu":
        _log("bench parent: the probe did not report a tpu "
             f"(got {platform!r}); no result is produced.  "
             "BENCH_PLATFORM=cpu runs the development path on the CPU.")
        return 1

    ladder_env = os.environ.get("BENCH_LADDER", "1000,10000,32768,100000")
    ladder = [int(x) for x in ladder_env.split(",") if x]
    results: dict = {}
    faults: dict = {}
    # -- 2. TPU G-ladder, two passes, smallest shape first.
    for pass_no in range(2):
        for G in ladder:
            if G in results:
                continue
            if remaining() < reserve + 60:
                faults.setdefault(G, []).append(
                    f"pass{pass_no}:budget-exhausted")
                continue
            got = _attempt(
                "", min(timeout_s, remaining() - reserve),
                # No per-rung latency sweep: each extra shape costs
                # two more compiles inside the rung's timeout; one
                # dedicated latency child runs after the ladder.
                extra_env={"BENCH_GROUPS": G,
                           "BENCH_SKIP_SWEEP": "1",
                           "BENCH_TICKS": os.environ.get(
                               "BENCH_TICKS", "400")},
                label=f"tpu-G{G}-p{pass_no}")
            if got and got.get("value", 0) > 0:
                results[G] = got
            else:
                faults.setdefault(G, []).append(
                    f"pass{pass_no}:"
                    + ("no-json-or-crash" if got is None else "zero"))
        if len(results) == len(ladder):
            break
    failed.extend(f"tpu-G{G}" for G in ladder if G not in results)
    _log(f"bench parent: ladder results "
         f"{ {g: round(r['value'], 1) for g, r in results.items()} } "
         f"faults {faults}")
    if not results:
        _log("bench parent: no ladder rung produced a result")
        return 1

    # -- 3-tpu. durable-path child ON THE DEVICE (fused runtime: one
    # dispatch per cluster tick + per-peer WAL fsync barrier), right
    # after the ladder.
    durable_tpu = None
    if os.environ.get("BENCH_SKIP_DURABLE") != "1" \
            and remaining() > reserve + 120:
        durable_tpu = attempt(
            "", min(timeout_s, remaining() - reserve),
            extra_env={"BENCH_CONFIG": "durable",
                       "BENCH_DURABLE_MODE": "fused",
                       # Measured best host shape (CPU, C++ apply
                       # plane): E=64 beats 32 (768k vs 525k commits/s)
                       # and 128 (590k — WAL bytes dominate past the
                       # framing amortization).
                       "BENCH_E": os.environ.get("BENCH_E", "64")},
            label="durable-tpu-fused")

    # -- 3. durable-path child, host runtime with the step pinned to
    # the cpu: the per-peer RaftNode mode (history-comparable).
    durable = None
    if os.environ.get("BENCH_SKIP_DURABLE") != "1" \
            and remaining() > reserve + 120:
        durable = attempt(
            "cpu", min(timeout_s, remaining() - reserve),
            extra_env={"BENCH_CONFIG": "durable",
                       "BENCH_DURABLE_MODE": "node"},
            label="durable-cpu")

    # -- 3a. end-to-end HTTP child (BASELINE config 1): the 3-process
    # Procfile cluster over real HTTP PUT/GET — the one configuration
    # the reference actually ships.  Three processes cannot share one
    # chip, so this child and its servers are pinned to the cpu.
    httpc = None
    if os.environ.get("BENCH_SKIP_HTTP") != "1" \
            and remaining() > reserve + 150:
        # 2x the per-attempt timeout: the child measures two rungs
        # (16-client latency point + high-concurrency throughput point),
        # each with its own cluster bring-up.
        httpc = attempt(
            "cpu", min(2 * timeout_s, remaining() - reserve),
            extra_env={"BENCH_CONFIG": "http"}, label="http-cpu")

    # -- 3b. config-1 ON THE DEVICE: ONE fused server process that
    # takes the chip, driven over real HTTP — the full client-visible
    # stack with the consensus step on the chip.  The bench child that
    # drives it is pinned to the cpu (one process per chip).
    http_tpu = None
    if os.environ.get("BENCH_SKIP_HTTP") != "1" \
            and remaining() > reserve + 460:
        # The guard covers the rung's worst case (360s device bring-up
        # + measurement); launching with less would kill the child
        # mid-compile and burn the tail budget for zero evidence.
        http_tpu = attempt(
            "cpu", min(2 * timeout_s, remaining() - reserve),
            extra_env={"BENCH_CONFIG": "http", "BENCH_HTTP_DEVICE": "1",
                       "BENCH_HTTP_CLIENTS": "0",
                       "BENCH_HTTP_CLIENTS_HI": "0"},
            label="http-tpu-fused")

    # -- 3c. fused durable with the step pinned to the cpu.
    durable_fused = None
    if os.environ.get("BENCH_SKIP_DURABLE") != "1" \
            and remaining() > reserve + 120:
        durable_fused = attempt(
            "cpu", min(timeout_s, remaining() - reserve),
            extra_env={"BENCH_CONFIG": "durable",
                       "BENCH_DURABLE_MODE": "fused",
                       "BENCH_E": os.environ.get("BENCH_E", "64")},
            label="durable-cpu-fused")

    # -- 3d. latency child on the device: ONE small shape (G=1024, E=16)
    # where the 3-tick pipeline meets the <2 ms p50 target; its own
    # child so a fault cannot cost the headline and the ladder rungs
    # stay single-shape.
    latc = None
    if remaining() > reserve + 180 \
            and os.environ.get("BENCH_SKIP_SWEEP") != "1":
        latc = attempt(
            "", min(timeout_s, remaining() - reserve),
            extra_env={"BENCH_CONFIG": "latency", "BENCH_GROUPS": "1024",
                       "BENCH_REPEATS": "2",
                       "BENCH_LAT_CURVE": os.environ.get(
                           "BENCH_LAT_CURVE", "1000,10000,100000")},
            label="latency-G1024")

    # -- 3e. commit-rule race on the device (point vs windowed vs
    # pallas-compiled), at a mid-ladder shape so a kernel fault in one
    # rule cannot cost the headline.  Runs LAST of the children: the
    # headline, latency-target, and durable evidence all outrank it
    # under budget pressure.
    rules = None
    if remaining() > reserve + 240 \
            and os.environ.get("BENCH_SKIP_RULES") != "1":
        rules_g = min(max(results), 10_000)
        rules = attempt(
            "", min(timeout_s, remaining() - reserve),
            extra_env={"BENCH_CONFIG": "rules", "BENCH_GROUPS": rules_g,
                       "BENCH_TICKS": "200", "BENCH_REPEATS": "2",
                       "BENCH_RULES_SET": "point,windowed"},
            label=f"rules-G{rules_g}")
        # Pallas in its own child: a compile hang there (observed at
        # P=15 on the device) burns only this attempt's timeout.
        if remaining() > reserve + 240:
            pall = attempt(
                "", min(timeout_s // 2, remaining() - reserve),
                extra_env={"BENCH_CONFIG": "rules",
                           "BENCH_GROUPS": rules_g,
                           "BENCH_TICKS": "200", "BENCH_REPEATS": "2",
                           "BENCH_RULES_SET": "pallas"},
                label=f"rules-pallas-G{rules_g}")
            prow = (pall or {}).get("rules") or {}
            if rules and rules.get("rules"):
                for label, row in rules["rules"].items():
                    row.update(prow.get(label,
                                        {"pallas": "fault: no result"}))

    def host_child(parsed: dict, prefix: str, child) -> None:
        """Merge a durable child's numbers under `prefix`, with the
        platform ITS device step ran on beside them."""
        if not child:
            return
        parsed[f"{prefix}_commits_per_s"] = child.get("value")
        parsed[f"{prefix}_tick_ms"] = child.get("durable_tick_ms")
        parsed[f"{prefix}_lat"] = child.get("durable_lat")
        parsed[f"{prefix}_sm"] = child.get("durable_sm")
        parsed[f"{prefix}_platform"] = child.get("platform")

    # Headline = best commits/s across the ladder (the throughput
    # curve peaks near G=32k and flattens; "largest G that ran" was
    # leaving ~30% on the table), with the full ladder recorded.
    bestG = max(results, key=lambda g: results[g]["value"])
    parsed = results[bestG]
    parsed["headline_groups"] = bestG
    parsed["ladder"] = {
        str(g): (round(results[g]["value"], 1) if g in results
                 else "fault: " + ";".join(faults.get(g, ["?"])))
        for g in ladder}
    if rules:
        parsed["rules"] = rules.get("rules")
    if latc:
        parsed["lat"] = latc.get("lat")
        # 0.0 means "sweep measured nothing", not a passed target.
        parsed["p50_light_ms"] = latc.get("value") or None
    host_child(parsed, "durable", durable)
    host_child(parsed, "durable_fused", durable_fused)
    host_child(parsed, "durable_tpu", durable_tpu)
    if httpc:
        parsed["http_req_per_s"] = httpc.get("value")
        for k in ("http_lat", "http_lat_hi", "http_lat_fused",
                  "http_lat_fused_hi"):
            parsed[k] = httpc.get(k)
        parsed["http_cpu_count"] = httpc.get("cpu_count")
        parsed["http_platform"] = httpc.get("platform")
    if http_tpu:
        parsed["http_tpu_req_per_s"] = http_tpu.get("value")
        parsed["http_lat_fused_tpu"] = \
            http_tpu.get("http_lat_fused_tpu")
    return finish(parsed)


if __name__ == "__main__":
    mode = os.environ.get("BENCH_CHILD")
    if mode == "probe":
        probe_main()
    elif mode:
        child_main()
    else:
        sys.exit(main())
