"""FusedClusterNode — the durable co-located runtime (runtime/fused.py).

Covers: election + identical commit streams on every peer, the
durable-before-send barrier (every peer's WAL fsync between consecutive
device dispatches), crash-restart WAL replay with the nil-sentinel
protocol (reference raft.go:122-134, 131-132), and KV apply off the
commit stream.
"""
import os

import numpy as np
import pytest

import raftsql_tpu.runtime.fused as fused_mod
from raftsql_tpu.config import RaftConfig
from raftsql_tpu.models.kv_sm import KVStateMachine
from raftsql_tpu.runtime.db import _expand_commit_item
from raftsql_tpu.runtime.fused import FusedClusterNode
from raftsql_tpu.storage.wal import WAL


def mkcfg(groups=4):
    return RaftConfig(num_groups=groups, num_peers=3, log_window=32,
                      max_entries_per_msg=4, tick_interval_s=0.0)


def elect(node, max_ticks=200):
    for t in range(max_ticks):
        node.tick()
        if t > 10 and (node._hints >= 0).all():
            return
    raise AssertionError("no full leadership within budget")


def drain(node, peer):
    out, sentinels = [], 0
    q = node.commit_q(peer)
    while True:
        try:
            item = q.get_nowait()
        except Exception:
            break
        if item is None:
            sentinels += 1
            continue
        out.extend(_expand_commit_item(item))
    return out, sentinels


def test_fused_commits_identically_on_all_peers(tmp_path):
    cfg = mkcfg()
    node = FusedClusterNode(cfg, str(tmp_path))
    elect(node)
    for p in range(3):
        drain(node, p)                      # discard noops/sentinel
    for g in range(cfg.num_groups):
        node.propose_many(g, [f"SET k{i} g{g}".encode()
                              for i in range(10)])
    for _ in range(40):
        node.tick()
    streams = [drain(node, p)[0] for p in range(3)]
    assert len(streams[0]) == 4 * 10
    # Per-group total order is identical across replicas (§2d.1 — each
    # group is its own raft; cross-group interleave is unordered).
    for g in range(cfg.num_groups):
        per = [[(i, q) for (gg, i, q) in s if gg == g] for s in streams]
        assert per[0] == per[1] == per[2]
        assert len(per[0]) == 10
    node.stop()


def test_fused_durable_barrier_every_dispatch(tmp_path, monkeypatch):
    """Between any two consecutive device dispatches, every peer's WAL
    was fsynced — the fused analog of save-before-send (reference
    raft.go:227-235; the dispatch IS the send)."""
    events = []
    real_step = fused_mod.cluster_step_host
    real_sync = WAL.sync

    def spy_step(*a, **k):
        events.append("dispatch")
        return real_step(*a, **k)

    def spy_sync(self):
        events.append("sync")
        return real_sync(self)

    monkeypatch.setattr(fused_mod, "cluster_step_host", spy_step)
    monkeypatch.setattr(WAL, "sync", spy_sync)

    cfg = mkcfg(groups=2)
    node = FusedClusterNode(cfg, str(tmp_path))
    elect(node)
    node.propose_many(0, [b"SET a 1", b"SET b 2"])
    for _ in range(10):
        node.tick()
    node.stop()
    # Every inter-dispatch gap carries one sync per peer.
    gaps = " ".join(events).split("dispatch")
    for gap in gaps[1:-1]:                  # complete gaps only
        assert gap.count("sync") >= cfg.num_peers, events[:30]


@pytest.mark.parametrize("steps", [1, 4])
def test_fused_commits_publish_before_next_dispatch(tmp_path, monkeypatch,
                                                    steps):
    """A dispatch's commits reach the publish workers (_enqueue_publish)
    after its OWN durable barrier and before the NEXT dispatch's device
    call: every tick is dispatch -> barrier -> publish, in that order
    (PR 39 removed the double-buffered stash, which held a dispatch's
    durable phase and publish behind the next launch).  The regression
    guard against any such deferral coming back."""
    monkeypatch.setenv("RAFTSQL_FUSED_PARALLEL", "1")   # publish workers
    events = []                 # (what, the tick it belongs to)
    real_finish = FusedClusterNode._finish_durable
    nodes = []

    def spy_device(real):
        def call(*a, **k):
            events.append(("dispatch", nodes[0]._tick_no))
            return real(*a, **k)
        return call

    def spy_finish(self, step_infos, staged):
        got = real_finish(self, step_infos, staged)
        events.append(("barrier", self._prof_tick))
        return got

    monkeypatch.setattr(fused_mod, "cluster_step_host",
                        spy_device(fused_mod.cluster_step_host))
    monkeypatch.setattr(fused_mod, "cluster_multistep_host",
                        spy_device(fused_mod.cluster_multistep_host))
    monkeypatch.setattr(FusedClusterNode, "_finish_durable", spy_finish)

    cfg = mkcfg(groups=2)
    node = FusedClusterNode(cfg, str(tmp_path), steps=steps)
    nodes.append(node)
    real_enq = node._enqueue_publish

    def spy_enq(pinfo):
        events.append(("publish", node._prof_tick))
        return real_enq(pinfo)

    node._enqueue_publish = spy_enq
    elect(node)
    node.propose_many(0, [b"SET a 1", b"SET b 2"])
    for _ in range(10):
        node.tick()
    node.publish_flush()
    got = [q for (_g, _i, q) in drain(node, 0)[0]]
    node.stop()
    assert {"SET a 1", "SET b 2"} <= set(got)
    n = node._tick_no
    assert n >= 10
    assert events == [(what, k) for k in range(n)
                      for what in ("dispatch", "barrier", "publish")], \
        events[:12]


def test_fused_restart_replays_wal(tmp_path):
    cfg = mkcfg(groups=2)
    node = FusedClusterNode(cfg, str(tmp_path))
    elect(node)
    for g in range(2):
        node.propose_many(g, [f"SET k{i} g{g}".encode()
                              for i in range(6)])
    for _ in range(30):
        node.tick()
    live, sent = drain(node, 0)
    assert sent == 1                        # fresh boot: one nil sentinel
    assert len(live) == 12
    node.stop()

    def per_group(items):
        return {g: [(i, q) for (gg, i, q) in items if gg == g]
                for g in range(2)}

    node2 = FusedClusterNode(cfg, str(tmp_path))
    for p in range(3):
        rep, sent = drain(node2, p)
        # Replayed committed prefix arrives BEFORE the sentinel and
        # matches what was committed pre-crash (raftsql_test.go:138-146
        # counts replay via this protocol).
        assert sent == 1
        assert per_group(rep) == per_group(live)
    elect(node2)
    node2.propose_many(0, [b"SET post 1"])
    for _ in range(25):
        node2.tick()
    post, _ = drain(node2, 0)
    assert [q for (_, _, q) in post] == ["SET post 1"]
    node2.stop()


def test_fused_kv_apply_converges(tmp_path):
    cfg = mkcfg(groups=3)
    node = FusedClusterNode(cfg, str(tmp_path))
    elect(node)
    for p in range(3):
        drain(node, p)
    sms = {p: [KVStateMachine() for _ in range(cfg.num_groups)]
           for p in range(3)}
    for g in range(3):
        node.propose_many(g, [f"SET x{i} v{g}.{i}".encode()
                              for i in range(5)])
    for _ in range(30):
        node.tick()
    for p in range(3):
        items, _ = drain(node, p)
        for (g, idx, cmd) in items:
            assert sms[p][g].apply(cmd, idx) is None
    for g in range(3):
        assert sms[0][g]._data == sms[1][g]._data == sms[2][g]._data
        assert sms[0][g]._data["x4"] == f"v{g}.4"
    node.stop()


def test_fused_compaction_bounds_log_under_load(tmp_path):
    """Sustained load + periodic compact(): floors advance, the payload
    log's retained span stays bounded, and commits keep flowing
    (VERDICT r4 task 8 — the soak's invariant at test scale)."""
    cfg = RaftConfig(num_groups=4, num_peers=3, log_window=32,
                     max_entries_per_msg=8, tick_interval_s=0.0)
    node = FusedClusterNode(cfg, str(tmp_path))
    elect(node)
    for p in range(3):
        drain(node, p)
    committed = 0
    for round_no in range(12):
        for g in range(4):
            node.propose_many(g, [b"SET k v"] * 16)
        for _ in range(4):
            node.tick()
        committed += len(drain(node, 0)[0])
        node.compact(keep=32)
    assert committed >= 4 * 12 * 10        # load flowed throughout
    for g in range(4):
        floor = node.plogs[0].start(g)
        span = node.plogs[0].length(g) - floor
        assert floor > 0, f"g{g} floor never advanced"
        # keep(=W) + in-flight slack bounds the retained span.
        assert span <= 32 + 4 * 8 + 16, (g, span)
    # Restart: replay from the compacted WAL (floors + suffix) works.
    # Read the cursor AFTER stop(): it flushes the deferred publish of
    # the final tick, advancing applied one last time.
    node.stop()
    applied_before = int(node._applied[0][0])
    node2 = FusedClusterNode(cfg, str(tmp_path))
    rep, _ = drain(node2, 0)
    assert int(node2._applied[0][0]) == applied_before
    assert rep, "nothing replayed above the compaction floor"
    elect(node2)
    node2.propose_many(0, [b"SET post compaction"])
    for _ in range(25):
        node2.tick()
    post, _ = drain(node2, 0)
    assert any(q == "SET post compaction" for (_, _, q) in post)
    node2.stop()


def test_fused_pipe_raftdb_sql_stack(tmp_path, monkeypatch):
    """The --fused deployment's stack: FusedClusterNode -> FusedPipe ->
    RaftDB(SQLite) serves writes with blocking acks, local reads, and
    linearizable reads, in one process (server/main.py build_fused_node
    wiring, driven in-process here)."""
    monkeypatch.chdir(tmp_path)
    from raftsql_tpu.server.main import build_fused_node

    rdb = build_fused_node(groups=2, peers=3, tick=0.002)
    try:
        assert rdb.propose("CREATE TABLE t (v text)", 0).wait(30) is None
        assert rdb.propose("INSERT INTO t (v) VALUES ('x')",
                           0).wait(30) is None
        # Group isolation: group 1 has its own database.
        err = rdb.propose("INSERT INTO t (v) VALUES ('y')", 1).wait(30)
        assert err is not None          # no such table in group 1
        assert rdb.query("SELECT v FROM t", 0) == "|x|\n"
        # Linearizable read: single-controller cluster, leader commit
        # is the linearization point (runtime/fused.py read_index).
        assert rdb.query("SELECT count(*) FROM t", 0,
                         linear=True, timeout=30) == "|1|\n"
    finally:
        rdb.close()


def test_served_depth_acks_a_put_after_the_dispatch_that_accepted_it(
        tmp_path):
    """RaftDB over a fused node of the served depth (server/main.py
    passes PIPELINE_STEPS), ticked by hand: a PUT proposed before a
    dispatch is acknowledged after THAT dispatch's barrier, with no
    other launch in between, whichever peer leads its group, and a
    linear read after the ack sees it.  At one step a dispatch the
    same PUT needs three to four launches."""
    from raftsql_tpu.models.sqlite_sm import SQLiteStateMachine
    from raftsql_tpu.runtime.db import RaftDB
    from raftsql_tpu.runtime.fused import PIPELINE_STEPS, FusedPipe

    G = 6
    launches = {}
    for steps in (1, PIPELINE_STEPS):
        d = tmp_path / f"s{steps}"
        d.mkdir()
        node = FusedClusterNode(mkcfg(G), str(d / "raft"), seed=7,
                                steps=steps)
        rdb = RaftDB(lambda g, d=d: SQLiteStateMachine(str(d / f"g{g}.db")),
                     FusedPipe(node), num_groups=G)
        try:
            elect(node)
            # Peer 0, whose stream is applied, leads some groups and
            # not others: both kinds are held to the one launch.
            leaders = {int(h) for h in node._hints}
            assert 0 in leaders and leaders - {0}, node._hints
            futs = [rdb.propose("CREATE TABLE t (v text)", g)
                    for g in range(G)]
            for _ in range(8):
                node.tick()
            node.publish_flush()
            assert [f.wait(10) for f in futs] == [None] * G
            for _ in range(4):          # nothing in flight, no stash
                node.tick()
            node.publish_flush()
            futs = [rdb.propose(f"INSERT INTO t (v) VALUES ('g{g}')", g)
                    for g in range(G)]
            t0 = node.metrics.ticks
            acked: set = set()
            while len(acked) < G:
                node.tick()                 # one launch ...
                node.publish_flush()        # ... its barrier, its publish
                for g, f in enumerate(futs):
                    if g not in acked:
                        try:
                            assert f.wait(2.0 if steps > 1 else 0.05) \
                                is None
                            acked.add(g)
                        except TimeoutError:
                            pass
                assert node.metrics.ticks - t0 <= 8
            launches[steps] = node.metrics.ticks - t0
            for g in range(G):
                assert rdb.query("SELECT v FROM t", g, linear=True,
                                 timeout=10) == f"|g{g}|\n"
        finally:
            rdb.close()
    assert launches[PIPELINE_STEPS] == 1, launches
    assert launches[1] >= 3, launches


@pytest.mark.parametrize("steps,share", [(1, 0.0), (4, 100.0)],
                         ids=["1step", "4steps"])
def test_dispatch_readers_on_a_real_nodes_counters(tmp_path, monkeypatch,
                                                   steps, share):
    """benchmarks/layers/steps_per_dispatch.py and
    commit_in_dispatch_pct.py over the counters a node keeps
    (`dispatch.steps`, `intake.committed_in_dispatch`): one proposal a
    group a dispatch commits inside it at the pipeline's depth and
    never at one step; a parent's document makes both silent, and both
    are in the manifest for every cell."""
    import importlib
    import json
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.syspath_prepend(os.path.join(repo, "benchmarks"))
    depth = importlib.import_module("layers.steps_per_dispatch")
    inside = importlib.import_module("layers.commit_in_dispatch_pct")
    node = FusedClusterNode(mkcfg(), str(tmp_path), seed=7, steps=steps)

    def scrape():
        node.publish_flush()
        doc = dict(node.prof.counters_doc(), ticks=node.metrics.ticks)
        return {"t": 0.0, "engine": doc, "workers": []}
    try:
        elect(node)
        before = scrape()
        for r in range(5):
            for g in range(4):
                node.propose_many(g, [f"SET r{r} g{g}".encode()])
            node.tick()
        for _ in range(4):
            node.tick()
        after = scrape()
    finally:
        node.stop()
    assert depth.read(before, after, {}, None) == steps
    assert after["engine"]["intake"]["accepted"] \
        - before["engine"]["intake"]["accepted"] == 20
    assert inside.read(before, after, {}, None) == share
    # Nothing accepted in the window, or a program without the counters.
    assert inside.read(after, after, {}, None) is None
    old = {"t": 0.0, "engine": {"ticks": 9, "intake": {"accepted": 7}},
           "workers": []}
    new = {"t": 1.0, "engine": {"ticks": 19, "intake": {"accepted": 27}},
           "workers": []}
    assert depth.read(old, new, {}, None) is None
    assert inside.read(old, new, {}, None) is None
    with open(os.path.join(repo, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = [w["name"] for w in manifest["workloads"]]
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in ("steps_per_dispatch", "commit_in_dispatch_pct"):
        m = by_name[name]
        assert set(cells[:5]) <= set(m["workloads"])
        assert m["moves"] == "write_p50_ms"
        assert m["layer"] == "host plane tick (runtime/hostplane.py)"


def test_served_ticks_compile_nothing_after_warm_up(tmp_path,
                                                    monkeypatch):
    """The served fused node runs ONE program: after the boot, the
    elections and a first write, 50 more served ticks under writes add
    no entry to any jit entry point's cache (the benchmark's
    `window_compiles` guard)."""
    from raftsql_tpu.analysis.tripwire import JitTripwire
    from raftsql_tpu.runtime.fused import PIPELINE_STEPS
    from raftsql_tpu.server.main import build_fused_node

    monkeypatch.chdir(tmp_path)
    rdb = build_fused_node(groups=3, peers=3, tick=0.001)
    node = rdb.pipe.node
    try:
        assert node._steps == PIPELINE_STEPS
        assert rdb.propose("CREATE TABLE t (v text)", 0).wait(60) is None
        tw = JitTripwire()
        t0 = node.metrics.ticks
        n = 0
        while node.metrics.ticks - t0 < 50:
            assert rdb.propose(f"INSERT INTO t (v) VALUES ('{n}')",
                               0).wait(30) is None
            n += 1
        assert tw.compiles()["cluster_multistep_host"] == 0
        assert not any(tw.compiles().values()), tw.compiles()
    finally:
        rdb.close()


@pytest.mark.parametrize("S", [2, 3, 4])
def test_multistep_dispatch_equals_single_step_ticks(tmp_path, S):
    """A dispatch of S steps must be EXACTLY S single-step ticks: same
    consensus math (same seed), same durable bytes, same published
    commits — only the dispatch/barrier granularity changes.  Drives
    two clusters through the identical step sequence (proposals enter
    at dispatch boundaries in both) and compares hard states, payload
    logs, applied KV state, and a restart replay of the multi-step
    node's WALs."""
    cfg = mkcfg()
    a = FusedClusterNode(cfg, str(tmp_path / "single"), seed=11)
    b = FusedClusterNode(cfg, str(tmp_path / "multi"), seed=11, steps=S)
    try:
        # Same total warmup steps for both (b ticks S steps at a time).
        warm = 40 * cfg.election_ticks // S * S
        for _ in range(warm):
            a.tick()
        for _ in range(warm // S):
            b.tick()
        assert (a._hints >= 0).all() and (b._hints >= 0).all()
        assert (a._hints == b._hints).all()

        for r in range(6):
            for g in range(cfg.num_groups):
                cmds = [f"SET k{r}_{i} g{g}".encode() for i in range(3)]
                a.propose_many(g, cmds)
                b.propose_many(g, cmds)
            for _ in range(S):
                a.tick()
            b.tick()
        for _ in range(2 * S):
            a.tick()
        for _ in range(2):
            b.tick()

        # Identical device-visible state...
        assert (a._hard == b._hard).all()
        # ...identical durable payload bytes on every peer...
        for p in range(cfg.num_peers):
            for g in range(cfg.num_groups):
                assert a.plogs[p].length(g) == b.plogs[p].length(g)
                n = a.plogs[p].length(g)
                ta_, da_ = a.plogs[p].slice_columns(g, 1, n)
                tb_, db_ = b.plogs[p].slice_columns(g, 1, n)
                assert list(ta_) == list(tb_) and list(da_) == list(db_)
        # ...identical published commit streams (as applied KV state).
        def applied_state(node):
            node.publish_flush()    # the publish workers deliver async
            sms = [KVStateMachine() for _ in range(cfg.num_groups)]
            items, _ = drain(node, 0)
            for (g, idx, cmd) in items:
                assert sms[g].apply(cmd, idx) is None
            return [sm.snapshot() for sm in sms]
        assert applied_state(a) == applied_state(b)
    finally:
        a.stop()
        b.stop()

    # The multi-step node's WALs replay to the same state.
    c = FusedClusterNode(cfg, str(tmp_path / "multi"), seed=11, steps=S)
    try:
        assert (c._hard == b._hard).all()
    finally:
        c.stop()


def test_multistep_uncommitted_dispatch_dropped_on_restart(tmp_path):
    """Crash mid-barrier atomicity: a multi-step dispatch fsynced on
    SOME peers but never epoch-committed must vanish everywhere on
    restart — otherwise one peer could durably remember observing a
    message (vote grant, append) its sender never persisted, the
    classic two-leaders-in-one-term replay hazard."""
    S = 4
    cfg = mkcfg()
    d = str(tmp_path / "n")
    node = FusedClusterNode(cfg, d, seed=5, steps=S)
    try:
        elect(node)
        for g in range(cfg.num_groups):
            node.propose_many(g, [b"SET a 1", b"SET b 2"])
        for _ in range(4):
            node.tick()
        node.publish_flush()
        lens = [[node.plogs[p].length(g) for g in range(cfg.num_groups)]
                for p in range(cfg.num_peers)]
        hard = node._hard.copy()
        committed_epoch = node._epoch_no
        assert committed_epoch > 0       # multi-step framing was live
    finally:
        node.stop()

    # Simulate the crash: peer 0's WAL gains a complete dispatch frame
    # (BEGIN + entries + hard state + END) and even fsyncs it, but the
    # cluster epoch-commit never happened; peer 1 tore mid-frame
    # (BEGIN only).  Peer 2 wrote nothing.
    w0 = WAL(os.path.join(d, "p1"))
    w0.epoch_mark(committed_epoch + 1, end=False)
    w0.append_ranges([0], [lens[0][0] + 1], [1], [99], [b"SET z 9"])
    w0.set_hardstates(np.array([0]), np.array([99]), np.array([-1]),
                      np.array([lens[0][0] + 1]))
    w0.epoch_mark(committed_epoch + 1, end=True)
    w0.sync()
    w0.close()
    w1 = WAL(os.path.join(d, "p2"))
    w1.epoch_mark(committed_epoch + 1, end=False)
    w1.sync()
    w1.close()

    node2 = FusedClusterNode(cfg, d, seed=5)
    try:
        # The whole uncommitted dispatch is gone on every peer: same
        # payload lengths, same hard states as before the "crash".
        for p in range(cfg.num_peers):
            for g in range(cfg.num_groups):
                assert node2.plogs[p].length(g) == lens[p][g], (p, g)
        assert (node2._hard == hard).all()
        assert node2._hard.dtype == hard.dtype == np.int32
        assert node2._epoch_no == committed_epoch
    finally:
        node2.stop()


def test_first_multistep_dispatch_uncommitted_dropped(tmp_path):
    """ADVICE r5 high: a crash mid-barrier during the FIRST-ever
    multi-step dispatch of a data_dir leaves epoch-1 BEGIN-framed
    records durable on some peers with NO EPOCHS file (it is created
    lazily at commit).  Restart must still run epoch repair (committed
    epoch 0) and drop the frame everywhere — before the fix the
    repair was gated on EPOCHS existing, and a durable vote grant
    whose sender's state was lost would survive replay."""
    cfg = mkcfg()
    d = str(tmp_path / "n")
    # Peer 1 fsynced its whole epoch-1 frame (a vote at term 5 and an
    # entry); peer 2 tore mid-frame (BEGIN only); peer 3 wrote nothing.
    # No EPOCHS file exists — the commit fsync never happened.
    w0 = WAL(os.path.join(d, "p1"))
    w0.epoch_mark(1, end=False)
    w0.append_ranges([0], [1], [1], [5], [b"SET z 9"])
    w0.set_hardstates(np.array([0]), np.array([5]), np.array([1]),
                      np.array([0]))
    w0.epoch_mark(1, end=True)
    w0.sync()
    w0.close()
    w1 = WAL(os.path.join(d, "p2"))
    w1.epoch_mark(1, end=False)
    w1.sync()
    w1.close()

    node = FusedClusterNode(cfg, d, seed=5)
    try:
        # The whole uncommitted dispatch is gone on every peer: no
        # remembered vote/term, no appended entry.
        assert node._hard[0, 0, 0] == 0, "term from dropped frame"
        assert node._hard[0, 0, 1] == -1, "vote from dropped frame"
        assert node.plogs[0].length(0) == 0
        # The cluster still elects and serves afterwards.
        elect(node)
        node.propose_many(0, [b"SET post repair"])
        for _ in range(25):
            node.tick()
        post, _ = drain(node, 0)
        assert any(q == "SET post repair" for (_, _, q) in post)
    finally:
        node.stop()


def test_epoch_file_creation_fsyncs_directory(tmp_path):
    """ADVICE r5 medium: the first _commit_epoch creates EPOCHS and
    fsyncs its record, but the directory ENTRY must also be fsynced
    before the epoch counts as committed — otherwise a crash can drop
    the whole file while the peers' WAL bytes survive, and recovery
    misclassifies committed (published/acked) dispatches as
    uncommitted.  Crash simulation via the fsio event log: the
    data_dir fsync must directly follow the EPOCHS record fsync."""
    from raftsql_tpu.storage import fsio

    cfg = mkcfg(groups=2)
    d = str(tmp_path / "n")
    inj = fsio.StorageFaultInjector()
    with fsio.installed(inj):
        node = FusedClusterNode(cfg, d, seed=2, steps=2)
        try:
            elect(node)
            node.propose_many(0, [b"SET a 1"])
            for _ in range(6):
                node.tick()
            assert node._epoch_no > 0    # epoch framing was live
        finally:
            node.stop()
    epath = os.path.join(d, "EPOCHS")
    ev = inj.events
    first = next(i for i, (kind, p) in enumerate(ev)
                 if kind == "fsync" and p == epath)
    assert ev[first + 1] == ("fsync_dir", d), (
        "EPOCHS dirent not made durable before the epoch was treated "
        f"as committed: {ev[first:first + 3]}")


def test_epoch_commit_file_rotates_and_recovers(tmp_path):
    """The epoch-commit file keeps only what recovery needs: rotation
    rewrites it to the newest record once it crosses the threshold, and
    a restart reads the committed epoch back across rotations."""
    from raftsql_tpu.runtime.fused import _read_committed_epoch

    cfg = mkcfg(groups=2)
    d = str(tmp_path / "n")
    n = FusedClusterNode(cfg, d)
    n._EPOCH_ROTATE_BYTES = 60          # rotate every 5 records
    try:
        for i in range(23):
            n._commit_epoch(i + 1)
        n._epoch_no = 23
    finally:
        n.stop()
    path = os.path.join(d, "EPOCHS")
    assert os.path.getsize(path) <= 60  # bounded by rotation
    assert _read_committed_epoch(path) == 23
    n2 = FusedClusterNode(cfg, d)
    try:
        assert n2._epoch_no == 23
    finally:
        n2.stop()


def test_fused_crash_with_torn_tail_recovers(tmp_path):
    """Hard-crash recovery: no graceful stop (buffered frames lost), a
    torn half-record appended to one peer's active segment — replay
    repairs the tail and the cluster serves again with the durable
    prefix intact on every peer (storage-level repair wired end to
    end)."""
    cfg = mkcfg(groups=2)
    node = FusedClusterNode(cfg, str(tmp_path))
    elect(node)
    for g in range(2):
        node.propose_many(g, [f"SET k{i} g{g}".encode()
                              for i in range(5)])
    for _ in range(30):
        node.tick()
    live, _ = drain(node, 0)
    assert len(live) == 10
    # Crash: skip stop() entirely (pending publish + close are lost);
    # then tear peer 1's active segment with a half-written frame.
    segs = sorted((tmp_path / "p1").glob("wal-*.log"))
    with open(segs[-1], "ab") as f:
        f.write(b"\x12\x34\x56")                  # torn frame header
    del node

    node2 = FusedClusterNode(cfg, str(tmp_path))
    for p in range(3):
        rep, sent = drain(node2, p)
        assert sent == 1
        # Every fsynced commit survives; the torn bytes do not.
        per_g = {g: [q for (gg, _, q) in rep if gg == g]
                 for g in range(2)}
        for g in range(2):
            assert per_g[g] == [f"SET k{i} g{g}" for i in range(5)]
    elect(node2)
    node2.propose_many(0, [b"SET post crash"])
    for _ in range(25):
        node2.tick()
    post, _ = drain(node2, 0)
    assert any(q == "SET post crash" for (_, _, q) in post)
    node2.stop()
