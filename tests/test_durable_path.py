"""The one durable path (runtime/hostplane.py `_durable_phases`,
`_publish_shard`): plan -> leader appends -> two-pass mirror -> hard
states -> fsync barrier, on the fused runtime and on a two-shard mesh.

The first three tests hand the plane SCRIPTED steps (a packed info
[P, G, C] and a staged write plan made by hand) so that the cases an
election makes once in a long while happen on demand:

  - a group whose mirror SOURCE is also a mirror DEST in the same step
    (its old leader accepts, with a truncation, from its new one while a
    lagging peer still mirrors from the old one): the reads must see the
    source as the previous step left it;
  - a mirrored batch that crosses term boundaries: RANGE records are
    uniform-term, so it is split, and replays with each entry's term;
  - a commit beyond what the payload log holds: `_publish_shard` raises,
    it never delivers a short batch.

The next drives real ticks and holds the WAL's record order: within a
dispatch every entry record of a peer precedes its hard states, which
precede its fsync, at one step a dispatch and at two; a second node on
the data dir then reads back the hard states the first one held.

The last part is phase 2c alone, the hard states.  One compare for all
peers finds them (`_hard_changed`, `_save_hard`); the function it
replaced ran once a peer, and lives on here as the oracle: scripted
dispatches go to both, over WALs that list what they are handed, and
every call, `_hard`, `_wal_hard` and the return value must agree.  Then
a restart reads back what dispatches that changed hard states ONLY
wrote.
"""
import functools

import numpy as np
import pytest

from raftsql_tpu.config import RaftConfig
from raftsql_tpu.runtime.db import _expand_commit_item
from raftsql_tpu.runtime.fused import PIPELINE_STEPS, FusedClusterNode
from raftsql_tpu.runtime.hostplane import _C
from raftsql_tpu.runtime.mesh import MeshClusterNode, MeshConfig
from raftsql_tpu.storage.wal import (_HDR, _RANGE, REC_RANGE, WAL,
                                     _segment_paths)

GROUPS, PEERS, SHARDS = 4, 3, 2
SCRIPTED = (0, 3)           # one group in each mesh shard


def cfg_for(peers=PEERS, groups=GROUPS):
    return RaftConfig(num_groups=groups, num_peers=peers, seed=7,
                      log_window=32, max_entries_per_msg=4,
                      tick_interval_s=0.0)


def fused(data_dir, steps=1, **shape):
    return FusedClusterNode(cfg_for(**shape), data_dir, seed=3,
                            steps=steps)


def mesh(data_dir, **shape):
    return MeshClusterNode(
        cfg_for(**shape), data_dir,
        MeshConfig(peer_shards=1, group_shards=SHARDS).build(), seed=3)


RUNTIMES = pytest.mark.parametrize("make", [fused, mesh],
                                   ids=["fused", "mesh2"])


# -- scripted steps -------------------------------------------------------

def blank(node):
    """A step in which nothing happened: every hard state as it stands,
    no append accepted."""
    pinfo = np.zeros(node._hard.shape[:2] + (len(_C),), np.int32)
    pinfo[:, :, _C["term"]] = node._hard[:, :, 0]
    pinfo[:, :, _C["voted_for"]] = node._hard[:, :, 1]
    pinfo[:, :, _C["commit"]] = node._hard[:, :, 2]
    pinfo[:, :, _C["app_from"]] = -1
    return pinfo


def accept(pinfo, peer, g, src, start, n, new_len):
    """Peer `peer` accepted entries start..start+n-1 of g from `src`."""
    row = pinfo[peer, g]
    row[_C["app_from"]], row[_C["app_start"]] = src, start
    row[_C["app_n"]], row[_C["new_log_len"]] = n, new_len


def no_writes(peers=PEERS):
    return [([], [], [], [], []) for _ in range(peers)]


def tail_append(staged, peer, g, start, term, datas):
    """A leader append in the staged plan: one uniform-term range."""
    r_g, r_start, r_count, r_term, w_d = staged[peer]
    r_g.append(g)
    r_start.append(start)
    r_count.append(len(datas))
    r_term.append(term)
    w_d.extend(datas)


def log_of(node, peer, g):
    plog = node.plogs[peer]
    terms, datas = plog.slice_columns(g, 1, plog.length(g))
    return list(zip(terms, datas))


def range_records(node, peer, g):
    """(start, term, count) of every RANGE record peer `peer` wrote for
    group g, in file order (a mesh: the shard stream that owns g)."""
    w = node.wals[peer]
    w = w._shard(g) if hasattr(w, "shards") else w
    out = []
    for _, path in _segment_paths(w.dirname):
        with open(path, "rb") as f:
            blob = f.read()
        off = 0
        while off + _HDR.size <= len(blob):
            _, n = _HDR.unpack_from(blob, off)
            off += _HDR.size
            if blob[off] == REC_RANGE:
                _, gg, start, term, count = _RANGE.unpack_from(blob, off)
                if gg == g:
                    out.append((start, term, count))
            off += n
    return out


def replayed(make, data_dir):
    """Boot a second node on the data dir: every peer's payload log and
    peer 0's replayed commit stream, per scripted group."""
    node = make(data_dir)
    try:
        logs = {(p, g): log_of(node, p, g)
                for p in range(PEERS) for g in SCRIPTED}
        stream = {}
        q = node.commit_q(0)
        while True:
            item = q.get_nowait()
            if item is None:
                break
            for g, idx, cmd in _expand_commit_item(item):
                stream.setdefault(g, []).append((idx, cmd))
        return logs, stream
    finally:
        node.stop()


@RUNTIMES
def test_a_mirror_source_that_is_also_a_mirror_dest(make, tmp_path):
    node = make(str(tmp_path))
    try:
        # Before: peer 0 led term 1 and holds x, which nobody else has;
        # peer 1 leads term 2, its no-op n at index 3; peer 2 lags.
        staged = no_writes()
        for g in SCRIPTED:
            tail_append(staged, 0, g, 1, 1, [b"a", b"b", b"x"])
            tail_append(staged, 1, g, 1, 1, [b"a", b"b"])
            tail_append(staged, 1, g, 3, 2, [b"n"])
            tail_append(staged, 2, g, 1, 1, [b"a"])
        pinfo = blank(node)
        pinfo[:, :, _C["term"]] = 1
        pinfo[1, :, _C["term"]] = 2
        assert node._finish_durable([pinfo], [staged])

        # The step: peer 0 accepts n from peer 1 over x (a conflict
        # truncation INTO plog[0]) while peer 2 still accepts b, x FROM
        # plog[0], composed from what peer 0 held a step ago.
        pinfo = pinfo.copy()
        pinfo[0, :, _C["term"]] = 2
        for g in SCRIPTED:
            accept(pinfo, 0, g, src=1, start=3, n=1, new_len=3)
            accept(pinfo, 2, g, src=0, start=2, n=2, new_len=3)
        assert node._finish_durable([pinfo], [no_writes()])
        for g in SCRIPTED:
            assert log_of(node, 0, g) == [(1, b"a"), (1, b"b"), (2, b"n")]
            # What the device composed, not what peer 0 holds by now.
            assert log_of(node, 2, g) == [(1, b"a"), (1, b"b"), (1, b"x")]

        # Peer 2 hears of term 2; all commit a, b.
        pinfo = blank(node)
        pinfo[2, :, _C["term"]] = 2
        pinfo[:, :, _C["commit"]] = 2
        for g in SCRIPTED:
            accept(pinfo, 2, g, src=1, start=3, n=1, new_len=3)
        assert node._finish_durable([pinfo], [no_writes()])
        live = {(p, g): log_of(node, p, g)
                for p in range(PEERS) for g in SCRIPTED}
        for g in SCRIPTED:
            assert live[0, g] == live[1, g] == live[2, g] \
                == [(1, b"a"), (1, b"b"), (2, b"n")]
            assert range_records(node, 2, g) == [(1, 1, 1), (2, 1, 2),
                                                (3, 2, 1)]
    finally:
        node.stop()
    logs, stream = replayed(make, str(tmp_path))
    assert logs == live
    assert stream == {g: [(1, "a"), (2, "b")] for g in SCRIPTED}


@RUNTIMES
def test_a_mirrored_batch_across_terms_is_split_and_replays(make,
                                                            tmp_path):
    node = make(str(tmp_path))
    want = [(1, b"a"), (2, b"b"), (2, b"c"), (4, b"d")]
    try:
        staged = no_writes()
        for g in SCRIPTED:
            tail_append(staged, 0, g, 1, 1, [b"a"])
            tail_append(staged, 0, g, 2, 2, [b"b", b"c"])
            tail_append(staged, 0, g, 4, 4, [b"d"])
        pinfo = blank(node)
        pinfo[:, :, _C["term"]] = 4
        assert node._finish_durable([pinfo], [staged])
        pinfo = pinfo.copy()
        for g in SCRIPTED:
            accept(pinfo, 1, g, src=0, start=1, n=4, new_len=4)
        assert node._finish_durable([pinfo], [no_writes()])
        for g in SCRIPTED:
            assert log_of(node, 1, g) == want
            # One mirrored row, three uniform-term records.
            assert range_records(node, 1, g) == [(1, 1, 1), (2, 2, 2),
                                                (4, 4, 1)]
    finally:
        node.stop()
    logs, _ = replayed(make, str(tmp_path))
    for g in SCRIPTED:
        assert logs[0, g] == logs[1, g] == want
        assert logs[2, g] == []


@RUNTIMES
def test_publish_refuses_a_commit_beyond_the_payload_log(make, tmp_path):
    node = make(str(tmp_path))
    try:
        g = SCRIPTED[-1]
        staged = no_writes()
        tail_append(staged, 0, g, 1, 1, [b"a"])
        pinfo = blank(node)
        pinfo[:, :, _C["term"]] = 1
        assert node._finish_durable([pinfo], [staged])
        pinfo = pinfo.copy()
        pinfo[0, g, _C["commit"]] = 2          # the log holds one entry
        shard = next(j for j, sel in enumerate(node._shard_groups)
                     if sel is None or g in sel)
        with pytest.raises(RuntimeError,
                           match="payload log shorter than commit"):
            node._publish_shard(pinfo, shard)
        assert node.commit_q(0).qsize() == 1   # the boot sentinel alone
        assert node._applied[0, g] == 0
        # What the log does hold is delivered.
        pinfo[0, g, _C["commit"]] = 1
        node._publish_shard(pinfo, shard)
        assert node._applied[0, g] == 1
    finally:
        node.stop()


# -- record order, on real ticks ------------------------------------------

@pytest.mark.parametrize(
    "make,steps",
    [(fused, 1), (functools.partial(fused, steps=2), 2),
     (functools.partial(fused, steps=PIPELINE_STEPS), PIPELINE_STEPS),
     (mesh, 1)],
    ids=["fused-1step", "fused-2steps", "fused-4steps", "mesh2-1step"])
def test_hard_states_follow_every_entry_record_of_the_dispatch(
        make, steps, tmp_path, monkeypatch):
    events = {}

    def logged(kind, real):
        def call(self, *a, **kw):
            if kind == "mark":
                events.setdefault(id(self), []).append(
                    "end" if kw.get("end", a[-1]) else "begin")
            else:
                events.setdefault(id(self), []).append(kind)
            return real(self, *a, **kw)
        return call

    for kind, name in (("entries", "append_ranges"),
                       ("hard", "set_hardstates"), ("mark", "epoch_mark"),
                       ("sync", "sync")):
        monkeypatch.setattr(WAL, name, logged(kind, getattr(WAL, name)))
    node = make(str(tmp_path))
    try:
        assert node._steps == steps
        for t in range(400):
            node.tick()
            if t > 10 and (node._hints >= 0).all():
                break
        assert (node._hints >= 0).all()
        for r in range(6):
            for g in range(GROUPS):
                node.propose_many(g, [f"SET k{r} g{g}".encode()])
            node.tick()
        for _ in range(6):
            node.tick()
        node.publish_flush()
        assert all(node.plogs[p].length(g) >= 7
                   for p in range(PEERS) for g in range(GROUPS))
        hard = node._hard.copy()
    finally:
        node.stop()
    # Elections (votes a tick before any entry), appends and commit
    # advances: a second node reads back every hard state of them.
    node2 = make(str(tmp_path))
    try:
        assert node2._hard.dtype == hard.dtype == np.int32
        np.testing.assert_array_equal(node2._hard, hard)
    finally:
        node2.stop()
    both = 0
    for seq in events.values():
        dispatch = []
        for ev in seq + ["sync"]:
            if ev != "sync":
                dispatch.append(ev)
                continue
            body = [e for e in dispatch if e in ("entries", "hard")]
            # entries*, then hard*: never an entry record after a hard
            # state of the same dispatch.
            assert body == sorted(body), dispatch
            if steps > 1 and dispatch:
                # Epoch-framed: BEGIN first, END after the hard states.
                assert dispatch[0] == "begin" and dispatch[-1] == "end" \
                    and dispatch.count("begin") == 1, dispatch
            both += "entries" in body and "hard" in body
            dispatch = []
    assert both >= GROUPS       # the schedule had such dispatches


# -- phase 2c: one hard-state compare for all peers -----------------------

class Listing:
    """A WAL that lists the hard states and epoch marks it is handed
    and, where it stands in front of a real one, hands them on."""

    def __init__(self, real=None):
        self.real, self.calls = real, []

    def set_hardstates(self, groups, terms, votes, commits):
        self.calls.append(("hard",) + tuple(
            np.asarray(a).tolist()
            for a in (groups, terms, votes, commits)))
        if self.real is not None:
            self.real.set_hardstates(groups, terms, votes, commits)

    def epoch_mark(self, no, end):
        self.calls.append(("end" if end else "begin", no))
        if self.real is not None:
            self.real.epoch_mark(no, end=end)

    def __getattr__(self, name):
        return getattr(self.real, name)


class PerPeerOracle:
    """Phase 2c as the parent commit ran it (hostplane.py at bcfac11):
    `_save_hard(p, pinfo)` and `_ensure_epoch_begin(p)` statement for
    statement, int64 state and all, with what `_finish_durable` and
    `_durable_phases` did around them for a dispatch without entries."""

    def __init__(self, node):
        P = node.cfg.num_peers
        self.P = P
        self._hard = node._hard.astype(np.int64)
        self._wal_hard = [None] * P
        self.wals = [Listing() for _ in range(P)]
        self._epoch_no = node._epoch_no
        self._ep_active = False

    def _ensure_epoch_begin(self, p):
        if not self._ep_active or self._ep_begun[p]:
            return
        if self._ep_no_this is None:
            self._ep_no_this = self._epoch_no + 1
        self._ep_begun[p] = True
        self.wals[p].epoch_mark(self._ep_no_this, end=False)

    def _save_hard(self, p, pinfo):
        col = pinfo[p]
        hs = np.stack([col[:, _C["term"]], col[:, _C["voted_for"]],
                       col[:, _C["commit"]]], axis=1)
        changed = np.nonzero((hs != self._hard[p]).any(axis=1))[0]
        if not changed.size:
            return False
        self._ensure_epoch_begin(p)
        self.wals[p].set_hardstates(changed, hs[changed, 0],
                                    hs[changed, 1], hs[changed, 2])
        self._hard[p][changed] = hs[changed]
        self._wal_hard[p] = changed
        return True

    def dispatch(self, step_infos):
        pinfo = step_infos[-1]
        self._ep_active = len(step_infos) > 1
        if self._ep_active:
            self._ep_begun = [False] * self.P
            self._ep_no_this = None
        tick_active = False
        for p in range(self.P):
            tick_active = self._save_hard(p, pinfo) or tick_active
        if self._ep_active:
            for p in range(self.P):
                if self._ep_begun[p]:
                    self.wals[p].epoch_mark(self._ep_no_this, end=True)
            if self._ep_no_this is not None:
                self._epoch_no = self._ep_no_this
        self._ep_active = False
        return tick_active


def nothing_changed(node, rng):
    yield [blank(node)]


def one_row_of_one_peer(node, rng):
    P, G = node._hard.shape[:2]
    pinfo = blank(node)
    pinfo[P - 1, G // 2, _C["commit"]] += 3
    yield [pinfo]
    yield [pinfo.copy()]                # and nothing the step after


def every_row_of_every_peer(node, rng):
    pinfo = blank(node)
    pinfo[:, :, _C["term"]] += 1
    yield [pinfo]
    pinfo = pinfo.copy()                # each column on its own, too
    pinfo[:, :, _C["voted_for"]] = 1
    yield [pinfo]
    pinfo = pinfo.copy()
    pinfo[:, :, _C["commit"]] += 2
    yield [pinfo]


def a_vote_of_minus_one(node, rng):
    P, G = node._hard.shape[:2]
    pinfo = blank(node)
    pinfo[:, :, _C["term"]] = 1
    pinfo[:, :, _C["voted_for"]] = P - 1
    yield [pinfo]
    # A higher term is heard of before any vote in it: the vote of a
    # peer goes back to -1, a value like any other.
    pinfo = pinfo.copy()
    pinfo[0, :, _C["term"]] = 2
    pinfo[0, :, _C["voted_for"]] = -1
    pinfo[1, G - 1, _C["voted_for"]] = -1
    yield [pinfo]


def a_change_of_role_alone(node, rng):
    pinfo = blank(node)
    pinfo[:, :, _C["role"]] = 2
    pinfo[:, :, _C["leader_hint"]] = 1
    pinfo[:, :, _C["lease"]] = 9
    yield [pinfo]


def two_steps_with_epochs(node, rng):
    P, G = node._hard.shape[:2]
    # Only the FINAL step's hard states are saved: what step one shows
    # of peer 0 is gone by step two, where the last peer alone differs
    # from what its WAL holds.  BEGIN for it, nothing for the others.
    first, final = blank(node), blank(node)
    first[0, :, _C["term"]] += 5
    final[P - 1, G - 1, _C["term"]] += 1
    final[P - 1, 0, _C["commit"]] += 1
    yield [first, final]
    # A second dispatch: every peer, so every peer is framed; then one
    # in which nothing changed frames nobody and commits no epoch.
    first, final = final.copy(), final.copy()
    final[:, 0, _C["voted_for"]] = 0
    yield [first, final]
    yield [final.copy(), final.copy()]


def a_seeded_run(node, rng):
    P, G = node._hard.shape[:2]
    pinfo = blank(node)
    for rows in (1, 0, 5, max(G // 3, 1), 2, P * G):
        pinfo = pinfo.copy()
        pinfo[:, :, _C["role"]] = rng.integers(0, 3, (P, G))
        at = rng.choice(P * G, size=min(rows, P * G), replace=False)
        pp, gg = np.unravel_index(at, (P, G))
        col = rng.choice([_C["term"], _C["voted_for"], _C["commit"]],
                         size=at.size)
        pinfo[pp, gg, col] += rng.integers(-1, 3, at.size).astype(
            np.int32)                   # a 0 among them: no change
        pinfo[:, :, _C["voted_for"]] = np.clip(
            pinfo[:, :, _C["voted_for"]], -1, P - 1)
        yield [pinfo]


def shaped(runtime, name, peers, groups):
    return pytest.param(
        lambda data_dir: runtime(data_dir, peers=peers, groups=groups),
        id=f"{name}-P{peers}-G{groups}")


def as_the_tpu_hands_it_over(pinfo):
    """The same packed info with G as its minor axis, [P][C][G] in
    memory: what `jax.device_get` returns on the TPU (strides
    640000, 4, 40000 at G=10,000), where the CPU backend's is row-major.
    A view that reinterprets rows passes every test here and cannot
    run there."""
    return np.ascontiguousarray(pinfo.transpose(0, 2, 1)).transpose(0, 2, 1)


@pytest.mark.parametrize("layout", [np.asarray, as_the_tpu_hands_it_over],
                         ids=["row-major", "G-minor"])
@pytest.mark.parametrize("script", [
    nothing_changed, one_row_of_one_peer, every_row_of_every_peer,
    a_vote_of_minus_one, a_change_of_role_alone, two_steps_with_epochs,
    a_seeded_run], ids=lambda f: f.__name__)
@pytest.mark.parametrize("make", [
    shaped(fused, "fused", 3, 1), shaped(fused, "fused", 3, 7),
    shaped(fused, "fused", 5, 7), shaped(fused, "fused", 3, 10_000),
    shaped(mesh, "mesh2", 3, 4), shaped(mesh, "mesh2", 5, 4),
    shaped(mesh, "mesh2", 3, 10_000)])
def test_one_compare_for_all_peers_is_the_per_peer_save(make, script,
                                                        layout, tmp_path):
    node = make(str(tmp_path))
    try:
        P = node.cfg.num_peers
        # With the telemetry plane off nothing takes `_wal_hard` away
        # before it is read here (`_wal_counts` would).
        node.prof = None
        node.wals = [Listing(w) for w in node.wals]
        oracle = PerPeerOracle(node)
        wrote = 0
        for step_infos in script(node, np.random.default_rng(31)):
            node._wal_hard = [None] * P
            oracle._wal_hard = [None] * P
            was = [len(w.calls) for w in node.wals]
            got = node._finish_durable(
                [layout(pi) for pi in step_infos],
                [no_writes(P) for _ in step_infos])
            assert got == oracle.dispatch(step_infos)
            for p in range(P):
                # The same calls in the same order: groups, terms,
                # votes, commits of every row, BEGIN before a peer's
                # first record, no mark for a peer that wrote nothing.
                assert node.wals[p].calls == oracle.wals[p].calls, p
                wrote += len(node.wals[p].calls) - was[p]
                if oracle._wal_hard[p] is None:
                    assert node._wal_hard[p] is None, p
                else:
                    np.testing.assert_array_equal(node._wal_hard[p],
                                                  oracle._wal_hard[p])
            np.testing.assert_array_equal(node._hard, oracle._hard)
            assert node._epoch_no == oracle._epoch_no
        assert node._hard.dtype == np.int32     # the packed info's
        assert node._hard.shape == oracle._hard.shape
        if script in (nothing_changed, a_change_of_role_alone):
            assert wrote == 0
        else:
            assert wrote
    finally:
        node.stop()


@RUNTIMES
def test_a_restart_reads_back_hard_states_written_without_entries(
        make, tmp_path):
    node = make(str(tmp_path))
    try:
        # One entry a scripted group at peer 0, so that a commit has
        # something to stand on.
        staged = no_writes()
        for g in SCRIPTED:
            tail_append(staged, 0, g, 1, 1, [b"a"])
        pinfo = blank(node)
        pinfo[:, :, _C["term"]] = 1
        pinfo[:, :, _C["voted_for"]] = 0
        assert node._finish_durable([pinfo], [staged])
        # An election with no entry: terms and votes alone.
        pinfo = pinfo.copy()
        pinfo[:, :, _C["term"]] = 2
        pinfo[:, :, _C["voted_for"]] = 1
        pinfo[2, :, _C["voted_for"]] = -1
        assert node._finish_durable([pinfo], [no_writes()])
        # A commit advance with no append.
        pinfo = pinfo.copy()
        for g in SCRIPTED:
            pinfo[0, g, _C["commit"]] = 1
        assert node._finish_durable([pinfo], [no_writes()])
        assert not node._finish_durable([pinfo.copy()], [no_writes()])
        hard = node._hard.copy()
        assert hard.dtype == pinfo.dtype == np.int32
        assert (hard[:, :, 0] == 2).all() and hard[0, SCRIPTED[0], 2] == 1
    finally:
        node.stop()
    node2 = make(str(tmp_path))
    try:
        assert node2._hard.dtype == hard.dtype
        np.testing.assert_array_equal(node2._hard, hard)
    finally:
        node2.stop()
