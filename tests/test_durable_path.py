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

The last drives real ticks and holds the WAL's record order: within a
dispatch every entry record of a peer precedes its hard states, which
precede its fsync, at one step a dispatch and at two.
"""
import numpy as np
import pytest

from raftsql_tpu.config import RaftConfig
from raftsql_tpu.runtime.db import _expand_commit_item
from raftsql_tpu.runtime.fused import FusedClusterNode
from raftsql_tpu.runtime.hostplane import _C
from raftsql_tpu.runtime.mesh import MeshClusterNode, MeshConfig
from raftsql_tpu.storage.wal import (_HDR, _RANGE, REC_RANGE, WAL,
                                     _segment_paths)

GROUPS, PEERS, SHARDS = 4, 3, 2
SCRIPTED = (0, 3)           # one group in each mesh shard


def cfg_for():
    return RaftConfig(num_groups=GROUPS, num_peers=PEERS, seed=7,
                      log_window=32, max_entries_per_msg=4,
                      tick_interval_s=0.0)


def fused(data_dir):
    return FusedClusterNode(cfg_for(), data_dir, seed=3)


def mesh(data_dir):
    return MeshClusterNode(
        cfg_for(), data_dir,
        MeshConfig(peer_shards=1, group_shards=SHARDS).build(), seed=3)


RUNTIMES = pytest.mark.parametrize("make", [fused, mesh],
                                   ids=["fused", "mesh2"])


# -- scripted steps -------------------------------------------------------

def blank(node):
    """A step in which nothing happened: every hard state as it stands,
    no append accepted."""
    pinfo = np.zeros((PEERS, GROUPS, len(_C)), np.int32)
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


def no_writes():
    return [([], [], [], [], []) for _ in range(PEERS)]


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

@pytest.mark.parametrize("make,steps", [(fused, 1), (fused, 2), (mesh, 1)],
                         ids=["fused-1step", "fused-2steps", "mesh2-1step"])
def test_hard_states_follow_every_entry_record_of_the_dispatch(
        make, steps, tmp_path, monkeypatch):
    monkeypatch.setenv("RAFTSQL_FUSED_STEPS", str(steps))
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
    finally:
        node.stop()
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
