"""The rows of the WAL mirror (runtime/hostplane.py `_mirror_keep`): an
accepted append that can change no log, an empty heartbeat ack, is
dropped before phase 1 of `_durable_phases` lists anything.

One seeded schedule drives a cluster plane through elections, a
partition (the chaos harness's `partition_peer`) of a leader that holds
entries nobody else has, the heal that conflict-truncates them, writes,
and a hard crash + replay; at one step a dispatch, at two (the
epoch-framed dispatch), and once on a MeshClusterNode over the forced
host devices.  Held:

  (i)   every row the mask drops has no entry and a `new_log_len`
        equal to the length of its payload log at that step (the
        proof that an empty append never truncates is core/step.py's;
        this is what holds the host to it);
  (ii)  with the mask swapped for "keep every accepted append" (what
        the plane did before) the same schedule leaves the same payload
        logs, hard states, device state and replay, and at one step a
        dispatch byte-identical WAL files;
  (iii) `_durable_phases`' return value and `_spin_hot` are the same,
        dispatch by dispatch;
  and a mask that drops the rows that truncate is caught by (i) and
  (ii) both: the schedule has such rows.

Last, the two READERS of the counters (benchmarks/layers/), against
hand-made scrapes.
"""
import importlib
import json
import os

import numpy as np
import pytest

from raftsql_tpu.chaos.scenarios import hard_crash_fused
from raftsql_tpu.config import RaftConfig
from raftsql_tpu.runtime.db import _expand_commit_item
from raftsql_tpu.runtime.fused import FusedClusterNode
from raftsql_tpu.runtime.hostplane import _C, ClusterHostPlane
from raftsql_tpu.runtime.mesh import MeshClusterNode, MeshConfig
from raftsql_tpu.storage import fsio
from raftsql_tpu.transport.faults import partition_peer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GROUPS, PEERS = 8, 3
MASK = ClusterHostPlane._mirror_keep
DURABLE = ClusterHostPlane._durable_phases


def cfg_for():
    return RaftConfig(num_groups=GROUPS, num_peers=PEERS, seed=7,
                      log_window=32, max_entries_per_msg=4,
                      election_ticks=10, heartbeat_ticks=1,
                      tick_interval_s=0.0)


def fused(data_dir):
    return FusedClusterNode(cfg_for(), data_dir, seed=11)


def mesh(data_dir):
    return MeshClusterNode(
        cfg_for(), data_dir,
        MeshConfig(peer_shards=1, group_shards=4).build(), seed=11)


# -- the masks a run can take -------------------------------------------

def took_of(infos):
    return infos[..., _C["app_from"]] >= 0


def lengths_of(plogs):
    return np.array([[pl.length(g) for g in range(GROUPS)]
                     for pl in plogs])


def keep_all(self, infos):
    """What phase 1 listed before: every accepted append."""
    took = took_of(infos)
    return took.reshape(len(infos), -1).sum(axis=1), np.nonzero(took)


def drops_truncations(self, infos):
    """A WRONG mask: it also drops every row whose `new_log_len` lies
    below its payload log's length, the conflict truncations.  (Every
    one: a leader sends an entry again until its ack arrives, and the
    second copy of a truncating append would repair the log.)"""
    n_took, kept = MASK(self, infos)
    ok = infos[kept][:, _C["new_log_len"]] \
        >= lengths_of(self.plogs)[kept[1:]]
    return n_took, tuple(k[ok] for k in kept)


def checked(mask, seen):
    """`mask` with invariant (i) held at every call, step by step of
    the dispatch it is handed, and what the schedule contained counted
    into `seen`."""
    def _keep(self, infos):
        # The payload logs' lengths as each step of the dispatch finds
        # them: a step's accepted appends leave `new_log_len`, its
        # leaders' own appends (a no-op at prop_base, proposals above
        # it) end at prop_base + prop_accepted.
        lengths = lengths_of(self.plogs)
        n_took, kept = mask(self, infos)
        keep = np.zeros(infos.shape[:3], bool)
        keep[kept] = True
        flat = (kept[0] * PEERS + kept[1]) * GROUPS + kept[2]
        assert (np.diff(flat) > 0).all()                    # the order
        for s, pinfo in enumerate(infos):
            took = took_of(pinfo)
            assert n_took[s] == took.sum() and not (keep[s] & ~took).any()
            new_len = pinfo[:, :, _C["new_log_len"]]
            dropped = took & ~keep[s]
            assert (new_len[dropped] == lengths[dropped]).all(), \
                "a dropped row would have changed its payload log"
            assert (pinfo[:, :, _C["app_n"]][dropped] == 0).all()
            seen["dropped"] += int(dropped.sum())
            seen["kept"] += int(keep[s].sum())
            seen["truncating"] += int((took & (new_len < lengths)).sum())
            acc = pinfo[:, :, _C["prop_accepted"]]
            led = (pinfo[:, :, _C["noop"]] != 0) | (acc > 0)
            lengths = np.where(took, new_len, lengths)
            lengths = np.where(led, pinfo[:, :, _C["prop_base"]] + acc,
                               lengths)
        return n_took, kept
    return _keep


# -- the schedule ---------------------------------------------------------

def drain(node):
    """Peer 0's commit stream so far, a group's entries in their order
    (a mesh's publish workers interleave the groups as they come)."""
    out = {}
    q = node.commit_q(0)
    while not q.empty():
        item = q.get_nowait()
        if item is not None:
            for g, idx, cmd in _expand_commit_item(item):
                out.setdefault(g, []).append((idx, cmd))
    return out


def logs_of(node):
    out = {}
    for p, plog in enumerate(node.plogs):
        for g in range(GROUPS):
            lo, n = plog.start(g), plog.length(g)
            terms, datas = plog.slice_columns(g, lo + 1, n - lo)
            out[p, g] = (lo, n, list(terms), list(datas))
    return out


def state_of(node):
    """What a run left in memory: payload logs, hard states, every
    leaf of the device state."""
    node.publish_flush()
    leaves = {k: np.asarray(v) for k, v in node.states._asdict().items()}
    return {"logs": logs_of(node), "hard": node._hard.copy(),
            "leaves": leaves}


def files_of(data_dir):
    out = {}
    for root, _dirs, names in os.walk(data_dir):
        for name in names:
            path = os.path.join(root, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, data_dir)] = f.read()
    return out


def run_schedule(monkeypatch, make, data_dir, mask, steps=1):
    """Drive the schedule under `mask`; everything a comparison needs."""
    monkeypatch.setattr(ClusterHostPlane, "_mirror_keep", mask)
    returns, trace, idle = [], [], {}

    def _durable(self, step_infos, staged):
        got = DURABLE(self, step_infos, staged)
        returns.append(got)
        return got
    monkeypatch.setattr(ClusterHostPlane, "_durable_phases", _durable)

    def tick(node, n=1, cut=None):
        # A partition is laid over the inboxes between dispatches, so
        # it holds only at one step a dispatch.
        if steps > 1:
            node._steps = 1 if cut is not None else steps
        for _ in range(n):
            if cut is not None:
                node.inboxes = partition_peer(node.inboxes, cut)
            node.tick()
            trace.append((len(returns), node._spin_hot,
                          node._tick_active))

    def settle(node, n):
        for t in range(400):
            tick(node)
            if t > n and (node._hints >= 0).all():
                return
        raise AssertionError("no full leadership within budget")

    def write(node, tag, groups=range(GROUPS), n=2):
        for g in groups:
            node.propose_many(g, [f"SET {tag}{i} g{g}".encode()
                                  for i in range(n)])

    # The Python WAL, so that a crash can be played (chaos/scenarios.py).
    with fsio.installed(fsio.StorageFaultInjector()):
        node = make(data_dir)
        try:
            settle(node, 10)                       # elections
            for r in range(3):
                write(node, f"a{r}_")
                tick(node, 2)
            tick(node, 8)
            # A multi-step dispatch of empty acks frames nothing.
            node.publish_flush()
            idle["wrote"] = (node._wal_written(), node._epoch_no)
            tick(node, 5)
            node.publish_flush()
            idle["after"] = (node._wal_written(), node._epoch_no)
            # Cut off group 0's leader holding four entries that only
            # it has; the others elect, and after the heal the first
            # append of the new leader truncates them away.
            cut = node.leader_of(0)
            write(node, "lost", groups=[0], n=4)
            tick(node, 1, cut)
            assert node.plogs[cut].length(0) \
                == max(pl.length(0) for pl in node.plogs) > 4
            tick(node, 60, cut)
            assert node.leader_of(0) not in (cut, -1) or cut == 0
            settle(node, 30 // steps)              # the heal
            assert node.leader_of(0) != cut
            for r in range(2):
                write(node, f"b{r}_")
                tick(node, 3)
            tick(node, 6)
            before_crash = state_of(node)
            hard_crash_fused(node)
        except BaseException:
            node.stop()
            raise
        node = make(data_dir)                              # crash + replay
        try:
            replayed = drain(node)
            after_replay = state_of(node)
            settle(node, 10)
            write(node, "c_")
            tick(node, 12)
            final = state_of(node)
            applied = drain(node)
        finally:
            node.stop()
        node = make(data_dir)                              # a clean restart
        try:
            restarted = state_of(node)
        finally:
            node.stop()
    return {"returns": returns, "trace": trace, "idle": idle,
            "before_crash": before_crash, "replayed": replayed,
            "after_replay": after_replay, "final": final,
            "applied": applied, "restarted": restarted,
            "files": files_of(data_dir)}


def assert_same(a, b, path=""):
    if isinstance(a, dict):
        assert a.keys() == b.keys(), path
        for k in a:
            assert_same(a[k], b[k], f"{path}/{k}")
    elif isinstance(a, np.ndarray):
        assert np.array_equal(a, b), path
    else:
        assert a == b, path


# -- the cases ------------------------------------------------------------

@pytest.mark.parametrize(
    "make,steps", [(fused, 1), (fused, 2), (fused, 4), (mesh, 1)],
    ids=["fused", "fused-2-steps", "fused-4-steps", "mesh4"])
def test_mask_leaves_what_keeping_every_row_leaves(
        tmp_path, monkeypatch, make, steps):
    seen = {"dropped": 0, "kept": 0, "truncating": 0}
    got = run_schedule(monkeypatch, make, str(tmp_path / "mask"),
                       checked(MASK, seen), steps)
    want = run_schedule(monkeypatch, make, str(tmp_path / "all"),
                        keep_all, steps)
    # The schedule held what it claims: empty acks by the thousand,
    # rows with entries, and conflict truncations among them.
    assert seen["dropped"] > 1000 and seen["kept"] > 50
    assert seen["truncating"] >= 1
    files, want_files = got.pop("files"), want.pop("files")
    idle, want_idle = got.pop("idle"), want.pop("idle")
    assert_same(got, want)                          # (ii), (iii)
    assert got["replayed"] and got["applied"]
    assert_same(got["restarted"]["logs"], got["final"]["logs"])
    assert idle["after"] == idle["wrote"]           # idle ticks write nothing
    if steps == 1:
        assert files.keys() == want_files.keys() and files
        for name in files:
            assert files[name] == want_files[name], name
    else:
        # Kept, a follower's empty acks open and close an epoch frame
        # every dispatch; masked, an idle dispatch costs zero records
        # and zero epochs (`_ensure_epoch_begin`), and replay holds.
        assert want_idle["after"][1] == want_idle["wrote"][1] + 5
        assert want_idle["after"][0][0] > want_idle["wrote"][0][0]


def test_a_mask_that_drops_truncations_is_caught(tmp_path, monkeypatch):
    """The falsification: the invariant (i) names the first dropped row
    that would have truncated, and without it the comparison (ii) sees
    the payload logs part."""
    seen = {"dropped": 0, "kept": 0, "truncating": 0}
    with pytest.raises(AssertionError, match="would have changed"):
        run_schedule(monkeypatch, fused, str(tmp_path / "checked"),
                     checked(drops_truncations, seen))
    assert seen["truncating"] == 0      # caught at the first one
    want = run_schedule(monkeypatch, fused, str(tmp_path / "all"), keep_all)
    got = run_schedule(monkeypatch, fused, str(tmp_path / "wrong"),
                       drops_truncations)
    with pytest.raises(AssertionError):
        assert_same(got["before_crash"]["logs"],
                    want["before_crash"]["logs"])


@pytest.mark.parametrize("app_from,app_n,took,keep", [
    (1, 2, True, True),         # entries: a row
    (0, 4, True, True),         # ... from peer 0 too (`app_from` 0)
    (1, 0, True, False),        # an empty ack: counted, not listed
    (0, 0, True, False),
    (-1, 0, False, False),      # no append accepted
])
def test_mirror_keep_on_hand_made_rows(tmp_path, app_from, app_n, took,
                                       keep):
    node = fused(str(tmp_path))
    try:
        # Two steps: a row in the first, the case in the second.
        infos = np.zeros((2, PEERS, GROUPS, len(_C)), np.int32)
        infos[..., _C["app_from"]] = -1
        infos[0, 1, 3, [_C["app_from"], _C["app_n"]]] = 2, 1
        infos[1, 2, 5, [_C["app_from"], _C["app_n"]]] = app_from, app_n
        n_took, (steps, peers, groups) = node._mirror_keep(infos)
    finally:
        node.stop()
    assert n_took.tolist() == [1, int(took)]
    assert list(zip(steps.tolist(), peers.tolist(), groups.tolist())) \
        == [(0, 1, 3)] + [(1, 2, 5)] * int(keep)


# -- the readers ----------------------------------------------------------

def _scrape(k, program="change", groups=10000):
    """A scrape after 10 k ticks of a node of `groups` groups of which
    21 take one entry a tick: `change` counts the 42 rows and the empty
    acks apart, `parent` (PR 28) every accepted append as a row, `old`
    has no wal.* counter at all."""
    doc = {"ticks": 10 * k}
    took, rows = 2 * groups * 10 * k, min(42, 2 * groups) * 10 * k
    if program == "change":
        doc["wal"] = {"mirror_rows": rows,
                      "mirror_skipped_rows": took - rows}
    elif program == "parent":
        doc["wal"] = {"mirror_rows": took}
    return {"t": 3.0 * k, "engine": doc, "workers": [doc]}


@pytest.mark.parametrize("name,change,parent,one_group", [
    ("wal_mirror_rows_per_tick", 42.0, 20000.0, 2.0),
    ("wal_mirror_skipped_pct", 99.79, None, 0.0),
])
def test_mirror_readers_on_a_pair_of_scrapes(monkeypatch, name, change,
                                             parent, one_group):
    monkeypatch.syspath_prepend(os.path.join(REPO, "benchmarks"))
    reader = importlib.import_module("layers." + name)

    def read(*scrapes):
        return reader.read(*scrapes, {}, None)
    assert read(_scrape(20), _scrape(60)) == pytest.approx(change)
    got = read(_scrape(20, "parent"), _scrape(60, "parent"))
    assert got == (None if parent is None else pytest.approx(parent))
    # One group: both followers take a real append every tick.
    assert read(_scrape(20, groups=1), _scrape(60, groups=1)) \
        == pytest.approx(one_group)
    # No counter; a counter on one side only; a window with no tick or
    # no accepted append: silent, never a division error.
    assert read(_scrape(20, "old"), _scrape(60, "old")) is None
    assert read(_scrape(20, "old"), _scrape(60)) is None
    assert read(_scrape(60), _scrape(60)) is None
    assert read(_scrape(60), _scrape(20)) is None       # a restart


def test_mirror_readers_are_in_the_manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = [w["name"] for w in manifest["workloads"]]
    tail = [m for m in manifest["per_layer"]
            if m["name"] in ("wal_mirror_rows_per_tick",
                             "wal_mirror_skipped_pct")]
    assert len(tail) == 2       # looked up by name: later PRs append
    wal_layer = {m["layer"] for m in manifest["per_layer"]
                 if m["name"] == "wal_mirror_fallback_pct"}
    for m in tail:
        assert m["workloads"] == cells and {m["layer"]} == wal_layer
        assert m["moves"] == "write_p50_ms"
        assert m["source"] == "program_counter"
        assert os.path.exists(os.path.join(
            REPO, "benchmarks", "layers", m["name"] + ".py"))
