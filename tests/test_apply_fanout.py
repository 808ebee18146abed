"""A drained run's groups apply side by side (runtime/db.py `_apply_run`):
the fan-out over the apply workers, the barrier in front of the shm
publish and the acks, what an error and an exception do, and that the
state a run leaves is a serial apply's.

Everything runs against a RaftDB over a pipe double: the commit stream
is a queue the test fills, so a run is exactly what the test put there
(one RAW_MANY item is one run, as a fused tick publishes it).
"""
import queue
import random
import threading
import time
import types

import pytest

from raftsql_tpu.models.sqlite_sm import SQLiteStateMachine
from raftsql_tpu.obs.prof import TickPhaseProfiler
from raftsql_tpu.runtime import db as db_mod
from raftsql_tpu.runtime.db import RaftDB
from raftsql_tpu.runtime.node import CLOSED, RAW_MANY

WAIT_S = 20.0                   # every wait of this file


class PipeDouble:
    """The propose/commit/error triple with nothing behind it."""

    def __init__(self):
        self.commit_q = queue.Queue()
        self.commit_q.put(None)             # the replay is empty
        self.node = types.SimpleNamespace(
            prof=TickPhaseProfiler(cap=64))
        self.error = None
        self.proposed = []

    def propose(self, group, payload, pid=None):
        self.proposed.append((group, payload))

    def close(self):
        self.commit_q.put(CLOSED)
        return None


class SleepySM:
    """A state machine that takes `delay` seconds a batch and says when,
    and on which thread, it ran; `boom` is raised instead."""

    def __init__(self, group, events, delay=0.0, boom=None):
        self.group, self.events = group, events
        self.delay, self.boom = delay, boom
        self.intervals = []                 # (t0, t1, thread)
        self._applied = 0

    def apply(self, command, index=0):
        return self.apply_batch([(command, index)])[0]

    def apply_batch(self, items):
        t0 = time.monotonic()
        if self.boom is not None:
            raise self.boom
        time.sleep(self.delay)
        self._applied = items[-1][1]
        self.intervals.append((t0, time.monotonic(),
                               threading.current_thread()))
        self.events.append(("applied", self.group))
        return [ValueError(q) if q.startswith("bad") else None
                for q, _ix in items]

    def applied_index(self):
        return self._applied

    def query(self, q):
        return ""

    def close(self):
        pass


class ShmDouble:
    def __init__(self, events):
        self.events = events

    def publish_deltas(self, per_g):
        self.events.append(("publish", sorted(per_g)))


class Rig:
    """A RaftDB over the doubles, with every ack, listener item, apply
    and publish of a run in ONE ordered event list."""

    def __init__(self, groups, sm_of=None, shm=True):
        self.events = []
        self.pipe = PipeDouble()
        self.listener = queue.Queue()
        sm_of = sm_of or SleepySM       # called as sm_of(group, events)
        self.sms = {}

        def factory(g):
            self.sms[g] = sm_of(g, self.events)
            return self.sms[g]

        self.db = RaftDB(factory, self.pipe, num_groups=groups,
                         listener=self.listener)
        assert self.listener.get(timeout=WAIT_S) is None    # replay done
        if shm:
            self.db.shm = ShmDouble(self.events)
        self.base = [0] * groups

    def run(self, writes):
        """Propose `writes` [(group, sql), ...] and commit them as ONE
        run: a batch a group, batches in the order the groups first
        appear (`self.order`, the run's commit order).  Returns the
        futures, in the order of `writes`."""
        futs, batches = [], {}
        for g, sql in writes:
            fut = self.db.propose(sql, g)
            fut.add_done_callback(
                lambda err, g=g, sql=sql:
                self.events.append(("ack", g, sql, err)))
            futs.append(fut)
            batches.setdefault(g, []).append(sql.encode())
        item = []
        for g, datas in batches.items():
            item.append((g, self.base[g], datas))
            self.base[g] += len(datas)
        self.order = [(g, d.decode()) for g, _b, datas in item
                      for d in datas]
        self.pipe.commit_q.put((RAW_MANY, item))
        return futs

    def heard(self, n):
        """The next n listener items (one an ack, in ack order)."""
        return [self.listener.get(timeout=WAIT_S) for _ in range(n)]

    def close(self):
        self.db.close()
        assert not self.db._reader.is_alive()


@pytest.fixture
def rig_of():
    rigs = []

    def make(*a, **kw):
        rigs.append(Rig(*a, **kw))
        return rigs[-1]

    yield make
    for r in rigs:
        r.close()


def counters(rig):
    return rig.pipe.node.prof.counters_doc()["apply"]


# -- (a) the fan-out, and the run that never leaves the reader ---------

@pytest.mark.parametrize("groups", [16, 40])
def test_a_runs_groups_overlap_on_the_workers(rig_of, groups):
    if db_mod.APPLY_WORKERS < 4:
        pytest.skip("needs four apply workers to show an overlap")
    delay = 0.05
    rig = rig_of(groups, lambda g, ev: SleepySM(g, ev, delay))
    t0 = time.monotonic()
    rig.run([(g, f"w{g}") for g in range(groups)])
    rig.heard(groups)
    took = time.monotonic() - t0
    assert took < groups * delay / 2, took      # under half the serial sum
    spans = [sm.intervals[0] for sm in rig.sms.values()]
    threads = {t for _t0, _t1, t in spans}
    assert rig.db._reader not in threads
    assert 1 < len(threads) <= db_mod.APPLY_WORKERS
    assert all(t.name.startswith("raftdb-apply") for t in threads)
    spans.sort(key=lambda s: s[0])
    assert any(b[0] < a[1] for a, b in zip(spans, spans[1:]))   # overlap
    assert counters(rig) == {"runs": 1, "groups": groups,
                             "fanout_runs": 1, "native_txns": 0,
                             "python_txns": groups}
    pair = rig.pipe.node.prof.stages_doc()["put"]["apply_batch"]
    assert pair["n"] == groups
    assert pair["total_ms"] >= groups * delay * 1e3 * 0.99


@pytest.mark.parametrize("entries", [1, 5])
def test_a_run_of_one_group_stays_on_the_reader_thread(rig_of, entries):
    rig = rig_of(4)
    rig.run([(2, f"w{i}") for i in range(entries)])
    rig.heard(entries)
    (_t0, _t1, thread), = rig.sms[2].intervals
    assert thread is rig.db._reader
    assert not rig.db._apply_pool._threads      # no worker was started
    assert counters(rig) == {"runs": 1, "groups": 1, "fanout_runs": 0,
                             "native_txns": 0, "python_txns": 1}
    assert rig.pipe.node.prof.stages_doc()["put"]["apply_batch"]["n"] == 1


# -- (b) the barrier: apply all, publish once, then ack in order -------

@pytest.mark.parametrize("slow_group", [0, 7, 15])
def test_b_acks_in_commit_order_after_the_slowest_group(rig_of,
                                                        slow_group):
    groups = 16
    rig = rig_of(groups, lambda g, ev: SleepySM(
        g, ev, 0.3 if g == slow_group else 0.0))
    # Two statements in some groups, interleaved across groups.
    writes = [(g, f"first{g}") for g in range(groups)] + \
             [(g, f"second{g}") for g in range(0, groups, 3)]
    futs = rig.run(writes)
    assert rig.order != writes and sorted(rig.order) == sorted(writes)
    assert rig.heard(len(writes)) == rig.order  # listener: commit order
    for f in futs:
        assert f.wait(WAIT_S) is None
    ev = rig.events
    acks = [e for e in ev if e[0] == "ack"]
    assert [(g, sql) for _k, g, sql, _e in acks] == rig.order
    assert [e for e in ev if e[0] == "publish"] == \
        [("publish", list(range(groups)))]
    first_ack = ev.index(acks[0])
    assert ev.index(("publish", list(range(groups)))) < first_ack
    applied = [i for i, e in enumerate(ev) if e[0] == "applied"]
    assert len(applied) == groups and max(applied) < first_ack
    assert ev.index(("applied", slow_group)) == max(applied)
    assert all(rig.db._delivered[g] == rig.base[g] for g in range(groups))


# -- (c) an error is one ack's; an exception is the reader's -----------

@pytest.mark.parametrize("bad_group", [0, 5])
def test_c_an_erroring_statement_fails_only_its_own_ack(rig_of,
                                                        bad_group):
    rig = rig_of(8)
    writes = [(g, "ok") for g in range(8)] + [(bad_group, "bad one"),
                                              (bad_group, "ok again")]
    futs = rig.run(writes)
    rig.heard(len(writes))
    errs = [f.wait(WAIT_S) for f in futs]
    assert [type(e) for e in errs] == [type(None)] * 8 + \
        [ValueError, type(None)]
    assert str(errs[8]) == "bad one"


@pytest.mark.parametrize("groups", [1, 6])
def test_c_an_exception_in_apply_reaches_the_reader_thread(rig_of,
                                                           groups):
    boom = RuntimeError("state machine broke")
    rig = rig_of(6, lambda g, ev: SleepySM(
        g, ev, 0.05, boom if g == 0 else None), shm=False)
    seen = []
    old = threading.excepthook
    threading.excepthook = seen.append
    try:
        rig.run([(g, "w") for g in range(groups)])
        rig.db._reader.join(WAIT_S)
        assert not rig.db._reader.is_alive()
    finally:
        threading.excepthook = old
    (args,) = seen
    assert args.thread is rig.db._reader
    assert args.exc_value is boom           # the one a serial call raises
    # The barrier held: the other groups had returned before it rose,
    # and nothing of the run was acknowledged.
    assert len([e for e in rig.events if e[0] == "applied"]) == groups - 1
    assert not [e for e in rig.events if e[0] == "ack"]


# -- (d) the state a fanned-out run leaves is a serial apply's ---------

def statements(seed, groups, n):
    """[(group, sql), ...]: inserts, updates, deletes and statements
    that fail (a duplicate key, a missing table), from the seed."""
    rng = random.Random(seed)
    out = [(g, "CREATE TABLE t (k INTEGER PRIMARY KEY, v TEXT)")
           for g in range(groups)]
    for i in range(n):
        g = rng.randrange(groups)
        k = rng.randrange(12)
        out.append((g, rng.choice([
            f"INSERT INTO t (k, v) VALUES ({k}, 'v{i}')",   # may collide
            f"INSERT OR REPLACE INTO t (k, v) VALUES ({k}, 'r{i}')",
            f"UPDATE t SET v = 'u{i}' WHERE k = {k}",
            f"DELETE FROM t WHERE k = {k}",
            f"INSERT INTO missing (k) VALUES ({i})"])))
    return out


@pytest.mark.parametrize("resume", [False, True])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_d_same_statements_same_state_as_a_serial_apply(rig_of, tmp_path,
                                                        seed, resume):
    groups, stmts = 12, statements(seed, 12, 400)

    def sm_of(kind):
        return lambda g, _events=None: SQLiteStateMachine(
            str(tmp_path / f"{kind}-g{g}.db"), resume=resume)

    rig = rig_of(groups, sm_of("fan"), shm=False)
    rng = random.Random(seed + 100)
    serial = {g: sm_of("serial")(g) for g in range(groups)}
    index = [0] * groups
    at, fan_errs, serial_errs = 0, [], []
    while at < len(stmts):
        run = stmts[at:at + rng.randrange(1, 60)]
        at += len(run)
        futs = rig.run(run)
        rig.heard(len(run))
        fan_errs += [f.wait(WAIT_S) for f in futs]
        for g, sql in run:                  # the reference: one by one
            index[g] += 1
            serial_errs.append(serial[g].apply(sql, index[g]))
    assert [(type(e), str(e)) for e in fan_errs] == \
        [(type(e), str(e)) for e in serial_errs]
    assert any(e is not None for e in serial_errs)
    for g in range(groups):
        assert rig.sms[g].applied_index() == serial[g].applied_index() \
            == index[g]
        q = "SELECT k, v FROM t ORDER BY k"
        assert rig.sms[g].query(q) == serial[g].query(q)
        serial[g].close()
    c = counters(rig)
    assert c["fanout_runs"] > 0 and c["groups"] > c["runs"]
