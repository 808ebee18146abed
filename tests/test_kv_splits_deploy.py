"""The deployment in which every group takes writes (PR 34): CockroachDB's
`kv` workload at 0% reads over a table pre-split into one range a raft
group (benchmarks/ops/kv_splits.py, configs/multiraft-10k-kv-splits.json,
traffic/kv0.json), small, on the CPU.

  (a) the served node (`--fused --workers 2 --groups 64 --peers 3`) fed
      the generator's statements by concurrent keep-alive clients
      answers every write 204, and every written key reads back,
      `linear` and `follower`, equal to benchmarks/lib/reference.py fed
      the same statements in answered order; every group was written
      and keeps its handle; the new phase and counter are on /metrics;
  (b) the split points: `group_of` against the formula;
  (c) the streams are pure functions of (p, seed, client);
  (d) a key written twice holds the second value (UPSERT);
  (e) the three new readers on hand-made scrapes, and on a parent's;
  (f) the configuration serves `multiraft-10k`'s node, to the letter.
"""
import collections
import http.client
import importlib
import itertools
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
GROUPS = 64
CLIENTS, OPS_EACH = 32, 12
SEED = 2**31 + 34
P = {"splits": GROUPS - 1, "load_connections": 16, "clients": CLIENTS,
     "read_percent": 0, "batch": 1, "min_block_bytes": 1,
     "max_block_bytes": 2}
MIN_INT64, MAX_INT64 = -2**63, 2**63 - 1


@pytest.fixture()
def kv(monkeypatch):
    monkeypatch.syspath_prepend(BENCH)
    return importlib.import_module("ops.kv_splits")


def bench_json(*path):
    with open(os.path.join(BENCH, *path)) as f:
        return json.load(f)


# -- (a) the served node against the plain reference ----------------------

class Client:
    """One keep-alive connection, as lib/loadgen.py's clients hold."""

    def __init__(self, port):
        self.conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)

    def request(self, method, group, sql, headers=None):
        h = {"X-Raft-Group": str(group)}
        h.update(headers or {})
        self.conn.request(method, "/", body=sql, headers=h)
        r = self.conn.getresponse()
        return r.status, dict(r.getheaders()), r.read().decode()

    def metrics(self):
        self.conn.request("GET", "/metrics")
        r = self.conn.getresponse()
        assert r.status == 200
        return json.loads(r.read())


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("kvsplits")
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("RAFTSQL_PROF", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    argv = bench_json("configs", "multiraft-10k-kv-splits.json")["argv"]
    argv[argv.index("--groups") + 1] = str(GROUPS)
    log = open(os.path.join(str(tmp), "server.log"), "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "raftsql_tpu.server.main", *argv,
         "--port", str(port)],
        cwd=str(tmp), env=env, stdout=log, stderr=subprocess.STDOUT)
    try:
        from raftsql_tpu.api.client import RaftSQLClient
        client = RaftSQLClient([port], timeout_s=10)
        client.wait_healthy(0, deadline_s=180)
        client.close()
        yield port
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=10)
        log.close()


def test_served_node_against_the_plain_reference(served, kv, monkeypatch):
    from lib.reference import Reference
    port = served
    answered = []                   # (t_answered, group, sql, key, value)
    failures = []
    mu = threading.Lock()

    def one_client(cid, statements):
        c = Client(port)
        try:
            for g, sql, key, val in statements:
                status, _h, body = c.request("PUT", g, sql)
                with mu:
                    if status != 204:
                        failures.append((cid, g, sql, status, body))
                    answered.append((time.monotonic(), g, sql, key, val))
        finally:
            c.conn.close()

    def in_threads(per_client):
        ts = [threading.Thread(target=one_client, args=(cid, sts))
              for cid, sts in enumerate(per_client)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(120)
            assert not t.is_alive()

    # The set-up as run.py makes it: one CREATE TABLE a range, spread
    # over `load_connections` connections.
    schema = kv.schema(P)
    assert [g for g, _ in schema] == list(range(GROUPS))
    n = P["load_connections"]
    in_threads([[(g, sql, None, None) for g, sql in schema[j::n]]
                for j in range(n)])
    assert not failures
    # The mix: CLIENTS closed-loop writers, each its own stream.
    streams = []
    for cid in range(CLIENTS):
        ops = list(itertools.islice(kv.client(P, SEED, cid), OPS_EACH))
        assert all(kind == "w" and field == 0 for kind, _k, field, _v in ops)
        streams.append([(kv.group_of(P, key), kv.write_sql(key, 0, val),
                         key, val) for _kind, key, _f, val in ops])
    c = Client(port)
    before = c.metrics()
    in_threads(streams)
    assert not failures, failures[:3]
    writes = [a for a in answered if a[3] is not None]
    assert len(writes) == CLIENTS * OPS_EACH
    assert len({g for _t, g, *_ in writes}) > GROUPS * 3 // 4

    ref = Reference()
    try:
        for _t, g, sql, _k, _v in sorted(answered):
            ref.apply(g, sql)
        by_group = collections.defaultdict(list)
        for _t, g, _sql, key, _v in writes:
            by_group[g].append(key)
        for key in kv.sample_keys(P, SEED, 16):     # never written
            by_group[kv.group_of(P, key)].append(key)
        for g, keys in sorted(by_group.items()):
            want = {key: ref.query(g, kv.read_sql(key)) for key in keys}
            for mode in ("linear", "follower"):
                status, _h, body = c.request(
                    "GET", g, kv.read_many_sql(keys),
                    {"X-Consistency": mode})
                assert status == 200, (mode, g, status, body)
                got = {line.split("|")[1]: line + "\n"
                       for line in body.splitlines()}
                assert {k: got.get(k, "") for k in keys} == want, (mode, g)
        a_key, a_val = writes[0][3], writes[0][4]
        assert ref.query(kv.group_of(P, a_key), kv.read_sql(a_key)) == \
            f"|{a_key}|{a_val}|\n"
    finally:
        ref.close()

    # Every range was made on first use and none was given back; the
    # staging has its own clock and the publish its count of groups.
    after = c.metrics()
    c.conn.close()
    assert after["sm"]["open_handles"] == GROUPS
    assert after["sm"]["evictions"] == 0
    pop, stage = (after["phase_profile"][k] for k in ("pop", "pop_stage"))
    assert 0 < stage["total_ms"] <= pop["total_ms"]
    assert stage["n"] * 2 == pop["n"]
    published = after["publish"]["groups"] - before["publish"]["groups"]
    accepted = after["intake"]["accepted"] - before["intake"]["accepted"]
    assert accepted == CLIENTS * OPS_EACH
    # A group with commits in a dispatch counts once, whatever it
    # committed; a write commits in one dispatch.
    assert 0 < published <= accepted
    for name in ("tick_pop_ms", "tick_pop_stage_ms",
                 "publish_groups_per_tick"):
        reader = importlib.import_module("layers." + name)
        value = reader.read({"engine": before}, {"engine": after}, {}, None)
        assert value is not None and value > 0, name


# -- (b) the split points --------------------------------------------------

@pytest.mark.parametrize("splits", [9999, 63, 6])
def test_group_of_follows_the_split_points(kv, splits):
    p = {"splits": splits}
    n = splits + 1
    stride = 2**64 // n
    assert kv.group_of(p, str(MIN_INT64)) == 0
    assert kv.group_of(p, str(MAX_INT64)) == splits
    assert kv.group_of(p, "0") == (2**63) // stride
    for i in sorted({1, 2, n // 3, n // 2, splits - 1, splits}):
        point = MIN_INT64 + i * stride          # range i's first key
        assert kv.group_of(p, str(point)) == i
        assert kv.group_of(p, str(point - 1)) == i - 1
    # Monotone over a sorted seeded sample of the whole space.
    import random
    rng = random.Random(34)
    keys = sorted(rng.getrandbits(64) + MIN_INT64 for _ in range(2000))
    groups = [kv.group_of(p, str(k)) for k in keys]
    assert groups == sorted(groups) and 0 <= groups[0] and groups[-1] <= splits


def test_seeded_keys_spread_over_every_range(kv):
    """100,000 keys of 100 clients' streams over 1,000 ranges: a mean of
    100 a range, every range hit, none with under 50 or over 160 (a
    Poisson(100) count passes either with probability ~1e-7)."""
    p = dict(P, splits=999)
    counts = collections.Counter()
    for cid in range(100):
        for _kind, key, _f, _v in itertools.islice(
                kv.client(p, SEED, cid), 1000):
            counts[kv.group_of(p, key)] += 1
    assert sorted(counts) == list(range(1000))
    assert 50 <= min(counts.values()) and max(counts.values()) <= 160


# -- (c) the streams -------------------------------------------------------

def test_streams_are_pure_functions_of_p_seed_and_client(kv):
    def take(p, seed, cid, n=200):
        return list(itertools.islice(kv.client(p, seed, cid), n))

    a = take(P, SEED, 3)
    assert a == take(dict(P), SEED, 3)
    assert a != take(P, SEED + 1, 3) and a != take(P, SEED, 4)
    keys = [int(k) for _kind, k, _f, _v in a]
    assert all(MIN_INT64 <= k <= MAX_INT64 for k in keys)
    assert len(set(keys)) == len(keys)
    assert min(keys) < -2**61 and max(keys) > 2**61     # the whole space
    vals = [v for *_, v in a]
    assert {len(v) for v in vals} == {2, 4}             # 1 or 2 bytes, hex
    assert all(v == v.upper() and bytes.fromhex(v) for v in vals)
    assert kv.load(P, SEED) == [] and list(kv.initial_rows(P, SEED)) == []
    assert kv.sample_keys(P, SEED, 5) == kv.sample_keys(P, SEED, 5)
    assert not set(kv.sample_keys(P, SEED, 50)) & {k for _, k, *_ in a}
    # kv0 and nothing else: another batch or read share is refused.
    for other in ({"batch": 2}, {"read_percent": 95}):
        with pytest.raises(ValueError):
            take(dict(P, **other), SEED, 3, 1)


# -- (d) UPSERT, not insert-or-fail ---------------------------------------

def test_a_key_written_twice_holds_the_second_value(kv):
    from lib.reference import Reference
    from raftsql_tpu.models.sqlite_sm import SQLiteStateMachine
    key = str(MIN_INT64 + 5)
    ref, sm = Reference(), SQLiteStateMachine(":memory:")
    try:
        for sql in (kv.TABLE, kv.write_sql(key, 0, "0A"),
                    kv.write_sql("7", 0, "FF"),
                    kv.write_sql(key, 0, "B1C2")):
            ref.apply(0, sql)
            assert sm.apply(sql) is None
        want = f"|{key}|B1C2|\n"
        assert ref.query(0, kv.read_sql(key)) == want
        assert sm.query(kv.read_sql(key)) == want
        assert kv.parse_row(want) == ["B1C2"] and kv.parse_row("") is None
        both = sm.query(kv.read_many_sql([key, "7", "8"]))
        assert sorted(both.splitlines()) == sorted(
            [f"|{key}|B1C2|", "|7|FF|"])
        # NOT NULL is the source's: a NULL value is refused, not stored.
        assert sm.apply("INSERT INTO kv (k, v) VALUES (9, NULL)") is not None
    finally:
        ref.close()
        sm.close()


# -- (e) the new readers ---------------------------------------------------

def scrape(k, has=True):
    """The engine's document after 10 x k dispatches; `has=False` is a
    program from before PR 34 (it clocks `pop` and nothing finer, and
    counts no published groups)."""
    doc = {"ticks": 10 * k,
           "phase_profile": {"pop": {"total_ms": 300.0 * k, "n": 20 * k}}}
    if has:
        doc["phase_profile"]["pop_stage"] = {"total_ms": 220.0 * k,
                                             "n": 10 * k}
        doc["publish"] = {"groups": 4500 * k}
    return {"t": float(k), "engine": doc, "workers": [doc]}


@pytest.mark.parametrize("name,want,on_parent", [
    ("tick_pop_ms", 30.0, 30.0),            # both samples of a dispatch
    ("tick_pop_stage_ms", 22.0, None),
    ("publish_groups_per_tick", 450.0, None),
])
def test_new_readers_on_hand_made_scrapes(monkeypatch, name, want,
                                          on_parent):
    monkeypatch.syspath_prepend(BENCH)
    reader = importlib.import_module("layers." + name)
    before, after = scrape(2), scrape(6)
    assert reader.read(before, after, {}, None) == pytest.approx(want)
    assert reader.read(after, after, {}, None) is None      # no dispatch
    old = scrape(2, has=False), scrape(6, has=False)
    assert reader.read(*old, {}, None) == on_parent
    entry = [m for m in bench_json("..", "BENCHMARK.json")["per_layer"]
             if m["name"] == name]
    assert len(entry) == 1 and "kv0-10ksplits" in entry[0]["workloads"]
    assert entry[0]["layer"] == "host plane tick (runtime/hostplane.py)"


# -- (f) the configuration -------------------------------------------------

def test_configuration_is_multiraft_10ks_node(kv):
    new = bench_json("configs", "multiraft-10k-kv-splits.json")
    old = bench_json("configs", "multiraft-10k.json")
    for key in ("argv", "env", "guarantees", "groups", "chips", "platform"):
        assert new[key] == old[key], key
    assert new["reduced"] == [] and len(new["source"]) <= 200
    assert new["scale"] == {"splits": 9999, "load_connections": 1000}
    traffic = bench_json("traffic", "kv0.json")
    want = {"ops": "kv_splits", "checker": "registers", "loop": "closed",
            "clients": 1000, "processes": 4, "read_percent": 0, "batch": 1,
            "min_block_bytes": 1, "max_block_bytes": 2,
            "read_consistency": "linear"}
    assert {k: traffic[k] for k in want} == want
    p = dict(new["scale"], **traffic)
    assert len(kv.schema(p)) == new["groups"] == kv.groups(p)
    manifest = bench_json("..", "BENCHMARK.json")
    cell = [w for w in manifest["workloads"] if w["name"] == "kv0-10ksplits"]
    assert cell == [dict(cell[0], config=new["name"], traffic="kv0",
                         chips=1)]
    entry = [c for c in manifest["configs"] if c["name"] == new["name"]]
    assert entry[0]["source"] == new["source"] and entry[0]["reduced"] == []
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    # Not `write_p95_ms`: 1,000 writes are always in flight behind an
    # apply plane of ~270 transactions a second, so the tail swings run
    # by run (20.8% over fourteen seeds on the chip, PERF.md PR 34).
    # Not `ops_per_s` (PR 38): in a closed loop with nothing saturated
    # behind it, it is 1,000 / mean latency and the driver's pairs of
    # one commit spread 21%, 18% and 37% against a bound of 15%.
    listed = {m["name"] for m in manifest["end_to_end"]
              if "kv0-10ksplits" in m.get("workloads", ["kv0-10ksplits"])}
    assert listed == {"write_p50_ms", "setup_s"}
