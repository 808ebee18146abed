"""The yardstick's own arithmetic: percentile rule, request distribution,
counter deltas, the per-layer readers on canned scrapes, the checker on
planted faults."""
import collections
import importlib
import random

import pytest

from lib import stats, zipf
from ops import etcd_put, registers, ycsb


# -- the percentile rule: ten samples beyond ------------------------------

@pytest.mark.parametrize("n,q,want", [
    (199, 0.95, None),          # rank 190: only 9 beyond
    (200, 0.95, 190.0),         # rank 190: 10 beyond
    (19, 0.50, None),           # rank 10: only 9 beyond
    (20, 0.50, 10.0),           # rank 10: 10 beyond
    (0, 0.50, None),
])
def test_percentile_needs_ten_samples_beyond(n, q, want):
    samples = [float(i) for i in range(1, n + 1)]
    random.Random(1).shuffle(samples)
    assert stats.percentile(samples, q) == want


# -- YCSB's distributions ---------------------------------------------------

def test_fnvhash64_is_ycsbs():
    # FNV-1a over the 8 octets of the long, low first, then Math.abs.
    assert zipf.fnvhash64(0) == 6284781860667377211
    assert zipf.fnvhash64(1) == 8517097267634966620
    assert zipf.key_name(5) == "user1000385178204227360"


def test_scrambled_zipfian_fixed_seed():
    gen = zipf.ScrambledZipfian(32768, random.Random(1))
    draws = [gen.next() for _ in range(200_000)]
    assert draws[:3] == [11370, 27796, 25140]
    counts = collections.Counter(draws)
    (hot, n), (_, n2) = counts.most_common(2)
    assert hot == 17979                     # fnvhash64(0) % 32768
    # item 0 of a zipfian(0.99) over YCSB's 10^10 items: 1 / 26.469
    assert 0.035 < n / len(draws) < 0.041
    assert 0.016 < n2 / len(draws) < 0.022  # item 1: 0.5^0.99 of that
    assert all(0 <= d < 32768 for d in draws)


def test_zipfian_small_range_computes_its_own_zeta():
    gen = zipf.Zipfian(100, random.Random(2))
    draws = [gen.next() for _ in range(50_000)]
    assert min(draws) == 0 and max(draws) < 100
    assert collections.Counter(draws).most_common(1)[0][0] == 0


def test_clients_are_functions_of_the_seed():
    p = {"group_stride": 9, "table_groups": 16, "recordcount": 512,
         "rows_per_insert": 16, "distribution": "zipfian",
         "read_share": 0.5}
    a = [op for _, op in zip(range(50), ycsb.client(p, 2**31 + 7, 3))]
    b = [op for _, op in zip(range(50), ycsb.client(p, 2**31 + 7, 3))]
    c = [op for _, op in zip(range(50), ycsb.client(p, 2**31 + 8, 3))]
    assert a == b and a != c
    assert {k for k, *_ in a} == {"r", "w"}
    load = ycsb.load(p, 5)
    assert sum(sql.count("('user") for _, sql in load) == 512
    assert {g for g, _ in load} <= {9 * i for i in range(16)}
    assert max(len(sql) for _, sql in load) < 32_000
    q = {"keyspace": 100000, "key_bytes": 8, "value_bytes": 256}
    kind, key, field, val = next(etcd_put.client(q, 1, 0))
    assert (kind, len(key), field, len(val)) == ("w", 8, 0, 256)


# -- counter deltas and the readers ------------------------------------------

def scrape(t, ticks, wal, fsync, drain_ms, drain_n, misses, proposals,
           hits, falls, ack=12.5):
    doc = {"ticks": ticks, "proposals": proposals,
           "propose_ack_p50_ms": ack,
           "phase_profile": {
               "wal_write": {"total_ms": wal, "n": ticks},
               "fsync": {"total_ms": fsync, "n": ticks},
               "publish": {"total_ms": 10.0 * ticks, "n": ticks},
               "dispatch": {"total_ms": 2.0 * ticks, "n": 2 * ticks},
               "ring_drain": {"total_ms": drain_ms, "n": drain_n}},
           "device": {"compile_cache": {"misses": misses},
                      "peak_bytes_in_use": [99_000_000, None]},
           "reads": {"shm_hits": hits, "shm_fallbacks": falls}}
    other = dict(doc, reads={"shm_hits": 2 * hits, "shm_fallbacks": 0})
    return {"t": t, "engine": doc, "workers": [doc, other]}


BEFORE = scrape(100.0, 1000, 170_000.0, 1_000.0, 50.0, 500, 36, 10_000,
                100, 10)
AFTER = scrape(120.0, 1100, 188_000.0, 1_150.0, 80.0, 800, 36, 15_000,
               400, 110)
CLIENT = {"write_p50_ms": 800.0, "read_p50_ms": 410.0, "window_s": 20.0,
          "generator_cpu_s": [2.0, 4.0]}
TRACE = {"window_s": 4.0, "busy_s": 0.02, "collective_s": 0.0}


@pytest.mark.parametrize("name,want", [
    ("tick_ms", 200.0),                 # 20 s / 100 ticks
    ("tick_wal_write_ms", 180.0),
    ("tick_fsync_ms", 1.5),
    ("tick_publish_ms", 10.0),
    ("tick_dispatch_ms", 2.0),
    ("ring_drain_ms", 0.1),             # 30 ms / 300 batches
    ("ticks_per_write", 4.0),
    ("read_p50_ms", 410.0),
    ("client_busy_pct", 15.0),          # 6 s / (20 s x 2)
    ("read_shm_hit_pct", 90.0),         # (300 + 600) / (400 + 600)
    ("window_compiles", 0),
    ("device_peak_mb", 99.0),
    ("device_idle_pct", 99.5),
    ("device_step_ms", 1.0),            # 20 ms busy / 20 ticks traced
])
def test_layer_readers_on_canned_scrapes(name, want):
    reader = importlib.import_module("layers." + name)
    got = reader.read(BEFORE, AFTER, CLIENT, TRACE)
    assert got == (want if want is None else pytest.approx(want))


def test_readers_return_nothing_where_nothing_is_to_read():
    assert importlib.import_module("layers.device_idle_pct").read(
        BEFORE, AFTER, CLIENT, None) is None
    restarted = scrape(120.0, 5, 1.0, 1.0, 1.0, 1, 0, 0, 0, 0)
    assert stats.delta(BEFORE["engine"], restarted["engine"],
                       "ticks") is None         # a counter ran backwards
    assert stats.delta(BEFORE["engine"], AFTER["engine"], "no.such") is None


# -- the checker ---------------------------------------------------------------

P = {"group_stride": 1, "table_groups": 2, "recordcount": 8,
     "rows_per_insert": 4, "distribution": "uniform", "read_share": 0.5}
SEED = 11


def world():
    """The set-up statements, initial rows, and a server that answers as
    the reference would."""
    setup = ycsb.schema(P) + ycsb.load(P, SEED)
    rows = dict(ycsb.initial_rows(P, SEED))
    return setup, rows


def row_body(key, fields):
    return "|" + "|".join([key] + fields) + "|\n"


def digests(fields):
    return [registers.crc(v) for v in fields]


def test_checker_accepts_overlapping_writes_in_either_order():
    setup, rows = world()
    key = next(iter(rows))
    a, b = "A" * 100, "B" * 100
    log = [[0, "w", key, 3, a, 1.0, 2.0, 204, 5],
           [1, "w", key, 3, b, 1.5, 1.9, 204, 5]]     # in flight together
    for final in (a, b):
        fields = list(rows[key])
        fields[3] = final
        readback = {key: {"linear": row_body(key, fields),
                          "follower": row_body(key, fields)}}
        got = registers.check(ycsb, P, SEED, setup, log, readback, "linear")
        assert got["correct"], got["mismatches"]


def test_checker_catches_a_lost_write():
    setup, rows = world()
    key = next(iter(rows))
    log = [[0, "w", key, 3, "A" * 100, 1.0, 2.0, 204, 5]]
    readback = {key: {"linear": row_body(key, rows[key])}}   # the old value
    got = registers.check(ycsb, P, SEED, setup, log, readback, "linear")
    assert not got["correct"]
    assert "FIELD3" in got["mismatches"][0]


def test_checker_catches_a_stale_linear_read():
    setup, rows = world()
    key = next(iter(rows))
    new = list(rows[key])
    new[3] = "A" * 100
    log = [[0, "w", key, 3, new[3], 1.0, 2.0, 204, 5],
           # sent after the write was answered, returns the old value
           [1, "r", key, -1, digests(rows[key]), 2.5, 2.6, 200, 5]]
    readback = {key: {"linear": row_body(key, new)}}
    got = registers.check(ycsb, P, SEED, setup, log, readback, "linear")
    assert not got["correct"]
    assert "superseded before the read was sent" in got["mismatches"][0]
    # The same read is allowed while the write is still in flight ...
    log[1][5], log[1][6] = 1.5, 1.6
    assert registers.check(ycsb, P, SEED, setup, log, readback,
                           "linear")["correct"]
    # ... and a session read is held only to the reader's OWN writes.
    log[1][5], log[1][6] = 2.5, 2.6
    assert registers.check(ycsb, P, SEED, setup, log, readback,
                           "session")["correct"]
    log[1][0] = 0
    assert not registers.check(ycsb, P, SEED, setup, log, readback,
                               "session")["correct"]


def test_checker_catches_a_value_never_written():
    setup, rows = world()
    key = next(iter(rows))
    odd = list(rows[key])
    odd[0] = "Z" * 100
    log = [[0, "r", key, -1, digests(odd), 1.0, 1.1, 200, 5]]
    got = registers.check(ycsb, P, SEED, setup, log, {}, "session")
    assert not got["correct"]
    assert "never written" in got["mismatches"][0]


def test_unanswered_write_may_or_may_not_have_happened():
    setup, rows = world()
    key = next(iter(rows))
    maybe = list(rows[key])
    maybe[2] = "M" * 100
    log = [[0, "w", key, 2, maybe[2], 1.0, 41.0, 0, 0]]       # timed out
    for fields in (rows[key], maybe):
        readback = {key: {"linear": row_body(key, list(fields))}}
        assert registers.check(ycsb, P, SEED, setup, log, readback,
                               "linear")["correct"]


def test_etcd_put_absent_and_replaced_rows():
    q = {"keyspace": 100, "key_bytes": 8, "value_bytes": 16}
    setup = etcd_put.schema(q)
    log = [[0, "w", "0000000a", 0, "v1" * 8, 1.0, 2.0, 204, 3],
           [0, "w", "0000000a", 0, "v2" * 8, 3.0, 4.0, 204, 4]]
    readback = {"0000000a": {"linear": "|0000000a|" + "v2" * 8 + "|\n"},
                "0000000b": {"linear": ""}}
    assert registers.check(etcd_put, q, 1, setup, log, readback,
                           "linear")["correct"]
    readback["0000000a"]["linear"] = "|0000000a|" + "v1" * 8 + "|\n"
    assert not registers.check(etcd_put, q, 1, setup, log, readback,
                               "linear")["correct"]
    readback["0000000a"]["linear"] = ""
    assert not registers.check(etcd_put, q, 1, setup, log, readback,
                               "linear")["correct"]
