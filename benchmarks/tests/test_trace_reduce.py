"""lib/trace_reduce.py: the arithmetic on hand-made events, and the whole
reduction on one small trace recorded on the chip (tests/data/)."""
import gzip
import json
import os

import pytest

from lib import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1e6


def test_union_merges_nested_and_touching_intervals():
    assert trace_reduce.union([(5, 9), (0, 4), (1, 2), (4, 5), (20, 30)]) \
        == [(0, 9), (20, 30)]


def test_reduce_hand_made_events():
    ev = {"devices": {
              "/device:TPU:0": [("fusion.1", 100 * MS, 2 * MS),
                                ("while.2", 300 * MS, 4 * MS),
                                ("fusion.1", 301 * MS, 1 * MS),   # nested
                                ("all-reduce.3", 500 * MS, 1 * MS)],
              "/device:TPU:1": [("fusion.1", 100 * MS, 3 * MS)]},
          "host": [("PjitFunction(step)", 0.0, 50 * MS),
                   ("wal", 110 * MS, 180 * MS),
                   ("tail", 990 * MS, 10 * MS)]}
    got = trace_reduce.reduce_events(ev)
    assert got["devices"] == 2
    assert got["window_s"] == pytest.approx(1.0)
    # device 0: 2 + 4 + 1 ms busy (the nested op adds nothing); device 1: 3
    assert got["busy_s"] == pytest.approx((7 + 3) / 2 / 1e3)
    assert got["collective_s"] == pytest.approx(0.5e-3)
    ops = dict(got["device_ops"])
    assert ops["fusion.1"] == pytest.approx((2 + 1 + 3) / 2 / 1e3)
    gaps = dict(got["idle_gaps"])
    # device 0's gap 102..300 ms lies mostly under the host's `wal` event
    assert gaps["host:wal"] >= 0.198 / 2
    assert sum(gaps.values()) == pytest.approx(1.0 - got["busy_s"])


def test_no_device_operation_reduces_to_nothing():
    assert trace_reduce.reduce_events(
        {"devices": {"/device:TPU:0": []}, "host": []}) == {}


def test_recorded_chip_trace():
    """Events extracted (`trace_reduce.py --events`) from 4 s of
    ycsb-a-10kgroups on a TPU v5 lite, PR 24: the numbers PERF.md quotes."""
    path = os.path.join(DATA, "tpu_v5e_ycsb_a_events.json.gz")
    with gzip.open(path, "rt") as f:
        ev = json.load(f)
    want = json.load(open(os.path.join(DATA, "tpu_v5e_ycsb_a_reduced.json")))
    got = trace_reduce.reduce_events(ev)
    assert list(ev["devices"]) == ["/device:TPU:0"]
    assert got["busy_s"] == pytest.approx(want["busy_s"])
    assert got["window_s"] == pytest.approx(want["window_s"])
    assert 0 < got["busy_s"] < 0.05 * got["window_s"]   # the chip waits
    assert [n for n, _ in got["device_ops"]] == \
        [n for n, _ in want["device_ops"]]
