"""BENCHMARK.json as data: what each cell is judged on, that every list
of cells names cells, that every per-layer metric moves a metric its
cells report, and the `slices` line of run.py on a log of rehearsal-mix's
shape (two generator processes, reads and writes)."""
import importlib
import os
import sys

import pytest

from test_rehearsal import MANIFEST, ROOT, names as reported

CELLS = [w["name"] for w in MANIFEST["workloads"]]
# Not `write_p95_ms` (20.8% over fourteen seeds) and not `ops_per_s`:
# 1,000 closed-loop clients with nothing saturated behind them make it
# 1,000 / MEAN latency, whose tail share swings run by run (pairs of one
# commit: 18-37% against a bound of 15%; PERF.md sections 2 and 6).  The
# long-lived variant drives the same clients.
KV0_CELLS = ("kv0-10ksplits", "kv0-10ksplits-resume")


@pytest.mark.parametrize("cell", KV0_CELLS)
def test_kv0_is_judged_on_its_median_and_its_setup(cell):
    assert reported("end_to_end", cell) == {"write_p50_ms", "setup_s"}


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_a_median_and_a_layer(cell):
    got = reported("end_to_end", cell)
    assert {"setup_s", "write_p50_ms"} <= got
    assert reported("per_layer", cell)


def test_every_list_of_cells_names_cells():
    for kind in ("end_to_end", "per_layer"):
        for m in MANIFEST[kind]:
            listed = m.get("workloads", [])
            assert set(listed) <= set(CELLS), m["name"]
            assert len(set(listed)) == len(listed), m["name"]


def test_a_listed_per_layer_metric_moves_what_its_cells_report():
    """A metric that lists cells may list only cells that report the
    end-to-end metric it moves (one without a list is asked of the cells
    that report it and of no other)."""
    for m in MANIFEST["per_layer"]:
        for cell in m.get("workloads", []):
            assert m["moves"] in reported("end_to_end", cell), \
                (m["name"], cell)


def test_the_other_cells_keep_ops_per_s_and_every_bound_is_as_it_was():
    for cell in CELLS:
        if cell not in KV0_CELLS:
            assert "ops_per_s" in reported("end_to_end", cell)
    assert {m["name"]: m["bound"] for m in MANIFEST["end_to_end"]} == {
        "ops_per_s": 0.15, "write_p50_ms": 0.2, "write_p95_ms": 0.25,
        "read_p95_ms": 0.2, "setup_s": 0.25}
    assert MANIFEST["run_seconds"] == 40


# -- the slices and the workers lines ---------------------------------------

def record(cid, kind, sent, answered, status):
    return [cid, kind, f"k{cid}", 0, "v", sent, answered, status, 0]


@pytest.fixture(scope="module")
def run():
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    return importlib.import_module("run")


def test_workers_line_is_one_row_a_scrape_connection(run):
    def doc(n, ms):
        return {"worker_stages": {"put": {"ring_rtt": {
            "total_ms": ms, "n": n, "max_ms": 1.0}}}}
    before = {"workers": [doc(100, 5000.0), doc(40, 800.0), {}]}
    after = {"workers": [doc(400, 65000.0), doc(40, 800.0), {}]}
    assert run.worker_shares(before, after) == [
        [300, 200.0], [0, None], [None, None]]


def test_slices_cover_the_window_and_sum_to_attempted(run):
    t0, t1 = 100.0, 112.0               # 5 s + 5 s + what is left, 2 s
    docs = [
        {"ops": [record(0, "w", 99.0, 99.9, 204),       # before the window
                 record(0, "w", 99.5, 100.0, 204),      # answered AT t0
                 record(0, "r", 100.1, 100.2, 200),
                 record(0, "w", 101.0, 104.0, 204),
                 record(0, "w", 104.5, 105.0, 204),     # a slice's first
                 record(0, "w", 105.5, 111.5, 204),
                 record(0, "w", 111.6, 112.0, 204),     # answered AT t1
                 record(0, "w", 111.9, 112.1, 204)]},   # after it
        {"ops": [record(1, "w", 100.0, 100.4, 204),
                 record(1, "w", 100.5, 100.7, 0),       # failed: counted,
                 record(1, "r", 106.0, 106.5, 200),     # no latency
                 record(1, "r", 110.0, 110.1, 503)]},
    ]
    got = run.window_slices(docs, t0, t1)
    assert got["slice_s"] == 5.0 and got["t0"] == 100.0
    assert got["by_process"] == [
        [[3, 1750.0], [1, 500.0], [2, 3200.0]],
        [[2, 400.0], [1, None], [1, None]]]
    assert got["all"] == [[5, 500.0], [2, 500.0], [3, 3200.0]]
    log = [r for d in docs for r in d["ops"]]
    assert sum(n for n, _ in got["all"]) == \
        run.client_numbers(log, t0, t1)["attempted"] == 10
    # The runner's t1 - t0 is 40.000000000007 or so: eight slices, not nine.
    assert len(run.window_slices(docs, t0, t0 + 40.0 + 1e-9)["all"]) == 8
    # A window shorter than a slice is one slice.
    assert len(run.window_slices(docs, t0, t0 + 4.0)["all"]) == 1
