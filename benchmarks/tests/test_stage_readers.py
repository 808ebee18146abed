"""The readers of the program's stage pairs and counters (PR 26): each on
a canned pair of scrapes, each silent where the program has no such key
(the parent commit's documents), and the traced CPU rehearsals reporting
them with the tick's phases named in the idle gaps."""
import copy
import importlib
import json
import os

import pytest

from test_rehearsal import MANIFEST, ROOT, run_cell

MANIFEST_NEW = [
    "edge_in_ms", "ring_hop_ms", "edge_out_ms", "engine_ack_ms",
    "engine_commit_ms", "apply_ms", "intake_wait_ticks", "writes_per_tick",
    "intake_accept_pct", "tick_launch_ms", "tick_readback_ms",
    "tick_wal_plan_ms", "tick_wal_append_ms", "tick_wal_hardstate_ms",
    "wal_bytes_per_write", "wal_hardstates_per_tick", "read_queue_ms",
    "read_wait_ms", "read_sql_ms", "read_shm_dead_pct",
    "read_shm_behind_pct", "intake_groups_per_tick",
    "wal_records_per_tick", "wal_groups_per_tick", "wal_fsyncs_per_tick",
    "read_edge_wait_ms"]


def pair(total_ms, n):
    return {"total_ms": total_ms, "n": n, "max_ms": 1.0}


def engine_doc(k):
    """The engine's part of a document after k units of everything."""
    return {
        "ticks": 100 * k,
        "stages": {
            "put": {"engine": pair(40000.0 * k, 100 * k),
                    "propose_commit": pair(30000.0 * k, 100 * k),
                    "apply": pair(500.0 * k, 100 * k)},
            "get": {"queue": pair(900.0 * k, 300 * k),
                    "wait": pair(6000.0 * k, 300 * k),
                    "sql": pair(150.0 * k, 300 * k)}},
        "intake": {"backlog": 1200 * k, "offered": 125 * k,
                   "accepted": 100 * k, "groups": 90 * k},
        "wal": {"records": 300 * k, "bytes": 150000 * k,
                "hardstates": 2500 * k, "groups_written": 900 * k,
                "fsyncs": 300 * k},
        "phase_profile": {
            "launch": pair(700.0 * k, 100 * k),
            "readback": pair(1800.0 * k, 100 * k),
            "wal_plan": pair(9000.0 * k, 100 * k),
            "wal_append": pair(6000.0 * k, 100 * k),
            "wal_hardstate": pair(4000.0 * k, 100 * k)},
    }


def worker_doc(k, puts, reasons):
    """What one worker relays: the engine's document with its own stages
    and read counts folded in."""
    doc = engine_doc(k)
    doc["worker_stages"] = {
        "put": {"edge_in": pair(0.2 * puts * k, puts * k),
                "ring_rtt": pair(410.0 * puts * k, puts * k),
                "edge_out": pair(3.0 * puts * k, puts * k)},
        "get": {"ring_rtt": pair(30.0 * k, 3 * k)}}
    doc["reads"] = {"shm_hits": 0,
                    "shm_fallbacks": sum(reasons.values()) * k,
                    "shm_fallback_reasons": {r: c * k
                                             for r, c in reasons.items()}}
    return doc


def scrape(k):
    workers = [worker_doc(k, 60, {"log_full": 30, "behind_watermark": 10,
                                  "catch_up": 0, "no_snapshot": 0,
                                  "no_mapping": 0, "broken": 0,
                                  "keymap_epoch": 0, "behind_commit": 0,
                                  "no_lease": 0, "stale_heartbeat": 0}),
               worker_doc(k, 40, {"log_full": 0, "behind_watermark": 40,
                                  "catch_up": 20, "no_snapshot": 0,
                                  "no_mapping": 0, "broken": 0,
                                  "keymap_epoch": 0, "behind_commit": 0,
                                  "no_lease": 0, "stale_heartbeat": 0})]
    return {"t": 20.0 * k, "engine": workers[0], "workers": workers}


BEFORE, AFTER = scrape(1), scrape(2)
CLIENT = {"read_p50_ms": 100.0, "write_p50_ms": 450.0}


@pytest.mark.parametrize("name,want", [
    ("edge_in_ms", 0.2),
    ("ring_hop_ms", 10.0),              # 410 round trip - 400 in the engine
    ("edge_out_ms", 3.0),
    ("engine_ack_ms", 400.0),
    ("engine_commit_ms", 300.0),
    ("apply_ms", 5.0),
    ("intake_wait_ticks", 12.0),        # 1200 queued-ticks / 100 accepted
    ("writes_per_tick", 1.0),
    ("intake_accept_pct", 80.0),
    ("tick_launch_ms", 7.0),
    ("tick_readback_ms", 18.0),
    ("tick_wal_plan_ms", 90.0),
    ("tick_wal_append_ms", 60.0),
    ("tick_wal_hardstate_ms", 40.0),
    ("wal_bytes_per_write", 1500.0),
    ("wal_hardstates_per_tick", 25.0),
    ("read_queue_ms", 3.0),
    ("read_wait_ms", 20.0),
    ("read_sql_ms", 0.5),
    ("read_shm_dead_pct", 30.0),        # 30 log_full of 100 fallbacks
    ("read_shm_behind_pct", 70.0),      # 50 behind_watermark + 20 catch_up
    ("intake_groups_per_tick", 0.9),
    ("wal_records_per_tick", 3.0),
    ("wal_groups_per_tick", 9.0),
    ("wal_fsyncs_per_tick", 3.0),
    ("read_edge_wait_ms", 90.0),        # clients' 100 - 10 over the ring
])
def test_stage_readers_on_canned_scrapes(name, want):
    reader = importlib.import_module("layers." + name)
    assert reader.read(BEFORE, AFTER, CLIENT, None) == pytest.approx(want)


def stripped(doc):
    """A scrape of a program that has none of this PR's keys (the parent
    commit), nor anything the new readers could mistake for them."""
    doc = copy.deepcopy(doc)
    for d in doc["workers"]:            # "engine" is workers[0]
        for key in ("stages", "worker_stages", "intake", "wal"):
            del d[key]
        del d["reads"]["shm_fallback_reasons"]
        for ph in ("launch", "readback", "wal_plan", "wal_append",
                   "wal_hardstate"):
            del d["phase_profile"][ph]
    return doc


@pytest.mark.parametrize("name", MANIFEST_NEW)
def test_stage_readers_are_silent_on_the_parents_documents(name):
    reader = importlib.import_module("layers." + name)
    assert reader.read(stripped(BEFORE), stripped(AFTER), CLIENT,
                       None) is None
    # ... and where the window saw none of what the reader divides by.
    assert reader.read(BEFORE, BEFORE, CLIENT, None) is None


def test_tick_epoch_commit_ms_reads_the_commit_record_alone():
    """PR 38: `phase_profile.epoch_commit` (recorded since PR 33) per tick
    of the window; silent where a dispatch writes no commit record (the
    mesh, the parents before PR 33) and where the window saw no tick."""
    reader = importlib.import_module("layers.tick_epoch_commit_ms")

    def doc(k, has=True):
        d = engine_doc(k)
        if has:
            d["phase_profile"]["epoch_commit"] = pair(1100.0 * k, 100 * k)
        return {"t": 20.0 * k, "engine": d, "workers": [d]}

    assert reader.read(doc(1), doc(3), CLIENT, None) == pytest.approx(11.0)
    assert reader.read(doc(1, False), doc(3, False), CLIENT, None) is None
    assert reader.read(doc(2), doc(2), CLIENT, None) is None
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    entry = by_name["tick_epoch_commit_ms"]
    assert entry["moves"] == "write_p50_ms"
    assert entry["layer"] == by_name["tick_fsync_ms"]["layer"]
    assert sorted(entry["workloads"]) == sorted(
        w["name"] for w in MANIFEST["workloads"] if w["chips"] == 1)


def test_every_new_reader_is_in_the_manifest_once():
    """Looked up by name: later PRs append entries (and a `benchmark` PR
    may take one away), so no position in `per_layer` is pinned."""
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert len(set(names)) == len(names)
    assert set(MANIFEST_NEW) <= set(names)
    for m in MANIFEST["per_layer"]:     # ... and every entry has its reader
        if m["name"] in MANIFEST_NEW:
            assert m["source"] == "program_counter"
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layers", m["name"] + ".py")), m["name"]


@pytest.mark.parametrize("workload", ["rehearsal-mix", "rehearsal-ycsb-b"])
def test_traced_rehearsal_reports_stages_and_names_tick_gaps(workload):
    r, lines, _scratch = run_cell(workload, 1, seconds=7)
    assert r.returncode == 0, r.stderr[-2000:]
    result = json.loads(lines[-1])
    assert result["correct"] is True
    for name in ("engine_ack_ms", "writes_per_tick", "tick_launch_ms",
                 "wal_bytes_per_write", "edge_in_ms", "ring_hop_ms",
                 "intake_wait_ticks", "tick_wal_hardstate_ms",
                 "intake_groups_per_tick", "wal_records_per_tick",
                 "wal_groups_per_tick", "wal_fsyncs_per_tick"):
        assert result["metrics"][name]["value"] > 0, name
    gaps = [name for name, _s in result["breakdown"]["idle_gaps"]]
    assert any(g.startswith("host:tick.") for g in gaps), gaps
