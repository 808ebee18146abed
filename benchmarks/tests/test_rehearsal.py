"""run.py end to end on the CPU rehearsal configuration: the result line,
the clean-up on success and on a forced failure, and that a missing chip
is a failure, never a CPU result under a device metric's name."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MANIFEST = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "compared"}


def run_cell(workload, trace, seconds=4):
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", workload, "--seed", str(2**31 + 12345),
         "--seconds", str(seconds), "--trace", str(trace)],
        cwd=ROOT, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        timeout=600)
    lines = r.stdout.strip().splitlines()
    scratch = [ln.split()[2] for ln in lines
               if ln.startswith("bench: scratch:")]
    return r, lines, scratch[0] if scratch else None


def assert_nothing_left(scratch):
    assert scratch and not os.path.exists(scratch)
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            cwd = os.readlink(f"/proc/{pid}/cwd")
        except OSError:
            continue
        assert not cwd.startswith(scratch), f"pid {pid} still in {cwd}"


def said(lines, key):
    """The JSON of the run's one `bench: <key>:` line."""
    found = [ln for ln in lines if ln.startswith(f"bench: {key}: ")]
    assert len(found) == 1, (key, found)
    return json.loads(found[0][len(f"bench: {key}: "):])


def names(kind, cell):
    return {m["name"] for m in MANIFEST[kind]
            if "workloads" not in m or cell in m["workloads"]}


def test_untraced_run_reports_end_to_end_metrics_on_cpu():
    r, lines, scratch = run_cell("rehearsal-mix", 0)
    assert r.returncode == 0, r.stderr[-2000:]
    result = json.loads(lines[-1])
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 100
    assert result["device"]["platform"] == "cpu"
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert set(result["metrics"]) == names("end_to_end", "ycsb-a-10kgroups")
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    # What `correct` compared, beside its limit: last in the result and
    # last on stderr.
    assert list(result)[-1] == "compared"
    assert result["compared"] == {"mismatched": {"value": 0, "limit": 0}}
    assert r.stderr.strip().splitlines()[-1] == \
        "bench: compared: mismatched 0 (limit 0)"
    # The slices line: 4 s is one slice; over all clients and per
    # generator process its answered counts sum to `attempted`.
    slices = said(lines, "slices")
    traffic = json.load(open(os.path.join(
        ROOT, "benchmarks", "traffic", "rehearsal-mix.json")))
    assert slices["slice_s"] == 5.0 and len(slices["all"]) == 1
    assert len(slices["by_process"]) == traffic["processes"]
    assert slices["all"][0][0] == result["attempted"] == sum(
        row[0][0] for row in slices["by_process"])
    assert slices["all"][0][1] > 0
    rows = said(lines, "workers")
    assert len(rows) == 4                           # SCRAPE_CONNECTIONS
    assert all(n > 0 and ms > 0 for n, ms in rows)
    assert_nothing_left(scratch)


def test_traced_run_reports_per_layer_metrics_and_breakdown():
    r, lines, scratch = run_cell("rehearsal-mix", 1, seconds=7)
    assert r.returncode == 0, r.stderr[-2000:]
    result = json.loads(lines[-1])
    assert set(result) == RESULT_KEYS | {"breakdown"}
    assert list(result)[-1] == "compared"
    assert result["correct"] is True
    assert len(said(lines, "slices")["all"]) == 2      # 7 s: 5 + 2
    got = set(result["metrics"])
    assert got <= names("per_layer", "ycsb-a-10kgroups")
    assert {"tick_ms", "tick_wal_write_ms", "device_idle_pct",
            "device_step_ms", "client_busy_pct", "window_compiles"} <= got
    assert result["metrics"]["window_compiles"]["value"] == 0
    dev = result["device"]
    assert dev["platform"] == "cpu"
    assert 0 < dev["busy_s"] < dev["window_s"]
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < len(result["breakdown"]["device_ops"]) <= 10
    assert_nothing_left(scratch)


def test_missing_chip_is_a_failure_with_no_result():
    r, lines, scratch = run_cell("rehearsal-no-chip", 0)
    assert r.returncode != 0
    assert not any(ln.startswith("{") for ln in lines)
    assert "the cell needs 'tpu'" in r.stderr
    assert_nothing_left(scratch)


def test_unknown_workload_fails_before_anything_starts():
    r, lines, scratch = run_cell("no-such-cell", 0)
    assert r.returncode != 0 and not lines and scratch is None


def test_sigterm_to_the_runner_leaves_nothing_behind():
    import signal
    import time
    p = subprocess.Popen(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "rehearsal-mix", "--seed", "3", "--seconds", "60",
         "--trace", "0"], cwd=ROOT, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)
    scratch = None
    for line in p.stdout:                   # until the window is open
        if line.startswith("bench: scratch:"):
            scratch = line.split()[2]
        if line.startswith("bench: window:"):
            break
    time.sleep(1.0)
    p.send_signal(signal.SIGTERM)
    out, err = p.communicate(timeout=120)
    assert p.returncode != 0
    assert not any(ln.startswith("{") for ln in out.splitlines())
    assert "signal 15" in err
    assert_nothing_left(scratch)
