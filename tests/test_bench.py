"""Benchmark-harness smoke tests (SURVEY.md §4 lists "no benchmark
tests" among the reference's gaps to close): a micro-scale bench child
must produce a well-formed result with nonzero commits that names its
device, and a run that failed — or found no chip — must say so with its
exit code and print no result.
"""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_bench(env_extra, timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       env=env, capture_output=True, text=True,
                       timeout=timeout, cwd=REPO)
    lines = [ln for ln in r.stdout.strip().splitlines() if ln.strip()]
    assert lines, f"no stdout; stderr tail: {r.stderr[-800:]}"
    return r, json.loads(lines[-1])


def test_headline_child_micro():
    r, out = run_bench({
        "BENCH_CHILD": "1", "BENCH_PLATFORM": "cpu", "BENCH_GROUPS": "64",
        "BENCH_TICKS": "20", "BENCH_REPEATS": "1", "BENCH_SKIP_SWEEP": "1",
        "BENCH_E": "8"})
    assert r.returncode == 0, r.stderr[-800:]
    assert out["metric"] == "raft_commits_per_sec"
    assert out["unit"] == "commits/s"
    assert out["value"] > 0
    assert out["platform"] == "cpu"
    assert out["device_kind"] == "cpu" and out["devices"] >= 1
    # Pipelined replication: the marked batch commits in ~3 ticks.
    assert out.get("p50_sat_ms") is not None


def test_durable_child_micro():
    r, out = run_bench({
        "BENCH_CHILD": "1", "BENCH_PLATFORM": "cpu",
        "BENCH_CONFIG": "durable", "BENCH_GROUPS": "32",
        "BENCH_TICKS": "8", "BENCH_REPEATS": "1"})
    assert r.returncode == 0, r.stderr[-800:]
    assert out["value"] > 0
    phases = out["durable_phase_ms"]
    assert set(phases) == {"stage", "device", "wal", "send", "publish"}


def test_durable_fused_child_records_phase_profile():
    """The durable fused rung's extras must carry the tick-phase
    profile summary (fsync/dispatch/publish shares + histograms) so
    the BENCH_*.json trajectory shows WHY a rung moved."""
    r, out = run_bench({
        "BENCH_CHILD": "1", "BENCH_PLATFORM": "cpu",
        "BENCH_CONFIG": "durable", "BENCH_DURABLE_MODE": "fused",
        "BENCH_GROUPS": "32", "BENCH_TICKS": "8",
        "BENCH_REPEATS": "1", "BENCH_E": "8"})
    assert r.returncode == 0, r.stderr[-800:]
    assert out["value"] > 0
    pp = out["phase_profile"]
    assert {"fsync_share", "dispatch_share", "publish_share"} <= set(pp)
    shares = sum(v for k, v in pp.items() if k.endswith("_share"))
    assert 0.99 <= shares <= 1.01, pp
    assert "fsync" in pp["phases"], pp["phases"]
    assert "p99_ms" in pp["phases"]["fsync"]


def test_failed_rung_gives_nonzero_exit():
    """A rung that raised is named in the JSON AND makes the exit code
    non-zero — the run no longer ends in 0 over a caught fault."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_CHILD="1",
               BENCH_PLATFORM="cpu", BENCH_CONFIG="rules",
               BENCH_RULES_SET="nosuchrule", BENCH_RULES_BIG_P="0",
               BENCH_GROUPS="64", BENCH_TICKS="20", BENCH_REPEATS="1",
               BENCH_E="8")
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       env=env, capture_output=True, text=True,
                       timeout=240, cwd=REPO)
    assert r.returncode == 1, r.stderr[-800:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["rung_faults"] == ["rules-P3-nosuchrule"]
    assert out["platform"] == "cpu"


def test_pinned_parent_passes_the_childs_json_and_exit_code():
    """BENCH_PLATFORM=cpu stays the explicit development path: the
    parent prints its one child's JSON, device fields included, and
    exits 0."""
    r, out = run_bench({
        "BENCH_PLATFORM": "cpu", "BENCH_GROUPS": "64", "BENCH_TICKS": "20",
        "BENCH_REPEATS": "1", "BENCH_SKIP_SWEEP": "1", "BENCH_E": "8"})
    assert r.returncode == 0, r.stderr[-800:]
    assert out["value"] > 0
    assert (out["platform"], out["device_kind"]) == ("cpu", "cpu")
    assert out["devices"] >= 1
    assert "failed_attempts" not in out


def test_parent_prints_no_result_when_its_attempt_fails():
    """BENCH_GROUPS=-1 makes the measurement child die in RaftConfig
    validation: the parent exits non-zero and prints NO result (it used
    to emit a platform="none" zero and exit 0)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_PLATFORM="cpu",
               BENCH_ATTEMPT_TIMEOUT_S="60", BENCH_GROUPS="-1",
               BENCH_TICKS="20", BENCH_REPEATS="1", BENCH_E="8")
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       env=env, capture_output=True, text=True,
                       timeout=240, cwd=REPO)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def test_unpinned_parent_on_a_cpu_probe_prints_no_result():
    """An unpinned parent whose probe does not report `tpu` exits
    non-zero instead of producing a CPU headline.  Here the probe child
    inherits JAX_PLATFORMS=cpu, so it reports `cpu` (the no-platform,
    no-accelerator case is tests/test_device.py's)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_PLATFORM", None)
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       env=env, capture_output=True, text=True,
                       timeout=240, cwd=REPO)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "BENCH_PLATFORM=cpu" in r.stderr


def test_no_ledger_or_fallback_machinery_left():
    """What item 3 removed stays removed: no on-disk TPU ledger, no
    last-good carry-over, no scripted probe, no backend remap."""
    import bench

    for name in ("TPU_RUNS_PATH", "_ledger_append", "_ledger_last_good",
                 "_ledger_last_matching", "_git_sha"):
        assert not hasattr(bench, name), name
    assert not os.path.exists(os.path.join(REPO, "TPU_RUNS.jsonl"))
    with open(os.path.join(REPO, "bench.py")) as f:
        src = f.read()
    for gone in ("BENCH_FAKE_PROBE_PLAN", "last_good_tpu",
                 "regression_warn"):
        assert gone not in src, gone
