"""chip_smoke.py's phases, driven against a JAX_PLATFORMS=cpu server at a
small size.  The script's command line has no CPU mode (a chip smoke
that passes on the CPU proves nothing); its phases are importable
functions so the control flow, the reference comparison, the restart
read-back and the clean stop are exercised here on every tier-1 run.
"""
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from raftsql_tpu.native.build import load_native_apply  # noqa: E402


def test_phases_against_cpu_server(tmp_path, monkeypatch):
    """Probe, cold start, load, reads in every mode against the plain
    sqlite3 reference, shm hits, SIGKILL + restart + read-back with
    compile-cache hits, SIGTERM -> 0, device child — at --groups 64,
    expecting platform == "cpu"."""
    # A cache of this test's own: the restart must hit what the cold
    # start of THIS run wrote, whatever earlier runs left elsewhere.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    # On the CPU the cache keeps JAX's one-second threshold
    # (utils/device.py), which nothing at this size reaches.
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.delenv("XLA_FLAGS", raising=False)     # one cpu device
    shape = chip_smoke.Shape(groups=64, tables=16, stride=4,
                             rows_per_table=4, conns=4,
                             device_groups=256, device_ticks=20)
    data = tmp_path / "data"
    data.mkdir()
    dev = chip_smoke.probe_device()
    chip_smoke.require_device(dev, "cpu", 1, "the device probe")
    assert json.loads(chip_smoke.result_line(True, dev)) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    out = chip_smoke.smoke(shape, chips=1, seed=7, dev=dev,
                           data_dir=str(data),
                           deadline=time.monotonic() + 400)
    assert (out["platform"], out["device_kind"], out["devices"]) == \
        ("cpu", "cpu", 1)
    assert out["native_wal"] is True
    # Where the host can build it (g++ and libsqlite3.so.0): a node
    # without serves on the Python loop and says so.
    assert out["native_apply"] is (load_native_apply() is not None)
    assert out["requests"] == {"attempted": 16 + 64 + 1,
                               "acked": 16 + 64 + 1, "failed": 0}
    assert out["rows"]["mismatched"] == 0
    # 4 modes x 64 rows before the restart, 65 rows read back after it.
    assert out["rows"]["compared"] == 4 * 64 + 65
    assert out["shm_hits"] > 0
    assert out["compile_cache"]["restart"]["hits"] > 0
    assert out["compile_cache"]["restart"]["dir"] == str(tmp_path / "cc")
    assert out["device_child"]["platform"] == "cpu"
    assert set(out["phase_ms_per_tick"]) >= {"device", "wal", "publish"}


def test_command_line_has_no_cpu_mode():
    """`python chip_smoke.py` on a machine without an accelerator exits
    non-zero and prints no result, even when the CPU is asked for by name
    (as this sandbox's environment does).  The unpinned case is
    tests/test_device.py's."""
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "required 'tpu'" in r.stderr


def _run_main(monkeypatch, capsys, smoke):
    dev = {"platform": "tpu", "device_kind": "TPU v5 lite", "count": 1,
           "jax": "0.9.0"}
    monkeypatch.setattr(chip_smoke, "probe_device", lambda: dict(dev))
    monkeypatch.setattr(chip_smoke, "smoke", smoke)
    rc = chip_smoke.main([])
    return rc, capsys.readouterr().out.splitlines()


def test_last_line_is_the_contract_object_and_nothing_more(monkeypatch,
                                                           capsys):
    """The last line of stdout has exactly the keys `ok` and `device`
    (`platform`, `kind`, `count`); what the run observed is on the
    `summary` line before it.  A phase that fails after the probe found
    the chip ends in `ok: false` and a non-zero exit."""
    want = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    rc, lines = _run_main(monkeypatch, capsys,
                          lambda *a, **k: {"groups": 10_000})
    assert rc == 0
    assert json.loads(lines[-1]) == {"ok": True, "device": want}
    assert lines[-2] == 'chip_smoke: summary: {"groups": 10000}'

    def failing(*a, **k):
        raise chip_smoke.SmokeFailure("a phase failed")
    rc, lines = _run_main(monkeypatch, capsys, failing)
    assert rc == 1
    assert json.loads(lines[-1]) == {"ok": False, "device": want}


def test_statements_are_seeded_and_spread():
    shape = chip_smoke.DEPLOYMENT
    groups = shape.table_groups()
    assert len(groups) == 256 and groups[-1] < shape.groups
    # Every 39th group: a four-way split of the id range is hit evenly
    # (64 tables per shard, give or take one).
    per_shard = [sum(1 for g in groups if g // 2500 == j)
                 for j in range(4)]
    assert all(63 <= n <= 65 for n in per_shard), per_shard
    c0, i0 = chip_smoke.make_statements(shape, seed=0)
    c1, i1 = chip_smoke.make_statements(shape, seed=0)
    _, i2 = chip_smoke.make_statements(shape, seed=1)
    assert (c0, i0) == (c1, i1) and i0 != i2
    assert len(c0) == 256 and len(i0) == 4096
    # The plain reference renders rows the way the server does.
    assert chip_smoke.reference_rows(
        ["CREATE TABLE t (k INTEGER, v TEXT)",
         "INSERT INTO t (k, v) VALUES (2, 'b')",
         "INSERT INTO t (k, v) VALUES (1, 'a')"]) == ["|1|a|", "|2|b|"]
    assert json.dumps(chip_smoke.server_argv(shape, 4, 1)).count(
        "--mesh") == 1
