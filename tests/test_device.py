"""The one device rule (raftsql_tpu/utils/device.py) and what hangs off it:
no silent CPU, the compile cache's place, one process per chip, and native
artifacts keyed by their source.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(**kw):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    for k, v in kw.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    return env


SELECT = ("import json; from raftsql_tpu.utils.device import select_device; "
          "print(json.dumps(select_device()))")


@pytest.mark.parametrize("argv", [
    ["-m", "raftsql_tpu.server.main", "--fused", "--port", "1"],
    [os.path.join(REPO, "bench.py")],
    [os.path.join(REPO, "chip_smoke.py")],
], ids=["server.main", "bench.py", "chip_smoke.py"])
def test_unpinned_entry_point_without_accelerator_exits_nonzero(
        argv, tmp_path):
    """JAX_PLATFORMS unset on a machine with no accelerator: the entry
    point exits non-zero, names JAX_PLATFORMS=cpu as the way to run on
    the CPU, and prints no result."""
    r = subprocess.run(
        [sys.executable, *argv], cwd=tmp_path, capture_output=True,
        text=True, timeout=240,
        env=_env(JAX_PLATFORMS=None, BENCH_PLATFORM=None,
                 BENCH_PROBE_TIMEOUT_S="120"))
    assert r.returncode != 0
    assert "JAX_PLATFORMS=cpu" in r.stderr
    assert r.stdout.strip() == ""


def test_cache_dir_is_the_environments_or_the_checkouts(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: nothing else is set in code.
    Unset: <checkout>/.jax_cache — a fixed path, no pid, time or temp
    name in it."""
    mine = str(tmp_path / "elsewhere")
    for env_dir, want in ((mine, mine),
                          (None, os.path.join(REPO, ".jax_cache"))):
        r = subprocess.run(
            [sys.executable, "-c", SELECT], capture_output=True,
            text=True, timeout=120, cwd=tmp_path,
            env=_env(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=env_dir))
        assert r.returncode == 0, r.stderr[-800:]
        doc = json.loads(r.stdout.strip().splitlines()[-1])
        assert doc["compile_cache"]["dir"] == want
        assert doc["platform"] == "cpu" and doc["count"] >= 1
        assert doc["device_kind"] and doc["jax"]


def test_healthz_and_metrics_carry_the_device(tmp_path):
    """/healthz names platform, device_kind, device count and the JAX
    version, and whether the native WAL loaded; /metrics carries the
    same device section."""
    import jax

    from raftsql_tpu.config import RaftConfig
    from raftsql_tpu.models.sqlite_sm import SQLiteStateMachine
    from raftsql_tpu.native.build import load_native_wal
    from raftsql_tpu.runtime.db import RaftDB
    from raftsql_tpu.runtime.fused import FusedClusterNode, FusedPipe

    cfg = RaftConfig(num_groups=2, num_peers=3, tick_interval_s=0.0)
    node = FusedClusterNode(cfg, str(tmp_path / "wal"), group_commit=True)
    rdb = RaftDB(lambda g: SQLiteStateMachine(":memory:"),
                 FusedPipe(node), num_groups=2)
    try:
        for doc in (rdb.health_doc(), rdb.metrics()):
            dev = doc["device"]
            assert dev["platform"] == "cpu"
            assert dev["device_kind"] == jax.devices()[0].device_kind
            assert dev["count"] == len(jax.devices())
            assert dev["jax"] == jax.__version__
            assert set(dev["compile_cache"]) == {"dir", "hits", "misses"}
        assert rdb.health_doc()["native_wal"] is (
            load_native_wal() is not None)
    finally:
        rdb.close()


def test_worker_is_pinned_to_cpu_whatever_the_engine_exported(monkeypatch):
    """One process for each chip: a worker sets JAX_PLATFORMS=cpu for
    itself even when it inherited the engine's JAX_PLATFORMS=tpu."""
    from raftsql_tpu.runtime import ring
    from raftsql_tpu.server import worker

    class Reached(Exception):
        pass

    def no_ring(*a, **kw):
        raise Reached(os.environ["JAX_PLATFORMS"])

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(ring, "RingClient", no_ring)
    with pytest.raises(Reached, match="^cpu$"):
        worker.main(["--rings", "nowhere", "--index", "0", "--port", "0"])


def test_stale_object_beside_changed_source_is_not_loaded(
        tmp_path, monkeypatch):
    """An artifact is named by a hash of its source: after the source
    changes, the old object is neither loaded nor kept."""
    import ctypes

    from raftsql_tpu.native import build

    monkeypatch.setattr(build, "_DIR", str(tmp_path))
    src = tmp_path / "answer.cc"
    src.write_text('extern "C" int answer() { return 1; }\n')
    so1 = build._artifact("_native_answer", [str(src)],
                          ("-shared", "-fPIC"), suffix=".so")
    if so1 is None:
        pytest.skip("no C++ toolchain")
    assert ctypes.CDLL(so1).answer() == 1
    # Same source: the same object, not rebuilt.
    assert build._artifact("_native_answer", [str(src)],
                           ("-shared", "-fPIC"), suffix=".so") == so1
    # Changed source, and an mtime that would have fooled a timestamp
    # check (the old object looks newer than the source).
    src.write_text('extern "C" int answer() { return 2; }\n')
    os.utime(src, (1, 1))
    so2 = build._artifact("_native_answer", [str(src)],
                          ("-shared", "-fPIC"), suffix=".so")
    assert so2 != so1
    assert ctypes.CDLL(so2).answer() == 2
    assert not os.path.exists(so1)
