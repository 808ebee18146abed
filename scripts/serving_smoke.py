"""Serving smoke — the CI gate for the multi-worker deployment.

Boots `server/main.py --fused --workers 2` (one engine process + two
SO_REUSEPORT HTTP workers sharing it through the propose ring), drives
it with the native epoll loadgen (`native/http_load.cc`; Python client
threads when the toolchain is absent) for a few seconds, and asserts
ZERO errors and a req/s floor.

    python scripts/serving_smoke.py
    SMOKE_SECONDS=10 SMOKE_CLIENTS=32 SMOKE_MIN_RPS=200 ...

`--reads` runs the READ-PLANE smoke instead (PR 12): the same
deployment with leases on, interleaved PUTs and session GETs from
concurrent clients, asserting (a) no session read ever answers below
the client's own PUT watermark (read-your-writes across workers), and
(b) the worker-mapped shared-memory fast path actually served reads
(`reads.shm_hits > 0` in /metrics — the zero-round-trip plane is live,
not silently falling back to the ring).

Exit 0 on pass; 1 with a diagnostic (and the server log tail) on fail.
"""
from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def python_loadgen(port: int, groups: int, seconds: float,
                   clients: int) -> dict:
    from raftsql_tpu.api.client import RaftSQLClient
    client = RaftSQLClient([port], timeout_s=10,
                           max_conns_per_node=clients + 4)
    n = [0]
    errors = [0]
    stop_at = time.monotonic() + seconds

    def worker(ci: int) -> None:
        k = 0
        while time.monotonic() < stop_at:
            k += 1
            try:
                client.put(f"INSERT INTO t (v) VALUES ('c{ci}_{k}')",
                           group=(ci + k) % groups, deadline_s=10)
                n[0] += 1
            except Exception:                           # noqa: BLE001
                errors[0] += 1
    threads = [threading.Thread(target=worker, args=(i,), daemon=True)
               for i in range(clients)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.monotonic() - t0
    client.close()
    return {"n": n[0], "errors": errors[0], "secs": dt}


def main() -> int:
    groups = int(os.environ.get("SMOKE_GROUPS", "4"))
    seconds = float(os.environ.get("SMOKE_SECONDS", "10"))
    clients = int(os.environ.get("SMOKE_CLIENTS", "32"))
    min_rps = float(os.environ.get("SMOKE_MIN_RPS", "200"))
    workers = int(os.environ.get("SMOKE_WORKERS", "2"))
    port = free_port()
    tmp = tempfile.mkdtemp(prefix="serving-smoke-")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    logf = open(os.path.join(tmp, "server.log"), "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "raftsql_tpu.server.main", "--fused",
         "--workers", str(workers), "--groups", str(groups),
         "--port", str(port), "--tick", "0.004"],
        cwd=tmp, env=env, stdout=logf, stderr=logf)

    def fail(msg: str) -> int:
        print(f"serving-smoke: FAIL: {msg}", file=sys.stderr)
        try:
            with open(os.path.join(tmp, "server.log")) as f:
                print(f.read()[-2000:], file=sys.stderr)
        except OSError:
            pass
        if proc.poll() is None:
            proc.kill()
        return 1

    try:
        from raftsql_tpu.api.client import RaftSQLClient
        boot = RaftSQLClient([port], timeout_s=10)
        boot.wait_healthy(0, deadline_s=120)
        for g in range(groups):
            boot.put("CREATE TABLE t (v text)", group=g, deadline_s=60)
        boot.close()

        loadgen = None
        if os.environ.get("SMOKE_LOADGEN", "native") == "native":
            from raftsql_tpu.native.build import build_http_load
            loadgen = build_http_load()
        if loadgen is not None:
            out = subprocess.run(
                [loadgen, str(seconds), str(clients), str(groups),
                 str(port)],
                capture_output=True, text=True, timeout=seconds + 60)
            if out.returncode != 0:
                return fail(f"loadgen rc={out.returncode}: "
                            f"{out.stderr[-500:]}")
            j = json.loads(out.stdout.strip())
        else:
            j = python_loadgen(port, groups, seconds, clients)
        rate = j["n"] / max(j["secs"], 1e-9)
        status, _, text = RaftSQLClient([port]).raw(0, "GET", "/metrics")
        m = json.loads(text) if status == 200 else {}
        print(f"serving-smoke: {j['n']} PUTs in {j['secs']:.1f}s -> "
              f"{rate:,.0f} req/s, {j['errors']} errors; "
              f"ring_workers={m.get('ring_workers')} "
              f"wal_group_commits={m.get('wal_group_commits')}")
        if j["errors"]:
            return fail(f"{j['errors']} errored requests")
        if rate < min_rps:
            return fail(f"{rate:,.0f} req/s below the {min_rps:,.0f} "
                        "floor")
        if m.get("ring_workers") != workers:
            return fail(f"ring_workers={m.get('ring_workers')} != "
                        f"{workers}")
        proc.send_signal(signal.SIGTERM)
        if proc.wait(timeout=30) != 0:
            return fail(f"server exit code {proc.returncode}")
        print("serving-smoke: PASS")
        return 0
    except Exception as e:                              # noqa: BLE001
        return fail(repr(e))
    finally:
        if proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except Exception:                           # noqa: BLE001
                proc.kill()
        logf.close()


def reads_main() -> int:
    """--reads: the zero-round-trip read-plane gate."""
    groups = int(os.environ.get("SMOKE_GROUPS", "2"))
    seconds = float(os.environ.get("SMOKE_SECONDS", "8"))
    clients = int(os.environ.get("SMOKE_CLIENTS", "8"))
    workers = int(os.environ.get("SMOKE_WORKERS", "2"))
    port = free_port()
    tmp = tempfile.mkdtemp(prefix="serving-smoke-reads-")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    logf = open(os.path.join(tmp, "server.log"), "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "raftsql_tpu.server.main", "--fused",
         "--workers", str(workers), "--groups", str(groups),
         "--port", str(port), "--tick", "0.004",
         "--lease-ticks", "6"],
        cwd=tmp, env=env, stdout=logf, stderr=logf)

    def fail(msg: str) -> int:
        print(f"serving-smoke --reads: FAIL: {msg}", file=sys.stderr)
        try:
            with open(os.path.join(tmp, "server.log")) as f:
                print(f.read()[-2000:], file=sys.stderr)
        except OSError:
            pass
        if proc.poll() is None:
            proc.kill()
        return 1

    try:
        from raftsql_tpu.api.client import RaftSQLClient
        boot = RaftSQLClient([port], timeout_s=10)
        boot.wait_healthy(0, deadline_s=120)
        for g in range(groups):
            boot.put("CREATE TABLE t (k INTEGER PRIMARY KEY, v text)",
                     group=g, deadline_s=60)
        boot.close()

        client = RaftSQLClient([port], timeout_s=10,
                               max_conns_per_node=clients + 4)
        stats = {"puts": 0, "gets": 0, "stale": 0, "errors": 0,
                 "linear_gets": 0, "linear_stale": 0}
        mu = threading.Lock()
        stop_at = time.monotonic() + seconds

        def worker(ci: int) -> None:
            g = ci % groups
            session = 0
            k = 0
            while time.monotonic() < stop_at:
                k += 1
                try:
                    wm = client.put(
                        f"INSERT OR REPLACE INTO t VALUES "
                        f"({ci * 1000000 + k}, 'v{k}')",
                        group=g, deadline_s=10)
                    if wm:
                        session = max(session, wm)
                    # A session read carrying my own PUT watermark must
                    # never answer from below it — whichever worker,
                    # whichever path (shm fast path or ring) serves it.
                    rows, echo = client.get_session(
                        "SELECT count(*) FROM t", group=g,
                        consistency="session", session=session,
                        deadline_s=10)
                    # A linear read issued after the PUT acked must
                    # observe it, whichever path serves it — the shm
                    # lease fast path gets no refresh-window grace
                    # (this is exactly the stale-commit-column bug
                    # class: acked write invisible inside the ~2ms
                    # restamp window).
                    lrows, _ = client.get_session(
                        f"SELECT count(*) FROM t WHERE "
                        f"k = {ci * 1000000 + k}", group=g,
                        consistency="linear", deadline_s=10)
                    with mu:
                        stats["puts"] += 1
                        stats["gets"] += 1
                        stats["linear_gets"] += 1
                        if echo is not None and echo < session:
                            stats["stale"] += 1
                        if lrows.strip() != "|1|":
                            stats["linear_stale"] += 1
                except Exception:                       # noqa: BLE001
                    with mu:
                        stats["errors"] += 1
        threads = [threading.Thread(target=worker, args=(i,),
                                    daemon=True)
                   for i in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        status, _, text = client.raw(0, "GET", "/metrics")
        m = json.loads(text) if status == 200 else {}
        reads = m.get("reads", {})
        client.close()
        print(f"serving-smoke --reads: {stats['puts']} PUTs / "
              f"{stats['gets']} session GETs "
              f"({stats['linear_gets']} linear), {stats['stale']} "
              f"stale, {stats['linear_stale']} linear-stale, "
              f"{stats['errors']} errors; shm_hits="
              f"{reads.get('shm_hits')} shm_fallbacks="
              f"{reads.get('shm_fallbacks')}")
        if stats["errors"]:
            return fail(f"{stats['errors']} errored requests")
        if stats["gets"] < clients:
            return fail(f"only {stats['gets']} session reads ran")
        if stats["stale"]:
            return fail(f"{stats['stale']} session reads observed a "
                        "watermark below the client's own PUT")
        if stats["linear_stale"]:
            return fail(f"{stats['linear_stale']} linear reads missed "
                        "an acked PUT (linearizability violation)")
        if not reads.get("shm_hits"):
            return fail("reads.shm_hits == 0: the shared-memory fast "
                        "path served nothing (scrape hit a worker "
                        "whose mapping is dead, or the plane is off)")
        proc.send_signal(signal.SIGTERM)
        if proc.wait(timeout=30) != 0:
            return fail(f"server exit code {proc.returncode}")
        print("serving-smoke --reads: PASS")
        return 0
    except Exception as e:                              # noqa: BLE001
        return fail(repr(e))
    finally:
        if proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except Exception:                           # noqa: BLE001
                proc.kill()
        logf.close()


if __name__ == "__main__":
    sys.exit(reads_main() if "--reads" in sys.argv[1:] else main())
