"""Real-process cluster smoke test — the Procfile topology end to end.

The reference's proof of life is 3 OS processes wired by real sockets
(reference Procfile:2-4, raftsql_test.go:16-41).  The in-process cluster
tests all ride LoopbackTransport; these tests boot 3 actual
`raftsql_tpu.server.main` processes on localhost (TcpTransport + HTTP API
+ WAL + SQLite) via the chaos harness's ProcCluster, drive them with the
hardened HTTP client (api/client.py — per-request timeouts, backoff,
leader caching, retry tokens: the former private `sql`/`put_when_up`/
`get_retry` helpers, done properly once), then crash-restart a node and
require catch-up.
"""
import pytest

from raftsql_tpu.api.client import RaftSQLClient, SQLError
from raftsql_tpu.chaos.proc import ProcCluster

TIMEOUT = 90.0


def _boot3(tmp_path, groups: int = 1):
    c = ProcCluster(str(tmp_path), peers=3, groups=groups, tick=0.02)
    for i in range(3):
        c.spawn(i)
    cli = RaftSQLClient([f"127.0.0.1:{p}" for p in c.http_ports],
                        timeout_s=10.0)
    return c, cli


def _logs(c: ProcCluster) -> str:
    return "\n".join(f"--- node{i + 1} ---\n" + c.log_tail(i, 2000)
                     for i in range(3))


def test_three_process_cluster_put_get_restart(tmp_path):
    c, cli = _boot3(tmp_path)
    try:
        # README curl recipe: PUT on node 1, INSERT via node 2, read on 3.
        cli.put("CREATE TABLE t (name text)", node=0,
                deadline_s=TIMEOUT)
        cli.put("INSERT INTO t (name) VALUES ('abc')", node=1,
                deadline_s=TIMEOUT)
        cli.get_until("SELECT name FROM t", "|abc|\n", node=2,
                      deadline_s=TIMEOUT)
        # Method semantics over the real stack: 405 + Allow header.
        status, _, _ = cli.raw(0, "POST", "/", "x")
        assert status == 405
        # Bad SQL propagates the apply error as 400 (reference
        # httpapi.go:45-49 blocking-PUT contract) — the client must NOT
        # retry a deterministic failure.
        with pytest.raises(SQLError):
            cli.put("INSERT INTO nosuch VALUES (1)", node=0,
                    deadline_s=TIMEOUT)

        # Crash node 2 (SIGKILL), write while it is down, restart it, and
        # require the missed write to stream in from the leader
        # (reference raftsql_test.go:117-170).
        c.sigkill(1)
        cli.put("INSERT INTO t (name) VALUES ('while-down')", node=0,
                deadline_s=TIMEOUT)
        c.spawn(1)
        try:
            cli.get_until("SELECT count(*) FROM t", "|2|\n", node=1,
                          deadline_s=TIMEOUT)
        except BaseException:
            print(_logs(c))
            raise
        # Clean stop is SIGTERM (graceful-shutdown handler): the WAL is
        # flushed and every process exits 0 — SIGKILL above was "crash",
        # this is "stop".
        codes = c.stop_all()
        assert codes == [0, 0, 0], (codes, _logs(c))
    finally:
        c.stop_all()


def test_multi_group_over_real_processes(tmp_path):
    """The flagship axis (N raft groups) over the reference's proof-of-
    life topology (3 OS processes, real sockets): writes routed to
    distinct groups via different nodes, per-group isolation (each group
    is its own SQLite database), and group state surviving a SIGKILL
    crash/restart — VERDICT r2 task 7."""
    c, cli = _boot3(tmp_path, groups=4)
    try:
        # One table per group, created via a different node each time;
        # rows encode the group id.
        for g in range(4):
            node = g % 3
            cli.put("CREATE TABLE t (v text)", group=g, node=node,
                    deadline_s=TIMEOUT)
            cli.put(f"INSERT INTO t (v) VALUES ('g{g}')", group=g,
                    node=node, deadline_s=TIMEOUT)
        # Every node serves every group; each group sees ONLY its row.
        for g in range(4):
            for node in range(3):
                cli.get_until("SELECT v FROM t", f"|g{g}|\n", group=g,
                              node=node, deadline_s=TIMEOUT)
        # Unknown group -> 400, not a crash.
        status, _, _ = cli.raw(0, "GET", "/", "SELECT v FROM t",
                               headers={"X-Raft-Group": "99"})
        assert status == 400

        # Crash node 3; write to two different groups while it is down;
        # restart; both groups' missed writes must stream in, and the
        # untouched groups must stay isolated.
        c.sigkill(2)
        cli.put("INSERT INTO t (v) VALUES ('late1')", group=1, node=0,
                deadline_s=TIMEOUT)
        cli.put("INSERT INTO t (v) VALUES ('late3')", group=3, node=1,
                deadline_s=TIMEOUT)
        c.spawn(2)
        try:
            for g, want in ((1, "|2|\n"), (3, "|2|\n"),
                            (0, "|1|\n"), (2, "|1|\n")):
                cli.get_until("SELECT count(*) FROM t", want, group=g,
                              node=2, deadline_s=TIMEOUT)
        except BaseException:
            print(_logs(c))
            raise
    finally:
        c.stop_all()


# The server entry point made to behave at 4 groups the way size alone
# makes it behave at `--groups 10000`, once the test arms it by creating
# the file `big`: a tick is always in flight and lasts (0.5 s pass
# between its top and its durable phase; one tick is ~0.3 s there), and
# closing the state machines at shutdown lasts (10,000 SQLite handles
# there).
_BIG_SERVER_AT_SMALL_G = """
import os, sys, time
import raftsql_tpu.models.sqlite_sm as sm
import raftsql_tpu.runtime.hostplane as hp
_tick = hp.ClusterHostPlane.tick
def long_tick(self):
    if not os.path.exists("big"):
        return _tick(self)
    time.sleep(0.5)
    _tick(self)
    self._tick_active = self._spin_hot = True      # never park
hp.ClusterHostPlane.tick = long_tick
_close = sm.SQLiteStateMachine.close
def slow_close(self):
    time.sleep(0.2)
    return _close(self)
sm.SQLiteStateMachine.close = slow_close
from raftsql_tpu.server.main import main
main(sys.argv[1:])
"""


def test_sigterm_with_a_tick_in_flight_is_a_clean_stop(tmp_path):
    """SIGTERM while a long tick is in flight exits 0 with the WAL
    flushed.  The engine used to die there with EXIT_CODE_FATAL
    ("cannot schedule new futures after shutdown"): the main thread fell
    off main() while the shutdown thread was still stopping the engine,
    and interpreter finalization tore the WAL sync pool down under the
    tick that was still running."""
    import os
    import signal
    import subprocess
    import sys
    import time

    from tests.conftest import free_port

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    port = free_port()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    with open(tmp_path / "server.log", "wb") as logf:
        proc = subprocess.Popen(
            [sys.executable, "-c", _BIG_SERVER_AT_SMALL_G, "--fused",
             "--groups", "4", "--port", str(port)],
            cwd=tmp_path, env=env, stdout=logf, stderr=logf)
    try:
        cli = RaftSQLClient([f"127.0.0.1:{port}"], timeout_s=10.0)
        cli.put("CREATE TABLE t (v text)", deadline_s=TIMEOUT)
        cli.put("INSERT INTO t (v) VALUES ('acked')", deadline_s=TIMEOUT)
        cli.close()         # no connection left for the HTTP plane to drain
        (tmp_path / "big").touch()
        time.sleep(1.2)             # a long tick is in flight by now
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=60)
        log = (tmp_path / "server.log").read_text()
        assert rc == 0, log[-2000:]
        assert "consensus engine failed" not in log
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
