"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the served path once, end to end, through the entry points a user
would call, at the size a user of a multi-raft store would call real:

    python -m raftsql_tpu.server.main --fused --workers 2 \\
        --groups 10000 --peers 3          (every other flag at its default)

i.e. 10,000 raft groups x 3 peers elected, ticking, durable (group-commit
WAL, fsync before ack) and served over HTTP into SQLite.  Phases, in order;
any failed phase, any mismatch, any child exit code other than the expected
one ends the run non-zero:

  probe     a child reports the device the rule selects
            (raftsql_tpu/utils/device.py); it must be a `tpu` with the
            number of chips asked for.
  cold      start the deployment in a scratch data dir; poll /healthz until
            every group reports a leader; the engine's own /healthz must say
            platform `tpu`, the device count asked for, native WAL loaded.
  load      CREATE TABLE in 256 groups spread over the whole id range (every
            39th group), then 4,096 INSERTs (16 per group, values from
            --seed) over 32 keep-alive connections.  Every answer but 204 is
            a failure.
  reads     the same acknowledged statements go into a plain in-process
            sqlite3 per group — the independent reference — and SELECTs are
            compared, as sorted sets, in every read mode of README's
            consistency table this deployment serves: local (poll-retry,
            stale by design), session, follower, linear.  /metrics must show
            reads.shm_hits > 0: the worker-mapped plane served.
  restart   SIGKILL the engine, start it again with the same flags on the
            same data dir (parity mode rebuilds SQLite from the WAL), read
            every acknowledged row back with a linear read.  The second
            start must report compile-cache hits.
  stop      SIGTERM; exit code 0 required.
  device    after the server released the chip: one bounded bench child at
            the design point, G=100,000, device-only, checked only to
            compile, run on the `tpu` and commit.  The served path takes
            that size too (`--groups 100000`: the benchmark's cell
            `ycsb-a-100ktenants`): the store opens a SQLite file only for a
            group in use, within the descriptors the process has
            (models/store.py), and a /healthz of 100,000 rows (~9.6 MB)
            reaches a worker in pieces of a quarter of its ring
            (runtime/ring.py); this script keeps to 10,000 to stay quick.

`--chips 4` runs the same phases against
`--mesh --group-shards 4 --workers 2 --groups 10000` and additionally
requires /healthz to show state, inbox and step output laid over four
distinct devices.

This parent never imports JAX: a chip belongs to one process at a time, so
everything that needs it is a child, one at a time.  There is no CPU mode on
the command line; tests import the phases and drive them against a
`JAX_PLATFORMS=cpu` server at a small size.

Last line of stdout, once the probe has found the chips asked for (before
that nothing is printed and the exit code alone says why), exactly:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
`ok` is false when a later phase failed.  What the run observed is the
`chip_smoke: summary:` line above it.
"""
from __future__ import annotations

import argparse
import dataclasses
from http.client import HTTPConnection, HTTPException
import json
import os
import random
import shutil
import signal
import socket
import sqlite3
import subprocess
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
TIME_LIMIT_S = 1200.0
READ_MODES = ("local", "session", "follower", "linear")
SELECT = "SELECT k, v FROM t"


class SmokeFailure(Exception):
    """A phase did not meet its contract."""


@dataclasses.dataclass(frozen=True)
class Shape:
    """How big the smoke is.  The command line always runs DEPLOYMENT;
    tests shrink it."""
    groups: int = 10_000
    peers: int = 3
    tables: int = 256           # groups that get a table ...
    stride: int = 39            # ... every `stride`-th group id
    rows_per_table: int = 16
    conns: int = 32
    device_groups: int = 100_000
    device_ticks: int = 64

    def table_groups(self) -> List[int]:
        gs = [i * self.stride for i in range(self.tables)]
        if gs[-1] >= self.groups:
            raise ValueError("tables x stride exceeds the group range")
        return gs


DEPLOYMENT = Shape()


def say(key: str, value) -> None:
    print(f"chip_smoke: {key}: {value}", flush=True)


# -- processes -----------------------------------------------------------

def child_env() -> dict:
    """Children inherit the environment untouched (the platform is
    whatever the machine gives — never pinned here) plus the checkout on
    PYTHONPATH."""
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    return env


def probe_device(timeout_s: float = 180.0) -> dict:
    """What the device rule selects in a fresh process (which exits, and
    so releases the chip, before the server starts)."""
    r = subprocess.run(
        [sys.executable, "-c",
         "import json; from raftsql_tpu.utils.device import select_device;"
         " print(json.dumps(select_device()))"],
        env=child_env(), cwd=HERE, stdout=subprocess.PIPE, text=True,
        timeout=timeout_s)
    if r.returncode != 0:
        raise SmokeFailure(f"device probe exited {r.returncode}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def require_device(dev: dict, platform: str, chips: int, who: str) -> None:
    if dev.get("platform") != platform or dev.get("count") != chips:
        raise SmokeFailure(
            f"{who} runs on platform={dev.get('platform')!r} "
            f"count={dev.get('count')}; required {platform!r} x{chips}")


def result_line(ok: bool, dev: dict) -> str:
    """The contract's last line of stdout: these keys and no others."""
    return json.dumps({"ok": ok, "device": {
        "platform": str(dev["platform"]), "kind": str(dev["device_kind"]),
        "count": int(dev["count"])}})


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def server_argv(shape: Shape, chips: int, port: int) -> List[str]:
    deploy = ["--fused"] if chips == 1 else \
        ["--mesh", "--group-shards", str(chips)]
    return [sys.executable, "-m", "raftsql_tpu.server.main", *deploy,
            "--workers", "2", "--groups", str(shape.groups),
            "--peers", str(shape.peers), "--port", str(port)]


class Server:
    """One engine process (plus the workers it spawns) in `data_dir`."""

    def __init__(self, shape: Shape, chips: int, data_dir: str, port: int):
        self.port = port
        self.t0 = time.monotonic()
        self.log_path = os.path.join(data_dir, "server.log")
        self._log = open(self.log_path, "ab")
        self._log_from = self._log.tell()
        # Own session: cleanup can take the workers down with the engine
        # even if the engine is already gone.
        self.proc = subprocess.Popen(
            server_argv(shape, chips, port), cwd=data_dir, env=child_env(),
            stdout=self._log, stderr=self._log, start_new_session=True)

    def since_start(self) -> float:
        return time.monotonic() - self.t0

    def say_log(self) -> None:
        """Print what this engine's own loggers (`raftsql.*`: device,
        boot timings, warnings) wrote."""
        with open(self.log_path, "rb") as f:
            f.seek(self._log_from)
            text = f.read().decode("utf-8", "replace")
        for line in text.splitlines():
            if " raftsql" in line:
                say("server log", line[:300])

    def signal_engine(self, sig: int, timeout_s: float) -> int:
        self.proc.send_signal(sig)
        return self.proc.wait(timeout=timeout_s)

    def destroy(self) -> None:
        """Kill whatever is left of the engine's process group."""
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        self.proc.wait()
        self._log.close()


# -- HTTP ----------------------------------------------------------------

def request(conn: HTTPConnection, method: str, path: str = "/",
            body: str = "", headers: Optional[dict] = None
            ) -> Tuple[int, Dict[str, str], str]:
    conn.request(method, path, body=body.encode(), headers=headers or {})
    resp = conn.getresponse()
    data = resp.read().decode("utf-8", "replace")
    return resp.status, {k.lower(): v for k, v in resp.getheaders()}, data


def connect(port: int, timeout_s: float = 60.0) -> HTTPConnection:
    return HTTPConnection("127.0.0.1", port, timeout=timeout_s)


def get_doc(port: int, path: str) -> dict:
    conn = connect(port)
    try:
        status, _, body = request(conn, "GET", path)
    finally:
        conn.close()
    if status != 200:
        raise SmokeFailure(f"GET {path} answered {status}: {body[:200]}")
    return json.loads(body)


def wait_all_led(server: Server, shape: Shape, deadline: float) -> dict:
    """Poll /healthz until it answers and EVERY group reports a leader.
    Returns the health doc, with the seconds since process start at which
    /healthz first answered and at which the last group had a leader."""
    up_s = None
    while True:
        if server.proc.poll() is not None:
            raise SmokeFailure(
                f"server exited {server.proc.returncode} during start-up")
        if time.monotonic() > deadline:
            raise SmokeFailure("server not ready in time")
        try:
            doc = get_doc(server.port, "/healthz")
        except (OSError, HTTPException, SmokeFailure):
            time.sleep(0.5)
            continue
        if up_s is None:
            up_s = server.since_start()
        groups = doc.get("groups", {})
        led = sum(1 for row in groups.values() if row.get("leader", 0) > 0)
        if doc.get("ready") and len(groups) == shape.groups \
                and led == shape.groups:
            doc["_healthz_up_s"] = round(up_s, 2)
            doc["_all_led_s"] = round(server.since_start(), 2)
            return doc
        time.sleep(1.0)


# -- load ----------------------------------------------------------------

def make_statements(shape: Shape, seed: int
                    ) -> Tuple[List[Tuple[int, str]], List[Tuple[int, str]]]:
    """(creates, inserts) as (group, sql) lists; insert values derive from
    `seed`."""
    rng = random.Random(seed)
    groups = shape.table_groups()
    creates = [(g, "CREATE TABLE t (k INTEGER, v TEXT)") for g in groups]
    inserts = []
    k = 0
    for _ in range(shape.rows_per_table):
        for g in groups:
            inserts.append(
                (g, f"INSERT INTO t (k, v) VALUES ({k}, "
                    f"'{rng.getrandbits(64):016x}')"))
            k += 1
    return creates, inserts


class Acked:
    """What the server acknowledged: per group the statements (the
    reference's input) and the highest X-Raft-Session watermark seen."""

    def __init__(self) -> None:
        self._mu = threading.Lock()
        self.statements: Dict[int, List[str]] = {}
        self.watermark: Dict[int, int] = {}
        self.attempted = 0
        self.acked = 0
        self.failures: List[str] = []

    def record(self, group: int, sql: str, status: int, headers: dict,
               body: str) -> None:
        with self._mu:
            self.attempted += 1
            if status != 204:
                self.failures.append(
                    f"g{group} {sql[:48]!r} -> {status} {body[:120]!r}")
                return
            self.acked += 1
            self.statements.setdefault(group, []).append(sql)
            wm = int(headers.get("x-raft-session", 0))
            if wm > self.watermark.get(group, 0):
                self.watermark[group] = wm


def on_connections(port: int, n: int, work) -> None:
    """Run work(conn_box, j) on n threads, each with its own keep-alive
    connection in conn_box[0] (replaceable by the worker); the first
    exception any of them raised is re-raised here."""
    errors: List[BaseException] = []

    def run(j: int) -> None:
        box = [connect(port)]
        try:
            work(box, j)
        except BaseException as e:          # re-raised below
            errors.append(e)
        finally:
            box[0].close()

    threads = [threading.Thread(target=run, args=(j,), name=f"conn-{j}")
               for j in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]


def put_all(port: int, work: List[Tuple[int, str]], conns: int,
            acked: Acked) -> None:
    """Send every (group, sql) as a PUT over `conns` keep-alive
    connections, statement i on connection i % conns."""
    def run(box: list, j: int) -> None:
        for group, sql in work[j::conns]:
            try:
                status, headers, body = request(
                    box[0], "PUT", body=sql,
                    headers={"X-Raft-Group": str(group)})
            except (OSError, HTTPException) as e:
                # A request that got no answer counts as failed.
                status, headers, body = 0, {}, repr(e)
                box[0].close()
                box[0] = connect(port)
            acked.record(group, sql, status, headers, body)

    on_connections(port, conns, run)


# -- answers -------------------------------------------------------------

def reference_rows(statements: List[str]) -> List[str]:
    """The plain reference: one in-process SQLite fed the acknowledged
    statements, its SELECT rendered the way the server renders rows
    (`|v1|v2|`), sorted — concurrent writers leave order undefined."""
    db = sqlite3.connect(":memory:")
    try:
        for sql in statements:
            db.execute(sql)
        rows = db.execute(SELECT).fetchall()
    finally:
        db.close()
    return sorted("|" + "|".join(str(v) for v in row) + "|" for row in rows)


def read_rows(conn, group: int, mode: str, watermark: int
              ) -> Tuple[int, List[str]]:
    headers = {"X-Raft-Group": str(group)}
    if mode != "local":
        headers["X-Consistency"] = mode
    if mode == "session":
        headers["X-Raft-Session"] = str(watermark)
    status, _, body = request(conn, "GET", body=SELECT, headers=headers)
    return status, sorted(body.splitlines())


def compare_reads(port: int, acked: Acked, modes, threads: int = 8) -> dict:
    """Read every table group in every mode and compare with the
    reference.  Returns {"rows_compared", "rows_mismatched", "shm_hits"};
    shm_hits is the largest reads.shm_hits any reading connection's
    worker reported on /metrics afterwards."""
    want = {g: reference_rows(stmts)
            for g, stmts in acked.statements.items()}
    groups = sorted(want)
    mu = threading.Lock()
    out = {"rows_compared": 0, "rows_mismatched": 0, "shm_hits": 0}
    bad: List[str] = []

    def run(box: list, j: int) -> None:
        conn = box[0]
        for mode in modes:
            for g in groups[j::threads]:
                # local reads are stale by design: poll-retry.
                give_up = time.monotonic() + (20.0 if mode == "local"
                                              else 0.0)
                while True:
                    status, got = read_rows(
                        conn, g, mode, acked.watermark.get(g, 0))
                    if (status == 200 and got == want[g]) \
                            or time.monotonic() >= give_up:
                        break
                    time.sleep(0.05)
                with mu:
                    out["rows_compared"] += len(want[g])
                    if status != 200 or got != want[g]:
                        miss = len(set(want[g]) ^ set(got)) or 1
                        out["rows_mismatched"] += miss
                        bad.append(f"{mode} g{g}: status {status}, "
                                   f"{len(got)} rows, want "
                                   f"{len(want[g])}")
        # This keep-alive connection is pinned to one worker: its
        # /metrics carries that worker's own shm counters.
        status, _, body = request(conn, "GET", "/metrics")
        if status == 200:
            hits = json.loads(body).get("reads", {}).get("shm_hits", 0)
            with mu:
                out["shm_hits"] = max(out["shm_hits"], int(hits))

    on_connections(port, threads, run)
    for line in bad[:20]:
        say("MISMATCH", line)
    return out


# -- device-only child ---------------------------------------------------

def device_child(shape: Shape, platform: str, timeout_s: float) -> dict:
    """The design-point device program the server cannot reach, as one
    bounded bench child: compile, run on `platform`, commit."""
    env = child_env()
    env.update({"BENCH_CHILD": "1", "BENCH_CONFIG": "headline",
                "BENCH_GROUPS": str(shape.device_groups), "BENCH_E": "32",
                "BENCH_SKIP_SWEEP": "1",
                "BENCH_TICKS": str(shape.device_ticks),
                "BENCH_REPEATS": "1"})
    r = subprocess.run([sys.executable, os.path.join(HERE, "bench.py")],
                       env=env, cwd=HERE, stdout=subprocess.PIPE,
                       text=True, timeout=timeout_s)
    if r.returncode != 0:
        raise SmokeFailure(f"device child exited {r.returncode}")
    out = json.loads(r.stdout.strip().splitlines()[-1])
    if out.get("platform") != platform or not out.get("value", 0) > 0:
        raise SmokeFailure(f"device child did not commit on {platform!r}: "
                           f"{out}")
    return out


# -- the run -------------------------------------------------------------

def check_engine(doc: dict, platform: str, chips: int) -> None:
    require_device(doc.get("device", {}), platform, chips, "the engine")
    if not doc.get("native_wal"):
        raise SmokeFailure("the native WAL did not load (the engine fell "
                           "back to the Python WAL)")
    if chips > 1:
        mesh = doc.get("mesh") or {}
        for leaf in ("state_devices", "inbox_devices", "info_devices"):
            held = mesh.get(leaf, [])
            if len(set(held)) != chips:
                raise SmokeFailure(
                    f"mesh {leaf} = {held}: not laid over {chips} "
                    f"distinct devices")


def smoke(shape: Shape, chips: int, seed: int, dev: dict,
          data_dir: str, deadline: float) -> dict:
    """Every phase after the probe, in order (module docstring), on the
    device `dev` the probe found.  Returns the summary; raises on the
    first phase that fails."""
    def left() -> float:
        return deadline - time.monotonic()

    platform = dev["platform"]
    say("platform", dev["platform"])
    say("device_kind", dev["device_kind"])
    say("device count", dev["count"])
    say("jax", dev["jax"])
    say("G/P", f"{shape.groups}/{shape.peers}")

    port = free_port()
    acked = Acked()
    server = Server(shape, chips, data_dir, port)
    try:
        # -- cold start
        health = wait_all_led(server, shape, time.monotonic()
                              + min(600.0, left()))
        server.say_log()
        check_engine(health, platform, chips)
        say("native WAL loaded", health["native_wal"])
        say("native apply loaded", health["native_apply"])
        if chips > 1:
            say("mesh placement", json.dumps(health["mesh"]))
        # -- load (the first statement of the first connection is the
        # cold start's first 204)
        creates, inserts = make_statements(shape, seed)
        put_all(port, creates[:1], 1, acked)
        cold_204_s = server.since_start()
        put_all(port, creates[1:], shape.conns, acked)
        put_all(port, inserts, shape.conns, acked)
        for line in acked.failures:
            say("FAILED request", line)
        say("seconds to first 204 (cold)",
            f"{cold_204_s:.2f} (healthz up {health['_healthz_up_s']}, "
            f"all {shape.groups} groups led {health['_all_led_s']})")
        say("requests attempted/acked/failed",
            f"{acked.attempted}/{acked.acked}/{len(acked.failures)}")
        if acked.failures:
            raise SmokeFailure("not every request was acknowledged")
        # -- answers
        reads = compare_reads(port, acked, READ_MODES)
        say("rows compared/mismatched",
            f"{reads['rows_compared']}/{reads['rows_mismatched']} "
            f"(modes {', '.join(READ_MODES)})")
        say("reads.shm_hits", reads["shm_hits"])
        if reads["rows_mismatched"]:
            raise SmokeFailure("server answers differ from the reference")
        if reads["shm_hits"] <= 0:
            raise SmokeFailure("no read was served from the worker-mapped "
                               "shm plane (reads.shm_hits == 0)")
        metrics = get_doc(port, "/metrics")
        say("phase_ms_per_tick (mean over all ticks since start)",
            json.dumps(metrics["phase_ms_per_tick"]))
        say("tick phase p50/p99 ms (n)", json.dumps(
            {ph: [st.get("p50_ms"), st.get("p99_ms"), st.get("n")]
             for ph, st in metrics.get("phase_profile", {}).items()
             if isinstance(st, dict)}))
        say("ticks", metrics.get("ticks"))
        say("peak device memory (bytes)",
            metrics["device"]["peak_bytes_in_use"])
        cold_cache = metrics["device"]["compile_cache"]
        say("compile cache (cold start)", json.dumps(cold_cache))
        # -- guarantees: crash, restart, read everything back
        rc = server.signal_engine(signal.SIGKILL, 30.0)
        if rc != -signal.SIGKILL:
            raise SmokeFailure(f"SIGKILLed engine exited {rc}")
    finally:
        server.destroy()

    server = Server(shape, chips, data_dir, port)
    try:
        health = wait_all_led(server, shape, time.monotonic()
                              + min(600.0, left()))
        server.say_log()
        check_engine(health, platform, chips)
        before = acked.acked
        put_all(port, [(shape.table_groups()[0],
                        "INSERT INTO t (k, v) VALUES (-1, 'after-restart')")],
                1, acked)
        warm_204_s = server.since_start()
        if acked.acked != before + 1:
            raise SmokeFailure("the restarted server did not acknowledge "
                               f"a write: {acked.failures[-1:]}")
        say("seconds to first 204 (after SIGKILL + restart)",
            f"{warm_204_s:.2f} (healthz up {health['_healthz_up_s']}, "
            f"all {shape.groups} groups led {health['_all_led_s']})")
        back = compare_reads(port, acked, ("linear",))
        say("rows read back after restart compared/mismatched",
            f"{back['rows_compared']}/{back['rows_mismatched']}")
        if back["rows_mismatched"]:
            raise SmokeFailure("acknowledged rows were lost or changed "
                               "across SIGKILL + restart")
        warm_cache = get_doc(port, "/healthz")["device"]["compile_cache"]
        say("compile cache (restart)", json.dumps(warm_cache))
        if warm_cache["hits"] <= 0:
            raise SmokeFailure("the restart compiled everything again: no "
                               "compile-cache hit")
        # -- clean stop
        rc = server.signal_engine(signal.SIGTERM, min(180.0, left()))
        say("exit code on SIGTERM", rc)
        if rc != 0:
            raise SmokeFailure(f"SIGTERM exited {rc}, not 0")
        # Both engines spawned their workers after JAX was up: neither
        # may have forked to do it (JAX warns "os.fork() was called").
        with open(server.log_path, "rb") as f:
            if b"os.fork()" in f.read():
                raise SmokeFailure("the engine forked after initialising "
                                   "JAX (os.fork() warning in its log)")
    finally:
        server.destroy()

    # -- the chip is free again: the device-only design point
    dchild = device_child(shape, platform, min(420.0, left()))
    say(f"device child G={shape.device_groups}",
        f"{dchild['value']:.1f} commits/s on {dchild['platform']} "
        f"({dchild['device_kind']} x{dchild['devices']}), tick_ms "
        f"{dchild.get('tick_ms')}, peak bytes "
        f"{dchild.get('peak_bytes_in_use')}")
    return {
        "platform": dev["platform"], "device_kind": dev["device_kind"],
        "devices": dev["count"], "jax": dev["jax"],
        "groups": shape.groups, "peers": shape.peers,
        "native_wal": health["native_wal"],
        "native_apply": health["native_apply"],
        "first_204_cold_s": round(cold_204_s, 2),
        "first_204_restart_s": round(warm_204_s, 2),
        "requests": {"attempted": acked.attempted, "acked": acked.acked,
                     "failed": len(acked.failures)},
        "rows": {"compared": reads["rows_compared"]
                 + back["rows_compared"], "mismatched": 0},
        "shm_hits": reads["shm_hits"],
        "phase_ms_per_tick": metrics["phase_ms_per_tick"],
        "compile_cache": {"cold": cold_cache, "restart": warm_cache},
        "device_child": {"groups": shape.device_groups,
                         "commits_per_s": dchild["value"],
                         "platform": dchild["platform"]},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="serve 10,000 raft groups from the TPU once, check "
                    "the answers, crash it, read everything back")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(HERE, "raftsql_tpu")):
        print("chip_smoke: no raftsql_tpu package beside this script",
              file=sys.stderr)
        return 2
    deadline = time.monotonic() + TIME_LIMIT_S - 30.0
    # Beside the checkout, not under /tmp: the WAL's fsyncs should hit
    # the machine's disk, not a tmpfs.  (`raftsql-*/` is git-ignored.)
    data_dir = tempfile.mkdtemp(prefix="raftsql-smoke-", dir=HERE)
    dev = None          # set once the probe found the chips asked for
    ok = False
    try:
        probed = probe_device()
        require_device(probed, "tpu", args.chips, "the device probe")
        dev = probed
        summary = smoke(DEPLOYMENT, args.chips, args.seed, dev, data_dir,
                        deadline)
        say("summary", json.dumps(summary))
        ok = True
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        log_path = os.path.join(data_dir, "server.log")
        if os.path.exists(log_path):
            with open(log_path, "rb") as f:
                tail = f.read()[-6000:].decode("utf-8", "replace")
            print(f"chip_smoke: end of the server's log:\n{tail}",
                  file=sys.stderr)
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)
        if dev is not None:     # no accelerator: no result at all
            print(result_line(ok, dev), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
