#!/usr/bin/env python3
"""Time a crash restart of the served 10,000-group node, by hand.

    python3 scripts/restart_probe.py [--groups 10000] [--writes 3000] \
        [--modes resume,parity] [--seed N] [--nofile N]

For each mode: start `server.main --fused --workers 2 --groups G
--peers 3` (+ `--resume --compact-every 1024 --compact-keep 256` in
resume mode) in a scratch directory, create and load YCSB's usertable
as the benchmark does (benchmarks/ops/ycsb.py, the `multiraft-10k`
scale), send `--writes` one-field updates over 48 connections, SIGKILL
the engine and its workers, start the same command on the same
directory, and report: seconds from spawn to the first `204`, the
bytes and entries of raft WAL the restart had to read, what it applied,
and a `linear` read-back of every key written (and 200 others) against
the plain reference (benchmarks/lib/reference.py), which was fed every
acknowledged statement.  One JSON line a mode; exit 1 on a mismatch.

`--nofile N` lowers RLIMIT_NOFILE, soft and hard, for this process and
so for the engine it starts: at 1,100 a `--resume` store has (1,100 -
512) / 3 = 196 handles for 256 table groups, so it evicts and reopens
under the load (`sm.evictions` in `before_kill`), which no cell of the
benchmark makes it do at G=10,000.

No cell of the benchmark restarts an engine (the runner cannot: PERF.md
section 7, `leaderkill-10kgroups`); this is the measurement that cell
will guard.  Touches no JAX: the engine holds the chip.
"""
import argparse
import http.client
import json
import os
import random
import shutil
import signal
import sys
import tempfile
import threading
import time
import zlib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
sys.path.insert(0, ROOT)

from lib import reference                               # noqa: E402
from lib.engine import Engine, free_port                # noqa: E402
from ops import ycsb                                    # noqa: E402

CONNS = 48
RESUME = ["--resume", "--compact-every", "1024", "--compact-keep", "256"]


def request(conn, method, group, sql, linear=False):
    headers = {"X-Raft-Group": str(group)}
    if linear:
        headers["X-Consistency"] = "linear"
    conn.request(method, "/", body=sql, headers=headers)
    resp = conn.getresponse()
    return resp.status, resp.read().decode()


def fan_out(port, jobs, work):
    """Run work(conn, job) for every job over CONNS keep-alive
    connections; returns the results in job order."""
    out = [None] * len(jobs)
    nxt = iter(range(len(jobs)))
    lock = threading.Lock()

    def run():
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
        while True:
            with lock:
                i = next(nxt, None)
            if i is None:
                break
            out[i] = work(conn, jobs[i])
        conn.close()

    threads = [threading.Thread(target=run) for _ in range(CONNS)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return out


def put_all(port, stmts, ref, what):
    got = fan_out(port, stmts,
                  lambda c, j: request(c, "PUT", j[0], j[1])[0])
    bad = [s for s in got if s != 204]
    if bad:
        raise SystemExit(f"{what}: {len(bad)} of {len(stmts)} not 204 "
                         f"({bad[:5]})")
    for g, sql in stmts:
        ref.apply(g, sql)


def updates(p, seed, n):
    """n one-field updates by YCSB's zipfian; a key's updates all go
    over one connection, in order, so the reference knows their order."""
    per_conn = [[] for _ in range(CONNS)]
    gen = ycsb.client(dict(p, read_share=0.0, distribution="zipfian"),
                      seed, 0)
    for _ in range(n):
        _kind, key, field, val = next(gen)
        per_conn[zlib.crc32(key.encode()) % CONNS].append(
            (key, ycsb.group_of(p, key), ycsb.write_sql(key, field, val)))
    return per_conn


def wal_on_disk(data_dir, groups):
    """(bytes, entries, entries with a statement) a restart reads."""
    from raftsql_tpu.storage.wal import GroupCommitWAL, _segment_paths
    d = os.path.join(data_dir, "raftsql-fused", "gc")
    size = sum(os.path.getsize(p) for _, p in _segment_paths(d))
    flat = GroupCommitWAL.replay_flat(d)
    entries = sum(len(gl.entries) for gl in flat.values())
    peer0 = sum(1 for fg, gl in flat.items() if fg < groups
                for (_t, data) in gl.entries if data)
    return size, entries, peer0


def spawn(data_dir, argv, port):
    eng = Engine(ROOT, ["-m", "raftsql_tpu.server.main"], argv, {},
                 data_dir, port)
    return eng


def first_204(eng, deadline_s=600.0):
    """Seconds from spawn to the first acknowledged write."""
    sql = "CREATE TABLE IF NOT EXISTS restart_probe (x)"
    while time.monotonic() - eng.t_spawn < deadline_s:
        if eng.proc.poll() is not None:
            raise SystemExit(f"engine exited {eng.proc.returncode}")
        try:
            conn = http.client.HTTPConnection("127.0.0.1", eng.port,
                                              timeout=30)
            status, _ = request(conn, "PUT", 0, sql)
            conn.close()
            if status == 204:
                return time.monotonic() - eng.t_spawn
        except (OSError, http.client.HTTPException):
            pass
        time.sleep(0.25)
    raise SystemExit("no 204 in time")


def probe(mode, args, p):
    data_dir = tempfile.mkdtemp(prefix=f"raftsql-restart-{mode}-",
                                dir=os.getcwd())
    argv = ["--fused", "--workers", "2", "--groups", str(args.groups),
            "--peers", "3"] + (RESUME if mode == "resume" else [])
    ref = reference.Reference()
    out = {"mode": mode, "groups": args.groups}
    eng = spawn(data_dir, argv, free_port())
    try:
        health = eng.wait_ready(args.groups, args.platform, 1)
        out["cold_all_led_s"] = health["all_led_s"]
        put_all(eng.port, ycsb.schema(p), ref, "schema")
        put_all(eng.port, ycsb.load(p, args.seed), ref, "load")
        plan = updates(p, args.seed, args.writes)
        t0 = time.monotonic()

        def writer(conn, jobs):
            return [request(conn, "PUT", g, sql)[0] for _k, g, sql in jobs]
        got = fan_out(eng.port, plan, writer)
        out["writes_s"] = round(time.monotonic() - t0, 2)
        written = {}
        for jobs, statuses in zip(plan, got):
            for (key, g, sql), status in zip(jobs, statuses):
                if status != 204:
                    raise SystemExit(f"update answered {status}")
                ref.apply(g, sql)
                written[key] = g
        doc = eng.get_doc("/metrics")
        out["before_kill"] = {
            "compact": doc.get("compact"), "sm": doc.get("sm"),
            "compact_stages": doc["stages"].get("compact"),
            "wal_bytes_since_boot": doc["wal"]["bytes"],
            "wal_disk_bytes": doc["wal"].get("disk_bytes"),
            "wal_segments_unlinked": doc["wal"].get("segments_unlinked")}
        os.killpg(eng.proc.pid, signal.SIGKILL)     # engine and workers
        eng.proc.wait()
        size, entries, stmts = wal_on_disk(data_dir, args.groups)
        out["wal_bytes_read"] = size
        out["wal_entries_replayed"] = entries
        out["statements_in_peer0_log"] = stmts
        eng2 = spawn(data_dir, argv, free_port())
        try:
            out["spawn_to_first_204_s"] = round(first_204(eng2), 2)
            doc = eng2.get_doc("/metrics")
            out["after_restart"] = {
                "sm_opens": (doc.get("sm") or {}).get("opens"),
                "apply_runs": doc["apply"]["runs"],
                "apply_batch_total_ms": doc["stages"]["put"][
                    "apply_batch"]["total_ms"]}
            rnd = random.Random(args.seed)
            keys = sorted(written) + [
                k for k in ycsb.sample_keys(p, args.seed, 200)
                if k not in written]
            rnd.shuffle(keys)

            want = {k: ref.query(ycsb.group_of(p, k), ycsb.read_sql(k))
                    for k in keys}

            def read(conn, key):
                status, body = request(conn, "GET", ycsb.group_of(p, key),
                                       ycsb.read_sql(key), linear=True)
                return status == 200 and body == want[key]
            ok = fan_out(eng2.port, keys, read)
            out["read_back_keys"] = len(keys)
            out["read_back_mismatches"] = ok.count(False)
        finally:
            eng2.destroy()
    finally:
        eng.destroy()
        ref.close()
        shutil.rmtree(data_dir, ignore_errors=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--groups", type=int, default=10000)
    ap.add_argument("--writes", type=int, default=3000)
    ap.add_argument("--modes", default="resume,parity")
    ap.add_argument("--platform", default="tpu")
    ap.add_argument("--seed", type=int, default=2147484032)
    ap.add_argument("--nofile", type=int, default=0)
    args = ap.parse_args()
    if args.nofile:
        import resource
        resource.setrlimit(resource.RLIMIT_NOFILE,
                           (args.nofile, args.nofile))
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "multiraft-10k.json")) as f:
        p = json.load(f)["scale"]
    if args.groups < 10000:        # a rehearsal: fewer, smaller groups
        p = dict(p, table_groups=8, group_stride=max(args.groups // 8, 1),
                 recordcount=512, rows_per_insert=64)
    p = dict(p, distribution="zipfian", read_share=0.0)
    bad = 0
    for mode in args.modes.split(","):
        out = probe(mode, args, p)
        print(json.dumps(out), flush=True)
        bad += out["read_back_mismatches"]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
