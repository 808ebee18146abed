"""The load generator: one process, one asyncio loop, many keep-alive
HTTP/1.1 connections, each a closed-loop client (it sends its next
request when the previous answer is wholly read — YCSB's `threadcount`,
etcd's `--clients`).

    python benchmarks/lib/loadgen.py <spec.json>

The spec (written by run.py) names the port, the ops module, the cell's
parameters, the seed, and either

  mode "mix"   clients [first, count): each draws its own endless stream
               from ops.<module>.client(p, seed, cid) and runs it until
               the window the runner announces on stdin
               ("window <t0> <t1>", CLOCK_MONOTONIC seconds) has ended;
  mode "list"  a fixed list of requests, each sent once, spread over
               `connections` connections (schema, load, read-back).

Every request is timed from the first byte written to the last byte of
the answer read.  A request that raises, times out or is cut is logged
with status 0 and its connection is replaced.  The log goes to the
spec's `out` file as one JSON document when the work is done; stdin
closing early (the runner died) ends the process at once.

This file imports nothing from the program under test.
"""
from __future__ import annotations

import asyncio
import importlib
import json
import os
import sys
import time
import zlib
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REQUEST_TIMEOUT_S = 40.0    # beyond the server's own 30 s -> 503
CONNECT_PARALLEL = 48       # under the workers' listen backlog of 256


class Conn:
    """One keep-alive connection, replaced on any failure."""

    def __init__(self, port: int, gate: asyncio.Semaphore):
        self.port = port
        self.gate = gate
        self.reader: Optional[asyncio.StreamReader] = None
        self.writer: Optional[asyncio.StreamWriter] = None

    async def open(self) -> None:
        async with self.gate:
            self.reader, self.writer = await asyncio.wait_for(
                asyncio.open_connection("127.0.0.1", self.port), 30.0)

    def close(self) -> None:
        if self.writer is not None:
            self.writer.close()
        self.reader = self.writer = None

    async def _exchange(self, head: bytes, body: bytes
                        ) -> Tuple[int, Dict[str, str], bytes]:
        self.writer.write(head + body)
        raw = await self.reader.readuntil(b"\r\n\r\n")
        lines = raw[:-4].split(b"\r\n")
        status = int(lines[0].split(b" ", 2)[1])
        headers = {}
        for line in lines[1:]:
            k, _, v = line.partition(b":")
            headers[k.strip().lower().decode("latin-1")] = \
                v.strip().decode("latin-1")
        n = int(headers.get("content-length", 0))
        data = await self.reader.readexactly(n) if n else b""
        return status, headers, data

    async def request(self, method: str, group: int, sql: str,
                      extra: str = "") -> Tuple[int, int, str, float, float]:
        """(status, watermark, body, t_sent, t_answered); status 0 for a
        request that got no whole answer."""
        body = sql.encode()
        head = (f"{method} / HTTP/1.1\r\nHost: bench\r\n"
                f"Content-Length: {len(body)}\r\nX-Raft-Group: {group}\r\n"
                f"{extra}\r\n").encode("latin-1")
        t_sent = time.monotonic()
        try:
            if self.writer is None:
                await self.open()
                t_sent = time.monotonic()
            status, headers, data = await asyncio.wait_for(
                self._exchange(head, body), REQUEST_TIMEOUT_S)
        except (OSError, asyncio.IncompleteReadError,
                asyncio.LimitOverrunError, asyncio.TimeoutError,
                ValueError, IndexError) as e:
            self.close()
            return 0, 0, repr(e)[:200], t_sent, time.monotonic()
        return (status, int(headers.get("x-raft-session", 0) or 0),
                data.decode("utf-8", "replace"), t_sent, time.monotonic())


def read_headers(mode: str, watermark: int) -> str:
    if mode == "local":
        return ""
    extra = f"X-Consistency: {mode}\r\n"
    if mode in ("session", "follower"):
        extra += f"X-Raft-Session: {watermark}\r\n"
    return extra


class Mix:
    """The closed-loop clients of one generator process."""

    def __init__(self, spec: dict):
        self.spec = spec
        self.ops = importlib.import_module("ops." + spec["ops"])
        self.p = spec["params"]
        self.t0 = self.t1 = float("inf")
        self.cpu = {}
        self.log: List[list] = []

    def announce(self, t0: float, t1: float) -> None:
        loop = asyncio.get_running_loop()
        self.t0, self.t1 = t0, t1
        for name, at in (("t0", t0), ("t1", t1)):
            loop.call_at(at, lambda n=name: self.cpu.__setitem__(
                n, (time.process_time(), time.monotonic())))

    async def client(self, cid: int, gate: asyncio.Semaphore) -> None:
        ops, p = self.ops, self.p
        conn = Conn(self.spec["port"], gate)
        marks: Dict[int, int] = {}          # group -> highest watermark seen
        mode = p.get("read_consistency", "local")
        try:
            await conn.open()
        except (OSError, asyncio.TimeoutError):
            pass                            # the first request retries
        for kind, key, field, val in ops.client(p, self.spec["seed"], cid):
            if time.monotonic() >= self.t1:
                break
            g = ops.group_of(p, key)
            if kind == "w":
                status, wm, body, ts, ta = await conn.request(
                    "PUT", g, ops.write_sql(key, field, val))
                got = val
            else:
                status, wm, body, ts, ta = await conn.request(
                    "GET", g, ops.read_sql(key),
                    read_headers(mode, marks.get(g, 0)))
                got = digest_row(ops, body) if status == 200 else body[:120]
            if wm > marks.get(g, 0):
                marks[g] = wm
            self.log.append([cid, kind, key, field, got, ts, ta, status, wm])
        conn.close()

    async def run(self) -> dict:
        gate = asyncio.Semaphore(CONNECT_PARALLEL)
        first, count = self.spec["clients"]
        tasks = [asyncio.ensure_future(self.client(cid, gate))
                 for cid in range(first, first + count)]
        await asyncio.gather(*tasks)
        return {"ops": self.log, "cpu": self.cpu}


def digest_row(ops, body: str):
    """A read's answer, small enough to log: crc32 per field, None for no
    row, "?..." for an answer that is not one row of the table."""
    try:
        row = ops.parse_row(body)
    except ValueError as e:
        return "?" + str(e)
    return None if row is None else [zlib.crc32(v.encode()) for v in row]


async def run_list(spec: dict) -> dict:
    """Send spec["requests"] = [[method, group, sql, extra_headers], ...]
    once each, request i on connection i % connections; bodies of GETs
    are kept whole."""
    reqs = spec["requests"]
    n = max(1, min(spec["connections"], len(reqs)))
    gate = asyncio.Semaphore(CONNECT_PARALLEL)
    out: List[Optional[list]] = [None] * len(reqs)

    async def worker(j: int) -> None:
        conn = Conn(spec["port"], gate)
        for i in range(j, len(reqs), n):
            method, group, sql, extra = reqs[i]
            status, wm, body, ts, ta = await conn.request(
                method, group, sql, extra)
            keep = body if method == "GET" else body[:400]
            out[i] = [status, wm, keep, ts, ta]
        conn.close()

    await asyncio.gather(*[asyncio.ensure_future(worker(j))
                           for j in range(n)])
    return {"answers": out}


async def amain(spec: dict) -> dict:
    loop = asyncio.get_running_loop()
    if spec["mode"] == "list":
        return await run_list(spec)
    mix = Mix(spec)

    def on_stdin() -> None:
        line = sys.stdin.readline()
        if not line:                        # the runner is gone
            os._exit(3)
        word, *args = line.split()
        if word == "window":
            mix.announce(float(args[0]), float(args[1]))

    loop.add_reader(sys.stdin.fileno(), on_stdin)
    try:
        return await mix.run()
    finally:
        loop.remove_reader(sys.stdin.fileno())


def main(argv: List[str]) -> int:
    with open(argv[1]) as f:
        spec = json.load(f)
    result = asyncio.run(amain(spec))
    result["cpu_total_s"] = time.process_time()
    tmp = spec["out"] + ".tmp"
    with open(tmp, "w") as f:
        json.dump(result, f)
    os.replace(tmp, spec["out"])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
