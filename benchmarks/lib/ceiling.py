"""The load generator's own ceiling: the cell's generator processes against
a trivial local responder (204 to every PUT, one fixed row to every GET,
nothing behind it), so that a later session can tell when a cell has become
generator-bound.  Run once by hand, not part of any run:

    python3 benchmarks/lib/ceiling.py <traffic name> [seconds]

Prints ops/s and the generators' CPU share.  No JAX, no program code.
"""
from __future__ import annotations

import asyncio
import json
import multiprocessing
import os
import socket
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

ROW = ("|k|" + "|".join(["v" * 100] * 10) + "|\n").encode()
PUT = b"HTTP/1.1 204 No Content\r\nX-Raft-Session: 1\r\nContent-Length: 0\r\n\r\n"
GET = (b"HTTP/1.1 200 OK\r\nX-Raft-Session: 1\r\nContent-Length: "
       + str(len(ROW)).encode() + b"\r\n\r\n" + ROW)


class Responder(asyncio.Protocol):
    def connection_made(self, transport):
        self.tr, self.buf = transport, b""

    def data_received(self, data):
        self.buf += data
        while True:
            end = self.buf.find(b"\r\n\r\n")
            if end < 0:
                return
            head = self.buf[:end].lower()
            at = head.find(b"content-length:")
            n = int(head[at + 15:].split(b"\r\n", 1)[0]) if at >= 0 else 0
            if len(self.buf) < end + 4 + n:
                return
            self.tr.write(PUT if self.buf.startswith(b"PUT") else GET)
            self.buf = self.buf[end + 4 + n:]


def serve(port: int) -> None:
    async def main():
        loop = asyncio.get_running_loop()
        server = await loop.create_server(Responder, "127.0.0.1", port,
                                          reuse_port=True, backlog=1024)
        await server.serve_forever()
    asyncio.run(main())


def main(argv) -> int:
    from run import Generators, client_numbers, load_json
    traffic = load_json(os.path.join(BENCH, "traffic", argv[1] + ".json"))
    seconds = float(argv[2]) if len(argv) > 2 else 10.0
    p = dict({"table_groups": 1024, "group_stride": 9, "recordcount": 32768,
              "keyspace": 100000}, **traffic)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    ctx = multiprocessing.get_context("spawn")
    servers = [ctx.Process(target=serve, args=(port,), daemon=True)
               for _ in range(2)]
    for proc in servers:
        proc.start()
    time.sleep(1.0)
    with tempfile.TemporaryDirectory(dir=os.environ.get("TMPDIR")) as tmp:
        gens = Generators(tmp)
        try:
            gens.spawn_mix(port, traffic, p, 1)
            time.sleep(3.0)
            t0 = time.monotonic() + 0.25
            gens.tell(f"window {t0!r} {t0 + seconds!r}")
            docs = gens.collect(seconds + 60.0)
        finally:
            gens.destroy()
            for proc in servers:
                proc.terminate()
                proc.join()
    c = client_numbers([r for d in docs for r in d["ops"]], t0, t0 + seconds)
    cpu = [d["cpu"]["t1"][0] - d["cpu"]["t0"][0] for d in docs]
    print(json.dumps({"traffic": argv[1], "ops_per_s": c["ops_per_s"],
                      "failed": c["failed"], "processes": len(cpu),
                      "generator_busy_pct": 100 * sum(cpu)
                      / (seconds * len(cpu))}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
