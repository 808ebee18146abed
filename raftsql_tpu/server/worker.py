"""HTTP worker process for the multi-worker serving plane.

Spawned by `server/main.py --workers N` (one process per worker): runs
the asyncio HTTP plane (api/aio.py) over a `RingClient` facade
(runtime/ring.py) instead of an in-process RaftDB — every proposal
becomes a record in this worker's mmap'd propose ring, every ack a
completion-ring record resolved into the event loop.  All N workers
bind the SAME port with SO_REUSEPORT; the kernel spreads connections.

The worker holds no consensus, storage, or SQLite state: it can be
killed and respawned freely (in-flight requests on its connections
fail; the engine's retry-token dedup keeps client-side retries
exactly-once).  It exits when its parent's rings disappear or on
SIGTERM.
"""
from __future__ import annotations

import argparse
import logging
import os
import signal


def _die_with_engine(engine_pid: int) -> None:
    """PR_SET_PDEATHSIG on ourselves: a worker must not outlive its
    engine — a SIGKILLed engine (crash, OOM) would otherwise leave
    orphan workers serving a dead ring forever.  Armed here, not in a
    preexec_fn of the engine (which would fork a process whose JAX
    threads are running); the getppid() check closes the window in
    which the engine died before the arm."""
    import ctypes
    try:
        ctypes.CDLL("libc.so.6", use_errno=True).prctl(
            1, signal.SIGTERM)                  # PR_SET_PDEATHSIG
    except OSError:                      # pragma: no cover - non-linux
        return
    if os.getppid() != engine_pid:
        os._exit(0)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description="raftsql HTTP ring worker")
    ap.add_argument("--rings", required=True,
                    help="ring directory created by the engine process")
    ap.add_argument("--index", type=int, required=True,
                    help="worker index (selects the ring pair)")
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--engine-pid", type=int, default=0,
                    help="pid of the spawning engine: the worker exits "
                         "when that process dies (0 = standalone)")
    ap.add_argument("--timeout", type=float, default=30.0)
    ap.add_argument("--trace", action="store_true",
                    help="stamp each ring round trip into a per-process "
                         "trace segment (pid/worker-id tagged) the "
                         "engine's /trace merges into one Perfetto "
                         "timeline")
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)
    logging.basicConfig(
        level=logging.DEBUG if args.verbose else logging.INFO,
        format="%(asctime)s worker%(process)d %(levelname)s %(message)s")

    if args.engine_pid:
        _die_with_engine(args.engine_pid)
    # The chip belongs to the engine.  The worker never computes on a
    # device, and must not be able to take one: set (not setdefault —
    # an operator's JAX_PLATFORMS=tpu is meant for the engine) before
    # anything imports jax.
    os.environ["JAX_PLATFORMS"] = "cpu"
    from raftsql_tpu.api.aio import AioSQLServer
    from raftsql_tpu.runtime.ring import RingClient

    rdb = RingClient(args.rings, args.index, trace=args.trace)
    srv = AioSQLServer(args.port, rdb, timeout_s=args.timeout,
                       reuse_port=True)

    def _term(signum, frame):
        os._exit(0)

    signal.signal(signal.SIGTERM, _term)
    signal.signal(signal.SIGINT, _term)
    try:
        srv.serve_forever()
    finally:
        rdb.close()


if __name__ == "__main__":
    main()
