"""The typed refusals of the read and write paths.

A module of their own, free of imports, so that an HTTP worker process
(server/worker.py over runtime/ring.py's RingClient) can raise and catch
them without loading the engine (runtime/db.py pulls in the consensus
runtime, and with it JAX).  runtime/db.py re-exports both names.
"""
from __future__ import annotations


class NotLeaderError(Exception):
    """A linearizable read hit a non-leader; retry at `leader` (1-based
    node id, 0 = unknown)."""

    def __init__(self, group: int, leader: int):
        super().__init__(
            f"group {group}: not the leader"
            + (f"; leader is node {leader}" if leader > 0 else ""))
        self.group = group
        self.leader = leader


class ReadTimeout(TimeoutError):
    """A read could not be served within the request timeout — a TYPED,
    RETRYABLE condition (quorum unreachable mid-ReadIndex round, apply
    lagging the read point, a session watermark not yet replicated, or
    leadership lost mid-round without a forward hint).  Subclasses
    TimeoutError so both HTTP planes keep answering 503 Service
    Unavailable (retry-at-will), never a 400; `phase` names which wait
    ran out, so a client log pinpoints the stall."""

    def __init__(self, group: int, phase: str, detail: str):
        super().__init__(f"group {group}: {detail}")
        self.group = group
        self.phase = phase
