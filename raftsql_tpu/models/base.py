"""State-machine protocol applied by committed log entries.

The reference hard-wires SQLite as its one state machine (reference
db.go:13-20); here apply/query are a protocol so multiple state-machine
families plug into the same replication engine: `sqlite_sm` (reference
parity) and `kv_sm` (dependency-free, used by benchmarks and chaos tests).

Snapshot/resume (beyond the reference, SURVEY.md §5.4): a state machine
MAY track the log index of the last applied entry durably and atomically
with the apply itself (`applied_index`).  The engine then resumes by
skipping re-apply of entries at or below it instead of deleting state and
replaying the full log (the reference's db.go:27-29 behavior, still the
default), and may compact the WAL prefix the snapshot covers.
"""
from __future__ import annotations

from typing import Optional, Protocol


class StateMachine(Protocol):
    def apply(self, command: str, index: int = 0) -> Optional[Exception]:
        """Execute a committed write command; returns the error, if any.
        Must be deterministic: every replica applies the same sequence.
        `index` is the entry's log position (1-based); snapshotting state
        machines persist it atomically with the command's effects."""
        ...

    def query(self, q: str) -> str:
        """Read-only local query; raises on invalid queries."""
        ...

    def applied_index(self) -> int:
        """Durable log index of the last applied entry; 0 if fresh or not
        tracked.  Only meaningful when the machine persists it atomically
        with apply (see SQLiteStateMachine resume mode).

        Machines whose applied_index survives a process crash advertise it
        with a truthy `has_durable_snapshot` attribute; the engine treats
        everything else as floor 0 for WAL compaction (compacting on a
        volatile index silently loses data on restart).  Such a machine
        also has `checkpoint() -> int`: put what was applied on disk and
        return the applied index that now survives a POWER loss (a
        commit need not sync); the compaction sweep drops the raft log
        only under an index this has returned (models/store.py)."""
        ...

    def close(self) -> None: ...
