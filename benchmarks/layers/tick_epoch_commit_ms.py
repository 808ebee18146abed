"""Host time a dispatch spends on its commit record: the write and fsync
of `EPOCHS` after the WAL barrier, which makes a multi-step dispatch
durable as a whole (runtime/hostplane.py; `phase_profile.epoch_commit`,
recorded since PR 33; the leaf span is `tick.epoch_commit`).  `total_ms`
difference per tick of the window.  It is in neither `tick_fsync_ms`
(the WAL barrier alone) nor `tick_wal_write_ms`.  A one-step dispatch
writes no such record and has no such phase (the mesh): None there.
"""
from lib import stages


def read(before, after, client, trace):
    return stages.phase_ms_per_tick(before, after, "epoch_commit")
