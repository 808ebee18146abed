"""Inside `wal_write`: leader appends (2a) and follower mirror appends (2b)
(`wal_append`, runtime/hostplane.py `_durable_phases`; on the parallel
path the slowest peer's mirror), per tick of the window.
"""
from lib import stages


def read(before, after, client, trace):
    return stages.phase_ms_per_tick(before, after, "wal_append")
