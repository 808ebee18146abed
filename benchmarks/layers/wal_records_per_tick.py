"""Log entries handed to the WALs per tick of the window, leaders' appends
and followers' mirrors of all peers together (`wal.records` over `ticks`;
runtime/hostplane.py `_durable_phases`).
"""
from lib import stats


def read(before, after, client, trace):
    return stats.per(before["engine"], after["engine"],
                     "wal.records", "ticks")
