"""Groups with any WAL record (entry or hard state, any peer) per tick of
the window (`wal.groups_written` over `ticks`; runtime/hostplane.py
`_wal_counts`): the groups the durable phase had work for, against the
groups it walks.
"""
from lib import stats


def read(before, after, client, trace):
    return stats.per(before["engine"], after["engine"],
                     "wal.groups_written", "ticks")
