"""Closed WAL segments a sweep unlinked (`wal.segments_unlinked` over
`compact.sweeps`; storage/wal.py `WAL.compact` returns the count,
runtime/hostplane.py `compact` hands it over).  0 on a node whose quiet
groups pin the oldest segment; `None` where no sweep ran in the window.
"""
from lib import stats


def read(before, after, client, trace):
    return stats.per(before["engine"], after["engine"],
                     "wal.segments_unlinked", "compact.sweeps")
