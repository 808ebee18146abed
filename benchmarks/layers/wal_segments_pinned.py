"""Closed WAL segments the last sweep before the after-scrape had to
leave (`wal.segments_pinned`, a gauge the WAL keeps: storage/wal.py
`WAL.compact`), because one holds a live entry of some group and the
sequence stays contiguous behind it.  `None` where the program has no
such gauge or no sweep has run since boot.
"""
from lib import stats


def read(before, after, client, trace):
    if not stats.dig(after["engine"], "compact.sweeps"):
        return None
    return stats.dig(after["engine"], "wal.segments_pinned")
