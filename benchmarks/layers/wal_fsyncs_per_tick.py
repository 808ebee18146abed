"""Durable barriers that had something to flush, per tick of the window,
all WALs together (`wal.fsyncs` over `ticks`; storage/wal.py `WAL.sync`).
"""
from lib import stats


def read(before, after, client, trace):
    return stats.per(before["engine"], after["engine"],
                     "wal.fsyncs", "ticks")
