"""Bytes the WALs of all peers grew by in the window (`wal.bytes`, entry and
hard-state records; runtime/hostplane.py `_count_wal`) per write
acknowledged (`stages.put.apply.n`).
"""
from lib import stats


def read(before, after, client, trace):
    return stats.per(before["engine"], after["engine"],
                     "wal.bytes", "stages.put.apply.n")
