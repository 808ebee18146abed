"""MiB of the WAL segments that exist at the after-scrape
(`wal.disk_bytes`, a gauge the WAL keeps as it rotates and unlinks:
storage/wal.py `WAL.disk_bytes`; no directory is listed for it).  What
a restart has to read; beside `wal.bytes` since boot it says how much
of the log the sweeps have dropped.  `None` where the program has no
such gauge.
"""
from lib import stats


def read(before, after, client, trace):
    b = stats.dig(after["engine"], "wal.disk_bytes")
    # The name is in the document from boot; 0 means nobody keeps it.
    return b / 2 ** 20 if b else None
