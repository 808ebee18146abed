"""Share of the follower ranges handed to the mirror in the window that
went through the Python two-pass mirror, because the native
`wal_mirror_all` could not take them (`wal.mirror_fallback_rows` over
`wal.mirror_rows`; runtime/hostplane.py `_durable_phases`).  None while
no range was mirrored.
"""
from lib import stats


def read(before, after, client, trace):
    share = stats.per(before["engine"], after["engine"],
                      "wal.mirror_fallback_rows", "wal.mirror_rows")
    return None if share is None else 100.0 * share
