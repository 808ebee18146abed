"""Share of the window's accepted follower appends that were dropped
before phase 1 listed them, because they could change no log: empty
heartbeat acks (`wal.mirror_skipped_rows` over it plus
`wal.mirror_rows`; runtime/hostplane.py `_mirror_keep`).  None where the
program has no such counter (before PR 29) or no append was accepted.
"""
from lib import stats


def read(before, after, client, trace):
    skipped = stats.delta(before["engine"], after["engine"],
                          "wal.mirror_skipped_rows")
    rows = stats.delta(before["engine"], after["engine"], "wal.mirror_rows")
    if skipped is None or rows is None or not skipped + rows:
        return None
    return 100.0 * skipped / (skipped + rows)
