"""Hard-state records written per tick of the window, all peers together
(`wal.hardstates` over `ticks`; runtime/hostplane.py `_save_hard`).
"""
from lib import stats


def read(before, after, client, trace):
    return stats.per(before["engine"], after["engine"],
                     "wal.hardstates", "ticks")
