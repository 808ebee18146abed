"""Share of the state-machine store's uses that found their handle
closed, in % (100 Δ`sm.misses` / Δ`sm.uses`; models/store.py `_pin`
counts both under its lock, read at the scrape as gauges).  A miss
reopens the file (a connect, two pragmas, the `_raft_meta` read) on the
thread of the apply or read that made it.  0 wherever the budget holds
every group in use.  `None` where no handle was used in the window, or
the program counts neither.
"""
from lib import stats


def read(before, after, client, trace):
    share = stats.per(before["engine"], after["engine"], "sm.misses",
                      "sm.uses")
    return None if share is None else 100.0 * share
