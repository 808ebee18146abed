"""State machines (SQLite handles) open at the after-scrape
(`sm.open_handles`, models/store.py `StateMachineStore`): the groups in
use, not all G.  `None` where the program has no store.
"""
from lib import stats


def read(before, after, client, trace):
    if stats.dig(after["engine"], "sm.opens") is None:
        return None
    return stats.dig(after["engine"], "sm.open_handles")
