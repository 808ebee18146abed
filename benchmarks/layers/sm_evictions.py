"""Handles the store released in the window to stay inside its budget
(`sm.evictions`, models/store.py `_take_slot`); 0 wherever the budget
holds every group in use.  `None` where the program has no store.
"""
from lib import stats


def read(before, after, client, trace):
    return stats.delta(before["engine"], after["engine"], "sm.evictions")
