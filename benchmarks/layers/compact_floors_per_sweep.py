"""Floors a sweep moved, groups x peers (`compact.floors_advanced` over
`compact.sweeps`; runtime/hostplane.py `compact`, one count() a sweep).
`None` where no sweep ran in the window.
"""
from lib import stats


def read(before, after, client, trace):
    return stats.per(before["engine"], after["engine"],
                     "compact.floors_advanced", "compact.sweeps")
