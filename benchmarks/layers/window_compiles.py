"""Programs compiled inside the window (persistent-cache misses counted
by the engine); should be 0.
"""
from lib import stats


def read(before, after, client, trace):
    return stats.delta(before["engine"], after["engine"],
                       "device.compile_cache.misses")
