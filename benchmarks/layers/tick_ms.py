"""Wall time of one tick of the host plane inside the window: the time
between the two scrapes over the ticks counted between them.
"""
from lib import stats


def read(before, after, client, trace):
    ticks = stats.delta(before["engine"], after["engine"], "ticks")
    if not ticks:
        return None
    return (after["t"] - before["t"]) * 1e3 / ticks
