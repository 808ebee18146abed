"""Host time a tick spends delivering commits to the apply plane.

The `publish` phase's cumulative `total_ms` on /metrics (`phase_profile`,
obs/prof.py) after the window minus before it, per tick in between.  The
profiler's own ring percentiles are not used: they mix set-up ticks in.
"""
from lib import stats


def read(before, after, client, trace):
    return stats.per(before["engine"], after["engine"],
                     "phase_profile.publish.total_ms", "ticks")
