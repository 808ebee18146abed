"""Time of one drain batch of the workers' propose rings.

The `ring_drain` phase's cumulative `total_ms` on /metrics (`phase_profile`,
obs/prof.py) after the window minus before it, per recorded batch in between.  The
profiler's own ring percentiles are not used: they mix set-up ticks in.
"""
from lib import stats


def read(before, after, client, trace):
    return stats.per(before["engine"], after["engine"],
                     "phase_profile.ring_drain.total_ms", "phase_profile.ring_drain.n")
