"""Host time a tick spends in the fsync barrier (it means nothing without
the filesystem of the scratch directory, which run.py prints).

The `fsync` phase's cumulative `total_ms` on /metrics (`phase_profile`,
obs/prof.py) after the window minus before it, per tick in between.  The
profiler's own ring percentiles are not used: they mix set-up ticks in.
"""
from lib import stats


def read(before, after, client, trace):
    return stats.per(before["engine"], after["engine"],
                     "phase_profile.fsync.total_ms", "ticks")
