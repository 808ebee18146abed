"""Inside `wal_write`: collecting the mirror metadata from the packed info
and planning the parallel path (`wal_plan`, runtime/hostplane.py
`_durable_phases` phase 1), per tick of the window.
"""
from lib import stages


def read(before, after, client, trace):
    return stages.phase_ms_per_tick(before, after, "wal_plan")
