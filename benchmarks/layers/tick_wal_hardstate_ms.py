"""Inside `wal_write`: finding and writing the changed hard states (2c)
(`wal_hardstate`, runtime/hostplane.py `_save_hard`; on the parallel path
the slowest peer's), per tick of the window.
"""
from lib import stages


def read(before, after, client, trace):
    return stages.phase_ms_per_tick(before, after, "wal_hardstate")
