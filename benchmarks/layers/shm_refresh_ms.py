"""One restamp of the shm table's commit, leader and lease columns and the
publisher's heartbeat (`stages.shm.refresh`, runtime/ring.py
`_shm_refresh` around runtime/shm.py `refresh_columns`; the engine's
refresh thread, every ~2 ms), window mean: what the refresh holds of the
engine's interpreter each time, which the tick, drain, apply and read
threads wait for.  `None` where the program has no such stage.
"""
from lib import stages


def read(before, after, client, trace):
    return stages.engine_mean_ms(before, after, "shm.refresh")
