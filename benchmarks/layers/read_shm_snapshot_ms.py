"""A worker's seqlock read of the shm header and the one row a read needs
(`worker_stages.get.shm_snapshot`, runtime/shm.py `try_read` around
`_snapshot_row`), window mean over every read that reached it, summed
over the workers as read_shm_hit_pct does: the price of asking the
mapping before the ring fallback.  `None` where the program has no such
stage or no read asked in the window.
"""
from lib import stages


def read(before, after, client, trace):
    return stages.worker_mean_ms(before, after, "get.shm_snapshot")
