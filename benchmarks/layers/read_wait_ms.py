"""A read's wait for its freshness in the engine: arrival in `RaftDB.query`
to `_wait_applied` / `_linear_wait` returned (`stages.get.wait`,
runtime/db.py), window mean over the modes the cell sends.
"""
from lib import stages


def read(before, after, client, trace):
    return stages.engine_mean_ms(before, after, "get.wait")
