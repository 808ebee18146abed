"""A ring GET's wait for a read-pool thread in the engine: record popped by
the drain to `_run` started (`stages.get.queue`, runtime/ring.py
`_handle_get`), window mean.
"""
from lib import stages


def read(before, after, client, trace):
    return stages.engine_mean_ms(before, after, "get.queue")
