"""Commit observed to ack fired: SQLite `apply_batch`, the shm delta publish
and the acks ahead of this one in the run (`stages.put.apply`,
runtime/db.py `_ack_one`), window mean.
"""
from lib import stages


def read(before, after, client, trace):
    return stages.engine_mean_ms(before, after, "put.apply")
