"""One group's `apply_batch` of a drained run, measured inside the thread
that ran it (`stages.put.apply_batch`, runtime/db.py `_apply_group`),
window mean: at SQLite, one transaction of a group's statements.
"""
from lib import stages


def read(before, after, client, trace):
    return stages.engine_mean_ms(before, after, "put.apply_batch")
