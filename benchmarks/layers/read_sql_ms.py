"""The SELECT itself on the engine's SQLite (`stages.get.sql`,
runtime/db.py `query`), window mean.
"""
from lib import stages


def read(before, after, client, trace):
    return stages.engine_mean_ms(before, after, "get.sql")
