"""Proposed to commit observed: `AckFuture.created` to the moment the apply
thread drains the run that carries the entry (`stages.put.propose_commit`,
runtime/db.py `_ack_one`), window mean.  The ticks a write waits are here.
"""
from lib import stages


def read(before, after, client, trace):
    return stages.engine_mean_ms(before, after, "put.propose_commit")
