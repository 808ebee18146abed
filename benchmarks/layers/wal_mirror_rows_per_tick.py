"""Follower ranges handed to the WAL mirror, per tick of the window, all
peers together (`wal.mirror_rows` over `ticks`; runtime/hostplane.py
`_durable_phases` phase 1).  Since PR 29 only the accepted appends that
can change a log (`_mirror_keep`); before it every accepted append, the
empty heartbeat acks too: two a group a tick at three peers.
"""
from lib import stats


def read(before, after, client, trace):
    return stats.per(before["engine"], after["engine"],
                     "wal.mirror_rows", "ticks")
