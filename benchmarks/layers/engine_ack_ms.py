"""A PUT's whole residence in the engine process: record popped by
`RingServer._drain` to completion record pushed (`stages.put.engine`,
runtime/ring.py), window mean.  A cumulative pair, so confined to the
window (the `propose_ack_p*` ring of PR 24 could not be).
"""
from lib import stages


def read(before, after, client, trace):
    return stages.engine_mean_ms(before, after, "put.engine")
