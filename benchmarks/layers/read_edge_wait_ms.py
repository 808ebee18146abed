"""What a read spends in the HTTP worker outside its ring round trip (the
worker's own read pool, parse and reply): the clients' median GET latency
of this (traced) run less the window mean of
`worker_stages.get.ring_rtt` (runtime/ring.py `_consume`).  A median
against a mean, so a lead on where the wait is, to a few percent.
"""
from lib import stages


def read(before, after, client, trace):
    rtt = stages.worker_mean_ms(before, after, "get.ring_rtt")
    if rtt is None or client.get("read_p50_ms") is None:
        return None
    return client["read_p50_ms"] - rtt
