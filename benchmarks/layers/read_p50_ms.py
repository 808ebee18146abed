"""The clients' median GET latency in this (traced) run, at the cell's
`X-Consistency`.  It stands here, not among the end-to-end metrics, because
in `ycsb-a-10kgroups` a read's wait for the tick's ReadIndex round spreads
too widely between runs for a bound of 25% (PERF.md, section 2).
"""


def read(before, after, client, trace):
    return client.get("read_p50_ms")
