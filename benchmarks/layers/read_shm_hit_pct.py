"""Share of shm-eligible reads the workers served from the shared-memory
snapshot without a ring trip.  The counts are per worker and a scrape
lands on whichever worker accepted its connection, so each of run.py's
scrape connections gives ONE worker's difference; they are summed, which
weights a worker by the connections that reached it.
"""
from lib import stats


def read(before, after, client, trace):
    hits = falls = 0
    for b, a in zip(before["workers"], after["workers"]):
        h = stats.delta(b, a, "reads.shm_hits")
        f = stats.delta(b, a, "reads.shm_fallbacks")
        if h is None or f is None:
            return None
        hits, falls = hits + h, falls + f
    if hits + falls == 0:
        return None
    return 100.0 * hits / (hits + falls)
