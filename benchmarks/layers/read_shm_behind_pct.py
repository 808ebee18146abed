"""Share of the workers' shm fallbacks for which the mapping was alive but
could not prove the read's freshness: applied behind the session
watermark or the commit index, no lease, a stale publisher heartbeat, or
the delta log short of the target (`reads.shm_fallback_reasons.*`,
runtime/ring.py `RingClient.query`).
"""
from lib import stages

REASONS = ("behind_watermark", "behind_commit", "no_lease",
           "stale_heartbeat", "catch_up")


def read(before, after, client, trace):
    return stages.fallback_share_pct(before, after, REASONS)
