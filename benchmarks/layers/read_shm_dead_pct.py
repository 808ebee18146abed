"""Share of the workers' shm fallbacks for which the mapping could serve
nothing at all: the log overflowed (`log_full`, out for good), no
consistent table (`no_snapshot`), no mapping, a broken one, or a stale
keymap epoch (`reads.shm_fallback_reasons.*`, runtime/ring.py
`RingClient.query`).
"""
from lib import stages

REASONS = ("log_full", "no_snapshot", "no_mapping", "broken", "keymap_epoch")


def read(before, after, client, trace):
    return stages.fallback_share_pct(before, after, REASONS)
