"""Window means of the program's cumulative stage pairs and counters
(`raftsql_tpu/obs/prof.py`: `{total_ms, n, max_ms}` per stage, plain
integers per counter, all on /metrics), for the layer readers.

A stage's mean over the window is the difference of `total_ms` between
the two scrapes over the difference of `n`.  An engine stage sits in the
document every scrape relays (`before["engine"]`); a worker's stages
(`worker_stages.*`) and read-plane counts are its own, so each of
run.py's scrape connections gives ONE worker's difference and the
differences are summed before the division, as read_shm_hit_pct does.
Every function returns None where a key is missing (a program without
these stages) or nothing was counted in the window.
"""
from __future__ import annotations

from typing import Iterable, Optional

from lib import stats


def engine_mean_ms(before: dict, after: dict, stage: str) -> Optional[float]:
    """Mean of engine stage `stages.<stage>` over the window."""
    path = "stages." + stage
    return stats.per(before["engine"], after["engine"],
                     path + ".total_ms", path + ".n")


def workers_sum(before: dict, after: dict, path: str) -> Optional[float]:
    """Sum over the scrape connections of counter `path`'s difference."""
    total = 0.0
    for b, a in zip(before["workers"], after["workers"]):
        d = stats.delta(b, a, path)
        if d is None:
            return None
        total += d
    return total


def worker_mean_ms(before: dict, after: dict, stage: str) -> Optional[float]:
    """Mean of worker stage `worker_stages.<stage>` over the window."""
    path = "worker_stages." + stage
    ms = workers_sum(before, after, path + ".total_ms")
    n = workers_sum(before, after, path + ".n")
    if ms is None or not n:
        return None
    return ms / n


def phase_ms_per_tick(before: dict, after: dict,
                      phase: str) -> Optional[float]:
    """A tick phase's time per tick of the window."""
    return stats.per(before["engine"], after["engine"],
                     f"phase_profile.{phase}.total_ms", "ticks")


def fallback_share_pct(before: dict, after: dict,
                       reasons: Iterable[str]) -> Optional[float]:
    """Share of the workers' shm fallbacks that gave one of `reasons`."""
    every = workers_sum(before, after, "reads.shm_fallbacks")
    some = 0.0
    for r in reasons:
        d = workers_sum(before, after, "reads.shm_fallback_reasons." + r)
        if d is None:
            return None
        some += d
    if not every:
        return None
    return 100.0 * some / every
