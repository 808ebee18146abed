"""Percentiles by the benchmark's rule, and counter deltas.

A percentile is reported only where at least TAIL samples lie beyond it,
so a tail is never one or two outliers read as a distribution: p95 needs
200 samples, the median 20.  `None` means "not enough samples"; the
caller leaves the metric out and says why.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

TAIL = 10


def percentile(samples: Sequence[float], q: float) -> Optional[float]:
    """The q-quantile (0 < q < 1) by nearest rank, or None where fewer
    than TAIL samples lie beyond that rank."""
    n = len(samples)
    if n == 0:
        return None
    rank = max(math.ceil(q * n), 1)         # 1-based nearest rank
    if n - rank < TAIL:
        return None
    return sorted(samples)[rank - 1]


def dig(doc: dict, path: str):
    """doc["a"]["b"] for path "a.b"; None where a key is missing."""
    for key in path.split("."):
        if not isinstance(doc, dict) or key not in doc:
            return None
        doc = doc[key]
    return doc


def delta(before: dict, after: dict, path: str) -> Optional[float]:
    """after[path] - before[path] for a cumulative counter; None where
    either scrape lacks it or the counter ran backwards (a restart)."""
    a, b = dig(after, path), dig(before, path)
    if not isinstance(a, (int, float)) or not isinstance(b, (int, float)):
        return None
    return a - b if a >= b else None


def per(before: dict, after: dict, num: str, den: str) -> Optional[float]:
    """Delta of counter `num` per delta of counter `den`, or None."""
    dn, dd = delta(before, after, num), delta(before, after, den)
    if dn is None or not dd:
        return None
    return dn / dd
