"""Compile and run both Pallas quorum entry points once on the chip.

`commit_rule="pallas"` is off the default path but still accepted by
RaftConfig, so a user process can reach `ops/pallas_quorum.py`; this
check says whether the installed Mosaic compiles both entry points (the
plain one and the mask-weighted one) at the served shape — G=10,000,
P=3, W=256 — and whether they agree with `ops/quorum.py`.  A compile can
hang, so run it in a process of its own under a hard timeout:

    chiprun --timeout 600 -- timeout 300 python scripts/pallas_chip_check.py

Prints one JSON line per entry point (seconds to compile+run the first
call, seconds for a second call, equality with the reference) and exits
non-zero on any mismatch.  The device rule applies: without a TPU (or an
explicit JAX_PLATFORMS) it refuses to run.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

G, P, W = 10_000, 3, 256


def random_case(rng):
    """A leader's view of G groups: ring of entry terms, match indexes,
    commit below log_len (same construction as tests/test_ops.py)."""
    log_len = rng.integers(1, W, G).astype(np.int32)
    term = rng.integers(1, 6, G).astype(np.int32)
    commit = (log_len * rng.random(G) * 0.6).astype(np.int32)
    steps = (rng.random((G, W)) < 0.2).astype(np.int32)
    by_index = np.minimum(1 + np.cumsum(steps, axis=1), term[:, None])
    # Entry n lives in ring slot (n - 1) % W; with log_len < W no wrap.
    log_term = np.where(np.arange(W)[None, :] < log_len[:, None],
                        by_index, 0).astype(np.int32)
    match = np.minimum(rng.integers(0, W, (G, P)),
                       log_len[:, None]).astype(np.int32)
    is_leader = rng.random(G) < 0.7
    return match, log_term, log_len, commit, term, is_leader


def timed(fn, *args, **kw):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args, **kw))
    return np.asarray(out), first, time.perf_counter() - t0


def main() -> int:
    from raftsql_tpu.utils.device import select_device
    dev = select_device()
    import jax
    import jax.numpy as jnp

    from raftsql_tpu.ops import pallas_quorum as pq
    from raftsql_tpu.ops.quorum import (masked_quorum_commit_index,
                                        quorum_commit_index)

    rng = np.random.default_rng(0)
    case = tuple(jnp.asarray(x) for x in random_case(rng))
    voters = rng.random((G, P)) < 0.8
    voters[:, 0] = True
    joint = np.where(rng.random((G, 1)) < 0.3,
                     rng.random((G, P)) < 0.8, voters)
    joint[:, 1] = True
    masks = dict(voters=jnp.asarray(voters), voters_joint=jnp.asarray(joint))

    ok = True
    runs = (
        ("pallas_quorum_commit_index",
         jax.jit(lambda *a: pq.pallas_quorum_commit_index(
             *a, quorum=2, window=W)),
         lambda *a: quorum_commit_index(*a, quorum=2, window=W), {}),
        ("pallas_masked_quorum_commit_index",
         jax.jit(lambda *a, **m: pq.pallas_masked_quorum_commit_index(
             *a, window=W, **m)),
         lambda *a, **m: masked_quorum_commit_index(*a, window=W, **m),
         masks),
    )
    for name, kernel, reference, kw in runs:
        got, first_s, second_s = timed(kernel, *case, **kw)
        want = np.asarray(reference(*case, **kw))
        same = bool((got == want).all())
        ok = ok and same
        print(json.dumps({
            "entry_point": name, "platform": dev["platform"],
            "device_kind": dev["device_kind"], "devices": dev["count"],
            "interpret": pq._interpret(), "G": G, "P": P, "W": W,
            "first_call_s": round(first_s, 3),
            "second_call_s": round(second_s, 5),
            "matches_ops_quorum": same,
            "advanced": int((want > np.asarray(case[3])).sum())}),
            flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
