"""Memory soak: sustained durable load with log compaction, RSS bounded.

VERDICT r4 task 8: storage/log.py grows without bound under parity
semantics (the reference's MemoryStorage, raft.go:129) — but the
framework HAS compaction; this soak proves the bounded-memory
configuration works at scale.  A FusedClusterNode runs saturated load
across G groups; every `--compact-every` ticks the runtime compacts to
(applied - keep); RSS is sampled each round and printed as a ledger.

Run (CPU or TPU; CPU shown):

    JAX_PLATFORMS=cpu python scripts/soak_memory.py \
        --groups 100000 --target-commits 10000000

Output: one line per round
  tick=N commits=M rss_mb=R plog_entries=K segments=S
and a final PASS/FAIL: RSS at end  <= --rss-budget-x times RSS after the
first round (steady state reached early), floors advanced, commits hit
the target.
"""
from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def rss_mb() -> float:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) / 1024.0
    return 0.0


def main() -> None:
    from raftsql_tpu.utils.device import select_device
    select_device()         # JAX_PLATFORMS, or an accelerator — never
    #                         a silent CPU (raftsql_tpu/utils/device.py)

    ap = argparse.ArgumentParser()
    ap.add_argument("--groups", type=int, default=100_000)
    ap.add_argument("--peers", type=int, default=3)
    ap.add_argument("--e", type=int, default=8)
    ap.add_argument("--target-commits", type=int, default=10_000_000)
    ap.add_argument("--compact-every", type=int, default=4)
    ap.add_argument("--keep", type=int, default=64)
    ap.add_argument("--rss-budget-x", type=float, default=1.5)
    args = ap.parse_args()

    from raftsql_tpu.config import RaftConfig
    from raftsql_tpu.runtime.fused import FusedClusterNode

    cfg = RaftConfig(num_groups=args.groups, num_peers=args.peers,
                     log_window=32, max_entries_per_msg=args.e,
                     tick_interval_s=0.0)
    tmp = tempfile.mkdtemp(prefix="soak-")
    node = FusedClusterNode(cfg, tmp)
    print(f"soak: G={args.groups} P={args.peers} E={args.e} "
          f"target={args.target_commits} commits, dir={tmp}", flush=True)

    for t in range(40 * cfg.election_ticks):
        node.tick()
        if t > cfg.election_ticks and (node._hints >= 0).all():
            break
    print(f"elected all groups at tick {node.metrics.ticks}", flush=True)

    from raftsql_tpu.runtime.db import _expand_commit_item

    def drain(q):
        # _expand_commit_item understands every live queue-item shape
        # (per-group RAW_PLAIN batches AND the whole-tick RAW_MANY item
        # the fused publish emits since the one-item-per-tick change) —
        # counting raw tuples undercounted a full tick's commits as 1.
        n = 0
        while True:
            try:
                item = q.get_nowait()
            except Exception:
                return n
            if isinstance(item, tuple):
                n += len(_expand_commit_item(item))

    committed = 0
    t0 = time.perf_counter()
    rss_first = None
    tick_no = 0
    payload = b"SET k soak-value-payload"
    while committed < args.target_commits:
        for g in range(args.groups):
            node.propose_many(
                g, [payload] * args.e)
        for _ in range(args.compact_every):
            node.tick()
            tick_no += 1
            for i, q in enumerate(node._commit_qs):
                n = drain(q)      # drain every peer; count peer 0 only
                if i == 0:
                    committed += n
        node.compact(keep=args.keep)
        ents = sum(node.plogs[0].length(g) - node.plogs[0].start(g)
                   for g in range(0, args.groups,
                                  max(args.groups // 1000, 1)))
        segs = sum(len(os.listdir(d)) for d in node.dirs)
        r = rss_mb()
        # Baseline RSS at the first round whose floor has advanced:
        # before that the per-group retained window is still filling.
        if rss_first is None and node.plogs[0].start(0) > 0:
            rss_first = r
        print(f"tick={tick_no} commits={committed} rss_mb={r:.0f} "
              f"plog_entries_sampled={ents} wal_files={segs} "
              f"rate={committed / (time.perf_counter() - t0):,.0f}/s",
              flush=True)
    dt = time.perf_counter() - t0
    r_end = rss_mb()
    floor0 = node.plogs[0].start(0)
    ok = (rss_first is not None
          and r_end <= args.rss_budget_x * rss_first and floor0 > 0
          and committed >= args.target_commits)
    print(f"{'PASS' if ok else 'FAIL'}: {committed} commits in {dt:.0f}s "
          f"({committed / dt:,.0f}/s), rss {rss_first:.0f} -> "
          f"{r_end:.0f} MB (budget {args.rss_budget_x}x), "
          f"g0 floor={floor0}", flush=True)
    node.stop()
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
