"""Profile the fused durable tick's host phases (cProfile over ~N ticks).

Usage: JAX_PLATFORMS=cpu python scripts/profile_fused.py [G] [E] [TICKS]
Prints the cumulative top of the profile plus the runtime's own
phase_ms_per_tick, so the t_wal/t_publish split can be attributed to
individual callees (WAL C call vs payload log vs numpy marshalling vs
queue traffic).
"""
import cProfile
import pstats
import sys
import tempfile

from raftsql_tpu.config import RaftConfig
from raftsql_tpu.runtime.fused import FusedClusterNode
from raftsql_tpu.utils.device import select_device


def main() -> None:
    select_device()
    G = int(sys.argv[1]) if len(sys.argv) > 1 else 10000
    E = int(sys.argv[2]) if len(sys.argv) > 2 else 32
    ticks = int(sys.argv[3]) if len(sys.argv) > 3 else 8
    cfg = RaftConfig(num_groups=G, num_peers=3, log_window=max(64, 4 * E),
                     max_entries_per_msg=E, tick_interval_s=0.0)
    tmp = tempfile.mkdtemp(prefix="prof-fused-")
    node = FusedClusterNode(cfg, tmp)
    for t in range(40 * cfg.election_ticks):
        node.tick()
        if t > cfg.election_ticks and (node._hints >= 0).all():
            break
    print(f"elected {int((node._hints >= 0).sum())}/{G}")

    def drain(apply: bool) -> int:
        import queue as _q
        n = 0
        q = node.commit_q(0)
        while True:
            try:
                item = q.get_nowait()
            except _q.Empty:
                break
            if isinstance(item, tuple):
                from raftsql_tpu.runtime.db import iter_plain_batches
                for _g, _b, datas in iter_plain_batches(item):
                    n += len(datas)
            # drop: profiling the producer side only
        return n

    cmds = [f"SET k{i} v".encode() for i in range(ticks * E)]
    for g in range(G):
        node.propose_many(g, cmds)
    drain(False)
    m = node.metrics
    m.ticks = 0
    m.t_device_ms = m.t_wal_ms = m.t_publish_ms = 0.0

    prof = cProfile.Profile()
    prof.enable()
    for _ in range(ticks):
        node.tick()
        drain(False)
    prof.disable()
    snap = node.metrics.snapshot()["phase_ms_per_tick"]
    print("phase_ms_per_tick:", {k: round(v, 2) for k, v in snap.items()
                                 if v})
    st = pstats.Stats(prof)
    st.sort_stats("cumulative")
    st.print_stats(28)
    node.stop()


if __name__ == "__main__":
    main()
