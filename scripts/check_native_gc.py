"""Build-check the native WAL group-commit path (`make native-check`).

Compiles native/wal.cc (via the ordinary loader), then exercises the
group-commit plumbing end to end on the NATIVE backend, with the calls
a served engine makes (runtime/hostplane.py _durable_phases): per-peer
views of one shared WAL write biased RANGE records (append_ranges)
beside a PayloadLog, one fsync covers all peers, and replay splits per
peer.  Exits 0 on pass (or SKIP when
no toolchain), 1 on any mismatch — CI runs this next to `make native`
so a wal.cc change that breaks the bias ABI fails the build step, not
a downstream serving run.
"""
from __future__ import annotations

import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> int:
    from raftsql_tpu.native.build import load_native_wal
    from raftsql_tpu.storage.log import PayloadLog
    from raftsql_tpu.storage.wal import GroupCommitWAL

    if load_native_wal() is None:
        print("native-check: SKIP (no toolchain; Python backend covers "
              "this host)")
        return 0
    P, G = 3, 2
    with tempfile.TemporaryDirectory(prefix="native-gc-") as tmp:
        d = os.path.join(tmp, "gc")
        gw = GroupCommitWAL(d, num_peers=P, num_groups=G)
        if not gw.base.is_native:
            print("native-check: FAIL: shared WAL fell back to Python",
                  file=sys.stderr)
            return 1
        views = [gw.view(p) for p in range(P)]
        plogs = [PayloadLog(G) for _ in range(P)]
        for p, v in enumerate(views):
            datas = [f"p{p}e{i}".encode() for i in range(3)]
            v.append_ranges([0, 1], [1, 1], [2, 1], [1, 1], datas)
            plogs[p].put_ranges([(0, 1, datas[:2], [1, 1], None),
                                 (1, 1, datas[2:], [1], None)])
            v.set_hardstates([0, 1], [1, 1], [-1, -1], [2, 1])
        for v in views:
            v.sync()
        if gw.group_commits != 1:
            print(f"native-check: FAIL: {gw.group_commits} fsyncs for "
                  "one barrier round", file=sys.stderr)
            return 1
        for v in views:
            v.close()
        flat = GroupCommitWAL.replay_flat(d)
        for p in range(P):
            mine = GroupCommitWAL.split_replay(flat, p, G)
            want0 = [f"p{p}e0".encode(), f"p{p}e1".encode()]
            if [e[1] for e in mine[0].entries] != want0 \
                    or [e[1] for e in mine[1].entries] \
                    != [f"p{p}e2".encode()]:
                print(f"native-check: FAIL: peer {p} replay mismatch: "
                      f"{mine}", file=sys.stderr)
                return 1
            if plogs[p].try_slice(0, 1, 2) != want0:
                print(f"native-check: FAIL: peer {p} plog mismatch",
                      file=sys.stderr)
                return 1
    print("native-check: ok (group-commit bias path, 1 fsync / round, "
          "per-peer replay split)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
