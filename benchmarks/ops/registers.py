"""The checker for key/field stores (ops/ycsb.py, ops/etcd_put.py): every
(key, field) is a register, a write sets one register, a read returns
every register of one key.

`X-Raft-Session` on a 204 is the serving replica's applied watermark
COVERING the request, not the write's own index, so two writes of one
register that were in flight together have no order a client can know.
The check is therefore by intervals, from the clients' logs of
(sent, answered) on one machine's CLOCK_MONOTONIC:

  * a write W is SUPERSEDED by an acknowledged write W' of the same
    register when W' was sent after W was answered;
  * a read may return W only if W was sent before the read was answered,
    and, for a `linear` read, no W' superseding W was answered before the
    read was sent; for a `session` read the same with W' restricted to
    the reading client's own writes (read-your-writes);
  * a value no write (and not the load) ever put into that register is
    wrong in any mode;
  * after the window every acknowledged write has been answered, so a
    read-back may return only writes that no acknowledged write
    supersedes — or equal the plain reference (lib/reference.py) fed the
    load and then the acknowledged writes in the order they were
    answered;
  * a write that got no answer may or may not have happened: it is a
    possible value from the moment it was sent and supersedes nothing.

The load's value is a write sent and answered before everything else.
"""
from __future__ import annotations

import zlib
from typing import Dict, Iterable, List, Optional, Tuple

from lib.reference import Reference

NEVER = float("inf")
ABSENT = -1                 # the digest of "no row / no value"


def crc(v: Optional[str]) -> int:
    return ABSENT if v is None else zlib.crc32(v.encode())


class Write:
    __slots__ = ("sent", "answered", "digest", "cid", "acked")

    def __init__(self, sent, answered, digest, cid, acked):
        self.sent, self.answered = sent, answered
        self.digest, self.cid, self.acked = digest, cid, acked


def superseded(w: Write, writes: Iterable[Write], before: float,
               only_cid: Optional[int] = None) -> bool:
    """Whether an acknowledged write sent after `w` was answered had
    itself been answered by `before`."""
    return any(x.acked and x.sent > w.answered and x.answered < before
               and (only_cid is None or x.cid == only_cid)
               for x in writes)


def read_allowed(digest: int, writes: List[Write], sent: float,
                 answered: float, mode: str, cid: int) -> bool:
    for w in writes:
        if w.digest != digest or w.sent >= answered:
            continue
        if mode == "linear":
            if not superseded(w, writes, sent):
                return True
        elif mode == "session":
            if not superseded(w, writes, sent, only_cid=cid):
                return True
        else:                               # local/follower: any written value
            return True
    return False


def check(ops, p: dict, seed: int, setup: List[Tuple[int, str]],
          log: List[list], readback: Dict[str, Dict[str, str]],
          mode: str) -> dict:
    """`setup` is the acknowledged schema + load statements as (group,
    sql); `log` the clients' records [cid, kind, key, field, got, sent,
    answered, status, watermark]; `readback` {key: {read mode: body}} of
    the reads made after the last write was answered.  Returns
    {"correct", "reads_checked", "readback_rows", "mismatches": [...]}."""
    bad: List[str] = []
    touched = {r[2] for r in log} | set(readback)
    regs: Dict[Tuple[str, int], List[Write]] = {}
    for key, fields in ops.initial_rows(p, seed):
        if key in touched:
            for f, v in enumerate(fields):
                regs[(key, f)] = [Write(-NEVER, -NEVER, crc(v), -1, True)]
    for key in touched:
        for f in range(ops.FIELDS):
            regs.setdefault((key, f), [Write(-NEVER, -NEVER, ABSENT, -1,
                                             True)])
    writes = sorted((r for r in log if r[1] == "w"), key=lambda r: r[6])
    for cid, _, key, field, val, sent, answered, status, _wm in writes:
        acked = status == 204
        regs[(key, field)].append(Write(
            sent, answered if acked else NEVER, crc(val), cid, acked))

    reads_checked = 0
    for cid, kind, key, _f, got, sent, answered, status, _wm in log:
        if kind != "r" or status != 200:
            continue
        reads_checked += 1
        if isinstance(got, str):
            bad.append(f"read of {key}: {got}")
            continue
        digests = [ABSENT] * ops.FIELDS if got is None else got
        for f, d in enumerate(digests):
            if not read_allowed(d, regs[(key, f)], sent, answered, mode,
                                cid):
                seen = "never written there" if all(
                    w.digest != d for w in regs[(key, f)]) else \
                    "superseded before the read was sent"
                bad.append(f"{mode} read of {key} FIELD{f} by client "
                           f"{cid} at {sent:.3f}: value {seen}")

    ref = Reference()
    try:
        for group, sql in setup:
            ref.apply(group, sql)
        for _cid, _, key, field, val, _s, _a, status, _wm in writes:
            if status == 204:
                ref.apply(ops.group_of(p, key),
                          ops.write_sql(key, field, val))
        for key, bodies in readback.items():
            want = ref.query(ops.group_of(p, key), ops.read_sql(key))
            for how, body in bodies.items():
                if body == want:
                    continue
                try:
                    row = ops.parse_row(body)
                except ValueError as e:
                    bad.append(f"{how} read-back of {key}: {e}")
                    continue
                got = [None] * ops.FIELDS if row is None else row
                for f, v in enumerate(got):
                    ws = regs[(key, f)]
                    if not any(w.digest == crc(v)
                               and not superseded(w, ws, NEVER) for w in ws):
                        bad.append(
                            f"{how} read-back of {key} FIELD{f}: holds "
                            f"neither the reference's value nor an "
                            f"unsuperseded write ({len(ws) - 1} writes)")
    finally:
        ref.close()
    return {"correct": not bad, "reads_checked": reads_checked,
            "readback_rows": len(readback), "mismatches": bad[:20],
            "mismatched": len(bad)}
