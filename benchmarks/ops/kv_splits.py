"""CockroachDB's `kv` load generator (`cockroach workload run kv`, the
nightly `kv0` series at `--read-percent 0`) over this system's SQL
surface, with the table pre-split as `--splits` asks.

  table   CREATE TABLE kv (k BIGINT NOT NULL PRIMARY KEY, v BYTES NOT
          NULL), one in each of the `splits` + 1 ranges (a range is a
          raft group)
  write   UPSERT INTO kv (k, v) VALUES ($1, $2), `--batch 1`; in
          SQLite's spelling INSERT INTO kv (k, v) VALUES (<k>, x'<hex>')
          ON CONFLICT(k) DO UPDATE SET v = excluded.v
  keys    the default generator: uniform over the whole int64 space,
          never wrapping, so practically every write inserts a new row
  values  `min_block_bytes`..`max_block_bytes` random bytes
  ranges  range i holds [MinInt64 + i x stride, MinInt64 + (i+1) x
          stride), stride = 2^64 // (splits + 1); the last range runs
          to MaxInt64

Nothing is loaded (`--insert-count` 0): the store starts empty.  The
same interface as ops/ycsb.py and ops/etcd_put.py (`p` = scale overlaid
with the traffic file), with one field per key.  A key travels as its
decimal text, which is how the server renders the BIGINT, and a value
as its upper-case hex, which is how `hex(v)` renders the blob: the
read-back's rows are then text that compares with what was sent.
Everything is a pure function of (p, seed, client).
"""
from __future__ import annotations

import random
from typing import Iterator, List, Optional, Tuple

from lib.reference import parse_one_row

FIELDS = 1
TABLE = "CREATE TABLE kv (k BIGINT NOT NULL PRIMARY KEY, v BYTES NOT NULL)"
MIN_INT64 = -2**63


def groups(p: dict) -> int:
    return p["splits"] + 1


def stride(p: dict) -> int:
    return 2**64 // groups(p)


def group_of(p: dict, key: str) -> int:
    """The range that holds `key`; the keys above the last whole stride
    (2^64 is no multiple of the range count) belong to the last range."""
    return min((int(key) - MIN_INT64) // stride(p), p["splits"])


def schema(p: dict) -> List[Tuple[int, str]]:
    return [(g, TABLE) for g in range(groups(p))]


def initial_rows(p: dict, seed: int) -> Iterator[Tuple[str, List[str]]]:
    return iter(())


def load(p: dict, seed: int) -> List[Tuple[int, str]]:
    return []


def read_sql(key: str) -> str:
    return f"SELECT k, hex(v) FROM kv WHERE k={key}"


def read_many_sql(keys: List[str]) -> str:
    return "SELECT k, hex(v) FROM kv WHERE k IN (" + ",".join(keys) + ")"


def write_sql(key: str, field: int, val: str) -> str:
    return (f"INSERT INTO kv (k, v) VALUES ({key}, x'{val}') "
            "ON CONFLICT(k) DO UPDATE SET v = excluded.v")


def draw_key(rng: random.Random) -> str:
    return str(rng.getrandbits(64) + MIN_INT64)


def client(p: dict, seed: int, cid: int
           ) -> Iterator[Tuple[str, str, int, Optional[str]]]:
    """Client `cid`'s endless operation stream: ("w", key, 0, value)."""
    if p["batch"] != 1 or p["read_percent"] != 0:
        raise ValueError("only --batch 1 --read-percent 0 (kv0: one-row "
                         "writes and no reads) is generated")
    rng = random.Random(f"kv-splits-client:{seed}:{cid}")
    lo, hi = p["min_block_bytes"], p["max_block_bytes"]
    while True:
        yield ("w", draw_key(rng), 0,
               rng.randbytes(rng.randint(lo, hi)).hex().upper())


def parse_row(body: str) -> Optional[List[str]]:
    row = parse_one_row(body, FIELDS + 1)
    return None if row is None else row[1:]


def sample_keys(p: dict, seed: int, n: int) -> List[str]:
    """Keys of a stream no client draws from: rows that were not
    written, which must read back as no row."""
    rng = random.Random(f"kv-splits-sample:{seed}")
    return [draw_key(rng) for _ in range(n)]
