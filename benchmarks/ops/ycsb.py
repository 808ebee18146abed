"""YCSB core workload over SQL, in the shapes of YCSB's JDBC binding.

  table   CREATE TABLE usertable (YCSB_KEY TEXT PRIMARY KEY, FIELD0 TEXT,
          ..., FIELD9 TEXT), one per table group
  load    INSERT INTO usertable VALUES (...), 10 fields x 100 bytes,
          `rows_per_insert` rows to a statement
  read    SELECT * FROM usertable WHERE YCSB_KEY='...'
  update  UPDATE usertable SET FIELD<i>='<100 bytes>' WHERE YCSB_KEY='...'
          (one random field: writeallfields=false)

Keys are `user<fnv64(n)>`; requests are drawn by the distribution the
traffic file names over the `recordcount` keys.  `usertable` is
hash-split client-side: a key lives in group
`group_stride * (crc32(key) % table_groups)`.

`p` is the cell's parameters: the configuration's `scale` overlaid with
the traffic file.  Everything is a pure function of (p, seed, client), so
two runs with one seed send the same statements.
"""
from __future__ import annotations

import base64
import random
import zlib
from typing import Iterator, List, Optional, Tuple

from lib import zipf
from lib.reference import parse_one_row

FIELDS = 10
FIELD_BYTES = 100
TABLE = ("CREATE TABLE usertable (YCSB_KEY TEXT PRIMARY KEY, "
         + ", ".join(f"FIELD{i} TEXT" for i in range(FIELDS)) + ")")
DISTRIBUTIONS = {"zipfian": zipf.ScrambledZipfian, "uniform": zipf.Uniform}


def value(rng: random.Random) -> str:
    """FIELD_BYTES characters of [A-Za-z0-9_-]: nothing SQL or the
    server's `|v|v|` row rendering would need escaped."""
    raw = rng.randbytes(FIELD_BYTES * 3 // 4)
    return base64.b64encode(raw, b"-_").decode()[:FIELD_BYTES]


def group_of(p: dict, key: str) -> int:
    return p["group_stride"] * (zlib.crc32(key.encode())
                                    % p["table_groups"])


def table_groups(p: dict) -> List[int]:
    return [p["group_stride"] * i for i in range(p["table_groups"])]


def schema(p: dict) -> List[Tuple[int, str]]:
    return [(g, TABLE) for g in table_groups(p)]


def initial_rows(p: dict, seed: int) -> Iterator[Tuple[str, List[str]]]:
    """(key, [FIELD0..FIELD9]) for every record, from the seed."""
    rng = random.Random(f"ycsb-load:{seed}")
    for n in range(p["recordcount"]):
        yield zipf.key_name(n), [value(rng) for _ in range(FIELDS)]


def load(p: dict, seed: int) -> List[Tuple[int, str]]:
    """The INSERT statements, `rows_per_insert` rows of one group each."""
    per_group: dict = {}
    for key, fields in initial_rows(p, seed):
        row = "('" + key + "','" + "','".join(fields) + "')"
        per_group.setdefault(group_of(p, key), []).append(row)
    n = p["rows_per_insert"]
    out = []
    for g in sorted(per_group):
        rows = per_group[g]
        for i in range(0, len(rows), n):
            out.append((g, "INSERT INTO usertable VALUES "
                        + ",".join(rows[i:i + n])))
    return out


def read_sql(key: str) -> str:
    return f"SELECT * FROM usertable WHERE YCSB_KEY='{key}'"


def read_many_sql(keys: List[str]) -> str:
    """The read-back's one statement for several keys of one group."""
    return ("SELECT * FROM usertable WHERE YCSB_KEY IN ('"
            + "','".join(keys) + "')")


def write_sql(key: str, field: int, val: str) -> str:
    return f"UPDATE usertable SET FIELD{field}='{val}' WHERE YCSB_KEY='{key}'"


def client(p: dict, seed: int, cid: int
           ) -> Iterator[Tuple[str, str, int, Optional[str]]]:
    """Client `cid`'s endless operation stream:
    ("r", key, -1, None) or ("w", key, field, value)."""
    rng = random.Random(f"ycsb-client:{seed}:{cid}")
    keys = DISTRIBUTIONS[p["distribution"]](p["recordcount"], rng)
    read_share = p["read_share"]
    while True:
        key = zipf.key_name(keys.next())
        if rng.random() < read_share:
            yield "r", key, -1, None
        else:
            yield "w", key, rng.randrange(FIELDS), value(rng)


def parse_row(body: str) -> Optional[List[str]]:
    """The fields (without the key) of the one row a read returned."""
    row = parse_one_row(body, FIELDS + 1)
    return None if row is None else row[1:]


def sample_keys(p: dict, seed: int, n: int) -> List[str]:
    """A seeded sample of keys for the read-back of rows that were not
    written in the window."""
    rng = random.Random(f"ycsb-sample:{seed}")
    return [zipf.key_name(i)
            for i in rng.sample(range(p["recordcount"]),
                                min(n, p["recordcount"]))]
