"""etcd's put benchmark (`benchmark put`, etcd docs op-guide/performance,
write table) over this system's SQL surface.

  table   CREATE TABLE kv (k TEXT PRIMARY KEY, v TEXT) in group 0
  put     INSERT OR REPLACE INTO kv VALUES ('<key_bytes key>',
          '<value_bytes value>'), keys uniform over `keyspace`

Nothing is loaded: etcd's test starts from an empty store.  The same
interface as ops/ycsb.py (`p` = scale overlaid with the traffic file),
with one field per key.
"""
from __future__ import annotations

import base64
import random
from typing import Iterator, List, Optional, Tuple

from lib.reference import parse_one_row

FIELDS = 1
TABLE = "CREATE TABLE kv (k TEXT PRIMARY KEY, v TEXT)"


def key_name(p: dict, n: int) -> str:
    return f"{n:0{p['key_bytes']}x}"


def value(p: dict, rng: random.Random) -> str:
    n = p["value_bytes"]
    return base64.b64encode(rng.randbytes(n * 3 // 4 + 3), b"-_").decode()[:n]


def group_of(p: dict, key: str) -> int:
    return 0


def schema(p: dict) -> List[Tuple[int, str]]:
    return [(0, TABLE)]


def initial_rows(p: dict, seed: int) -> Iterator[Tuple[str, List[str]]]:
    return iter(())


def load(p: dict, seed: int) -> List[Tuple[int, str]]:
    return []


def read_sql(key: str) -> str:
    return f"SELECT * FROM kv WHERE k='{key}'"


def read_many_sql(keys: List[str]) -> str:
    return "SELECT * FROM kv WHERE k IN ('" + "','".join(keys) + "')"


def write_sql(key: str, field: int, val: str) -> str:
    return f"INSERT OR REPLACE INTO kv VALUES ('{key}','{val}')"


def client(p: dict, seed: int, cid: int
           ) -> Iterator[Tuple[str, str, int, Optional[str]]]:
    rng = random.Random(f"etcd-put-client:{seed}:{cid}")
    while True:
        yield ("w", key_name(p, rng.randrange(p["keyspace"])), 0,
               value(p, rng))


def parse_row(body: str) -> Optional[List[str]]:
    row = parse_one_row(body, FIELDS + 1)
    return None if row is None else row[1:]


def sample_keys(p: dict, seed: int, n: int) -> List[str]:
    rng = random.Random(f"etcd-put-sample:{seed}")
    return [key_name(p, i) for i in rng.sample(range(p["keyspace"]),
                                               min(n, p["keyspace"]))]
