"""The plain reference: one in-process `sqlite3` database per raft group,
fed the statements the server acknowledged, read with the statements the
clients send.  It shares no code with the program under test (nothing
here imports `raftsql_tpu`): the same operations on the same data must
give the same answers.
"""
from __future__ import annotations

import sqlite3
from typing import Dict, List, Optional


def render_rows(rows) -> str:
    """Rows as the server renders them: one `|v1|v2|` line each."""
    return "".join("|" + "|".join(str(v) for v in row) + "|\n"
                   for row in rows)


def parse_one_row(body: str, columns: int) -> Optional[List[str]]:
    """The cells of the ONE row a keyed read returned, None for no row.
    Raises ValueError on anything else (two rows, another width)."""
    lines = body.splitlines()
    if not lines:
        return None
    cells = lines[0].split("|")
    if len(lines) != 1 or len(cells) != columns + 2:
        raise ValueError(f"not one row of {columns} columns: {body[:80]!r}")
    return cells[1:-1]


class Reference:
    def __init__(self) -> None:
        self._dbs: Dict[int, sqlite3.Connection] = {}

    def _db(self, group: int) -> sqlite3.Connection:
        db = self._dbs.get(group)
        if db is None:
            db = self._dbs[group] = sqlite3.connect(":memory:")
        return db

    def apply(self, group: int, sql: str) -> None:
        self._db(group).execute(sql)

    def query(self, group: int, sql: str) -> str:
        return render_rows(self._db(group).execute(sql).fetchall())

    def close(self) -> None:
        for db in self._dbs.values():
            db.close()
        self._dbs.clear()
