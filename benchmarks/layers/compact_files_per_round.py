"""Files a compaction round put on disk, window mean (Δ`compact.files` /
Δ`compact.rounds`; runtime/db.py `_compact_round`, one count() a round:
the groups with `applied > synced` when it began, less those a release
put on disk before their turn).  `None` where no round ended in the
window, or the program counts neither.
"""
from lib import stats


def read(before, after, client, trace):
    return stats.per(before["engine"], after["engine"], "compact.files",
                     "compact.rounds")
