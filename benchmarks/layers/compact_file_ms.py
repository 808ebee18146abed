"""One file of a compaction round put on disk, wall time, window mean
(`stages.compact.file`, models/store.py `checkpoint_round`, run by
runtime/db.py `_compact_round`: a `wal_checkpoint(FULL)`, the journal
synced, its pages copied, the file synced, as the native thread that
ran it timed it; an apply or a read of the group waits for its batch).
Several run side by side, so a round's `compact_checkpoint_ms` is not
this times the files.  `None`
where no file was put on disk in the window, or the program has no
such stage.
"""
from lib import stages


def read(before, after, client, trace):
    return stages.engine_mean_ms(before, after, "compact.file")
