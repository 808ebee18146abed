"""Putting the state machines on disk before a sweep, wall time, window
mean (`stages.compact.checkpoint`, runtime/db.py `_compact_round`: a
thread of its own, started by the apply thread every `--compact-every`
applied entries, checkpoints and fsyncs every SQLite file written since
the last round, one after another, and only then asks the tick thread
for the sweep, which may drop the raft log no further than that).  The
apply thread does not wait for it; an apply or a read of the ONE group
whose file is being checkpointed does.  `None` where no round ended in
the window, or the program has no such stage.
"""
from lib import stages


def read(before, after, client, trace):
    return stages.engine_mean_ms(before, after, "compact.checkpoint")
