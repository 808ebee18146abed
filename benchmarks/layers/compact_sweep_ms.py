"""One compaction sweep, wall time, window mean (`stages.compact.sweep`,
runtime/hostplane.py `compact`: the floors of every peer from one pass
over [P, G] arrays, the payload logs cut, the markers and re-asserts
written and fsynced, the superseded segments unlinked).  It runs on the
tick thread inside a tick's dispatch window, so a write that waits for
that tick waits for the sweep too.  `None` where no sweep ran in the
window, or the program has no such stage.
"""
from lib import stages


def read(before, after, client, trace):
    return stages.engine_mean_ms(before, after, "compact.sweep")
