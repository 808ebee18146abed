"""One victim released, wall time, window mean (`stages.sm.release`,
models/store.py `_take_slot`, on the thread of the apply or read that
needed its slot: the connection closed, which checkpoints the file as
`checkpoint` does, two syncs where the file was not on disk yet, and
a look for the `-wal` the close leaves where that did not run to its
end).  `None` where nothing was released in the window, or the
program has no such stage.
"""
from lib import stages


def read(before, after, client, trace):
    return stages.engine_mean_ms(before, after, "sm.release")
