"""A use that found its handle closed, wall time from the pin to the
file open again, window mean (`stages.sm.miss`, models/store.py `_pin`,
on the thread of the apply or read that missed: the release of a
victim where no slot is free, then the reopen).
`None` where nothing missed in the window, or the program has no such
stage.
"""
from lib import stages


def read(before, after, client, trace):
    return stages.engine_mean_ms(before, after, "sm.miss")
