"""A closed handle opened again, wall time, window mean
(`stages.sm.reopen`, models/store.py `_pin`, on the thread of the apply
or read that missed, after the victim's release: the connect, the
pragmas and, with `--resume`, the `_raft_meta` read, in one native call
or through the module).  The part of `sm_miss_ms` that is not
`sm_release_ms`.  `None` where nothing was reopened in the window, or
the program has no such stage.
"""
from lib import stages


def read(before, after, client, trace):
    return stages.engine_mean_ms(before, after, "sm.reopen")
