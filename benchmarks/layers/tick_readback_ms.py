"""Host time a tick spends blocked on the device's packed info: the
`readback` phase (the second half of `dispatch`; runtime/hostplane.py
`tick`), `total_ms` difference per tick of the window.
"""
from lib import stages


def read(before, after, client, trace):
    return stages.phase_ms_per_tick(before, after, "readback")
