"""Host time a tick spends staging inputs and launching the device step:
the `launch` phase (the first half of `dispatch`; runtime/hostplane.py
`tick`), `total_ms` difference per tick of the window.
"""
from lib import stages


def read(before, after, client, trace):
    return stages.phase_ms_per_tick(before, after, "launch")
