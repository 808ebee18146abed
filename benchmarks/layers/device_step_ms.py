"""Device time per tick: seconds in which an operation ran on the device
in the traced interval, over the ticks in that interval (its length over
the window's tick time).
"""
from layers import tick_ms


def read(before, after, client, trace):
    tick = tick_ms.read(before, after, client, trace)
    if not trace or not tick:
        return None
    ticks = trace["window_s"] * 1e3 / tick
    return trace["busy_s"] * 1e3 / ticks
