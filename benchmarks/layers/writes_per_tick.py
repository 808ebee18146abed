"""Entries the device accepted and the host popped, per tick of the window
(`intake.accepted` over `ticks`; runtime/hostplane.py `_stage_ranges`).
"""
from lib import stats


def read(before, after, client, trace):
    return stats.per(before["engine"], after["engine"],
                     "intake.accepted", "ticks")
