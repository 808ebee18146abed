"""Share of the entries offered to the device (`intake.offered`: at most E a
group a step) that it accepted (`intake.accepted`); under 100 the
device's window, or a leader not yet confirmed, refuses work.
"""
from lib import stats


def read(before, after, client, trace):
    got = stats.per(before["engine"], after["engine"],
                    "intake.accepted", "intake.offered")
    return None if got is None else 100.0 * got
