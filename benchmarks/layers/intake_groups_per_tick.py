"""Groups that had proposals queued at the tick's offer, per tick of the
window (`intake.groups` over `ticks`; runtime/hostplane.py
`_build_prop_n`): with `writes_per_tick`, how many entries a busy group
gets accepted a tick.
"""
from lib import stats


def read(before, after, client, trace):
    return stats.per(before["engine"], after["engine"],
                     "intake.groups", "ticks")
