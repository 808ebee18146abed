"""Mean number of ticks an entry waits in the host plane's per-group queue,
by Little's law: entries queued at the moment of each tick's pop, summed
(`intake.backlog`), over entries the device accepted (`intake.accepted`)
(runtime/hostplane.py `_build_prop_n`, `_stage_ranges`).
"""
from lib import stats


def read(before, after, client, trace):
    return stats.per(before["engine"], after["engine"],
                     "intake.backlog", "intake.accepted")
