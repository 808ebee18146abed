"""Groups with a batch in one drained run of the apply thread, window mean
(`apply.groups` over `apply.runs`; runtime/db.py `_apply_run`): how many
state machines a run can apply side by side.
"""
from lib import stats


def read(before, after, client, trace):
    return stats.per(before["engine"], after["engine"],
                     "apply.groups", "apply.runs")
