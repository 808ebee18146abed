"""Share of the window's apply runs whose groups were applied side by side
on the apply workers (`apply.fanout_runs` over `apply.runs`; runtime/db.py
`_apply_run`); a run of one group applies on the reader thread and counts
as not fanned out.
"""
from lib import stats


def read(before, after, client, trace):
    got = stats.per(before["engine"], after["engine"],
                    "apply.fanout_runs", "apply.runs")
    return None if got is None else 100.0 * got
