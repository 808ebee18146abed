"""Share of the accepted entries that the publishing peer saw committed
before the dispatch that accepted them ended
(`intake.committed_in_dispatch` over `intake.accepted`;
runtime/hostplane.py `_stage_ranges`: of the entries popped in a dispatch,
those at or under peer 0's commit index in the dispatch's last step).  Near
100 where a dispatch is as deep as the pipeline; 0 at one step a dispatch,
where a commit always takes later launches.  None where the program keeps
no such counter (before PR 33) or the window accepted nothing.
"""
from lib import stats


def read(before, after, client, trace):
    share = stats.per(before["engine"], after["engine"],
                      "intake.committed_in_dispatch", "intake.accepted")
    return None if share is None else 100.0 * share
