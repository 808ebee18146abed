"""Groups in which the publish workers found commits to deliver, per
dispatch of the window (`publish.groups` over `ticks`;
runtime/hostplane.py `_publish_shard` counts the groups whose commit
index passed the publishing peer's cursor, `_pub_run` hands the count
over with the worker's `publish` phase).  What the publish walk, the
apply run and the acks are each paid for once.  None where the program
keeps no such counter (before PR 34).
"""
from lib import stats


def read(before, after, client, trace):
    return stats.per(before["engine"], after["engine"],
                     "publish.groups", "ticks")
