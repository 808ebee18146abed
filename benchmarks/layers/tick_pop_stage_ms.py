"""Inside `pop`: the staging alone, per dispatch of the window
(`phase_profile.pop_stage`, runtime/hostplane.py `_stage_ranges`: the
accepted payloads popped off the queues, group by group, into the
durable phase's write plans).  `tick_pop_ms` less this is the offer
(`_build_prop_n`).  None where the program keeps no such phase (before
PR 34).
"""
from lib import stages


def read(before, after, client, trace):
    return stages.phase_ms_per_tick(before, after, "pop_stage")
