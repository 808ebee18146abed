"""The tick thread's proposal pop, per dispatch of the window: the offer
cut from the queues before the launch and the staging of what the device
accepted after the readback, the two samples a dispatch that
`phase_profile.pop` sums (runtime/hostplane.py `_build_prop_n` +
`_stage_ranges`; the span `tick.pop`).  Both walk the groups that have
proposals, one by one, so this is the first phase to grow with the
groups a dispatch carries.
"""
from lib import stages


def read(before, after, client, trace):
    return stages.phase_ms_per_tick(before, after, "pop")
