"""Consensus steps one launch of the device program carries, window mean
(`dispatch.steps` over `ticks`; runtime/hostplane.py `tick()`).  4.0 where
the served one-chip node runs the whole propose -> replicate -> commit ->
learn pipeline in a dispatch (runtime/fused.py `PIPELINE_STEPS`), 1.0 on
the mesh, whose sharded step carries one.  None where the program counts
no steps (before PR 33).
"""
from lib import stats


def read(before, after, client, trace):
    return stats.per(before["engine"], after["engine"],
                     "dispatch.steps", "ticks")
