"""Seconds on the engine's own clock from the host plane's constructor to
the first tick at which every group had a leader (`boot.all_led_s`, a
gauge of runtime/hostplane.py's tick), read at the after-scrape: boot,
compile and election without the runner's polling.  `None` where the
program has no such gauge.
"""
from lib import stats


def read(before, after, client, trace):
    value = stats.dig(after["engine"], "boot.all_led_s")
    return value or None
