"""Peak device memory in use on the fullest chip, after the window.
"""
from lib import stats


def read(before, after, client, trace):
    peaks = [b for b in stats.dig(after["engine"], "device.peak_bytes_in_use")
             or [] if b is not None]
    return max(peaks) / 1e6 if peaks else None
