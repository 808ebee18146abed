"""Share of the traced interval in which no operation ran on the device
(averaged over the chips used): 1 - union of busy intervals / interval.
"""


def read(before, after, client, trace):
    if not trace or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
