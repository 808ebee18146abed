"""The two shm rings' waits and the two consumers' wake-ups: a worker's
round trip of a PUT (`worker_stages.put.ring_rtt`: record pushed to
completion popped) less the engine's residence (`stages.put.engine`),
both window means.
"""
from lib import stages


def read(before, after, client, trace):
    rtt = stages.worker_mean_ms(before, after, "put.ring_rtt")
    engine = stages.engine_mean_ms(before, after, "put.engine")
    if rtt is None or engine is None:
        return None
    return rtt - engine
