"""How many ticks a write waits: the clients' median PUT latency of this
(traced) run over the window's tick time.  The pipeline's floor is 3.
"""
from layers import tick_ms


def read(before, after, client, trace):
    tick = tick_ms.read(before, after, client, trace)
    if not tick or client.get("write_p50_ms") is None:
        return None
    return client["write_p50_ms"] / tick
