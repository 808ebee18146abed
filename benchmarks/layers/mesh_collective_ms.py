"""Device time per tick in the mesh step's collectives (`all-reduce`
operations: the `pmax` of the busy bit, the `pmin` of the timer margin),
mean over the device planes, over the ticks in the traced interval,
counted as device_step_ms counts them.

lib/trace_reduce.py's `collective_s` looks for a collective's name in an
operation's NAME, and the compiler names the instruction after the JAX
primitive it came from (`%pmax.6 = ... all-reduce(...)` reads `pmax.6`):
where `collective_s` is 0, the entries of the reducer's ten longest
operations (`device_ops`) that bear such a primitive's name are summed
instead, which is a lower bound.  A program on one device has none: 0.
"""
import re

from layers import tick_ms

PRIMITIVE = re.compile(r"^(psum|pmax|pmin|ppermute|pbroadcast|psum_scatter)"
                       r"(\.\d+)?$")


def read(before, after, client, trace):
    tick = tick_ms.read(before, after, client, trace)
    if not trace or not tick or "collective_s" not in trace:
        return None
    seconds = trace["collective_s"] or sum(
        s for name, s in trace.get("device_ops", ())
        if PRIMITIVE.match(name))
    ticks = trace["window_s"] * 1e3 / tick
    return seconds * 1e3 / ticks
