"""From a JAX profiler trace (`*.xplane.pb`) to the device's busy time, its
idle gaps and its longest operations.

    python benchmarks/lib/trace_reduce.py <trace_dir>         # JSON metrics
    python benchmarks/lib/trace_reduce.py <trace_dir> --dump  # look by hand
    python benchmarks/lib/trace_reduce.py <trace_dir> --events # extract()

Two steps, so that the arithmetic can be checked on a recorded trace
without JAX: `extract()` reads the file with `jax.profiler.ProfileData`
(run as a process of its own, after the engine has exited, pinned to the
CPU so that it can never take the chip) into plain lists;
`reduce_events()` is arithmetic on those lists.

What a TPU trace holds (looked at by hand, PR 24): one plane per chip,
`/device:TPU:<n>`, with a line `XLA Ops` (one event per executed HLO
operation, named by its whole HLO line, nested where an operation
contains others), a line `XLA Modules` (one event per executed program)
and `Steps`; host threads are lines of the plane `/host:CPU`.  All share one clock, in nanoseconds.
On the CPU backend (rehearsal) there is no device plane: XLA's operations
run on host threads and carry an `hlo_op` stat; they are taken as the
device's operations there.

  busy_s     per device plane, the union of its operation intervals
             inside the traced window; averaged over the device planes
  window_s   the traced window: first to last event of any plane
  device_ops the ten operation names with the largest summed duration
  idle_gaps  the ten names with the largest summed idle time, a gap
             being named after the host event that overlaps it longest
             (`host:untraced` where no host event does: the program's
             tick phases are not annotated yet)
"""
from __future__ import annotations

import glob
import json
import os
import sys
from typing import Dict, List, Tuple

Event = Tuple[str, float, float]            # name, start_ns, duration_ns
OP_LINES = ("XLA Ops", "XLA Modules")
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
               "collective-permute", "all_reduce", "all_gather",
               "collective_permute", "reduce_scatter", "all_to_all")
MIN_HOST_EVENT_NS = 50_000                  # shorter host events name no gap


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return found[-1]


def extract(xplane_path: str) -> dict:
    """{"devices": {plane: [Event...]}, "host": [Event...]} from the
    profiler's file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    cpu_ops: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            for want in OP_LINES:
                if want in lines:
                    devices[plane.name] = [
                        (op_name(e.name), float(e.start_ns),
                         float(e.duration_ns)) for e in lines[want].events]
                    break
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns <= 0:
                        continue
                    ev = (e.name, float(e.start_ns), float(e.duration_ns))
                    if any(k == "hlo_op" for k, _ in e.stats):
                        cpu_ops.append(ev)
                    elif e.duration_ns >= MIN_HOST_EVENT_NS:
                        host.append(ev)
    if not devices and cpu_ops:
        devices["/host:CPU (XLA:CPU operations)"] = cpu_ops
    return {"devices": devices, "host": host}


def op_name(text: str) -> str:
    """`select_reduce_fusion` from the trace's `%select_reduce_fusion =
    s32[3,10000,256]{...} fusion(...)`: a TPU trace names an operation by
    its whole HLO line."""
    return text.split(" = ", 1)[0].lstrip("%")[:120]


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Disjoint sorted intervals covering the same points."""
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def top(sums: Dict[str, float], n: int = 10) -> List[list]:
    return [[name, ns / 1e9] for name, ns in
            sorted(sums.items(), key=lambda kv: -kv[1])[:n]]


def reduce_events(ev: dict) -> dict:
    """The metrics of the module docstring from extract()'s output; {}
    where no operation ran on any device."""
    devices = {k: v for k, v in ev["devices"].items() if v}
    if not devices:
        return {}
    starts = [s for evs in devices.values() for _, s, _ in evs]
    ends = [s + d for evs in devices.values() for _, s, d in evs]
    starts += [s for _, s, _ in ev["host"]]
    ends += [s + d for _, s, d in ev["host"]]
    w0, w1 = min(starts), max(ends)
    host = sorted(ev["host"], key=lambda e: e[1])
    busy_ns = []
    op_sums: Dict[str, float] = {}
    gap_sums: Dict[str, float] = {}
    collective_ns = 0.0
    for evs in devices.values():
        busy = union([(s, s + d) for _, s, d in evs])
        busy_ns.append(sum(b - a for a, b in busy))
        for name, _, d in evs:
            op_sums[name] = op_sums.get(name, 0.0) + d
            if any(c in name for c in COLLECTIVES):
                collective_ns += d
        edges = [w0] + [x for ab in busy for x in ab] + [w1]
        gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
        for name, ns in name_gaps(host, gaps):
            gap_sums[name] = gap_sums.get(name, 0.0) + ns
    n = len(devices)
    return {
        "devices": n,
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy_ns) / n / 1e9,
        "collective_s": collective_ns / n / 1e9,
        "device_ops": top({k: v / n for k, v in op_sums.items()}),
        "idle_gaps": top({k: v / n for k, v in gap_sums.items()}),
    }


def name_gaps(host: List[Event], gaps: List[Tuple[float, float]]):
    """(name, length) per gap: the host event covering most of the gap,
    `host:untraced` if none does.  `host` sorted by start, `gaps` sorted
    and disjoint: one sweep, each host event dropped once it has ended
    before the gap at hand."""
    live: List[Event] = []
    i = 0
    for a, b in gaps:
        while i < len(host) and host[i][1] < b:
            live.append(host[i])
            i += 1
        live = [e for e in live if e[1] + e[2] > a]
        best, best_ns = "host:untraced", 0.0
        for name, s, d in live:
            over = min(b, s + d) - max(a, s)
            if over > best_ns:
                best, best_ns = "host:" + name, over
        yield best, b - a


def dump(xplane_path: str) -> None:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    print(xplane_path, os.path.getsize(xplane_path), "bytes")
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            sums: Dict[str, float] = {}
            for e in evs:
                sums[e.name] = sums.get(e.name, 0.0) + e.duration_ns
            print(f"  LINE {line.name!r}: {len(evs)} events")
            for name, ns in sorted(sums.items(), key=lambda kv: -kv[1])[:8]:
                print(f"      {ns / 1e6:12.3f} ms  {name[:100]}")


def main(argv: List[str]) -> int:
    path = argv[1]
    if os.path.isdir(path):
        path = find_xplane(path)
    if "--dump" in argv:
        dump(path)
    elif "--events" in argv:
        print(json.dumps(extract(path)))
    else:
        print(json.dumps(reduce_events(extract(path))))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
