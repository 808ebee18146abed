"""The engine of a `--trace 1` run: the same `raftsql_tpu.server.main`
entry function with the same argv as `python -m raftsql_tpu.server.main`,
called in-process, plus one thread that brackets a few seconds with the
JAX profiler when the runner asks.  Only the process that holds the chip
can trace it, and the program has no hook for that yet.

    python benchmarks/lib/serve_traced.py <trace_dir> <seconds> <server argv...>

The cue is the file `<trace_dir>/start` appearing (the program owns its
signals).  When the trace is written the thread leaves
`<trace_dir>/done` holding the CLOCK_MONOTONIC seconds at which tracing
started and stopped.  The Python tracer is off: it would slow the host
path that the traced window is there to observe.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time

POLL_S = 0.2


def trace_on_cue(trace_dir: str, seconds: float) -> None:
    cue = os.path.join(trace_dir, "start")
    while not os.path.exists(cue):
        time.sleep(POLL_S)
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    t0 = time.monotonic()
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    time.sleep(seconds)
    jax.profiler.stop_trace()
    t1 = time.monotonic()
    tmp = os.path.join(trace_dir, "done.tmp")
    with open(tmp, "w") as f:
        json.dump({"t_start": t0, "t_stop": t1}, f)
    os.replace(tmp, os.path.join(trace_dir, "done"))


def main(argv) -> None:
    trace_dir, seconds, server_argv = argv[1], float(argv[2]), argv[3:]
    # As under `-m`: the script's own directory is not an import root.
    sys.path[:] = [p for p in sys.path
                   if os.path.abspath(p or ".") != os.path.dirname(
                       os.path.abspath(__file__))]
    threading.Thread(target=trace_on_cue, args=(trace_dir, seconds),
                     name="bench-trace", daemon=True).start()
    from raftsql_tpu.server.main import main as serve
    serve(server_argv)


if __name__ == "__main__":
    main(sys.argv)
