"""Observability subsystem: device-plane event rings, host-plane span
tracing, Chrome-trace (Perfetto) export, and the chaos flight recorder.

Two planes, matching the engine's own split:

  * DEVICE plane (`device_ring.py`): a tick-indexed on-device event
    ring — a fixed-shape [depth, P, G, NEV] i32 array the fused runtime
    writes one slot per tick (one tiny fused dispatch; no host
    round-trip), drained to the host in whole-ring batches so the
    steady-state cost is ~one device_get per `depth` ticks.
  * HOST plane (`spans.py`): a span tracer following each proposal
    through its lifecycle (propose → WAL append → replicate → quorum →
    commit → apply → ack) with monotonic timestamps, plus a generic
    timeline-event ring for WAL fsyncs, TCP frames, and anything else
    the host planes want on the trace.

Exports (`export.py`): Chrome trace-event JSON loadable in Perfetto
(`make trace`, `GET /trace`), raw event JSON (`GET /events`).  The
chaos harness wires both planes into a flight recorder (`flight.py`):
an invariant failure dumps the last N ticks of device events plus the
host spans next to the failing seed.

TRACING is OFF by default: the engine carries a `tracer`/`ring`
attribute that is None until `enable_tracing()` is called, and every
hook is gated on that attribute — the disabled cost is one attribute
test, and the fused scan signatures are untouched.

The production TELEMETRY plane is ON by default (it is cheap enough to
be): the tick-phase profiler (`prof.py` — per-phase p50/p95/p99 of
where the tick's wall time goes, overlap-aware, RAFTSQL_PROF=0 to
disable) and the per-group traffic accounting
(utils/metrics.py GroupTraffic — `[G]` propose/commit/ack counters +
EWMA rates feeding the /metrics top-K hot-groups table).  Both are
pure observers: chaos digests are pinned bit-identical with them on.
Cross-process trace SEGMENTS (`export.py TraceSegmentWriter`) let
`--workers N` HTTP worker processes land on the engine's /trace as one
merged multi-process Perfetto timeline.

The names below resolve on first use: an HTTP worker imports
`obs.prof` (its stage pairs) and `obs.export` (its trace segments)
without loading `device_ring`, and with it JAX.
"""
_HOME = {
    "EVENT_FIELDS": "device_ring", "DeviceEventRing": "device_ring",
    "TraceSegmentWriter": "export", "chrome_trace": "export",
    "collect_segments": "export", "validate_chrome_trace": "export",
    "FlightRecorder": "flight",
    "PROF_PHASES": "prof", "TickPhaseProfiler": "prof",
    "SpanTracer": "spans",
}

__all__ = sorted(_HOME)


def __getattr__(name: str):
    if name not in _HOME:
        raise AttributeError(f"module {__name__!r} has no attribute "
                             f"{name!r}")
    import importlib
    value = getattr(importlib.import_module(
        f"{__name__}.{_HOME[name]}"), name)
    globals()[name] = value
    return value
