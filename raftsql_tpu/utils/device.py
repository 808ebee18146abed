"""The one rule for which device a process computes on.

Every entry point that runs a consensus step (`server.main`, the
`bench.py` child and probe, `pod/dryrun.py`, `__graft_entry__.py`'s
`__main__`) calls `select_device()` before its first JAX computation,
and nothing else decides:

  * the platform is what `JAX_PLATFORMS` says — JAX itself fails at
    start-up when the named platform cannot be initialised;
  * when `JAX_PLATFORMS` is unset the process REQUIRES an accelerator:
    if JAX resolved to the CPU it exits non-zero with a sentence naming
    `JAX_PLATFORMS=cpu`, so a deployment can never end up on the CPU
    unnoticed.  CPU happens only when asked for by name (tests, CI, the
    Makefile, the chaos runners and the scripts all do).

The same call places the persistent compilation cache: where
`JAX_COMPILATION_CACHE_DIR` is set nothing is set in code; where it is
not, the cache is `<checkout>/.jax_cache` — a fixed path, because the
path is part of the cache key and a directory that moves never hits.
On an accelerator every compiled program is kept, whatever its compile
time.

`device_doc()` is what `/healthz` and `/metrics` publish (and what every
`bench.py` / `chip_smoke.py` result line carries), so anything outside
the process can tell a chip run from a CPU run.
"""
from __future__ import annotations

import logging
import os

log = logging.getLogger("raftsql.device")

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(REPO_ROOT, ".jax_cache")

NO_ACCELERATOR = (
    "raftsql: JAX found no accelerator and JAX_PLATFORMS is unset. The "
    "consensus step is written for a TPU and will not fall back on its "
    "own; set JAX_PLATFORMS=cpu to run on the CPU on purpose.")

# Persistent-cache traffic of THIS process, counted from JAX's own
# monitoring events (one listener, registered by select_device): a
# restart of the same shape must show hits here.
_cache_events = {"/jax/compilation_cache/cache_hits": 0,
                 "/jax/compilation_cache/cache_misses": 0}
_selected = False


def _count_cache_event(event: str, **_kw) -> None:
    if event in _cache_events:
        _cache_events[event] += 1


def select_device() -> dict:
    """Apply the device rule (module docstring), place the compile
    cache, log `platform / device_kind / count` once and return
    `device_doc()`.  Idempotent; exits the process (SystemExit with the
    NO_ACCELERATOR sentence) when unpinned and no accelerator exists."""
    global _selected
    import jax
    if _selected:
        return device_doc()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.monitoring.register_event_listener(_count_cache_event)
    platform = jax.devices()[0].platform
    if not os.environ.get("JAX_PLATFORMS") and platform == "cpu":
        raise SystemExit(NO_ACCELERATOR)
    if platform != "cpu":
        # On an accelerator keep every program, not only those that
        # took JAX's default second to compile: a start runs dozens of
        # small ones, and what one process compiled the next start of
        # the same shape must find again (chip_smoke.py's restart phase
        # checks it).  On the CPU the default stays: XLA:CPU's loader
        # logs two error-level lines per cache hit, enough to fill the
        # stdout pipe of a test that does not drain its server.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    _selected = True
    doc = device_doc()
    log.info("device: platform=%s device_kind=%s count=%d jax=%s "
             "compile_cache=%s", doc["platform"], doc["device_kind"],
             doc["count"], doc["jax"], doc["compile_cache"]["dir"])
    return doc


def device_doc() -> dict:
    """The device this process computes on, as JAX reports it, plus the
    JAX version, each device's peak memory in use (where the backend
    reports memory_stats; None on the CPU) and the compile cache's
    directory and hit/miss counts."""
    import jax
    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "count": len(devs),
        "jax": jax.__version__,
        "peak_bytes_in_use": [
            (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devs],
        "compile_cache": {
            "dir": jax.config.jax_compilation_cache_dir,
            "hits": _cache_events["/jax/compilation_cache/cache_hits"],
            "misses": _cache_events["/jax/compilation_cache/cache_misses"],
        },
    }
