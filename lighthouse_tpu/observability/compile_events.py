"""Compile/cache-event accounting: where did this executable come from?

A stage dispatch has four very different cold-start stories — first
compile (minutes on this workload), persistent compile-cache hit
(seconds), AOT warm-bundle hit (sub-second deserialize), or a bundle
fallback (corrupt/stale → recompile) — and which one happened is
invisible at the call site. This module gives every provenance event one
spine: `record(event)` bumps `engine_compile_events_total{event}` and
drops a trace instant, and `install()` additionally subscribes to jax's
internal monitoring bus so the persistent-cache hits/misses and backend
compile durations report themselves without any call-site wiring.

`install()` is idempotent and failure-tolerant: `jax._src.monitoring` is
an internal API, so if it moves the hooks silently degrade to the
explicit `record()` calls from `serving/aot.py` and
`beacon_processor/warming.py`.
"""

from __future__ import annotations

import threading
from typing import Optional

from lighthouse_tpu.common import metrics as m
from lighthouse_tpu.observability import trace

# The event vocabulary (the docs and the benchmark's compile count key
# off these exact strings):
#   first_compile         jax persistent-cache miss -> full XLA compile
#   persistent_cache_hit  jax persistent-cache hit  -> deserialize only
#   warm_bundle_hit       serving/aot bundle loaded (no jax work at all)
#   warm_bundle_miss      no bundle for the shape -> jit path decides
#   bundle_corrupt        bundle failed verification -> fell back
#   bundle_stale          bundle version/env mismatch -> fell back
#   warm_compile_path     ShapeWarmer took the compile path for a shape

COMPILE_BUCKETS = (0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
                   120.0, 300.0, 600.0)

_installed = False
_install_lock = threading.Lock()

_JAX_EVENT_MAP = {
    "/jax/compilation_cache/cache_hits": "persistent_cache_hit",
    "/jax/compilation_cache/cache_misses": "first_compile",
}
_JAX_COMPILE_DURATION = "/jax/core/compile/backend_compile_duration"


def _events_total(registry: Optional[m.Registry] = None) -> m.LabeledCounter:
    return (registry or m.REGISTRY).counter_vec(
        "engine_compile_events_total",
        "Executable provenance events (first_compile|persistent_cache_hit"
        "|warm_bundle_hit|warm_bundle_miss|bundle_corrupt|bundle_stale"
        "|warm_compile_path)", "event")


def _compile_seconds(registry: Optional[m.Registry] = None) -> m.Histogram:
    return (registry or m.REGISTRY).histogram(
        "engine_backend_compile_seconds",
        "XLA backend_compile wall time per compiled computation",
        buckets=COMPILE_BUCKETS)


def record(event: str, **args) -> None:
    """Count one provenance event and mirror it into the trace."""
    _events_total().labels(event).inc()
    trace.instant(f"compile:{event}", cat="compile", **args)


def counts() -> dict:
    """Current per-event totals (zero-filled for the known vocabulary)."""
    c = _events_total()
    known = ("first_compile", "persistent_cache_hit", "warm_bundle_hit",
             "warm_bundle_miss", "bundle_corrupt", "bundle_stale",
             "warm_compile_path")
    return {e: c.get(e) for e in known}


def install() -> bool:
    """Subscribe to jax's monitoring bus (idempotent). Returns whether
    the internal hooks are live; False means only explicit record()
    calls feed the counters."""
    global _installed
    with _install_lock:
        if _installed:
            return True
        try:
            from jax._src import monitoring
        except Exception:
            return False

        def _on_event(event: str, **kw) -> None:
            mapped = _JAX_EVENT_MAP.get(event)
            if mapped is not None:
                record(mapped)

        def _on_duration(event: str, duration: float, **kw) -> None:
            if event == _JAX_COMPILE_DURATION:
                _compile_seconds().observe(duration)
                trace.instant("compile:backend_compile", cat="compile",
                              seconds=round(duration, 6))

        try:
            monitoring.register_event_listener(_on_event)
            monitoring.register_event_duration_secs_listener(_on_duration)
        except Exception:
            return False
        _installed = True
        return True
