"""Observability spine: span tracing, compile-event accounting, and the
shared probe-report schema (ROADMAP Open item 2's measurement layer).

The pieces:

  * `trace`          — the one span API: every span is a
                       `jax.profiler.TraceAnnotation` (on the device
                       trace's clock under a profiler session) and, when
                       enabled, an event in a Chrome trace-event buffer.
  * `compile_events` — executable-provenance counters (first compile vs
                       persistent-cache hit vs warm-bundle hit) plus
                       jax-internal monitoring hooks.
  * `report`         — the one probe-script JSON envelope.
  * `timeseries`     — in-process ring buffer of registry snapshots;
                       windowed delta/rate/quantile queries.
  * `slo`            — declarative objectives evaluated over those
                       windows (`slo_status{objective}`).

Everything degrades to no-ops rather than raising: instrumentation must
never be the thing that takes the batch path down.

Submodules import lazily (PEP 562): `ops.backend` and `serving.aot`
consult this package from inside builders, and an eager import of
`compile_events` (which imports `common.metrics`) from those seams would
cycle through `lighthouse_tpu` package init.
"""

_SUBMODULES = ("trace", "compile_events", "report", "timeseries", "slo")

__all__ = [
    "trace", "compile_events", "report", "timeseries", "slo",
    "Tracer", "TRACER", "span", "instant", "enable", "disable",
    "TimeSeries", "SloEngine", "Objective", "serving_objectives",
]

_EXPORTS = {
    "Tracer": ("trace", "Tracer"),
    "TRACER": ("trace", "TRACER"),
    "span": ("trace", "span"),
    "instant": ("trace", "instant"),
    "enable": ("trace", "enable"),
    "disable": ("trace", "disable"),
    "TimeSeries": ("timeseries", "TimeSeries"),
    "SloEngine": ("slo", "SloEngine"),
    "Objective": ("slo", "Objective"),
    "serving_objectives": ("slo", "serving_objectives"),
}


def __getattr__(name):
    import importlib

    if name in _SUBMODULES:
        return importlib.import_module(f".{name}", __name__)
    if name in _EXPORTS:
        mod, attr = _EXPORTS[name]
        return getattr(importlib.import_module(f".{mod}", __name__), attr)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
