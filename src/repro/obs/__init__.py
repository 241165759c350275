"""Observability: ring-buffer tracing, exporters, metrics, stats schema.

Usage (batch mining)::

    from repro.obs import Tracer, write_chrome_trace, summary_table
    tr = Tracer()
    supports, met = mine(bitmaps, min_support, trace=tr)
    write_chrome_trace(tr, "mine.trace.json")   # open in ui.perfetto.dev
    print(summary_table(tr, wall_s=met.wall_s))

Tracing is off by default: every instrumented site holds a tracer
reference that is ``None`` unless the caller passed one and opens
``region(tracer, name)``, so the disabled fast path is a single ``is
None`` test and a shared null context. Every region of a live tracer
is also a ``repro:<name>`` ``jax.profiler`` annotation, so under
``jax.profiler.trace`` the program's spans share the profiler's clock
with the device operations. See
``repro.obs.tracer`` for the ring-buffer design, ``repro.obs.schema``
for the unified merged-stats schema, ``repro.obs.registry`` for the
pull-based metrics snapshot API.
"""
from repro.obs.export import (  # noqa: F401
    check_nesting, chrome_trace, summary_table, time_in_state,
    write_chrome_trace,
)
from repro.obs.registry import LatencyRecorder, MetricsRegistry  # noqa: F401
from repro.obs.tracer import TraceEvent, Tracer, region  # noqa: F401
from repro.obs import schema  # noqa: F401

__all__ = [
    "Tracer", "TraceEvent", "region", "chrome_trace", "write_chrome_trace",
    "summary_table", "time_in_state", "check_nesting",
    "MetricsRegistry", "LatencyRecorder", "schema",
]
