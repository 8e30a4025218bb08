"""Unified runtime event tracing: Perfetto timelines, per-request
waterfalls, and simulated-time series on both backends.

Enable by passing an :class:`EventRecorder` (or an output path) to
``repro.core.simulate(..., trace=...)``, ``Cluster(...,
recorder=...)``, or ``ServeDriver(..., recorder=...)``.  Disabled is
the default and costs nothing: the runtime's ``obs`` attributes stay
``None`` and every emission site is guarded.

:func:`span` marks host phases of the served path on the profiler's
clock once :func:`bind_profiler` has been called (``repro.obs.spans``).
"""
from repro.obs.attribution import SEGMENTS, attribution
from repro.obs.events import Event
from repro.obs.export import (chrome_trace, validate_chrome_trace,
                              write_chrome_trace)
from repro.obs.record import EventRecorder
from repro.obs.spans import bind_profiler, span

__all__ = ["Event", "EventRecorder", "attribution", "SEGMENTS",
           "chrome_trace", "write_chrome_trace", "validate_chrome_trace",
           "span", "bind_profiler"]
