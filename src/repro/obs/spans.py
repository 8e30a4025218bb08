"""Host spans on the profiler's clock.

``span(name, **counters)`` marks a phase of the served path (page-table
pushes, program dispatch, sampling, the closing sync, the runtime's
scheduling and bookkeeping).  Until :func:`bind_profiler` is called it
returns one shared null context, so a simulator-only process pays one
``None`` check per span and never loads jax.  Once bound (``JaxBackend``
does it), a span is a ``jax.profiler.TraceAnnotation``: a TraceMe on the
host thread's line of the same trace, and on the same clock, as the
device's programs.  With no profiler session active a TraceMe records
nothing.  Counters ride as TraceMe metadata (shown by Perfetto and
TensorBoard).

Names are ``backend.<phase>`` or ``runtime.<phase>``; see
``docs/observability.md`` for what each covers.
"""
from __future__ import annotations

import contextlib
from typing import Callable, Optional

_NULL = contextlib.nullcontext()
_annotation: Optional[Callable] = None


def span(name: str, **counters):
    """A context manager that marks ``name`` on the profiler's host line
    (a shared null context while no profiler is bound)."""
    if _annotation is None:
        return _NULL
    return _annotation(name, **counters)


def bind_profiler() -> None:
    """Record spans as ``jax.profiler.TraceAnnotation`` from now on."""
    global _annotation
    from jax.profiler import TraceAnnotation
    _annotation = TraceAnnotation
