"""Share of the traced window the host spent outside ``JaxBackend.execute``
(scheduler, router and runtime bookkeeping, waiting for arrivals), from the
harness's ``execute <k>`` spans.  Layer: runtime loop."""
from bench.xplane import union


def read(ctx):
    spans = [s for s in ctx.trace.host if s.name.startswith("execute ")]
    if not spans:
        return None
    return 100.0 * (1.0 - union(spans, ctx.lo, ctx.hi) / ctx.window_ns)
