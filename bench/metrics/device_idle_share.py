"""Share of the traced window in which no program ran on the device: one
minus the union of the ``XLA Modules`` intervals, averaged over devices."""
from bench.xplane import union


def read(ctx):
    if not ctx.trace.devices:
        return None
    busy = [union(d.modules, ctx.lo, ctx.hi) for d in ctx.trace.devices]
    return 100.0 * (1.0 - sum(busy) / len(busy) / ctx.window_ns)
