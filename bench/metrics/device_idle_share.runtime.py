"""Share of the traced window in which device 0 ran no program while the
host's innermost program span was one of the runtime's (``runtime.*``:
scheduling, iteration bookkeeping, arrivals).  Layer: runtime loop."""
from bench.idle import share


def read(ctx):
    return share(ctx, "runtime.")
