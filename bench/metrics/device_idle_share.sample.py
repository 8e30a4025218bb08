"""Share of the traced window in which device 0 ran no program while the
host's innermost program span was ``backend.sample``: the argmax, its
readback and the per-row token bookkeeping.  Layer: backend."""
from bench.idle import share


def read(ctx):
    return share(ctx, ("backend.sample",))
