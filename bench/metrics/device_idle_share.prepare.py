"""Share of the traced window in which device 0 ran no program while the
host's innermost program span was ``backend.prepare`` (page allocation and
block-table pushes, token and length uploads) or ``backend.release`` (a
finished request's slot and pages freed).  Layer: backend."""
from bench.idle import share


def read(ctx):
    return share(ctx, ("backend.prepare", "backend.release"))
