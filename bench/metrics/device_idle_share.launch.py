"""Share of the traced window in which device 0 ran no program while the
host's innermost program span was ``backend.launch``: the Python dispatch
of one program (a step jit or a slot helper).  Layer: backend."""
from bench.idle import share


def read(ctx):
    return share(ctx, ("backend.launch",))
