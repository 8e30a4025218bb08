"""Decode rows the scheduler put in each decode iteration, over the
engine's ``max_batch``; mean over the traced window's decode iterations."""


def read(ctx):
    rows = [len(it.decode) for it in ctx.iterations if it.decode]
    if not rows:
        return None
    return 100.0 * sum(rows) / (len(rows) * ctx.max_batch)
