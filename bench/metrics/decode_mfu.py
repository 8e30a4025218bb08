"""Model FLOPs of the scheduled decode rows over the device time of the
decode programs at the chip's bf16 peak."""
from bench import costs
from bench.xplane import decode_module


def read(ctx):
    flops = secs = 0.0
    for it in ctx.iterations:
        m = decode_module(it)
        if m is None:
            continue
        flops += costs.decode_step_flops(ctx.dims, it.decode)
        secs += m.dur * 1e-9
    if secs <= 0:
        return None
    return 100.0 * flops / (secs * ctx.peaks["bf16_flops"])
