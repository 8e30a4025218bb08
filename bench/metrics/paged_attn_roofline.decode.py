"""Paged-attention kernel calls inside the decode programs: least time at
the chip's peaks for the scheduled rows (their pages read once, their
FLOPs), over the kernel's device time.  Memory bound at serving contexts."""
from bench import costs
from bench.families.transformer import Dims
from bench.xplane import decode_module, kernel_calls


def read(ctx):
    if not isinstance(ctx.dims, Dims):
        return None, f"no count of this kernel for {ctx.family.__name__}"
    least = secs = 0.0
    bounds = set()
    for it in ctx.iterations:
        m = decode_module(it)
        calls = kernel_calls(m, "paged_attention") if m is not None else []
        if not calls:
            continue
        t, bound = costs.least_time(*costs.paged_decode(ctx.dims, it.decode),
                                    ctx.peaks)
        least += t * len(calls)
        secs += sum(c.dur for c in calls) * 1e-9
        bounds.add(bound)
    if secs <= 0:
        return None
    return 100.0 * least / secs, "bound: " + "+".join(sorted(bounds))
