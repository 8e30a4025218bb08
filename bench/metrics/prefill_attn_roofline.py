"""Attention of prompt tokens: the flash kernel's calls in prefill programs
and the paged kernel's calls in extend programs.  Least time at the chip's
peaks for the real tokens over the kernels' device time."""
from bench import costs
from bench.xplane import kernel_calls, paired_chunks


def read(ctx):
    least = secs = 0.0
    bounds = set()
    for it in ctx.iterations:
        for m, start, n in paired_chunks(it):
            if start == 0:
                calls = kernel_calls(m, "flash_attention")
                cost = costs.flash_prefill(ctx.dims, n)
            else:
                calls = kernel_calls(m, "paged_attention")
                cost = costs.paged_extend(ctx.dims, start, n)
            if not calls:
                continue
            t, bound = costs.least_time(*cost, ctx.peaks)
            least += t * len(calls)
            secs += sum(c.dur for c in calls) * 1e-9
            bounds.add(bound)
    if secs <= 0:
        return None
    return 100.0 * least / secs, "bound: " + "+".join(sorted(bounds))
