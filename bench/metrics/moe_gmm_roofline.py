"""MoE grouped-matmul kernel (gate, up and down calls alike) inside the
decode programs: least time at the chip's peaks over its device time.
Bytes are every expert's weights per call, FLOPs the routed rows of the
scheduled tokens.  Decode only: at a decode batch of 48 rows every expert
is routed some row on all but a few calls ((1 - 8/40)**48 ~ 2e-5 per
expert), so the kernel reads every expert's weights; a short prompt routes
too few rows for that, the kernel skips the experts left empty, and
counting their bytes would put the share above what the chip can do."""
from bench import costs
from bench.families.transformer import Dims
from bench.xplane import decode_module, kernel_calls


def read(ctx):
    if not isinstance(ctx.dims, Dims):
        return None, f"no count of this kernel for {ctx.family.__name__}"
    if not ctx.dims.moe:
        return None
    least = secs = 0.0
    bounds = set()
    for it in ctx.iterations:
        m = decode_module(it)
        calls = kernel_calls(m, "moe_gmm") if m is not None else []
        if not calls:
            continue
        t, bound = costs.least_time(*costs.moe_gmm(ctx.dims, len(it.decode)),
                                    ctx.peaks)
        least += t * len(calls)
        secs += sum(c.dur for c in calls) * 1e-9
        bounds.add(bound)
    if secs <= 0:
        return None
    return 100.0 * least / secs, "bound: " + "+".join(sorted(bounds))
