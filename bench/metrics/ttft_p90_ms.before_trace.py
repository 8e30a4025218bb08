"""90th percentile of the time to first token over the requests due in the
window before the trace began, censored at the close as ``ttft_p90_ms``
would be.  The tail of a light open loop turns on whether an arrival meets
another prompt's prefill, so it spreads too widely between runs to be
bounded end to end; here it is read, not judged.  Layer: scheduler."""
from bench import stats


def read(ctx):
    if not ctx.ttft_ms:
        return None
    return stats.percentile(ctx.ttft_ms, 90)
