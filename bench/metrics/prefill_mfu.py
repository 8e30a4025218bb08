"""Model FLOPs of the prompt tokens prefilled (first chunk) or extended
(later chunks), as the cell's model family counts them, over the device
time of those programs at the bf16 peak."""
from bench.xplane import paired_chunks


def read(ctx):
    flops = secs = 0.0
    for it in ctx.iterations:
        for m, start, n in paired_chunks(it):
            flops += ctx.family.prefill_chunk_flops(ctx.dims, start, n)
            secs += m.dur * 1e-9
    if secs <= 0:
        return None
    return 100.0 * flops / (secs * ctx.peaks["bf16_flops"])
