"""Operations and bytes of the work a run scheduled, counted from shapes.

These are the yardstick of every roofline metric: the least work the
algorithm needs for what was scheduled, not what the program happened to
compute.  Rows of free slots, pad tails and recomputation count for
nothing, so waste lowers the share.  All matmul operands are bf16
(2 bytes); attention reads whole KV pages of ``PAGE`` tokens, as a paged
cache must.

The kernel counts are those of the transformer family's kernels (GQA
paged and flash attention, the grouped expert matmul) over its shapes,
``bench.families.transformer.Dims``.  A family's model FLOPs per step,
which the MFU metrics read, are its own (``bench/families``).
"""
from __future__ import annotations

BYTES = 2          # bf16
PAGE = 64          # tokens per KV page of the serving engine


def _pages(tokens: int) -> int:
    return -(-tokens // PAGE)


def kv_bytes(dims, tokens: int) -> int:
    """K and V of ``tokens`` positions of one layer, read in whole pages."""
    return 2 * _pages(tokens) * PAGE * dims.kv_heads * dims.d_head * BYTES


def attn_flops(dims, q_pos_start: int, n: int) -> int:
    """QK^T and PV of ``n`` queries at positions ``start..start+n-1`` over
    their causal context, one layer."""
    ctx = n * q_pos_start + n * (n + 1) // 2
    return 4 * dims.heads * dims.d_head * ctx


# ---- kernels (one call = one layer) ----

def paged_decode(dims, contexts) -> tuple:
    """Paged attention over decode rows with the given context lengths."""
    flops = sum(attn_flops(dims, c - 1, 1) for c in contexts)
    qo = 2 * len(contexts) * dims.heads * dims.d_head * BYTES
    return flops, qo + sum(kv_bytes(dims, c) for c in contexts)


def paged_extend(dims, start: int, n: int) -> tuple:
    qo = 2 * n * dims.heads * dims.d_head * BYTES
    return attn_flops(dims, start, n), qo + kv_bytes(dims, start + n)


def flash_prefill(dims, n: int) -> tuple:
    qo = 2 * n * dims.heads * dims.d_head * BYTES
    kv = 2 * n * dims.kv_heads * dims.d_head * BYTES
    return attn_flops(dims, 0, n), qo + kv


def moe_gmm(dims, tokens: int) -> tuple:
    """One grouped matmul (gate, up or down: the same cost) over the
    ``top_k * tokens`` routed rows; every expert's weights are read."""
    rows = dims.top_k * tokens
    w = dims.experts * dims.d * dims.d_ff * BYTES
    return 2 * rows * dims.d * dims.d_ff, \
        w + rows * (dims.d + dims.d_ff) * BYTES


def least_time(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(seconds, bound) of one call at the chip's peaks."""
    tc = flops / peaks["bf16_flops"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
