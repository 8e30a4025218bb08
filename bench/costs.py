"""Operations and bytes of the work a run scheduled, counted from shapes.

These are the yardstick of every MFU and roofline metric: the least work
the algorithm needs for what was scheduled, not what the program happened
to compute.  Rows of free slots, pad tails and recomputation count for
nothing, so waste lowers the share.  All matmul operands are bf16
(2 bytes); attention reads whole KV pages of ``PAGE`` tokens, as a paged
cache must.
"""
from __future__ import annotations

from bench.dims import Dims

BYTES = 2          # bf16
PAGE = 64          # tokens per KV page of the serving engine


def _pages(tokens: int) -> int:
    return -(-tokens // PAGE)


def kv_bytes(dims: Dims, tokens: int) -> int:
    """K and V of ``tokens`` positions of one layer, read in whole pages."""
    return 2 * _pages(tokens) * PAGE * dims.kv_heads * dims.d_head * BYTES


def attn_flops(dims: Dims, q_pos_start: int, n: int) -> int:
    """QK^T and PV of ``n`` queries at positions ``start..start+n-1`` over
    their causal context, one layer."""
    ctx = n * q_pos_start + n * (n + 1) // 2
    return 4 * dims.heads * dims.d_head * ctx


# ---- kernels (one call = one layer) ----

def paged_decode(dims: Dims, contexts) -> tuple:
    """Paged attention over decode rows with the given context lengths."""
    flops = sum(attn_flops(dims, c - 1, 1) for c in contexts)
    qo = 2 * len(contexts) * dims.heads * dims.d_head * BYTES
    return flops, qo + sum(kv_bytes(dims, c) for c in contexts)


def paged_extend(dims: Dims, start: int, n: int) -> tuple:
    qo = 2 * n * dims.heads * dims.d_head * BYTES
    return attn_flops(dims, start, n), qo + kv_bytes(dims, start + n)


def flash_prefill(dims: Dims, n: int) -> tuple:
    qo = 2 * n * dims.heads * dims.d_head * BYTES
    kv = 2 * n * dims.kv_heads * dims.d_head * BYTES
    return attn_flops(dims, 0, n), qo + kv


def moe_gmm(dims: Dims, tokens: int) -> tuple:
    """One grouped matmul (gate, up or down: the same cost) over the
    ``top_k * tokens`` routed rows; every expert's weights are read."""
    rows = dims.top_k * tokens
    w = dims.experts * dims.d * dims.d_ff * BYTES
    return 2 * rows * dims.d * dims.d_ff, \
        w + rows * (dims.d + dims.d_ff) * BYTES


# ---- whole steps ----

def decode_step_flops(dims: Dims, contexts) -> int:
    """Model FLOPs of one decode step over the scheduled rows: every
    matmul weight once per row, the head per row, attention at each row's
    context."""
    per_row = 2 * (dims.n_layers * dims.layer_matmul_params()
                   + dims.d * dims.vocab)
    attn = sum(attn_flops(dims, c - 1, 1) for c in contexts)
    return len(contexts) * per_row + dims.n_layers * attn


def prefill_chunk_flops(dims: Dims, start: int, n: int) -> int:
    """A prefill or extend call of ``n`` prompt tokens after ``start``
    cached ones; the head runs for the last token only."""
    return 2 * dims.n_layers * dims.layer_matmul_params() * n \
        + 2 * dims.d * dims.vocab \
        + dims.n_layers * attn_flops(dims, start, n)


def least_time(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(seconds, bound) of one call at the chip's peaks."""
    tc = flops / peaks["bf16_flops"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
