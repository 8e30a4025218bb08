"""Pallas TPU flash attention (prefill, causal, GQA, lengths + window).

Grid (B, H, nQ, nKV): each program owns one (batch, head, query-block)
tile and one streamed KV block; the online-softmax state lives in VMEM
scratch across the KV axis ("arbitrary" semantics).  Q/K/V are laid out
head-major, (B, heads, S, dh), so every block's trailing dims are
(block, dh) and meet the TPU's (8, 128) tiling rule at real widths.

Per-sequence ``lengths`` and the sliding ``window`` are scalar-prefetched
into SMEM.  KV blocks past the causal diagonal or past the sequence's
length are skipped: their compute is predicated off and their index map
clamps to the last needed block, so no DMA is issued for them.  The
masking surface matches ``models/flash.flash_attention`` exactly, so the
pallas backend serves windowed layers too.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
#: "no window" sentinel: larger than any context length we ever serve
NO_WINDOW = 1 << 30


def _last_kv_block(qi, length, *, bq: int, bkv: int, causal: bool):
    """Index of the last KV block query block ``qi`` attends to (-1 for
    an empty sequence)."""
    last = (length - 1) // bkv
    if causal:
        last = jnp.minimum(last, (qi * bq + bq - 1) // bkv)
    return last


def _flash_kernel(len_ref, win_ref, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc,
                  acc_sc, *, bq: int, bkv: int, causal: bool):
    # q_ref/o_ref: (1, 1, bq, dh); k_ref/v_ref: (1, 1, bkv, dh)
    b, qi, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    length = len_ref[b]
    window = win_ref[0]

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    @pl.when(j <= _last_kv_block(qi, length, bq=bq, bkv=bkv, causal=causal))
    def _step():
        dh = q_ref.shape[-1]
        q = q_ref[0, 0].astype(jnp.float32) * dh ** -0.5
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        kv_pos = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = (kv_pos < length) & (q_pos - kv_pos < window)
        if causal:
            mask = mask & (q_pos >= kv_pos)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_sc[...] = acc_sc[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        o_ref[0, 0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-20)
                       ).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, lengths=None, window=None,
                           bq: int = 128, bkv: int = 128,
                           causal: bool = True, interpret: bool = False):
    """q: (B,S,H,dh); k/v: (B,S,KV,dh) -> (B,S,H,dh).

    ``lengths``: (B,) int32, KV positions >= length are masked (output rows
    at q_pos >= length are garbage, as in the pure-JAX twin).  ``window``:
    scalar (python int or traced), masks q_pos - kv_pos >= window.
    """
    B, S, H, dh = q.shape
    KV = k.shape[2]
    G = H // KV
    bq = min(bq, S)
    bkv = min(bkv, S)
    if S % bq or S % bkv:
        raise ValueError(f"flash_attention: S={S} is not a multiple of "
                         f"bq={bq} and bkv={bkv}")
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    lengths = lengths.astype(jnp.int32)
    if window is None:
        window = NO_WINDOW
    win = jnp.reshape(jnp.asarray(window, jnp.int32), (1,))

    def q_map(b, h, i, j, len_ref, win_ref):
        return b, h, i, 0

    def kv_map(b, h, i, j, len_ref, win_ref):
        last = _last_kv_block(i, len_ref[b], bq=bq, bkv=bkv, causal=causal)
        return b, h // G, jnp.clip(j, 0, jnp.maximum(last, 0)), 0

    kernel = functools.partial(_flash_kernel, bq=bq, bkv=bkv, causal=causal)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H, S // bq, S // bkv),
            in_specs=[pl.BlockSpec((1, 1, bq, dh), q_map),
                      pl.BlockSpec((1, 1, bkv, dh), kv_map),
                      pl.BlockSpec((1, 1, bkv, dh), kv_map)],
            out_specs=pl.BlockSpec((1, 1, bq, dh), q_map),
            scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, 1), jnp.float32),
                            pltpu.VMEM((bq, dh), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, S, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, win, q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
      v.transpose(0, 2, 1, 3))
    return out.transpose(0, 2, 1, 3)
