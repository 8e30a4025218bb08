"""Pallas TPU paged attention (block-table indirection, decode + extend).

The serving engine's KV lives in fixed-size pages (PagedAttention [9]); a
per-sequence block table maps logical positions to pages.  The pools of
all L layers of a stage are one stacked array ``(L, P, ps, KV*dh)``: a
token's KV heads sit side by side in the last dim, so the pool's two
minor dims are ``(ps, KV*dh)`` and its chip layout is row-major with no
lane padding at any head width.  The layer to read is a scalar-prefetched
index, so a caller scanning over layers hands the whole stack to every
layer's call and nothing copies one layer's pool out of it.

Grid (B, row-blocks, pages): each program takes one page of one sequence
of that layer, fetched by DMA through the scalar-prefetched block table,
and folds it into the online-softmax state of every KV head (VMEM
scratch carried across the page axis).  Head ``h`` reads its lanes of
the page block ``(1, ps, KV*dh)`` as whole 128-lane tiles: at dh >= 128
its own ``h*dh:(h+1)*dh``; a narrower head (dh = 64: two heads per tile)
reads the tile that holds it, with its query zero outside its own lanes
so the other head's lanes add nothing to the scores, and keeps only its
own lanes of the output.  A slice at a lane offset inside a tile would
shift lanes on every page.  Pages past a sequence's length are
predicated off and their index map repeats the last live page, so no DMA
is issued for them.

Queries are laid out head-major, ``(B, KV, S*G, D)`` with ``D`` the lane
width a head is read at: row ``r`` of a KV head's block is query
position ``start + r // G``.  Long extend chunks are split into row
blocks to bound VMEM.

One kernel serves both serving phases:

* **decode** — q is (B, H, dh): one query per sequence at its last
  position (``lengths - 1``), mask ``kv_pos < length`` (+ window), the
  exact semantics of ``models/layers.decode_attention``.
* **extend** — q is (B, S, H, dh) with per-sequence ``start``: queries sit
  at ``start + s``, mask ``kv_pos <= q_pos & kv_pos < length`` (+ window),
  the exact semantics of ``models/layers.extend_attention`` — chunked
  prefill continuations and speculative verify run through this path with
  zero KV copies (the pages are shared, the table is the view).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
NO_WINDOW = 1 << 30
#: most query rows (S*G) one program holds per KV head
MAX_ROWS = 512


def _row_block(R: int) -> int:
    if R <= MAX_ROWS:
        return R
    return next((b for b in range(MAX_ROWS, 7, -8) if R % b == 0), R)


def paged_attention_pallas(q, k_pages, v_pages, block_table, lengths, layer,
                           *, page_size: int, start=None, window=None,
                           interpret: bool = False):
    """q: (B,H,dh) decode or (B,S,H,dh) extend; k_pages/v_pages:
    (L,P,ps,KV*dh) stacked pools; block_table: (B,maxp) int32; lengths:
    (B,); ``layer``: scalar index into the pools' first dim.  ``start``:
    (B,) first query position (extend; decode infers ``lengths - 1``);
    ``window``: scalar sliding window."""
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]          # (B, 1, H, dh)
    B, S, H, dh = q.shape
    _, _, ps, F = k_pages.shape
    KV = F // dh
    if ps != page_size:
        raise ValueError(f"page pool has pages of {ps}, not {page_size}")
    G = H // KV
    R = S * G
    br = _row_block(R)
    maxp = block_table.shape[1]
    lengths = lengths.astype(jnp.int32)
    if start is None:
        if not squeeze:
            raise ValueError(
                "paged_attention: multi-query (extend) calls must pass "
                "start= (the first query position per sequence)")
        start = jnp.maximum(lengths - 1, 0)
    start = start.astype(jnp.int32)
    if window is None:
        window = NO_WINDOW
    win = jnp.reshape(jnp.asarray(window, jnp.int32), (1,))
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    qr = q.reshape(B, S, KV, G, dh).transpose(0, 2, 1, 3, 4) \
        .reshape(B, KV, R, dh)
    # a head narrower than a lane tile is read with the whole 128-lane
    # tile that holds it: its query sits at its lane offset, zeros around
    # (the output's other lanes are the tile's other head, dropped below)
    D = 128 if dh < 128 and 128 % dh == 0 and F % 128 == 0 else dh
    offs = [(h * dh) % D for h in range(KV)]
    if D != dh:
        qr = jnp.stack([jnp.pad(qr[:, h], ((0, 0), (0, 0),
                                           (o, D - dh - o)))
                        for h, o in enumerate(offs)], axis=1)

    def q_map(b, r, j, table, start_ref, len_ref, win_ref, layer_ref):
        return b, 0, r, 0

    def page_map(b, r, j, table, start_ref, len_ref, win_ref, layer_ref):
        n_used = jnp.clip((len_ref[b] + ps - 1) // ps, 1, maxp)
        return (layer_ref[0], table[b * maxp + jnp.minimum(j, n_used - 1)],
                0, 0)

    kernel = functools.partial(_paged_kernel, page_size=ps, G=G, br=br,
                               dh=dh)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B, R // br, maxp),
            in_specs=[pl.BlockSpec((1, KV, br, D), q_map),
                      pl.BlockSpec((None, 1, ps, F), page_map),
                      pl.BlockSpec((None, 1, ps, F), page_map)],
            out_specs=pl.BlockSpec((1, KV, br, D), q_map),
            scratch_shapes=[pltpu.VMEM((KV, br, 1), jnp.float32),
                            pltpu.VMEM((KV, br, 1), jnp.float32),
                            pltpu.VMEM((KV, br, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, KV, R, D), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_table.reshape(-1).astype(jnp.int32), start, lengths, win, layer,
      qr, k_pages, v_pages)
    if D != dh:
        out = jnp.stack([out[:, h, :, o:o + dh] for h, o in enumerate(offs)],
                        axis=1)
    out = out.reshape(B, KV, S, G, dh).transpose(0, 2, 1, 3, 4) \
        .reshape(B, S, H, dh)
    return out[:, 0] if squeeze else out


def _paged_kernel(table_ref, start_ref, len_ref, win_ref, layer_ref, q_ref,
                  k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *, page_size: int,
                  G: int, br: int, dh: int):
    """One (sequence, row block, page): every KV head's rows x one page."""
    del table_ref, layer_ref   # consumed by the index maps
    b, r, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    KV, D = q_ref.shape[1], q_ref.shape[3]
    length = len_ref[b]

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    @pl.when(j * page_size < length)
    def _step():
        rows = r * br + jax.lax.broadcasted_iota(jnp.int32, (br, page_size),
                                                 0)
        q_pos = start_ref[b] + rows // G
        kv_pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (br, page_size), 1)
        mask = (kv_pos <= q_pos) & (kv_pos < length) \
            & (q_pos - kv_pos < win_ref[0])
        for h in range(KV):
            q = q_ref[0, h].astype(jnp.float32) * dh ** -0.5
            lo = h * dh // D * D
            k = k_ref[0, :, lo:lo + D].astype(jnp.float32)
            v = v_ref[0, :, lo:lo + D].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_sc[h]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_sc[h] = l_sc[h] * corr + p.sum(axis=-1, keepdims=True)
            acc_sc[h] = acc_sc[h] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_sc[h] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-20)
                    ).astype(o_ref.dtype)
