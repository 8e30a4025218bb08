"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NO_WINDOW = 1 << 30


def flash_attention_ref(q, k, v, *, causal: bool = True, lengths=None,
                        window=None):
    """q: (B,S,H,dh); k/v: (B,S,KV,dh) -> (B,S,H,dh)."""
    B, S, H, dh = q.shape
    KV = k.shape[2]
    G = H // KV
    qr = q.reshape(B, S, KV, G, dh) * dh ** -0.5
    s = jnp.einsum("bqkgd,bjkd->bkgqj", qr.astype(jnp.float32),
                   k.astype(jnp.float32))
    q_pos = jnp.arange(S)[:, None]
    kv_pos = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool)
    if causal:
        mask = mask & (q_pos >= kv_pos)
    if window is not None:
        mask = mask & (q_pos - kv_pos < window)
    mask = jnp.broadcast_to(mask[None], (B, S, S))
    if lengths is not None:
        mask = mask & (kv_pos[None] < lengths[:, None, None])
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqj,bjkd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(B, S, H, dh).astype(q.dtype)


def paged_attention_ref(q, k_pages, v_pages, block_table, lengths, layer, *,
                        page_size: int, start=None, window=None):
    """q: (B,H,dh) decode or (B,S,H,dh) extend (with ``start``);
    k/v_pages: (L,P,ps,KV*dh) stacked pools, ``layer`` the one read;
    block_table: (B,maxp) int32; lengths: (B,)."""
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    B, S, H, dh = q.shape
    ps = k_pages.shape[2]
    KV = k_pages.shape[3] // dh
    G = H // KV
    maxp = block_table.shape[1]
    if start is None:
        start = jnp.maximum(lengths - 1, 0)
    pages = block_table.reshape(-1)
    kg = k_pages[layer, pages].reshape(B, maxp * ps, KV, dh)
    vg = v_pages[layer, pages].reshape(B, maxp * ps, KV, dh)
    qr = q.reshape(B, S, KV, G, dh).astype(jnp.float32) * dh ** -0.5
    s = jnp.einsum("bskgd,bjkd->bskgj", qr, kg.astype(jnp.float32))
    q_pos = start[:, None] + jnp.arange(S)[None, :]          # (B, S)
    kv_pos = jnp.arange(maxp * ps)
    win = NO_WINDOW if window is None else window
    mask = (kv_pos[None, None] <= q_pos[..., None]) \
        & (kv_pos[None, None] < lengths[:, None, None]) \
        & (q_pos[..., None] - kv_pos[None, None] < win)      # (B, S, J)
    s = jnp.where(mask[:, :, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bskgj,bjkd->bskgd", p, vg.astype(jnp.float32))
    o = o.reshape(B, S, H, dh).astype(q.dtype)
    return o[:, 0] if squeeze else o


def moe_gmm_ref(x, w, group_sizes, layer=None):
    """Grouped matmul: x: (E,C,d); w: (E,d,f), or (L,E,d,f) stacked with
    ``layer`` the one read; rows >= group_sizes[e] give 0."""
    if layer is not None:
        w = w[layer]
    E, C, d = x.shape
    out = jnp.einsum("ecd,edf->ecf", x.astype(jnp.float32),
                     w.astype(jnp.float32))
    mask = jnp.arange(C)[None, :] < group_sizes[:, None]
    return (out * mask[..., None]).astype(x.dtype)
