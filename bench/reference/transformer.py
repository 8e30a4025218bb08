"""Plain float32 reference of a pre-norm decoder: Qwen3 and Granite-MoE.

Written from the published architectures in straightforward ``jax.numpy``
with every matrix product at ``Precision.HIGHEST``; no kernels, no cache,
no batching tricks, and nothing imported from the program under test.

* attention: GQA with rotary embeddings (rotate-half, ``theta ** (-2i/dh)``),
  optional per-head RMSNorm of q and k before the rotation (Qwen3), causal
  softmax at ``attention_multiplier`` (default ``1/sqrt(d_head)``);
* feed-forward: SwiGLU, or a softmax router over all experts whose top-k
  probabilities are renormalised, each chosen expert a SwiGLU (Granite-MoE,
  dropless);
* RMSNorm gains are ``1 + w``; Granite's ``embedding_multiplier``,
  ``residual_multiplier`` and ``logits_scaling`` are applied where the
  published model applies them, and a tied head is the embedding's
  transpose (``tie_word_embeddings``).

The model is run layer by layer, each layer's weights made afresh from the
seed (``bench.weights``), so only one layer is ever held in float32.
``quantize="fp8"`` is the control: the same forward computed in fp8, as
an fp8 serving path would compute it: every weight matrix rounded to
float8_e4m3 with one scale per output column, and every input of a
product with a weight matrix rounded to float8_e4m3 with one scale per
row; products accumulate in float32, attention stays in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.dims import Dims

HI = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + gain)


def _rope(x, theta):
    """x: (S, L, heads, dh), positions 0..L-1."""
    L, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dh)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _fp8(w):
    """float8_e4m3 with one scale per output column (axis -2 reduced)."""
    if w.ndim < 2:
        return w
    s = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _fp8_rows(x):
    """float8_e4m3 with one scale per row (last axis reduced)."""
    s = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _act(x, quantize):
    """An input of a product with a weight matrix, in the control's
    precision."""
    return x if quantize is None else _fp8_rows(x)


def _maybe_quantize(tree, quantize):
    if quantize is None:
        return tree
    if quantize != "fp8":
        raise ValueError(f"unknown control precision {quantize!r}")
    return jax.tree_util.tree_map(_fp8, tree)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _block(dims: Dims, w, x, lengths, quantize):
    w = _maybe_quantize(w, quantize)
    S, L, _ = x.shape
    H, KV, dh = dims.heads, dims.kv_heads, dims.d_head
    a = w["attn"]
    h = _act(_rms(x, w["norm1"], dims.eps), quantize)
    q = _mm(h, a["wq"]).reshape(S, L, H, dh)
    k = _mm(h, a["wk"]).reshape(S, L, KV, dh)
    v = _mm(h, a["wv"]).reshape(S, L, KV, dh)
    if dims.qk_norm:
        q = _rms(q, a["q_norm"], dims.eps)
        k = _rms(k, a["k_norm"], dims.eps)
    q, k = _rope(q, dims.rope_theta), _rope(k, dims.rope_theta)
    qg = q.reshape(S, L, KV, H // KV, dh)
    s = jnp.einsum("sqkgd,sjkd->skgqj", qg, k, precision=HI) \
        * dims.attn_scale
    pos = jnp.arange(L)
    mask = (pos[None, :] <= pos[:, None])[None] \
        & (pos[None, None, :] < lengths[:, None, None])
    s = jnp.where(mask[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("skgqj,sjkd->sqkgd", p, v, precision=HI)
    x = x + _mm(_act(o.reshape(S, L, H * dh), quantize), a["wo"]) \
        * dims.residual_multiplier
    h = _act(_rms(x, w["norm2"], dims.eps), quantize)
    if dims.moe:
        m = w["moe"]
        probs = jax.nn.softmax(_mm(h, m["router"]), axis=-1)
        top_p, top_i = jax.lax.top_k(probs, dims.top_k)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        gate = jnp.sum(jax.nn.one_hot(top_i, dims.experts) * top_p[..., None],
                       axis=-2)                                  # (S, L, E)
        g = jnp.einsum("sld,edf->slef", h, m["w_gate"], precision=HI)
        u = jnp.einsum("sld,edf->slef", h, m["w_up"], precision=HI)
        act = _act(jax.nn.silu(g) * u, quantize) * gate[..., None]
        ff = jnp.einsum("slef,efd->sld", act, m["w_down"], precision=HI)
    else:
        m = w["mlp"]
        ff = _mm(_act(jax.nn.silu(_mm(h, m["w_gate"])) * _mm(h, m["w_up"]),
                      quantize), m["w_down"])
    return x + ff * dims.residual_multiplier


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(dims: Dims, table, tokens):
    return table[tokens] * dims.embedding_multiplier


@functools.partial(jax.jit, static_argnums=(0,))
def _final(dims: Dims, gain, x):
    return _rms(x, gain, dims.eps)


@functools.partial(jax.jit, static_argnums=(0,))
def _head_rows(dims: Dims, head, head_c, xr, xc, target):
    """Per row: reference best logit minus the logit of ``target`` and of
    the control's first choice."""
    lr = _mm(xr, head[:, :dims.vocab]) / dims.logits_scaling
    best = jnp.max(lr, axis=-1)
    at = jnp.take_along_axis(lr, target[:, None], axis=-1)[:, 0]
    lc = _mm(xc, head_c[:, :dims.vocab])
    pick = jnp.argmax(lc, axis=-1)
    at_c = jnp.take_along_axis(lr, pick[:, None], axis=-1)[:, 0]
    return best - at, best - at_c


def _forward(dims: Dims, seed: int, tokens, lengths, top, quantize):
    x = _embed(dims, _maybe_quantize(top["embed"]["tok"], quantize), tokens)
    for layer in range(dims.n_layers):
        x = _block(dims, weights.layer_f32(dims, seed, layer), x, lengths,
                   quantize)
    return _final(dims, top["final_norm"], x)


def logit_gaps(dims: Dims, seed: int, tokens: np.ndarray,
               lengths: np.ndarray, targets: np.ndarray,
               control: bool = False, rows: int = 256) -> dict:
    """Teacher-forced reference over ``tokens`` (S, L); at every position
    whose ``targets`` entry is >= 0, the gap between the reference's best
    logit and that of the target token.  With ``control`` also the gap of
    the token the fp8 control puts first at the same positions."""
    top = weights.top_f32(dims, seed)
    tok = jnp.asarray(tokens, jnp.int32)
    ln = jnp.asarray(lengths, jnp.int32)
    xr = _forward(dims, seed, tok, ln, top, None)
    xc = _forward(dims, seed, tok, ln, top, "fp8") if control else xr
    sel = np.nonzero(np.asarray(targets) >= 0)
    xr, xc = xr[sel], xc[sel]
    tgt = jnp.asarray(np.asarray(targets)[sel], jnp.int32)
    head = top["head"]["w"]
    head_c = _fp8(head) if control else head
    xc = _fp8_rows(xc) if control else xc
    n = int(tgt.shape[0])
    pad = -n % rows
    xr = jnp.pad(xr, ((0, pad), (0, 0)))
    xc = jnp.pad(xc, ((0, pad), (0, 0)))
    tgt = jnp.pad(tgt, (0, pad))
    gp, gc = [], []
    for i in range(0, n + pad, rows):
        a, b = _head_rows(dims, head, head_c, xr[i:i + rows], xc[i:i + rows],
                          tgt[i:i + rows])
        gp.append(np.asarray(a))
        gc.append(np.asarray(b))
    out = {"positions": n, "program": np.concatenate(gp)[:n]}
    if control:
        out["control"] = np.concatenate(gc)[:n]
    return out
