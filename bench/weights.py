"""Seeded random weights, made on the device from ``--seed``.

Every weight is ``scale * (2j + 1 - 256) / 256`` for a random byte ``j``
and a power-of-two ``scale``: a uniform draw whose values are exact in
bfloat16 and float32 alike.  So the served bf16 weights and the f32 copy
the reference makes for itself from the same seed are the same numbers,
however XLA fuses the arithmetic that makes them.  Scales follow the
fan-in (LeCun) rule rounded to a power of two; norm gains are
``1 + w`` with ``w`` within +-1/8.  The embedding is drawn at ``2**-5``
over the power of two nearest the configuration's
``embedding_multiplier``, so the embedding the layers see keeps that size
whatever the multiplier (with a large one and a tied head, random weights
would otherwise make every token's logit its own embedding's norm, and
the model would echo its input).

``served_params`` builds the whole model in the layout and type the
serving engine runs (one jitted call; layers stacked for its scan).
``layer_f32``/``top_f32`` rebuild one layer, or the embedding, final norm
and head, for the reference.

The engine's layer has no scalar multipliers and always keeps a head of
its own.  Where a configuration states them (Granite's
``embedding_multiplier``, ``attention_multiplier``,
``residual_multiplier``, ``logits_scaling``, ``tie_word_embeddings``), the
served weights carry them instead, folded in float32 before the cast: the
embedding times ``embedding_multiplier``; ``wq`` times
``attention_multiplier * sqrt(d_head)`` (the engine scales scores by
``1/sqrt(d_head)``); ``wo`` and ``w_down`` times ``residual_multiplier``;
the head the embedding's transpose where it is tied, over
``logits_scaling``.  So the engine computes the published equations; the
reference applies the scalars where the published model does, to the
unfolded weights.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.dims import Dims

NORM_SCALE = 2.0 ** -3
EMBED_SCALE = 2.0 ** -5


def base_key(seed: int):
    """A key for any whole-number seed, also one wider than 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def fan_in_scale(fan_in: int) -> float:
    return 2.0 ** round(math.log2(math.sqrt(3.0 / fan_in)))


def _uniform(key, shape, scale):
    j = jax.random.bits(key, shape, jnp.uint8).astype(jnp.float32)
    return (2.0 * j + 1.0 - 256.0) / 256.0 * scale


def _layer_shapes(dims: Dims):
    """(path, shape, scale) of one layer's tensors, in a fixed order."""
    d, q, kv = dims.d, dims.heads * dims.d_head, dims.kv_heads * dims.d_head
    out = [(("norm1",), (d,), NORM_SCALE),
           (("attn", "wq"), (d, q), fan_in_scale(d)),
           (("attn", "wk"), (d, kv), fan_in_scale(d)),
           (("attn", "wv"), (d, kv), fan_in_scale(d)),
           (("attn", "wo"), (q, d), fan_in_scale(q))]
    if dims.qk_norm:
        out += [(("attn", "q_norm"), (dims.d_head,), NORM_SCALE),
                (("attn", "k_norm"), (dims.d_head,), NORM_SCALE)]
    out.append((("norm2",), (d,), NORM_SCALE))
    f = dims.d_ff
    if dims.moe:
        E = dims.experts
        out += [(("moe", "router"), (d, E), fan_in_scale(d)),
                (("moe", "w_gate"), (E, d, f), fan_in_scale(d)),
                (("moe", "w_up"), (E, d, f), fan_in_scale(d)),
                (("moe", "w_down"), (E, f, d), fan_in_scale(f))]
    else:
        out += [(("mlp", "w_gate"), (d, f), fan_in_scale(d)),
                (("mlp", "w_up"), (d, f), fan_in_scale(d)),
                (("mlp", "w_down"), (f, d), fan_in_scale(f))]
    return out


def _folds(dims: Dims) -> dict:
    """Layer weight path -> the scalar the served copy is multiplied by."""
    if dims.qk_norm and dims.attention_multiplier is not None:
        raise ValueError("an attention multiplier cannot be folded into wq "
                         "ahead of a q norm")
    out = {("attn", "wo"): dims.residual_multiplier,
           ("moe" if dims.moe else "mlp", "w_down"): dims.residual_multiplier}
    if dims.attention_multiplier is not None:
        out[("attn", "wq")] = dims.attention_multiplier \
            * math.sqrt(dims.d_head)
    return {k: v for k, v in out.items() if v != 1.0}


def _layer(dims: Dims, key, layer, dtype, fold=False):
    lk = jax.random.fold_in(key, layer + 1)
    folds = _folds(dims) if fold else {}
    tree: dict = {}
    for i, (path, shape, scale) in enumerate(_layer_shapes(dims)):
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        w = _uniform(jax.random.fold_in(lk, i), shape, scale)
        node[path[-1]] = (w * folds.get(path, 1.0)).astype(dtype)
    return tree


def _top(dims: Dims, key, dtype, fold=False):
    tk = jax.random.fold_in(key, 0)
    V, d = dims.padded_vocab, dims.d

    def u(i, shape, scale):
        return _uniform(jax.random.fold_in(tk, i), shape, scale)
    embed = u(0, (V, d), EMBED_SCALE
              / 2.0 ** round(math.log2(dims.embedding_multiplier)))
    head = embed.T if dims.tied_head else u(2, (d, V), fan_in_scale(d))
    if fold:
        embed = embed * dims.embedding_multiplier
        head = head / dims.logits_scaling
    return {"embed": {"tok": embed.astype(dtype)},
            "final_norm": u(1, (d,), NORM_SCALE).astype(dtype),
            "head": {"w": head.astype(dtype)}}


@functools.partial(jax.jit, static_argnums=(0, 2))
def _served(dims: Dims, key, dtype):
    params = _top(dims, key, dtype, fold=True)
    params["stage0"] = jax.lax.map(
        lambda l: _layer(dims, key, l, dtype, fold=True),
        jnp.arange(dims.n_layers))
    return params


def served_params(dims: Dims, seed: int, dtype=jnp.bfloat16) -> dict:
    """The whole model, stacked for the engine's layer scan."""
    return _served(dims, base_key(seed), jnp.dtype(dtype))


@functools.partial(jax.jit, static_argnums=(0,))
def _layer_f32(dims: Dims, key, layer):
    return _layer(dims, key, layer, jnp.float32)


@functools.partial(jax.jit, static_argnums=(0,))
def _top_f32(dims: Dims, key):
    return _top(dims, key, jnp.float32)


def layer_f32(dims: Dims, seed: int, layer: int) -> dict:
    return _layer_f32(dims, base_key(seed), jnp.int32(layer))


def top_f32(dims: Dims, seed: int) -> dict:
    return _top_f32(dims, base_key(seed))
