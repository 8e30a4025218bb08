"""Tiny pytree-parameter module substrate (flax is not installed).

Params are nested dicts of jnp arrays. Initializers take an explicit PRNG
key; stacked (scanned) stages are initialized by mapping over a key batch so
every layer gets independent weights while the HLO stays a single scan body.
"""
from __future__ import annotations

import math
from typing import Sequence

import jax
import jax.numpy as jnp


def dense_init(key, d_in: int, d_out: int, dtype=jnp.float32):
    """Truncated-normal fan-in init (LeCun)."""
    std = 1.0 / math.sqrt(d_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, (d_in, d_out)) * std
            ).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.float32):
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)


def zeros(shape, dtype=jnp.float32):
    return jnp.zeros(shape, dtype)


def ones(shape, dtype=jnp.float32):
    return jnp.ones(shape, dtype)


def stack_init(key, n: int, init_fn):
    """Map an init function over ``n`` independent keys -> stacked params,
    one element at a time (under jit only one element's intermediates are
    live)."""
    keys = jax.random.split(key, n)
    return jax.lax.map(init_fn, keys)


def param_count(tree) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(tree))


def param_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))


def cast_tree(tree, dtype):
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        tree)


def tree_paths(tree, prefix=()) -> Sequence:
    """Yield (path_tuple, leaf) pairs for a nested-dict pytree."""
    out = []
    if isinstance(tree, dict):
        for k, v in tree.items():
            out.extend(tree_paths(v, prefix + (k,)))
    else:
        out.append((prefix, tree))
    return out
