"""Seeded random weights, made on the device from ``--seed``: the draw every
family's weights follow (``bench/families``).

Every weight is ``scale * (2j + 1 - 256) / 256`` for a random byte ``j``
and a power-of-two ``scale``: a uniform draw whose values are exact in
bfloat16 and float32 alike.  So the served bf16 weights and the f32 copy
the reference makes for itself from the same seed are the same numbers,
however XLA fuses the arithmetic that makes them.  Scales follow the
fan-in (LeCun) rule rounded to a power of two; norm gains are
``1 + w`` with ``w`` within +-1/8.

A family lists a layer's tensors as ``(path, shape, scale)`` in a fixed
order; ``draw`` makes tensor ``i`` from ``fold_in(key, i)``, so a family
builds the served copy (in one jitted call, folds applied) and the
reference's float32 copy from the same key.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NORM_SCALE = 2.0 ** -3
EMBED_SCALE = 2.0 ** -5


def base_key(seed: int):
    """A key for any whole-number seed, also one wider than 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def fan_in_scale(fan_in: int) -> float:
    return 2.0 ** round(math.log2(math.sqrt(3.0 / fan_in)))


def uniform(key, shape, scale):
    j = jax.random.bits(key, shape, jnp.uint8).astype(jnp.float32)
    return (2.0 * j + 1.0 - 256.0) / 256.0 * scale


def draw(key, shapes, dtype, folds=None) -> dict:
    """The tree of ``shapes`` (``(path, shape, scale)`` in order), tensor
    ``i`` drawn from ``fold_in(key, i)`` and multiplied in float32 by
    ``folds[path]`` (default 1) before the cast to ``dtype``."""
    folds = folds or {}
    tree: dict = {}
    for i, (path, shape, scale) in enumerate(shapes):
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        w = uniform(jax.random.fold_in(key, i), shape, scale)
        node[path[-1]] = (w * folds.get(path, 1.0)).astype(dtype)
    return tree
