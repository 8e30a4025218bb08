"""Pallas TPU grouped expert matmul (MegaBlocks-style, dense-padded groups).

Computes out[e] = x[e] @ w[e] for E experts with per-expert valid row counts
(``group_sizes``, scalar-prefetched into SMEM): rows past a group's size
produce zeros and their tiles are skipped via @pl.when (compute
proportional to actual load, which is what makes top-k MoE cheap).  Grid
(E, nC): one (expert, row-block) tile per program; d and f stay resident
in VMEM per expert.  The capacity axis is zero-padded to a whole number
of row blocks so every block meets the TPU's tiling rule.

The weights may be a stack ``(L, E, d, f)`` of L layers' experts with a
scalar-prefetched layer index: a caller scanning over layers hands the
whole stack to every layer's call, so each expert block is read from HBM
by the kernel's own DMA and nothing slices one layer's experts out first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(gs_ref, layer_ref, x_ref, w_ref, o_ref, *, bc: int):
    # x_ref: (1, bc, d); w_ref: (1, d, f); o_ref: (1, bc, f)
    del layer_ref   # consumed by the index map
    size = gs_ref[pl.program_id(0)]
    start = pl.program_id(1) * bc

    @pl.when(start < size)
    def _():
        x = x_ref[0].astype(jnp.float32)
        w = w_ref[0].astype(jnp.float32)
        out = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        rows = start + jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
        out = jnp.where(rows < size, out, 0.0)
        o_ref[0] = out.astype(o_ref.dtype)

    @pl.when(start >= size)
    def _():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)


def moe_gmm_pallas(x, w, group_sizes, layer=None, *, bc: int = 128,
                   interpret: bool = False):
    """x: (E,C,d); w: (E,d,f), or (L,E,d,f) stacked with ``layer`` the
    scalar index of the layer read; group_sizes: (E,) -> (E,C,f)."""
    if layer is None:
        w, layer = w[None], 0
    E, C, d = x.shape
    f = w.shape[-1]
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    # expert capacity is workload-derived and rarely a multiple of the
    # tile: use one block of C rounded up to the sublane multiple when it
    # is small, otherwise pad C to a whole number of bc-row blocks
    bc = min(bc, -(-C // 8) * 8)
    Cp = -(-C // bc) * bc
    if Cp != C:
        x = jnp.pad(x, ((0, 0), (0, Cp - C), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, bc=bc),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(E, Cp // bc),
            in_specs=[pl.BlockSpec((1, bc, d),
                                   lambda e, c, gs, li: (e, c, 0)),
                      pl.BlockSpec((None, 1, d, f),
                                   lambda e, c, gs, li: (li[0], e, 0, 0))],
            out_specs=pl.BlockSpec((1, bc, f),
                                   lambda e, c, gs, li: (e, c, 0))),
        out_shape=jax.ShapeDtypeStruct((E, Cp, f), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(group_sizes.astype(jnp.int32), layer, x, w)
    return out[:, :C]
