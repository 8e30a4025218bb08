"""Mixture-of-Experts FFN with top-k routing — sort-based dispatch.

TPU-idiomatic implementation: instead of the GShard (T, E, C) one-hot
dispatch einsum (whose dispatch tensor is quadratically large), tokens are
*sorted by expert id*, packed into per-expert capacity buffers, run through a
batched (E, C, d) einsum (the grouped GEMM that the Pallas kernel
``kernels/moe_gmm.py`` accelerates), and scattered back with combine weights.
Capacity overflow tokens are dropped (standard top-k MoE semantics); the
router is the model-side analogue of the simulator's ``core/expert.py``
ExpertRouter and can be swapped out the same way.

FLOPs: 3 · E · C · d · d_e per layer — matches the active-parameter roofline.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def router_topk(x, w_router, top_k: int):
    """Return (expert_idx (T,k) int32, combine_w (T,k) f32, aux_loss scalar)."""
    logits = (x @ w_router.astype(x.dtype)).astype(jnp.float32)  # (T, E)
    E = logits.shape[-1]
    probs = jax.nn.softmax(logits, axis=-1)
    combine_w, expert_idx = jax.lax.top_k(probs, top_k)
    combine_w = combine_w / jnp.maximum(
        combine_w.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balancing aux loss: E * sum_e f_e * p_e
    me = probs.mean(axis=0)                                   # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[expert_idx.reshape(-1)].add(
        jnp.ones(expert_idx.size, jnp.float32)) / expert_idx.size
    aux = E * jnp.sum(me * ce)
    return expert_idx.astype(jnp.int32), combine_w, aux


def moe_ffn(x, params, *, top_k: int, capacity_factor: float = 1.25,
            gated: bool = True, shard_experts: bool = False,
            router_fn=None, positions=None, layer=None, valid=None,
            backend: str = "reference", interpret: bool = True,
            expert_layer=None):
    """x: (T, d). params: router (d,E), w_gate/w_up (E,d,de), w_down (E,de,d).

    ``backend="pallas"`` swaps the three batched einsums for the fused
    grouped-GEMM kernel (``kernels.moe_gmm``) with per-expert group sizes
    from the dispatch counts — tiles past a group's size are skipped on
    real TPUs (compute proportional to routed load, not capacity).  With
    ``expert_layer`` (pallas only) the expert weights are a whole stage's
    stack (L,E,d,de) and the kernel reads index ``expert_layer`` of it.

    ``router_fn`` is the injectable routing hook (``repro.moe.hooks``):
    called as ``router_fn(logits, positions=(T,), layer=scalar,
    top_k=int, valid=(T,) bool or None)`` and returning ``(expert_idx
    (T,k) int32, combine_w (T,k), aux scalar)``.  It replaces only the
    *assignment* step — dispatch, capacity and combine run unchanged — so
    a replayed skew exercises the real grouped-GEMM path end-to-end.
    ``valid`` flags which rows are real workload tokens (pad tails and
    empty decode slots are False); recording taps mask on it, and dispatch
    sends invalid rows straight to the overflow slot so they never consume
    a real token's expert capacity (forced replay would otherwise route
    every empty decode slot to the same table row and let it evict real
    work from the capacity buffers).
    """
    T, d = x.shape
    E = params["router"].shape[-1]
    if router_fn is None:
        expert_idx, combine_w, aux = router_topk(x, params["router"], top_k)
    else:
        logits = (x @ params["router"].astype(x.dtype)).astype(jnp.float32)
        expert_idx, combine_w, aux = router_fn(
            logits, positions=positions, layer=layer, top_k=top_k,
            valid=valid)
        expert_idx = expert_idx.astype(jnp.int32)
    # the one capacity definition shared with the simulator's pricing and
    # the drop-rate metric (T is a static Python int under jit)
    from repro.core.expert import expert_capacity
    C = expert_capacity(T, top_k, E, capacity_factor)

    # --- dispatch: sort (token, k) pairs by expert --------------------------
    flat_e = expert_idx.reshape(-1)                    # (T*k,)
    if valid is None:
        sort_e = flat_e
    else:
        # invalid rows sort into a trash bucket past every real expert
        sort_e = jnp.where(jnp.repeat(valid, top_k), flat_e, E)
    order = jnp.argsort(sort_e)                        # stable
    tok_of = order // top_k                            # token index per entry
    e_sorted = flat_e[order]
    s_sorted = sort_e[order]
    # position within expert group = rank - group_start[expert]
    counts = jnp.zeros((E + 1,), jnp.int32).at[sort_e].add(1)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                              jnp.cumsum(counts)[:-1]])
    pos_in_e = jnp.arange(T * top_k, dtype=jnp.int32) - starts[s_sorted]
    keep = (pos_in_e < C) & (s_sorted < E)             # capacity drop
    dst_e = jnp.where(keep, e_sorted, 0)
    dst_c = jnp.where(keep, pos_in_e, C)               # C = overflow slot

    buf = jnp.zeros((E, C + 1, d), x.dtype)
    buf = buf.at[dst_e, dst_c].set(x[tok_of])          # (E, C+1, d)
    hidden_in = buf[:, :C]                             # (E, C, d)
    if shard_experts:
        # pin the expert buffers to the model axis so XLA routes tokens with
        # one all-to-all instead of resharding per einsum (Perf iteration 2;
        # GSPMD pads E when it does not divide the axis)
        from jax.sharding import PartitionSpec as P
        hidden_in = jax.lax.with_sharding_constraint(
            hidden_in, P("model", None, None))

    # --- grouped expert FFN -------------------------------------------------
    if backend == "pallas" and not shard_experts:
        from repro.kernels import moe_gmm
        # valid rows per expert buffer; rows >= size are zero either way
        # (silu(0)*0 == 0, gelu(0) == 0), the kernel just skips their tiles
        group_sizes = jnp.minimum(counts[:E], C)

        def gmm(h, name):
            return moe_gmm(h, params[name].astype(x.dtype), group_sizes,
                           expert_layer, interpret=interpret)
        if gated:
            h = jax.nn.silu(gmm(hidden_in, "w_gate")) \
                * gmm(hidden_in, "w_up")
        else:
            h = jax.nn.gelu(gmm(hidden_in, "w_up"))
        out_e = gmm(h, "w_down")
    elif gated:
        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", hidden_in,
                                   params["w_gate"].astype(x.dtype)))
        u = jnp.einsum("ecd,edf->ecf", hidden_in,
                       params["w_up"].astype(x.dtype))
        h = g * u
        out_e = jnp.einsum("ecf,efd->ecd", h,
                           params["w_down"].astype(x.dtype))
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", hidden_in,
                                   params["w_up"].astype(x.dtype)))
        out_e = jnp.einsum("ecf,efd->ecd", h,
                           params["w_down"].astype(x.dtype))
    if shard_experts:
        from jax.sharding import PartitionSpec as P
        out_e = jax.lax.with_sharding_constraint(
            out_e, P("model", None, None))

    # --- combine: gather back and weight ------------------------------------
    gathered = out_e[dst_e, jnp.minimum(dst_c, C - 1)]  # (T*k, d)
    w = (combine_w.reshape(-1)[order] * keep).astype(x.dtype)
    contrib = gathered * w[:, None]
    y = jnp.zeros((T, d), x.dtype).at[tok_of].add(contrib)
    return y, aux
