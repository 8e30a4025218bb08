"""Sharding rules: params, caches, and batch inputs -> PartitionSpec trees.

TP on the ``model`` axis (attention heads / FFN hidden / experts / vocab),
DP on ``data`` (+``pod``); long-context (batch < dp) decode shards the KV
cache sequence dim instead (sequence parallelism). GSPMD handles the
not-evenly-divisible cases (e.g. 36 heads on 16 shards) by padding — the
roofline table records where that costs us (§Perf).
"""
from __future__ import annotations

from typing import Any, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

MODEL = "model"

# leaf name -> which *trailing* dim gets the model axis (negative index),
# None = replicate.  Context key "moe" overrides for expert-stacked weights.
_COL = {"wq", "wk", "wv", "wqkv", "bq", "bk", "bv", "w_gate", "w_up", "w_in",
        "w_zx", "w_dt", "w_q", "w_k", "w_v", "w_gates"}
_ROW = {"wo", "w_down", "w_out"}
_REPL = {"norm1", "norm2", "norm", "final_norm", "q_norm", "k_norm",
         "norm_scale", "norm_in", "norm_h", "conv_w", "conv_b", "A_log",
         "D", "dt_bias", "w_bc", "router", "r_gates", "b_gates", "f_bias",
         "w_i", "w_f", "lengths"}


def fit_to_mesh(spec_tree, shape_tree, mesh):
    """Replace any sharded dim that does not divide evenly by None.

    pjit requires *boundary* (input/output) shardings to divide exactly;
    GSPMD only pads intermediates. This post-pass keeps the rules simple and
    makes every uneven case (e.g. 40 experts on 16 shards) explicit:
    the leaf is replicated and the roofline table shows the cost.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def ax_size(entry) -> int:
        if entry is None:
            return 1
        if isinstance(entry, (tuple, list)):
            n = 1
            for e in entry:
                n *= sizes[e]
            return n
        return sizes[entry]

    def fix(spec, leaf):
        dims = tuple(spec) + (None,) * (leaf.ndim - len(tuple(spec)))
        out = []
        for d, entry in zip(leaf.shape, dims):
            out.append(entry if d % ax_size(entry) == 0 else None)
        return P(*out)

    return jax.tree_util.tree_map(
        fix, spec_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, P))


def _param_spec(path: Tuple[str, ...], leaf, model_size: int = 16) -> P:
    name = path[-1]
    rank = np.ndim(leaf) if not hasattr(leaf, "ndim") else leaf.ndim
    in_moe = "moe" in path
    if path[-2:] == ("embed", "tok") or (len(path) >= 2 and path[-2] == "embed"):
        return P(MODEL, None)
    if "head" in path:
        return _trailing(rank, -1)
    if in_moe and name in ("w_gate", "w_up", "w_down"):
        # experts stacked at dim -3: expert parallelism when E divides the
        # TP axis; otherwise fall back to TP inside each expert.
        E = leaf.shape[-3]
        if E % model_size == 0:
            return _trailing(rank, -3)
        return _trailing(rank, -1 if name in ("w_gate", "w_up") else -2)
    if name in _REPL:
        return P(*([None] * rank))
    if name in _COL:
        return _trailing(rank, -1)
    if name in _ROW:
        return _trailing(rank, -2)
    return P(*([None] * rank))


def _trailing(rank: int, dim: int) -> P:
    spec = [None] * rank
    spec[dim] = MODEL
    return P(*spec)


def param_pspecs(params_shape: Any, model_size: int = 16):
    """Map a params (or opt-state) shape tree to PartitionSpecs.

    ``model_size`` is the model-axis extent divisibility heuristics use
    (16 for the production mesh; the serving engine passes its tp degree).
    """
    def walk(tree, path=()):
        if isinstance(tree, dict):
            return {k: walk(v, path + (k,)) for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            vals = [walk(v, path + (str(i),)) for i, v in enumerate(tree)]
            return type(tree)(vals)
        return _param_spec(path, tree, model_size)
    return walk(params_shape)


def state_pspecs(state_shape, zero1: bool = False):
    """TrainState(params, AdamWState(step, mu, nu)) -> same-leaf specs.

    ``zero1=True`` additionally shards the Adam moments over the 'data'
    axis (ZeRO-1): the first not-yet-sharded dim of each moment leaf gets
    'data'. XLA inserts the gather/scatter around the update.
    """
    from repro.train.train_step import TrainState
    from repro.train.optimizer import AdamWState
    pspec = param_pspecs(state_shape.params)
    mu = param_pspecs(state_shape.opt.mu)
    nu = param_pspecs(state_shape.opt.nu)
    if zero1:
        def add_data(spec, leaf):
            dims = list(tuple(spec)) + [None] * (leaf.ndim - len(tuple(spec)))
            for i, (d, entry) in enumerate(zip(leaf.shape, dims)):
                if entry is None and d % 16 == 0 and d > 1:
                    dims[i] = "data"
                    break
            return P(*dims)
        mu = jax.tree_util.tree_map(add_data, mu, state_shape.opt.mu,
                                    is_leaf=lambda x: isinstance(x, P))
        nu = jax.tree_util.tree_map(add_data, nu, state_shape.opt.nu,
                                    is_leaf=lambda x: isinstance(x, P))
    return TrainState(pspec, AdamWState(P(), mu, nu))


def batch_pspecs(batch_shape, dp: Tuple[str, ...]):
    """Shard the leading batch dim of every batch leaf on the dp axes."""
    def spec(leaf):
        rank = leaf.ndim
        if leaf.shape[0] == 1:
            return P(*([None] * rank))   # batch-1: unshardable
        return P(dp, *([None] * (rank - 1)))
    return jax.tree_util.tree_map(spec, batch_shape)


def cache_pspecs(cache_shape, dp: Tuple[str, ...], batch: int,
                 seq_shard: bool = False, model_size: int = 16):
    """KV caches (L,B,S,KV,dh) / SSM states -> specs.

    batch >= dp size: shard B on dp, KV heads on model.
    batch == 1 (long-context): shard cache sequence on 'data' (SP) and KV
    heads on model; SSM states shard heads on model only.
    ``model_size`` is the model-axis extent (16 for the production mesh;
    the serving engine passes its tp) used to choose between sharding the
    KV-head dim and the head_dim.
    """
    sp = batch > 1

    def walk(tree, path=()):
        if isinstance(tree, dict):
            return {k: walk(v, path + (k,)) for k, v in tree.items()}
        name = path[-1]
        rank = tree.ndim
        if name == "lengths":
            return P(dp) if sp else P(None)
        if name == "block_table":        # (B, maxp) int32: replicate —
            return P(*([None] * rank))   # every shard walks the same pages
        b_ax = rank - tree.shape[::-1].index(batch) - 1 if batch in tree.shape \
            else None
        if name in ("k_pages", "v_pages"):
            # paged pools (..., n_pages, ps, KV*dh): no batch dim — pages
            # are shared storage — so only the folded head dim can carry
            # TP (whole KV heads per shard where KV divides the tp degree)
            spec = [None] * rank
            spec[-1] = MODEL
            return P(*spec)
        if name in ("k", "v"):
            # (..., B, S, KV, dh)
            spec = [None] * rank
            if sp:
                spec[-4] = dp
            else:
                spec[-3] = "data"       # SP over cache sequence
            if seq_shard and sp:
                # Perf iteration 3: shard the cache sequence on the model
                # axis (flash-decoding style split-K) instead of padding
                # few KV heads / splitting head_dim
                spec[-3] = MODEL
            elif tree.shape[-2] % model_size == 0:  # KV heads fill TP axis
                spec[-2] = MODEL
            else:                           # shard head_dim (128/16=8)
                spec[-1] = MODEL
            return P(*spec)
        if name == "ssd":                # (..., B, nh, hd, ds)
            spec = [None] * rank
            if sp:
                spec[-4] = dp
            spec[-3] = MODEL
            return P(*spec)
        if name == "conv":               # (..., B, k-1, cd)
            spec = [None] * rank
            if sp:
                spec[-3] = dp
            return P(*spec)
        if name == "C":                  # mlstm (..., B, nh, hd, hd)
            spec = [None] * rank
            if sp:
                spec[-4] = dp
            return P(*spec)
        if name in ("n", "m", "h", "c"):
            spec = [None] * rank
            if sp and b_ax is not None:
                spec[b_ax] = dp
            return P(*spec)
        spec = [None] * rank
        if sp and b_ax is not None:
            spec[b_ax] = dp
        return P(*spec)

    return walk(cache_shape)


def logits_pspec(rank: int, dp, batch: int):
    spec = [None] * rank
    if batch > 1:
        spec[0] = dp
    spec[-1] = MODEL
    return P(*spec)
