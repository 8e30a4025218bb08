"""Generic stage-composed decoder-only model.

One `Model` class covers all 10 assigned architectures: the config's
``stages`` tuple picks block kinds (attention+MLP, attention+MoE, Mamba2,
zamba superblock, xLSTM pair); every stage is a homogeneous stack run under
``jax.lax.scan`` (stacked leading layer dim), keeping the HLO compact for
fast 512-device dry-run compiles.

Three entry points (all pure functions of (params, inputs)):
  * ``loss_fn`` / ``forward``  — training (no cache),
  * ``prefill``                — forward + materialize per-layer caches,
  * ``decode``                 — one token against the cache, per-seq lengths.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import (
    ATTN_MLP, ATTN_MOE, MAMBA2, XLSTM_PAIR, ZAMBA_SUPER, ArchConfig,
)
from repro.models import module as m
from repro.models import mamba2 as mb
from repro.models import xlstm as xl
from repro.models.layers import (
    chunked_attention, decode_attention, extend_attention,
    folded_causal_attention, local_banded_attention, rmsnorm, rmsnorm_ct16,
    rope, swiglu_mlp, gelu_mlp,
)
from repro.models.flash import flash_attention
from repro.models.moe import moe_ffn


# --------------------------------------------------------------------------
# per-block init
# --------------------------------------------------------------------------

def _init_attn(key, cfg: ArchConfig, fuse_qkv: bool = False) -> dict:
    d, H, KV, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = jax.random.split(key, 4)
    if fuse_qkv:
        # single fused projection -> one dx all-reduce in backward instead
        # of a 3-tuple (see EXPERIMENTS.md Perf iteration 1)
        p = {
            "wqkv": m.dense_init(ks[0], d, (H + 2 * KV) * dh),
            "wo": m.dense_init(ks[3], H * dh, d),
        }
    else:
        p = {
            "wq": m.dense_init(ks[0], d, H * dh),
            "wk": m.dense_init(ks[1], d, KV * dh),
            "wv": m.dense_init(ks[2], d, KV * dh),
            "wo": m.dense_init(ks[3], H * dh, d),
        }
    if cfg.qkv_bias:
        p["bq"] = m.zeros((H * dh,))
        p["bk"] = m.zeros((KV * dh,))
        p["bv"] = m.zeros((KV * dh,))
    if cfg.qk_norm:
        p["q_norm"] = m.zeros((dh,))
        p["k_norm"] = m.zeros((dh,))
    return p


def _init_mlp(key, cfg: ArchConfig) -> dict:
    d, ff = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_gated:
        return {"w_gate": m.dense_init(ks[0], d, ff),
                "w_up": m.dense_init(ks[1], d, ff),
                "w_down": m.dense_init(ks[2], ff, d)}
    return {"w_in": m.dense_init(ks[0], d, ff),
            "w_out": m.dense_init(ks[1], ff, d)}


def _init_moe(key, cfg: ArchConfig) -> dict:
    d, mo = cfg.d_model, cfg.moe
    ks = jax.random.split(key, 4)
    def one(k):
        kk = jax.random.split(k, 3)
        return {"w_gate": m.dense_init(kk[0], d, mo.d_expert),
                "w_up": m.dense_init(kk[1], d, mo.d_expert),
                "w_down": m.dense_init(kk[2], mo.d_expert, d)}
    experts = m.stack_init(ks[0], mo.n_experts, one)
    return {"router": m.dense_init(ks[1], d, mo.n_experts) * 0.1,
            "w_gate": experts["w_gate"], "w_up": experts["w_up"],
            "w_down": experts["w_down"]}


def _init_attn_mlp_layer(key, cfg: ArchConfig, fuse_qkv: bool = False) -> dict:
    ks = jax.random.split(key, 2)
    return {"norm1": m.zeros((cfg.d_model,)),
            "attn": _init_attn(ks[0], cfg, fuse_qkv),
            "norm2": m.zeros((cfg.d_model,)),
            "mlp": _init_mlp(ks[1], cfg)}


def _init_attn_moe_layer(key, cfg: ArchConfig, fuse_qkv: bool = False) -> dict:
    ks = jax.random.split(key, 2)
    return {"norm1": m.zeros((cfg.d_model,)),
            "attn": _init_attn(ks[0], cfg, fuse_qkv),
            "norm2": m.zeros((cfg.d_model,)),
            "moe": _init_moe(ks[1], cfg)}


def _init_mamba_layer(key, cfg: ArchConfig) -> dict:
    return {"norm": m.zeros((cfg.d_model,)),
            "mamba": mb.init_mamba(key, cfg.d_model, cfg.ssm)}


def _init_zamba_super(key, cfg: ArchConfig) -> dict:
    return {"inner": m.stack_init(key, 6,
                                  lambda k: _init_mamba_layer(k, cfg))}


def _init_xlstm_pair(key, cfg: ArchConfig) -> dict:
    ks = jax.random.split(key, 2)
    return {"mlstm": xl.init_mlstm(ks[0], cfg.d_model, cfg.n_heads),
            "slstm": xl.init_slstm(ks[1], cfg.d_model, cfg.n_heads)}


_STAGE_INIT = {
    ATTN_MLP: _init_attn_mlp_layer,
    ATTN_MOE: _init_attn_moe_layer,
    MAMBA2: _init_mamba_layer,
    ZAMBA_SUPER: _init_zamba_super,
    XLSTM_PAIR: _init_xlstm_pair,
}


# --------------------------------------------------------------------------
# block forward helpers
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelCfg:
    """Resolved kernel-backend choice threaded through the blocks.

    ``backend`` is concrete ("reference" | "pallas"; "auto" resolves at
    engine construction via ``repro.kernels.resolve_backend``).  Pallas
    serves the no-grad phases (prefill/extend/decode); training always
    runs the differentiable pure-JAX twins.
    """
    backend: str = "reference"
    interpret: bool = True
    page_size: int = 64


def _divisor_block(S: int, b: int = 128) -> int:
    """Largest flash block size <= b that divides S (S is a static int)."""
    return next(x for x in range(min(b, S), 0, -1) if S % x == 0)


def _attention(p, x, cfg: ArchConfig, *, positions, lengths, window,
               mode: str, cache: Optional[dict], attn_impl: str,
               unroll: bool = False, kernels: Optional[KernelCfg] = None,
               block_table=None):
    """window: traced scalar (0 = full causal). Returns (out, new_cache)."""
    B, S, d = x.shape
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    pallas = kernels is not None and kernels.backend == "pallas"
    xn = x
    if "wqkv" in p:
        qkv = xn @ p["wqkv"].astype(x.dtype)
        q, k, v = jnp.split(qkv, [H * dh, (H + KV) * dh], axis=-1)
    else:
        q = xn @ p["wq"].astype(x.dtype)
        k = xn @ p["wk"].astype(x.dtype)
        v = xn @ p["wv"].astype(x.dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(B, S, H, dh)
    k = k.reshape(B, S, KV, dh)
    v = v.reshape(B, S, KV, dh)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)

    new_cache = None
    paged = cache is not None and "k_pages" in cache
    if paged and block_table is None:
        raise ValueError("paged KV cache needs the block_table threaded "
                         "through decode/extend (cache['block_table'])")
    if paged and not pallas:
        raise ValueError("paged KV cache requires the pallas kernel "
                         "backend (kernels='pallas' or 'auto')")
    if paged:
        # decode, chunked-prefill extend and spec verify on the stage's
        # stacked pools (L, n_pages, ps, KV*dh): scatter the new tokens in
        # place at [layer, page, offset] through the block table, then one
        # paged-attention walk over each sequence's pages of that layer.
        # Writes past the table's reach (a full or unscheduled decode
        # slot, an extend's pad tail) go to the scratch page, which is
        # never read; they must not clobber a real token.
        kc, vc, li = cache["k_pages"], cache["v_pages"], cache["layer"]
        ps = kernels.page_size
        maxp = block_table.shape[1]
        pidx = positions // ps
        page = block_table[jnp.arange(B)[:, None],
                           jnp.minimum(pidx, maxp - 1)]
        page = jnp.where(pidx < maxp, page, kc.shape[1] - 1)
        off = positions % ps
        kc = kc.at[li, page, off].set(
            k.reshape(B, S, KV * dh).astype(kc.dtype))
        vc = vc.at[li, page, off].set(
            v.reshape(B, S, KV * dh).astype(vc.dtype))
        from repro.kernels import paged_attention
        out = paged_attention(q, kc, vc, block_table, lengths, li,
                              page_size=ps, start=positions[:, 0],
                              window=window, interpret=kernels.interpret)
        new_cache = {"k_pages": kc, "v_pages": vc}
    elif mode == "decode":
        kc, vc = cache["k"], cache["v"]
        idx = jnp.maximum(lengths - 1, 0)
        bidx = jnp.arange(B)
        kc = kc.at[bidx, idx].set(k[:, 0].astype(kc.dtype))
        vc = vc.at[bidx, idx].set(v[:, 0].astype(vc.dtype))
        out = decode_attention(q, kc, vc, lengths=lengths, window=window)
        new_cache = {"k": kc, "v": vc}
    elif mode == "extend":
        # chunked/cached prefill: S new slots written after `positions[:,0]`
        # (pad tail masked out by `lengths`); attend to the whole cache
        kc, vc = cache["k"], cache["v"]
        start = positions[:, 0]
        bidx = jnp.arange(B)[:, None]
        sidx = start[:, None] + jnp.arange(S)[None, :]
        sidx = jnp.minimum(sidx, kc.shape[1] - 1)
        kc = kc.at[bidx, sidx].set(k.astype(kc.dtype))
        vc = vc.at[bidx, sidx].set(v.astype(vc.dtype))
        out = extend_attention(q, kc, vc, start=start, lengths=lengths,
                               window=window)
        new_cache = {"k": kc, "v": vc}
    else:
        if pallas and mode == "prefill":
            from repro.kernels import flash_attention as flash_pallas
            b = _divisor_block(S)
            out = flash_pallas(q, k, v, lengths, window, bq=b, bkv=b,
                               interpret=kernels.interpret)
        elif attn_impl == "flash":
            out = flash_attention(q, k, v, lengths, window, 1024, unroll)
        elif attn_impl == "folded" and window is None:
            out = folded_causal_attention(q, k, v, lengths=lengths,
                                          unroll=unroll)
        else:
            out = chunked_attention(q, k, v, lengths=lengths, window=window,
                                    unroll=unroll)
        if mode == "prefill":
            new_cache = {"k": k.astype(cfg.compute_dtype),
                         "v": v.astype(cfg.compute_dtype)}
    out = out.reshape(B, S, H * dh)
    return out @ p["wo"].astype(x.dtype), new_cache


def _mlp(p, x, cfg: ArchConfig):
    if cfg.mlp_gated:
        return swiglu_mlp(x, p["w_gate"], p["w_up"], p["w_down"])
    return gelu_mlp(x, p["w_in"], p["w_out"])


def _attn_mlp_block(p, x, cfg, *, positions, lengths, window, mode, cache,
                    attn_impl, unroll=False, norm_fn=rmsnorm, kernels=None,
                    block_table=None):
    h, new_cache = _attention(
        p["attn"], norm_fn(x, p["norm1"], cfg.norm_eps), cfg,
        positions=positions, lengths=lengths, window=window, mode=mode,
        cache=cache, attn_impl=attn_impl, unroll=unroll, kernels=kernels,
        block_table=block_table)
    x = x + h
    x = x + _mlp(p["mlp"], norm_fn(x, p["norm2"], cfg.norm_eps), cfg)
    return x, new_cache, jnp.zeros((), jnp.float32)


def _attn_moe_block(p, x, cfg, *, positions, lengths, window, mode, cache,
                    attn_impl, unroll=False, shard_experts=False,
                    layer_idx=None, routing_hook=None, row_valid=None,
                    kernels=None, block_table=None, expert_layer=None):
    h, new_cache = _attention(
        p["attn"], rmsnorm(x, p["norm1"], cfg.norm_eps), cfg,
        positions=positions, lengths=lengths, window=window, mode=mode,
        cache=cache, attn_impl=attn_impl, unroll=unroll, kernels=kernels,
        block_table=block_table)
    x = x + h
    B, S, d = x.shape
    xn = rmsnorm(x, p["norm2"], cfg.norm_eps).reshape(B * S, d)
    pos_flat = valid = None
    if routing_hook is not None:
        # flattened (B*S,) token positions line up with xn's rows — the
        # routing hook keys its per-position expert table on them.  The
        # validity mask flags pad-tail rows (bucketed prefill/extend
        # process positions >= the sequence's real length) so recording
        # taps don't histogram padding.  In decode — a full-buffer batch
        # where empty AND occupied-but-unscheduled (mid-prefill) slots
        # are routed too — ``row_valid`` (derived from the tokens-buffer
        # sentinel in ``decode``) identifies the really-scheduled rows;
        # position 0 additionally screens empty slots for direct callers
        # that pass plain token ids.
        pos_flat = positions.reshape(B * S)
        if mode == "decode":
            valid = pos_flat > 0
            if row_valid is not None:
                valid = valid & jnp.broadcast_to(row_valid[:, None],
                                                 (B, S)).reshape(B * S)
        elif lengths is not None:
            valid = (positions < lengths[:, None]).reshape(B * S)
    y, aux = moe_ffn(xn, p["moe"], top_k=cfg.moe.top_k,
                     capacity_factor=cfg.moe.capacity_factor,
                     gated=cfg.mlp_gated, shard_experts=shard_experts,
                     router_fn=routing_hook, positions=pos_flat,
                     layer=layer_idx, valid=valid,
                     backend=kernels.backend if kernels is not None
                     else "reference",
                     interpret=kernels.interpret if kernels is not None
                     else True, expert_layer=expert_layer)
    x = x + y.reshape(B, S, d)
    return x, new_cache, aux


def _mamba_block(p, x, cfg, *, mode, cache):
    xn = rmsnorm(x, p["norm"], cfg.norm_eps)
    if mode == "decode":
        y, st = mb.mamba_decode(p["mamba"], xn, cfg, cache)
        return x + y, st, jnp.zeros((), jnp.float32)
    if mode == "prefill":
        y, st = mb.mamba_forward(p["mamba"], xn, cfg, return_state=True)
        return x + y, st, jnp.zeros((), jnp.float32)
    if mode == "extend":
        y, st = mb.mamba_forward(p["mamba"], xn, cfg, state=cache,
                                 return_state=True)
        return x + y, st, jnp.zeros((), jnp.float32)
    y = mb.mamba_forward(p["mamba"], xn, cfg)
    return x + y, None, jnp.zeros((), jnp.float32)


def _xlstm_block(p, x, cfg, *, mode, cache, unroll=False):
    nh, eps = cfg.n_heads, cfg.norm_eps
    if mode == "extend":
        raise NotImplementedError(
            "xLSTM cached-prefill (extend) is not supported; the serving "
            "engine uses fresh prefill for xLSTM models")
    if mode == "decode":
        x, st_m = xl.mlstm_decode(p["mlstm"], x, nh, eps, cache["mlstm"])
        x, st_s = xl.slstm_decode(p["slstm"], x, nh, eps, cache["slstm"])
        return x, {"mlstm": st_m, "slstm": st_s}, jnp.zeros((), jnp.float32)
    if mode == "prefill":
        x, st_m = xl.mlstm_forward(p["mlstm"], x, nh, eps, return_state=True,
                                   unroll=unroll)
        x, st_s = xl.slstm_forward(p["slstm"], x, nh, eps, return_state=True)
        return x, {"mlstm": st_m, "slstm": st_s}, jnp.zeros((), jnp.float32)
    x = xl.mlstm_forward(p["mlstm"], x, nh, eps, unroll=unroll)
    x = xl.slstm_forward(p["slstm"], x, nh, eps)
    return x, None, jnp.zeros((), jnp.float32)


# --------------------------------------------------------------------------
# the Model
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Model:
    cfg: ArchConfig
    attn_impl: str = "flash"        # flash | chunked | folded
    remat: bool = True
    gemma_superblock: bool = False  # banded local layers (perf variant)
    # Fully unroll the layer stack + inner flash/SSD scans. Used by the
    # dry-run: XLA's cost_analysis does not multiply while-loop bodies by
    # trip count, so loop-free HLO is required for trustworthy roofline
    # numbers (compile is slower; execution semantics identical).
    unroll: bool = False
    fuse_qkv: bool = False          # single QKV matmul (Perf iteration 1)
    shard_experts: bool = False     # pin MoE buffers to model axis (Perf it.2)
    norm_ct16: bool = False         # bf16 cotangent boundary at norms (it.4)
    # injectable MoE routing hook (repro.moe.hooks): replaces the top-k
    # assignment step of every MoE layer — forced replay of a recorded/
    # synthetic ExpertRoutingTrace, logit biasing, or a recording tap.
    # Must be set at construction (the jitted closures capture it).
    routing_hook: Optional[Any] = None
    # resolved kernel backend ("reference" | "pallas" — resolve "auto" via
    # repro.kernels.resolve_backend before constructing the Model).  Pallas
    # only serves the no-grad phases; training uses the pure-JAX twins.
    kernel_backend: str = "reference"
    pallas_interpret: bool = True
    # paged slot-KV layout: attention caches become shared page pools
    # ("k_pages"/"v_pages", (L, n_pages, page_size, KV*dh): a token's KV
    # heads side by side in the last dim) indexed by a per-sequence block
    # table (cache["block_table"], (B, maxp) int32).  A stage's stacked
    # pools travel whole through its layers; layer li writes and reads
    # index li of them (see ``_run_paged``).  Requires
    # kernel_backend="pallas" and an all-attention stage list.
    paged: bool = False
    page_size: int = 64

    def _kernel_cfg(self, mode: str) -> Optional[KernelCfg]:
        if self.kernel_backend != "pallas" or mode == "train":
            return None
        return KernelCfg(backend="pallas", interpret=self.pallas_interpret,
                         page_size=self.page_size)

    # ---- init ----
    def init(self, key, out_shardings=None) -> dict:
        """Random weights in ``cfg.param_dtype``.  One jit builds them,
        casting each layer as it is made, so no float32 copy of a stage
        is ever materialised (a full-width stage would not fit a chip);
        ``out_shardings`` builds them straight into a sharded layout."""
        return jax.jit(self._init, out_shardings=out_shardings)(key)

    def _init(self, key) -> dict:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.param_dtype)
        keys = jax.random.split(key, len(cfg.stages) + 4)
        params: Dict[str, Any] = {}
        if cfg.embed_inputs:
            params["embed"] = {"tok": m.embed_init(keys[0], cfg.padded_vocab,
                                                   cfg.d_model)}
        for i, st in enumerate(cfg.stages):
            init_fn = _STAGE_INIT[st.kind]
            args = (self.fuse_qkv,) if st.kind in (ATTN_MLP, ATTN_MOE) \
                else ()
            params[f"stage{i}"] = m.stack_init(
                keys[i + 1], st.n_layers,
                lambda k: m.cast_tree(init_fn(k, cfg, *args), dtype))
        if any(st.kind == ZAMBA_SUPER for st in cfg.stages):
            params["shared_attn"] = _init_attn_mlp_layer(keys[-3], cfg)
        params["final_norm"] = m.zeros((cfg.d_model,))
        nout = max(1, cfg.n_codebooks or 1)
        params["head"] = {"w": m.dense_init(keys[-2], cfg.d_model,
                                            nout * cfg.padded_vocab)}
        return m.cast_tree(params, dtype)

    # ---- embedding / head ----
    def _embed(self, params, tokens):
        cfg = self.cfg
        if cfg.embed_inputs:
            x = params["embed"]["tok"].astype(cfg.compute_dtype)[tokens]
        else:
            x = tokens.astype(cfg.compute_dtype)   # precomputed embeddings
        return x

    def _head(self, params, x):
        """Logits over the *padded* vocab; consumers slice [..., :vocab]."""
        cfg = self.cfg
        logits = x @ params["head"]["w"].astype(x.dtype)
        if cfg.n_codebooks:
            B, S, _ = logits.shape
            logits = logits.reshape(B, S, cfg.n_codebooks, cfg.padded_vocab)
        return logits

    # ---- stage runners ----
    def _window_for_layer(self, li, period):
        """Traced per-layer window; None = full causal everywhere.

        Global layers get a huge window (== no restriction) so one scanned
        body covers the local:global interleave.
        """
        cfg = self.cfg
        if cfg.sliding_window == 0 or period == 0:
            return None
        is_global = (li % period) == (period - 1)
        return jnp.where(is_global, jnp.int32(2 ** 30),
                         jnp.int32(cfg.sliding_window))

    def _run_stage(self, idx, stage, params, x, *, positions, lengths, mode,
                   cache, shared_attn, row_valid=None, block_table=None):
        cfg = self.cfg
        sp = params[f"stage{idx}"]
        kind = stage.kind
        L = stage.n_layers
        # closure-captured (NOT scan xs): the kernel config is static and
        # the block table is shared by every layer of every stage
        kernels = self._kernel_cfg(mode)
        # global MoE-layer index base: routing hooks key their per-layer
        # tables on the model-wide MoE layer, not the stage-local one
        moe_off = sum(s.n_layers for s in cfg.stages[:idx]
                      if s.kind == ATTN_MOE)
        # the grouped-matmul kernel reads a layer's experts out of the
        # whole stage stack at a scalar-prefetched index, so the stack is
        # closure-captured too: a layer's experts sliced out of it as scan
        # xs would be a copy of every expert's weights per layer
        experts = None
        if kind == ATTN_MOE and kernels is not None \
                and not self.shard_experts:
            moe = dict(sp["moe"])
            experts = {k: moe.pop(k) for k in ("w_gate", "w_up", "w_down")}
            sp = {**sp, "moe": moe}

        def layer(x, li, p, kcache):
            if kind == ATTN_MLP:
                window = self._window_for_layer(li, stage.local_global_period)
                return _attn_mlp_block(
                    p, x, cfg, positions=positions, lengths=lengths,
                    window=window, mode=mode, cache=kcache,
                    attn_impl=self.attn_impl, unroll=self.unroll,
                    norm_fn=rmsnorm_ct16 if self.norm_ct16 else rmsnorm,
                    kernels=kernels, block_table=block_table)
            if kind == ATTN_MOE:
                if experts is not None:
                    p = {**p, "moe": {**p["moe"], **experts}}
                return _attn_moe_block(
                    p, x, cfg, positions=positions, lengths=lengths,
                    window=None, mode=mode, cache=kcache,
                    attn_impl=self.attn_impl, unroll=self.unroll,
                    shard_experts=self.shard_experts,
                    layer_idx=moe_off + li,
                    routing_hook=self.routing_hook, row_valid=row_valid,
                    kernels=kernels, block_table=block_table,
                    expert_layer=None if experts is None else li)
            if kind == MAMBA2:
                return _mamba_block(p, x, cfg, mode=mode, cache=kcache)
            if kind == ZAMBA_SUPER:
                return self._zamba_super(p, x, li, kcache, shared_attn,
                                         positions=positions, lengths=lengths,
                                         mode=mode)
            if kind == XLSTM_PAIR:
                return _xlstm_block(p, x, cfg, mode=mode, cache=kcache,
                                    unroll=self.unroll)
            raise ValueError(kind)

        if self.remat and mode == "train":
            layer = jax.checkpoint(
                layer, policy=jax.checkpoint_policies.nothing_saveable)

        if cache is not None and "k_pages" in cache:
            return self._run_paged(layer, L, sp, x, cache)

        if self.unroll:
            new_caches_l, auxes_l = [], []
            for li in range(L):
                p = jax.tree_util.tree_map(lambda a: a[li], sp)
                kcache = None if cache is None else jax.tree_util.tree_map(
                    lambda a: a[li], cache)
                x, nc, aux = layer(x, jnp.int32(li), p, kcache)
                new_caches_l.append(nc)
                auxes_l.append(aux)
            new_caches = None
            if new_caches_l and new_caches_l[0] is not None:
                new_caches = jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *new_caches_l)
            return x, new_caches, sum(auxes_l)

        def body(carry, xs):
            x = carry
            li, p, kcache = xs
            x, new_cache, aux = layer(x, li, p, kcache)
            return x, (new_cache, aux)

        lis = jnp.arange(L)
        xs = (lis, sp, cache)
        x, (new_caches, auxes) = jax.lax.scan(body, x, xs)
        return x, new_caches, auxes.sum()

    def _run_paged(self, layer, L, sp, x, cache):
        """The layer stack over a paged cache.  The stacked pools travel
        whole through the layers, each layer writing its tokens at its
        own index and its kernel reading that index of the stack, so no
        layer's pool is ever sliced out of the stack or written back."""
        pools = (cache["k_pages"], cache["v_pages"])

        def step(x, li, p, pools):
            x, nc, aux = layer(x, li, p, {"k_pages": pools[0],
                                          "v_pages": pools[1], "layer": li})
            return x, (nc["k_pages"], nc["v_pages"]), aux

        if self.unroll:
            auxes = []
            for li in range(L):
                p = jax.tree_util.tree_map(lambda a: a[li], sp)
                x, pools, aux = step(x, jnp.int32(li), p, pools)
                auxes.append(aux)
            aux = sum(auxes)
        else:
            def body(carry, xs):
                x, pools = carry
                li, p = xs
                x, pools, aux = step(x, li, p, pools)
                return (x, pools), aux
            (x, pools), auxes = jax.lax.scan(body, (x, pools),
                                             (jnp.arange(L), sp))
            aux = auxes.sum()
        return x, {"k_pages": pools[0], "v_pages": pools[1]}, aux

    def _zamba_super(self, p, x, li, kcache, shared_attn, *, positions,
                     lengths, mode):
        """5 mamba + 1 (mamba + shared attention) per superblock."""
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        inner = p["inner"]
        new_inner = []
        for j in range(6):
            pj = jax.tree_util.tree_map(lambda a: a[j], inner)
            cj = None if kcache is None else jax.tree_util.tree_map(
                lambda a: a[j], kcache["mamba"])
            x, st, _ = _mamba_block(pj, x, cfg, mode=mode, cache=cj)
            new_inner.append(st)
        attn_cache = None if kcache is None else kcache["attn"]
        x, new_attn, _ = _attn_mlp_block(
            shared_attn, x, cfg, positions=positions, lengths=lengths,
            window=None, mode=mode, cache=attn_cache,
            attn_impl=self.attn_impl, unroll=self.unroll)
        new_cache = None
        if mode in ("prefill", "decode", "extend"):
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *new_inner)
            new_cache = {"mamba": stacked, "attn": new_attn}
        return x, new_cache, aux

    # ---- entry points ----
    def forward(self, params, tokens, *, lengths=None):
        """Training/scoring forward. tokens: (B,S) ids or (B,S,d) embeds."""
        cfg = self.cfg
        x = self._embed(params, tokens)
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        aux_total = jnp.zeros((), jnp.float32)
        for i, st in enumerate(cfg.stages):
            cache_xs = None
            x, _, aux = self._run_stage(
                i, st, params, x, positions=positions, lengths=lengths,
                mode="train", cache=cache_xs,
                shared_attn=params.get("shared_attn"))
            aux_total = aux_total + aux
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        return self._head(params, x), aux_total

    def loss_fn(self, params, batch):
        """batch: {tokens/inputs, labels, (weights)} -> (loss, metrics)."""
        cfg = self.cfg
        inputs = batch["inputs"]
        labels = batch["labels"]
        logits, aux = self.forward(params, inputs)
        logits = logits.astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        if cfg.n_codebooks:
            nll = nll.mean(axis=-1)          # average over codebook heads
        weights = batch.get("weights")
        if weights is None:
            weights = jnp.ones(nll.shape, jnp.float32)
        loss = (nll * weights).sum() / jnp.maximum(weights.sum(), 1.0)
        total = loss + 0.01 * aux
        return total, {"loss": loss, "aux_loss": aux,
                       "tokens": weights.sum()}

    def prefill(self, params, tokens, *, lengths=None):
        """Returns (logits_last, cache). tokens: (B,S)."""
        cfg = self.cfg
        x = self._embed(params, tokens)
        B, S = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        if lengths is None:
            lengths = jnp.full((B,), S, jnp.int32)
        caches = {}
        for i, st in enumerate(cfg.stages):
            x, new_cache, _ = self._run_stage(
                i, st, params, x, positions=positions, lengths=lengths,
                mode="prefill", cache=None,
                shared_attn=params.get("shared_attn"))
            caches[f"stage{i}"] = new_cache
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        idx = jnp.maximum(lengths - 1, 0)
        x_last = x[jnp.arange(B), idx][:, None]        # (B,1,d)
        logits = self._head(params, x_last)
        caches["lengths"] = lengths
        return logits, caches

    def decode(self, params, cache, tokens):
        """One decode step. tokens: (B,1) ids (or (B,1,d) embeds).

        cache["lengths"] counts tokens *already in* the cache; the new token
        is written at index lengths (then lengths+1 is returned).
        """
        cfg = self.cfg
        # MoE routing-hook row mask for the full-buffer batch: a negative
        # token id is the engine's sentinel for a slot that is NOT
        # scheduled this iteration (free, or occupied mid-prefill) — its
        # row still computes, but must neither be recorded as workload
        # routing nor consume expert capacity under forced replay
        row_valid = None
        if jnp.issubdtype(tokens.dtype, jnp.integer):
            row_valid = tokens.reshape(tokens.shape[0], -1)[:, 0] >= 0
            tokens = jnp.maximum(tokens, 0)
        x = self._embed(params, tokens)
        B = x.shape[0]
        lengths = cache["lengths"] + 1       # include current token
        positions = (lengths - 1)[:, None]
        block_table = cache.get("block_table")
        new_cache = {"lengths": lengths}
        if block_table is not None:
            new_cache["block_table"] = block_table
        for i, st in enumerate(cfg.stages):
            x, nc, _ = self._run_stage(
                i, st, params, x, positions=positions, lengths=lengths,
                mode="decode", cache=cache[f"stage{i}"],
                shared_attn=params.get("shared_attn"),
                row_valid=row_valid, block_table=block_table)
            new_cache[f"stage{i}"] = nc
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = self._head(params, x)
        return logits, new_cache

    def _extend_states(self, params, cache, tokens, n_new):
        """Shared body of ``extend``/``verify``: append up to S tokens to
        the cache and return the final-norm hidden states of every
        position, ``(B, S, d)``, plus the new cache."""
        cfg = self.cfg
        x = self._embed(params, tokens)
        B, S = x.shape[:2]
        start = cache["lengths"]
        if n_new is None:
            n_new = jnp.full((B,), S, jnp.int32)
        lengths = start + n_new
        positions = start[:, None] + jnp.arange(S)[None, :]
        block_table = cache.get("block_table")
        new_cache = {"lengths": lengths}
        if block_table is not None:
            new_cache["block_table"] = block_table
        for i, st in enumerate(cfg.stages):
            x, nc, _ = self._run_stage(
                i, st, params, x, positions=positions, lengths=lengths,
                mode="extend", cache=cache[f"stage{i}"],
                shared_attn=params.get("shared_attn"),
                block_table=block_table)
            new_cache[f"stage{i}"] = nc
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        return x, new_cache, n_new

    def extend(self, params, cache, tokens, n_new=None):
        """Cached/chunked prefill: append up to S tokens (``n_new`` (B,)
        real, rest padding) to a cache holding cache["lengths"] tokens per
        sequence. Returns (last-real-token logits, cache)."""
        x, new_cache, n_new = self._extend_states(params, cache, tokens,
                                                  n_new)
        idx = jnp.maximum(n_new - 1, 0)
        x_last = x[jnp.arange(x.shape[0]), idx][:, None]
        logits = self._head(params, x_last)
        return logits, new_cache

    def verify(self, params, cache, tokens, n_new=None):
        """Speculative-decoding verification: ``extend`` the cache with up
        to S tokens (the pending token + the draft's proposals) but return
        logits at EVERY position — ``(B, S, Vpad)`` — so the caller can
        compare each draft token against the target's greedy prediction
        and pick the accepted prefix + bonus token.  KV for all S slots is
        written; the caller rolls ``lengths`` back to the accepted prefix
        (unaccepted rows are dead weight overwritten by the next write at
        the same indices)."""
        x, new_cache, _ = self._extend_states(params, cache, tokens, n_new)
        logits = self._head(params, x)
        return logits, new_cache

    # ---- cache construction ----
    def page_geometry(self, batch: int, max_len: int) -> Tuple[int, int]:
        """(pages per sequence, total pool pages incl. the scratch page)."""
        maxp = -(-max_len // self.page_size)
        return maxp, batch * maxp + 1

    def init_cache(self, batch: int, max_len: int, dtype=None):
        """Zeroed cache pytree (concrete); see ``cache_specs`` for dry-run."""
        cfg = self.cfg
        dtype = dtype or cfg.compute_dtype
        cache: Dict[str, Any] = {
            "lengths": jnp.zeros((batch,), jnp.int32)}
        if self.paged:
            bad = [st.kind for st in cfg.stages
                   if st.kind not in (ATTN_MLP, ATTN_MOE)]
            if bad:
                raise ValueError(
                    f"paged KV cache only supports attention stages; "
                    f"{self.cfg.name} has {bad}")
            # every sequence starts pointing at the scratch page (last pool
            # index): garbage writes from unscheduled decode slots land
            # there and are never read back
            maxp, n_pages = self.page_geometry(batch, max_len)
            cache["block_table"] = jnp.full((batch, maxp), n_pages - 1,
                                            jnp.int32)
        for i, st in enumerate(cfg.stages):
            cache[f"stage{i}"] = self._stage_cache(st, batch, max_len, dtype)
        return cache

    def _stage_cache(self, st, batch, max_len, dtype):
        cfg = self.cfg
        L = st.n_layers
        KV, dh = cfg.n_kv_heads, cfg.d_head

        def kv(n):
            if self.paged:
                _, n_pages = self.page_geometry(batch, max_len)
                shape = (n, n_pages, self.page_size, KV * dh)
                return {"k_pages": jnp.zeros(shape, dtype),
                        "v_pages": jnp.zeros(shape, dtype)}
            return {"k": jnp.zeros((n, batch, max_len, KV, dh), dtype),
                    "v": jnp.zeros((n, batch, max_len, KV, dh), dtype)}

        if st.kind in (ATTN_MLP, ATTN_MOE):
            return kv(L)
        if st.kind == MAMBA2:
            one = mb.init_mamba_state(batch, cfg.d_model, cfg.ssm, dtype)
            return jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (L,) + a.shape), one)
        if st.kind == ZAMBA_SUPER:
            one = mb.init_mamba_state(batch, cfg.d_model, cfg.ssm, dtype)
            mamba = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a, (L, 6) + a.shape), one)
            return {"mamba": mamba,
                    "attn": jax.tree_util.tree_map(lambda a: a, kv(L))}
        if st.kind == XLSTM_PAIR:
            ml = xl.init_mlstm_state(batch, cfg.d_model, cfg.n_heads, dtype)
            sl = xl.init_slstm_state(batch, cfg.d_model, cfg.n_heads)
            return {
                "mlstm": jax.tree_util.tree_map(
                    lambda a: jnp.broadcast_to(a, (L,) + a.shape), ml),
                "slstm": jax.tree_util.tree_map(
                    lambda a: jnp.broadcast_to(a, (L,) + a.shape), sl),
            }
        raise ValueError(st.kind)
