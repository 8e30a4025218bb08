"""The transformer family: one stage of pre-norm decoder layers, each GQA
attention (rotary, optional qk-norm) followed by a SwiGLU MLP or a
softmax-routed mixture of SwiGLU experts.  Qwen3 and Granite-MoE.

Shapes come from the configuration's file under the keys of the published
Hugging Face ``config.json``, never from the program's own config classes.

Served weights (``served_params``) follow ``bench.weights``' draw.  The
engine's layer has no scalar multipliers and always keeps a head of its
own.  Where a configuration states them (Granite's
``embedding_multiplier``, ``attention_multiplier``,
``residual_multiplier``, ``logits_scaling``, ``tie_word_embeddings``), the
served weights carry them instead, folded in float32 before the cast: the
embedding times ``embedding_multiplier``; ``wq`` times
``attention_multiplier * sqrt(d_head)`` (the engine scales scores by
``1/sqrt(d_head)``); ``wo`` and ``w_down`` times ``residual_multiplier``;
the head the embedding's transpose where it is tied, over
``logits_scaling``.  So the engine computes the published equations; the
reference (``bench/reference/transformer.py``) applies the scalars where
the published model does, to the unfolded weights from ``layer_f32`` and
``top_f32``.  The embedding is drawn at ``2**-5`` over the power of two
nearest ``embedding_multiplier``, so the embedding the layers see keeps
that size whatever the multiplier (with a large one and a tied head,
random weights would otherwise make every token's logit its own
embedding's norm, and the model would echo its input).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from bench import costs, weights
from bench.weights import EMBED_SCALE, NORM_SCALE, fan_in_scale


@dataclasses.dataclass(frozen=True)
class Dims:
    n_layers: int
    d: int
    heads: int
    kv_heads: int
    d_head: int
    d_ff: int                 # dense MLP width, or one expert's width
    vocab: int
    eps: float
    rope_theta: float
    qk_norm: bool
    experts: int = 0          # 0: dense MLP
    top_k: int = 0
    # Granite's scalars; 1 (and 1/sqrt(d_head)) mean the plain equations
    embedding_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    tied_head: bool = False   # the head is the embedding's transpose

    @property
    def padded_vocab(self) -> int:
        """Rows of the served embedding and head: vocab rounded up to 256."""
        return -(-self.vocab // 256) * 256

    @property
    def attn_scale(self) -> float:
        return (self.attention_multiplier if self.attention_multiplier
                is not None else 1.0 / math.sqrt(self.d_head))

    @property
    def moe(self) -> bool:
        return self.experts > 0

    def layer_matmul_params(self) -> int:
        """Matmul weights one token passes through in one layer."""
        q, kv = self.heads * self.d_head, self.kv_heads * self.d_head
        attn = self.d * q + 2 * self.d * kv + q * self.d
        if self.moe:
            return attn + self.d * self.experts \
                + self.top_k * 3 * self.d * self.d_ff
        return attn + 3 * self.d * self.d_ff


def dims(conf: dict) -> Dims:
    c = conf["config"]
    d = c["hidden_size"]
    heads = c["num_attention_heads"]
    return Dims(
        n_layers=c["num_hidden_layers"], d=d, heads=heads,
        kv_heads=c["num_key_value_heads"],
        d_head=c.get("head_dim") or d // heads,
        d_ff=c["intermediate_size"], vocab=c["vocab_size"],
        eps=c["rms_norm_eps"], rope_theta=float(c["rope_theta"]),
        qk_norm=c.get("model_type") == "qwen3",
        experts=c.get("num_local_experts", 0),
        top_k=c.get("num_experts_per_tok", 0),
        embedding_multiplier=c.get("embedding_multiplier", 1.0),
        attention_multiplier=c.get("attention_multiplier"),
        residual_multiplier=c.get("residual_multiplier", 1.0),
        logits_scaling=c.get("logits_scaling", 1.0),
        tied_head=c.get("tie_word_embeddings", False))


# ---- the program's config ----

#: published key -> ArchConfig field, checked after the build
_WIDTHS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
           "num_key_value_heads": "n_kv_heads", "vocab_size": "vocab",
           "num_hidden_layers": "n_layers"}


def arch_config(cell, tiny: bool = False):
    """The program's ``ArchConfig`` for the cell: the registry entry named
    by the configuration's ``arch``, cut to its layer count, with the
    serving settings of its ``serve`` block.  Every width is checked
    against the configuration's file, so the program serves what the
    reference computes.  Scalars and a tied head, which the program's
    layer lacks, travel in the served weights."""
    from repro.configs import get_config
    from repro.configs.base import simple_stages
    serve = cell.conf["serve"]
    base = get_config(cell.conf["arch"] + ("-tiny" if tiny else ""))
    moe = base.moe
    if moe is not None:
        moe = dataclasses.replace(moe,
                                  capacity_factor=serve["capacity_factor"])
    kind = base.stages[0].kind
    n_layers = base.n_layers if tiny else cell.dims.n_layers
    cfg = dataclasses.replace(
        base, n_layers=n_layers, stages=simple_stages(kind, n_layers),
        param_dtype=serve["param_dtype"], kernels=serve["kernels"],
        norm_eps=cell.dims.eps, moe=moe)
    if tiny:
        return cfg
    c, want = cell.conf["config"], {}
    for key, field in _WIDTHS.items():
        want[field] = c[key]
    want["d_head"] = cell.dims.d_head
    want["rope_theta"] = cell.dims.rope_theta
    want["qk_norm"] = cell.dims.qk_norm
    if cell.dims.moe:
        got = {"n_experts": moe.n_experts, "top_k": moe.top_k,
               "d_expert": moe.d_expert}
        exp = {"n_experts": cell.dims.experts, "top_k": cell.dims.top_k,
               "d_expert": cell.dims.d_ff}
    else:
        got, exp = {"d_ff": cfg.d_ff}, {"d_ff": cell.dims.d_ff}
    got.update({f: getattr(cfg, f) for f in want})
    exp.update(want)
    bad = {k: (got[k], exp[k]) for k in exp if got[k] != exp[k]}
    if bad:
        raise ValueError(f"{cell.conf['name']}: the program's config differs "
                         f"from the file (program, file): {bad}")
    return cfg


def tiny_dims(cell, cfg) -> Dims:
    """Dims of a ``-tiny`` program config, for CPU tests of the harness."""
    return dataclasses.replace(
        cell.dims, n_layers=cfg.n_layers, d=cfg.d_model, heads=cfg.n_heads,
        kv_heads=cfg.n_kv_heads, d_head=cfg.d_head, vocab=cfg.vocab,
        d_ff=cfg.moe.d_expert if cfg.moe else cfg.d_ff,
        experts=cfg.moe.n_experts if cfg.moe else 0,
        top_k=cfg.moe.top_k if cfg.moe else 0)


# ---- weights ----

def layer_shapes(dims: Dims):
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


def folds(dims: Dims) -> dict:
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


def layer(dims: Dims, key, index, dtype, fold=False):
    """Layer ``index`` of the model (0-based over all its layers)."""
    return weights.draw(jax.random.fold_in(key, index + 1),
                        layer_shapes(dims), dtype,
                        folds(dims) if fold else {})


def top(dims: Dims, key, dtype, fold=False):
    """Embedding, final norm and head."""
    tk = jax.random.fold_in(key, 0)
    V, d = dims.padded_vocab, dims.d

    def u(i, shape, scale):
        return weights.uniform(jax.random.fold_in(tk, i), shape, scale)
    embed = u(0, (V, d), EMBED_SCALE
              / 2.0 ** round(math.log2(dims.embedding_multiplier)))
    head = embed.T if dims.tied_head else u(2, (d, V), fan_in_scale(d))
    if fold:
        embed = embed * dims.embedding_multiplier
        head = head / dims.logits_scaling
    return {"embed": {"tok": embed.astype(dtype)},
            "final_norm": u(1, (d,), NORM_SCALE).astype(dtype),
            "head": {"w": head.astype(dtype)}}


def stacked(dims: Dims, key, first: int, count: int, dtype):
    """Served layers ``first .. first + count - 1``, stacked for one stage
    of the engine's layer scan."""
    return jax.lax.map(lambda i: layer(dims, key, i, dtype, fold=True),
                       jnp.arange(first, first + count))


@functools.partial(jax.jit, static_argnums=(0, 2))
def _served(dims: Dims, key, dtype):
    params = top(dims, key, dtype, fold=True)
    params["stage0"] = stacked(dims, key, 0, dims.n_layers, dtype)
    return params


def served_params(dims: Dims, seed: int, dtype=jnp.bfloat16) -> dict:
    """The whole model, stacked for the engine's layer scan."""
    return _served(dims, weights.base_key(seed), jnp.dtype(dtype))


@functools.partial(jax.jit, static_argnums=(0,))
def _layer_f32(dims: Dims, key, index):
    return layer(dims, key, index, jnp.float32)


@functools.partial(jax.jit, static_argnums=(0,))
def _top_f32(dims: Dims, key):
    return top(dims, key, jnp.float32)


def layer_f32(dims: Dims, seed: int, index: int) -> dict:
    """Layer ``index`` unfolded in float32, for the reference."""
    return _layer_f32(dims, weights.base_key(seed), jnp.int32(index))


def top_f32(dims: Dims, seed: int) -> dict:
    return _top_f32(dims, weights.base_key(seed))


# ---- model FLOPs ----

def decode_step_flops(dims: Dims, contexts) -> int:
    """Model FLOPs of one decode step over the scheduled rows: every
    matmul weight once per row, the head per row, attention at each row's
    context."""
    per_row = 2 * (dims.n_layers * dims.layer_matmul_params()
                   + dims.d * dims.vocab)
    attn = sum(costs.attn_flops(dims, c - 1, 1) for c in contexts)
    return len(contexts) * per_row + dims.n_layers * attn


def prefill_chunk_flops(dims: Dims, start: int, n: int) -> int:
    """A prefill or extend call of ``n`` prompt tokens after ``start``
    cached ones; the head runs for the last token only."""
    return 2 * dims.n_layers * dims.layer_matmul_params() * n \
        + 2 * dims.d * dims.vocab \
        + dims.n_layers * costs.attn_flops(dims, start, n)
