"""Shapes and equation constants of a configuration, read from its JSON.

``configs/<config>.json`` holds the model as it is run, under the keys of
the published Hugging Face ``config.json``.  Everything in ``bench`` that
needs a shape (weights, the plain reference, FLOP and byte counts) reads
it from here, never from the program's own config classes.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from typing import Optional

HERE = Path(__file__).resolve().parent


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


def load_config(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def dims_of(conf: dict) -> Dims:
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
