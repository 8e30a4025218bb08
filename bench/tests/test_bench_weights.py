"""Seeded weights: the served bf16 model and the reference's f32 layers are
the same numbers, in the engine's layout."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights
from bench.dims import Dims

D = Dims(n_layers=3, d=32, heads=4, kv_heads=2, d_head=8, d_ff=48, vocab=300,
         eps=1e-6, rope_theta=1e4, qk_norm=True)
MOE = Dims(n_layers=2, d=32, heads=4, kv_heads=2, d_head=8, d_ff=16,
           vocab=300, eps=1e-6, rope_theta=1e4, qk_norm=False, experts=4,
           top_k=2)
SEED = 2 ** 33 + 5


def test_served_layers_equal_reference_layers_exactly():
    for dims in (D, MOE):
        served = weights.served_params(dims, SEED)
        assert {x.dtype for x in jax.tree_util.tree_leaves(served)} == \
            {jnp.dtype(jnp.bfloat16)}
        for layer in range(dims.n_layers):
            ref = weights.layer_f32(dims, SEED, layer)
            got = jax.tree_util.tree_map(lambda a: a[layer], served["stage0"])
            for a, b in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(ref)):
                assert np.array_equal(np.asarray(a, np.float32),
                                      np.asarray(b))
        top = weights.top_f32(dims, SEED)
        for k in ("embed", "final_norm", "head"):
            for a, b in zip(jax.tree_util.tree_leaves(served[k]),
                            jax.tree_util.tree_leaves(top[k])):
                assert np.array_equal(np.asarray(a, np.float32),
                                      np.asarray(b))


def test_layout_and_seeds():
    p = weights.served_params(MOE, 7)
    assert p["embed"]["tok"].shape == (512, 32)
    assert p["head"]["w"].shape == (32, 512)
    assert p["stage0"]["moe"]["w_gate"].shape == (2, 4, 32, 16)
    assert p["stage0"]["moe"]["w_down"].shape == (2, 4, 16, 32)
    q = weights.served_params(MOE, 8)
    assert not np.array_equal(np.asarray(p["head"]["w"]),
                              np.asarray(q["head"]["w"]))
    l0, l1 = (np.asarray(p["stage0"]["attn"]["wq"][i]) for i in (0, 1))
    assert not np.array_equal(l0, l1)
    w = np.asarray(weights.layer_f32(D, 7, 0)["attn"]["wq"])
    assert abs(w.std() / (0.25 / np.sqrt(3)) - 1) < 0.05   # 2**-2 at fan-in 32


GRANITE = dataclasses.replace(MOE, embedding_multiplier=12.0,
                              attention_multiplier=1 / 64,
                              residual_multiplier=0.22, logits_scaling=6.0,
                              tied_head=True)


def test_scalars_and_tied_head_are_folded_into_the_served_weights():
    served = weights.served_params(GRANITE, SEED)
    top = weights.top_f32(GRANITE, SEED)
    bf = lambda a: np.asarray(jnp.asarray(a, jnp.bfloat16), np.float32)
    embed = np.asarray(top["embed"]["tok"])
    assert np.array_equal(np.asarray(top["head"]["w"]), embed.T)
    assert np.array_equal(np.asarray(served["embed"]["tok"], np.float32),
                          bf(embed * 12.0))
    assert np.array_equal(np.asarray(served["head"]["w"], np.float32),
                          bf(embed.T / 6.0))
    fold = {("attn", "wq"): np.sqrt(8) / 64, ("attn", "wo"): 0.22,
            ("moe", "w_down"): 0.22}
    for layer in range(GRANITE.n_layers):
        ref = weights.layer_f32(GRANITE, SEED, layer)
        for (a, b), f in [(k, fold.get(k, 1.0)) for k in
                          [("attn", n) for n in ("wq", "wk", "wv", "wo")]
                          + [("moe", n) for n in ("router", "w_gate",
                                                  "w_up", "w_down")]]:
            got = np.asarray(served["stage0"][a][b][layer], np.float32)
            assert np.array_equal(got, bf(np.asarray(ref[a][b]) * f)), (a, b)
