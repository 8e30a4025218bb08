"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

TOLS = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
        jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


@pytest.mark.parametrize("S,H,KV,dh", [
    (64, 4, 4, 16), (128, 4, 2, 32), (256, 8, 2, 16), (64, 2, 1, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(S, H, KV, dh, dtype):
    B = 2
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, dh), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, dh), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, dh), dtype)
    out = ops.flash_attention(q, k, v, bq=32, bkv=32)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               **TOLS[dtype])


@pytest.mark.parametrize("B,H,KV,dh,ps,maxp", [
    (2, 4, 2, 16, 16, 4), (3, 8, 4, 32, 8, 6), (1, 2, 1, 64, 32, 3),
    (2, 6, 2, 64, 16, 4), (2, 4, 2, 128, 16, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_sweep(B, H, KV, dh, ps, maxp, dtype):
    """Stacked pools of three layers, each with values of its own; the
    kernel reads the middle one."""
    L, layer = 3, 1
    P = B * maxp + 2
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (B, H, dh), dtype)
    kp = jax.random.normal(ks[1], (L, P, ps, KV * dh), dtype)
    vp = jax.random.normal(ks[2], (L, P, ps, KV * dh), dtype)
    table = jax.random.permutation(ks[3], P)[: B * maxp].reshape(B, maxp)
    table = table.astype(jnp.int32)
    lengths = jnp.array([(i % maxp) * ps + ps // 2 + 1 for i in range(B)],
                        jnp.int32)
    out = ops.paged_attention(q, kp, vp, table, lengths, layer,
                              page_size=ps)
    want = ref.paged_attention_ref(q, kp, vp, table, lengths, layer,
                                   page_size=ps)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               **TOLS[dtype])
    # the other layers' pages would give other outputs
    for other in (0, 2):
        assert not np.allclose(
            np.asarray(ref.paged_attention_ref(q, kp, vp, table, lengths,
                                               other, page_size=ps),
                       np.float32),
            np.asarray(out, np.float32), **TOLS[dtype])


@pytest.mark.parametrize("E,C,d,f", [(4, 64, 32, 16), (8, 128, 16, 64),
                                     (2, 32, 128, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("layer", [None, 1], ids=["one", "stacked"])
def test_moe_gmm_sweep(E, C, d, f, dtype, layer):
    """One layer's experts (E,d,f), or layer 1 of a stack (3,E,d,f) whose
    other layers hold other random weights."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(ks[0], (E, C, d), dtype)
    shape = (E, d, f) if layer is None else (3, E, d, f)
    w = jax.random.normal(ks[1], shape, dtype)
    gs = jax.random.randint(ks[2], (E,), 0, C + 1).astype(jnp.int32)
    out = ops.moe_gmm(x, w, gs, layer, bc=32)
    want = ref.moe_gmm_ref(x, w if layer is None else w[layer], gs)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               **TOLS[dtype])
    if layer is not None:
        # the other layers' experts would give other outputs
        for other in (0, 2):
            assert not np.allclose(
                np.asarray(ref.moe_gmm_ref(x, w, gs, other), np.float32),
                np.asarray(out, np.float32), **TOLS[dtype])
