"""The three Pallas kernels compile for a TPU v5e at real model widths.

Interpret-mode tests check semantics but not what the TPU compiler
accepts (block tiling, VMEM budget, scalar memory).  These tests compile
each kernel for a *described* v5e chip — no chip needed — at the widths
of llama3.1-8b (H=32, KV=8, dh=128) and granite-moe-3b-a800m (H=24,
KV=8, dh=64, 40 experts of 512), so a refusal shows up here first.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core.expert import expert_capacity
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.moe_gmm import moe_gmm_pallas
from repro.kernels.paged_attention import paged_attention_pallas

LLAMA = get_config("llama3.1-8b")
GRANITE = get_config("granite-moe-3b-a800m")
PAGE = 64
MAX_LEN = 2048


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler / topology here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("cfg,S", [(LLAMA, MAX_LEN), (GRANITE, 512)],
                         ids=["llama3.1-8b", "granite-moe-3b"])
def test_flash_attention_compiles(one_chip, cfg, S):
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head

    def fn(q, k, v, lengths, window):
        return flash_attention_pallas(q, k, v, lengths=lengths,
                                      window=window)
    _compile(fn, one_chip, ((1, S, H, dh), jnp.bfloat16),
             ((1, S, KV, dh), jnp.bfloat16), ((1, S, KV, dh), jnp.bfloat16),
             ((1,), jnp.int32), ((), jnp.int32))


@pytest.mark.parametrize("cfg,B,S", [(LLAMA, 8, 0), (LLAMA, 1, 256),
                                     (GRANITE, 8, 0)],
                         ids=["llama3.1-8b-decode", "llama3.1-8b-extend",
                              "granite-moe-3b-decode"])
def test_paged_attention_compiles(one_chip, cfg, B, S):
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    maxp = MAX_LEN // PAGE
    pool = (8 * maxp + 1, PAGE, KV, dh)
    q = (B, H, dh) if S == 0 else (B, S, H, dh)

    def fn(q, kp, vp, table, lengths, start):
        return paged_attention_pallas(
            q, kp, vp, table, lengths, page_size=PAGE,
            start=None if S == 0 else start)
    _compile(fn, one_chip, (q, jnp.bfloat16), (pool, jnp.bfloat16),
             (pool, jnp.bfloat16), ((B, maxp), jnp.int32),
             ((B,), jnp.int32), ((B,), jnp.int32))


@pytest.mark.parametrize("tokens", [8, 256], ids=["decode", "prefill"])
@pytest.mark.parametrize("proj", ["up", "down"])
def test_moe_gmm_compiles(one_chip, tokens, proj):
    mo = GRANITE.moe
    E = mo.n_experts
    C = expert_capacity(tokens, mo.top_k, E, mo.capacity_factor)
    d, f = GRANITE.d_model, mo.d_expert
    if proj == "down":
        d, f = f, d
    _compile(moe_gmm_pallas, one_chip, ((E, C, d), jnp.bfloat16),
             ((E, d, f), jnp.bfloat16), ((E,), jnp.int32))
