"""The three Pallas kernels compile for a TPU v5e at real model widths.

Interpret-mode tests check semantics but not what the TPU compiler
accepts (block tiling, VMEM budget, scalar memory).  These tests compile
each kernel for a *described* v5e chip — no chip needed — at the widths
of llama3.1-8b (H=32, KV=8, dh=128) and granite-moe-3b-a800m (H=24,
KV=8, dh=64, 40 experts of 512), so a refusal shows up here first.

The paged decode and extend steps are compiled whole as well, at the
benchmark cells' sizes, and their optimised HLO is read: the stacked KV
pools must stay in place through the layer scan.
"""
import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.configs.base import simple_stages
from repro.core.expert import expert_capacity
from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.moe_gmm import moe_gmm_pallas
from repro.kernels.paged_attention import paged_attention_pallas
from repro.models.transformer import Model
from repro.serve.engine import _read_tokens, _write_tokens

LLAMA = get_config("llama3.1-8b")
GRANITE = get_config("granite-moe-3b-a800m")
QWEN = get_config("qwen3-8b")
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
    pool = (4, 8 * maxp + 1, PAGE, KV * dh)
    q = (B, H, dh) if S == 0 else (B, S, H, dh)

    def fn(q, kp, vp, table, lengths, start, layer):
        return paged_attention_pallas(
            q, kp, vp, table, lengths, layer, page_size=PAGE,
            start=None if S == 0 else start)
    _compile(fn, one_chip, (q, jnp.bfloat16), (pool, jnp.bfloat16),
             (pool, jnp.bfloat16), ((B, maxp), jnp.int32),
             ((B,), jnp.int32), ((B,), jnp.int32), ((), jnp.int32))


@pytest.mark.parametrize("tokens", [8, 256], ids=["decode", "prefill"])
@pytest.mark.parametrize("proj", ["up", "down"])
@pytest.mark.parametrize("stacked", [False, True], ids=["one", "stacked"])
def test_moe_gmm_compiles(one_chip, tokens, proj, stacked):
    mo = GRANITE.moe
    E = mo.n_experts
    C = expert_capacity(tokens, mo.top_k, E, mo.capacity_factor)
    d, f = GRANITE.d_model, mo.d_expert
    if proj == "down":
        d, f = f, d
    shapes = [((E, C, d), jnp.bfloat16), ((E, d, f), jnp.bfloat16),
              ((E,), jnp.int32)]
    if stacked:
        # every layer's experts, read at a traced layer index
        shapes[1] = ((GRANITE.n_layers, E, d, f), jnp.bfloat16)
        shapes.append(((), jnp.int32))
    _compile(moe_gmm_pallas, one_chip, *shapes)


def _served(cfg, n_layers):
    """``cfg`` as the benchmark serves it: cut to ``n_layers``, bf16
    weights, Pallas kernels."""
    return dataclasses.replace(
        cfg, n_layers=n_layers,
        stages=simple_stages(cfg.stages[0].kind, n_layers),
        param_dtype="bfloat16", kernels="pallas")


#: ``%name = bf16[2,3]{1,0:T(8,128)} opcode(`` -> (dims, minor-to-major,
#: opcode); tuple-valued instructions do not match
_HLO_OP = re.compile(r"^\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]"
                     r"(?:\{([\d,]*)[^}]*\})? ([\w-]+)\(")


def _hlo_ops(text):
    for line in text.splitlines():
        m = _HLO_OP.match(line)
        if m:
            dims = tuple(int(d) for d in m.group(1).split(",") if d)
            layout = tuple(int(d) for d in (m.group(2) or "").split(",")
                           if d)
            yield dims, layout, m.group(3)


#: a 256-token chunk: one of the serving engine's extend buckets
CHUNK = 256


#: the benchmark cells' models and slot counts (1024-token slots)
CELLS = pytest.mark.parametrize(
    "cfg,B", [(_served(QWEN, 18), 32), (_served(GRANITE, 32), 48)],
    ids=["qwen3-8b-l18", "granite-moe-3b"])


@CELLS
def test_paged_steps_keep_the_pools_in_place(one_chip, monkeypatch, cfg, B):
    """Paged decode and extend at the benchmark cells' sizes (1024-token
    slots): no op of either program yields one layer's pool (the scan
    carries the stacked pools and the kernel reads them by layer index),
    the undonated decode copies each pool once and the donated extend
    not at all, the pools enter row-major, and the temporaries hold no
    pool.  Granite's MoE kernel reads the stacked expert weights by layer
    index too: no op yields one layer's experts."""
    monkeypatch.setattr(ops, "_interpret", lambda interpret=None: False)
    model = Model(cfg, remat=False, kernel_backend="pallas",
                  pallas_interpret=False, paged=True, page_size=PAGE)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def ints(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = on_chip(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(lambda: model.init_cache(B, 1024)))
    pool = cache["stage0"]["k_pages"].shape
    L, n_pages, ps, F = pool
    assert F == cfg.n_kv_heads * cfg.d_head
    sub = {**cache, "block_table": ints(1, cache["block_table"].shape[1]),
           "lengths": ints(1)}
    programs = {
        "decode": (jax.jit(model.decode).lower(
            params, cache, ints(B, 1)).compile(), 2),
        "extend": (jax.jit(model.extend, donate_argnums=(1,)).lower(
            params, sub, ints(1, CHUNK), ints(1)).compile(), 0)}
    for name, (compiled, copies) in programs.items():
        hlo = list(_hlo_ops(compiled.as_text()))
        layer_pools = [op for dims, _, op in hlo
                       if math.prod(dims) == n_pages * ps * F]
        assert not layer_pools, (name, layer_pools)
        if cfg.moe is not None:
            size = cfg.moe.n_experts * cfg.d_model * cfg.moe.d_expert
            layer_experts = [op for dims, _, op in hlo
                             if math.prod(dims) == size]
            assert not layer_experts, (name, layer_experts)
        pool_copies = [dims for dims, _, op in hlo
                       if op in ("copy", "copy-start") and dims == pool]
        assert len(pool_copies) == copies, name
        entry = [layout for dims, layout, op in hlo
                 if op == "parameter" and dims == pool]
        assert len(entry) >= 2 and all(
            layout == (3, 2, 1, 0) for layout in entry), (name, entry)
        assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20, name


@CELLS
def test_slot_writes_and_reads_keep_the_pools_in_place(one_chip, cfg, B):
    """The engine's slot write (prefill write and restore, donated) and
    export of a 512-token slot at the cells' sizes: the write lands in
    place and neither copies a pool."""
    KV, dh = cfg.n_kv_heads, cfg.d_head
    _, n_pages = Model(cfg, paged=True, page_size=PAGE).page_geometry(
        B, 1024)
    pool = (cfg.n_layers, n_pages, PAGE, KV * dh)
    T = 512

    def on_chip(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    write = jax.jit(_write_tokens, donate_argnums=(0,)).lower(
        on_chip(pool, jnp.bfloat16), on_chip((T,)), on_chip((T,)),
        on_chip((cfg.n_layers, T, KV, dh), jnp.bfloat16)).compile()
    read = jax.jit(_read_tokens, static_argnums=(3,)).lower(
        on_chip(pool, jnp.bfloat16), on_chip((T,)), on_chip((T,)),
        dh).compile()
    for name, compiled in (("write", write), ("read", read)):
        hlo = list(_hlo_ops(compiled.as_text()))
        assert not [op for dims, _, op in hlo
                    if op in ("copy", "copy-start") and dims == pool], name
        assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20, name
