"""The platform, not an option, decides how the serving path runs.

Kernels run compiled on TPU and interpreted on CPU, and anything else is
an error; the engine refuses configs its kernels cannot serve instead of
downgrading; the KV ledger's hardware spec comes from the device's
``device_kind``; weights are built in ``param_dtype``; the compilation
cache goes where ``JAX_COMPILATION_CACHE_DIR`` says, else to a fixed
directory of the checkout.
"""
import dataclasses
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.launch import compile_cache
from repro.models import Model
from repro.serve import ServingEngine

ARCH = "llama3.1-8b-tiny"


@pytest.mark.parametrize("platform,want", [("cpu", ("pallas", True)),
                                           ("tpu", ("pallas", False))])
@pytest.mark.parametrize("choice", ["pallas", "auto"])
def test_resolve_backend_follows_platform(monkeypatch, platform, want,
                                          choice):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert ops.resolve_backend(choice) == want
    assert ops.resolve_backend("reference") == ("reference", False)


def test_unknown_platform_raises(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops.resolve_backend("auto")
    assert ops.resolve_backend("reference") == ("reference", False)


def test_interpret_contradicting_platform_raises(monkeypatch):
    q = jnp.zeros((1, 16, 2, 8))
    k = jnp.zeros((1, 16, 1, 8))
    with pytest.raises(ValueError, match="interpret=False"):
        ops.flash_attention(q, k, k, interpret=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="interpret=True"):
        ops._interpret(True)


@pytest.mark.parametrize("arch,tp", [("zamba2-1.2b-tiny", 1), (ARCH, 2)],
                         ids=["non-attention-stages", "tp2"])
@pytest.mark.parametrize("kernels", ["pallas", "auto"])
def test_engine_refuses_what_kernels_cannot_serve(arch, tp, kernels):
    cfg = dataclasses.replace(get_config(arch), kernels=kernels)
    with pytest.raises(ValueError, match="kernels='reference'"):
        ServingEngine(cfg, max_batch=2, max_len=64, tp=tp)


def test_hw_for_device():
    from repro.hw.specs import get_hw, hw_for_device
    cpu = SimpleNamespace(platform="cpu", device_kind="cpu")
    v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    odd = SimpleNamespace(platform="tpu", device_kind="TPU v99")
    assert hw_for_device(cpu) is get_hw("cpu-engine")
    assert hw_for_device(v5e) is get_hw("tpu-v5e")
    with pytest.raises(KeyError, match="TPU v99"):
        hw_for_device(odd)


def test_engine_instance_cfg_uses_device_spec():
    from repro.serve.driver import engine_instance_cfg
    eng = ServingEngine(get_config(ARCH), max_batch=2, max_len=64)
    assert engine_instance_cfg(eng).hw.name == "cpu-engine"


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_init_honours_param_dtype(dtype):
    cfg = dataclasses.replace(get_config(ARCH), param_dtype=dtype)
    params = Model(cfg, remat=False).init(jax.random.PRNGKey(0))
    assert {str(x.dtype) for x in jax.tree_util.tree_leaves(params)} \
        == {dtype}


def test_compile_cache_location(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == str(compile_cache.CHECKOUT / ".jax_cache")
        assert (compile_cache.CHECKOUT / "chip_smoke.py").exists()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
