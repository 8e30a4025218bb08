"""Host spans on the profiler's clock (``repro.obs.span``) and the names of
the engine's programs, which a device trace shows as ``jit_<name>``."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.serve.engine import ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARCH = "llama3.1-8b-tiny"

SIM_ONLY = """
import sys
import repro.obs
from repro.core import ClusterCfg, InstanceCfg, RouterCfg, SchedulerCfg
from repro.core.cluster import Cluster
from repro.core.config import TPU_V5E, ModelSpec
from repro.obs import span
from repro.workload import ShareGPTConfig, generate

m = ModelSpec("m", n_layers=2, d_model=256, n_heads=4, n_kv_heads=2,
              d_head=64, d_ff=512, vocab=1000)
cl = Cluster(ClusterCfg(instances=(InstanceCfg(
    name="i0", hw=TPU_V5E, model=m, n_devices=1,
    scheduler=SchedulerCfg(max_batch_size=8, max_batch_tokens=2048)),),
    router=RouterCfg("round_robin")))
cl.submit_workload(generate(ShareGPTConfig(n_requests=20, rate=10.0,
                                           vocab=1000)))
assert cl.run()["finished"] == 20
assert span("runtime.schedule") is span("backend.launch", rows=3)
assert "jax" not in sys.modules, "a simulator-only run loaded jax"
print("ok")
"""


def test_simulator_only_run_loads_no_jax():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    p = subprocess.run([sys.executable, "-c", SIM_ONLY], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "ok"


def _shapes(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
        if isinstance(x, jax.Array) else x, tree)


def _helpers(kernels):
    """Run every slot helper of an engine once, recording the arguments of
    each helper program's first call (as shapes: the cache is donated)."""
    calls = {}
    eng = ServingEngine(dataclasses.replace(get_config(ARCH),
                                            kernels=kernels),
                        max_batch=2, max_len=64, prefix_cache=True)
    put = eng._put_jit

    def recording(kind, key, fn):
        def call(*args):
            calls.setdefault(kind, (fn, _shapes(args)))
            return fn(*args)
        return put(kind, key, call)
    eng._put_jit = recording
    P = 16
    _, c1 = eng._jit_prefill(eng.params, jnp.ones((1, P), jnp.int32),
                             lengths=jnp.asarray([P], jnp.int32))
    eng._write_slot_from_prefill(0, c1, P)
    sub = eng._slot_subcache(0, P)
    eng._write_slot(0, sub, P)
    payload = eng._export_slot(0, P)
    eng._restore_slot(1, payload, P)
    return eng, calls


@pytest.mark.parametrize("kernels,kinds", [
    ("auto", {"write_prefill_paged", "subcache_paged", "export_paged",
              "restore_paged"}),
    ("reference", {"write_prefill", "subcache", "write_slot", "export",
                   "restore"}),
])
def test_helper_programs_are_named_by_kind(kernels, kinds):
    """No helper program is called ``impl``: each lowers to a module named
    after the kind it is cached under; the step programs keep theirs."""
    eng, calls = _helpers(kernels)
    assert set(calls) == kinds
    for kind, (fn, args) in calls.items():
        assert fn.__name__ == kind
        assert f"module @jit_{kind} " in fn.lower(*args).as_text()
    toks = jnp.zeros((2, 1), jnp.int32)
    assert "module @jit_decode " in eng._jit_decode.lower(
        eng.params, eng.cache, toks).as_text()
    pad = jnp.zeros((1, 16), jnp.int32)
    n = jnp.asarray([16], jnp.int32)
    assert "module @jit_prefill " in eng._jit_prefill.lower(
        eng.params, pad, lengths=n).as_text()
    sub = eng._slot_subcache(0, 16)
    assert "module @jit_extend " in eng._jit_extend.lower(
        eng.params, sub, pad, n).as_text()
