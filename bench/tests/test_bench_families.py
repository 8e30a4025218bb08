"""A model family arrives as new modules only.

The ``two_stage`` family here (one dense SwiGLU layer, then expert layers
counted under ``n_routed_experts``, as DeepSeek-V3's layer plan has them)
and its float32 reference are in-memory modules, found by the
configuration's ``"family"`` and ``"reference"`` keys as files under
``bench/families`` and ``bench/reference`` would be.  At ``-tiny`` sizes on
the CPU it is served through ``run.Setup`` and a paced run on the Pallas
paged path as two program stages (``ATTN_MLP``, then ``ATTN_MOE``), read
correct, and read not correct under each planted fault and the fp8
control.  Nothing of the harness is edited for it.  That the benchmark's own
tests also take a new configuration, cell and metric unedited, added to
``BENCHMARK.json`` as new files plus entries, is shown by
``test_bench_additions.py``."""
import copy
import dataclasses
import functools
import importlib.util
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import cell as cells
from bench import costs, run, weights, xplane
from bench.families import transformer
from bench.reference import transformer as transformer_ref

HERE = Path(__file__).resolve().parent
SEED = 2 ** 32 + 77
#: limit of the check at the ``-tiny`` sizes, set as the harness test's
#: are, from CPU readings over every request finished in a 4 s window,
#: seeds 5..16 and SEED: program mean gap up to 0.0036, the fp8 control
#: at least 0.060.  The widest gaps overlap (program up to 1.10, control
#: from 1.35: a near-tie of two experts' router scores can fall either way
#: in bfloat16), as Granite's do, so only the mean is compared.
LIMITS = {"mean_logit_gap": 0.015}
#: a made-up model: DeepSeek-V3's layer plan (dense layers first, then
#: routed experts) at Granite-3.0-1B-A400M's attention and expert widths
CONF = {
    "name": "two-stage-test",
    "family": "two_stage",
    "reference": "two_stage",
    "arch": "granite-moe-1b-a400m",
    "config": {
        "hidden_size": 1024, "num_attention_heads": 16,
        "num_key_value_heads": 8, "head_dim": 64,
        "intermediate_size": 4096, "moe_intermediate_size": 512,
        "n_routed_experts": 32, "num_experts_per_tok": 8,
        "first_k_dense_replace": 1, "num_hidden_layers": 24,
        "vocab_size": 49155, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    },
    "serve": {"param_dtype": "bfloat16", "kernels": "pallas",
              "capacity_factor": 4.0},
}
TINY_LAYERS = 3


# ---- the family: bench/families/two_stage.py ----

@dataclasses.dataclass(frozen=True)
class TwoStageDims:
    dense: transformer.Dims   # a layer of the leading dense stage
    moe: transformer.Dims     # a layer of the expert stage
    n_dense: int

    @property
    def n_layers(self) -> int:
        return self.dense.n_layers

    @property
    def vocab(self) -> int:
        return self.dense.vocab

    @property
    def padded_vocab(self) -> int:
        return self.dense.padded_vocab

    def layer(self, i: int) -> transformer.Dims:
        return self.dense if i < self.n_dense else self.moe


def dims(conf):
    c = conf["config"]
    d, heads = c["hidden_size"], c["num_attention_heads"]
    common = dict(n_layers=c["num_hidden_layers"], d=d, heads=heads,
                  kv_heads=c["num_key_value_heads"],
                  d_head=c.get("head_dim") or d // heads,
                  vocab=c["vocab_size"], eps=c["rms_norm_eps"],
                  rope_theta=float(c["rope_theta"]), qk_norm=False)
    return TwoStageDims(
        dense=transformer.Dims(d_ff=c["intermediate_size"], **common),
        moe=transformer.Dims(d_ff=c["moe_intermediate_size"],
                             experts=c["n_routed_experts"],
                             top_k=c["num_experts_per_tok"], **common),
        n_dense=c["first_k_dense_replace"])


def arch_config(cell, tiny=False):
    from repro.configs import get_config
    from repro.configs.base import ATTN_MLP, ATTN_MOE, Stage
    serve, dm = cell.conf["serve"], cell.dims
    base = get_config(cell.conf["arch"] + ("-tiny" if tiny else ""))
    n = TINY_LAYERS if tiny else dm.n_layers
    cfg = dataclasses.replace(
        base, n_layers=n, d_ff=base.d_ff if tiny else dm.dense.d_ff,
        stages=(Stage(ATTN_MLP, dm.n_dense), Stage(ATTN_MOE, n - dm.n_dense)),
        param_dtype=serve["param_dtype"], kernels=serve["kernels"],
        norm_eps=dm.dense.eps,
        moe=dataclasses.replace(base.moe,
                                capacity_factor=serve["capacity_factor"]))
    if tiny:
        return cfg
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.d_head, cfg.vocab, cfg.rope_theta, cfg.qk_norm, cfg.d_ff,
           cfg.moe.n_experts, cfg.moe.top_k, cfg.moe.d_expert)
    d, m = dm.dense, dm.moe
    want = (d.n_layers, d.d, d.heads, d.kv_heads, d.d_head, d.vocab,
            d.rope_theta, d.qk_norm, d.d_ff, m.experts, m.top_k, m.d_ff)
    if got != want:
        raise ValueError(f"the program's config differs from the file: "
                         f"{got} != {want}")
    return cfg


def tiny_dims(cell, cfg):
    common = dict(n_layers=cfg.n_layers, d=cfg.d_model, heads=cfg.n_heads,
                  kv_heads=cfg.n_kv_heads, d_head=cfg.d_head,
                  vocab=cfg.vocab)
    return TwoStageDims(
        dense=dataclasses.replace(cell.dims.dense, d_ff=cfg.d_ff, **common),
        moe=dataclasses.replace(cell.dims.moe, d_ff=cfg.moe.d_expert,
                                experts=cfg.moe.n_experts,
                                top_k=cfg.moe.top_k, **common),
        n_dense=cfg.stages[0].n_layers)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _served(dm, key, dtype):
    params = transformer.top(dm.dense, key, dtype, fold=True)
    params["stage0"] = transformer.stacked(dm.dense, key, 0, dm.n_dense,
                                           dtype)
    params["stage1"] = transformer.stacked(dm.moe, key, dm.n_dense,
                                           dm.n_layers - dm.n_dense, dtype)
    return params


def served_params(dm, seed, dtype=jnp.bfloat16):
    return _served(dm, weights.base_key(seed), jnp.dtype(dtype))


def _matmul_params(dm) -> int:
    return sum(dm.layer(i).layer_matmul_params() for i in range(dm.n_layers))


def decode_step_flops(dm, contexts):
    per_row = 2 * (_matmul_params(dm) + dm.dense.d * dm.vocab)
    attn = sum(costs.attn_flops(dm.dense, c - 1, 1) for c in contexts)
    return len(contexts) * per_row + dm.n_layers * attn


def prefill_chunk_flops(dm, start, n):
    return 2 * _matmul_params(dm) * n + 2 * dm.dense.d * dm.vocab \
        + dm.n_layers * costs.attn_flops(dm.dense, start, n)


# ---- its reference: bench/reference/two_stage.py ----

def logit_gaps(dm, seed, tokens, lengths, targets, control=False):
    """The transformer reference's equations, each layer at its stage's
    shapes."""
    top = transformer.top_f32(dm.dense, seed)
    tok = jnp.asarray(tokens, jnp.int32)
    ln = jnp.asarray(lengths, jnp.int32)

    def forward(quantize):
        x = transformer_ref.embed(dm.dense, transformer_ref.maybe_quantize(
            top["embed"]["tok"], quantize), tok)
        for i in range(dm.n_layers):
            w = transformer.layer_f32(dm.layer(i), seed, i)
            x = transformer_ref.block(dm.layer(i), w, x, ln, quantize)
        return transformer_ref.final(dm.dense, top["final_norm"], x)
    xr = forward(None)
    xc = forward("fp8") if control else xr
    return transformer_ref.readout(dm.dense, top, xr, xc, targets, control)


def module(name, *fns):
    mod = types.ModuleType(name)
    for f in fns:
        setattr(mod, f.__name__, f)
    return mod


FAMILY = module("bench.families.two_stage", dims, arch_config, tiny_dims,
                served_params, decode_step_flops, prefill_chunk_flops)
REFERENCE = module("bench.reference.two_stage", logit_gaps)


def harness():
    """The harness test's paced run, tiny cell and planted faults."""
    spec = importlib.util.spec_from_file_location(
        "bench_harness_test", HERE / "test_bench_harness.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


H = harness()


@pytest.fixture(scope="module", autouse=True)
def two_stage_modules():
    """The family and reference where their files would be imported."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(sys.modules, FAMILY.__name__, FAMILY)
        mp.setitem(sys.modules, REFERENCE.__name__, REFERENCE)
        yield


def two_stage_cell(conf=CONF):
    chat = H.tiny_cell("qwen3-8b-l18.chat")
    spec = dict(copy.deepcopy(chat.spec),
                check=dict(chat.spec["check"], limits=LIMITS))
    return cells.Cell("two-stage-test.chat", 1, conf, chat.traffic, spec)


@pytest.fixture(scope="module")
def setup(tmp_path_factory):
    saved = {k: getattr(jax.config, k) for k in H._CACHE_KEYS}
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("JAX_COMPILATION_CACHE_DIR",
                  str(tmp_path_factory.mktemp("jax_cache")))
        yield run.Setup(two_stage_cell(), SEED, require_tpu=False, tiny=True)
    for k, v in saved.items():
        jax.config.update(k, v)


def test_the_configuration_names_its_family():
    c = two_stage_cell()
    assert c.family is FAMILY
    assert (c.dims.n_layers, c.dims.n_dense, c.dims.moe.experts) == (24, 1, 32)
    cfg = c.family.arch_config(c)
    assert [(s.kind, s.n_layers) for s in cfg.stages] == \
        [("attn_mlp", 1), ("attn_moe", 23)]
    assert (cfg.d_ff, cfg.moe.d_expert) == (4096, 512)
    bad = copy.deepcopy(CONF)
    bad["config"]["moe_intermediate_size"] = 1024
    with pytest.raises(ValueError):
        c.family.arch_config(two_stage_cell(bad))


def test_served_weights_are_the_references():
    c = two_stage_cell()
    cfg = c.family.arch_config(c, tiny=True)
    dm = c.family.tiny_dims(c, cfg)
    p = served_params(dm, SEED)
    assert p["stage0"]["mlp"]["w_gate"].shape == (1, 64, 128)
    assert p["stage1"]["moe"]["w_gate"].shape == (2, 4, 64, 32)
    for i in range(dm.n_layers):
        stage, j = ("stage0", i) if i < dm.n_dense \
            else ("stage1", i - dm.n_dense)
        got = jax.tree_util.tree_map(lambda a: a[j], p[stage])
        ref = transformer.layer_f32(dm.layer(i), SEED, i)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(ref)):
            assert np.array_equal(np.asarray(a, np.float32), np.asarray(b))


def test_paced_run_is_correct(setup):
    assert setup.eng.paged and len(setup.cfg.stages) == 2
    s, res = H.paced(setup, seed=SEED)
    assert s.lowered == 0 and s.active and s.failed == 0
    sample = setup.cell.spec["check"]["requests"]
    assert res["requests"] == min(sample, len(s.finished)) >= 4
    assert res["missing_tokens"] == 0
    assert run.judge(setup.cell, res)[0]


@pytest.mark.parametrize("fault", ["altered_token", "unchanged_state",
                                   "half_batch"])
def test_a_broken_timed_path_is_not_correct(setup, fault):
    with pytest.MonkeyPatch.context() as mp:
        getattr(H, fault)(setup, mp)
        _, res = H.paced(setup, seed=SEED)
    assert not run.judge(setup.cell, res)[0]


def test_fp8_control_is_not_correct(setup):
    _, res = H.paced(setup, seed=SEED, control=True)
    control = {k: res[k.replace("logit_gap", "control_gap")]
               for k in LIMITS}
    assert not run.judge(setup.cell, dict(res, **control))[0]


def test_readers_count_with_the_family():
    """The MFU readers take the family's counts; the transformer kernel
    readers find nothing they count and say so."""
    c = two_stage_cell()
    mods = [xplane.Module("jit_decode(1)", 110, 150),
            xplane.Module("jit_prefill(2)", 210, 230),
            xplane.Module("jit_extend(3)", 240, 280)]
    host = [xplane.Span("execute 3", 105, 160),
            xplane.Span("execute 4", 200, 290)]
    records = {3: {"decode": [64, 65], "chunks": []},
               4: {"decode": [], "chunks": [(0, 16), (32, 8)]}}
    tr = xplane.Trace([xplane.Device("/device:TPU:0", mods)], host)
    ctx = xplane.Context(tr, 100, 400, xplane.iterations(tr, records, 100,
                                                         400),
                         c.family, c.dims,
                         {"bf16_flops": 1e9, "hbm_bytes_per_s": 1e6}, 4)
    assert run.load_reader("decode_mfu")(ctx) == pytest.approx(
        100 * decode_step_flops(c.dims, [64, 65]) / (40e-9 * 1e9))
    assert run.load_reader("prefill_mfu")(ctx) == pytest.approx(
        100 * (prefill_chunk_flops(c.dims, 0, 16)
               + prefill_chunk_flops(c.dims, 32, 8)) / (60e-9 * 1e9))
    for name in ("paged_attn_roofline.decode", "prefill_attn_roofline",
                 "moe_gmm_roofline"):
        value, note = run.load_reader(name)(ctx)
        assert value is None and "two_stage" in note


GENERIC = ["cell.py", "run.py", "check.py", "xplane.py",
           "metrics/decode_mfu.py", "metrics/prefill_mfu.py"]
PUBLISHED = ("hidden_size", "num_attention_heads", "num_key_value_heads",
             "head_dim", "intermediate_size", "num_local_experts",
             "n_routed_experts", "num_experts_per_tok", "vocab_size",
             "num_hidden_layers")


@pytest.mark.parametrize("name", GENERIC)
def test_the_generic_harness_knows_no_family(name):
    """What every family goes through reads no published key and imports
    no family's shapes: it asks the cell's family."""
    text = (HERE.parent / name).read_text()
    assert not [k for k in PUBLISHED if k in text]
    assert "families.transformer" not in text and "Dims" not in text
