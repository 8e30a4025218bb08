"""The transformer family behind ``bench/families`` computes what the
harness computed before it had families: served weights, the program's
config, model FLOP counts and every per-layer reading on the recorded
traces equal values recorded from that harness, exactly.

The pins cover the cells and readers recorded here.  A later cell, metric
or reader enters as new files plus entries and is pinned by tests of its
own; ``test_bench_additions.py`` shows that such an addition leaves these
tests passing."""
import gzip
import hashlib
import importlib.util
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import cell as cells
from bench import xplane
from bench.peaks import peaks_for

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data"
SEED = 2 ** 33 + 5
CONTEXTS = [1, 63, 64, 65, 300, 1023]
CHUNKS = [(0, 512), (512, 100), (37, 1), (0, 161)]

RECORDED = {
    "qwen3-8b-l18.chat": {
        "arch_config": "ArchConfig(name='qwen3-8b', family='dense', n_layers=18, d_model=4096, n_heads=32, n_kv_heads=8, d_ff=12288, vocab=151936, d_head=128, stages=(Stage(kind='attn_mlp', n_layers=18, local_global_period=0),), qkv_bias=False, qk_norm=True, rope_theta=1000000.0, sliding_window=0, mlp_gated=True, moe=None, ssm=None, tie_embeddings=False, n_codebooks=0, embed_inputs=True, norm_eps=1e-06, subquadratic=False, param_dtype='bfloat16', compute_dtype='bfloat16', kernels='pallas')",  # noqa: E501
        "tiny_arch_config": "ArchConfig(name='qwen3-8b-tiny', family='dense', n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab=256, d_head=16, stages=(Stage(kind='attn_mlp', n_layers=2, local_global_period=0),), qkv_bias=False, qk_norm=True, rope_theta=1000000.0, sliding_window=0, mlp_gated=True, moe=None, ssm=None, tie_embeddings=False, n_codebooks=0, embed_inputs=True, norm_eps=1e-06, subquadratic=False, param_dtype='bfloat16', compute_dtype='bfloat16', kernels='pallas')",  # noqa: E501
        "served_tiny": "47385d8ca1229b17182fd897c5673696b511a9b00b733ece08119624a777294a",  # noqa: E501
        "dims": "Dims(n_layers=18, d=4096, heads=32, kv_heads=8, d_head=128, d_ff=12288, vocab=151936, eps=1e-06, rope_theta=1000000.0, qk_norm=True, experts=0, top_k=0, embedding_multiplier=1.0, attention_multiplier=None, residual_multiplier=1.0, logits_scaling=1.0, tied_head=False)",  # noqa: E501
        "decode_step_flops": 49589649408,
        "prefill_chunk_flops": [3596207783936, 712410202112, 8201633792,
                                1123359162368],
    },
    "granite-moe-3b-a800m.offline": {
        "arch_config": "ArchConfig(name='granite-moe-3b-a800m', family='moe', n_layers=32, d_model=1536, n_heads=24, n_kv_heads=8, d_ff=512, vocab=49155, d_head=64, stages=(Stage(kind='attn_moe', n_layers=32, local_global_period=0),), qkv_bias=False, qk_norm=False, rope_theta=10000.0, sliding_window=0, mlp_gated=True, moe=MoECfg(n_experts=40, top_k=8, d_expert=512, capacity_factor=5.0, n_shared_experts=0), ssm=None, tie_embeddings=False, n_codebooks=0, embed_inputs=True, norm_eps=1e-06, subquadratic=False, param_dtype='bfloat16', compute_dtype='bfloat16', kernels='pallas')",  # noqa: E501
        "tiny_arch_config": "ArchConfig(name='granite-moe-3b-a800m-tiny', family='moe', n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab=256, d_head=16, stages=(Stage(kind='attn_moe', n_layers=2, local_global_period=0),), qkv_bias=False, qk_norm=False, rope_theta=10000.0, sliding_window=0, mlp_gated=True, moe=MoECfg(n_experts=4, top_k=2, d_expert=32, capacity_factor=5.0, n_shared_experts=0), ssm=None, tie_embeddings=False, n_codebooks=0, embed_inputs=True, norm_eps=1e-06, subquadratic=False, param_dtype='bfloat16', compute_dtype='bfloat16', kernels='pallas')",  # noqa: E501
        "served_tiny": "47408efff06afe1a578ef7607232036c8a658c7e67e539650a5b36d64d38508a",  # noqa: E501
        "dims": "Dims(n_layers=32, d=1536, heads=24, kv_heads=8, d_head=64, d_ff=512, vocab=49155, eps=1e-06, rope_theta=10000.0, qk_norm=False, experts=40, top_k=8, embedding_multiplier=12.0, attention_multiplier=0.015625, residual_multiplier=0.22, logits_scaling=6.0, tied_head=True)",  # noqa: E501
        "decode_step_flops": 10891352064,
        "prefill_chunk_flops": [852618126336, 172664693760, 1773020160,
                                262656697344],
    },
}
CELLS = list(RECORDED)


def digest(tree) -> str:
    """sha256 over every leaf's path, dtype, shape and bytes."""
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(leaves,
                             key=lambda kv: jax.tree_util.keystr(kv[0])):
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}"
                 .encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("name", CELLS)
def test_arch_config_and_dims(name):
    c, want = cells.load(name), RECORDED[name]
    assert repr(c.family.arch_config(c)) == want["arch_config"]
    assert repr(c.family.arch_config(c, tiny=True)) == \
        want["tiny_arch_config"]
    assert repr(c.dims) == want["dims"]


@pytest.mark.parametrize("name", CELLS)
def test_served_weights_at_tiny_sizes(name):
    c = cells.load(name)
    cfg = c.family.arch_config(c, tiny=True)
    dims = c.family.tiny_dims(c, cfg)
    assert digest(c.family.served_params(dims, SEED, cfg.param_dtype)) == \
        RECORDED[name]["served_tiny"]


@pytest.mark.parametrize("name", CELLS)
def test_model_flops(name):
    c, want = cells.load(name), RECORDED[name]
    assert c.family.decode_step_flops(c.dims, CONTEXTS) == \
        want["decode_step_flops"]
    assert [c.family.prefill_chunk_flops(c.dims, s, n)
            for s, n in CHUNKS] == want["prefill_chunk_flops"]


# ---- per-layer readers on the recorded TPU traces ----

TRACES = {"qwen3-8b-l18.chat": "qwen3-2layer-tpu",
          "granite-moe-3b-a800m.offline": "granite-moe-2layer-tpu"}
NOTE = ("unspanned {!r} %, under sync 0.0 %, of idle {!r} %; decode "
        "on the device inside its launch and sync in 0 of 0 iterations")
#: readings as (value, note or None); None: the reader found nothing
READINGS = {
    "qwen3-8b-l18.chat": {
        "host_gap_share": 22.278499682645602,
        "ttft_p90_ms.before_trace": 91.0,
        "batch_occupancy": 100.0,
        "decode_mfu": 2.532783253138304,
        "prefill_mfu": 17.58618919559799,
        "paged_attn_roofline.decode": (44.46026921703934, "bound: memory"),
        "prefill_attn_roofline": (9.637019663795073, "bound: memory"),
        "moe_gmm_roofline": None,
        "device_idle_share": 22.936481898994,
        "device_idle_share.prepare": 0.23300616161493773,
        "device_idle_share.launch": 0.23300616161493773,
        "device_idle_share.sample": 0.3256455280370101,
        "device_idle_share.runtime": 0.38834360269156293,
    },
    "granite-moe-3b-a800m.offline": {
        "host_gap_share": 46.34689541993624,
        "ttft_p90_ms.before_trace": 91.0,
        "batch_occupancy": 100.0,
        "decode_mfu": 0.7349431646837504,
        "prefill_mfu": 4.692326933006425,
        "paged_attn_roofline.decode": (22.1209662203451, "bound: memory"),
        "prefill_attn_roofline": (5.17185582182123, "bound: memory"),
        "moe_gmm_roofline": (1818.2221598177048, "bound: memory"),
        "device_idle_share": 45.698723184040176,
        "device_idle_share.prepare": 0.3487234281465838,
        "device_idle_share.launch": 0.3487234281465838,
        "device_idle_share.sample": 0.518842340510759,
        "device_idle_share.runtime": 0.5812057135776397,
    },
}
IDLE_NOTES = {"qwen3-8b-l18.chat": (21.756480445035557, 22.936481898994003),
              "granite-moe-3b-a800m.offline": (43.90122827365862,
                                               45.69872318404018)}
#: the metrics whose readers are pinned here
METRICS = list(READINGS["qwen3-8b-l18.chat"])
#: each of them with the cells it listed when its readings were recorded
LISTED = {
    "host_gap_share": CELLS,
    "ttft_p90_ms.before_trace": ["qwen3-8b-l18.chat"],
    "batch_occupancy": ["granite-moe-3b-a800m.offline"],
    "decode_mfu": CELLS,
    "prefill_mfu": ["qwen3-8b-l18.chat"],
    "paged_attn_roofline.decode": CELLS,
    "prefill_attn_roofline": ["qwen3-8b-l18.chat"],
    "moe_gmm_roofline": ["granite-moe-3b-a800m.offline"],
    "device_idle_share": CELLS,
    "device_idle_share.prepare": CELLS,
    "device_idle_share.launch": CELLS,
    "device_idle_share.sample": CELLS,
    "device_idle_share.runtime": CELLS,
}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, ROOT / "bench" / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture(scope="module")
def contexts(tmp_path_factory):
    """A context per recorded trace (two layers of the cell's widths): one
    iteration per step program, a prefill of 200 tokens, an extend of 100
    and decode steps of eight rows at made-up contexts, with host spans
    laid around each program.  The rows are not what the traced steps ran,
    so the shares pin arithmetic and are no readings of the chip (the
    expert share exceeds 100 % for that reason)."""
    import dataclasses
    out = {}
    for name, stem in TRACES.items():
        path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
        path.write_bytes(gzip.open(DATA / f"{stem}.xplane.pb.gz").read())
        tr = xplane.read(str(path))
        mods = tr.devices[0].modules
        its, step = [], 0
        for k, m in enumerate(mods):
            if m.kind == "prefill":
                chunks, rows = [(0, 200)], []
            elif m.kind == "extend":
                chunks, rows = [(200, 100)], []
            elif m.kind == "decode":
                chunks, rows = [], [65 + 37 * j + step for j in range(8)]
                step += 1
            else:
                continue
            its.append(xplane.Iteration(k, rows, chunks, xplane.Span(
                f"execute {k}", m.start, m.end), [m]))
        us = 1000.0
        for it in its:
            m = it.modules[0]
            tr.host += [
                xplane.Span("runtime.iteration", m.start - 60 * us,
                            m.end + 60 * us),
                xplane.Span(f"execute {it.k}", m.start - 40 * us,
                            m.end + 40 * us),
                xplane.Span("backend.prepare", m.start - 40 * us,
                            m.start - 20 * us),
                xplane.Span("backend.launch", m.start - 20 * us, m.start),
                xplane.Span("backend.sample", m.end, m.end + 40 * us)]
        c = cells.load(name)
        out[name] = xplane.Context(
            tr, mods[0].start, mods[-1].end, its, c.family,
            dataclasses.replace(c.dims, n_layers=2),
            peaks_for("TPU v5 lite"), 8, [float(v) for v in range(10, 110,
                                                                   10)])
    return out


@pytest.mark.parametrize("metric", METRICS)
@pytest.mark.parametrize("name", CELLS)
def test_reader_on_recorded_trace(contexts, name, metric):
    got = reader(metric)(contexts[name])
    want = READINGS[name][metric]
    if metric.startswith("device_idle_share."):
        want = (want, NOTE.format(*IDLE_NOTES[name]))
    assert got == want


def test_recorded_metrics_are_in_the_benchmark():
    """Every metric pinned here is still a per-layer metric of the
    benchmark and still lists the cells it listed when recorded."""
    per_layer = {m["name"]: m for m in
                 json.loads((ROOT / "BENCHMARK.json").read_text())
                 ["per_layer"]}
    assert all(list(READINGS[name]) == METRICS for name in CELLS)
    assert list(LISTED) == METRICS
    for metric, listed in LISTED.items():
        assert metric in per_layer, metric
        assert set(listed) <= set(per_layer[metric].get("workloads",
                                                        listed)), metric
