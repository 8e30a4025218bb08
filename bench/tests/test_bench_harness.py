"""One paced run of the harness end to end on the CPU, at the ``-tiny``
sizes of each configuration with interpreted kernels: pacing, the output
check, the faults it must catch, the fp8 control, and the refusals."""
import copy
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import cell as cells
from bench import run

ROOT = Path(__file__).resolve().parents[2]
SEED = 2 ** 31 + 101
SECONDS = 2.0
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
#: every one-chip cell of the benchmark; each brings the limits of its check
#: at the ``-tiny`` sizes, and why, under ``"tiny_check"`` in its cell file
CELLS = [w["name"] for w in BENCH["workloads"] if w["chips"] == 1]
#: the per-layer metrics that read every cell, whatever its family: a new
#: cell's name joins each of their ``workloads`` lists
EVERY_CELL = ["host_gap_share", "decode_mfu", "device_idle_share",
              "device_idle_share.prepare", "device_idle_share.launch",
              "device_idle_share.sample", "device_idle_share.runtime"]
_CACHE_KEYS = ("jax_compilation_cache_dir",
               "jax_persistent_cache_min_compile_time_secs",
               "jax_persistent_cache_min_entry_size_bytes")


def tiny_cell(name: str):
    c = cells.load(name)
    c.spec, c.traffic = copy.deepcopy(c.spec), copy.deepcopy(c.traffic)
    c.spec["engine"] = {"max_batch": 4, "max_len": 256}
    tiny = c.spec["tiny_check"]
    c.spec["check"] = {"requests": tiny["requests"], "limits": tiny["limits"]}
    c.traffic["prompt"]["max"] = 64
    c.traffic["output"].update(min=4, max=40)
    if "rate" in c.spec:
        c.spec.update(rate=8.0, preroll_s=0.5)
    else:
        c.spec["backlog_per_s"] = 4
    return c


@pytest.fixture(scope="module", params=CELLS)
def setup(request, tmp_path_factory):
    saved = {k: getattr(jax.config, k) for k in _CACHE_KEYS}
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("JAX_COMPILATION_CACHE_DIR",
                  str(tmp_path_factory.mktemp("jax_cache")))
        yield run.Setup(tiny_cell(request.param), SEED, require_tpu=False,
                        tiny=True)
    for k, v in saved.items():
        jax.config.update(k, v)


def paced(setup, seed=SEED, control=False):
    reqs = run.requests_for(setup.cell, seed, SECONDS, setup.dims.vocab)
    s = run.serve(setup, reqs, SECONDS)
    setup.free_params()
    try:
        return s, run.output_check(setup, s, seed, control=control)
    finally:
        setup.load_params(seed)


def test_paced_run_is_correct(setup):
    s, res = paced(setup)
    assert s.lowered == 0                       # nothing compiles in the window
    assert s.late and min(s.late.values()) >= 0.0
    due = {r.req_id: r.arrival for r in s.requests}
    assert all(ts[0] > due[rid] for rid, ts in s.stamps.items())
    assert all(ts == sorted(ts) for ts in s.stamps.values())
    assert s.active and s.failed == 0
    sample = setup.cell.spec["check"]["requests"]
    assert res["requests"] == min(sample, len(s.finished)) >= 4
    assert res["missing_tokens"] == 0
    assert run.judge(setup.cell, res)[0]
    e2e = run.end_to_end(s, SECONDS)
    assert e2e["tpot_p90_ms"] > 0 and e2e["tokens_per_s"] > 0


def altered_token(setup, mp):
    import repro.serve.sampler as sampler
    greedy = sampler.greedy
    mp.setattr(sampler, "greedy",
               lambda logits, vocab: (greedy(logits, vocab) + 1) % vocab)


def unchanged_state(setup, mp):
    """Decode returns its input cache unchanged: a copy taken before the
    call, which a decode that donates its cache leaves alive."""
    decode = setup.eng._jit_decode

    def broken(params, cache, tokens):
        kept = jax.tree_util.tree_map(jnp.copy, cache)
        return decode(params, cache, tokens)[0], kept
    mp.setattr(setup.eng, "_jit_decode", broken)


def half_batch(setup, mp):
    """Decode computes the first half of the slots; the rest get no
    logits of their own."""
    decode = setup.eng._jit_decode

    def broken(params, cache, tokens):
        logits, cache = decode(params, cache, tokens)
        return logits.at[logits.shape[0] // 2:].set(0.0), cache
    mp.setattr(setup.eng, "_jit_decode", broken)


@pytest.mark.parametrize("fault", [altered_token, unchanged_state,
                                   half_batch])
def test_a_broken_timed_path_is_not_correct(setup, fault):
    with pytest.MonkeyPatch.context() as mp:
        fault(setup, mp)
        _, res = paced(setup)
    correct, checked = run.judge(setup.cell, res)
    assert not correct
    over = {k for k, v in checked.items() if v["value"] > v["limit"]}
    assert over and over >= {"max_logit_gap"} & set(checked)


def test_fp8_control_is_not_correct(setup):
    _, res = paced(setup, control=True)
    assert run.judge(setup.cell, res)[0]
    control = {k: res[k.replace("logit_gap", "control_gap")]
               for k in setup.cell.spec["check"]["limits"]}
    assert not run.judge(setup.cell, dict(res, **control))[0]


def test_refuses_to_report_off_the_chip(capsys):
    saved = {k: getattr(jax.config, k) for k in _CACHE_KEYS}
    try:
        with pytest.raises(SystemExit) as exit_:
            run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
    assert "TPU" in str(exit_.value.code)
    assert "{" not in capsys.readouterr().out


def test_needs_the_program_beside_it(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1"],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_cells_are_data():
    for w in BENCH["workloads"]:
        c = cells.load(w["name"])
        family = c.conf.get("family", "transformer")
        assert (ROOT / "bench" / "families" / f"{family}.py").exists()
        assert c.family.__name__ == f"bench.families.{family}"
        assert (ROOT / "bench" / "reference"
                / f"{c.conf['reference']}.py").exists()
        cfg = c.family.arch_config(c)
        assert cfg.kernels == "pallas" and cfg.param_dtype == "bfloat16"
        assert cfg.n_layers == c.dims.n_layers
    for m in BENCH["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_is_read_and_checked(name):
    """Each metric that reads every cell lists this one, and a one-chip
    cell brings the limits of its check at the ``-tiny`` sizes."""
    listed = {m["name"]: m.get("workloads", [name])
              for m in BENCH["per_layer"]}
    assert [m for m in EVERY_CELL if name not in listed[m]] == []
    if name in CELLS:
        tiny = cells.load(name).spec["tiny_check"]
        assert tiny["requests"] > 0 and tiny["limits"] and tiny["why"]


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_every_listed_cell_is_a_cell(metric):
    assert set(metric.get("workloads", [])) <= \
        {w["name"] for w in BENCH["workloads"]}
