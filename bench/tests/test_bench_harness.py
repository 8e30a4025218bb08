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
import pytest

from bench import cell as cells
from bench import run

ROOT = Path(__file__).resolve().parents[2]
SEED = 2 ** 31 + 101
SECONDS = 2.0
CELLS = ["qwen3-8b-l18.chat", "granite-moe-3b-a800m.offline"]
#: limits of the check at the ``-tiny`` sizes, set as the cells' are, from
#: CPU readings over every request finished in a 4 s window, seeds 5..10 and
#: SEED.  qwen3: program widest / mean gap up to 0.043 / 2.9e-4, the fp8
#: control at least 0.666 / 0.0287.  granite, whose tied head over
#: ``logits_scaling`` 6 makes logits of about 0.01: program mean up to
#: 5.4e-6, control at least 2.5e-5; its widest gaps overlap (program up to
#: 2.4e-3, control from 1.8e-3), as on the chip, so only the mean is
#: compared.  A sample of twelve requests keeps the mean steady whichever
#: requests a loaded CPU finishes in the window.
TINY_LIMITS = {
    "qwen3-8b-l18.chat": {"max_logit_gap": 0.15, "mean_logit_gap": 0.004},
    "granite-moe-3b-a800m.offline": {"mean_logit_gap": 1.2e-5},
}
TINY_SAMPLE = 12
_CACHE_KEYS = ("jax_compilation_cache_dir",
               "jax_persistent_cache_min_compile_time_secs",
               "jax_persistent_cache_min_entry_size_bytes")


def tiny_cell(name: str):
    c = cells.load(name)
    c.spec, c.traffic = copy.deepcopy(c.spec), copy.deepcopy(c.traffic)
    c.spec["engine"] = {"max_batch": 4, "max_len": 256}
    c.spec["check"] = {"requests": TINY_SAMPLE, "limits": TINY_LIMITS[name]}
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
    assert res["requests"] == min(TINY_SAMPLE, len(s.finished)) >= 4
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
    decode = setup.eng._jit_decode
    mp.setattr(setup.eng, "_jit_decode",
               lambda params, cache, tokens: (decode(params, cache,
                                                     tokens)[0], cache))


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
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        c = cells.load(w["name"])
        cfg = cells.arch_config(c)
        assert cfg.kernels == "pallas" and cfg.param_dtype == "bfloat16"
        assert cfg.n_layers == c.dims.n_layers
    for m in bench["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
