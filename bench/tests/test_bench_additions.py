"""A configuration, a cell and a per-layer metric enter the benchmark as
new files plus entries, and the benchmark's own tests pass unedited.

On a copy of ``BENCHMARK.json`` and ``bench/``: Granite's configuration
under a new name, a cell of it under the offline mix (Granite-offline's
cell file and readings), and a metric whose reader finds nothing to read,
listed for the new cell alone.  The new cell's name joins the lists of the
metrics that read every cell, and no other entry or file changes.  The
tests that read cells and metrics from ``BENCHMARK.json`` then collect the
new ones and pass."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
OLD_CONFIG = "granite-moe-3b-a800m"
OLD_CELL = f"{OLD_CONFIG}.offline"
CONFIG = "added-moe"
CELL = f"{CONFIG}.offline"
METRIC = "added_kernel_roofline"
READER = '''"""A kernel that no recorded trace holds: nothing to read."""


def read(ctx):
    return None, "no calls of the added kernel"
'''
#: the benchmark's tests that read cells and metrics from BENCHMARK.json,
#: without the paced runs
TESTS = ["bench/tests/test_bench_identity.py",
         "bench/tests/test_bench_readings.py",
         "bench/tests/test_bench_harness.py::test_cells_are_data",
         "bench/tests/test_bench_harness.py::"
         "test_every_cell_is_read_and_checked",
         "bench/tests/test_bench_harness.py::"
         "test_every_listed_cell_is_a_cell"]


def every_cell() -> list:
    """The harness test's list of the metrics that read every cell."""
    spec = importlib.util.spec_from_file_location(
        "bench_harness_test", HERE / "test_bench_harness.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.EVERY_CELL


def add(root: Path, joins: list) -> None:
    """Add the configuration, cell and metric to the benchmark at ``root``;
    the new cell joins the ``workloads`` lists of the metrics ``joins``."""
    b = root / "bench"
    conf = json.loads((b / "configs" / f"{OLD_CONFIG}.json").read_text())
    (b / "configs" / f"{CONFIG}.json").write_text(
        json.dumps(dict(conf, name=CONFIG), indent=2))
    for d in ("cells", "readings"):
        shutil.copy(b / d / f"{OLD_CELL}.json", b / d / f"{CELL}.json")
    (b / "metrics" / f"{METRIC}.py").write_text(READER)
    path = root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    conf_entry = next(c for c in bench["configs"] if c["name"] == OLD_CONFIG)
    bench["configs"].append(dict(conf_entry, name=CONFIG,
                                 file=f"bench/configs/{CONFIG}.json"))
    cell_entry = next(w for w in bench["workloads"] if w["name"] == OLD_CELL)
    bench["workloads"].append(dict(cell_entry, name=CELL, config=CONFIG))
    for m in bench["per_layer"]:
        if m["name"] in joins:
            m["workloads"].append(CELL)
    bench["per_layer"].append({
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "kernels",
        "moves": "tpot_p90_ms", "workloads": [CELL]})
    path.write_text(json.dumps(bench, indent=1))


def run_tests(root: Path, tests: list) -> subprocess.CompletedProcess:
    # the outer run's pytest settings and workers are not the inner run's
    env = {k: v for k, v in os.environ.items() if not k.startswith("PYTEST")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    return subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-rA", "-p", "no:cacheprovider",
         *tests], cwd=root, env=env, capture_output=True, text=True,
        timeout=300)


def test_an_addition_of_new_files_and_entries_passes(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    add(tmp_path, every_cell())
    p = run_tests(tmp_path, TESTS)
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-2000:]
    passed = {line.split()[1] for line in p.stdout.splitlines()
              if line.startswith("PASSED ")}
    assert {
        f"bench/tests/test_bench_readings.py::"
        f"test_limits_lie_between_the_readings[{CELL}]",
        f"bench/tests/test_bench_harness.py::"
        f"test_every_cell_is_read_and_checked[{CELL}]",
        f"bench/tests/test_bench_harness.py::"
        f"test_every_listed_cell_is_a_cell[{METRIC}]",
    } <= passed
