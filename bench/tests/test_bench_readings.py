"""Each cell's committed limits against its chip readings
(``bench/readings/<cell>.json``): every sound run of the program passes,
and the control, the reference computed in fp8, fails on every seed."""
import json
from pathlib import Path

import pytest

from bench import cell as cells
from bench import run

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


def readings(name):
    return json.loads((ROOT / "bench" / "readings" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("name", CELLS)
def test_limits_lie_between_the_readings(name):
    c, r = cells.load(name), readings(name)
    assert len({x["seed"] for x in r["program"]}) >= 12
    assert len({x["seed"] for x in r["control"]}) >= 3
    for x in r["program"]:
        assert run.judge(c, dict(x, requests=6, missing_tokens=0))[0], x
    for x in r["control"]:
        assert not run.judge(c, dict(x, requests=6, missing_tokens=0))[0], x


@pytest.mark.parametrize("name", CELLS)
def test_each_limit_separates_its_two_readings(name):
    """A number compared has an upper reading at least three times its
    lower one, and its limit lies between them with room on both sides."""
    r = readings(name)
    for k, limit in cells.load(name).spec["check"]["limits"].items():
        lower = max(x[k] for x in r["program"])
        upper = min(x[k] for x in r["control"])
        assert upper >= 3 * lower, k
        assert 2 * lower <= limit <= upper / 2, k
