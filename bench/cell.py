"""Find a cell's files by name.

``BENCHMARK.json`` names the cell's configuration, traffic mix and chips;
``cells/<cell>.json`` holds what belongs to the pair: engine sizes,
scheduler budget, arrival rate, how the window opens, and the limits of
the output check.  ``configs/<config>.json`` names the model's family
(``families/<family>.py``), which reads its shapes and builds the
program's config.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

from bench.families import family_of

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: dict          # configs/<config>.json
    traffic: dict       # traffic/<mix>.json
    spec: dict          # cells/<cell>.json
    family: object = dataclasses.field(init=False)   # families/<family>.py
    dims: object = dataclasses.field(init=False)     # family.dims(conf)

    def __post_init__(self):
        self.family = family_of(self.conf)
        self.dims = self.family.dims(self.conf)

    @property
    def max_batch(self) -> int:
        return self.spec["engine"]["max_batch"]

    @property
    def max_len(self) -> int:
        return self.spec["engine"]["max_len"]


def load_config(name: str) -> dict:
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def load(name: str) -> Cell:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    traffic = json.loads(
        (HERE / "traffic" / f"{entry['traffic']}.json").read_text())
    spec = json.loads((HERE / "cells" / f"{name}.json").read_text())
    return Cell(name, entry["chips"], load_config(entry["config"]), traffic,
                spec)
