"""Find a cell's files by name and build the served model's configuration.

``BENCHMARK.json`` names the cell's configuration, traffic mix and chips;
``cells/<cell>.json`` holds what belongs to the pair: engine sizes,
scheduler budget, arrival rate, how the window opens, and the limits of
the output check.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

from bench.dims import Dims, dims_of, load_config

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: dict          # configs/<config>.json
    traffic: dict       # traffic/<mix>.json
    spec: dict          # cells/<cell>.json
    dims: Dims

    @property
    def max_batch(self) -> int:
        return self.spec["engine"]["max_batch"]

    @property
    def max_len(self) -> int:
        return self.spec["engine"]["max_len"]


def load(name: str) -> Cell:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    conf = load_config(entry["config"])
    traffic = json.loads(
        (HERE / "traffic" / f"{entry['traffic']}.json").read_text())
    spec = json.loads((HERE / "cells" / f"{name}.json").read_text())
    return Cell(name, entry["chips"], conf, traffic, spec, dims_of(conf))


#: published key -> ArchConfig field, checked after the build
_WIDTHS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
           "num_key_value_heads": "n_kv_heads", "vocab_size": "vocab",
           "num_hidden_layers": "n_layers"}


def arch_config(cell: Cell, tiny: bool = False):
    """The program's ``ArchConfig`` for the cell: the registry entry named
    by the configuration's ``arch``, cut to its layer count, with the
    serving settings of its ``serve`` block.  Every width is checked
    against the configuration's file, so the program serves what the
    reference computes.  Scalars and a tied head, which the program's
    layer lacks, travel in the served weights (``weights.py``)."""
    from repro.configs import get_config
    from repro.configs.base import simple_stages
    serve = cell.conf["serve"]
    base = get_config(cell.conf["arch"] + ("-tiny" if tiny else ""))
    moe = base.moe
    if moe is not None:
        moe = dataclasses.replace(moe, capacity_factor=serve["capacity_factor"])
    kind = base.stages[0].kind
    n_layers = base.n_layers if tiny else cell.dims.n_layers
    cfg = dataclasses.replace(
        base, n_layers=n_layers, stages=simple_stages(kind, n_layers),
        param_dtype=serve["param_dtype"], kernels=serve["kernels"],
        norm_eps=cell.dims.eps, moe=moe)
    if tiny:
        return cfg
    c, want = cell.conf["config"], {}
    for key, field in _WIDTHS.items():
        want[field] = c[key]
    want["d_head"] = cell.dims.d_head
    want["rope_theta"] = cell.dims.rope_theta
    want["qk_norm"] = cell.dims.qk_norm
    if cell.dims.moe:
        got = {"n_experts": moe.n_experts, "top_k": moe.top_k,
               "d_expert": moe.d_expert}
        exp = {"n_experts": cell.dims.experts, "top_k": cell.dims.top_k,
               "d_expert": cell.dims.d_ff}
    else:
        got, exp = {"d_ff": cfg.d_ff}, {"d_ff": cell.dims.d_ff}
    got.update({f: getattr(cfg, f) for f in want})
    exp.update(want)
    bad = {k: (got[k], exp[k]) for k in exp if got[k] != exp[k]}
    if bad:
        raise ValueError(f"{cell.conf['name']}: the program's config differs "
                         f"from the file (program, file): {bad}")
    return cfg


def tiny_dims(cell: Cell, cfg) -> Dims:
    """Dims of a ``-tiny`` program config, for CPU tests of the harness."""
    return dataclasses.replace(
        cell.dims, n_layers=cfg.n_layers, d=cfg.d_model, heads=cfg.n_heads,
        kv_heads=cfg.n_kv_heads, d_head=cfg.d_head, vocab=cfg.vocab,
        d_ff=cfg.moe.d_expert if cfg.moe else cfg.d_ff,
        experts=cfg.moe.n_experts if cfg.moe else 0,
        top_k=cfg.moe.top_k if cfg.moe else 0)
