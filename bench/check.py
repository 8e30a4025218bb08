"""Decide ``correct``: served tokens against the plain reference.

After the window closes, a sample of finished requests drawn from the
seed (the one with the most served tokens always in it, and one whose
prompt was prefilled in several chunks when there is one) is run through
the configuration's reference, teacher-forced over prompt + served tokens
at the engine's ``max_len``.  At each served position the gap between the
reference's best logit and the served token's logit is read.  Greedy
serving of a correct model keeps every gap at rounding size; a wrong
token, mask, page or expert shows as a gap of the logits' own size.

Numbers the cell can compare (each with its limit from
``cells/<cell>.json``): ``max_logit_gap`` (widest gap over the sampled
positions), ``mean_logit_gap`` (mean over them) and ``missing_tokens``
(served minus requested tokens of the sample, exact).
"""
from __future__ import annotations

import importlib
from typing import List

import numpy as np


def sample(finished: List[dict], n: int, seed: int) -> List[dict]:
    """``finished``: dicts with ``req_id``, ``prompt``, ``served``,
    ``output_len`` and ``chunks``."""
    if not finished:
        return []
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    pool = sorted(finished, key=lambda r: r["req_id"])
    longest = max(pool, key=lambda r: (len(r["served"]), -r["req_id"]))
    pick = [longest]
    chunked = [r for r in pool if r["chunks"] > 1 and r is not longest]
    if chunked:
        pick.append(chunked[int(rng.integers(len(chunked)))])
    rest = [r for r in pool if all(r is not p for p in pick)]
    for i in rng.permutation(len(rest))[:max(n - len(pick), 0)]:
        pick.append(rest[int(i)])
    return pick[:n]


def arrays(picked: List[dict], rows: int, max_len: int):
    """Teacher-forcing inputs: row i holds prompt + served[:-1]; the
    target at position len(prompt) - 1 + j is served[j]."""
    tokens = np.zeros((rows, max_len), np.int32)
    lengths = np.ones((rows,), np.int32)
    targets = np.full((rows, max_len), -1, np.int32)
    for i, r in enumerate(picked):
        seq = list(r["prompt"]) + list(r["served"][:-1])
        if len(seq) > max_len:
            raise ValueError(f"request {r['req_id']}: {len(seq)} positions "
                             f"> max_len {max_len}")
        tokens[i, :len(seq)] = seq
        lengths[i] = len(seq)
        p0 = len(r["prompt"]) - 1
        targets[i, p0:p0 + len(r["served"])] = r["served"]
    return tokens, lengths, targets


def compare(conf: dict, dims, seed: int, picked: List[dict], rows: int,
            max_len: int, control: bool = False) -> dict:
    ref = importlib.import_module(f"bench.reference.{conf['reference']}")
    tokens, lengths, targets = arrays(picked, rows, max_len)
    out = ref.logit_gaps(dims, seed, tokens, lengths, targets,
                         control=control)
    res = {"positions": out["positions"],
           "missing_tokens": sum(abs(len(r["served"]) - r["output_len"])
                                 for r in picked)}
    res.update(gap_stats(out["program"], "logit_gap"))
    if control:
        res.update(gap_stats(out["control"], "control_gap"))
    return res


def gap_stats(gaps: np.ndarray, name: str) -> dict:
    """Widest and mean gap, and how many positions are not the reference's
    first choice; no position compared reads as an infinite gap."""
    if not len(gaps):
        return {f"max_{name}": float("inf"), f"mean_{name}": float("inf"),
                f"{name}_positions_off": 0}
    return {f"max_{name}": float(np.max(gaps)),
            f"mean_{name}": float(np.mean(gaps)),
            f"{name}_positions_off": int(np.count_nonzero(gaps > 0))}
