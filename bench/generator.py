"""Traffic generator: one general generator read by every traffic file.

A traffic file (``traffic/<mix>.json``) gives the shape of the mix:

    {"source": "...",
     "arrival": "poisson" | "all_at_start",
     "prompt": {"mean": 161, "sigma": 0.9, "min": 4, "max": 512},
     "output": {"mean": 338, "sigma": 0.9, "min": 4, "max": 511},
     "sizes_seed": 0}

Lengths are lognormal with the stated mean (``mu = ln(mean) - sigma**2/2``)
clipped to ``[min, max]``, and Poisson arrivals have exponential gaps:
the arithmetic of ``repro.workload.sharegpt.generate``, copied so that the
yardstick does not move when the program's generator does.

Every seed offers the same work.  The multiset of
(prompt, output) lengths and of arrival gaps comes from ``sizes_seed`` and
the request counts alone; the run's ``--seed`` only permutes them and draws
the token ids.  Poisson arrivals are laid out span by span: a span
``(t0, t1)`` holds exactly ``round(rate * (t1 - t0))`` requests, placed as
a Poisson process conditioned on that count (the normalised sums of one
more exponential gap than requests).  With the warm-up and the measured
window as two spans, every seed puts the same requests into the window,
and the spread between seeds is the system's, not the traffic's.  A
backlog due at once keeps one order for every seed: which requests a
window reaches depends on the order in which they are served.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass
class Request:
    req_id: int
    arrival: float               # seconds after the clock's origin
    prompt_tokens: List[int]
    output_len: int
    model: str = "default"


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    mu = np.log(spec["mean"]) - spec["sigma"] ** 2 / 2
    draw = rng.lognormal(mu, spec["sigma"], n)
    return np.clip(draw, spec["min"], spec["max"]).astype(np.int64)


def generate(traffic: dict, *, seed: int, vocab: int, n: int = 0,
             spans=(), rate: float = 0.0) -> List[Request]:
    """``arrival: all_at_start``: ``n`` requests, all due at 0.
    ``arrival: poisson``: Poisson arrivals at ``rate`` per second in each
    of ``spans``, ``round(rate * (t1 - t0))`` requests to a span."""
    sizes = np.random.default_rng(traffic.get("sizes_seed", 0))
    rng = np.random.default_rng(seed)
    kind = traffic["arrival"]
    if kind == "all_at_start":
        groups = [np.zeros(n)]
    elif kind == "poisson":
        if rate <= 0 or not spans:
            raise ValueError("poisson arrivals need a rate > 0 and spans")
        groups = []
        for t0, t1 in spans:
            k = int(round(rate * (t1 - t0)))
            gaps = rng.permutation(sizes.exponential(1.0, k + 1))
            groups.append(t0 + (t1 - t0) * np.cumsum(gaps)[:k] / gaps.sum())
    else:
        raise ValueError(f"unknown arrival process {kind!r}")
    out = []
    for arrivals in groups:
        k = len(arrivals)
        prompts = lengths(traffic["prompt"], k, sizes)
        outputs = lengths(traffic["output"], k, sizes)
        order = rng.permutation(k) if kind == "poisson" else range(k)
        for i, j in enumerate(order):
            out.append(Request(
                req_id=len(out), arrival=float(arrivals[i]),
                prompt_tokens=rng.integers(0, vocab, int(prompts[j])).tolist(),
                output_len=int(outputs[j])))
    return out
