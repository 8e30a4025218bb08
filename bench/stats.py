"""End-to-end metrics from the wall-clock stamps of one window.

Each is a percentile over every request or a rate over the whole window,
so a stall anywhere inside the window moves it.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, List


def percentile(values: Iterable[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    v = sorted(values)
    if not v:
        return math.nan
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def ttft_ms(requests, stamps: Dict[int, List[float]], t_open: float,
            t_close: float) -> List[float]:
    """Due time to first token of every request due in [t_open, t_close).
    A request with no first token by the close counts with the time it
    has waited so far."""
    out = []
    for r in requests:
        if not t_open <= r.arrival < t_close:
            continue
        ts = stamps.get(r.req_id)
        first = ts[0] if ts and ts[0] <= t_close else t_close
        out.append((first - r.arrival) * 1e3)
    return out


def tpot_ms(stamps: Dict[int, List[float]], t_open: float,
            t_close: float) -> List[float]:
    """Per request with at least two tokens in the window: (last - first
    in-window token time) / (tokens - 1)."""
    out = []
    for ts in stamps.values():
        w = [t for t in ts if t_open <= t <= t_close]
        if len(w) >= 2:
            out.append((w[-1] - w[0]) / (len(w) - 1) * 1e3)
    return out


def tokens_in_window(stamps: Dict[int, List[float]], t_open: float,
                     t_close: float) -> int:
    return sum(1 for ts in stamps.values() for t in ts
               if t_open <= t <= t_close)
