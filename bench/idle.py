"""Device idle time put down to the host phase that caused it.

The program marks the phases of its served path with host spans on the
profiler's clock (``repro.obs.span``: names starting ``backend.`` or
``runtime.``), on the same host line as the harness's own spans.  Every
interval of the traced window in which device 0 ran no program is cut at
the boundaries of those spans, and each piece is filed under the innermost
program span the host was in: the latest-starting span covering it, since
spans on one thread nest.  A piece no program span covers is filed under
``none``.  A trace of a program without such spans files nothing.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Tuple

from bench.xplane import Span, gaps

PREFIXES = ("backend.", "runtime.")
NONE = "none"


def program_spans(trace) -> List[Span]:
    """The program's own spans on the harness line, by start."""
    return sorted((s for s in trace.host if s.name.startswith(PREFIXES)),
                  key=lambda s: s.start)


def idle_by_span(trace, lo: float,
                 hi: float) -> Optional[Dict[str, float]]:
    """Device-0 idle ns in [lo, hi] by innermost program span name (and
    ``none``); None when the trace holds no program span."""
    spans = program_spans(trace)
    if not spans:
        return None
    starts = [s.start for s in spans]
    # reach[i]: the latest end among spans[:i + 1], so a backward search
    # for a covering span stops once nothing earlier can reach t
    reach, top = [], float("-inf")
    for s in spans:
        top = max(top, s.end)
        reach.append(top)
    cuts = sorted({t for s in spans for t in (s.start, s.end)})
    mods = trace.devices[0].modules if trace.devices else []
    out: Dict[str, float] = {}
    for a, b in gaps(mods, lo, hi):
        i, j = bisect.bisect_right(cuts, a), bisect.bisect_left(cuts, b)
        edges = [a, *cuts[i:j], b]
        for x, y in zip(edges, edges[1:]):
            name = _innermost(spans, starts, reach, 0.5 * (x + y))
            out[name] = out.get(name, 0.0) + (y - x)
    return out


def _innermost(spans, starts, reach, t: float) -> str:
    k = bisect.bisect_right(starts, t) - 1
    while k >= 0 and reach[k] > t:
        if spans[k].end > t:
            return spans[k].name
        k -= 1
    return NONE


def clock_check(ctx) -> Tuple[int, int]:
    """(passed, checked) over the iterations with decode rows: passed when
    exactly one ``decode`` program starts on device 0 between the start of
    the iteration's first ``backend.launch`` (the decode's, which comes
    first) and the end of its ``backend.sync``.  The host cannot dispatch
    a program later than the device starts it, nor pass the sync before
    it ran, so a failure means the device planes and the host line are
    not on one clock, and the attribution is not to be read."""
    mods = ctx.trace.devices[0].modules if ctx.trace.devices else []
    dec = sorted(m.start for m in mods if m.kind == "decode")
    spans = program_spans(ctx.trace)
    starts = [s.start for s in spans]
    passed = checked = 0
    for it in ctx.iterations:
        if not it.decode:
            continue
        i = bisect.bisect_left(starts, it.span.start)
        j = bisect.bisect_right(starts, it.span.end)
        inner = [s for s in spans[i:j] if s.end <= it.span.end]
        launch = [s for s in inner if s.name == "backend.launch"]
        sync = [s for s in inner if s.name == "backend.sync"]
        if not launch or not sync:
            continue
        checked += 1
        passed += bisect.bisect_right(dec, sync[-1].end) \
            - bisect.bisect_left(dec, launch[0].start) == 1
    return passed, checked


def share(ctx, names) -> object:
    """What a ``device_idle_share.<phase>`` reader returns: the share (%)
    of the traced window in which device 0 was idle under a span named in
    ``names`` (or, if ``names`` is a prefix string, starting with it),
    with a note of the unspanned and ``backend.sync`` shares, the whole
    idle share and the clock check where there is idle time; None when
    the trace holds no program span."""
    idle = idle_by_span(ctx.trace, ctx.lo, ctx.hi)
    if idle is None:
        return None
    if not idle:
        return 0.0
    if isinstance(names, str):
        ns = sum(v for k, v in idle.items() if k.startswith(names))
    else:
        ns = sum(idle.get(k, 0.0) for k in names)
    pct = 100.0 / ctx.window_ns
    passed, checked = clock_check(ctx)
    note = (f"unspanned {idle.get(NONE, 0.0) * pct!r} %, under sync "
            f"{idle.get('backend.sync', 0.0) * pct!r} %, of idle "
            f"{sum(idle.values()) * pct!r} %; decode on the device inside "
            f"its launch and sync in {passed} of {checked} iterations")
    return ns * pct, note
