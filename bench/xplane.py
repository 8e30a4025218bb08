"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics read.

The trace is read with ``jax.profiler.ProfileData``.  On a TPU:

* plane ``/device:TPU:<n>``, line ``XLA Modules``: one event per program
  run on the device, named ``<jit name>(<fingerprint>)``;
* line ``XLA Ops``: one event per HLO op, named by its HLO text
  (``%paged_attention.9 = bf16[...] custom-call(...)``).  A Pallas kernel
  appears as a ``custom-call`` named after the jitted wrapper that calls it
  (``flash_attention``, ``paged_attention``, ``moe_gmm``); a ``while`` op
  spans its whole loop body and is left out of op totals;
* plane ``/host:CPU``, the line of the thread that holds the ``traced``
  span: the harness's ``TraceAnnotation`` spans (``traced``,
  ``execute <k>``, ``runtime``, ``wait``).

Device and host events share one clock (nanoseconds from the trace start).
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, List, Optional, Tuple

#: ops that contain other ops on the same line; their time is their body's
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Span:
    name: str
    start: float      # ns
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start

    @property
    def mid(self) -> float:
        return 0.5 * (self.start + self.end)


@dataclasses.dataclass
class Module(Span):
    ops: List[Span] = dataclasses.field(default_factory=list)

    @property
    def kind(self) -> str:
        """``jit_decode(123)`` -> ``decode``."""
        base = self.name.split("(", 1)[0]
        return base[4:] if base.startswith("jit_") else base


@dataclasses.dataclass
class Device:
    name: str
    modules: List[Module]


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    host: List[Span]          # annotations on the python thread


def op_base(hlo_text: str) -> str:
    """``%paged_attention.9 = bf16[..] custom-call(..)`` -> ``paged_attention``."""
    ident = hlo_text.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"(\.\d+)+$", "", ident)


def read(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            mods = sorted((Module(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in lines["XLA Modules"].events)
                          if "XLA Modules" in lines else [],
                          key=lambda m: m.start)
            starts = [m.start for m in mods]
            for e in lines["XLA Ops"].events if "XLA Ops" in lines else ():
                base = op_base(e.name)
                if base.startswith(CONTAINERS):
                    continue
                i = bisect.bisect_right(starts, e.start_ns) - 1
                if i >= 0 and e.start_ns < mods[i].end:
                    mods[i].ops.append(Span(base, e.start_ns,
                                            e.start_ns + e.duration_ns))
            devices.append(Device(plane.name, mods))
        elif plane.name == "/host:CPU":
            host.extend(_harness_line(plane.lines))
    return Trace(devices, host)


def _harness_line(lines) -> List[Span]:
    """Spans of the host thread that holds the harness's ``traced``
    annotation.  The line is named after the thread, which is named after
    the executable (``python``, ``python3``, ...), so it is found by what
    it holds."""
    spans = [[Span(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for e in line.events] for line in lines]
    for line_spans in spans:
        if any(s.name == "traced" for s in line_spans):
            return line_spans
    return [s for line, line_spans in zip(lines, spans)
            if line.name.startswith("python") for s in line_spans]


def union(spans, lo: float, hi: float) -> float:
    """Length of [lo, hi] covered by at least one span."""
    total, cur_s, cur_e = 0.0, None, None
    for s in sorted(spans, key=lambda x: x.start):
        a, b = max(s.start, lo), min(s.end, hi)
        if b <= a:
            continue
        if cur_e is None or a > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = a, b
        else:
            cur_e = max(cur_e, b)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(spans, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Intervals of [lo, hi] that no span covers."""
    out, t = [], lo
    for s in sorted(spans, key=lambda x: x.start):
        if s.end <= t:
            continue
        if s.start > t:
            out.append((t, min(s.start, hi)))
        t = max(t, s.end)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [g for g in out if g[1] > g[0]]


def annotation(trace: Trace, name: str) -> Optional[Span]:
    return next((s for s in trace.host if s.name == name), None)


@dataclasses.dataclass
class Iteration:
    """One ``execute`` call: what the harness scheduled, and the device
    programs that ran inside it (on device 0)."""
    k: int
    decode: List[int]                 # context length of each decode row
    chunks: List[Tuple[int, int]]     # (start, n) of each prefill chunk
    span: Span
    modules: List[Module]


def iterations(trace: Trace, records: Dict[int, dict],
               lo: float, hi: float) -> List[Iteration]:
    """Iterations whose ``execute <k>`` span lies inside [lo, hi], with
    device 0's modules attributed by their midpoint."""
    spans = sorted((s for s in trace.host if s.name.startswith("execute ")
                    and lo <= s.start and s.end <= hi), key=lambda s: s.start)
    mods = trace.devices[0].modules if trace.devices else []
    mids = [m.mid for m in mods]
    out = []
    for s in spans:
        k = int(s.name.split()[1])
        rec = records.get(k)
        if rec is None:
            continue
        i, j = bisect.bisect_left(mids, s.start), bisect.bisect_right(mids,
                                                                     s.end)
        out.append(Iteration(k, rec["decode"], rec["chunks"], s, mods[i:j]))
    return out


def host_state(trace: Trace, t: float) -> str:
    """What the python thread was doing at trace time ``t``."""
    inside = [s.name.split()[0] for s in trace.host if s.start <= t < s.end]
    for label in ("execute", "wait", "runtime"):
        if label in inside:
            return label
    return "other"


HOST_LABELS = {
    "execute": "host inside execute (dispatch, sampling, page table)",
    "runtime": "runtime loop outside execute (scheduler, router, bookkeeping)",
    "wait": "waiting for the next arrival",
    "other": "harness outside the runtime",
}


def breakdown(trace: Trace, lo: float, hi: float) -> dict:
    """Top device ops by time, and idle time by what the host was doing."""
    ops: Dict[str, float] = {}
    mods = trace.devices[0].modules if trace.devices else []
    for m in mods:
        if m.end <= lo or m.start >= hi:
            continue
        for op in m.ops:
            key = f"{m.kind}/{op.name}"
            ops[key] = ops.get(key, 0.0) + op.dur * 1e-9
    idle: Dict[str, float] = {}
    for a, b in gaps(mods, lo, hi):
        label = HOST_LABELS[host_state(trace, 0.5 * (a + b))]
        idle[label] = idle.get(label, 0.0) + (b - a) * 1e-9
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in
                          sorted(idle.items(), key=lambda kv: -kv[1])[:10]]}


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader gets: the reduced trace, the traced
    window [lo, hi] in trace ns, the iterations inside it, the cell, and
    the wall-clock readings of the window before the trace."""
    trace: Trace
    lo: float
    hi: float
    iterations: List[Iteration]
    dims: object              # bench.dims.Dims
    peaks: dict
    max_batch: int
    #: time to first token (ms) of each request due in the window before
    #: the trace began (the profiler slows the host)
    ttft_ms: List[float] = dataclasses.field(default_factory=list)

    @property
    def window_ns(self) -> float:
        return self.hi - self.lo


def kernel_calls(module: Module, kernel: str) -> List[Span]:
    return [op for op in module.ops if op.name == kernel]


def paired_chunks(it: Iteration):
    """(module, start, n) for each prefill chunk of the iteration, paired
    in dispatch order with the ``prefill``/``extend`` programs it ran;
    empty when the two do not line up."""
    mods = [m for m in it.modules if m.kind in ("prefill", "extend")]
    if len(mods) != len(it.chunks):
        return []
    out = []
    for m, (start, n) in zip(mods, it.chunks):
        if m.kind != ("prefill" if start == 0 else "extend"):
            return []
        out.append((m, start, n))
    return out


def decode_module(it: Iteration) -> Optional[Module]:
    mods = [m for m in it.modules if m.kind == "decode"]
    return mods[0] if len(mods) == 1 and it.decode else None
