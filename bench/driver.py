"""Drive the serving runtime against the wall clock.

The runtime keeps a virtual clock that moves only by what its backend
reports, so host time between iterations would never count.  Here the
backend is wrapped: ``TimedBackend.execute`` stamps the wall clock around
``JaxBackend.execute`` and reports the wall time at which the iteration
ended as its completion time, so the virtual clock is the wall clock at
every iteration boundary and never runs ahead of it.  ``advance`` releases
events (arrivals, iteration completions) only once the wall clock has
reached them, so no request is served before it is due.

End-to-end metrics come from the stamps: the wall time at which each
emitted token's iteration returned to the host.
"""
from __future__ import annotations

import contextlib
import time
from typing import Callable, Dict, List, Optional


class TimedBackend:
    """A ``JaxBackend`` with wall-clock stamps around ``execute``."""

    def __init__(self, inner, clock: Callable[[], float]):
        self.inner = inner
        self.clock = clock
        self.stamps: Dict[int, List[float]] = {}   # req_id -> token times
        self.chunks: Dict[int, int] = {}           # req_id -> prefill calls
        self.records: Dict[int, dict] = {}         # iteration -> work
        self.exec_s = []                           # (t0, t1) per iteration
        self.k = 0
        self.annotate = False
        self.on_iteration: Optional[Callable[[int, List[int]], None]] = None

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def execute(self, work, now: float) -> float:
        out = self.inner.out_tokens
        before = {w.request.req_id: len(out.get(w.request.req_id, ()))
                  for w in work}
        # context each decode row attends over (its pending token included)
        decode = [w.request.prompt_len + w.request.generated
                  for w in work if w.phase == "decode"]
        chunks = [(w.request.cached_prefix + w.request.prefill_done_tokens,
                   w.tokens) for w in work if w.phase == "prefill"]
        for w in work:
            if w.phase == "prefill":
                rid = w.request.req_id
                self.chunks[rid] = self.chunks.get(rid, 0) + 1
        k = self.k
        self.k += 1
        span = _annotation(f"execute {k}") if self.annotate \
            else contextlib.nullcontext()
        t0 = self.clock()
        with span:
            self.inner.execute(work, now)
        t1 = self.clock()
        for rid, n0 in before.items():
            n1 = len(out.get(rid, ()))
            if n1 > n0:
                self.stamps.setdefault(rid, []).extend([t1] * (n1 - n0))
        self.records[k] = {"decode": decode, "chunks": chunks}
        self.exec_s.append((t0, t1))
        if self.on_iteration is not None:
            self.on_iteration(k, decode)
        # completion at the wall time the work returned to the host
        return max(t1 - now, 0.0)


def _annotation(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def _next_event(queue) -> float:
    times = [e.time for e in queue._heap if not e.cancelled]
    return min(times) if times else float("inf")


def advance(runtime, clock: Callable[[], float], t_stop: float,
            stop: Callable[[], bool] = lambda: False,
            annotate: bool = False) -> float:
    """Run ``runtime`` paced by ``clock`` until ``t_stop`` or ``stop()``.
    While an iteration is in flight the loop runs the next one as soon as
    the last returns; while the instance is idle it sleeps until the next
    event is due."""
    inst = next(iter(runtime.instances.values()))
    q = runtime.queue

    def span(name):
        return _annotation(name) if annotate else contextlib.nullcontext()
    while True:
        now = clock()
        if now >= t_stop or stop():
            return now
        with span("runtime"):
            q.run(until=now)
        if not inst.busy:
            wait = min(_next_event(q), t_stop) - clock()
            if wait > 0:
                with span("wait"):
                    time.sleep(wait)
