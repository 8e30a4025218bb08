"""The program's host spans, read back from a profiler trace of the served
path: a ``-tiny`` engine on the Pallas paged path, through the harness's
``TimedBackend`` and the runtime, paced by the wall clock with the
harness's annotations on, as a ``--trace 1`` run takes its window."""
import time

import jax
import pytest

from bench import cell as cells
from bench import idle, run, xplane
from bench.driver import advance
from bench.generator import Request

SPANS = ("backend.prepare", "backend.launch", "backend.sample",
         "backend.sync", "backend.release", "runtime.schedule",
         "runtime.finish", "runtime.arrive")


def serve_once(eng, sched, reqs, annotate=False):
    t0 = time.perf_counter()

    def clock():
        return time.perf_counter() - t0
    rt, tb = run.build_runtime(eng, sched, clock)
    rt.submit_workload(reqs)
    tb.annotate = annotate
    while len(rt.finished) < len(reqs) and clock() < 120:
        advance(rt, clock, clock() + 0.05, annotate=annotate)
    tb.annotate = False
    assert len(rt.finished) == len(reqs)
    tb.inner.reset()


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    from repro.core.config import SchedulerCfg
    from repro.serve.engine import ServingEngine
    cell = cells.load("qwen3-8b-l18.chat")
    cfg = cell.family.arch_config(cell, tiny=True)
    eng = ServingEngine(cfg, max_batch=2, max_len=128, name="e0")
    assert eng.paged
    sched = SchedulerCfg(max_batch_size=2, max_batch_tokens=32,
                         chunked_prefill=True, prefill_chunk=16)
    # 40-token prompts prefill in three chunks (prefill, then two extends
    # through the slot view and write-back); arrivals land mid-run
    reqs = [Request(i, 0.05 * i, [1 + i] * 40, 3) for i in range(3)]
    serve_once(eng, sched, reqs)              # compiles every program
    out = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(out), profiler_options=opts)
    with jax.profiler.TraceAnnotation("traced"):
        serve_once(eng, sched, reqs, annotate=True)
    jax.profiler.stop_trace()
    return xplane.read(str(next(out.rglob("*.xplane.pb"))))


def inside(s, outer):
    return outer.start <= s.start and s.end <= outer.end


def test_every_span_is_on_the_harness_line(traced):
    names = {s.name for s in idle.program_spans(traced)}
    assert names == set(SPANS)
    assert xplane.annotation(traced, "traced") is not None


def test_backend_spans_lie_inside_execute(traced):
    """Every ``backend.*`` span lies inside an ``execute <k>`` span, but
    for a finished request's release (and the table push nested in it),
    which the runtime's bookkeeping runs after the iteration returned."""
    executes = [s for s in traced.host if s.name.startswith("execute ")]
    finishes = [s for s in traced.host if s.name == "runtime.finish"]
    releases = [s for s in traced.host if s.name == "backend.release"]
    assert executes and finishes and releases
    for s in traced.host:
        if not s.name.startswith("backend."):
            continue
        if any(inside(s, e) for e in executes):
            continue
        assert any(inside(s, r) for r in releases) or (
            s.name == "backend.release"
            and any(inside(s, f) for f in finishes)), s


def test_finish_closes_before_the_next_launch(traced):
    finishes = [s for s in traced.host if s.name == "runtime.finish"]
    launches = [s for s in traced.host if s.name == "backend.launch"]
    assert launches
    assert not any(inside(la, f) for f in finishes for la in launches)
