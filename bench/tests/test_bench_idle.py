"""Device idle time put down to the program's host spans (``bench/idle.py``)
and the four ``device_idle_share.<phase>`` readers, on hand-built traces."""
import importlib.util
from pathlib import Path

import pytest

from bench import idle, xplane
from bench.xplane import Device, Module, Span, Trace

METRICS = Path(__file__).resolve().parents[1] / "metrics"
PHASES = ("prepare", "launch", "sample", "runtime")


def reader(name):
    spec = importlib.util.spec_from_file_location(name, METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def ctx_of(host, modules=(), lo=100, hi=400):
    tr = Trace([Device("/device:TPU:0", list(modules))],
               [Span("traced", lo, hi), *host])
    return xplane.Context(tr, lo, hi, [], None, {}, max_batch=4)


def one_iteration():
    """Device busy 150-200 (decode) and 260-270 (a slot helper); the host
    in the harness's own spans around the program's nested ones."""
    mods = [Module("jit_decode(1)", 150, 200),
            Module("jit_write_slot(2)", 260, 270)]
    host = [Span("runtime", 100, 400),
            Span("runtime.schedule", 100, 120),
            Span("execute 0", 120, 300),
            Span("backend.prepare", 120, 140),
            Span("backend.launch", 140, 160),
            Span("backend.sample", 160, 230),
            Span("backend.launch", 240, 250),
            Span("backend.prepare", 244, 248),      # a table push inside
            Span("backend.sync", 250, 300),
            Span("runtime.finish", 310, 380),
            Span("backend.release", 330, 350),
            Span("backend.prepare", 335, 340),      # its table push
            Span("wait", 390, 400)]
    return ctx_of(host, mods)


def test_a_gap_is_cut_across_two_spans():
    """The gap 100-150 runs through schedule, prepare and into launch."""
    ctx = one_iteration()
    got = idle.idle_by_span(ctx.trace, 100, 150)
    assert got == {"runtime.schedule": 20, "backend.prepare": 20,
                   "backend.launch": 10}


def test_nested_spans_file_to_the_innermost():
    ctx = one_iteration()
    got = idle.idle_by_span(ctx.trace, ctx.lo, ctx.hi)
    # 200-230 sample, 230-240 between spans inside execute, 240-250 launch
    # with its table push 244-248, 250-260 and 270-300 sync, 300-310 none,
    # 310-380 finish with release 330-350 and its push 335-340, 380-400 none
    assert got == pytest.approx({
        "runtime.schedule": 20, "backend.prepare": 20 + 4 + 5,
        "backend.launch": 10 + 6, "backend.sample": 30, "none": 10 + 10 + 20,
        "backend.sync": 40, "runtime.finish": 50, "backend.release": 15})
    assert sum(got.values()) == pytest.approx(
        ctx.window_ns * reader("device_idle_share")(ctx) / 100)


def test_readers_sum_to_the_idle_share():
    ctx = one_iteration()
    shares, notes = {}, []
    for ph in PHASES:
        shares[ph], note = reader(f"device_idle_share.{ph}")(ctx)
        notes.append(note)
    pct = 100 / 300
    assert shares == pytest.approx({"prepare": (29 + 15) * pct,
                                    "launch": 16 * pct, "sample": 30 * pct,
                                    "runtime": 70 * pct})
    assert len(set(notes)) == 1
    assert notes[0].startswith(f"unspanned {40 * pct!r} %, under sync "
                               f"{40 * pct!r} %")
    total = sum(shares.values()) + (40 + 40) * pct
    assert total == pytest.approx(reader("device_idle_share")(ctx))


def test_harness_spans_are_not_program_spans():
    """``runtime``, ``execute <k>``, ``wait`` and ``traced`` are the
    harness's: idle under them alone files as ``none``."""
    host = [Span("runtime", 100, 200), Span("execute 7", 120, 180),
            Span("wait", 200, 400), Span("runtime.arrive", 390, 395)]
    ctx = ctx_of(host, [Module("jit_decode(1)", 150, 160)])
    assert [s.name for s in idle.program_spans(ctx.trace)] == \
        ["runtime.arrive"]
    assert idle.idle_by_span(ctx.trace, ctx.lo, ctx.hi) == \
        {"none": 285, "runtime.arrive": 5}
    value, note = reader("device_idle_share.runtime")(ctx)
    assert value == pytest.approx(100 * 5 / 300)
    assert reader("device_idle_share.launch")(ctx)[0] == 0.0


@pytest.mark.parametrize("phase", PHASES)
def test_none_without_program_spans(phase):
    """A trace of a program without spans (the harness's alone) reads
    nothing; one with spans but no idle time reads zero."""
    host = [Span("runtime", 100, 400), Span("execute 0", 120, 300)]
    assert reader(f"device_idle_share.{phase}")(
        ctx_of(host, [Module("jit_decode(1)", 150, 200)])) is None
    busy = ctx_of([Span("backend.launch", 100, 400)],
                  [Module("jit_decode(1)", 90, 410)])
    assert reader(f"device_idle_share.{phase}")(busy) == 0.0


@pytest.mark.parametrize("shift,passed", [(0, 1), (-15, 0), (160, 0)])
def test_clock_check(shift, passed):
    """The decode program must start on the device between its launch and
    the iteration's sync; a device plane shifted off the host's clock
    fails, and the readers' note says so."""
    ctx = one_iteration()
    for m in ctx.trace.devices[0].modules:
        m.start, m.end = m.start + shift, m.end + shift
    records = {0: {"decode": [5], "chunks": []}}
    ctx.iterations = xplane.iterations(ctx.trace, records, ctx.lo, ctx.hi)
    assert idle.clock_check(ctx) == (passed, 1)
    _, note = reader("device_idle_share.sample")(ctx)
    assert note.endswith(f"inside its launch and sync in {passed} of 1 "
                         f"iterations")
