"""Trace reduction: busy/idle union, attribution of device programs to the
harness's iterations, kernel times, and the metric readers on top."""
import importlib.util
from pathlib import Path

import pytest

from bench import xplane
from bench.dims import Dims
from bench.xplane import Module, Span, Trace, Device

METRICS = Path(__file__).resolve().parents[1] / "metrics"
D = Dims(n_layers=2, d=8, heads=4, kv_heads=2, d_head=2, d_ff=16, vocab=10,
         eps=1e-6, rope_theta=1e4, qk_norm=False)
PEAKS = {"bf16_flops": 1e9, "hbm_bytes_per_s": 1e6}


def reader(name):
    spec = importlib.util.spec_from_file_location(name, METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_op_base_strips_hlo_text():
    assert xplane.op_base("%paged_attention.9 = bf16[8,8] custom-call(s32[1])"
                          ) == "paged_attention"
    assert xplane.op_base("%fusion.12.clone = f32[2] fusion()") == \
        "fusion.12.clone"
    assert xplane.op_base("%copy-start.3 = (f32[2]) copy-start()") == \
        "copy-start"
    assert Module("jit_decode(123)", 0, 1).kind == "decode"


def test_harness_line_is_found_by_its_traced_span():
    """The host line is named after the thread (``python3/123`` when the
    harness runs as ``python3``): it is found by the span it holds."""
    from types import SimpleNamespace as NS

    def line(name, *events):
        return NS(name=name, events=[NS(name=e, start_ns=i, duration_ns=1)
                                     for i, e in enumerate(events)])
    lines = [line("python", "PjitFunction(decode)"),
             line("main/289", "tpu::System::Execute"),
             line("python3/77", "traced", "execute 0", "wait")]
    got = xplane._harness_line(lines)
    assert [s.name for s in got] == ["traced", "execute 0", "wait"]
    assert [s.name for s in xplane._harness_line(lines[:2])] == \
        ["PjitFunction(decode)"]


def test_traced_span_of_this_process_is_read_back(tmp_path):
    """A trace taken here, whatever this executable is called, gives back
    the harness's spans."""
    import jax
    import jax.numpy as jnp
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    f = jax.jit(lambda x: x @ x)
    x = jnp.ones((8, 8))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("traced"):
        with jax.profiler.TraceAnnotation("execute 0"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = xplane.read(str(next(tmp_path.rglob("*.xplane.pb"))))
    traced = xplane.annotation(tr, "traced")
    assert traced is not None
    (ex,) = [s for s in tr.host if s.name == "execute 0"]
    assert traced.start <= ex.start < ex.end <= traced.end


def test_union_and_gaps():
    spans = [Span("a", 0, 10), Span("b", 5, 15), Span("c", 20, 30),
             Span("d", 40, 50)]
    assert xplane.union(spans, 0, 100) == 35
    assert xplane.union(spans, 8, 25) == 12
    assert xplane.gaps(spans, 0, 45) == [(15, 20), (30, 40)]
    assert xplane.gaps([], 0, 5) == [(0, 5)]


def synthetic():
    """Two iterations: k=3 decodes 2 rows (one decode program with two
    paged-attention calls); k=4 prefills a chunk and extends another."""
    dec = Module("jit_decode(1)", 110, 150, [
        Span("paged_attention", 115, 120), Span("paged_attention", 125, 131),
        Span("fusion", 131, 150)])
    pre = Module("jit_prefill(2)", 210, 230, [
        Span("flash_attention", 212, 214), Span("flash_attention", 220, 222)])
    ext = Module("jit_extend(3)", 240, 280, [
        Span("paged_attention", 250, 260), Span("paged_attention", 262, 270)])
    small = Module("jit_impl(4)", 232, 234)
    host = [Span("traced", 100, 400), Span("runtime", 100, 300),
            Span("execute 3", 105, 160), Span("execute 4", 200, 290),
            Span("wait", 300, 400)]
    records = {3: {"decode": [64, 65], "chunks": []},
               4: {"decode": [], "chunks": [(0, 16), (32, 8)]}}
    return Trace([Device("/device:TPU:0", [dec, pre, small, ext])], host), \
        records


def context():
    tr, records = synthetic()
    its = xplane.iterations(tr, records, 100, 400)
    return xplane.Context(tr, 100, 400, its, D, PEAKS, max_batch=4)


def test_iterations_get_their_programs():
    ctx = context()
    assert [it.k for it in ctx.iterations] == [3, 4]
    assert [m.kind for m in ctx.iterations[0].modules] == ["decode"]
    assert [m.kind for m in ctx.iterations[1].modules] == \
        ["prefill", "impl", "extend"]
    pairs = xplane.paired_chunks(ctx.iterations[1])
    assert [(m.kind, s, n) for m, s, n in pairs] == \
        [("prefill", 0, 16), ("extend", 32, 8)]


def test_busy_idle_and_breakdown():
    ctx = context()
    busy = 40 + 20 + 2 + 40
    assert reader("device_idle_share")(ctx) == pytest.approx(
        100 * (1 - busy / 300))
    assert reader("host_gap_share")(ctx) == pytest.approx(
        100 * (1 - (55 + 90) / 300))
    assert reader("batch_occupancy")(ctx) == pytest.approx(50.0)
    bd = xplane.breakdown(ctx.trace, 100, 400)
    assert bd["device_ops"][0] == ["decode/fusion", pytest.approx(19e-9)]
    idle = dict(bd["idle_gaps"])
    assert idle[xplane.HOST_LABELS["wait"]] == pytest.approx(120e-9)
    # gaps at 100-110, 230-232 and 234-240 fall inside an execute span,
    # 150-210 between two of them in the runtime loop
    assert idle[xplane.HOST_LABELS["execute"]] == pytest.approx(18e-9)
    assert idle[xplane.HOST_LABELS["runtime"]] == pytest.approx(60e-9)
    assert sum(idle.values()) == pytest.approx((300 - busy) * 1e-9)


def test_mfu_and_rooflines_from_the_counts():
    from bench import costs
    ctx = context()
    assert reader("decode_mfu")(ctx) == pytest.approx(
        100 * costs.decode_step_flops(D, [64, 65]) / (40e-9 * 1e9))
    assert reader("prefill_mfu")(ctx) == pytest.approx(
        100 * (costs.prefill_chunk_flops(D, 0, 16)
               + costs.prefill_chunk_flops(D, 32, 8)) / (60e-9 * 1e9))
    t, bound = costs.least_time(*costs.paged_decode(D, [64, 65]), PEAKS)
    value, note = reader("paged_attn_roofline.decode")(ctx)
    assert value == pytest.approx(100 * 2 * t / 11e-9) and bound in note
    tf, _ = costs.least_time(*costs.flash_prefill(D, 16), PEAKS)
    te, _ = costs.least_time(*costs.paged_extend(D, 32, 8), PEAKS)
    value, _ = reader("prefill_attn_roofline")(ctx)
    assert value == pytest.approx(100 * 2 * (tf + te) / (4e-9 + 18e-9))
    assert reader("moe_gmm_roofline")(ctx) is None       # a dense model


def test_moe_gmm_roofline_reads_decode_calls_only():
    """Prefill and extend calls route too few rows for every expert to be
    read, so only the decode programs' calls count."""
    import dataclasses
    from bench import costs
    moe = dataclasses.replace(D, experts=4, top_k=2)
    dec = Module("jit_decode(1)", 110, 150, [
        Span("moe_gmm", 115, 120), Span("moe_gmm", 125, 133)])
    pre = Module("jit_prefill(2)", 210, 230, [Span("moe_gmm", 212, 213)])
    host = [Span("traced", 100, 400), Span("execute 3", 105, 160),
            Span("execute 4", 200, 290)]
    records = {3: {"decode": [64, 65], "chunks": []},
               4: {"decode": [], "chunks": [(0, 16)]}}
    tr = Trace([Device("/device:TPU:0", [dec, pre])], host)
    its = xplane.iterations(tr, records, 100, 400)
    ctx = xplane.Context(tr, 100, 400, its, moe, PEAKS, max_batch=4)
    t, bound = costs.least_time(*costs.moe_gmm(moe, 2), PEAKS)
    value, note = reader("moe_gmm_roofline")(ctx)
    assert value == pytest.approx(100 * 2 * t / 13e-9) and bound in note


def test_readers_find_nothing_in_an_empty_window():
    tr, _ = synthetic()
    ctx = xplane.Context(tr, 500, 600, [], D, PEAKS, max_batch=4)
    for name in ("decode_mfu", "prefill_mfu", "paged_attn_roofline.decode",
                 "prefill_attn_roofline", "batch_occupancy",
                 "ttft_p90_ms.before_trace"):
        assert reader(name)(ctx) is None


def test_ttft_tail_before_the_trace():
    tr, _ = synthetic()
    ctx = xplane.Context(tr, 100, 400, [], D, PEAKS, max_batch=4,
                         ttft_ms=[float(v) for v in range(10, 110, 10)])
    assert reader("ttft_p90_ms.before_trace")(ctx) == pytest.approx(91.0)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A real TPU v5e trace: Qwen3-8B widths cut to 2 layers, one prefill
    of 200 tokens (bucket 256), one extend of 100 after them (bucket 128),
    then 4 decode steps of an 8-slot engine."""
    import gzip
    src = Path(__file__).parent / "data" / "qwen3-2layer-tpu.xplane.pb.gz"
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(gzip.open(src).read())
    return xplane.read(str(path))


def test_recorded_trace_programs_and_kernels(recorded):
    import collections
    (dev,) = recorded.devices
    assert dev.name == "/device:TPU:0"
    kinds = collections.Counter(m.kind for m in dev.modules)
    assert (kinds["prefill"], kinds["extend"], kinds["decode"]) == (1, 1, 4)
    lo, hi = dev.modules[0].start, dev.modules[-1].end
    # one core runs one program at a time: the union is the plain sum
    assert xplane.union(dev.modules, lo, hi) == \
        pytest.approx(sum(m.dur for m in dev.modules)) == 19844158.0
    idle = xplane.gaps(dev.modules, lo, hi)
    assert sum(b - a for a, b in idle) == pytest.approx(hi - lo - 19844158.0)
    want = {"prefill": "flash_attention", "extend": "paged_attention",
            "decode": "paged_attention"}
    for m in dev.modules:
        if m.kind in want:
            calls = xplane.kernel_calls(m, want[m.kind])
            assert len(calls) == 2                       # one per layer
            assert all(m.start <= c.start and c.end <= m.end for c in calls)
            assert not xplane.kernel_calls(m, "moe_gmm")
    decode = [m for m in dev.modules if m.kind == "decode"]
    assert sum(c.dur for c in xplane.kernel_calls(decode[0],
                                                  "paged_attention")) \
        == pytest.approx(42477.0)
    assert decode[0].dur == pytest.approx(3243237.0)


def test_recorded_moe_trace_finds_the_gmm_calls(tmp_path):
    """A real TPU v5e trace of Granite-3.0-3B-A800M widths cut to 2 layers,
    the same calls as the Qwen3 trace: every program of the MoE layer runs
    three ``moe_gmm`` calls (gate, up, down) per layer."""
    import collections
    import gzip
    src = Path(__file__).parent / "data" / "granite-moe-2layer-tpu.xplane.pb.gz"
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(gzip.open(src).read())
    (dev,) = xplane.read(str(path)).devices
    kinds = collections.Counter(m.kind for m in dev.modules)
    assert (kinds["prefill"], kinds["extend"], kinds["decode"]) == (1, 1, 4)
    for m in dev.modules:
        if m.kind in ("prefill", "extend", "decode"):
            calls = xplane.kernel_calls(m, "moe_gmm")
            assert len(calls) == 6
            assert all(m.start <= c.start and c.end <= m.end for c in calls)
    decode = [m for m in dev.modules if m.kind == "decode"]
    assert sum(c.dur for c in xplane.kernel_calls(decode[0], "moe_gmm")) \
        == pytest.approx(25449.0)
