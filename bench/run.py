#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the served path as ``repro.launch.serve.build_driver`` does — one
``ServingEngine`` (Pallas kernels, paged KV, bf16 weights made on the
device from ``--seed``), ``JaxBackend``, ``ServingRuntime`` with chunked
prefill — warms up every shape the cell's traffic reaches, then offers the
cell's traffic against the wall clock for ``--seconds``.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` takes a
profiler trace over the last seconds of the window and reports
the per-layer metrics.  Either way the served tokens are then checked
against the plain reference (``check.py``).  The last stdout line is the
result as one JSON object; the numbers compared, each with its limit, are
the last lines on stderr and the last key of the result.

Exits non-zero without a result where JAX finds no TPU, or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import cell as cells  # noqa: E402
from bench import stats  # noqa: E402

#: the trace covers the window's last TRACE_LEN share, at most TRACE_MAX_S:
#: stopping the profiler holds the host for seconds while it writes the
#: trace, so that falls after the close
TRACE_LEN, TRACE_MAX_S = 0.1, 4.0

_COUNTS: dict = {}


def log(msg: str):
    print(msg, flush=True)


def setup_jax():
    """Persistent compilation cache at one fixed place: where
    ``JAX_COMPILATION_CACHE_DIR`` says, else ``<checkout>/.jax_cache``;
    every program is cached, so only a checkout's first run compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if not _COUNTS:
        _COUNTS["lowered"] = 0

        def listen(name, *_a, **_k):
            if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
                _COUNTS["lowered"] += 1
        jax.monitoring.register_event_duration_secs_listener(listen)
    return _COUNTS


def device_info(chips: int, require_tpu: bool = True) -> dict:
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise SystemExit(f"this cell needs {chips} TPU chip(s); JAX found "
                         f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _bucket(n: int) -> int:
    b = 16
    while b < n:
        b *= 2
    return b


def scheduler_cfg(cell):
    from repro.core.config import SchedulerCfg
    s = cell.spec["scheduler"]
    return SchedulerCfg(max_batch_size=cell.max_batch,
                        max_batch_tokens=s["max_batch_tokens"],
                        chunked_prefill=True, prefill_chunk=s["prefill_chunk"])


def build_runtime(eng, sched, clock):
    """``ServingRuntime`` over one engine, as ``ServeDriver`` wires it, with
    the wall-clock stamping backend in place of the bare ``JaxBackend``."""
    from repro.core.config import ClusterCfg, RouterCfg
    from repro.runtime.backends.jax_engine import JaxBackend
    from repro.runtime.cluster import ServingRuntime
    from repro.serve.driver import engine_instance_cfg
    from bench.driver import TimedBackend
    made = []

    def factory(icfg, _trace):
        made.append(TimedBackend(JaxBackend(eng, icfg), clock))
        return made[0]
    rt = ServingRuntime(ClusterCfg(instances=(engine_instance_cfg(eng, sched),),
                                   router=RouterCfg("round_robin")), factory)
    return rt, made[0]


def warmup(eng, cell, sched):
    """Compile every program the cell's traffic reaches.  The engine's and
    the backend's own warm-ups cover prefill at each bucket up to the
    first chunk, extend at each chunk bucket and the decode step; the
    paged prefill write and the slot view take the slot as a static
    argument, so those compile once per slot here; a short unpaced serve
    through the runtime compiles the eager ops of the serving loop."""
    import jax
    import jax.numpy as jnp
    from bench.generator import Request
    top = _bucket(min(cell.traffic["prompt"]["max"], sched.prefill_chunk,
                      eng.max_len - 1))
    buckets = [b for b in (16 << i for i in range(12)) if b <= top]
    eng.warmup(buckets=buckets)
    w0 = time.perf_counter()
    rt, tb = build_runtime(eng, sched, lambda: time.perf_counter() - w0)
    tb.inner.warmup()
    for P in buckets:
        _, c1 = eng._jit_prefill(eng.params, jnp.zeros((1, P), jnp.int32),
                                 lengths=jnp.asarray([P], jnp.int32))
        for slot in range(eng.max_batch):
            eng._write_slot_from_prefill(slot, c1, P)
            eng._release_slot(slot)
    for slot in range(eng.max_batch):
        eng._slot_subcache(slot, 16)
    n = min(cell.traffic["prompt"]["max"], eng.max_len - 8)
    rt.submit_workload([Request(i, 0.0, [1 + i] * p, 3)
                        for i, p in enumerate((n, n, 20))])
    rt.queue.run()
    jax.block_until_ready(eng.cache)
    if len(rt.finished) != 3 or len(eng.slot_free) != eng.max_batch:
        raise RuntimeError("warm-up serve did not finish cleanly")


def requests_for(cell, seed: int, seconds: float, vocab: int):
    """The cell's requests: open-loop arrivals over the pre-roll and the
    window, as two spans (so the window's work is the same for every
    seed), or a backlog all due at once."""
    from bench.generator import generate
    spec, traffic = cell.spec, cell.traffic
    if traffic["arrival"] == "poisson":
        pre = float(spec["preroll_s"])
        return generate(traffic, seed=seed, vocab=vocab, rate=spec["rate"],
                        spans=[(0.0, pre), (pre, pre + seconds)])
    n = math.ceil(spec["backlog_per_s"] * seconds) + 2 * cell.max_batch
    return generate(traffic, seed=seed, vocab=vocab, n=n)


def load_reader(name: str):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_of(cell_name: str, kind: str) -> list:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", [cell_name])]


class Setup:
    """The served path of one cell, warm: engine, scheduler config and the
    compile counter.  Weights are swapped per seed by ``load_params``."""

    def __init__(self, cell, seed: int, *, require_tpu: bool = True,
                 tiny: bool = False):
        from repro.serve.engine import ServingEngine
        self.cell = cell
        self.counts = setup_jax()
        self.device = device_info(cell.chips, require_tpu)
        self.cfg = cells.arch_config(cell, tiny=tiny)
        self.dims = cells.tiny_dims(cell, self.cfg) if tiny else cell.dims
        t0 = time.perf_counter()
        self.eng = ServingEngine(self.cfg, params=self.make_params(seed),
                                 max_batch=cell.max_batch,
                                 max_len=cell.max_len, name="e0")
        if self.eng.kernel_backend != "pallas" or not self.eng.paged:
            raise RuntimeError("the engine is not on the Pallas paged path")
        t1 = time.perf_counter()
        self.sched = scheduler_cfg(cell)
        warmup(self.eng, cell, self.sched)
        log(f"set-up: weights and engine {time.perf_counter() - t0:.3f} s "
            f"(warm-up {time.perf_counter() - t1:.3f} s), programs lowered "
            f"{self.counts['lowered']}")

    def make_params(self, seed: int):
        import jax
        from bench import weights
        return jax.block_until_ready(
            weights.served_params(self.dims, seed, self.cfg.param_dtype))

    def load_params(self, seed: int):
        self.eng.params = None
        gc.collect()
        self.eng.params = self.make_params(seed)

    def free_params(self):
        self.eng.params = None
        gc.collect()


class Served:
    """What one paced window left behind (times on the run's clock, s)."""
    setup_s: float
    t_open: float
    t_close: float
    t_trace: float            # the trace's start, or t_close untraced
    requests: list
    stamps: dict
    late: dict
    records: dict
    finished: list
    active: list
    failed: int
    lowered: int
    peak: int
    trace_dir: object = None


def serve(setup: Setup, reqs, seconds: float, trace: bool = False) -> Served:
    """Offer ``reqs`` against the wall clock: pre-roll until the window
    opens, then ``seconds`` of window (with a trace in its middle)."""
    import jax
    from bench.driver import advance
    cell, eng, spec = setup.cell, setup.eng, setup.cell.spec
    origin = time.perf_counter()

    def clock():
        return time.perf_counter() - origin
    rt, tb = build_runtime(eng, setup.sched, clock)
    late = {}
    arrive = rt._arrive

    def timed_arrive(req):
        late[req.req_id] = clock() - req.arrival
        arrive(req)
    rt._arrive = timed_arrive
    rt.submit_workload(reqs)
    if spec["window_opens"] == "after_preroll":
        t_open = float(spec["preroll_s"])
        advance(rt, clock, t_open)
    elif spec["window_opens"] == "batch_full":
        full = []
        tb.on_iteration = lambda k, decode: full.append(k) \
            if len(decode) == eng.max_batch else None
        advance(rt, clock, spec["fill_timeout_s"], stop=lambda: bool(full))
        if not full:
            raise RuntimeError("the running batch never filled")
        tb.on_iteration = None
        t_open = clock()
    else:
        raise ValueError(f"unknown window_opens {spec['window_opens']!r}")
    s = Served()
    s.setup_s = time.perf_counter() - T_START
    lowered0 = setup.counts["lowered"]
    t_close = t_open + seconds
    s.trace_dir, s.t_trace = None, t_close
    if trace:
        a = s.t_trace = t_close - min(TRACE_LEN * seconds, TRACE_MAX_S)
        advance(rt, clock, a)
        s.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(s.trace_dir, profiler_options=opts)
        with jax.profiler.TraceAnnotation("traced"):
            tb.annotate = True
            advance(rt, clock, t_close, annotate=True)
            tb.annotate = False
        jax.profiler.stop_trace()
    advance(rt, clock, t_close)
    s.lowered = setup.counts["lowered"] - lowered0
    s.peak = int((jax.devices()[0].memory_stats() or {})
                 .get("peak_bytes_in_use", 0))
    s.t_open, s.t_close, s.requests = t_open, t_close, reqs
    s.stamps, s.late, s.records = tb.stamps, late, dict(tb.records)
    if spec["window_opens"] == "after_preroll":
        s.active = [r for r in reqs if t_open <= r.arrival < t_close]
    else:
        s.active = [r for r in reqs if any(
            t_open <= t <= t_close for t in tb.stamps.get(r.req_id, ()))]
    by_id = {r.req_id: r for r in rt._all_requests}
    s.failed = sum(1 for r in s.active if by_id[r.req_id].n_preemptions
                   or by_id[r.req_id].n_restarts)
    out = tb.inner.out_tokens
    s.finished = [{"req_id": r.req_id, "prompt": list(r.prompt_tokens),
                   "served": list(out.get(r.req_id, [])),
                   "output_len": r.output_len,
                   "chunks": tb.chunks.get(r.req_id, 0)}
                  for r in rt.finished]
    tb.inner.reset()          # free every slot and page for the next window
    return s


def end_to_end(s: Served, seconds: float) -> dict:
    ttft = stats.ttft_ms(s.requests, s.stamps, s.t_open, s.t_close)
    tpot = stats.tpot_ms(s.stamps, s.t_open, s.t_close)
    ntok = stats.tokens_in_window(s.stamps, s.t_open, s.t_close)
    lat = [v * 1e3 for k, v in s.late.items()
           if s.t_open <= s.requests[k].arrival < s.t_close]
    log(f"window: {seconds} s from {s.t_open!r} s; requests active "
        f"{len(s.active)}, failed {s.failed}; tokens {ntok}; programs "
        f"lowered inside the window {s.lowered}")
    log(f"ttft ms: n {len(ttft)}, median {stats.percentile(ttft, 50)!r}, "
        f"p90 {stats.percentile(ttft, 90)!r}")
    log(f"tpot ms: n {len(tpot)}, median {stats.percentile(tpot, 50)!r}, "
        f"p90 {stats.percentile(tpot, 90)!r}")
    log(f"generator lateness ms: n {len(lat)}, median "
        f"{stats.percentile(lat, 50)!r}, max {max(lat, default=0.0)!r}")
    log(f"peak_bytes_in_use {s.peak}")
    return {"ttft_p90_ms": stats.percentile(ttft, 90),
            "tpot_p90_ms": stats.percentile(tpot, 90),
            "tokens_per_s": ntok / seconds, "setup_s": s.setup_s}


def output_check(setup: Setup, s: Served, seed: int,
                 control: bool = False) -> dict:
    """Reference comparison of a sample of ``s.finished``; the program's
    weights must already be freed."""
    from bench import check
    limits = setup.cell.spec["check"]
    picked = check.sample(s.finished, limits["requests"], seed)
    res = check.compare(setup.cell.conf, setup.dims, seed, picked,
                        limits["requests"], setup.cell.max_len,
                        control=control)
    log(f"check: {len(picked)} requests, {res['positions']} served "
        f"positions compared")
    res["requests"] = len(picked)
    return res


def judge(cell, res: dict):
    """(correct, numbers compared with their limits) of an output check:
    every number named under ``limits`` in ``cells/<cell>.json``, and the
    served token count, exactly."""
    limits = dict(cell.spec["check"]["limits"], missing_tokens=0)
    checked = {k: {"value": res[k], "limit": v} for k, v in limits.items()}
    correct = res["requests"] > 0 and all(
        v["value"] <= v["limit"] for v in checked.values())
    return correct, checked


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, tiny: bool = False) -> dict:
    """One run of one cell; returns the result object (see module doc).
    ``tiny`` serves the configuration's ``-tiny`` registry entry (CPU
    tests)."""
    setup = Setup(cell, seed, require_tpu=require_tpu, tiny=tiny)
    reqs = requests_for(cell, seed, seconds, setup.dims.vocab)
    s = serve(setup, reqs, seconds, trace)
    e2e = end_to_end(s, seconds)
    setup.eng = None          # the program's weights and cache go first
    gc.collect()
    correct, checked = judge(cell, output_check(setup, s, seed))
    result = {"correct": correct, "attempted": len(s.active),
              "failed": s.failed}
    device = dict(setup.device, memory_peak_bytes=s.peak)
    if trace:
        result["metrics"], result["breakdown"] = per_layer(
            cell, setup.dims, device, s)
    else:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in metrics_of(cell.name, "end_to_end")}
    result["device"] = device
    result["check"] = checked
    return result


def per_layer(cell, dims, device, s: Served):
    import shutil
    from bench import xplane
    from bench.peaks import peaks_for
    path = next(Path(s.trace_dir).rglob("*.xplane.pb"))
    tr = xplane.read(str(path))
    window = xplane.annotation(tr, "traced")
    if window is None or not tr.devices:
        raise RuntimeError("the trace holds no traced window or no device")
    lo, hi = window.start, window.end
    busy = [xplane.union(d.modules, lo, hi) for d in tr.devices]
    device["busy_s"] = sum(busy) / len(busy) * 1e-9
    device["window_s"] = (hi - lo) * 1e-9
    peaks = peaks_for(device["kind"])
    before = [r for r in s.requests if r.arrival < s.t_trace]
    ctx = xplane.Context(tr, lo, hi,
                         xplane.iterations(tr, s.records, lo, hi), dims,
                         peaks, cell.max_batch,
                         stats.ttft_ms(before, s.stamps, s.t_open, s.t_close))
    log(f"trace: {device['window_s']!r} s, {len(ctx.iterations)} iterations, "
        f"device busy {device['busy_s']!r} s")
    out = {}
    for m in metrics_of(cell.name, "per_layer"):
        got = load_reader(m["name"])(ctx)
        if isinstance(got, tuple):
            got, note = got
            log(f"{m['name']}: {note}")
        if got is not None:
            out[m["name"]] = {"value": got, "unit": m["unit"]}
    bd = xplane.breakdown(tr, lo, hi)
    shutil.rmtree(s.trace_dir, ignore_errors=True)
    return out, bd


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.load(args.workload)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    for name, v in result["check"].items():
        print(f"{name} {v['value']!r} limit {v['limit']!r}", file=sys.stderr,
              flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
