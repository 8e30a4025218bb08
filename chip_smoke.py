#!/usr/bin/env python3
"""Bring-up smoke: serve llama3.1-8b widths on a TPU through the real path.

    python chip_smoke.py              # one TPU chip
    python chip_smoke.py --chips 4    # tensor-parallel serving on four chips
    python chip_smoke.py --cpu        # CPU rehearsal: llama3.1-8b-tiny with
                                      # interpreted kernels (add --chips 4
                                      # for four virtual CPU devices)

One chip: llama3.1-8b at its published widths, cut to 16 of 32 layers,
bf16 random weights from ``--seed``.  A ``kernels="pallas"`` engine (paged
KV, compiled Pallas kernels) is checked against a ``kernels="reference"``
engine on the same params: prefill of three prompts of different lengths,
then 8 cached decode steps, logits compared.  Then ``ServeDriver`` (the
same setup ``repro.launch.serve`` builds) serves 16 ShareGPT-style
requests with chunked prefill, so the paged extend path runs too.

``--chips 4``: only the tensor-parallel path.  A tp=4 engine against a
tp=1 engine on the same params at the 16-layer cut (logits compared),
then ``ServeDriver`` serving a few requests with all 32 layers at tp=4,
a model that does not fit one chip in bf16.  The Pallas path serves
tp=1 only, so this phase names ``kernels="reference"``.

Progress goes to stdout.  Any failed check raises, so the exit code is
non-zero and no result line is printed.  On success the last line is
``{"ok": true, "device": {"platform", "kind", "count"}}`` as JAX reports
the devices.  Times printed here are smoke, not a benchmark.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

FULL_LAYERS = 32
CUT_LAYERS = 16
MAX_BATCH = 8
MAX_LEN = 2048
DECODE_STEPS = 8
# bf16 compute: the two engines round activations to bf16 (8-bit mantissa,
# relative step 2**-8) at different points — attention inside a kernel in
# f32 vs jnp einsums, sharded vs whole matmul reductions — and the
# differences compound over 16 layers.  A wrong mask, page or head gives
# differences of the logits' own size; rounding stays a few percent of it.
LOGIT_TOL = 0.05


def log(msg: str):
    print(msg, flush=True)


def llama(tiny: bool, n_layers: int, kernels: str):
    from repro.configs import get_config
    from repro.configs.base import ATTN_MLP, simple_stages
    base = get_config("llama3.1-8b-tiny" if tiny else "llama3.1-8b")
    if tiny:
        n_layers = base.n_layers
    return dataclasses.replace(
        base, n_layers=n_layers, stages=simple_stages(ATTN_MLP, n_layers),
        param_dtype="bfloat16", kernels=kernels)


def memory(label: str):
    """Device 0's allocator counters, where the backend reports them."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    log(f"memory after {label}: bytes_in_use "
        f"{stats.get('bytes_in_use', 'not reported')}, peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use', 'not reported')}")


def devices_of(tree) -> set:
    import jax
    return set().union(*(leaf.devices()
                         for leaf in jax.tree_util.tree_leaves(tree)))


def prefill_decode_logits(eng, prompts, decode_tokens):
    """Prefill each prompt into its own slot, then run cached decode steps
    feeding ``decode_tokens[step]``; returns every logits row produced."""
    import jax.numpy as jnp
    import numpy as np
    from repro.serve.engine import _bucket
    vocab = eng.cfg.vocab
    out = []
    for slot, p in enumerate(prompts):
        pad = np.zeros((1, _bucket(len(p))), np.int32)
        pad[0, :len(p)] = p
        logits, c1 = eng._jit_prefill(
            eng.params, jnp.asarray(pad),
            lengths=jnp.asarray([len(p)], jnp.int32))
        eng._write_slot_from_prefill(slot, c1, len(p))
        out.append(np.asarray(logits[0, 0, :vocab], np.float32))
    lengths = [len(p) for p in prompts]
    for toks in decode_tokens:
        buf = np.zeros((eng.max_batch, 1), np.int32)
        buf[:len(prompts), 0] = toks
        for slot, n in enumerate(lengths):
            eng.ensure_capacity(slot, n + 1)
        logits, eng.cache = eng._jit_decode(eng.params, eng.cache,
                                            jnp.asarray(buf))
        out.append(np.asarray(logits[:len(prompts), 0, :vocab], np.float32))
        lengths = [n + 1 for n in lengths]
    return out


def compare_logits(eng, ref, label: str, seed: int, max_prompt: int):
    """Same prompts and decode tokens through both engines; fails above
    LOGIT_TOL of the reference logits' largest magnitude."""
    import numpy as np
    rng = np.random.default_rng(seed)
    vocab = eng.cfg.vocab
    prompts = [rng.integers(0, vocab, n).astype(np.int32)
               for n in (37, max_prompt // 3, max_prompt - 24)]
    steps = rng.integers(0, vocab, (DECODE_STEPS, len(prompts)))
    t0 = time.perf_counter()
    got = prefill_decode_logits(eng, prompts, steps)
    want = prefill_decode_logits(ref, prompts, steps)
    secs = time.perf_counter() - t0
    diff = max(float(np.abs(a - b).max()) for a, b in zip(got, want))
    scale = max(float(np.abs(b).max()) for b in want)
    if not all(np.isfinite(a).all() for a in got + want):
        raise AssertionError(f"{label}: non-finite logits")
    log(f"{label}: prompts {[len(p) for p in prompts]} + {DECODE_STEPS} "
        f"decode steps; max |logits diff| {diff!r}, max |ref logit| "
        f"{scale!r}, ratio {diff / scale!r} (tolerance {LOGIT_TOL}); "
        f"{secs:.1f} s incl. compile")
    if diff > LOGIT_TOL * scale:
        raise AssertionError(f"{label}: logits differ by {diff} > "
                             f"{LOGIT_TOL} x {scale}")


def serve(cfg, *, params, n: int, max_len: int, max_output: int, tp: int,
          seed: int):
    """ServeDriver over one engine; checks every request finished with
    tokens and prints its counts.  Returns the engine."""
    from repro.launch.serve import build_driver
    from repro.workload import ShareGPTConfig, generate
    drv = build_driver(cfg, params=params, max_batch=MAX_BATCH,
                       max_len=max_len, tp=tp, chunked_prefill=True)
    eng = drv.engines["e0"]
    reqs = generate(ShareGPTConfig(
        n_requests=n, rate=10.0, vocab=cfg.vocab, seed=seed,
        mean_prompt=max_len // 5, max_prompt=max_len // 2,
        max_output=max_output, share_fraction=0.0))
    t0 = time.perf_counter()
    drv.runtime.warmup()
    log(f"serve[{cfg.name} x{cfg.n_layers} tp={tp}]: compile + warmup "
        f"{time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    m = drv.run(reqs, warmup=False)
    wall = time.perf_counter() - t0
    out = drv.runtime.instances["e0"].backend.out_tokens
    n_tok = sum(len(t) for t in out.values())
    log(f"serve: {m['finished']}/{n} requests finished, prompt tokens "
        f"{sum(len(r.prompt_tokens) for r in reqs)} (longest "
        f"{max(len(r.prompt_tokens) for r in reqs)}), generated tokens "
        f"{n_tok}, iterations {m['instances']['e0']['engine_iterations']}")
    log(f"serve latency (smoke, not a benchmark): wall {wall:.1f} s, "
        f"ttft mean {m['ttft_mean_s']:.4f} s, itl mean "
        f"{m['itl_mean_s']:.4f} s")
    memory(f"serving {cfg.n_layers} layers at tp={tp}")
    if m["finished"] != n:
        raise AssertionError(f"finished {m['finished']} of {n} requests")
    silent = [r.req_id for r in reqs if not out.get(r.req_id)]
    if silent:
        raise AssertionError(f"requests produced no tokens: {silent}")
    return eng


def one_chip(tiny: bool, seed: int):
    import jax
    from repro.serve import ServingEngine
    cut = llama(tiny, CUT_LAYERS, "pallas")
    max_len = 512 if tiny else MAX_LEN
    log(f"model: {cut.name} d_model {cut.d_model}, heads {cut.n_heads}, "
        f"kv heads {cut.n_kv_heads}, d_head {cut.d_head}, d_ff {cut.d_ff}, "
        f"vocab {cut.vocab}, {cut.param_dtype} weights from seed {seed}")
    if not tiny:
        log(f"reduced: n_layers {FULL_LAYERS}→{CUT_LAYERS}")
    t0 = time.perf_counter()
    eng = ServingEngine(cut, max_batch=MAX_BATCH, max_len=max_len,
                        seed=seed, name="pallas")
    params = jax.block_until_ready(eng.params)
    log(f"init: {time.perf_counter() - t0:.1f} s, params "
        f"{sum(x.nbytes for x in jax.tree_util.tree_leaves(params))} "
        f"bytes, dtypes "
        f"{sorted({str(x.dtype) for x in jax.tree_util.tree_leaves(params)})}")
    memory("init")
    interpret = jax.default_backend() == "cpu"
    if eng.kernel_backend != "pallas" or eng.pallas_interpret is not \
            interpret or not eng.paged:
        raise AssertionError(
            f"engine runs {eng.kernel_backend} (interpret="
            f"{eng.pallas_interpret}, paged={eng.paged})")
    log(f"engine: kernel_backend {eng.kernel_backend}, pallas_interpret "
        f"{eng.pallas_interpret}, paged KV {eng.paged}, max_batch "
        f"{MAX_BATCH}, max_len {max_len}")
    ref = ServingEngine(dataclasses.replace(cut, kernels="reference"),
                        params=params, max_batch=MAX_BATCH, max_len=max_len,
                        name="reference")
    compare_logits(eng, ref, "logits pallas vs reference", seed,
                   max_len // 2)
    del eng, ref
    gc.collect()
    memory("logits check")
    srv = serve(cut, params=params, n=16, max_len=max_len, max_output=32,
                tp=1, seed=seed)
    if srv.kernel_backend != "pallas" or srv.pallas_interpret is not \
            interpret:
        raise AssertionError("served engine left the Pallas path")


def four_chips(tiny: bool, seed: int):
    import jax
    from repro.serve import ServingEngine
    cut = llama(tiny, CUT_LAYERS, "reference")
    max_len = 512 if tiny else MAX_LEN
    if not tiny:
        log(f"reduced: n_layers {FULL_LAYERS}→{CUT_LAYERS} (tp=4 vs tp=1 "
            f"logits; serving below uses all {FULL_LAYERS})")
    one = ServingEngine(cut, max_batch=MAX_BATCH, max_len=max_len,
                        seed=seed, name="tp1")
    tp4 = ServingEngine(cut, params=one.params, max_batch=MAX_BATCH,
                        max_len=max_len, tp=4, name="tp4")
    for what, tree in (("params", tp4.params), ("cache", tp4.cache)):
        n = len(devices_of(tree))
        log(f"tp=4 {what} span {n} devices")
        if n != 4:
            raise AssertionError(f"tp=4 {what} span {n} devices, not 4")
    compare_logits(tp4, one, "logits tp=4 vs tp=1", seed, max_len // 2)
    del one, tp4
    gc.collect()
    memory("logits check")
    full = llama(tiny, FULL_LAYERS, "reference")
    log(f"model: {full.name} all {full.n_layers} layers, "
        f"{full.param_dtype} weights from seed {seed}, tp=4")
    srv = serve(full, params=None, n=8, max_len=max_len, max_output=16,
                tp=4, seed=seed)
    for what, tree in (("params", srv.params), ("cache", srv.cache)):
        n = len(devices_of(tree))
        log(f"served tp=4 {what} span {n} devices")
        if n != 4:
            raise AssertionError(f"served {what} span {n} devices, not 4")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--cpu", action="store_true",
                    help="rehearse on the CPU with llama3.1-8b-tiny and "
                         "interpreted kernels")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if args.chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") + " --xla_force_host_"
                f"platform_device_count={args.chips}").strip()

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    log(f"device: platform {dev.platform}, device_kind {dev.device_kind}, "
        f"count {len(devs)}")
    log(f"compile cache: {cache_dir}")
    if dev.platform != "tpu" and not args.cpu:
        raise SystemExit(f"no TPU: JAX found platform {dev.platform!r} "
                         f"(use --cpu for the CPU rehearsal)")
    if len(devs) < args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} devices, "
                         f"JAX sees {len(devs)}")

    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args.cpu, args.seed)
    else:
        one_chip(args.cpu, args.seed)
    log(f"total: {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
