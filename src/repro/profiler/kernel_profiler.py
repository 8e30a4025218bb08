"""Kernel-granular profiler: per-kernel latency sub-buckets (hwtrace/3).

Where ``runtime_profiler`` measures whole engine iterations, this module
times the four kernels one forward pass composes from — ``attention``
(qkv projection + flash/paged attention + output projection), ``mlp``,
``moe_gmm`` (capacity-dispatched expert FFN), and ``head`` — in isolation,
per kernel backend, over the same (tokens, context) buckets the runtime
profiler sweeps.  The rows land in a ``HardwareTrace`` as
``kern:<backend>:<kernel>`` points (see ``repro.hw.trace``), giving the
perf model a fidelity tier between whole-iteration and op-class pricing
and letting ``benchmarks/fig2_fidelity.py`` attribute prediction error to
one specific kernel.

Row key conventions match ``PerfModel._kernel_level``:

* prefill rows at ``(tokens=T, context=T)`` — one fresh T-token prompt;
* decode rows at ``(tokens=B, context=c)`` — a B-wide step attending
  over c cached positions (paged layout, block-table indirection).

Each kernel is jitted, warmed (compile excluded) and timed over ``reps``
repetitions; the median lands in the trace.  On CPU the pallas backend
runs in interpret mode — structurally the production path, numerically
valid, but the latencies describe the interpreter; real accelerator
sweeps (TPU/GPU) are where pallas rows become pricing-grade.
"""
from __future__ import annotations

import time
from typing import List, Optional, Sequence

import numpy as np

from repro.configs import get_config
from repro.core.trace import OpPoint
from repro.hw.trace import HardwareTrace, kern_op

#: kernel backends a sweep can target
SWEEP_BACKENDS = ("reference", "pallas")


def _median_time(fn, args, reps: int) -> float:
    import jax
    jax.block_until_ready(fn(*args))          # compile + warm
    lat = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        lat.append(time.perf_counter() - t0)
    return float(np.median(lat))


def _divisor_block(n: int, b: int = 128) -> int:
    while n % b:
        b //= 2
    return max(b, 1)


def kernel_points(arch: str, backend: str, *,
                  max_batch: int = 4, max_len: int = 512,
                  prefill_buckets: Sequence[int] = (16, 32, 64, 128, 256),
                  decode_ctxs: Sequence[int] = (32, 64, 128, 256),
                  reps: int = 3, seed: int = 0, page_size: int = 64,
                  interpret: Optional[bool] = None) -> List[OpPoint]:
    """Sweep one kernel backend for ``arch``; returns ``kern:*`` OpPoints.

    ``interpret`` forwards to the pallas wrappers (None = platform
    default); ignored for the reference backend.
    """
    import jax
    import jax.numpy as jnp
    from repro.kernels import flash_attention, moe_gmm, paged_attention
    from repro.kernels.ref import flash_attention_ref, paged_attention_ref

    if backend not in SWEEP_BACKENDS:
        raise ValueError(f"kernel sweep backend must be one of "
                         f"{SWEEP_BACKENDS}, got {backend!r}")
    cfg = get_config(arch)
    dt = jnp.dtype(cfg.compute_dtype)
    d, H, KV, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    key = jax.random.PRNGKey(seed)

    def rand(*shape):
        nonlocal key
        key, sub = jax.random.split(key)
        return (jax.random.normal(sub, shape, jnp.float32)
                * shape[-1] ** -0.5).astype(dt)

    wqkv = rand(d, (H + 2 * KV) * dh)
    wo = rand(H * dh, d)
    wh = rand(d, cfg.vocab)
    pts: List[OpPoint] = []

    def add(kernel, phase, tokens, context, fn, args):
        pts.append(OpPoint(kern_op(backend, kernel), phase, int(tokens),
                           int(context), _median_time(fn, args, reps)))

    def split_qkv(x):
        """(N, d) -> q (N,H,dh), k/v (N,KV,dh) via one fused projection."""
        qkv = x @ wqkv
        n = x.shape[0]
        return (qkv[:, :H * dh].reshape(n, H, dh),
                qkv[:, H * dh:(H + KV) * dh].reshape(n, KV, dh),
                qkv[:, (H + KV) * dh:].reshape(n, KV, dh))

    # ---- attention: prefill (flash) ----
    for T in prefill_buckets:
        if T >= max_len:
            continue
        b = _divisor_block(T)

        @jax.jit
        def attn_prefill(x, lengths):
            q, k, v = split_qkv(x)
            q, k, v = q[None], k[None], v[None]
            if backend == "pallas":
                o = flash_attention(q, k, v, lengths=lengths, bq=b, bkv=b,
                                    interpret=interpret)
            else:
                o = flash_attention_ref(q, k, v, lengths=lengths)
            return o.reshape(1, T, H * dh)[0] @ wo

        add("attention", "prefill", T, T, attn_prefill,
            (rand(T, d), jnp.full((1,), T, jnp.int32)))

    # ---- attention: decode (paged) ----
    for ctx in decode_ctxs:
        if ctx + 16 >= max_len:
            continue
        npg = -(-ctx // page_size)
        for nb in sorted({1, max(1, max_batch // 2), max_batch}):
            kp = rand(1, nb * npg, page_size, KV * dh)
            vp = rand(1, nb * npg, page_size, KV * dh)
            table = jnp.arange(nb * npg, dtype=jnp.int32).reshape(nb, npg)
            lengths = jnp.full((nb,), ctx, jnp.int32)

            @jax.jit
            def attn_decode(x, kp, vp, table, lengths):
                q, _, _ = split_qkv(x)
                if backend == "pallas":
                    o = paged_attention(q, kp, vp, table, lengths, 0,
                                        page_size=page_size,
                                        interpret=interpret)
                else:
                    o = paged_attention_ref(q, kp, vp, table, lengths, 0,
                                            page_size=page_size)
                return o.reshape(-1, H * dh) @ wo

            add("attention", "decode", nb, ctx, attn_decode,
                (rand(nb, d), kp, vp, table, lengths))

    # ---- ffn: mlp or moe_gmm ----
    if cfg.moe is None:
        wg, wu = rand(d, cfg.d_ff), rand(d, cfg.d_ff)
        wd = rand(cfg.d_ff, d)

        @jax.jit
        def mlp(x):
            h = jax.nn.silu(x @ wg) * (x @ wu) if cfg.mlp_gated \
                else jax.nn.gelu(x @ wg)
            return h @ wd

        def ffn_at(phase, tokens, context):
            add("mlp", phase, tokens, context, mlp, (rand(tokens, d),))
    else:
        E, k_top = cfg.moe.n_experts, cfg.moe.top_k
        de = cfg.moe.d_expert
        weg, weu = rand(E, d, de), rand(E, d, de)
        wed = rand(E, de, d)

        def ffn_at(phase, tokens, context):
            # capacity-dispatched expert FFN at this batch's expert load
            C = max(1, int(np.ceil(tokens * k_top
                                   * cfg.moe.capacity_factor / E)))
            gs = jnp.full((E,), min(C, tokens), jnp.int32)

            if backend == "pallas":
                @jax.jit
                def moe(xe):
                    h = jax.nn.silu(moe_gmm(xe, weg, gs)) \
                        * moe_gmm(xe, weu, gs)
                    return moe_gmm(h, wed, gs)
            else:
                @jax.jit
                def moe(xe):
                    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, weg)) \
                        * jnp.einsum("ecd,edf->ecf", xe, weu)
                    return jnp.einsum("ecf,efd->ecd", h, wed)
            add("moe_gmm", phase, tokens, context, moe, (rand(E, C, d),))

    # ---- head ----
    @jax.jit
    def head(x):
        return x.astype(jnp.float32) @ wh.astype(jnp.float32)

    for T in prefill_buckets:
        if T >= max_len:
            continue
        ffn_at("prefill", T, T)
        add("head", "prefill", T, T, head, (rand(T, d),))
    for ctx in decode_ctxs:
        if ctx + 16 >= max_len:
            continue
        for nb in sorted({1, max(1, max_batch // 2), max_batch}):
            ffn_at("decode", nb, ctx)
            add("head", "decode", nb, ctx, head, (rand(nb, d),))
    return pts


def add_kernel_grid(hwt: HardwareTrace, arch: str,
                    backends: Sequence[str] = SWEEP_BACKENDS,
                    **kwargs) -> HardwareTrace:
    """Sweep ``backends`` and append the rows to ``hwt``'s base grid
    (kernel sweeps are single-device; tp collectives are composed
    analytically by the perf model on top of kernel rows)."""
    t0 = time.time()
    for backend in backends:
        hwt.points.extend(kernel_points(arch, backend, **kwargs))
    hwt.meta["kernel_backends"] = list(backends)
    hwt.meta["kernel_wall_s"] = round(time.time() - t0, 3)
    return hwt
