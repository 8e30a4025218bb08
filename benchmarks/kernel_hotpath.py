"""Serving hot-path kernel microbenchmark: reference vs pallas.

Times the three Pallas kernels the serving engine dispatches to under
``kernels="auto"`` — flash prefill attention, paged decode attention
(block-table indirection), and the fused MoE grouped matmul — against
their pure-JAX reference twins, and checks numerical parity on every
case (f32, awkward shapes: ragged lengths crossing page boundaries,
permuted block tables, sliding windows, zero-size expert groups).

On CPU the pallas side runs through the Pallas interpreter, so the
wall-clock columns describe the interpreter, not production kernels —
the parity columns are the point there (CI runs this to pin the
kernel-backend contract); on TPU the timings compare compiled Pallas
against XLA.  Emits one JSON row per case::

  PYTHONPATH=src python -m benchmarks.kernel_hotpath --out BENCH_kernels.json
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import flash_attention, moe_gmm, paged_attention
from repro.kernels.ops import _interpret
from repro.kernels.ref import (flash_attention_ref, moe_gmm_ref,
                               paged_attention_ref)


def _timeit(fn, reps: int) -> float:
    jax.block_until_ready(fn())            # compile + warm
    lat = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        lat.append(time.perf_counter() - t0)
    return float(np.median(lat))


def _case(name, pallas_fn, ref_fn, reps, valid=None):
    out_p = np.asarray(pallas_fn())
    out_r = np.asarray(ref_fn())
    if valid is not None:
        out_p, out_r = out_p[valid], out_r[valid]
    diff = float(np.max(np.abs(out_p - out_r))) if out_p.size else 0.0
    row = {
        "case": name,
        "max_abs_diff": diff,
        "parity": bool(diff < 2e-5),
        "pallas_s": _timeit(pallas_fn, reps),
        "reference_s": _timeit(ref_fn, reps),
    }
    row["speedup"] = row["reference_s"] / max(row["pallas_s"], 1e-12)
    return row


def run(reps: int = 5, seed: int = 0):
    key = jax.random.PRNGKey(seed)

    def rand(*shape):
        nonlocal key
        key, sub = jax.random.split(key)
        return jax.random.normal(sub, shape, jnp.float32)

    rows = []

    # ---- flash prefill (GQA + ragged lengths + sliding window) ----
    B, S, H, KV, dh = 2, 128, 8, 4, 64
    q, k, v = rand(B, S, H, dh), rand(B, S, KV, dh), rand(B, S, KV, dh)
    lengths = jnp.array([S, S - 37], jnp.int32)
    valid = np.zeros((B, S), bool)
    for b, n in enumerate(np.asarray(lengths)):
        valid[b, :n] = True
    rows.append(_case(
        "flash_prefill_gqa_lengths",
        lambda: flash_attention(q, k, v, lengths=lengths, bq=64, bkv=64),
        lambda: flash_attention_ref(q, k, v, lengths=lengths),
        reps, valid=valid))
    win = 48
    rows.append(_case(
        "flash_prefill_window",
        lambda: flash_attention(q, k, v, lengths=lengths, window=win,
                                bq=64, bkv=64),
        lambda: flash_attention_ref(q, k, v, lengths=lengths, window=win),
        reps, valid=valid))

    # ---- paged decode (ragged lengths crossing page boundaries, permuted
    # block table) ----
    ps, maxp, nb = 16, 8, 4
    n_pages = nb * maxp + 1
    kp = rand(1, n_pages, ps, KV * dh)
    vp = rand(1, n_pages, ps, KV * dh)
    table = jnp.asarray(np.random.default_rng(seed).permutation(
        nb * maxp)[:nb * maxp].reshape(nb, maxp), jnp.int32)
    dlen = jnp.array([1, ps, ps + 1, maxp * ps], jnp.int32)  # page edges
    qd = rand(nb, H, dh)
    rows.append(_case(
        "paged_decode_ragged",
        lambda: paged_attention(qd, kp, vp, table, dlen, 0, page_size=ps),
        lambda: paged_attention_ref(qd, kp, vp, table, dlen, 0,
                                    page_size=ps),
        reps))
    rows.append(_case(
        "paged_decode_window",
        lambda: paged_attention(qd, kp, vp, table, dlen, 0, page_size=ps,
                                window=win),
        lambda: paged_attention_ref(qd, kp, vp, table, dlen, 0,
                                    page_size=ps, window=win),
        reps))

    # ---- extend through the same paged kernel (chunked prefill) ----
    Se = 24
    qe = rand(nb, Se, H, dh)
    start = jnp.maximum(dlen - Se, 0)
    elen = jnp.minimum(start + Se, maxp * ps)
    rows.append(_case(
        "paged_extend",
        lambda: paged_attention(qe, kp, vp, table, elen, 0, page_size=ps,
                                start=start),
        lambda: paged_attention_ref(qe, kp, vp, table, elen, 0,
                                    page_size=ps, start=start),
        reps))

    # ---- fused MoE grouped matmul (uneven groups incl. zero-size) ----
    E, C, d, f = 4, 96, 64, 128
    x, w = rand(E, C, d), rand(E, d, f)
    gs = jnp.array([C, 17, 0, 5], jnp.int32)
    rows.append(_case(
        "moe_gmm_uneven_groups",
        lambda: moe_gmm(x, w, gs, bc=32),
        lambda: moe_gmm_ref(x, w, gs),
        reps))

    return {
        "jax_backend": jax.default_backend(),
        "pallas_interpret": _interpret(),
        "reps": reps,
        "cases": rows,
        "all_parity": all(r["parity"] for r in rows),
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None, help="write the JSON report here")
    args = ap.parse_args()
    out = run(reps=args.reps, seed=args.seed)
    text = json.dumps(out, indent=1, default=float)
    print(text)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(text)
    if not out["all_parity"]:
        raise SystemExit("kernel parity FAILED (see max_abs_diff above)")


if __name__ == "__main__":
    main()
