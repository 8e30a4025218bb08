"""Kernel-backend contract (ISSUE 8): the pallas serving hot path is a
drop-in for the reference path.

Kernel-level: interpret-mode Pallas vs the pure-jnp oracles on the awkward
shapes serving actually produces — GQA with ragged per-sequence lengths
and sliding windows, paged decode whose lengths land exactly on page
boundaries through a permuted block table, extend queries crossing pages,
and grouped matmuls with uneven (including zero-size) expert groups.

End-to-end: a ``kernels="auto"`` engine (paged KV + pallas kernels on this
CPU host, via the interpreter) must emit the SAME tokens as the
``kernels="reference"`` engine in f32 (bf16 argmax near-ties may flip
tokens between numerically-equivalent backends — f32 pins exact
equality), and the simulator must make the identical scheduling decisions
against the paged engine (the sim==real parity contract of
``tests/test_runtime_parity.py``, now on the pallas path).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import simple_stages
from repro.core import ClusterCfg, RouterCfg
from repro.core.cluster import Cluster
from repro.kernels import ops, ref
from repro.serve import DriverCfg, ServeDriver, ServingEngine
from repro.serve.driver import engine_instance_cfg, engine_scheduler_cfg
from repro.workload import ShareGPTConfig, generate

ARCH = "llama3.1-8b-tiny"
MOE_ARCH = "phimini-moe-tiny"


# ---------- kernel-level parity (interpret mode vs oracles) ----------

def test_flash_gqa_lengths_window():
    B, S, H, KV, dh = 2, 64, 8, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, dh), jnp.float32)
    lengths = jnp.array([S, 29], jnp.int32)
    for window in (None, 24):
        out = ops.flash_attention(q, k, v, lengths=lengths, window=window,
                                  bq=32, bkv=32)
        want = ref.flash_attention_ref(q, k, v, lengths=lengths,
                                       window=window)
        # rows past a sequence's length can be fully masked (softmax over
        # nothing): only rows a real engine would read are compared
        for b, n in enumerate(np.asarray(lengths)):
            np.testing.assert_allclose(np.asarray(out)[b, :n],
                                       np.asarray(want)[b, :n],
                                       rtol=2e-5, atol=2e-5)


def test_paged_decode_ragged_page_boundaries():
    H, KV, dh, ps, maxp = 4, 2, 16, 16, 4
    B, L, layer = 4, 3, 2
    P = B * maxp + 1
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    q = jax.random.normal(ks[0], (B, H, dh), jnp.float32)
    # stacked pools of L layers, each with values of its own
    kp = jax.random.normal(ks[1], (L, P, ps, KV * dh), jnp.float32)
    vp = jax.random.normal(ks[2], (L, P, ps, KV * dh), jnp.float32)
    # block-table indirection: pages deliberately permuted across slots
    table = jax.random.permutation(ks[3], B * maxp).reshape(B, maxp)
    table = table.astype(jnp.int32)
    # lengths straddle page boundaries: 1, exactly one page, one page + 1,
    # and the full table
    lengths = jnp.array([1, ps, ps + 1, maxp * ps], jnp.int32)
    out = ops.paged_attention(q, kp, vp, table, lengths, layer,
                              page_size=ps)
    want = ref.paged_attention_ref(q, kp, vp, table, lengths, layer,
                                   page_size=ps)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_paged_extend_crossing_pages():
    H, KV, dh, ps, maxp, S = 4, 2, 16, 8, 6, 12
    B, L, layer = 3, 2, 1
    P = B * maxp + 1
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(ks[0], (B, S, H, dh), jnp.float32)
    kp = jax.random.normal(ks[1], (L, P, ps, KV * dh), jnp.float32)
    vp = jax.random.normal(ks[2], (L, P, ps, KV * dh), jnp.float32)
    table = jax.random.permutation(ks[3], B * maxp).reshape(B, maxp)
    table = table.astype(jnp.int32)
    # chunks starting mid-page, on a boundary, and at zero
    start = jnp.array([ps - 3, ps, 0], jnp.int32)
    lengths = start + S
    for window in (None, 7):
        out = ops.paged_attention(q, kp, vp, table, lengths, layer,
                                  page_size=ps, start=start, window=window)
        want = ref.paged_attention_ref(q, kp, vp, table, lengths, layer,
                                       page_size=ps, start=start,
                                       window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_paged_extend_heads_share_a_lane_tile():
    """dh = 64: two KV heads per 128-lane tile, each read through the
    whole tile with its query zero outside its own lanes (Granite's
    widths: 3 query heads per KV head)."""
    H, KV, dh, ps, maxp, S = 12, 4, 64, 16, 3, 9
    B, L, layer = 2, 3, 2
    P = B * maxp + 1
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    q = jax.random.normal(ks[0], (B, S, H, dh), jnp.float32)
    kp = jax.random.normal(ks[1], (L, P, ps, KV * dh), jnp.float32)
    vp = jax.random.normal(ks[2], (L, P, ps, KV * dh), jnp.float32)
    table = jax.random.permutation(ks[3], B * maxp).reshape(B, maxp)
    table = table.astype(jnp.int32)
    start = jnp.array([ps - 2, 5], jnp.int32)
    lengths = start + S
    out = ops.paged_attention(q, kp, vp, table, lengths, layer,
                              page_size=ps, start=start)
    want = ref.paged_attention_ref(q, kp, vp, table, lengths, layer,
                                   page_size=ps, start=start)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layer", [None, 2], ids=["one", "stacked"])
def test_moe_gmm_zero_and_uneven_groups(layer):
    E, C, d, f = 4, 48, 32, 24
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    x = jax.random.normal(ks[0], (E, C, d), jnp.float32)
    shape = (E, d, f) if layer is None else (3, E, d, f)
    w = jax.random.normal(ks[1], shape, jnp.float32)
    gs = jnp.array([C, 0, 5, 17], jnp.int32)   # full, empty, tiny, partial
    out = ops.moe_gmm(x, w, gs, layer, bc=16)
    want = ref.moe_gmm_ref(x, w, gs, layer)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert not np.asarray(out)[1].any()        # zero-size group emits zeros


# ---------- end-to-end: pallas engine == reference engine ----------

def _workload(n, vocab, seed=3):
    reqs = generate(ShareGPTConfig(
        n_requests=n, rate=50.0, vocab=vocab, seed=seed,
        mean_prompt=40, mean_output=5, sigma_prompt=0.4, sigma_output=0.3,
        max_prompt=80, max_output=6, share_fraction=0.0))
    for r in reqs:
        r.arrival = 0.0
    return reqs


def _drive(cfg, reqs, scheduler):
    eng = ServingEngine(cfg, max_batch=2, max_len=256, name="e0")
    drv = ServeDriver([eng], DriverCfg(scheduler=scheduler))
    res = drv.run(reqs, warmup=False)
    inst = drv.runtime.instances["e0"]
    return eng, res, dict(inst.backend.out_tokens), inst.decisions


@pytest.mark.parametrize("arch", [ARCH, MOE_ARCH])
def test_engine_auto_matches_reference(arch):
    """f32 token-exact equality between kernels='auto' (paged + pallas)
    and kernels='reference' (contiguous) engines on the same workload."""
    base = dataclasses.replace(get_config(arch), compute_dtype="float32")
    n = 4
    reqs = _workload(n, base.vocab)
    sched = engine_scheduler_cfg(2)
    eng_r, res_r, tok_r, dec_r = _drive(
        dataclasses.replace(base, kernels="reference"), reqs, sched)
    eng_a, res_a, tok_a, dec_a = _drive(
        dataclasses.replace(base, kernels="auto"), reqs, sched)
    assert not eng_r.paged
    assert eng_a.paged and eng_a.kernel_backend == "pallas"
    assert res_r["finished"] == res_a["finished"] == n
    assert dec_r == dec_a
    assert tok_r == tok_a


def test_sim_real_decision_parity_on_paged_engine():
    """The sim==real scheduling-parity contract holds when the real engine
    runs the paged-KV pallas path (chunked prefill exercises extend)."""
    cfg = dataclasses.replace(get_config(ARCH), compute_dtype="float32",
                              kernels="auto")
    from repro.core.config import SchedulerCfg
    sched = SchedulerCfg(max_batch_size=2, max_batch_tokens=64,
                         chunked_prefill=True, prefill_chunk=16)
    reqs = _workload(6, cfg.vocab)
    eng = ServingEngine(cfg, max_batch=2, max_len=256, name="e0")
    assert eng.paged
    drv = ServeDriver([eng], DriverCfg(scheduler=sched))
    real = drv.run(reqs, warmup=False)
    real_dec = {n: i.decisions for n, i in drv.runtime.instances.items()}

    icfg = engine_instance_cfg(eng, sched)
    sim_cluster = Cluster(ClusterCfg(instances=(icfg,),
                                     router=RouterCfg("round_robin")))
    sim_cluster.submit_workload(_workload(6, cfg.vocab))
    sim = sim_cluster.run()
    sim_dec = {n: i.decisions for n, i in sim_cluster.instances.items()}
    assert real["finished"] == sim["finished"] == 6
    assert real_dec == sim_dec


def test_paged_chunk_extend_reuses_the_page_pools():
    """A chunked-prefill extend on the paged engine allocates no page pool
    of its own: the subcache shares the live pools, the extend donates
    them, and the write-back adopts the result by reference."""
    cfg = dataclasses.replace(get_config(ARCH), kernels="auto")
    eng = ServingEngine(cfg, max_batch=2, max_len=128)
    eng.ensure_capacity(1, 48)
    stages = [k for k in eng.cache if k.startswith("stage")]
    pools = [eng.cache[k]["k_pages"] for k in stages]
    sub = eng._slot_subcache(1, 16)
    assert all(sub[k]["k_pages"] is p for k, p in zip(stages, pools))
    _, new = eng._jit_extend(eng.params, sub, jnp.ones((1, 32), jnp.int32),
                             jnp.asarray([32], jnp.int32))
    assert all(p.is_deleted() for p in pools)
    eng._write_slot(1, new, 48)
    assert all(eng.cache[k]["k_pages"] is new[k]["k_pages"] for k in stages)
    assert eng.cache["lengths"].tolist() == [0, 48]


def _prefilled_pair(prompts):
    """A paged (pallas) and a contiguous (reference) engine on the same
    three-layer f32 weights, each slot prefilled with the same prompt of
    ``prompts[slot]`` tokens and given room for one more."""
    base = get_config(ARCH)
    cfg = dataclasses.replace(
        base, compute_dtype="float32", n_layers=3,
        stages=simple_stages(base.stages[0].kind, 3))
    paged = ServingEngine(dataclasses.replace(cfg, kernels="auto"),
                          max_batch=len(prompts), max_len=256)
    contig = ServingEngine(dataclasses.replace(cfg, kernels="reference"),
                           params=paged.params, max_batch=len(prompts),
                           max_len=256)
    rng = np.random.default_rng(0)
    tokens = {slot: jnp.asarray(rng.integers(1, cfg.vocab, (1, n)),
                                jnp.int32) for slot, n in prompts.items()}
    for eng in (paged, contig):
        for slot, n in prompts.items():
            _, c1 = eng._jit_prefill(eng.params, tokens[slot],
                                     lengths=jnp.asarray([n], jnp.int32))
            eng._write_slot_from_prefill(slot, c1, n)
            eng.ensure_capacity(slot, n + 1)
            eng._tokens_buf[slot, 0] = 7 + slot
    return cfg, paged, contig


def test_paged_decode_writes_only_the_new_tokens():
    """One paged decode step changes the stacked pools exactly at
    ``(layer, page, offset)`` of each row's new token, in every layer, and
    writes there the K/V the contiguous reference engine stores for the
    same token; other pages, offsets and the scratch page are unchanged."""
    # slot 0's new token opens its second page; slot 1's lands mid-page
    prompts = {0: 64, 1: 37}
    cfg, paged, contig = _prefilled_pair(prompts)
    before = jax.tree_util.tree_map(np.asarray, paged.cache)
    for eng in (paged, contig):
        _, eng.cache = eng._jit_decode(eng.params, eng.cache,
                                       jnp.asarray(eng._tokens_buf))
    after = jax.tree_util.tree_map(np.asarray, paged.cache)
    table = after["block_table"]
    assert table[0, 1] != paged._scratch
    KV, dh = cfg.n_kv_heads, cfg.d_head
    stages = [k for k in after if k.startswith("stage")]
    for key in stages:
        for pool, kv in (("k_pages", "k"), ("v_pages", "v")):
            old, new = before[key][pool], after[key][pool]
            L, n_pages, ps, F = new.shape
            assert L == 3 and F == KV * dh
            want = np.zeros((L, n_pages, ps), bool)
            for slot, n in prompts.items():
                want[:, table[slot, n // ps], n % ps] = True
            np.testing.assert_array_equal((old != new).any(-1), want)
            ref_kv = np.asarray(contig.cache[key][kv])
            for slot, n in prompts.items():
                got = new[:, table[slot, n // ps], n % ps]
                np.testing.assert_allclose(
                    got.reshape(L, KV, dh), ref_kv[:, slot, n],
                    rtol=1e-5, atol=1e-5)


def test_paged_export_restore_round_trip():
    """A paged slot's export is the contiguous payload the reference
    engine exports for the same tokens, and restoring it into another
    slot reproduces it."""
    prompts = {0: 70, 1: 5}
    _, paged, contig = _prefilled_pair(prompts)
    n = prompts[0]
    got = paged._export_slot(0, n)
    want = contig._export_slot(0, n)
    stages = [k for k in paged.cache if k.startswith("stage")]
    for key in stages:
        for kv in ("k", "v"):
            np.testing.assert_allclose(got[key][kv][:, :n],
                                       want[key][kv][:, :n],
                                       rtol=1e-5, atol=1e-5)
    paged._release_slot(1)
    paged._restore_slot(1, got, n)
    again = paged._export_slot(1, n)
    for key in stages:
        for kv in ("k", "v"):
            np.testing.assert_array_equal(again[key][kv][:, :n],
                                          got[key][kv][:, :n])
