"""Real JAX execution substrate: jitted model calls over a slot KV cache.

``ServingEngine`` is deliberately *mechanism only*: it owns the params, the
slot-based KV cache, the jitted ``prefill``/``extend``/``decode`` closures,
the per-bucket slot copy plumbing (export/restore/subcache), and an
optional *real* radix prefix store (actual KV tensors keyed by token
prefix).  It makes no serving decisions and runs no loop of its own — the
unified runtime (``repro.runtime``) schedules every iteration and drives
this engine through ``JaxBackend.execute``.

The legacy one-request-at-a-time ``step()`` loop (and its private
queue/handoff state) was retired once the profiler started probing through
the runtime: ``repro.profiler.runtime_profiler`` measures the exact
``JaxBackend`` code paths production serving runs.

Hybrid emulation (paper §III, adapted to this container): compute is REAL —
every batch runs the actual jitted model on the local device and is
wall-clock timed; time is VIRTUAL — the runtime's shared event queue
advances by the measured latencies, so multi-instance configurations behave
as if instances ran in parallel even though this container has one CPU.
"""
from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ArchConfig
from repro.models import Model
from repro.obs.spans import span


@dataclasses.dataclass
class SpecDecodeCfg:
    """Speculative decoding for a real engine: draft model + verification.

    ``draft`` is the proposer's architecture (its own params, its own slot
    KV cache — built as a nested mechanism-only ``ServingEngine``); the
    target verifies all ``k`` proposals in one batched ``verify`` call
    (an ``extend`` that returns every position's logits).  With
    ``acceptance`` unset the engine is **greedy-lossless**: the emitted
    sequence equals vanilla greedy decode token-for-token (accepted
    prefix + the target's own bonus/correction token).  With an
    ``AcceptanceTrace`` attached, the acceptance *decision* is replayed
    from the trace instead (the spec-decode analogue of forced MoE
    routing) so sim/real parity can be pinned; ``recorder`` taps
    (position, accepted) pairs for artifact capture
    (``repro.spec.record``).
    """
    draft: ArchConfig
    k: int = 4
    acceptance: Optional[Any] = None      # repro.spec.AcceptanceTrace
    draft_seed: int = 1
    draft_params: Optional[Any] = None
    recorder: Optional[Any] = None        # repro.spec.AcceptanceRecorder


def _bucket(n: int, lo: int = 16) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


#: hotter tiers have lower rank; demotion only moves entries downward
_TIER_RANK = {"device": 0, "host": 1, "ssd": 2}


#: (paged pool, contiguous array) keys of a stage cache's K and V
_PAGED_KV = (("k_pages", "k"), ("v_pages", "v"))


# The stacked pools ``(L, n_pages, ps, KV*dh)`` are read and written one
# token row per (layer, token): indexed so, the pool keeps its row-major
# layout and a write lands in place.  Sliced over the layer dim instead,
# the TPU compiler moves the layer dim minor and copies the whole pool
# out of that layout (and back, for a write).

def _write_tokens(pool, page, off, kv):
    """Contiguous K or V ``(L, T, KV, dh)`` of T tokens into the pool at
    pages ``page`` and offsets ``off`` (each ``(T,)``)."""
    layers = jnp.arange(pool.shape[0])[:, None]
    return pool.at[layers, page, off].set(kv.reshape(kv.shape[:2] + (-1,)))


def _read_tokens(pool, page, off, dh: int):
    """The pool's K or V of T tokens at ``page``, ``off`` (each ``(T,)``)
    as the contiguous ``(L, T, KV, dh)``."""
    layers = jnp.arange(pool.shape[0])[:, None]
    rows = pool[layers, page, off]
    return rows.reshape(rows.shape[:2] + (-1, dh))


def _payload_to_host(payload: dict) -> dict:
    """Device -> host copy of a store entry (metadata keys pass through)."""
    return {k: v if k.startswith("_")
            else jax.tree_util.tree_map(np.asarray, v)
            for k, v in payload.items()}


def _payload_nbytes(payload: dict) -> float:
    data = {k: v for k, v in payload.items() if not k.startswith("_")}
    return float(sum(getattr(leaf, "nbytes", 0)
                     for leaf in jax.tree_util.tree_leaves(data)))


class RealRadixCache:
    """Real prefix cache: token-prefix -> stored KV slices, tier-tagged.

    Entries live on one of three tiers mirroring the runtime radix tree's
    block accounting: ``device`` (jax arrays, accelerator-resident — the
    insert default), ``host`` (numpy), ``ssd`` (pickled to a spill file;
    a matched stub is only read back through :meth:`resolve`, so the disk
    I/O lands inside the caller's wall-timed region).  Tier moves are
    driven by the runtime's eviction decisions via
    ``JaxBackend.on_tier_transfer`` — this class is mechanism only.
    Moves are entry-granular: demoting one radix block demotes every
    stored entry containing it (the payloads are whole-prefix slices,
    not per-block pages)."""

    def __init__(self, block: int = 16, max_entries: int = 64):
        self.block = block
        self.store: "OrderedDict[tuple, dict]" = OrderedDict()
        self.tier: Dict[tuple, str] = {}
        self.max_entries = max_entries
        self.hits = 0
        self.misses = 0
        self._ssd_dir: Optional[str] = None
        self._ssd_seq = 0

    def match(self, tokens,
              limit: Optional[int] = None) -> Tuple[int, Optional[dict]]:
        """Longest stored prefix of ``tokens`` (optionally capped at
        ``limit`` tokens, e.g. the runtime's radix-tree match length)."""
        best_len, best = 0, None
        n = (len(tokens) // self.block) * self.block
        if limit is not None:
            n = min(n, (limit // self.block) * self.block)
        for l in range(n, 0, -self.block):
            key = tuple(tokens[:l])
            if key in self.store:
                self.store.move_to_end(key)
                best_len, best = l, self.store[key]
                break
        if best is None:
            self.misses += 1
        else:
            self.hits += 1
        return best_len, best

    def insert(self, tokens, kv_slices: dict, tier: str = "device"):
        l = (len(tokens) // self.block) * self.block
        if l == 0:
            return
        key = tuple(tokens[:l])
        if key in self.store:
            return
        self.store[key] = kv_slices
        self.tier[key] = tier
        while len(self.store) > self.max_entries:
            old, payload = self.store.popitem(last=False)
            self.tier.pop(old, None)
            self._unlink(payload)

    # ---- tier moves (entry-granular; see class docstring) ----
    def _covering(self, prefix) -> list:
        p = tuple(prefix)
        n = len(p)
        return [k for k in list(self.store) if len(k) >= n and k[:n] == p]

    def demote(self, prefix, dst: str) -> float:
        """Move entries containing ``prefix`` down to ``dst`` ("host" |
        "ssd"); returns bytes actually moved."""
        moved = 0.0
        for k in self._covering(prefix):
            if _TIER_RANK.get(self.tier.get(k, "host"), 1) \
                    >= _TIER_RANK[dst]:
                continue
            host = _payload_to_host(self.resolve(self.store[k]))
            moved += _payload_nbytes(host)
            self._unlink(self.store[k])
            self.store[k] = host if dst == "host" else self._to_ssd(host)
            self.tier[k] = dst
        return moved

    def promote(self, prefix) -> float:
        """Bring entries containing ``prefix`` back to device arrays."""
        moved = 0.0
        for k in self._covering(prefix):
            if self.tier.get(k, "device") == "device":
                continue
            host = self.resolve(self.store[k])
            moved += _payload_nbytes(host)
            dev = {kk: v if kk.startswith("_")
                   else jax.tree_util.tree_map(jax.device_put, v)
                   for kk, v in host.items()}
            self._unlink(self.store[k])
            self.store[k] = dev
            self.tier[k] = "device"
        return moved

    def drop(self, prefix):
        for k in self._covering(prefix):
            payload = self.store.pop(k)
            self.tier.pop(k, None)
            self._unlink(payload)

    def resolve(self, payload: dict) -> dict:
        """Materialize a matched payload: SSD stubs are unpickled here, so
        call this inside the region whose wall time should absorb the
        disk read (``JaxBackend._prefill_chunk`` does)."""
        if isinstance(payload, dict) and "_ssd" in payload:
            import pickle
            with open(payload["_ssd"], "rb") as f:
                return pickle.load(f)
        return payload

    def residency(self) -> Dict[str, int]:
        out = {"device": 0, "host": 0, "ssd": 0}
        for k in self.store:
            out[self.tier.get(k, "device")] += 1
        return out

    def _to_ssd(self, host_payload: dict) -> dict:
        import os
        import pickle
        import tempfile
        if self._ssd_dir is None:
            self._ssd_dir = tempfile.mkdtemp(prefix="kv-ssd-")
        self._ssd_seq += 1
        path = os.path.join(self._ssd_dir, f"kv{self._ssd_seq}.pkl")
        with open(path, "wb") as f:
            pickle.dump(host_payload, f, protocol=pickle.HIGHEST_PROTOCOL)
        return {"_ssd": path,
                "_length": host_payload.get("_length"),
                "_length_bucket": host_payload.get("_length_bucket")}

    @staticmethod
    def _unlink(payload):
        path = payload.get("_ssd") if isinstance(payload, dict) else None
        if path:
            import os
            try:
                os.remove(path)
            except OSError:
                pass


class ServingEngine:
    """One instance's execution substrate (slots, jits, KV plumbing).

    Driven exclusively by ``repro.runtime.backends.jax_engine.JaxBackend``;
    see the module docstring for the division of labor.

    ``tp > 1`` makes the engine a tensor-parallel group: params and the
    slot KV cache are sharded over an explicit (data=1, model=tp) mesh
    (``repro.launch.mesh.make_engine_mesh``) using the production sharding
    rules (``repro.launch.sharding``), and every jit — prefill, extend,
    decode, and the slot-copy plumbing — runs SPMD over that mesh with
    GSPMD inserting the collectives.  On CPU this is validated by forcing
    host device count (``XLA_FLAGS=--xla_force_host_platform_device_count``).
    """

    def __init__(self, cfg: ArchConfig, params=None, *, max_batch: int = 8,
                 max_len: int = 512, prefix_cache: bool = False,
                 role: str = "unified", name: str = "engine0", seed: int = 0,
                 tp: int = 1, routing=None, spec: Optional[SpecDecodeCfg]
                 = None):
        self.cfg = cfg
        self.name = name
        self.role = role
        self.tp = max(int(tp), 1)
        self.mesh = None
        # MoE routing injection must happen here, before any jit traces:
        # the jitted closures capture the model's routing hook, so a hook
        # installed later would be silently ignored by cached traces.
        # ``routing`` is either an ExpertRoutingTrace (replayed verbatim —
        # forced assignment — and remembered so JaxBackend accounts
        # expert-load metrics from the same table) or a raw hook callable
        # (bias / recording; see repro.moe.hooks).
        self.routing_trace = None
        hook = None
        if routing is not None:
            if callable(routing):
                hook = routing
            else:
                from repro.moe.hooks import make_replay_hook
                from repro.moe.trace import moe_layer_count
                routing.check_model(cfg)
                if routing.n_layers != moe_layer_count(cfg):
                    raise ValueError(
                        f"routing trace {routing.model!r} has "
                        f"{routing.n_layers} MoE layers but {cfg.name!r} "
                        f"has {moe_layer_count(cfg)}")
                self.routing_trace = routing
                hook = make_replay_hook(routing)
        # kernel backend: resolve against the platform; pallas serves
        # attention-only archs at tp=1 (its decode path is the paged
        # slot-KV layout, which has no sharded variant yet).  A config
        # the kernels cannot serve is an error: the reference path runs
        # only when the config names it.
        from repro.configs.base import ATTN_MLP, ATTN_MOE
        from repro.kernels import resolve_backend
        backend, interpret = resolve_backend(cfg.kernels)
        if backend == "pallas":
            bad = [st.kind for st in cfg.stages
                   if st.kind not in (ATTN_MLP, ATTN_MOE)]
            if bad or self.tp > 1:
                why = f"tp={self.tp}" if self.tp > 1 else \
                    f"non-attention stages {bad}"
                raise ValueError(
                    f"kernels={cfg.kernels!r} cannot serve {why} on "
                    f"{cfg.name!r}; set kernels='reference' explicitly")
        self.kernel_backend = backend
        self.pallas_interpret = interpret
        self.paged = backend == "pallas"
        self.page_size = 64
        self.model = Model(cfg, remat=False, routing_hook=hook,
                           kernel_backend=backend,
                           pallas_interpret=interpret, paged=self.paged,
                           page_size=self.page_size)
        self.max_batch = max_batch
        self.max_len = max_len
        if self.tp > 1:
            self._shard_over_mesh(params, seed)
        else:
            self.params = params if params is not None else \
                self.model.init(jax.random.PRNGKey(seed))
            self.cache = self.model.init_cache(max_batch, max_len)
        if self.paged:
            # page allocator: free-list over the shared pool, a host
            # numpy mirror of the device block table, and per-slot
            # allocation counts.  The last pool index is the scratch
            # page — never allocated, absorbs every masked garbage write.
            self._maxp, self._n_pages = self.model.page_geometry(
                max_batch, max_len)
            self._scratch = self._n_pages - 1
            self._page_free = list(range(self._n_pages - 1))
            self._table_np = np.full((max_batch, self._maxp),
                                     self._scratch, np.int32)
            self._slot_pages = [0] * max_batch
        self.slot_free = list(range(max_batch))
        self.radix = RealRadixCache() if prefix_cache else None
        self._jit_decode = jax.jit(self.model.decode)
        self._jit_prefill = jax.jit(self.model.prefill,
                                    static_argnames=())
        # the extended (sub)cache is donated: a paged subcache shares the
        # live page pools, so the extend writes them in place instead of
        # copying a whole pool per chunk.  Callers adopt the returned
        # cache through ``_write_slot``.
        self._jit_extend = jax.jit(self.model.extend, donate_argnums=(1,))
        self._tokens_buf = np.zeros((max_batch, 1), np.int32)
        # speculative decoding: a nested mechanism-only draft engine
        # (same slot geometry, so draft slot i mirrors target slot i) and
        # the target-side batched verification jit.  The draft engine is
        # plain (tp=1, no prefix cache, no spec of its own); JaxBackend
        # orchestrates the propose/verify/rollback steps.
        self.spec = spec
        self.draft = None
        self._jit_verify = None
        if spec is not None:
            if routing is not None:
                raise ValueError(
                    "speculative decoding and trace-driven MoE routing "
                    "cannot be combined on one engine (draft tokens that "
                    "fail verification have no expert-load semantics)")
            if spec.k < 1:
                raise ValueError(f"spec.k must be >= 1, got {spec.k}")
            if spec.draft.vocab != cfg.vocab:
                raise ValueError(
                    f"draft {spec.draft.name!r} has vocab "
                    f"{spec.draft.vocab} but target {cfg.name!r} has "
                    f"{cfg.vocab}; draft/target token ids must line up")
            if spec.acceptance is not None:
                spec.acceptance.validate().check_k(spec.k)
            self.draft = ServingEngine(
                spec.draft, params=spec.draft_params, max_batch=max_batch,
                max_len=max_len, name=f"{name}.draft",
                seed=spec.draft_seed)
            self._jit_verify = jax.jit(self.model.verify)

    def _shard_over_mesh(self, params, seed: int):
        """Lay params + slot cache out over the (data=1, model=tp) mesh.

        Uses the same PartitionSpec rules as the production launcher
        (params: column/row TP; KV: heads or head_dim on the model axis),
        post-passed by ``fit_to_mesh`` so dims that do not divide the tp
        degree are replicated explicitly.  Fresh params and the cache are
        built directly in that layout (a model that needs tp devices does
        not fit one); given params are moved into it.  The jits then pick
        the committed shardings up from their inputs — no per-jit
        in_shardings needed.
        """
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch import sharding as shd
        from repro.launch.mesh import make_engine_mesh
        self.mesh = make_engine_mesh(self.tp)

        def shardings(tree, spec_tree):
            fitted = shd.fit_to_mesh(spec_tree, tree, self.mesh)
            return jax.tree_util.tree_map(
                lambda s: NamedSharding(self.mesh, s), fitted,
                is_leaf=lambda x: isinstance(x, P))

        key = jax.random.PRNGKey(seed)
        shapes = jax.eval_shape(self.model.init, key) if params is None \
            else params
        out = shardings(shapes, shd.param_pspecs(shapes, model_size=self.tp))
        self.params = self.model.init(key, out_shardings=out) \
            if params is None else jax.device_put(params, out)

        def new_cache():
            return self.model.init_cache(self.max_batch, self.max_len)
        shapes = jax.eval_shape(new_cache)
        self.cache = jax.jit(new_cache, out_shardings=shardings(
            shapes, shd.cache_pspecs(shapes, ("data",), self.max_batch,
                                     model_size=self.tp)))()

    def warmup(self, buckets=(16, 32, 64, 128, 256)):
        """Compile prefill/extend/decode at every bucket so measured
        iteration latencies are steady-state (compile time excluded).
        ``JaxBackend.warmup`` extends this with chunked-prefill extend
        buckets and slot export/restore jits."""
        for P in buckets:
            if P >= self.max_len:
                continue
            pad = jnp.zeros((1, P), jnp.int32)
            lengths = jnp.asarray([P], jnp.int32)
            jax.block_until_ready(
                self._jit_prefill(self.params, pad, lengths=lengths))
            if self.radix is not None:
                sub = self._slot_subcache(0, 16)
                try:
                    _, sub = self._jit_extend(self.params, sub, pad,
                                              jnp.asarray([P], jnp.int32))
                    self._write_slot(0, sub, 16)
                    self._release_slot(0)
                    jax.block_until_ready(self.cache)
                except NotImplementedError:
                    pass
        jax.block_until_ready(self._jit_decode(
            self.params, self.cache, jnp.asarray(self._tokens_buf)))

    # ---- jitted slot/cache plumbing ----
    # eager per-op dispatch costs ~ms on CPU; these helpers are jitted per
    # bucket size with cache donation so slot copies stay O(slice).
    def _get_jit(self, kind: str, key):
        jits = getattr(self, "_slot_jits", None)
        if jits is None:
            jits = self._slot_jits = {}
        return jits.get((kind, key))

    def _put_jit(self, kind: str, key, fn):
        self._slot_jits[(kind, key)] = fn
        return fn

    # ---- paged-KV allocator (no-ops on the contiguous layout) ----
    def ensure_capacity(self, slot: int, length: int):
        """Grow ``slot``'s page allocation to cover ``length`` tokens.
        Called by JaxBackend before any write that lands past the current
        allocation (decode at the old length, spec verify's window,
        chunked-prefill extends); free-list capacity is exact — every slot
        can hold its full ``maxp`` pages simultaneously."""
        if not self.paged:
            return
        need = min(-(-length // self.page_size), self._maxp)
        have = self._slot_pages[slot]
        if need <= have:
            return
        for j in range(have, need):
            self._table_np[slot, j] = self._page_free.pop()
        self._slot_pages[slot] = need
        self._push_table()

    def _push_table(self):
        with span("backend.prepare",
                  pages=self._n_pages - 1 - len(self._page_free)):
            self.cache["block_table"] = jnp.asarray(self._table_np)

    def _free_pages(self, slot: int):
        if not self.paged or not self._slot_pages[slot]:
            return
        for j in range(self._slot_pages[slot]):
            self._page_free.append(int(self._table_np[slot, j]))
            self._table_np[slot, j] = self._scratch
        self._slot_pages[slot] = 0
        self._push_table()

    def _release_slot(self, slot: int):
        if slot not in self.slot_free:
            self.slot_free.append(slot)
        # zero the slot length
        self.cache["lengths"] = self.cache["lengths"].at[slot].set(0)
        self._free_pages(slot)

    def _write_slot_from_prefill(self, slot: int, cache1, n: int):
        """Copy a (B=1) prefill cache into slot ``slot`` of the big cache."""
        P = None
        for leaf in jax.tree_util.tree_leaves(cache1):
            if leaf.ndim >= 3 and leaf.shape[1] == 1:
                P = leaf.shape[2]
                break
        if self.paged:
            # prefill itself ran contiguous (flash over the chunk); the
            # engine owns the page layout, so scatter the (B=1) cache
            # through the slot's freshly-allocated table row.  Pad-tail
            # positions past the allocation route to the scratch page.
            self.ensure_capacity(slot, min(P, self.max_len))
            fn = self._get_jit("write_prefill_paged", P)
            if fn is None:
                ps, maxp, scratch = self.page_size, self._maxp, self._scratch

                def write_prefill_paged(cache, cache1, slot, n):
                    row = cache["block_table"][slot]
                    pos = jnp.arange(P)
                    pidx = pos // ps
                    page = row[jnp.minimum(pidx, maxp - 1)]
                    page = jnp.where(pidx < maxp, page, scratch)
                    off = pos % ps
                    out = dict(cache)
                    for key in cache:
                        if key in ("lengths", "block_table"):
                            continue
                        out[key] = {
                            name: _write_tokens(cache[key][name], page, off,
                                                cache1[key][kv][:, 0])
                            for name, kv in _PAGED_KV}
                    out["lengths"] = cache["lengths"].at[slot].set(n)
                    return out
                # the slot is traced (its table row is read on the
                # device), so one program per bucket serves every slot
                fn = self._put_jit("write_prefill_paged", P, jax.jit(
                    write_prefill_paged, donate_argnums=(0,)))
            self.cache = fn(self.cache, cache1, slot, n)
            return
        fn = self._get_jit("write_prefill", P)
        if fn is None:
            def write_prefill(cache, cache1, slot, n):
                def write(big, small):
                    if big.ndim >= 2 and small.shape[1] == 1:
                        if big.ndim >= 3 and small.ndim >= 3 \
                                and small.shape[2] <= big.shape[2] \
                                and big.shape[2] == self.max_len:
                            pad_len = small.shape[2]
                            return big.at[:, slot, :pad_len].set(small[:, 0])
                        return big.at[:, slot].set(small[:, 0])
                    return big
                out = dict(cache)
                for key in cache:
                    if key == "lengths":
                        continue
                    out[key] = jax.tree_util.tree_map(
                        write, cache[key], cache1[key])
                out["lengths"] = cache["lengths"].at[slot].set(n)
                return out
            fn = self._put_jit("write_prefill", P, jax.jit(
                write_prefill, donate_argnums=(0,), static_argnums=(2,)))
        self.cache = fn(self.cache, cache1, slot, n)

    def _slot_subcache(self, slot: int, length: int):
        """A (B=1) view of one slot (full max_len buffers, real length)."""
        if self.paged:
            # zero-copy: the shared pools ARE the storage; the one-row
            # table is the view.  The pools pass by reference (a jit
            # returning them unchanged would copy each one), and
            # ``extend`` on this subcache scatters straight into the
            # slot's pages.
            fn = self._get_jit("subcache_paged", None)
            if fn is None:
                def subcache_paged(table, slot, length):
                    return (jax.lax.dynamic_slice_in_dim(table, slot, 1),
                            jnp.full((1,), length, jnp.int32))
                fn = self._put_jit("subcache_paged", None,
                                   jax.jit(subcache_paged))
            table, lengths = fn(self.cache["block_table"], slot, length)
            return {**self.cache, "block_table": table, "lengths": lengths}
        fn = self._get_jit("subcache", None)
        if fn is None:
            def subcache(cache, slot, length):
                def take(big):
                    return big[:, slot: slot + 1] if big.ndim >= 2 else big
                sub = {}
                for key in cache:
                    if key == "lengths":
                        sub[key] = jnp.full((1,), length, jnp.int32)
                    else:
                        sub[key] = jax.tree_util.tree_map(take, cache[key])
                return sub
            fn = self._put_jit("subcache", None,
                               jax.jit(subcache, static_argnums=(1,)))
        return fn(self.cache, slot, length)

    def _write_slot(self, slot: int, sub_cache, n: int):
        if self.paged:
            # the subcache's pools already hold the extend's writes
            # (shared storage): adopt them by reference, as a jit
            # returning them would copy each one, and bump the slot
            # length.
            self.cache = {**sub_cache,
                          "block_table": self.cache["block_table"],
                          "lengths": self.cache["lengths"].at[slot].set(n)}
            return
        fn = self._get_jit("write_slot", None)
        if fn is None:
            def write_slot(cache, sub, slot, n):
                def write(big, small):
                    return big.at[:, slot: slot + 1].set(small) \
                        if big.ndim >= 2 else big
                out = dict(cache)
                for key in cache:
                    if key == "lengths":
                        continue
                    out[key] = jax.tree_util.tree_map(
                        write, cache[key], sub[key])
                out["lengths"] = cache["lengths"].at[slot].set(n)
                return out
            fn = self._put_jit("write_slot", None, jax.jit(
                write_slot, donate_argnums=(0,), static_argnums=(2,)))
        self.cache = fn(self.cache, sub_cache, slot, n)

    def _export_slot(self, slot: int, length: int,
                     to_host: bool = True) -> dict:
        """Copy a slot's KV out (prefix cache / P/D).  Device-side gather
        is jitted per bucketed length; ``to_host=True`` adds the final
        np.asarray host copy, ``to_host=False`` keeps the gathered jax
        arrays device-resident (the prefix store's hot tier)."""
        blen = _bucket(length)
        blen = min(blen, self.max_len)
        if self.paged:
            # normalize to the contiguous ("k"/"v") payload so prefix
            # store entries and P/D handoffs interoperate across layouts
            fn = self._get_jit("export_paged", blen)
            if fn is None:
                ps, dh = self.page_size, self.cfg.d_head

                def export_paged(cache, slot):
                    pos = jnp.arange(blen)
                    page = cache["block_table"][slot, pos // ps]
                    off = pos % ps
                    out = {}
                    for key in cache:
                        if key in ("lengths", "block_table"):
                            continue
                        out[key] = {
                            kv: _read_tokens(cache[key][name], page, off, dh)
                            for name, kv in _PAGED_KV}
                    return out
                fn = self._put_jit("export_paged", blen,
                                   jax.jit(export_paged,
                                           static_argnums=(1,)))
            dev = fn(self.cache, slot)
            out = jax.tree_util.tree_map(np.asarray, dev) if to_host \
                else dict(dev)
            out["_length"] = length
            out["_length_bucket"] = blen
            return out
        fn = self._get_jit("export", blen)
        if fn is None:
            def export(cache, slot):
                def take(big):
                    if big.ndim >= 3 and big.shape[2] == self.max_len:
                        return jax.lax.dynamic_slice_in_dim(
                            big[:, slot], 0, blen, axis=1)
                    if big.ndim >= 2:
                        return big[:, slot]
                    return big
                return {key: jax.tree_util.tree_map(take, cache[key])
                        for key in cache if key != "lengths"}
            fn = self._put_jit("export", blen,
                               jax.jit(export, static_argnums=(1,)))
        dev = fn(self.cache, slot)
        out = jax.tree_util.tree_map(np.asarray, dev) if to_host \
            else dict(dev)
        out["_length"] = length
        out["_length_bucket"] = blen
        return out

    def _restore_slot(self, slot: int, kv: dict, length: int):
        blen = kv.get("_length_bucket")
        if blen is None:   # legacy export: derive from the stored arrays
            for leaf in jax.tree_util.tree_leaves(
                    {k: v for k, v in kv.items()
                     if not k.startswith("_")}):
                if leaf.ndim >= 2 and leaf.shape[1] not in (1,) and \
                        leaf.shape[1] <= self.max_len and leaf.shape[1] >= 8:
                    blen = leaf.shape[1]
                    break
        if self.paged:
            # payload is the normalized contiguous layout (possibly from a
            # contiguous peer — P/D across layouts); scatter it through
            # the slot's freshly-allocated table row
            self.ensure_capacity(slot, blen)
            fn = self._get_jit("restore_paged", blen)
            if fn is None:
                ps, maxp, scratch = self.page_size, self._maxp, self._scratch

                def restore_paged(cache, kv, slot, n):
                    row = cache["block_table"][slot]
                    pos = jnp.arange(blen)
                    pidx = pos // ps
                    page = row[jnp.minimum(pidx, maxp - 1)]
                    page = jnp.where(pidx < maxp, page, scratch)
                    off = pos % ps
                    out = dict(cache)
                    for key in cache:
                        if key in ("lengths", "block_table"):
                            continue
                        out[key] = {
                            name: _write_tokens(cache[key][name], page, off,
                                                kv[key][k])
                            for name, k in _PAGED_KV}
                    out["lengths"] = cache["lengths"].at[slot].set(n)
                    return out
                fn = self._put_jit("restore_paged", blen, jax.jit(
                    restore_paged, donate_argnums=(0,),
                    static_argnums=(2,)))
            kvdev = {k: v for k, v in kv.items() if not k.startswith("_")}
            self.cache = fn(self.cache, kvdev, slot, length)
            return
        fn = self._get_jit("restore", blen)
        if fn is None:
            def restore(cache, kv, slot, n):
                def write(big, small):
                    if big.ndim >= 3 and big.shape[2] == self.max_len \
                            and small.ndim >= 2 and small.shape[1] == blen:
                        return big.at[:, slot, :blen].set(small)
                    if big.ndim >= 2:
                        return big.at[:, slot].set(small)
                    return big
                out = dict(cache)
                for key in cache:
                    if key == "lengths":
                        continue
                    out[key] = jax.tree_util.tree_map(
                        write, cache[key], kv[key])
                out["lengths"] = cache["lengths"].at[slot].set(n)
                return out
            fn = self._put_jit("restore", blen, jax.jit(
                restore, donate_argnums=(0,), static_argnums=(2,)))
        kvdev = {k: v for k, v in kv.items() if not k.startswith("_")}
        self.cache = fn(self.cache, kvdev, slot, length)
