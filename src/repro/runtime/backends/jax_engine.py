"""Real-execution backend: jitted prefill/extend/decode over slot KV.

Wraps a ``repro.serve.engine.ServingEngine`` purely as a *KV mechanism*
(slot cache, jitted model calls, export/restore plumbing).  All serving
decisions — admission, chunking, decode composition, preemption, prefix
policy, P/D handoff — come from the unified runtime, so the real engine
gains chunked prefill, SJF, preemption and every registered routing policy
for free.

Hybrid emulation is preserved: compute is REAL (wall-clock timed on the
local device), time is VIRTUAL (the runtime's shared event queue advances
by the measured latencies), exactly the paper's §III methodology adapted to
this container.

Chunked prefill maps onto the model API naturally: the first chunk runs the
bucketed ``prefill`` kernel; subsequent chunks ``extend`` the slot's
subcache.  One batched ``decode`` serves all scheduled decode slots per
iteration (the full-buffer decode the engine always ran).
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from repro.core.config import InstanceCfg
from repro.core.memory import MemoryModel
from repro.core.request import SimRequest
from repro.obs.events import SPEC_STEP
from repro.obs.spans import bind_profiler, span
from repro.runtime.backend import KvHandoff
from repro.runtime.prefix_cache import MatchResult
from repro.runtime.scheduler import ScheduledWork


class JaxBackend:
    name = "jax"

    def __init__(self, engine, cfg: InstanceCfg):
        # late imports: the sim path must not pay for jax
        import jax  # noqa: F401
        # host spans of the served path go on the profiler's clock
        bind_profiler()
        self.eng = engine
        self.cfg = cfg
        self.memory = MemoryModel(cfg)
        self._slot: Dict[int, int] = {}      # req_id -> engine slot
        self._len: Dict[int, int] = {}       # slot   -> tokens held in KV
        self._restore: Dict[int, tuple] = {} # req_id -> (payload, length)
        self._iterations = 0
        # real work done outside execute() (prefix store, P/D export) is
        # wall-timed and charged to the next iteration
        self._carry_s = 0.0
        # event recorder, wired by RuntimeInstance.attach_obs.  The real
        # engine emits the same schema as the sim; restore cost is folded
        # into the wall-timed iteration, so kv_restore reports 0 seconds
        self.obs = None
        self.last_restore_s = 0.0
        # KV-tier accounting: restores counted at match time (mirrors
        # SimBackend), tier moves measured as they execute on the store
        self._restored_tokens = 0
        self._restore_events = 0
        self._tier_moves = 0
        self._tier_move_s = 0.0
        # expert-load accounting for a replayed ExpertRoutingTrace: the
        # engine's replay hook forces every token's assignment in-graph
        # (ServingEngine(routing=trace)); this mirror maps the *executed
        # slot positions* — tracked independently of the scheduler's
        # bookkeeping — through the same table, so the metrics state what
        # really routed and the parity suite can pin sim == real.  The
        # engine's own trace is the only valid source: a cfg-named trace
        # the engine does not replay would make these metrics fiction
        # (the model routed with its learned router), so that mismatch is
        # an error, not a fallback.
        from repro.moe import ExpertLoadTracker, resolve_routing
        self.routing = getattr(engine, "routing_trace", None)
        # output-token capture: req_id -> emitted token ids, in order.
        # Cheap, always on — it is what the greedy-losslessness suite
        # compares (speculative vs vanilla emission, token-for-token).
        self.out_tokens: Dict[int, List[int]] = {}
        # speculative decoding: the engine carries the mechanism (draft
        # engine + verify jit, ServingEngine(spec=...)); this backend
        # orchestrates propose/verify/rollback per scheduled iteration
        # and accounts metrics()["spec_decode"].  Mirrors the MoE rule:
        # a cfg that names spec decoding the engine does not run (or a
        # different acceptance trace than the engine replays) is a hard
        # error, never silently-diverging accounting.
        self.spec = getattr(engine, "spec", None)
        self.spec_tracker = None
        if getattr(cfg.spec, "enabled", False) \
                or getattr(cfg.spec, "acceptance_trace", None):
            if self.spec is None:
                raise ValueError(
                    f"instance {cfg.name!r} configures speculative "
                    f"decoding but its engine has no draft; build it "
                    f"with ServingEngine(spec=SpecDecodeCfg(...)) so the "
                    f"scheduler's multi-token accounting matches what "
                    f"actually executes")
        if self.spec is not None:
            from repro.spec import SpecDecodeTracker, resolve_acceptance
            if cfg.spec.acceptance_trace:
                named = resolve_acceptance(cfg)
                if self.spec.acceptance is None:
                    raise ValueError(
                        f"instance {cfg.name!r} names acceptance_trace="
                        f"{cfg.spec.acceptance_trace!r} but its engine "
                        f"replays no trace; build it with ServingEngine("
                        f"spec=SpecDecodeCfg(acceptance=<trace>)) so the "
                        f"reported spec_decode is what actually ran")
                if named is not self.spec.acceptance \
                        and named.to_json() != self.spec.acceptance.to_json():
                    raise ValueError(
                        f"instance {cfg.name!r} names acceptance_trace="
                        f"{cfg.spec.acceptance_trace!r} but its engine "
                        f"replays a different trace; the accounting "
                        f"table must be the one the engine draws from")
            dt = cfg.scheduler.decode_tokens
            if dt != self.spec.k + 1:
                raise ValueError(
                    f"instance {cfg.name!r} speculates k={self.spec.k} "
                    f"but its scheduler reserves decode_tokens={dt}; set "
                    f"SchedulerCfg(decode_tokens=k + 1) (engine_instance_"
                    f"cfg does this automatically) so the KV ledger "
                    f"covers the verification window")
            self.spec_tracker = SpecDecodeTracker(self.spec.k)
        # spec bookkeeping, all keyed by engine slot and tracked
        # independently of the scheduler (that independence is what the
        # sim/real parity suite tests): token history in target KV,
        # draft KV length, emitted-token count
        self._hist: Dict[int, List[int]] = {}
        self._draft_len: Dict[int, int] = {}
        self._emit: Dict[int, int] = {}
        self._steps: Dict[int, int] = {}     # slot -> spec-step ordinal
        self._emitted: Dict[int, int] = {}   # req_id -> last step's tokens
        if getattr(cfg.moe, "routing_trace", None):
            if self.routing is None:
                raise ValueError(
                    f"instance {cfg.name!r} names routing_trace="
                    f"{cfg.moe.routing_trace!r} but its engine replays no "
                    f"trace; build it with ServingEngine(routing=<trace>) "
                    f"so the reported expert_load is what actually routed")
            named = resolve_routing(cfg)
            if named is not self.routing \
                    and named.to_json() != self.routing.to_json():
                raise ValueError(
                    f"instance {cfg.name!r} names routing_trace="
                    f"{cfg.moe.routing_trace!r} but its engine replays a "
                    f"different trace ({self.routing.model!r}); the "
                    f"accounting table must be the one the model executes")
        self.expert_load = ExpertLoadTracker(
            self.routing, ep=cfg.parallelism.ep,
            capacity_factor=engine.cfg.moe.capacity_factor
            if engine.cfg.moe is not None else None) \
            if self.routing is not None else None
        self._routed_pos: List[int] = []     # positions routed this iter

    # ---- helpers ----
    def prompt_cap(self, req: SimRequest) -> int:
        """Slot capacity: prompt + generated output + 1 must fit max_len.
        The runtime truncates the request on submit, so the scheduler's
        chunk plan and the backend's KV state always agree.  Speculative
        decoding additionally writes up to k draft rows past the accepted
        context before rollback, so the window shrinks by k."""
        extra = self.eng.spec.k if self.eng.spec is not None else 0
        return max(self.eng.max_len - req.output_len - 1 - extra, 1)

    def _prompt(self, req: SimRequest) -> List[int]:
        toks = list(req.prompt_tokens)
        cap = self.prompt_cap(req)
        return toks[:cap] if len(toks) > cap else toks

    def warmup(self):
        import jax
        import jax.numpy as jnp
        from repro.serve.engine import _bucket
        eng = self.eng
        eng.warmup()
        sched = self.cfg.scheduler
        if sched.chunked_prefill or eng.radix is not None:
            # chunk 2+ of a chunked prefill (and any prefix-hit suffix)
            # runs the ``extend`` path, which compiles one jit per padded
            # chunk bucket; pre-warm every bucket a chunk can map to so
            # measured latencies are steady-state from the first request
            top = _bucket(min(max(sched.prefill_chunk, 16),
                              eng.max_len - 1)) \
                if sched.chunked_prefill else eng.max_len - 1
            P = 16
            while P <= top and P < eng.max_len:
                pad = jnp.zeros((1, P), jnp.int32)
                try:
                    sub = eng._slot_subcache(0, 16)
                    _, sub = eng._jit_extend(eng.params, sub, pad,
                                             jnp.asarray([P], jnp.int32))
                    # the chunk write-back (slot update) compiles once;
                    # the extend donated ``sub``, so adopt its output
                    eng._write_slot(0, sub, 16)
                    jax.block_until_ready(eng.cache)
                except NotImplementedError:
                    break   # no cached-prefill path (e.g. xLSTM)
                P *= 2
            eng._release_slot(0)
        if eng.radix is not None:
            # pre-compile the slot export/restore jits at every bucket so
            # prefix-cache hits don't pay compile time on the virtual clock
            for blen in (16, 32, 64, 128, 256):
                if blen >= eng.max_len:
                    break
                payload = eng._export_slot(0, blen)
                eng._restore_slot(0, payload, blen)
            eng._release_slot(0)
        if eng.spec is not None:
            # draft prefill/decode buckets + the one verify shape
            eng.draft.warmup()
            vt = jnp.zeros((eng.max_batch, eng.spec.k + 1), jnp.int32)
            n0 = jnp.zeros((eng.max_batch,), jnp.int32)
            jax.block_until_ready(
                eng._jit_verify(eng.params, eng.cache, vt, n0)[0])

    # ---- execution ----
    def execute(self, work: List[ScheduledWork], now: float) -> float:
        import jax
        t0 = time.perf_counter()
        decodes = [w for w in work if w.phase == "decode"]
        prefills = [w for w in work if w.phase == "prefill"]
        if decodes:
            if self.eng.spec is not None:
                self._spec_decode_step(decodes, now)
            else:
                self._decode_step(decodes)
        for w in prefills:
            self._prefill_chunk(w)
        with span("backend.sync"):
            jax.block_until_ready(self.eng.cache)
        self._iterations += 1
        latency = time.perf_counter() - t0 + self._carry_s
        self._carry_s = 0.0
        if self.expert_load is not None:
            self.expert_load.observe(self._routed_pos, now)
            self._routed_pos = []
        return latency

    def _decode_step(self, decodes: List[ScheduledWork]):
        import jax.numpy as jnp
        from repro.serve.sampler import greedy
        eng = self.eng
        rows = len(decodes)
        with span("backend.prepare", rows=rows):
            tokens = eng._tokens_buf
            for w in decodes:
                # paged KV: the decode writes each scheduled slot's new
                # token at its old length — make sure that page exists
                # (no-op on the contiguous layout)
                slot = self._slot[w.request.req_id]
                eng.ensure_capacity(slot, self._len[slot] + 1)
            if self.routing is not None or self.eng.model.routing_hook \
                    is not None:
                # routing-hook runs: mark every NON-scheduled slot (free,
                # or occupied mid-prefill) with the sentinel token -1 so
                # the model's decode mask excludes its row from MoE
                # recording and capacity — the full-buffer decode
                # computes it regardless, but it is not workload routing.
                # The engine buffer itself is left untouched (mid-prefill
                # slots keep their pending first token).
                tokens = tokens.copy()
                scheduled_slots = {self._slot[w.request.req_id]
                                   for w in decodes}
                for slot in range(eng.max_batch):
                    if slot not in scheduled_slots:
                        tokens[slot, 0] = -1
            tokens = jnp.asarray(tokens)
        with span("backend.launch", program="decode", rows=rows):
            logits, eng.cache = eng._jit_decode(eng.params, eng.cache,
                                                tokens)
        with span("backend.sample", rows=rows):
            nxt = np.asarray(greedy(logits, eng.cfg.vocab))
            scheduled = set()
            for w in decodes:
                slot = self._slot[w.request.req_id]
                eng._tokens_buf[slot, 0] = int(nxt[slot, 0])
                self.out_tokens.setdefault(w.request.req_id, []).append(
                    int(nxt[slot, 0]))
                if self.expert_load is not None:
                    # the decode wrote this slot's token at KV index _len
                    self._routed_pos.append(self._len[slot])
                self._len[slot] += 1
                scheduled.add(slot)
            hooked = self.routing is not None \
                or eng.model.routing_hook is not None
            refresh = scheduled != set(self._len) \
                or (hooked and len(self._len) < eng.max_batch)
        if refresh:
            # the full-buffer decode bumped every slot's length; restore
            # the authoritative lengths of mid-prefill / unscheduled
            # slots.  With a MoE routing hook installed, ALSO zero the
            # free slots every iteration: free slots may otherwise keep
            # garbage lengths (harmless for attention — nothing reads
            # them), but the hook's validity mask identifies an empty
            # slot by its zero length (position 0), and letting the bump
            # accumulate across consecutive decode-only iterations would
            # mark phantom rows valid — contaminating recorded routing
            # traces and letting empty slots consume real tokens' expert
            # capacity under forced replay.  Unhooked engines keep the
            # old fast path.
            with span("backend.prepare", rows=rows):
                lengths = np.zeros((eng.max_batch,), np.int32)
                for s, n in self._len.items():
                    lengths[s] = n
                eng.cache["lengths"] = jnp.asarray(lengths)

    def _spec_decode_step(self, decodes: List[ScheduledWork], now: float):
        """One speculative iteration for the scheduled decode set: the
        draft proposes k tokens per slot (k + 1 sequential full-buffer
        draft decodes — the extra call consumes the last proposal so the
        draft KV stays one-pending-token behind, exactly like the
        target), the target verifies all proposals in one batched
        ``verify`` (an extend returning every position's logits), and
        each slot keeps the accepted prefix + the target's bonus token,
        rolling both KV lengths back to the accepted context.

        Acceptance is the true greedy match (lossless) unless the engine
        replays an ``AcceptanceTrace``, in which case the decision is
        forced from the trace's deterministic draw at this slot's emitted
        position — the spec-decode analogue of forced MoE routing, and
        what the sim/real parity suite pins.
        """
        import jax.numpy as jnp
        from repro.serve.sampler import accept_length, greedy
        eng = self.eng
        dr = eng.draft
        k = eng.spec.k
        trace = eng.spec.acceptance
        recorder = eng.spec.recorder

        # 1. draft context sync: (re)build a slot's draft KV from the
        # token history whenever it diverged (first spec step, preemption
        # restart, P/D arrival) — one bucketed draft prefill per slot
        for w in decodes:
            slot = self._slot[w.request.req_id]
            hist = self._hist[slot]
            if self._draft_len.get(slot) != len(hist):
                from repro.serve.engine import _bucket
                P = _bucket(max(len(hist), 1))
                pad = np.zeros((1, P), np.int32)
                pad[0, :len(hist)] = np.asarray(hist, np.int32)
                rid = w.request.req_id
                with span("backend.launch", program="draft.prefill",
                          req=rid, tokens=len(hist)):
                    _, c1 = dr._jit_prefill(
                        dr.params, jnp.asarray(pad),
                        lengths=jnp.asarray([len(hist)], jnp.int32))
                with span("backend.launch", program="draft.write_prefill",
                          req=rid, tokens=len(hist)):
                    dr._write_slot_from_prefill(slot, c1, len(hist))
                self._draft_len[slot] = len(hist)

        # tail clamp: a request with r = output_len - generated tokens
        # left can emit at most r per step (accepted + bonus), so it only
        # uses min(k, r - 1) drafts.  Clamping the proposal window — not
        # just the emission — keeps the verified positions meaningful and
        # matches SimBackend's pricing of the same step exactly.
        k_eff = {}
        for w in decodes:
            req = w.request
            k_eff[self._slot[req.req_id]] = max(
                0, min(k, req.output_len - req.generated - 1))
        k_step = max(k_eff.values(), default=0)

        # paged KV: verify writes the pending token + k_eff drafts at
        # positions [len, len + k_eff]; the draft's k_step + 1 decodes
        # walk one position per call (no-ops on contiguous layouts)
        rows = len(decodes)
        with span("backend.prepare", rows=rows):
            for w in decodes:
                slot = self._slot[w.request.req_id]
                eng.ensure_capacity(slot,
                                    self._len[slot] + k_eff[slot] + 1)
                dr.ensure_capacity(
                    slot, self._draft_len.get(slot, 0) + k_step + 1)

        # 2. propose: k_step + 1 sequential full-buffer draft decodes
        cur = np.maximum(np.asarray(eng._tokens_buf), 0)
        drafts = np.zeros((eng.max_batch, k_step), np.int32)
        for j in range(k_step + 1):
            with span("backend.launch", program="draft.decode", rows=rows):
                dlogits, dr.cache = dr._jit_decode(dr.params, dr.cache,
                                                   jnp.asarray(cur))
            with span("backend.sample", rows=rows):
                cur = np.asarray(greedy(dlogits, eng.cfg.vocab))
            if j < k_step:
                drafts[:, j] = cur[:, 0]

        # 3. batched target verification over [pending, d1..dk_eff]
        with span("backend.prepare", rows=rows):
            vt = np.concatenate(
                [np.maximum(np.asarray(eng._tokens_buf), 0), drafts],
                axis=1)
            n_new = np.zeros((eng.max_batch,), np.int32)
            for w in decodes:
                slot = self._slot[w.request.req_id]
                n_new[slot] = k_eff[slot] + 1
        with span("backend.launch", program="verify", rows=rows):
            vlogits, eng.cache = eng._jit_verify(
                eng.params, eng.cache, jnp.asarray(vt), jnp.asarray(n_new))
        with span("backend.sample", rows=rows):
            target = np.asarray(greedy(vlogits, eng.cfg.vocab))  # (B, k+1)
            matched = accept_length(drafts, target)

        # 4. acceptance + rollback per scheduled slot
        for w in decodes:
            req = w.request
            slot = self._slot[req.req_id]
            pos = self._emit[slot] - 1       # last emitted token's index
            step = self._steps.get(slot, 0)
            self._steps[slot] = step + 1
            if trace is not None:
                accepted = trace.accepted_for(pos, step)
            else:
                accepted = int(matched[slot])
            # matched/trace draws range over 0..k_step; a slot near its
            # output budget only verified k_eff positions (beyond that the
            # target row is unverified padding), so clamp first
            accepted = min(accepted, k_eff[slot])
            if recorder is not None:
                recorder.observe(pos, min(int(matched[slot]), k_eff[slot]))
            if self.spec_tracker is not None:
                self.spec_tracker.observe(pos, accepted, now,
                                          proposed=k_eff[slot])
            bonus = int(target[slot, accepted])
            emitted = [int(t) for t in drafts[slot, :accepted]] + [bonus]
            remaining = max(req.output_len - req.generated, 1)
            emitted = emitted[:remaining]
            t0 = int(eng._tokens_buf[slot, 0])
            self._hist[slot].extend(
                [t0] + [int(t) for t in drafts[slot, :accepted]])
            self._len[slot] += 1 + accepted
            self._draft_len[slot] += 1 + accepted
            # truncation only happens on the request's final step (its
            # slot is released before any further decode), so the bonus
            # is always the correct next pending token
            eng._tokens_buf[slot, 0] = bonus
            self.out_tokens.setdefault(req.req_id, []).extend(emitted)
            self._emit[slot] += len(emitted)
            self._emitted[req.req_id] = len(emitted)
            if self.obs is not None:
                self.obs.emit(now, SPEC_STEP, inst=self.cfg.name,
                              req=req.req_id, tenant=req.tenant,
                              payload={"accepted": int(accepted),
                                       "proposed": int(k_eff[slot])})

        # 5. restore authoritative lengths on both caches: verify bumped
        # scheduled slots to the full window; draft decodes bumped every
        # row.  Unaccepted rows become dead weight overwritten by the
        # next write at the same indices.
        with span("backend.prepare", rows=rows):
            lengths = np.zeros((eng.max_batch,), np.int32)
            for s, n in self._len.items():
                lengths[s] = n
            eng.cache["lengths"] = jnp.asarray(lengths)
            dlen = np.zeros((eng.max_batch,), np.int32)
            for s, n in self._draft_len.items():
                dlen[s] = n
            dr.cache["lengths"] = jnp.asarray(dlen)

    def decode_emitted(self, req: SimRequest) -> int:
        """Tokens the last decode step emitted for ``req`` (1 for vanilla
        decode; accepted + 1 under speculative decoding)."""
        return self._emitted.pop(req.req_id, 1)

    def _prefill_chunk(self, w: ScheduledWork):
        import jax.numpy as jnp
        from repro.serve.engine import _bucket
        from repro.serve.sampler import greedy
        eng = self.eng
        req = w.request
        rid = req.req_id
        with span("backend.prepare", req=rid, tokens=w.tokens):
            toks = self._prompt(req)
            slot = self._slot.get(rid)
            if slot is None:
                slot = eng.slot_free.pop()
                self._slot[rid] = slot
                self._len[slot] = 0
                self._hist[slot] = []
                self._draft_len.pop(slot, None)
                restore = self._restore.pop(rid, None)
                if restore is not None and req.cached_prefix > 0:
                    payload, length = restore
                    length = min(length, req.cached_prefix)
                    # SSD-tier stubs load here, inside execute()'s timed
                    # region, so the disk read lands on the virtual clock
                    payload = eng.radix.resolve(payload)
                    with span("backend.launch", program="restore", req=rid,
                              tokens=length):
                        eng._restore_slot(slot, payload, length)
                    self._len[slot] = length
                    self._hist[slot] = list(toks[:length])
            start = self._len[slot]
            end = min(start + w.tokens, len(toks))
            chunk = toks[start:end]
            if chunk:
                n = len(chunk)
                P = _bucket(n)
                pad = np.zeros((1, P), np.int32)
                pad[0, :n] = np.asarray(chunk, np.int32)
                n_new = jnp.asarray([n], jnp.int32)
                pad = jnp.asarray(pad)
                if start > 0:
                    eng.ensure_capacity(slot, start + n)
        logits = None
        if chunk:
            if start == 0:
                with span("backend.launch", program="prefill", req=rid,
                          tokens=n):
                    logits, c1 = eng._jit_prefill(eng.params, pad,
                                                  lengths=n_new)
                with span("backend.launch", program="write_prefill",
                          req=rid, tokens=n):
                    eng._write_slot_from_prefill(slot, c1, n)
            else:
                with span("backend.launch", program="subcache", req=rid,
                          tokens=start):
                    sub = eng._slot_subcache(slot, start)
                with span("backend.launch", program="extend", req=rid,
                          tokens=n):
                    logits, new_sub = eng._jit_extend(eng.params, sub, pad,
                                                      n_new)
                with span("backend.launch", program="write_slot", req=rid,
                          tokens=start + n):
                    eng._write_slot(slot, new_sub, start + n)
            if self.expert_load is not None:
                # the chunk's tokens occupy KV positions [start, start+n)
                self._routed_pos.extend(range(start, start + n))
            self._len[slot] = start + n
            self._hist[slot].extend(int(t) for t in chunk)
        if self._len[slot] >= len(toks) and logits is not None:
            # prompt complete: the last chunk's logits give the first token
            with span("backend.sample", req=rid):
                first = int(np.asarray(greedy(logits, eng.cfg.vocab))[0, 0])
                eng._tokens_buf[slot, 0] = first
                self.out_tokens.setdefault(rid, []).append(first)
                self._emit[slot] = 1

    # ---- prefix cache payloads ----
    def on_prefix_hit(self, req: SimRequest, match: MatchResult,
                      usable: int) -> int:
        if self.eng.radix is None or usable <= 0:
            return 0
        toks = self._prompt(req)
        limit = min(usable, len(toks) - 1 if toks else 0)
        length, payload = self.eng.radix.match(toks, limit=limit)
        if payload is None or length <= 0:
            return 0
        self._restore[req.req_id] = (payload, length)
        if match is not None:
            # match is None on the preemption re-match path (on_preempt):
            # that restore was already counted when the request first hit
            self._restored_tokens += length
            self._restore_events += 1
        return length

    def on_prefill_complete(self, req: SimRequest):
        if self.eng.radix is None:
            return
        slot = self._slot.get(req.req_id)
        if slot is None:
            return
        t0 = time.perf_counter()
        toks = self._prompt(req)
        blk = (len(toks) // self.eng.radix.block) * self.eng.radix.block
        if blk > 0:
            # device-resident entry (hot tier): the gathered jax arrays
            # stay on device until the runtime demotes them
            with span("backend.launch", program="export", req=req.req_id,
                      tokens=blk):
                kv = self.eng._export_slot(slot, blk, to_host=False)
            self.eng.radix.insert(toks, kv)
        self._carry_s += time.perf_counter() - t0

    def on_tier_transfer(self, src: str, dst: str, n_bytes: float,
                         prefix) -> None:
        """Execute the runtime's tier decision on the real payload store:
        demotions convert device entries to host numpy (then pickle to a
        spill file for SSD), promotions ``device_put`` them back, drops
        delete.  All of it is wall-timed into ``_carry_s`` — the same
        carry discipline as prefix-store inserts — so tier traffic is
        *measured* on this backend, matching the simulator's priced
        ``transfer_time`` charge on the other."""
        if self.eng.radix is None:
            return
        t0 = time.perf_counter()
        if dst == "device":
            self.eng.radix.promote(prefix)
        elif dst in ("host", "ssd"):
            self.eng.radix.demote(prefix, dst)
        else:
            self.eng.radix.drop(prefix)
        self._carry_s += time.perf_counter() - t0
        self._tier_move_s += time.perf_counter() - t0
        self._tier_moves += 1

    def kv_tier_stats(self) -> dict:
        s = {"restored_tokens": self._restored_tokens,
             "restore_events": self._restore_events,
             "tier_moves": self._tier_moves,
             "tier_move_s": self._tier_move_s}
        if self.eng.radix is not None:
            s["store_residency"] = self.eng.radix.residency()
        return s

    def on_preempt(self, req: SimRequest) -> int:
        self.release(req)
        # the restart regenerates the whole output from scratch — drop the
        # partial capture or out_tokens would hold it twice over
        self.out_tokens.pop(req.req_id, None)
        # re-match the store so the restart restores whatever KV survives
        return self.on_prefix_hit(req, None, req.cached_prefix) \
            if req.cached_prefix > 0 else 0

    def release(self, req: SimRequest):
        slot = self._slot.pop(req.req_id, None)
        self._restore.pop(req.req_id, None)
        self._emitted.pop(req.req_id, None)
        if slot is None:
            return
        self._len.pop(slot, None)
        self._hist.pop(slot, None)
        self._draft_len.pop(slot, None)
        self._emit.pop(slot, None)
        self._steps.pop(slot, None)
        with span("backend.release", req=req.req_id):
            self.eng._release_slot(slot)

    # ---- P/D handoff ----
    def export_kv(self, req: SimRequest) -> KvHandoff:
        t0 = time.perf_counter()
        slot = self._slot[req.req_id]
        length = self._len[slot]
        with span("backend.launch", program="export", req=req.req_id,
                  tokens=length):
            kv = self.eng._export_slot(slot, length)
        first = int(self.eng._tokens_buf[slot, 0])
        nbytes = float(sum(
            np.asarray(leaf).nbytes
            for k, v in kv.items() if not k.startswith("_")
            for leaf in _leaves(v)))
        self.release(req)
        self._carry_s += time.perf_counter() - t0
        return KvHandoff(nbytes=nbytes,
                         payload={"kv": kv, "first": first, "len": length})

    def import_kv(self, req: SimRequest, handoff: Optional[KvHandoff]):
        if handoff is None or handoff.payload is None:
            return
        slot = self.eng.slot_free.pop()
        self._slot[req.req_id] = slot
        p = handoff.payload
        with span("backend.launch", program="restore", req=req.req_id,
                  tokens=p["len"]):
            self.eng._restore_slot(slot, p["kv"], p["len"])
        self.eng._tokens_buf[slot, 0] = p["first"]
        self._len[slot] = p["len"]
        # spec bookkeeping: the transferred KV holds exactly the (possibly
        # truncated) prompt; the pending first token is the 1 emitted
        self._hist[slot] = list(self._prompt(req))[:p["len"]]
        self._draft_len.pop(slot, None)
        self._emit[slot] = 1
        self.out_tokens.setdefault(req.req_id, []).append(p["first"])

    # ---- lifecycle ----
    def reset(self):
        import jax.numpy as jnp
        eng = self.eng
        self._slot.clear()
        self._len.clear()
        self._restore.clear()
        self._routed_pos = []
        self._hist.clear()
        self._draft_len.clear()
        self._emit.clear()
        self._steps.clear()
        self._emitted.clear()
        eng.slot_free = list(range(eng.max_batch))
        eng.cache["lengths"] = jnp.zeros((eng.max_batch,), jnp.int32)
        if getattr(eng, "paged", False):
            for slot in range(eng.max_batch):
                eng._free_pages(slot)
        if eng.spec is not None:
            eng.draft.cache["lengths"] = jnp.zeros((eng.max_batch,),
                                                   jnp.int32)
            if getattr(eng.draft, "paged", False):
                for slot in range(eng.max_batch):
                    eng.draft._free_pages(slot)

    def stats(self) -> dict:
        s = {"engine_iterations": self._iterations}
        if self.eng.radix is not None:
            s["kv_store_hits"] = self.eng.radix.hits
            s["kv_store_misses"] = self.eng.radix.misses
        if self.expert_load is not None:
            s["expert_load"] = self.expert_load.metrics()
        if self.spec_tracker is not None:
            s["spec_decode"] = self.spec_tracker.metrics()
        return s


def _leaves(tree):
    out = []
    if isinstance(tree, dict):
        for v in tree.values():
            out.extend(_leaves(v))
    elif isinstance(tree, (list, tuple)):
        for v in tree:
            out.extend(_leaves(v))
    else:
        out.append(tree)
    return out
