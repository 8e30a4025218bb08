"""Multi-instance real-engine driver: real compute, virtual time.

A thin wrapper over the unified ``ServingRuntime``: N ``ServingEngine``
instances become runtime instances with ``JaxBackend`` execution.  Routing
uses the shared policy registry (``repro.runtime.router``), scheduling the
shared ``BatchScheduler``, and P/D handoff the shared cluster orchestration
— the exact code path the simulator runs, so fidelity comparisons isolate
hardware-model error only.

At each virtual instant the runtime picks the next event; an instance
iteration runs ONE real (wall-clock measured) batch and schedules its
completion at ``now + latency`` on the shared event queue, so instances
behave as if they ran in parallel.  KV transfers between instances cost
bytes/bw in virtual time (configurable, default PCIe-class).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax

from repro.core.config import (ClusterCfg, InstanceCfg, NetworkCfg,
                               ParallelismCfg, PrefixCacheCfg, RouterCfg,
                               SchedulerCfg, engine_scheduler_cfg)
from repro.core.request import SimRequest
from repro.hw.specs import hw_for_device
from repro.runtime.backends.jax_engine import JaxBackend
from repro.runtime.cluster import ServingRuntime
from repro.serve.engine import ServingEngine
from repro.workload.sharegpt import Request


def engine_instance_cfg(engine: ServingEngine,
                        scheduler: Optional[SchedulerCfg] = None,
                        trace_name: Optional[str] = None,
                        moe=None, spec=None, hw=None,
                        prefix_cache: Optional[PrefixCacheCfg] = None
                        ) -> InstanceCfg:
    """Runtime InstanceCfg mirroring a live ``ServingEngine``.

    ``moe`` (a ``repro.core.MoECfg``) lets the simulated twin of a MoE
    engine name the same ``routing_trace`` the engine replays, and
    ``spec`` (a ``repro.core.SpecCfg``) the same ``acceptance_trace`` a
    speculating engine replays, so sim-vs-real comparisons report
    comparable ``expert_load`` / ``spec_decode`` metrics.  A speculating
    engine always mirrors its draft length into the scheduler
    (``decode_tokens = k + 1``) so the KV ledger reserves the real
    verification window.  ``hw`` defaults to the spec of the device the
    engine's params live on (``repro.hw.specs.hw_for_device``), so the KV
    ledger is sized for that device; ``hw`` overrides it and
    ``prefix_cache`` the derived ``PrefixCacheCfg`` — e.g. a
    sim-vs-real KV-tier comparison shrinking tier capacities so both
    backends walk the same spill chain (``tests/test_kv_tiers.py``).
    """
    from repro.core.config import MoECfg, SpecCfg
    from repro.profiler import model_spec_from_arch
    model = model_spec_from_arch(engine.cfg)
    scheduler = scheduler or engine_scheduler_cfg(engine.max_batch)
    if scheduler.max_batch_size > engine.max_batch:
        # the engine's slot count is a physical limit; an oversized batch
        # would crash slot allocation mid-run
        scheduler = dataclasses.replace(scheduler,
                                        max_batch_size=engine.max_batch)
    if spec is None and engine.spec is not None:
        spec = SpecCfg(enabled=True, k=engine.spec.k,
                       draft=model_spec_from_arch(engine.spec.draft))
    if engine.spec is not None:
        scheduler = dataclasses.replace(scheduler,
                                        decode_tokens=engine.spec.k + 1)
    if hw is None:
        leaf = jax.tree_util.tree_leaves(engine.params)[0]
        hw = hw_for_device(min(leaf.devices(), key=lambda d: d.id))
    if prefix_cache is None:
        prefix_cache = PrefixCacheCfg(
            enabled=engine.radix is not None,
            block_tokens=engine.radix.block if engine.radix else 16,
            capacity_fraction=0.5)
    return InstanceCfg(
        name=engine.name, hw=hw,
        model=model,
        n_devices=engine.tp, role=engine.role,
        parallelism=ParallelismCfg(tp=engine.tp),
        scheduler=scheduler,
        prefix_cache=prefix_cache,
        moe=moe if moe is not None else MoECfg(),
        spec=spec if spec is not None else SpecCfg(),
        trace_name=trace_name)


@dataclasses.dataclass
class DriverCfg:
    router: str = "round_robin"         # any registered routing policy
    kv_transfer_bw: float = 16e9        # bytes/s for P/D handoff
    kv_transfer_latency: float = 10e-6
    # None -> ServingEngine-matched semantics; pass any SchedulerCfg to give
    # the real engine chunked prefill / SJF / preemption etc.
    scheduler: Optional[SchedulerCfg] = None


class ServeDriver:
    def __init__(self, engines: List[ServingEngine],
                 cfg: DriverCfg = DriverCfg(),
                 pd_map: Optional[Dict[str, Tuple[str, ...]]] = None,
                 recorder=None):
        self.cfg = cfg
        self.engines = {e.name: e for e in engines}
        ccfg = ClusterCfg(
            instances=tuple(engine_instance_cfg(e, cfg.scheduler)
                            for e in engines),
            router=RouterCfg(cfg.router),
            network=NetworkCfg(inter_instance_bw=cfg.kv_transfer_bw,
                               inter_instance_latency=cfg.kv_transfer_latency),
            pd_map=pd_map)
        # recorder: a repro.obs.EventRecorder — build it with
        # wall_clock=True so the real engine's events carry wall-clock
        # stamps alongside simulated time (same schema as the sim)
        self.runtime = ServingRuntime(
            ccfg,
            backend_factory=lambda icfg, trace: JaxBackend(
                self.engines[icfg.name], icfg),
            recorder=recorder)

    @property
    def finished(self) -> List[SimRequest]:
        return self.runtime.finished

    def run(self, requests: Sequence[Request], warmup: bool = True) -> dict:
        if warmup:
            self.runtime.warmup()
        self.runtime.submit_workload(requests)
        return self._augment(self.runtime.run())

    def metrics(self) -> dict:
        return self._augment(self.runtime.metrics())

    def _augment(self, m: dict) -> dict:
        for name, stats in m.get("instances", {}).items():
            cache = stats.get("prefix_cache")
            if cache:
                m[f"{name}_cache_hits"] = cache["hits"]
                m[f"{name}_cache_misses"] = cache["misses"]
        return m
