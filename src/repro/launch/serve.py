"""Serving driver: real JAX engine(s) with batched requests.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.1-8b-tiny \
      --n 32 --rate 10 [--pd] [--prefix-cache] [--instances 2]
"""
from __future__ import annotations

import argparse
import json

from repro.configs import ArchConfig, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.serve import DriverCfg, ServeDriver, ServingEngine
from repro.workload import ShareGPTConfig, generate


def build_driver(cfg: ArchConfig, *, params=None, instances: int = 1,
                 pd: bool = False, prefix_cache: bool = False,
                 max_batch: int = 4, max_len: int = 512, tp: int = 1,
                 router: str = "round_robin",
                 chunked_prefill: bool = False) -> ServeDriver:
    """Engines + scheduler + ``ServeDriver`` for one served model.

    Every engine shares the first engine's params (or ``params``).  With
    ``pd`` one prefill engine hands off to one decode engine; otherwise
    ``instances`` unified engines sit behind ``router``.
    ``chunked_prefill`` gives the runtime continuous batching with
    64-token prefill chunks (later chunks run the engine's extend path).
    """
    kw = dict(max_batch=max_batch, max_len=max_len,
              prefix_cache=prefix_cache, tp=tp)
    if pd:
        p0 = ServingEngine(cfg, params=params, name="p0", role="prefill",
                           **kw)
        engines = [p0, ServingEngine(cfg, params=p0.params, name="d0",
                                     role="decode", **kw)]
        pd_map = {"p0": ("d0",)}
    else:
        e0 = ServingEngine(cfg, params=params, name="e0", **kw)
        engines = [e0] + [
            ServingEngine(cfg, params=e0.params, name=f"e{i}", **kw)
            for i in range(1, instances)]
        pd_map = None
    sched = None
    if chunked_prefill:
        from repro.core.config import SchedulerCfg
        sched = SchedulerCfg(max_batch_size=max_batch,
                             max_batch_tokens=256,
                             chunked_prefill=True, prefill_chunk=64)
    return ServeDriver(engines, DriverCfg(router=router, scheduler=sched),
                       pd_map=pd_map)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.1-8b-tiny")
    ap.add_argument("--n", type=int, default=24)
    ap.add_argument("--rate", type=float, default=10.0)
    ap.add_argument("--instances", type=int, default=1)
    ap.add_argument("--pd", action="store_true")
    ap.add_argument("--prefix-cache", action="store_true")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=512)
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree per engine (needs >= tp "
                         "visible devices; on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count)")
    ap.add_argument("--router", default="round_robin",
                    help="any registered routing policy "
                         "(round_robin | least_loaded | prefix_aware)")
    ap.add_argument("--chunked-prefill", action="store_true",
                    help="continuous batching with chunked prefill on the "
                         "real engine (unified runtime scheduler)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    reqs = generate(ShareGPTConfig(
        n_requests=args.n, rate=args.rate, vocab=cfg.vocab,
        mean_prompt=90, mean_output=24, max_prompt=args.max_len // 2,
        max_output=48, share_fraction=0.5 if args.prefix_cache else 0.0))
    drv = build_driver(cfg, instances=args.instances, pd=args.pd,
                       prefix_cache=args.prefix_cache,
                       max_batch=args.max_batch, max_len=args.max_len,
                       tp=args.tp, router=args.router,
                       chunked_prefill=args.chunked_prefill)
    m = drv.run(reqs)
    print(json.dumps(m, indent=1, default=float))


if __name__ == "__main__":
    main()
