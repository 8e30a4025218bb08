#!/usr/bin/env python3
"""Chip-side calibration of a cell; never part of a benchmark run.

    python3 bench/calibrate.py knee --workload <cell> --seed <n> \
        --seconds <s> --rates 2,2.5,3
    python3 bench/calibrate.py limits --workload <cell> --seconds <s> \
        --seeds 1,2,3

``knee``: one engine, the cell's traffic at each rate in turn; per rate the
TTFT and TPOT medians and 90th percentiles and how the backlog of requests
waiting for a first token grew over the window.  The cell's rate is set
from this sweep once, at about four fifths of the highest sustained rate.

``limits``: for each seed, weights made from it, one window at the cell's
own load, then the output check of ``run.py`` and, at the same positions,
the fp8 control (the reference with every weight matrix in float8_e4m3).
The program's readings over a dozen seeds and the control's smallest
reading bound each limit in ``cells/<cell>.json``.

One JSON line per rate or seed goes to stdout.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parent.parent)]

from bench import run  # noqa: E402
from bench import stats  # noqa: E402


def backlog(s, t: float) -> int:
    """Requests due by ``t`` still waiting for their first token."""
    return sum(1 for r in s.requests if r.arrival <= t
               and not any(x <= t for x in s.stamps.get(r.req_id, ())))


def knee(cell, seed: int, seconds: float, rates):
    setup = run.Setup(cell, seed)
    for rate in rates:
        cell.spec["rate"] = rate
        reqs = run.requests_for(cell, seed, seconds, setup.dims.vocab)
        s = run.serve(setup, reqs, seconds)
        ttft = stats.ttft_ms(reqs, s.stamps, s.t_open, s.t_close)
        tpot = stats.tpot_ms(s.stamps, s.t_open, s.t_close)
        yield {"rate": rate, "due": len(ttft),
               "ttft_p50_ms": stats.percentile(ttft, 50),
               "ttft_p90_ms": stats.percentile(ttft, 90),
               "tpot_p50_ms": stats.percentile(tpot, 50),
               "tpot_p90_ms": stats.percentile(tpot, 90),
               "tokens_per_s": stats.tokens_in_window(
                   s.stamps, s.t_open, s.t_close) / seconds,
               "backlog_open": backlog(s, s.t_open),
               "backlog_close": backlog(s, s.t_close),
               "lowered_in_window": s.lowered}


def limits(cell, seeds, seconds: float):
    setup = run.Setup(cell, seeds[0])
    for i, seed in enumerate(seeds):
        if i:
            setup.load_params(seed)
        reqs = run.requests_for(cell, seed, seconds, setup.dims.vocab)
        s = run.serve(setup, reqs, seconds)
        setup.free_params()
        res = run.output_check(setup, s, seed, control=True)
        yield {"seed": seed, **res, "finished": len(s.finished),
               "peak": s.peak}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("mode", choices=("knee", "limits"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)
    cell = run.cells.load(args.workload)
    if args.mode == "knee":
        rows = knee(cell, args.seed, args.seconds,
                    [float(r) for r in args.rates.split(",")])
    else:
        rows = limits(cell, [int(s) for s in args.seeds.split(",")],
                      args.seconds)
    for row in rows:
        print(json.dumps({"cell": args.workload, **row}), flush=True)


if __name__ == "__main__":
    main()
