#!/usr/bin/env python3
"""Readings that set a cell's limits and rate; not part of a benchmark run.

    # the numbers compared, for the program and for the fp8 control, on
    # many seeds in one process (one JSON line per seed); --fault plants
    # one of chipbench/faults.py's faults in the program:
    python3 chipbench/calibrate.py check --workload <name> --seeds 1,2,3 \\
        --seconds 30 [--control] [--fault keep|exchange]

    # an open-loop cell's latency at several rates (one set-up), to find
    # the highest rate the system sustains:
    python3 chipbench/calibrate.py sweep --workload <name> --seed 7 \\
        --rates 2,3,4 --seconds 20

Both run on the chips of this machine and exit non-zero without them.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import sys
import time

import run as entry                        # chipbench/run.py


def check(args, devs) -> None:
    from chipbench import cells, faults
    from chipbench.bench import find_cell
    cell = find_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        with (faults.FAULTS[args.fault]() if args.fault
              else contextlib.nullcontext()):
            m = cells.measure(cell, devs, seed, args.seconds)
        values, ctrl = cells.compared(m, control=args.control)
        print(json.dumps({"seed": seed, "fault": args.fault,
                          "program": values, "control": ctrl,
                          "e2e": m.e2e, "attempted": m.attempted,
                          "failed": m.failed, "compiles": m.compiles,
                          "memory_peak_bytes": m.memory_peak_bytes,
                          "wall_s": time.perf_counter() - t}), flush=True)
        del m
        gc.collect()


def sweep(args, devs) -> None:
    from chipbench import serve
    from chipbench.bench import find_cell, percentile
    cell = find_cell(args.workload)
    engine, _ = serve.setup(cell, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        cell.mix["rate_per_s"] = rate
        rec, compiles = serve.window(cell, engine, args.seed, args.seconds)
        backlog = sum(1 for r in rec.requests
                      if not r.stamps or r.stamps[0] >= rec.t1)
        engine.run(max_ticks=1 << 30)
        engine.reap()
        e2e = serve.end_to_end(rec)
        e2e["ttft_p90_ms"] = percentile(serve.ttfts(rec), 90) * 1e3
        print(json.dumps({"rate_per_s": rate, "requests": len(rec.requests),
                          "no_first_token_at_close": backlog,
                          "compiles": compiles, **e2e}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="mode", required=True)
    c = sub.add_parser("check")
    c.add_argument("--workload", required=True)
    c.add_argument("--seeds", required=True)
    c.add_argument("--seconds", type=float, required=True)
    c.add_argument("--control", action="store_true")
    c.add_argument("--fault", choices=("keep", "exchange"))
    s = sub.add_parser("sweep")
    s.add_argument("--workload", required=True)
    s.add_argument("--seed", type=int, required=True)
    s.add_argument("--rates", required=True)
    s.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    if not entry._paths():
        return 2
    from chipbench.bench import find_cell
    devs = entry.devices_for(find_cell(args.workload).chips)
    if devs is None:
        return 1
    entry.enable_cache()
    {"check": check, "sweep": sweep}[args.mode](args, devs)
    return 0


if __name__ == "__main__":
    sys.exit(main())
