#!/usr/bin/env python3
"""Find a serving cell's knee, once: one engine, several Poisson rates,
one window each.  Not part of a run; its table goes into PERF.md.

    python3 benchmarks/tools/sweep.py --workload <cell> --rates 2,4,6 \
        --seconds 25 [--set num_slots=24 --set kv_pool_tokens=12288]

A rate is sustained when no request fails and the queue at the end of the
window is no deeper than at its middle.  The knee is the highest such
rate.  Rows are printed as JSON lines and appended to
``chiprun_out/sweeps.jsonl`` when that directory's parent is writable.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)
sys.path.insert(0, REPO_DIR)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--seed", type=int, default=100)
    ap.add_argument("--set", action="append", default=[],
                    help="engine setting to try, key=value")
    args = ap.parse_args()

    from benchmarks.lib.device import (device_report, place_compile_cache,
                                       process_age_s, require_devices)
    from benchmarks.lib.manifest import Bench
    from benchmarks.lib.stats import percentile
    from benchmarks.lib.traffic import serve_schedule

    bench = Bench()
    cell = bench.cell(args.workload)
    for kv in args.set:
        k, v = kv.split("=")
        cell["engine"][k] = int(v)
    mix = bench.traffic(cell["traffic"])
    driver = bench.driver(mix["kind"])
    devices = require_devices(int(cell["chips"]))
    place_compile_cache()
    ctx = {"bench": bench, "cell": cell, "config": bench.config(cell["config"]),
           "mix": mix, "seed": args.seed, "devices": devices}
    built = driver.build(ctx)
    serve, vocab = built["serve"], built["vocab"]
    driver.warm_up(serve, cell, mix, vocab, args.seed)
    setup_s = process_age_s()
    out_path = os.path.join(REPO_DIR, "chiprun_out", "sweeps.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        schedule = serve_schedule(mix, rate, args.seconds, args.seed + i,
                                  vocab)
        res = driver.drive(serve, schedule, args.seconds, 600.0)
        view = driver.client_view(res, schedule, args.seconds)
        iters = res["iters"]
        mid = [it[3] for it in iters
               if args.seconds * 0.4 <= it[1] < args.seconds * 0.6]
        end = [it[3] for it in iters
               if args.seconds * 0.9 <= it[1] < args.seconds]
        occ = [it[2] for it in iters if it[1] <= args.seconds]
        dur = [it[1] - it[0] for it in iters if it[1] <= args.seconds]
        row = {"workload": args.workload, "engine": cell["engine"],
               "rate_rps": rate, "seconds": args.seconds,
               "attempted": len(schedule), "failed": view["failed"],
               **view["values"],
               "queue_mid_mean": sum(mid) / max(1, len(mid)),
               "queue_end_mean": sum(end) / max(1, len(end)),
               "occupancy_mean": sum(occ) / max(1, len(occ)) / serve.num_slots,
               "iter_p50_ms": percentile(dur, 50) * 1e3 if dur else None,
               "iter_max_ms": max(dur) * 1e3 if dur else None,
               "late_p99_ms": percentile(res["late_s"], 99) * 1e3,
               "preemptions": sum(lv.req.preemptions
                                  for lv in res["records"] if lv),
               "drain_s": res["elapsed_s"] - args.seconds,
               "setup_s": setup_s, "device": device_report(devices)}
        line = json.dumps(row)
        print(line, flush=True)
        with open(out_path, "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
