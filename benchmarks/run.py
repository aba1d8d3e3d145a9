#!/usr/bin/env python3
"""Run one cell of the benchmark and print its result as the last line.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's files are found by name from ``BENCHMARK.json`` (see
``lib/manifest.py``).  ``--trace 0`` prints the cell's end-to-end metrics,
taken with the profiler off; ``--trace 1`` profiles a short part of the
window and prints the per-layer metrics, each from a reader of its own in
``layer_metrics/``.  Without a TPU, or with fewer chips than the cell asks
for, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
from typing import Any, Dict, Optional, Sequence

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_DIR = os.path.dirname(BENCH_DIR)
if REPO_DIR not in sys.path:
    sys.path.insert(0, REPO_DIR)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             manifest_path: Optional[str] = None, root: Optional[str] = None,
             allow_cpu: bool = False) -> Dict[str, Any]:
    """Run the cell and return the object of the last line.  ``allow_cpu``
    exists for the benchmark's own tests and has no command-line flag."""
    from benchmarks.lib.device import (place_compile_cache, process_age_s,
                                       require_devices)
    from benchmarks.lib.manifest import Bench

    bench = Bench(manifest_path, root)
    entry = bench.workload_entry(workload)
    cell = bench.cell(workload)
    config = bench.config(cell["config"])
    mix = bench.traffic(cell["traffic"])
    driver = bench.driver(mix["kind"])

    devices = require_devices(int(entry["chips"]), allow_cpu=allow_cpu)
    place_compile_cache()

    trace_dir = bench.path("out", "trace", workload)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
    ctx = {"bench": bench, "workload": workload, "cell": cell,
           "config": config, "mix": mix, "seed": int(seed),
           "seconds": float(seconds), "trace": bool(trace),
           "trace_dir": trace_dir, "devices": devices, "age": process_age_s}
    result = driver.run(ctx)

    values = dict(result["values"])
    values["setup_s"] = result["setup_s"]
    device = dict(result["device"])
    line: Dict[str, Any] = {"correct": result["correct"],
                            "attempted": result["attempted"],
                            "failed": result["failed"]}
    if not trace:
        wanted = bench.metrics_for("end_to_end", workload)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in wanted if m["name"] in values}
    else:
        from benchmarks.lib import trace_reduce
        from benchmarks.lib.peaks import peaks

        summary = None
        xplane = trace_reduce.find_xplane(trace_dir)
        if xplane is not None and result["trace_window"] is not None:
            try:
                summary = trace_reduce.summarize(
                    trace_reduce.load_xplane(xplane),
                    host_scopes=result["host_scopes"])
            except trace_reduce.NoDeviceTrace as e:
                # no busy_s on the line: the driver refuses such a run
                result["checks"]["trace"] = str(e)
        if summary is not None:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            line["breakdown"] = trace_reduce.breakdown(summary)
        rctx = dict(result, bench=bench, workload=workload, cell=cell,
                    config=config, mix=mix, trace=summary,
                    end_to_end=values,
                    peaks=(peaks(device["kind"])
                           if device["platform"] == "tpu" else None))
        metrics = {}
        reported = {m["name"] for m in bench.metrics_for("end_to_end",
                                                         workload)}
        for m in bench.metrics_for("per_layer", workload):
            if m["moves"] not in reported:
                continue
            value = bench.reader(m["name"]).read(rctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    line["metrics"] = metrics
    line["device"] = device
    line["checks"] = result["checks"]
    return line


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from benchmarks.lib.device import NoAccelerator

    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace))
    except NoAccelerator as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 1
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
