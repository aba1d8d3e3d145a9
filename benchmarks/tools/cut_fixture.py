#!/usr/bin/env python3
"""Cut a small fixture out of a recorded TPU trace, and describe the trace.

    python3 benchmarks/tools/cut_fixture.py <trace dir or .xplane.pb> <out dir> [--ms 60]

Writes ``<out dir>/describe.txt`` (planes, lines, the heaviest event names,
two sample events per line with ALL their stats) and
``<out dir>/fixture.json.gz``: the device events of ``--ms`` milliseconds
from the middle of the trace, and the host ranges the reduction reads.
"""

from __future__ import annotations

import argparse
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH_DIR))


def main() -> int:
    from benchmarks.lib import trace_reduce as tr

    ap = argparse.ArgumentParser()
    ap.add_argument("trace")
    ap.add_argument("out")
    ap.add_argument("--ms", type=float, default=60.0)
    args = ap.parse_args()
    path = args.trace if args.trace.endswith(".pb") else tr.find_xplane(
        args.trace)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "describe.txt"), "w") as f:
        f.write(tr.describe(tr.load_xplane(path, planes=[".*"],
                                           all_stats=True)))
    trace = tr.load_xplane(path)
    lo, hi = tr.window_of(trace)
    mid = (lo + hi) / 2
    cut_lo, cut_hi = mid - args.ms * 5e5, mid + args.ms * 5e5
    keep_host = {tr.WINDOW_MARK, tr.STEP_MARK, "ds_serve_admit",
                 "ds_serve_prefill", "ds_serve_decode", "ds_fwd_bwd"}
    small = {}
    for plane, lines in trace.items():
        device = bool(tr.DEVICE_PLANE.match(plane))
        for line, evs in lines.items():
            if device and line not in (tr.OPS_LINE, tr.MODULES_LINE,
                                       tr.ASYNC_LINE):
                continue
            kept = [e for e in evs if device or e.name in keep_host]
            if kept:
                small.setdefault(plane, {})[line] = kept
    tr.save_events(small, os.path.join(args.out, "fixture.json.gz"),
                   cut_lo, cut_hi)
    return 0


if __name__ == "__main__":
    sys.exit(main())
