#!/usr/bin/env python3
"""Run cells one after another, each in a process of its own, and keep every
last line in the form ``tools/spreads.py`` reads.  Not part of a run.

    python3 benchmarks/tools/runs.py [--seconds 40] [--root DIR] \
        <cell>:<set>:<seed>[:trace] ...

``--root`` names another checkout (the parent's, unpacked under a directory
``.gitignore`` lists) whose ``benchmarks/run.py`` is run instead.  Lines go
to ``chiprun_out/m_<cell>.jsonl`` of THIS checkout, so parent and change can
share a call and be told apart by their set's letter.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--root", default=REPO_DIR)
    ap.add_argument("runs", nargs="+")
    args = ap.parse_args()
    out_dir = os.path.join(REPO_DIR, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    for spec in args.runs:
        cell, tag, seed, *trace = spec.split(":")
        t0 = time.time()
        r = subprocess.run(
            [sys.executable, "benchmarks/run.py", "--workload", cell,
             "--seed", seed, "--seconds", str(args.seconds), "--trace",
             "1" if trace else "0"], cwd=args.root, capture_output=True,
            text=True)
        last = r.stdout.strip().splitlines()[-1:] or [""]
        try:
            line = json.loads(last[0])
        except ValueError:
            line = None
        rec = {"cell": cell, "set": tag, "seed": int(seed), "rc": r.returncode,
               "trace": bool(trace), "wall_s": time.time() - t0, "line": line}
        with open(os.path.join(out_dir, f"m_{cell}.jsonl"), "a") as f:
            f.write(json.dumps(rec) + "\n")
        brief = {k: v["value"] for k, v in (line or {}).get(
            "metrics", {}).items()}
        print(json.dumps({"run": spec, "rc": r.returncode,
                          "correct": (line or {}).get("correct"),
                          "failed": (line or {}).get("failed"),
                          "wall_s": round(rec["wall_s"], 1), **brief}),
              flush=True)
        if line is None:
            print(r.stderr[-3000:], flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
