#!/usr/bin/env python3
"""Where one cell's set-up goes, from inside the program.  Not part of a run.

    python3 benchmarks/tools/setup_phases.py --workload <cell> --seed <n> \
        [--seconds 2]

Enables the program's registry (so its ``ds_setup_*`` ranges count and its
compile ledger listens), runs the cell through ``run.run_cell`` with the
profiler off and both drivers as they are, and prints one JSON line:
``setup_s`` as the driver stamped it; ``setup_parts`` (``lib/setup_spans``)
from the registry at that same moment; ``unattributed_s``, the rest, and
``backend_init_s``, the one piece of it the tool can time (``jax.devices()``
finding the chip); the twenty heaviest programs of the ledger by then; and what the run spent after
its window (``after_window_s``: drain, reference) with the process's age at
its end, so one line holds everything a cold first run costs.  The cell's
end-to-end metrics are on the line too: with ``--seconds 40`` this is a
run with the registry ENABLED, to set against ``run.py``'s.

Cold or warm is the caller's: give ``JAX_COMPILATION_CACHE_DIR`` a FIXED
path (the path is part of each entry's key), empty for a cold run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Any, Dict, Optional, Sequence

REPO_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO_DIR not in sys.path:
    sys.path.insert(0, REPO_DIR)

HEAVIEST = 20


def setup_phases(workload: str, seed: int, seconds: float = 2.0, *,
                 manifest_path: Optional[str] = None,
                 allow_cpu: bool = False) -> Dict[str, Any]:
    import jax

    from deepspeed_tpu.monitor.metrics import get_registry
    from deepspeed_tpu.profiling.trace import compile_ledger

    from benchmarks.lib import device
    from benchmarks.lib.setup_spans import setup_parts, unattributed_s
    from benchmarks.run import run_cell

    registry = get_registry().enable()
    ledger = compile_ledger()
    t0 = time.perf_counter()
    jax.devices()       # the backend's start: the largest known piece of
    backend_init_s = time.perf_counter() - t0      # `unattributed_s`
    at_begin: Dict[str, Any] = {}
    age = device.process_age_s

    def age_and_snapshot() -> float:
        # the drivers read the clock once, where set-up ends
        setup_s = age()
        at_begin["counters"] = {
            k: v for k, v in registry.snapshot().items()
            if isinstance(v, (int, float))}
        at_begin["rows"] = ledger.rows()
        return setup_s

    device.process_age_s = age_and_snapshot
    try:
        line = run_cell(workload, seed, seconds, False,
                        manifest_path=manifest_path, allow_cpu=allow_cpu)
    finally:
        device.process_age_s = age
    setup_s = line["metrics"]["setup_s"]["value"]
    parts = setup_parts(at_begin["counters"])
    rows = sorted(at_begin["rows"], key=lambda r: -r["self_s"])[:HEAVIEST]
    return {
        "workload": workload, "seed": int(seed), "setup_s": setup_s,
        "setup_parts": parts,
        "unattributed_s": unattributed_s(setup_s, parts),
        "backend_init_s": backend_init_s,
        "programs": [{k: r[k] for k in (
            "program", "phase", "calls", "trace_s", "lower_s", "compile_s",
            "cache_read_s", "saved_s", "self_s", "hits", "misses")}
            for r in rows],
        "programs_named": len(at_begin["rows"]),
        "checks": {"after_window_s": line["checks"]["after_window_s"],
                   "compiles_in_window":
                       line["checks"]["compiles_in_window"]},
        "process_s": age(), "correct": line["correct"],
        "failed": line["failed"],
        "metrics": {k: v["value"] for k, v in line["metrics"].items()},
        "device": line["device"]}


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    from benchmarks.lib.device import NoAccelerator

    try:
        out = setup_phases(args.workload, args.seed, args.seconds)
    except NoAccelerator as e:
        print(f"benchmarks/tools/setup_phases.py: {e}", file=sys.stderr)
        return 1
    sys.stdout.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
