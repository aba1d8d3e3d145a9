#!/usr/bin/env python3
"""Spreads of repeated runs, as the driver computes them, and the bound
they lead to.  Not part of a run; its table goes into PERF.md.

    python3 benchmarks/tools/spreads.py chiprun_out/m_<cell>.jsonl ...

Each input line is ``{"cell", "set", "seed", "rc", "line"}`` with ``line``
the last line of one run.  For each cell and end-to-end metric: the median
and the spread (distance between the quartiles over the median) of each
set, the wider of the two, and five times it (the bound's rule; never under
1%, never over 10%).
"""

from __future__ import annotations

import collections
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main() -> int:
    from benchmarks.lib.stats import median, spread

    runs = collections.defaultdict(lambda: collections.defaultdict(list))
    for path in sys.argv[1:]:
        with open(path) as f:
            for raw in f:
                r = json.loads(raw)
                if r["set"] == "T" or not r["line"]:
                    continue
                for name, m in r["line"]["metrics"].items():
                    runs[(r["cell"], name)][r["set"]].append(m["value"])
    worst = collections.defaultdict(float)
    for (cell, name), sets in sorted(runs.items()):
        parts = []
        for tag, values in sorted(sets.items()):
            if name == "setup_s":
                values = values[1:] if tag == "A" else values   # first compiles
            parts.append(f"{tag}: n={len(values)} median={median(values):.4g} "
                         f"spread={100 * spread(values):.2f}%")
        wide = max(spread(v[1:] if name == "setup_s" and t == "A" else v)
                   for t, v in sets.items())
        worst[name] = max(worst[name], wide)
        print(f"{cell:34s} {name:14s} {' | '.join(parts)} | wider "
              f"{100 * wide:.2f}%")
    for name, wide in sorted(worst.items()):
        print(f"{name:14s} widest spread {100 * wide:.2f}% -> bound "
              f"{min(0.1, max(0.01, 5 * wide)):.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
