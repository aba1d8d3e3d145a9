"""What the program's own stamps, counters and host spans say about one
serve run: the reductions behind the ``ttft_*_p50_ms``, ``host_*_share``
and ``idle_*_share`` readers.

The serve engine wraps every phase of a scheduler iteration in a host range
named ``ds_serve_<phase>`` that sits in the profiler's trace (one clock
with the device) and counts its seconds in ``ds_serve_<phase>_seconds_total``
(``deepspeed_tpu/profiling/trace.py`` ``phase``).  Two of the ranges block
on the chip (:data:`BLOCKING`); during the others the chip runs dry unless
earlier dispatches still cover it.  A program older than those ranges has
neither the children nor their counters, and stamps ``t_first_token``
before the token is fetched: every function here then returns None, and
the reader leaves its metric out.

    python -m benchmarks.lib.host_spans <trace dir or .xplane.pb>
        [--cut <out.json.gz> [--ms 150]]

prints chip 0's idle seconds by the innermost host span over each gap's
middle: the table a person reads.  ``--cut`` also writes that many
milliseconds from the middle of the trace, device events and the
``bench_*`` / ``ds_serve_*`` host ranges, as a test fixture
(``tools/cut_fixture.py`` keeps the three parent ranges only).
"""

from __future__ import annotations

import bisect
import functools
import sys
from typing import Any, Dict, List, Optional

from benchmarks.lib import trace_reduce as tr
from benchmarks.lib.stats import median

STEP = "ds_serve_step"
PREFIX = "ds_serve_"
BLOCKING = ("ds_serve_first_token_fetch", "ds_serve_block_fetch")
OUTSIDE = "between steps"


# ---------------------------------------------------------------------------
# per-request stamps: where a first token's time went
# ---------------------------------------------------------------------------

def ttft_parts(ctx: Dict[str, Any]) -> Optional[List[Dict[str, float]]]:
    """Per request, in seconds: ``late`` (due -> stamped by ``submit()``),
    ``queue`` (submit -> slot), ``prefill`` (slot -> first token's value on
    the host: chunks, the wait behind the block already on the chip, the
    fetch), ``tail`` (-> ``step()`` returned and the benchmark saw the
    token: the decode dispatch and lag-1 drain of the same iteration), and
    ``ttft``, the benchmark's own number: the four parts sum to it exactly.

    The same requests as ``ttft_p90_ms`` reads (first token seen, due
    before the profiler started, one second earlier in a traced run), less
    those preempted: their ``t_admit`` is the last admission's.  The
    ``Request`` stamps are absolute ``perf_counter()``, the loop's are
    relative to a ``t0`` that ``drive()`` keeps to itself: recovered as the
    median of ``t_submit - (due + late_s)``, submit being stamped
    microseconds after the loop takes ``late_s``.  An error in ``t0``
    moves time between ``late`` and ``tail`` and leaves the sum alone.

    None from a program that has no ``ds_serve_first_token_fetch`` counter
    (the registry is on in every run that calls a reader): it stamps
    ``t_first_token`` where the last chunk is enqueued, which would put the
    wait for the chip under ``tail``."""
    if BLOCKING[0] + "_seconds_total" not in ctx["counters"].get("begin", ()):
        return None
    loop = ctx["loop"]
    horizon = loop["until_s"] - (1.0 if ctx["trace_window"] else 0.0)
    rows = [(lv, a, float(late)) for lv, a, late in zip(
        loop["records"], loop["schedule"], loop["late_s"]) if lv is not None]
    if not rows:
        return None
    t0 = median([lv.req.t_submit - (a.due_s + late) for lv, a, late in rows])
    out = []
    for lv, a, _ in rows:
        req = lv.req
        if (lv.t_first is None or a.due_s >= horizon or req.preemptions
                or not req.t_first_token):
            continue
        out.append({"late": req.t_submit - t0 - a.due_s,
                    "queue": req.t_admit - req.t_submit,
                    "prefill": req.t_first_token - req.t_admit,
                    "tail": t0 + lv.t_first - req.t_first_token,
                    "ttft": lv.t_first - a.due_s})
    return out


def ttft_part_p50_ms(ctx: Dict[str, Any], part: str) -> Optional[float]:
    parts = ttft_parts(ctx)
    return median([p[part] for p in parts]) * 1e3 if parts else None


# ---------------------------------------------------------------------------
# counters: the loop's host seconds, untraced part of the window
# ---------------------------------------------------------------------------

def host_work_share(ctx: Dict[str, Any]) -> Optional[float]:
    """Percent of the wall time from the window's begin to the profiler's
    start that the engine thread spent inside ``step()`` and not under a
    :data:`BLOCKING` fetch: the growth of ``ds_serve_step_seconds_total``
    less that of the two fetch counters.  The loop is always inside
    ``step()`` while it has work, so the rest of 100% is the share it
    spent blocked on the chip."""
    c = ctx["counters"]
    a, b = c.get("begin"), c.get("trace_start")
    wall = ctx["loop"]["until_s"]
    if not a or not b or wall <= 0:
        return None
    seconds = 0.0
    for name, sign in ((STEP, 1.0),) + tuple((n, -1.0) for n in BLOCKING):
        key = name + "_seconds_total"
        if key not in b:
            return None
        seconds += sign * (b[key] - a.get(key, 0.0))
    return 100.0 * seconds / wall


# ---------------------------------------------------------------------------
# the trace: chip 0's idle time by what the host was doing
# ---------------------------------------------------------------------------

def idle_by_span(trace: tr.Trace) -> Optional[Dict[str, Any]]:
    """Chip 0's idle seconds inside the traced window, by the innermost
    host range (``bench_step`` and every ``ds_serve_*``) over each gap's
    middle; the rule of ``trace_reduce.summarize``, over all the names the
    trace holds.  ``fetch_s`` is the idle time under :data:`BLOCKING`,
    ``host_work_s`` under any other ``ds_serve_*`` range (a child that does
    not block, or a parent's self time), ``outside_s`` the rest: outside
    ``step()``, the load generator's.  None where the trace has no
    ``ds_serve_step`` range."""
    planes = tr.device_planes(trace)
    if not planes:
        raise tr.NoDeviceTrace("the trace has no /device:TPU plane")
    window = tr.window_of(trace)
    lo, hi = window
    ops = tr.leaf_ops(trace, planes[0], window)
    busy = tr.merge(tr.clip([(e.start, e.start + e.dur) for e in ops],
                            lo, hi))
    names = {e.name for p, lines in trace.items()
             if not tr.DEVICE_PLANE.match(p)
             for evs in lines.values() for e in evs
             if e.name == tr.STEP_MARK or e.name.startswith(PREFIX)}
    if STEP not in names:
        return None
    # ranges of one name come from one thread and do not overlap; ranges of
    # different names nest, so the innermost one is the one that began last
    scopes = [(n, evs, [e.start for e in evs])
              for n, evs in ((n, tr.host_events(trace, n)) for n in names)]
    by_span: Dict[str, float] = {n: 0.0 for n in names}
    by_span[OUTSIDE] = 0.0
    for s, e in tr.subtract([window], busy):
        mid = (s + e) / 2
        label, began = OUTSIDE, float("-inf")
        for name, evs, starts in scopes:
            i = bisect.bisect_right(starts, mid) - 1
            if i >= 0 and mid < evs[i].start + evs[i].dur \
                    and evs[i].start > began:
                label, began = name, evs[i].start
        by_span[label] += (e - s) / 1e9
    fetch = sum(by_span.get(n, 0.0) for n in BLOCKING)
    work = sum(v for n, v in by_span.items()
               if n.startswith(PREFIX) and n not in BLOCKING)
    spans = {n: {"count": len(evs), "seconds": sum(e.dur for e in evs) / 1e9}
             for n, evs, _ in scopes}
    return {"window_s": (hi - lo) / 1e9,
            "idle_s": ((hi - lo) - tr.total(busy)) / 1e9,
            "fetch_s": fetch, "host_work_s": work,
            "outside_s": by_span[OUTSIDE] + by_span.get(tr.STEP_MARK, 0.0),
            "by_span": by_span, "spans": spans}


@functools.lru_cache(maxsize=2)
def _idle_of_xplane(path: str) -> Optional[Dict[str, Any]]:
    return idle_by_span(tr.load_xplane(path))


def idle_of_run(ctx: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """:func:`idle_by_span` of this run's trace, loaded once per run; None
    where the run has no device trace."""
    if ctx.get("trace") is None:
        return None
    path = tr.find_xplane(ctx["bench"].path("out", "trace", ctx["workload"]))
    return _idle_of_xplane(path) if path else None


def idle_share(ctx: Dict[str, Any], key: str) -> Optional[float]:
    idle = idle_of_run(ctx)
    return 100.0 * idle[key] / idle["window_s"] if idle else None


def table(idle: Dict[str, Any]) -> str:
    w = idle["window_s"]
    out = [f"traced window {w:.4f} s, chip 0 idle {idle['idle_s']:.4f} s "
           f"({100 * idle['idle_s'] / w:.2f}%): under a blocking fetch "
           f"{idle['fetch_s']:.4f}, under other host work "
           f"{idle['host_work_s']:.4f}, outside step() "
           f"{idle['outside_s']:.4f}",
           f"{'innermost host range':34s} {'idle s':>9s} {'% window':>9s} "
           f"{'ranges':>7s} {'range s':>9s}"]
    for name, s in sorted(idle["by_span"].items(), key=lambda kv: -kv[1]):
        sp = idle["spans"].get(name, {"count": 0, "seconds": 0.0})
        out.append(f"{name:34s} {s:9.4f} {100 * s / w:9.2f} "
                   f"{sp['count']:7d} {sp['seconds']:9.4f}")
    return "\n".join(out)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--cut", help="also write a fixture (.json.gz) here")
    ap.add_argument("--ms", type=float, default=150.0)
    args = ap.parse_args(argv)
    path = args.trace if args.trace.endswith(".pb") else tr.find_xplane(
        args.trace)
    if path is None:
        print(f"no .xplane.pb under {args.trace}", file=sys.stderr)
        return 1
    trace = tr.load_xplane(path)
    try:
        idle = idle_by_span(trace)
    except tr.NoDeviceTrace as e:
        print(str(e), file=sys.stderr)
        return 1
    if idle is None:
        print(f"the trace has no {STEP} range", file=sys.stderr)
        return 1
    print(table(idle))
    if args.cut:
        lo, hi = tr.window_of(trace)
        mid = (lo + hi) / 2
        small: tr.Trace = {}
        for plane, lines in trace.items():
            device = bool(tr.DEVICE_PLANE.match(plane))
            for line, evs in lines.items():
                if device and line not in (tr.OPS_LINE, tr.MODULES_LINE):
                    continue
                kept = [e for e in evs if device or e.name.startswith(
                    (PREFIX, "bench_"))]
                if kept:
                    small.setdefault(plane, {})[line] = kept
        tr.save_events(small, args.cut, mid - args.ms * 5e5,
                       mid + args.ms * 5e5)
    return 0


if __name__ == "__main__":
    sys.exit(main())
