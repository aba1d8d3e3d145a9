"""From a profiler trace to numbers: the reduction every PR shares.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``;
``jax.profiler.ProfileData`` reads it with nothing but JAX.  A v5e trace
(looked at by hand for PR 24, see PERF.md section 3) has one plane per chip
named ``/device:TPU:<n>`` with three lines: ``XLA Modules``, one event per
executed program, named ``jit_<fn>(<fingerprint>)``; ``XLA Ops``, one
event per executed HLO instruction, named by the instruction's whole text
(``%copy.547 = bf16[48,33,25,256,64]{...} copy(...)``), with enclosing
``while`` instructions on the same line as their bodies; and ``Async XLA
Ops``, the spans of asynchronous copies, slices and collectives from their
``-start`` to their ``-done``.  An event carries its times and nothing
else: no op_name path, so a ``jax.named_scope`` does not reach the trace.
A Pallas kernel is a ``custom-call`` with
``custom_call_target="tpu_custom_call"`` whose instruction is named after
the ``pallas_call(name=...)``.  The plane ``/host:CPU`` has one line per
host thread; ``TraceAnnotation`` ranges sit on the line of the thread that
opened them (``python3`` for the main thread).  All planes share one
clock, in nanoseconds.

Here a trace is plain data, ``{plane: {line: [Ev, ...]}}``, so that the
same reduction runs on a freshly recorded ``.xplane.pb`` and on the small
recorded trace kept as a test fixture (:func:`save_events` writes one).

The interval arithmetic (:func:`merge`, :func:`subtract`, :func:`clip`) is
copied from ``deepspeed_tpu/profiling/device_trace.py`` (``_merge``,
``_subtract``, ``_clip``): right arithmetic that had only ever been fed CPU
proxy rows.  The original stays with the program (PERF.md, Open questions).
"""

from __future__ import annotations

import bisect
import collections
import glob
import gzip
import json
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Ev = collections.namedtuple("Ev", "name start dur stats")   # ns, ns, dict
Interval = Tuple[float, float]
Trace = Dict[str, Dict[str, List[Ev]]]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_MARK = "bench_window"
STEP_MARK = "bench_step"
# stats kept per event: enough to name a kernel and a scope
KEEP_STATS = ("hlo_op", "run_id")
_COLL = r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
# an instruction is a collective when it is named after one (XLA names an
# instruction after its opcode; the TPU's ``all-reduce-scatter`` fusion
# too) or its opcode is one
COLLECTIVE_NAME = re.compile(r"^" + _COLL)
COLLECTIVE_OPCODE = re.compile(r"\s" + _COLL + r"(-start|-done)?\(")
# instructions that only enclose others: their time is their children's
CONTAINER = re.compile(r"^(while|conditional|call)([.\d]|$)")
PALLAS_TARGET = 'custom_call_target="tpu_custom_call"'


class NoDeviceTrace(ValueError):
    """The trace holds no TPU plane or no device instruction."""


# ---------------------------------------------------------------------------
# reading and writing
# ---------------------------------------------------------------------------

def find_xplane(trace_dir: str) -> Optional[str]:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def _stat_value(v: Any) -> Any:
    return v if isinstance(v, (int, float, str)) else str(v)


def load_xplane(path: str, planes: Optional[Sequence[str]] = None,
                all_stats: bool = False) -> Trace:
    """Read an ``.xplane.pb`` into plain data.  ``planes`` (regexes) limits
    which planes are read; default: the TPU planes and the host's.
    ``all_stats`` keeps every stat of every event (for :func:`describe`)."""
    from jax.profiler import ProfileData

    want = [re.compile(p) for p in (planes or (DEVICE_PLANE.pattern,
                                               r"^/host:CPU$"))]
    out: Trace = {}
    for plane in ProfileData.from_file(path).planes:
        if not any(w.search(plane.name) for w in want):
            continue
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for e in line.events:
                stats = ({k: _stat_value(v) for k, v in e.stats
                          if all_stats or k in KEEP_STATS}
                         if device or all_stats else {})
                evs.append(Ev(e.name, int(e.start_ns), int(e.duration_ns),
                              stats))
    return out


def save_events(trace: Trace, path: str, lo: float, hi: float) -> None:
    """Write the events that overlap ``[lo, hi)``, clipped to it, as gzipped
    JSON: how the test fixture was cut from a recorded TPU trace."""
    lo, hi = int(lo), int(hi)
    doc = {p: {l: [[e.name, max(e.start, lo),
                    min(e.start + e.dur, hi) - max(e.start, lo), e.stats]
                   for e in evs if e.start < hi and e.start + e.dur > lo]
               for l, evs in ls.items()}
           for p, ls in trace.items()}
    doc = {p: {l: evs for l, evs in ls.items() if evs}
           for p, ls in doc.items()}
    with gzip.open(path, "wt") as f:
        json.dump(doc, f, separators=(",", ":"))


def load_events(path: str) -> Trace:
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    return {p: {l: [Ev(*e) for e in evs] for l, evs in ls.items()}
            for p, ls in doc.items()}


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def merge(intervals: Iterable[Interval]) -> List[Interval]:
    intervals = sorted(intervals)
    if not intervals:
        return []
    out = [intervals[0]]
    for s, e in intervals[1:]:
        ls, le = out[-1]
        if s <= le:
            out[-1] = (ls, max(le, e))
        else:
            out.append((s, e))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in merge(intervals))


def subtract(a: Iterable[Interval], b: Iterable[Interval]) -> List[Interval]:
    """Interval set difference ``a - b``."""
    a, b = merge(a), merge(b)
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            bs, be = b[k]
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _iv(evs: Iterable[Ev]) -> List[Interval]:
    return [(e.start, e.start + e.dur) for e in evs]


# ---------------------------------------------------------------------------
# naming
# ---------------------------------------------------------------------------

def device_planes(trace: Trace) -> List[str]:
    return sorted((p for p in trace if DEVICE_PLANE.match(p)),
                  key=lambda p: int(DEVICE_PLANE.match(p).group(1)))


def program_name(module_event_name: str) -> str:
    """``jit_prefill(1234567)`` -> ``jit_prefill``."""
    return module_event_name.split("(", 1)[0]


def short_name(ev: Ev) -> str:
    """``%copy.547 = bf16[...] copy(...)`` -> ``copy.547``."""
    return ev.name.split(" = ", 1)[0].lstrip("%")


def kernel_name(ev: Ev) -> Optional[str]:
    """The ``pallas_call(name=...)`` of a Pallas kernel's event: its
    instruction's name without the number; None for an instruction XLA
    generated."""
    if PALLAS_TARGET not in ev.name:
        return None
    return re.sub(r"\.\d+$", "", short_name(ev))


def is_collective(ev: Ev) -> bool:
    return bool(COLLECTIVE_NAME.match(short_name(ev))
                or COLLECTIVE_OPCODE.search(ev.name))


def is_container(ev: Ev) -> bool:
    return bool(CONTAINER.match(short_name(ev)))


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

def host_events(trace: Trace, name: str) -> List[Ev]:
    out = []
    for plane, lines in trace.items():
        if DEVICE_PLANE.match(plane):
            continue
        for evs in lines.values():
            out.extend(e for e in evs if e.name == name)
    return sorted(out, key=lambda e: e.start)


def window_of(trace: Trace) -> Interval:
    """The traced window: the benchmark's own ``bench_window`` host range
    where the trace has one, else first to last device instruction."""
    marks = host_events(trace, WINDOW_MARK)
    if marks:
        return (marks[0].start, marks[-1].start + marks[-1].dur)
    ops = [e for p in device_planes(trace)
           for e in trace[p].get(OPS_LINE, ())]
    if not ops:
        raise NoDeviceTrace("the trace holds no device instruction")
    return (min(e.start for e in ops), max(e.start + e.dur for e in ops))


def leaf_ops(trace: Trace, plane: str, window: Interval) -> List[Ev]:
    """Instructions of one chip that start inside the window, without the
    ones that only enclose others."""
    lo, hi = window
    return [e for e in trace[plane].get(OPS_LINE, ())
            if lo <= e.start < hi and not is_container(e)]


def summarize(trace: Trace, host_scopes: Sequence[str] = ()) -> Dict[str, Any]:
    """Everything the per-layer readers take from a trace, in seconds.

    ``busy_s`` is averaged over the chips; the rest is chip 0's.
    ``programs[name]`` = executions, their summed span and the busy time
    inside them; ``kernels[name]`` = calls and summed time; ``ops`` = the
    same for every instruction name; ``comm_s`` the union of collective
    time and ``comm_exposed_s`` the part of it in which no other
    instruction ran; ``idle_gaps`` the idle time by what the host was doing
    (the innermost of ``host_scopes`` covering the gap's middle, else
    ``bench_step`` or "between steps")."""
    planes = device_planes(trace)
    if not planes:
        raise NoDeviceTrace("the trace has no /device:TPU plane")
    window = window_of(trace)
    lo, hi = window
    busy = []
    for p in planes:
        busy.append(total(clip(_iv(leaf_ops(trace, p, window)), lo, hi)))
    p0 = planes[0]
    ops = leaf_ops(trace, p0, window)
    busy_iv = merge(clip(_iv(ops), lo, hi))

    programs: Dict[str, Dict[str, float]] = {}
    for m in trace[p0].get(MODULES_LINE, ()):
        if not (lo <= m.start < hi):
            continue
        rec = programs.setdefault(program_name(m.name),
                                  {"count": 0, "span_s": 0.0, "busy_s": 0.0})
        rec["count"] += 1
        rec["span_s"] += m.dur / 1e9
        rec["busy_s"] += total(clip(busy_iv, m.start, m.start + m.dur)) / 1e9

    kernels: Dict[str, Dict[str, float]] = {}
    by_op: Dict[str, Dict[str, float]] = {}
    for e in ops:
        k = kernel_name(e)
        if k is not None:
            rec = kernels.setdefault(k, {"count": 0, "seconds": 0.0})
            rec["count"] += 1
            rec["seconds"] += e.dur / 1e9
        rec = by_op.setdefault(k or short_name(e),
                               {"count": 0, "seconds": 0.0})
        rec["count"] += 1
        rec["seconds"] += e.dur / 1e9

    # a collective's time: the instruction itself where it is synchronous,
    # the span from its -start to its -done where it is not
    lo_hi = lambda evs: [e for e in evs if lo <= e.start < hi]
    comm_iv = merge(_iv(e for e in ops + lo_hi(
        trace[p0].get(ASYNC_LINE, ())) if is_collective(e)))
    other_iv = merge(_iv(e for e in ops if not is_collective(e)))
    exposed = subtract(comm_iv, other_iv)

    # idle gaps, by what the host was doing at their middle
    # (ranges of one name come from one thread and do not overlap, so the
    # only candidate is the last one that starts before the gap's middle)
    scopes = [(name, evs, [ev.start for ev in evs]) for name, evs in (
        (n, host_events(trace, n)) for n in (STEP_MARK,) + tuple(host_scopes))]
    gaps: Dict[str, float] = collections.defaultdict(float)
    for s, e in subtract([window], busy_iv):
        mid = (s + e) / 2
        label = "between steps"
        for name, evs, starts in scopes:
            i = bisect.bisect_right(starts, mid) - 1
            if i >= 0 and mid < evs[i].start + evs[i].dur:
                label = name
        gaps[label] += (e - s) / 1e9

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "busy_s_chip0": busy[0] / 1e9,
        "chips": len(planes),
        "programs": programs,
        "kernels": kernels,
        "ops": by_op,
        "comm_s": total(comm_iv) / 1e9,
        "comm_exposed_s": total(exposed) / 1e9,
        "idle_gaps": dict(gaps),
    }


def breakdown(summary: Dict[str, Any], top: int = 10) -> Dict[str, Any]:
    ops = sorted(summary["ops"].items(), key=lambda kv: -kv[1]["seconds"])
    gaps = sorted(summary["idle_gaps"].items(), key=lambda kv: -kv[1])
    return {"device_ops": [[k, v["seconds"]] for k, v in ops[:top]],
            "idle_gaps": [[k, v] for k, v in gaps[:top]]}


def describe(trace: Trace, top: int = 12) -> str:
    """What a trace holds, for looking at one by hand."""
    out = []
    for plane, lines in trace.items():
        out.append(f"plane {plane}")
        for line, evs in lines.items():
            if not evs:
                continue
            t0 = min(e.start for e in evs)
            t1 = max(e.start + e.dur for e in evs)
            out.append(f"  line {line!r}: {len(evs)} events, "
                       f"{t0} .. {t1} ns")
            agg: Dict[str, List[float]] = {}
            for e in evs:
                a = agg.setdefault(e.name, [0, 0.0])
                a[0] += 1
                a[1] += e.dur
            for name, (n, d) in sorted(agg.items(),
                                       key=lambda kv: -kv[1][1])[:top]:
                out.append(f"    {n:7d} x {d / 1e6:10.3f} ms  {name[:100]}")
            for e in evs[:2]:
                out.append(f"    e.g. {e.name[:60]!r} stats={e.stats}")
    return "\n".join(out)

