"""Device-truth layer: perfetto trace post-processing for the named scopes
the engines already emit.

PR 3 deliberately shipped ``ds_comm_*_seconds`` as *host-window
attribution* (byte-weighted shares of the measured dispatch window —
monitor/comms.py) because a collective inside a compiled program cannot be
wall-clocked from the host.  The device truth was always in the trace:
every collective wrapper emits a ``ds_comm_<op>`` ``jax.named_scope``, the
train step carries ``ds_fwd_bwd`` / ``ds_optimizer_step``, and the serving
loop emits ``ds_serve_prefill`` / ``ds_serve_decode`` host ranges.  This
module closes the loop: ``jax.profiler.start_trace(...,
create_perfetto_trace=True)`` writes ``perfetto_trace.json.gz`` — plain
trace-event JSON, stdlib gzip+json parseable, no xplane proto dep — and
the post-processor here walks it, separates device tracks from host
threads via the trace's process/thread metadata, matches our named-scope
prefixes, and backfills the metrics registry with device-true series:

- ``ds_comm_<op>_device_seconds`` histograms (+ recomputed
  ``ds_comm_<op>_device_busbw_gbps`` when the caller knows the bytes) —
  kept DISTINCT from the PR 3 analytic ``ds_comm_<op>_seconds`` series,
  which stays the always-on cheap feed;
- a per-step phase breakdown ``ds_profile_{fwd_bwd,optimizer,comm,other,
  gap}_seconds`` where ``gap`` is device idle inside the captured window —
  the overlap-headroom number fine-grained-overlap work (T3,
  arXiv:2401.16677) optimizes against;
- serving-side device decode time vs host dispatch time
  (``ds_profile_serve_decode_{device,host}_seconds``), exposing the
  dispatch slack the sync-free decode path banks on.

Track classification, concretely:

- a *device process* is one whose ``process_name`` metadata starts with
  ``/device`` (TPU/GPU xplane exports one process per device plane);
  within it, *op rows* are threads whose name does not mark a summary lane
  (``Steps`` / ``XLA Modules`` / name-scope lines) — those lanes overlap
  op rows and would inflate the busy union;
- the CPU backend exports no device process; its XLA *runtime* threads
  carry op rows tagged ``args.hlo_op``, which this module accepts as
  device-proxy rows (CPU "device" time is host-thread time, but the
  busy/gap arithmetic still holds);
- when a trace holds NO device rows at all (pure host capture), the phase
  breakdown degrades to the host annotation ranges and says so
  (``"degraded": true``) — host attribution again, but labeled.

Scope matching scans event names AND string arg values (TPU op rows keep
the scope path in ``tf_op``-style args; dedicated name-scope lanes carry
it in the event name).  Per-scope time is an INTERVAL UNION per track
class, so nested/parent events never double-count.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
import threading
from typing import Any, Dict, List, Optional, Tuple

# RELATIVE imports, deliberately: tools/trace_report.py loads this module
# by file path under stub parent packages so an operator box never
# executes the jax-pulling ``deepspeed_tpu/__init__`` (dslint DSL003);
# monitor.comms / monitor.metrics are stdlib-only.
from ..monitor.comms import KNOWN_OPS, busbw_factor


__all__ = ["find_perfetto_trace", "load_trace_events", "summarize_trace",
           "publish_summary", "analyze_capture", "ensure_registered",
           "ProfileBroker",
           "ProfileRequest", "get_profile_broker",
           "TRAIN_SCOPES", "SERVE_SCOPES"]

# the named scopes the engines emit (see monitor/comms.py, runtime/engine.py,
# serving/engine.py); comm ops matched as ds_comm_<known op slug>
TRAIN_SCOPES = ("ds_fwd_bwd", "ds_optimizer_step")
SERVE_SCOPES = ("ds_serve_prefill", "ds_serve_decode")

_COMM_RE = re.compile(
    r"\bds_comm_(" + "|".join(sorted(KNOWN_OPS, key=len, reverse=True)) + r")\b")
_SCOPE_RE = re.compile(
    r"\b(" + "|".join(TRAIN_SCOPES + SERVE_SCOPES) + r")\b")

# summary lanes on a device process that overlap the op rows (step markers,
# whole-module spans, the name-scope band) — excluded from the busy union
_SUMMARY_LANE_RE = re.compile(r"steps|modules|scope|source", re.IGNORECASE)


# ---------------------------------------------------------------------------
# parsing
# ---------------------------------------------------------------------------


def find_perfetto_trace(path: str) -> Optional[str]:
    """Locate the perfetto JSON under a trace directory (jax writes it at
    ``<dir>/plugins/profile/<run>/perfetto_trace.json.gz``); accepts a
    direct file path too.  Newest wins when several runs exist."""
    if os.path.isfile(path):
        return path
    hits = glob.glob(os.path.join(path, "**", "perfetto_trace.json.gz"),
                     recursive=True)
    hits += glob.glob(os.path.join(path, "**", "*.perfetto-trace"),
                      recursive=True)
    return max(hits, key=os.path.getmtime) if hits else None


def load_trace_events(path: str) -> List[Dict[str, Any]]:
    """Read + normalize the trace-event JSON: returns complete-duration
    events as ``{"name", "ts", "dur", "args", "process", "thread"}`` with
    process/thread METADATA already resolved (``ts``/``dur`` stay in the
    file's microseconds)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as fh:
        data = json.load(fh)
    raw = data["traceEvents"] if isinstance(data, dict) else data
    pnames: Dict[Any, str] = {}
    tnames: Dict[Tuple[Any, Any], str] = {}
    for e in raw:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            pnames[e.get("pid")] = str(e.get("args", {}).get("name", ""))
        elif e.get("name") == "thread_name":
            tnames[(e.get("pid"), e.get("tid"))] = \
                str(e.get("args", {}).get("name", ""))
    out = []
    for e in raw:
        if e.get("ph") != "X":
            continue
        ts, dur = e.get("ts"), e.get("dur")
        if ts is None or dur is None:
            continue
        pid, tid = e.get("pid"), e.get("tid")
        out.append({"name": str(e.get("name", "")), "ts": float(ts),
                    "dur": float(dur), "args": e.get("args") or {},
                    "process": pnames.get(pid, ""),
                    "thread": tnames.get((pid, tid), "")})
    return out


def _is_device_op_row(ev: Dict[str, Any]) -> bool:
    """Op-granularity device work: rows on a ``/device`` process outside
    the summary lanes, or (CPU proxy) XLA-runtime rows tagged with the
    executed ``hlo_op``."""
    if ev["process"].startswith("/device"):
        return not _SUMMARY_LANE_RE.search(ev["thread"])
    return "hlo_op" in ev["args"]


def _is_device_row(ev: Dict[str, Any]) -> bool:
    """Any device-process row (op rows + name-scope/summary lanes) or CPU
    proxy op row — the pool scope matching draws from."""
    return ev["process"].startswith("/device") or "hlo_op" in ev["args"]


def _scope_matches(ev: Dict[str, Any]) -> List[str]:
    """Every ds_ scope this event belongs to, scanned from the event name
    and its string arg values (TPU op rows keep the scope path in args)."""
    hay = ev["name"]
    for v in ev["args"].values():
        if isinstance(v, str):
            hay += "\x00" + v
    out = [m.group(0) for m in _SCOPE_RE.finditer(hay)]
    out += ["ds_comm_" + m.group(1) for m in _COMM_RE.finditer(hay)]
    return sorted(set(out))


# -- interval arithmetic (all per-scope times are unions: nested or
# duplicated rows never double-count) ---------------------------------------


def _merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    if not intervals:
        return []
    intervals = sorted(intervals)
    out = [intervals[0]]
    for s, e in intervals[1:]:
        ls, le = out[-1]
        if s <= le:
            out[-1] = (ls, max(le, e))
        else:
            out.append((s, e))
    return out


def _union_len(intervals: List[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in _merge(intervals))


def _subtract(a: List[Tuple[float, float]],
              b: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Interval set difference ``a - b`` (both get merged first)."""
    a, b = _merge(a), _merge(b)
    out: List[Tuple[float, float]] = []
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


def _clip(intervals: List[Tuple[float, float]], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _intersect(a: List[Tuple[float, float]],
               b: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Interval set intersection ``a ∩ b`` (via ``a - (a - b)``)."""
    a = _merge(a)
    return _subtract(a, _subtract(a, b))


# ---------------------------------------------------------------------------
# summarization
# ---------------------------------------------------------------------------


def summarize_trace(trace_path: str,
                    steps: Optional[int] = None,
                    clock: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Walk one perfetto trace into the device-truth summary.

    Returns (durations in SECONDS)::

        {"source", "degraded", "steps", "window_s", "device_busy_s",
         "device_rows",
         "window_lo_us", "window_hi_us",             # raw trace-file ts
         "phases": {"fwd_bwd_s", "optimizer_s", "comm_s", "other_s",
                    "gap_s"},                       # sums to window_s
         "per_step": {... phases / steps ...},       # when steps known
         "comm_device": {op: {"seconds", "count", "max_s"}},
         "serve": {"decode_host_s", "decode_device_s",
                   "dispatch_slack_s", "decode_blocks",
                   "prefill_host_s", "prefill_device_s"} | None}

    ``window_lo_us``/``window_hi_us`` are in the FILE's clock domain —
    microseconds since the profiler-session start — the same domain
    ``/requestz?format=perfetto`` exports into, so a request span and the
    device phase tracks compare directly.  ``clock`` (the capturing
    ``TraceCapture.clock`` anchor) additionally translates the window
    onto the unix clock: ``summary["clock"] = {"anchor_unix",
    "window_unix_lo", "window_unix_hi", "source"}``.

    Phase accounting is exclusive by construction: ``comm`` is the union of
    device comm-scope time; ``fwd_bwd`` / ``optimizer`` are their scope
    unions minus comm; ``other`` is device-busy time in none of our
    scopes; ``gap`` is the device-idle remainder of the window — so the
    five phases partition the captured window exactly.  With no device
    rows at all the same arithmetic runs over the HOST annotation ranges
    and the result is flagged ``degraded`` (host attribution, the PR 3
    semantics, labeled as such).
    """
    path = find_perfetto_trace(trace_path)
    if path is None:
        raise FileNotFoundError(
            f"no perfetto_trace.json.gz under {trace_path!r} — was the "
            f"capture started with perfetto=True on a jax with "
            f"create_perfetto_trace support?")
    events = load_trace_events(path)

    dev_ops = [e for e in events if _is_device_op_row(e)]
    degraded = not dev_ops
    # scope pool: all device rows when we have them (op rows + dedicated
    # name-scope lanes), host rows otherwise
    pool = ([e for e in events if _is_device_row(e)] if not degraded
            else [e for e in events if not _is_device_row(e)])
    busy_rows = dev_ops if not degraded else []

    scope_iv: Dict[str, List[Tuple[float, float]]] = {}
    for e in pool:
        for scope in _scope_matches(e):
            scope_iv.setdefault(scope, []).append((e["ts"],
                                                   e["ts"] + e["dur"]))
    # host annotation ranges (always collected: the serving slack numbers
    # need them even on a device-true trace)
    host_iv: Dict[str, List[Tuple[float, float]]] = {}
    for e in events:
        if _is_device_row(e):
            continue
        for scope in _scope_matches(e):
            host_iv.setdefault(scope, []).append((e["ts"],
                                                  e["ts"] + e["dur"]))
    host_scoped: List[str] = []
    if degraded:
        scope_iv = host_iv
        busy_iv = [iv for ivs in host_iv.values() for iv in ivs]
    else:
        busy_iv = [(e["ts"], e["ts"] + e["dur"]) for e in busy_rows]
        merged_busy = _merge(busy_iv)
        # name-scope/summary lane rows can pad past the op rows or span
        # the idle between them: clamp every scope to the busy union so
        # the phase partition stays exact (phases + gap == window)
        scope_iv = {s: _clip_to(merged_busy, _merge(ivs))
                    for s, ivs in scope_iv.items()}
        # a scope with host ranges but NO device-row matches (CPU proxy
        # rows carry hlo_op tags, not scope paths) is attributed the
        # device-busy time INSIDE its host ranges — device-true durations,
        # host-bracketed assignment, reported in "host_scoped"
        for scope, hivs in host_iv.items():
            if scope_iv.get(scope):
                continue
            attributed = _clip_to(merged_busy, _merge(hivs))
            if attributed:
                scope_iv[scope] = attributed
                host_scoped.append(scope)

    def _clock_block(lo_us: float, hi_us: float) -> Dict[str, Any]:
        return {"anchor_unix": clock.get("unix"),
                "source": clock.get("source"),
                "window_unix_lo": clock.get("unix", 0.0) + lo_us * 1e-6,
                "window_unix_hi": clock.get("unix", 0.0) + hi_us * 1e-6}

    window_rows = busy_iv or [iv for ivs in scope_iv.values() for iv in ivs]
    if not window_rows:
        out = {"source": path, "degraded": True, "steps": steps,
               "window_s": 0.0, "device_busy_s": 0.0, "device_rows": 0,
               "window_lo_us": 0.0, "window_hi_us": 0.0,
               "overlapped_comm_s": 0.0,
               "phases": {"fwd_bwd_s": 0.0, "optimizer_s": 0.0,
                          "comm_s": 0.0, "other_s": 0.0, "gap_s": 0.0},
               "comm_device": {}, "serve": None}
        if clock is not None:
            # the documented clock contract holds on degraded summaries
            # too — those are exactly the captures someone is diagnosing
            out["clock"] = _clock_block(0.0, 0.0)
        return out
    lo = min(s for s, _ in window_rows)
    hi = max(e for _, e in window_rows)
    us = 1e-6  # file timestamps are microseconds

    comm_iv = _merge([iv for scope, ivs in scope_iv.items()
                      if scope.startswith("ds_comm_") for iv in ivs])
    fwd_iv = _merge(scope_iv.get("ds_fwd_bwd", []))
    opt_iv = _merge(scope_iv.get("ds_optimizer_step", []))
    serve_iv = _merge(scope_iv.get("ds_serve_prefill", [])
                      + scope_iv.get("ds_serve_decode", []))
    busy = _merge(_clip(busy_iv, lo, hi))
    comm_s = _union_len(comm_iv)
    fwd_s = _union_len(_subtract(fwd_iv, comm_iv))
    opt_s = _union_len(_subtract(opt_iv, comm_iv + fwd_iv))
    # comm concurrent with compute scopes — the comm the overlap schedule
    # HID.  The exclusive partition claims this time for ``comm`` and
    # subtracts it from fwd_bwd/optimizer exactly once (never from gap,
    # which is computed against the busy union), so overlapped comm is
    # not double-subtracted; this reports it explicitly so the hidden-
    # comm gauge and the bench ablation can read it.
    overlapped_s = _union_len(_intersect(comm_iv, fwd_iv + opt_iv))
    claimed = comm_iv + fwd_iv + opt_iv + (serve_iv if degraded else [])
    other_s = _union_len(_subtract(busy, claimed))
    gap_s = (hi - lo) - _union_len(busy)
    serve_claim = _union_len(_subtract(serve_iv, comm_iv + fwd_iv + opt_iv)) \
        if degraded else 0.0

    comm_device: Dict[str, Dict[str, float]] = {}
    if not degraded:
        for scope, ivs in scope_iv.items():
            if not scope.startswith("ds_comm_"):
                continue
            merged = _merge(ivs)
            if not merged:   # scope clipped to nothing against busy time
                continue
            comm_device[scope[len("ds_comm_"):]] = {
                "seconds": _union_len(merged) * us,
                "count": len(merged),
                "max_s": max(e - s for s, e in merged) * us,
            }

    serve = None
    dec_host = _merge(host_iv.get("ds_serve_decode", []))
    pre_host = _merge(host_iv.get("ds_serve_prefill", []))
    if dec_host or pre_host:
        dev_in_dec = _union_len(_clip_to(busy, dec_host))
        dev_in_pre = _union_len(_clip_to(busy, pre_host))
        serve = {
            "decode_blocks": len(dec_host),
            "decode_host_s": _union_len(dec_host) * us,
            "decode_device_s": dev_in_dec * us,
            "dispatch_slack_s": max(0.0, _union_len(dec_host) - dev_in_dec)
            * us,
            "prefill_host_s": _union_len(pre_host) * us,
            "prefill_device_s": dev_in_pre * us,
        }

    n_steps = steps
    if n_steps is None and opt_iv:
        n_steps = len(opt_iv)
    phases = {"fwd_bwd_s": fwd_s * us, "optimizer_s": opt_s * us,
              "comm_s": comm_s * us,
              "other_s": (other_s + serve_claim) * us, "gap_s": gap_s * us}
    out = {"source": path, "degraded": degraded, "steps": n_steps,
           "window_s": (hi - lo) * us, "device_busy_s": _union_len(busy) * us,
           "device_rows": len(dev_ops), "host_scoped": sorted(host_scoped),
           "window_lo_us": lo, "window_hi_us": hi,
           "overlapped_comm_s": overlapped_s * us,
           "phases": phases, "comm_device": comm_device, "serve": serve}
    if clock is not None:
        out["clock"] = _clock_block(lo, hi)
    if n_steps:
        out["per_step"] = {k: v / n_steps for k, v in phases.items()}
    return out


def _clip_to(intervals: List[Tuple[float, float]],
             windows: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Restrict an interval set to a union of windows."""
    out = []
    for lo, hi in windows:
        out.extend(_clip(intervals, lo, hi))
    return out


# ---------------------------------------------------------------------------
# registry backfill
# ---------------------------------------------------------------------------

_PROFILE_GAUGES = ("ds_profile_fwd_bwd_seconds", "ds_profile_optimizer_seconds",
                   "ds_profile_comm_seconds", "ds_profile_other_seconds",
                   "ds_profile_gap_seconds", "ds_profile_window_seconds",
                   "ds_profile_steps",
                   "ds_profile_serve_decode_host_seconds",
                   "ds_profile_serve_decode_device_seconds",
                   "ds_profile_serve_dispatch_slack_seconds")

# single source of truth for the overlap gauge help strings — registered
# here AND at engine init (docs/OBSERVABILITY.md "Overlap")
OVERLAP_GAUGES = {
    "ds_overlap_buckets":
        "layer-chunked overlap schedule bucket count "
        "(0 = overlap_comm off/ineligible)",
    "ds_overlap_hidden_comm_seconds_est":
        "per-step device comm time measured CONCURRENT with compute "
        "scopes in the last trace capture (the comm the overlap schedule "
        "hid; 0 until a capture runs)",
}


def ensure_registered(registry) -> None:
    """Register the device-truth instrument family up front (namespace
    guard + exporter warm-up; recording still gates on the registry)."""
    for name in _PROFILE_GAUGES:
        registry.gauge(name, "device-true profile (last capture; see "
                             "docs/OBSERVABILITY.md 'Device truth')")
    for name, help_ in OVERLAP_GAUGES.items():
        registry.gauge(name, help_)
    for op in KNOWN_OPS:
        registry.histogram(
            f"ds_comm_{op}_device_seconds",
            f"device-true {op} scope time per capture (perfetto "
            f"post-processor; distinct from the analytic ds_comm_{op}_"
            f"seconds host attribution)")
        registry.gauge(
            f"ds_comm_{op}_device_busbw_gbps",
            f"bus bandwidth recomputed from device-true {op} time")


def publish_summary(summary: Dict[str, Any], registry=None,
                    bytes_per_op: Optional[Dict[str, Tuple[int, int]]] = None
                    ) -> None:
    """Backfill the registry from a :func:`summarize_trace` result.

    ``bytes_per_op`` maps op slug -> (payload bytes moved inside the
    captured window, world size) — the engine knows both from its analytic
    comm plan — enabling the recomputed device busbw gauge.  The analytic
    ``ds_comm_<op>_seconds`` series is NEVER touched here: device truth
    lands only in ``*_device_*`` names.
    """
    if registry is None:
        from ..monitor.metrics import get_registry

        registry = get_registry()
    phases = summary["phases"]
    per = summary.get("per_step") or phases
    g = registry.gauge
    g("ds_profile_fwd_bwd_seconds").set(per["fwd_bwd_s"])
    g("ds_profile_optimizer_seconds").set(per["optimizer_s"])
    g("ds_profile_comm_seconds").set(per["comm_s"])
    g("ds_profile_other_seconds").set(per["other_s"])
    g("ds_profile_gap_seconds").set(per["gap_s"])
    g("ds_profile_window_seconds").set(summary["window_s"])
    g("ds_profile_steps").set(summary.get("steps") or 0)
    # measured comm∩compute per step — backfills the engine-registered
    # overlap gauge (docs/OBSERVABILITY.md "Overlap")
    g("ds_overlap_hidden_comm_seconds_est",
      OVERLAP_GAUGES["ds_overlap_hidden_comm_seconds_est"]).set(
        summary.get("overlapped_comm_s", 0.0)
        / max(1, summary.get("steps") or 1))
    for op, rec in summary.get("comm_device", {}).items():
        registry.histogram(f"ds_comm_{op}_device_seconds").record(
            rec["seconds"])
        if bytes_per_op and op in bytes_per_op and rec["seconds"] > 0:
            nbytes, world = bytes_per_op[op]
            alg = nbytes / rec["seconds"] / 1e9
            registry.gauge(f"ds_comm_{op}_device_busbw_gbps").set(
                alg * busbw_factor(op, world))
    serve = summary.get("serve")
    if serve:
        g("ds_profile_serve_decode_host_seconds").set(serve["decode_host_s"])
        g("ds_profile_serve_decode_device_seconds").set(
            serve["decode_device_s"])
        g("ds_profile_serve_dispatch_slack_seconds").set(
            serve["dispatch_slack_s"])


def analyze_capture(trace_dir: str, steps: int,
                    bytes_per_op: Optional[Dict[str, Tuple[int, int]]] = None,
                    clock: Optional[Dict[str, Any]] = None,
                    **tags: Any) -> Dict[str, Any]:
    """Summarize + tag + registry-backfill in one call — the shared tail
    of every capture lifecycle (training aux slot, serving ``/profilez``):
    ``tags`` (e.g. ``trigger=\"watchdog\"``, ``engine=\"serving\"``) land
    on the returned summary verbatim; ``clock`` (the capture's
    ``TraceCapture.clock`` anchor) translates the window onto the unix
    clock for cross-file correlation (``/requestz``)."""
    summary = summarize_trace(trace_dir, steps=steps, clock=clock)
    summary.update(tags)
    publish_summary(summary, bytes_per_op=bytes_per_op)
    return summary


# ---------------------------------------------------------------------------
# on-demand capture broker (/profilez)
# ---------------------------------------------------------------------------


class ProfileRequest:
    """One on-demand capture: created by the HTTP thread, claimed and
    fulfilled by whichever live engine hits its next step boundary."""

    def __init__(self, steps: int, trace_dir: Optional[str] = None):
        self.steps = max(1, int(steps))
        self.trace_dir = trace_dir
        self.result: Optional[Dict[str, Any]] = None
        self.error: Optional[str] = None
        self._done = threading.Event()

    def finish(self, summary: Dict[str, Any]) -> None:
        self.result = summary
        self._done.set()

    def fail(self, message: str) -> None:
        self.error = message
        self._done.set()

    def wait(self, timeout: float) -> Dict[str, Any]:
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"profile capture did not complete within {timeout:.0f}s "
                f"(is an engine stepping?)")
        if self.error is not None:
            raise RuntimeError(self.error)
        return self.result


class ProfileBroker:
    """Single-slot handoff between the metrics HTTP server and the live
    engines.  ``submit`` parks one request; engines check :attr:`pending`
    (one attribute load per step — the hot-path cost) and ``claim`` it at
    a step boundary; the claimer runs the windowed capture, post-processes,
    and resolves the request.  One capture at a time: jax has a single
    global profiler session."""

    # dslint DSL006: the HTTP thread and N engine threads race on the
    # single slot — every transition holds the lock (``pending`` is READ
    # lock-free as the engines' one-attribute-load fast path; writes are
    # what must serialize)
    _dslint_shared = {"pending": "lock:_lock", "_claimed": "lock:_lock"}

    def __init__(self):
        self._lock = threading.Lock()
        self.pending: Optional[ProfileRequest] = None
        self._claimed: Optional[ProfileRequest] = None

    def submit(self, steps: int,
               trace_dir: Optional[str] = None) -> ProfileRequest:
        with self._lock:
            if self.pending is not None or self._claimed is not None:
                raise RuntimeError("a profile capture is already in flight")
            req = ProfileRequest(steps, trace_dir)
            self.pending = req
            return req

    def claim(self) -> Optional[ProfileRequest]:
        with self._lock:
            req = self.pending
            if req is not None:
                self.pending = None
                self._claimed = req
            return req

    def resolve(self, req: ProfileRequest, summary=None,
                error: Optional[str] = None) -> None:
        with self._lock:
            if self._claimed is req:
                self._claimed = None
        if error is not None:
            req.fail(error)
        else:
            req.finish(summary)

    def cancel(self, req: ProfileRequest) -> None:
        """Abandon a timed-out request so the slot frees: clears it from
        ``pending`` (nobody claimed it) AND from ``_claimed`` (an engine
        claimed it but stopped stepping before the window closed — leaving
        it there would 409 every later submit forever).  A late
        ``resolve`` from the original claimer is harmless: it only sets an
        event nobody waits on."""
        with self._lock:
            if self.pending is req:
                self.pending = None
            if self._claimed is req:
                self._claimed = None


_BROKER = ProfileBroker()


def get_profile_broker() -> ProfileBroker:
    """The process-global broker ``/profilez`` submits to and every live
    engine polls at its step boundary."""
    return _BROKER
