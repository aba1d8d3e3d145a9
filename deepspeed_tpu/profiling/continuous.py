"""Always-on continuous profiler (ISSUE 20 tentpole).

Every device-truth number used to be operator-triggered: ``/profilez`` is
one-shot, so ``ds_comm_<op>_device_seconds`` only existed while someone
was watching.  This module turns the existing capture/decompose machinery
(``profiling/trace.py`` TraceCapture + ``profiling/device_trace.py``)
into a scheduled, low-duty-cycle attribution feed:

- the training engine's boundary tick and the serving loop drive a
  :class:`ContinuousProfiler`; every ``every_steps`` steps or
  ``every_seconds`` seconds (whichever comes FIRST), it opens a short
  TraceCapture window — unless the projected capture overhead would push
  the cumulative duty cycle past ``max_duty_cycle`` (default ≤1% of run
  wall clock), in which case the window is deferred;
- each closed window is decomposed offline via
  ``device_trace.analyze_capture`` (feeding the one registry the
  operator-triggered paths feed: ``ds_comm_<op>_device_seconds``,
  ``ds_profile_*``) and additionally committed as
  ``ds_prof_scope_device_seconds{scope=}`` + ``ds_prof_window_*``
  coverage/overhead gauges;
- window summaries persist to a bounded on-disk ring
  (``profile_history/ds_prof_window_<seq>.json``, retention by count AND
  bytes, atomic tmp+``os.replace``) that ``GET /profilez/history``,
  ``tools/trace_report.py --history``, ``tools/metrics_dump.py
  --profile`` and ``fleet_dump --profiles`` all read;
- a window-over-window differ names the regressing scope when the
  step-time decomposition drifts past tolerance (flight event
  ``prof_regression`` + ``ds_prof_regressions_total{scope=}``); the
  tolerance rules are ``(substring, tol)`` pairs, first match wins
  (:func:`tolerance_for`).

Layout contract: everything above the ``live capture half`` marker is
stdlib-only with RELATIVE imports, so jax-less operator tools load this
file by path under stub packages (the fleet_dump/trace_report idiom;
dslint rule DSL003 pins the closure).  The live half lazily imports
TraceCapture (which pulls jax) only when a window actually opens.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from .device_trace import analyze_capture

SCHEMA_VERSION = 1

# phase scopes every window carries (a partition of the window: the five
# per-step phase seconds sum to the per-step wall clock)
PHASE_SCOPES = ("fwd_bwd", "optimizer", "comm", "other", "gap")

# regression tolerances: (substring, tol) rules, FIRST match wins,
# default otherwise.  All window scopes are seconds — lower is better;
# a relative increase past tolerance is a regression.  gap/other are
# the noisy remainder lanes, so they get a looser default bar.
DEFAULT_TOLERANCE = 0.25
SCOPE_TOLERANCES: Tuple[Tuple[str, float], ...] = (
    ("gap", 0.50),
    ("other", 0.50),
)

_WINDOW_RE = re.compile(r"^ds_prof_window_(\d+)\.json$")


def tolerance_for(name: str,
                  tolerances: Optional[List[Tuple[str, float]]] = None,
                  default: float = DEFAULT_TOLERANCE) -> float:
    """First substring match wins: the caller's rules, then the built-in
    scope rules, then default."""
    for sub, tol in list(tolerances or []) + list(SCOPE_TOLERANCES):
        if sub in name:
            return float(tol)
    return float(default)


# ---------------------------------------------------------------------------
# history ring (offline half — jax-free)
# ---------------------------------------------------------------------------


class HistoryRing:
    """Bounded on-disk ring of window summaries.

    One JSON file per window (``ds_prof_window_<seq>.json``, monotonic
    sequence numbers), written atomically (tmp + ``os.replace``, the
    checkpoint latest-pointer idiom) so a reader — the HTTP handler, a
    fleet scrape, an operator tool — never sees a torn file.  Retention
    prunes oldest-first by BOTH count (``max_windows``) and total bytes
    (``max_bytes``)."""

    def __init__(self, directory: str, max_windows: int = 64,
                 max_bytes: int = 4 << 20):
        self.directory = directory
        self.max_windows = max(1, int(max_windows))
        self.max_bytes = max(1, int(max_bytes))

    def paths(self) -> List[str]:
        """Window files oldest-first (by sequence number)."""
        try:
            names = os.listdir(self.directory)
        except OSError:
            return []
        out = []
        for n in names:
            m = _WINDOW_RE.match(n)
            if m:
                out.append((int(m.group(1)), os.path.join(self.directory, n)))
        return [p for _, p in sorted(out)]

    def next_seq(self) -> int:
        paths = self.paths()
        if not paths:
            return 1
        m = _WINDOW_RE.match(os.path.basename(paths[-1]))
        return int(m.group(1)) + 1 if m else 1

    def append(self, window: Dict[str, Any]) -> str:
        """Atomically persist one window summary; prune; return its path."""
        os.makedirs(self.directory, exist_ok=True)
        seq = int(window.get("seq") or self.next_seq())
        window["seq"] = seq
        path = os.path.join(self.directory, f"ds_prof_window_{seq:08d}.json")
        tmp = path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(window, fh, indent=1, sort_keys=True)
        os.replace(tmp, path)
        self.prune()
        return path

    def prune(self) -> None:
        paths = self.paths()
        sizes = {}
        for p in paths:
            try:
                sizes[p] = os.path.getsize(p)
            except OSError:
                sizes[p] = 0
        total = sum(sizes.values())
        while paths and (len(paths) > self.max_windows
                         or total > self.max_bytes):
            victim = paths.pop(0)
            total -= sizes.get(victim, 0)
            try:
                os.unlink(victim)
            except OSError:
                pass

    @staticmethod
    def load(path: str) -> Optional[Dict[str, Any]]:
        try:
            with open(path) as fh:
                return json.load(fh)
        except (OSError, ValueError):
            return None   # pruned underneath us, or torn by a crash

    def latest(self, n: int = 1) -> List[Dict[str, Any]]:
        """Newest ``n`` windows, oldest-first."""
        out = []
        for p in self.paths()[-max(0, int(n)):]:
            w = self.load(p)
            if w is not None:
                out.append(w)
        return out


# ---------------------------------------------------------------------------
# window schema + differ (offline half — jax-free)
# ---------------------------------------------------------------------------


def build_window(summary: Dict[str, Any], *, engine: str, step: int,
                 capture_wall_s: float, coverage_ratio: float,
                 overhead_ratio: float,
                 trigger: str = "continuous") -> Dict[str, Any]:
    """Compact one ``summarize_trace`` result into the persisted window
    record.  ``scopes`` holds PER-STEP device-seconds and is an exact
    partition of the per-step wall clock (the five phases), plus one
    ``comm_<op>`` entry per device-true collective; the raw
    ``comm_device`` table and the ``clock`` anchors ride along verbatim
    so fleet merges can place the window on the shared unix clock."""
    per = summary.get("per_step") or summary["phases"]
    steps = summary.get("steps") or 1
    scopes = {name: per[name + "_s"] for name in PHASE_SCOPES}
    for op, rec in (summary.get("comm_device") or {}).items():
        scopes["comm_" + op] = rec["seconds"] / max(1, steps)
    return {
        "schema_version": SCHEMA_VERSION,
        "engine": engine,
        "trigger": trigger,
        "step": int(step),
        "steps": steps,
        "degraded": bool(summary.get("degraded")),
        "source": summary.get("source"),
        "window_s": summary["window_s"],
        "device_busy_s": summary["device_busy_s"],
        "busy_ratio": (summary["device_busy_s"] / summary["window_s"]
                       if summary["window_s"] else 0.0),
        "capture_wall_s": capture_wall_s,
        "coverage_ratio": coverage_ratio,
        "overhead_ratio": overhead_ratio,
        "clock": summary.get("clock"),
        "scopes": scopes,
        "comm_device": summary.get("comm_device") or {},
    }


def diff_windows(prev: Dict[str, Any], cur: Dict[str, Any], *,
                 default_tol: float = DEFAULT_TOLERANCE,
                 tolerances: Optional[List[Tuple[str, float]]] = None,
                 min_seconds: float = 5e-5) -> List[Dict[str, Any]]:
    """Window-over-window regression triage: compare per-step scope
    device-seconds (plus the synthesized ``step_time`` = per-step wall
    clock) and name every scope whose time grew past tolerance.

    Relative drift ``(cur - prev) / prev`` against a substring-matched
    tolerance (:func:`tolerance_for`); scopes below the ``min_seconds``
    noise floor in the BASELINE window are skipped (a 2us scope
    tripling is measurement noise, not a finding).
    Returns regressions sorted worst-first."""
    def scope_map(w: Dict[str, Any]) -> Dict[str, float]:
        out = dict(w.get("scopes") or {})
        steps = w.get("steps") or 1
        if w.get("window_s"):
            out["step_time"] = w["window_s"] / max(1, steps)
        return out

    base, now = scope_map(prev), scope_map(cur)
    out = []
    for scope, prev_s in base.items():
        if prev_s < min_seconds:
            continue
        cur_s = now.get(scope)
        if cur_s is None:
            continue
        tol = tolerance_for(scope, tolerances, default_tol)
        rel = (cur_s - prev_s) / prev_s
        if rel > tol:
            out.append({"scope": scope, "prev_s": prev_s, "cur_s": cur_s,
                        "rel": rel, "tol": tol})
    return sorted(out, key=lambda r: -r["rel"])


def render_window(window: Dict[str, Any]) -> str:
    """Terminal render of one window record (shared by ``trace_report
    --history`` and the fleet/metrics dump tools' profile views)."""
    def pct(v: float) -> str:
        return f"{100.0 * v:.2f}%"

    head = (f"window #{window.get('seq', '?')} engine={window.get('engine')}"
            f" step={window.get('step')}: {window.get('steps')} step(s), "
            f"{window.get('window_s', 0.0) * 1e3:.3f}ms wall, device busy "
            f"{pct(window.get('busy_ratio', 0.0))}")
    lines = [head]
    if window.get("degraded"):
        lines.append("NOTE: degraded (host-range attribution only)")
    lines.append(f"run coverage {pct(window.get('coverage_ratio', 0.0))}, "
                 f"capture overhead {pct(window.get('overhead_ratio', 0.0))}")
    scopes = sorted((window.get("scopes") or {}).items(),
                    key=lambda kv: -kv[1])
    steps = window.get("steps") or 1
    wall = window.get("window_s", 0.0) / max(1, steps)
    rows = []
    for name, sec in scopes:
        if sec <= 0.0:
            continue
        share = f"{100.0 * sec / wall:.1f}%" if wall else ""
        rows.append([name, f"{sec * 1e3:.4f}ms", share])
    if rows:
        widths = [max(len(r[i]) for r in [["scope", "per-step", "share"]]
                      + rows) for i in range(3)]
        lines.append("")
        lines.append("  ".join(c.ljust(w) for c, w in
                               zip(["scope", "per-step", "share"], widths)))
        lines.append("  ".join("-" * w for w in widths))
        for r in rows:
            lines.append("  ".join(c.ljust(w)
                                   for c, w in zip(r, widths)).rstrip())
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# live capture half (imports that pull jax stay lazy below this line)
# ---------------------------------------------------------------------------

# process-global directory of live profilers, keyed by engine kind —
# the /profilez/history handler reads it; latest registration wins.
# dslint DSL006: assignment under _ACTIVE_LOCK; the HTTP thread only
# reads (dict snapshot) — GIL-atomic.
_ACTIVE: Dict[str, "ContinuousProfiler"] = {}
_ACTIVE_LOCK = threading.Lock()


def history_snapshot(limit: int = 8) -> Dict[str, Any]:
    """Latest windows of every live profiler in this process — the
    ``GET /profilez/history`` payload (and the fleet scrape unit)."""
    with _ACTIVE_LOCK:
        active = sorted(_ACTIVE.items())
    windows: List[Dict[str, Any]] = []
    for _, prof in active:
        windows.extend(prof.ring.latest(limit))
    windows.sort(key=lambda w: (str(w.get("engine")), w.get("seq") or 0))
    return {"engines": [name for name, _ in active], "windows": windows}


class ContinuousProfiler:
    """Scheduled TraceCapture windows + offline decompose + history ring.

    The owning engine calls :meth:`maybe_begin` at a step boundary when no
    other capture slot owns the one global jax profiler session, and
    :meth:`after_step` after every completed step.  Disabled is not a
    state this class has — the engines keep ``self._cprof = None`` and
    one ``is not None`` branch per boundary (the PR 3 contract)."""

    def __init__(self, *, engine: str = "train",
                 every_steps: int = 200, every_seconds: float = 120.0,
                 capture_steps: int = 2, max_duty_cycle: float = 0.01,
                 history_dir: str = "profile_history",
                 max_windows: int = 64, max_bytes: int = 4 << 20,
                 regression_tolerance: float = DEFAULT_TOLERANCE,
                 tolerances: Optional[List[Tuple[str, float]]] = None,
                 min_scope_seconds: float = 5e-5,
                 bytes_per_op_fn: Optional[Callable[[int], dict]] = None,
                 registry=None, flight=None,
                 clock: Callable[[], float] = time.perf_counter):
        self.engine = engine
        self.every_steps = max(1, int(every_steps))
        self.every_seconds = float(every_seconds)
        self.capture_steps = max(1, int(capture_steps))
        self.max_duty_cycle = float(max_duty_cycle)
        self.regression_tolerance = float(regression_tolerance)
        self.tolerances = list(tolerances or [])
        self.min_scope_seconds = float(min_scope_seconds)
        self.ring = HistoryRing(history_dir, max_windows=max_windows,
                                max_bytes=max_bytes)
        self._bytes_per_op_fn = bytes_per_op_fn
        self._registry = registry
        self._flight = flight
        self._clock = clock
        self._t0 = clock()
        self._last_t = self._t0         # end of the previous window
        self._last_step = 0
        self._cap = None                # live TraceCapture, else None
        self._cap_t0 = 0.0
        self._captured_s = 0.0          # window wall covered so far
        self._overhead_s = 0.0          # capture + decompose wall so far
        self.windows = 0
        self.skipped_duty = 0           # deferrals by the duty-cycle cap
        # resume against an existing ring: the differ baselines on the
        # newest persisted window, so a restart keeps triaging
        prev = self.ring.latest(1)
        self._prev_window = prev[-1] if prev else None
        with _ACTIVE_LOCK:
            _ACTIVE[engine] = self

    # -- scheduling ------------------------------------------------------

    @property
    def active(self) -> bool:
        return self._cap is not None

    def due(self, upcoming_step: int) -> bool:
        """Every N steps or T seconds, whichever comes first."""
        if upcoming_step - self._last_step >= self.every_steps:
            return True
        return self._clock() - self._last_t >= self.every_seconds

    def _duty_ok(self) -> bool:
        """Projected duty cycle stays under the cap: the cost of the NEXT
        window is estimated from the measured per-window overhead so far
        (the first window is always admitted — nothing measured yet)."""
        if self.windows == 0:
            return True
        elapsed = max(self._clock() - self._t0, 1e-9)
        est = self._overhead_s / self.windows
        return (self._overhead_s + est) <= self.max_duty_cycle * elapsed

    def maybe_begin(self, upcoming_step: int) -> bool:
        """Open a capture window covering ``upcoming_step ..
        upcoming_step + capture_steps - 1``.  The CALLER guarantees no
        other capture slot (profile_trace, /profilez, watchdog) owns the
        global profiler session."""
        if self._cap is not None:
            return False
        if not self.due(upcoming_step):
            return False
        if not self._duty_ok():
            self.skipped_duty += 1
            # push the timer cadence back so the deferral doesn't retry
            # every single boundary while the budget recovers
            self._last_t = self._clock()
            return False
        from .trace import TraceCapture  # dslint: disable=DSL003 -- live-capture path only; the offline half (tools/trace_report.py --history, tools/metrics_dump.py --profile) never opens a window, and on an engine box jax is already present
        trace_dir = os.path.join(self.ring.directory, "_capture")
        cap = TraceCapture(trace_dir, start_step=upcoming_step,
                           num_steps=self.capture_steps, perfetto=True)
        try:
            cap.maybe_start(upcoming_step)
        except Exception as exc:  # profiler session contention, FS errors
            self._count_failure()
            self._record_flight("prof_capture_failed", error=str(exc))
            self._last_t = self._clock()
            return False
        if not cap.active:
            return False
        self._cap = cap
        self._cap_t0 = self._clock()
        return True

    def after_step(self, completed_step: int) -> Optional[Dict[str, Any]]:
        """Close + decompose + commit when the window just finished;
        returns the persisted window record then, else None."""
        if self._cap is None:
            return None
        try:
            trace_dir = self._cap.after_step(completed_step)
        except Exception as exc:
            self._cap = None
            self._count_failure()
            self._record_flight("prof_capture_failed", error=str(exc))
            return None
        if trace_dir is None:
            return None
        return self._finish(trace_dir, completed_step)

    def close(self) -> None:
        """Abandon a still-open window (engine shutdown mid-capture)."""
        cap, self._cap = self._cap, None
        if cap is not None:
            try:
                cap.close()
            except Exception:
                pass

    # -- decompose + commit ---------------------------------------------

    def _finish(self, trace_dir: str,
                completed_step: int) -> Optional[Dict[str, Any]]:
        cap, self._cap = self._cap, None
        now = self._clock()
        window_wall = now - self._cap_t0
        try:
            bytes_per_op = (self._bytes_per_op_fn(cap.num_steps)
                            if self._bytes_per_op_fn else None)
            summary = analyze_capture(
                trace_dir, cap.num_steps, bytes_per_op=bytes_per_op,
                clock=cap.clock, trigger="continuous", engine=self.engine)
        except Exception as exc:
            self._count_failure()
            self._record_flight("prof_decompose_failed", error=str(exc))
            return None
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
            # book the whole capture+decompose cost before the next
            # scheduling decision reads the duty-cycle ledger
            decompose_done = self._clock()
            self._captured_s += window_wall
            self._overhead_s += decompose_done - self._cap_t0
            self._last_t = decompose_done
            self._last_step = completed_step
        elapsed = max(self._clock() - self._t0, 1e-9)
        window = build_window(
            summary, engine=self.engine, step=completed_step,
            capture_wall_s=window_wall,
            coverage_ratio=self._captured_s / elapsed,
            overhead_ratio=self._overhead_s / elapsed)
        self.ring.append(window)
        self.windows += 1
        regressions = []
        if self._prev_window is not None:
            regressions = diff_windows(
                self._prev_window, window,
                default_tol=self.regression_tolerance,
                tolerances=self.tolerances,
                min_seconds=self.min_scope_seconds)
        self._prev_window = window
        self._publish(window, regressions)
        return window

    # -- registry / flight commits --------------------------------------

    def _reg(self):
        if self._registry is not None:
            return self._registry
        from ..monitor.metrics import get_registry

        return get_registry()

    def _count_failure(self) -> None:
        try:
            self._reg().counter("ds_prof_capture_failures_total").inc()
        except Exception:
            pass

    def _record_flight(self, kind: str, **fields: Any) -> None:
        flight = self._flight
        if flight is None:
            from ..monitor.flight_recorder import get_flight_recorder

            flight = get_flight_recorder()
        try:
            flight.record(kind, engine=self.engine, **fields)
        except Exception:
            pass

    def _publish(self, window: Dict[str, Any],
                 regressions: List[Dict[str, Any]]) -> None:
        reg = self._reg()
        g = reg.gauge
        g("ds_prof_window_seconds").set(window["window_s"])
        g("ds_prof_window_steps").set(window["steps"])
        g("ds_prof_window_coverage_ratio").set(window["coverage_ratio"])
        g("ds_prof_window_overhead_ratio").set(window["overhead_ratio"])
        for scope, sec in window["scopes"].items():
            g("ds_prof_scope_device_seconds", labels={"scope": scope}).set(sec)
        reg.counter("ds_prof_windows_total").inc()
        for r in regressions:
            reg.counter("ds_prof_regressions_total",
                        "window-over-window scope regressions flagged by "
                        "the profile differ",
                        labels={"scope": r["scope"]}).inc()
            self._record_flight(
                "prof_regression", scope=r["scope"], step=window["step"],
                prev_s=round(r["prev_s"], 9), cur_s=round(r["cur_s"], 9),
                rel=round(r["rel"], 4), tol=r["tol"])


def ensure_registered(registry) -> None:
    """Pre-register the bare ``ds_prof_*`` series (namespace guard +
    exporter warm-up, like ``device_trace.ensure_registered``).  The
    labeled families — ``ds_prof_scope_device_seconds{scope=}`` and
    ``ds_prof_regressions_total{scope=}`` — register at first use with
    their labels (the ``ds_slo_burn_total{rule=}`` idiom): a name must be
    uniformly labeled or uniformly bare."""
    registry.gauge("ds_prof_window_seconds",
                   "wall length of the last continuous-profiler window")
    registry.gauge("ds_prof_window_steps",
                   "steps inside the last continuous-profiler window")
    registry.gauge("ds_prof_window_coverage_ratio",
                   "fraction of run wall clock covered by completed "
                   "continuous-profiler windows")
    registry.gauge("ds_prof_window_overhead_ratio",
                   "capture+decompose wall time as a fraction of run wall "
                   "clock (duty cycle actually paid; capped by config)")
    registry.counter("ds_prof_windows_total",
                     "completed continuous-profiler windows")
    registry.counter("ds_prof_capture_failures_total",
                     "continuous-profiler captures that failed to open, "
                     "close, or decompose")
