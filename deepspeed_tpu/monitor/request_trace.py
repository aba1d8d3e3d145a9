"""Per-request span tracing for the serving stack.

The PR 2/4 serving metrics are *aggregate*: a p99 TTFT histogram can say
the tail is slow, but not WHICH requests were slow or WHY — queue wait vs
chunked prefill vs decode stretch vs paged-KV preemption.  This module is
the per-request half: the scheduler and serving engine already own every
lifecycle edge (submit, admit, each prefill chunk, first token on the host,
decode blocks, preempt/requeue, EOS-drain fetch, finish), and the
:class:`RequestTracer` records them into one timeline per request.

Two layers per timeline, with distinct semantics:

- **edges** — lifecycle transitions ``(t, phase_entered)``.  The phase a
  request is in between two consecutive edges is the phase entered at the
  first, so the per-request phase durations (``queue`` / ``prefill`` /
  ``decode`` / ``preempted_wait``) TELESCOPE to exactly
  ``t_finish - t_submit``: the phase-attribution histograms
  (``ds_serve_phase_*_seconds``, recorded at finish) reconcile with the
  existing ``ds_serve_request_latency_seconds`` observations by
  construction (tested).  ``prefill`` here is admit → the first
  token's value on the host (it includes the slot's share of interleaving
  with other slots' chunks and the wait for the chip — that IS the
  latency the request experienced);
  ``preempted_wait`` is preempt → re-admission.
- **spans** — the measured host dispatch windows inside those phases
  (``prefill_chunk`` / ``decode_block`` / ``drain_fetch``, each with its
  token count), capped per request so a pathological run cannot grow a
  timeline unboundedly.

Retention is fixed-size: a ring of the most recently completed timelines
plus a top-K slowest-exemplar heap (the tail survives even when the ring
has churned past it).  Disabled (the default) every hook is ONE
attribute-load + branch and allocates nothing — the same hot-path
contract as ``monitor/metrics.py``; enable via
``init_serving(request_trace=True)`` or ``get_request_tracer().enable()``.

Exports (see docs/OBSERVABILITY.md "Request spans"):

- ``GET /requestz`` on the metrics server — JSON snapshot (recent ring,
  slowest exemplars, tail attribution);
- ``GET /requestz?format=perfetto`` — trace-event JSON whose timestamps
  are keyed to the clock anchor of the most recent profiler capture
  (:func:`set_trace_clock_anchor`, stamped by ``TraceCapture`` at
  ``start_trace`` time — the trace file's ts epoch), so request spans
  and a ``/profilez`` device capture load in ONE Perfetto session on a
  shared clock;
- :meth:`RequestTracer.tail_attribution` — the dominant phase among
  requests above the p99 latency cut, attached to the bench serving
  record and rendered by ``tools/metrics_dump.py --requests``.

Zero dependencies: stdlib + the metrics registry (itself stdlib-only).
"""

from __future__ import annotations

import heapq
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

from deepspeed_tpu.monitor.metrics import get_registry

__all__ = ["RequestTracer", "get_request_tracer", "PHASES",
           "StepTimeline", "get_step_timeline",
           "set_trace_clock_anchor", "get_trace_clock_anchor"]

# the edge-partition phases; each gets a ds_serve_phase_<phase>_seconds
# histogram recorded once per finished request
PHASES = ("queue", "prefill", "decode", "preempted_wait")

DEFAULT_RING = 256
DEFAULT_SLOWEST_K = 32
DEFAULT_MAX_SPANS = 512


# ---------------------------------------------------------------------------
# trace clock anchor
# ---------------------------------------------------------------------------
# jax's perfetto export writes timestamps in microseconds RELATIVE to the
# profiler-session start (measured: the first event lands within ~100us of
# the start_trace call).  TraceCapture stamps this anchor immediately
# before start_trace, so mapping a perf_counter reading t to
# (t - anchor_perf) * 1e6 puts host spans in the SAME clock domain as the
# capture's device rows.  Before any capture runs, the anchor is the
# process import time (spans still export, just in a process-relative
# domain nothing else shares).

_ANCHOR: Dict[str, Any] = {"perf": time.perf_counter(), "unix": time.time(),
                           "source": "process"}

# dslint DSL006 contract (enforced statically, tools/dslint.py): the
# anchor is read lock-free by /requestz and the perfetto exporter — it
# may only be REBOUND whole, never patched field-by-field (a torn
# perf/unix pair was the PR 7 scrape-race class)
_DSLINT_SHARED_GLOBALS = {"_ANCHOR": "swap"}


def set_trace_clock_anchor() -> Dict[str, Any]:
    """Stamp 'now' as the trace-session clock epoch; returns a copy.
    Called by ``TraceCapture.maybe_start`` immediately before
    ``jax.profiler.start_trace`` (the perfetto file's ts epoch).  The
    global is swapped whole (never mutated in place) so a concurrent
    scrape can't read a torn perf/unix pair."""
    global _ANCHOR
    anchor = {"perf": time.perf_counter(), "unix": time.time(),
              "source": "trace_session"}
    _ANCHOR = anchor
    return dict(anchor)


def get_trace_clock_anchor() -> Dict[str, Any]:
    """The most recent capture's clock anchor (process-start fallback)."""
    return dict(_ANCHOR)


def _perfetto_doc(events: List[Dict[str, Any]],
                  anchor: Dict[str, Any]) -> Dict[str, Any]:
    """The ONE trace-event envelope every exporter in this process emits
    (request spans and the training step timeline both go through it):
    ``ts`` values are microseconds since ``anchor["perf"]``, and
    ``otherData.clock_anchor_unix`` is that same instant on the WALL
    clock — the per-endpoint translation key ``tools/fleet_dump.py
    --trace`` uses to merge N processes' exports onto one shared clock
    (ts' = ts + (anchor_unix_source - anchor_unix_reference) * 1e6)."""
    return {"displayTimeUnit": "ns", "traceEvents": events,
            "otherData": {"clock_anchor_unix": anchor["unix"],
                          "clock_source": anchor["source"],
                          "domain": "microseconds since the last "
                                    "profiler-session start"}}


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------


class RequestTracer:
    """Process-global per-request span recorder (see module docstring).

    Single-writer like the metrics instruments: all hooks run on the
    engine thread; ``/requestz`` scrapes read completed timelines, which
    are append-only dicts swapped in whole (GIL-atomic)."""

    # dslint DSL006: scrape threads snapshot-copy these (list(self._ring))
    # — every writer-side mutation must be ONE GIL-atomic op (append /
    # heappush / whole rebind); published records are immutable
    _dslint_shared = {"_ring": "atomic", "_slowest": "atomic"}

    def __init__(self, ring: int = DEFAULT_RING,
                 slowest_k: int = DEFAULT_SLOWEST_K,
                 max_spans: int = DEFAULT_MAX_SPANS):
        self.enabled = False
        self._open: Dict[int, Dict[str, Any]] = {}
        self._ring: deque = deque(maxlen=max(1, int(ring)))
        self._slowest_k = max(1, int(slowest_k))
        self._slowest: List[Tuple[float, int, Dict[str, Any]]] = []
        self._max_spans = max(1, int(max_spans))
        self._seq = 0                 # completion order (heap tiebreak)
        self.completed_total = 0
        # phase-attribution histograms (registered unconditionally so the
        # namespace guard covers them; record() gates on the registry)
        reg = get_registry()
        self._h_phase = {
            p: reg.histogram(
                f"ds_serve_phase_{p}_seconds",
                f"per-request time in the {p} phase (edge partition; the "
                f"four phases sum to ds_serve_request_latency_seconds)")
            for p in PHASES}

    # -- switches -------------------------------------------------------
    def enable(self) -> "RequestTracer":
        self.enabled = True
        return self

    def disable(self) -> "RequestTracer":
        """Stop tracing and drop in-flight timelines: their finish edges
        will never be recorded while disabled, so keeping them would leak
        them as phantom 'open' requests forever (retained completions are
        kept)."""
        self.enabled = False
        self._open.clear()
        return self

    def configure(self, ring: Optional[int] = None,
                  slowest_k: Optional[int] = None,
                  max_spans: Optional[int] = None) -> "RequestTracer":
        """Resize the retention structures IN PLACE: existing completions
        are kept (the slowest heap is trimmed to the new K; the ring to
        its new length) — call :meth:`reset` for a clean slate.  The
        bench sizes the ring to its wave so tail attribution sees every
        request."""
        if ring is not None:
            self._ring = deque(self._ring, maxlen=max(1, int(ring)))
        if slowest_k is not None:
            self._slowest_k = max(1, int(slowest_k))
            self._slowest = heapq.nsmallest(
                self._slowest_k, self._slowest,
                key=lambda it: (-it[0], it[1]))
            heapq.heapify(self._slowest)
        if max_spans is not None:
            self._max_spans = max(1, int(max_spans))
        return self

    def reset(self) -> None:
        """Drop every retained and open timeline (bench warm-pass
        hygiene, mirrors ``registry.reset()``)."""
        self._open.clear()
        self._ring.clear()
        self._slowest = []
        self.completed_total = 0

    # -- hot path: lifecycle edges --------------------------------------
    # Every hook is one attribute-load + branch while disabled; arguments
    # are plain scalars so a disabled call allocates nothing.

    def submit(self, rid: int, t: float, prompt_len: int,
               max_new: int, trace: str = "") -> None:
        """``trace`` is the propagated distributed-trace id (the 32-hex
        trace-id from the router's ``traceparent`` header, empty for
        direct submits): it keys this replica's timeline to the router's
        hop spans so a fleet merge can join them."""
        if not self.enabled:
            return
        self._open[rid] = {"id": rid, "prompt_len": prompt_len,
                           "max_new": max_new, "t_submit": t, "slot": -1,
                           "trace": trace,
                           "preemptions": 0, "spans_dropped": 0,
                           "edges": [(t, "queue")], "spans": []}

    def admit(self, rid: int, slot: int, t: float) -> None:
        if not self.enabled:
            return
        rec = self._open.get(rid)
        if rec is None:        # submitted while tracing was off
            return
        rec["slot"] = slot
        rec["edges"].append((t, "prefill"))

    def decode_start(self, rid: int, t: float) -> None:
        """The prefill-sampled token's value is on the host (again, after
        a preempt-resume re-prefill): the prefill phase ends where a token
        exists for a client.  A non-streaming request without EOS defers
        that fetch to its finish, so its decode phase is ~0."""
        if not self.enabled:
            return
        rec = self._open.get(rid)
        if rec is None:
            return
        if "t_first_token" not in rec:
            rec["t_first_token"] = t
        rec["edges"].append((t, "decode"))

    def preempt(self, rid: int, t: float) -> None:
        """Pages reclaimed under pool pressure; requeued at the head."""
        if not self.enabled:
            return
        rec = self._open.get(rid)
        if rec is None:
            return
        rec["preemptions"] += 1
        rec["edges"].append((t, "preempted_wait"))

    def span(self, rid: int, kind: str, t0: float, t1: float,
             tokens: int, seq: Optional[int] = None) -> None:
        """One measured host window with its token count.  A
        ``prefill_chunk`` / ``decode_block`` span is the ENQUEUE of that
        program (the host's dispatch, not the program's run on the chip)
        and carries the engine's launch number ``seq``, the one the
        ``ds_serve_*_dispatch`` range of a profiler capture carries too;
        ``drain_fetch`` is the blocking fetch of a block's tokens."""
        if not self.enabled:
            return
        rec = self._open.get(rid)
        if rec is None:
            return
        spans = rec["spans"]
        if len(spans) >= self._max_spans:
            rec["spans_dropped"] += 1
            return
        spans.append((kind, t0, t1, tokens, seq))

    def finish(self, rid: int, t: float, reason: str, n_out: int) -> None:
        """Terminal edge: close the timeline, compute the phase partition,
        record the phase histograms, retain the completed record."""
        if not self.enabled:
            return
        rec = self._open.pop(rid, None)
        if rec is None:
            return
        edges = rec["edges"]
        edges.append((t, "finish"))
        phases = dict.fromkeys(PHASES, 0.0)
        for (t0, phase), (t1, _) in zip(edges, edges[1:]):
            if phase in phases:
                phases[phase] += t1 - t0
        rec["phases"] = phases
        rec["t_finish"] = t
        rec["latency_s"] = t - rec["t_submit"]
        rec["reason"] = reason
        rec["tokens_out"] = n_out
        for p, v in phases.items():
            self._h_phase[p].record(v)
        self.completed_total += 1
        self._seq += 1
        self._ring.append(rec)
        item = (rec["latency_s"], self._seq, rec)
        if len(self._slowest) < self._slowest_k:
            heapq.heappush(self._slowest, item)
        elif item[0] > self._slowest[0][0]:
            heapq.heapreplace(self._slowest, item)

    # -- reads ----------------------------------------------------------
    @property
    def open_count(self) -> int:
        return len(self._open)

    def open_ids(self) -> List[int]:
        return sorted(self._open)

    def completed(self) -> List[Dict[str, Any]]:
        """Every retained completed timeline (ring ∪ slowest heap,
        deduplicated), oldest completion first.  Copies both containers
        C-level-atomically first: scrapes run on the HTTP server thread
        while the engine thread appends (a Python-level loop over the
        live deque would race 'mutated during iteration')."""
        seen: Dict[int, Dict[str, Any]] = {}
        for rec in list(self._ring):
            seen[id(rec)] = rec
        for _, _, rec in list(self._slowest):
            seen.setdefault(id(rec), rec)
        return sorted(seen.values(), key=lambda r: (r["t_finish"], r["id"]))

    def slowest(self, n: Optional[int] = None) -> List[Dict[str, Any]]:
        out = sorted(self._slowest, key=lambda it: (-it[0], it[1]))
        if n is not None:
            out = out[:n]
        return [rec for _, _, rec in out]

    def tail_attribution(self, p: float = 0.99) -> Dict[str, Any]:
        """Dominant phase among retained requests ABOVE the p-quantile
        latency cut: the "why is my p99 slow" answer.  ``phase_share`` is
        each phase's share of total tail latency; ``exemplars`` the
        slowest tail request ids (drill into them via ``/requestz``)."""
        recs = self.completed()
        if not recs:
            return {"p": p, "n": 0, "tail_n": 0, "cut_s": 0.0,
                    "dominant_phase": None, "phase_share": {},
                    "exemplars": []}
        lats = sorted(r["latency_s"] for r in recs)
        idx = min(len(lats) - 1, int(p * len(lats)))
        cut = lats[idx]
        tail = [r for r in recs if r["latency_s"] >= cut]
        totals = dict.fromkeys(PHASES, 0.0)
        for r in tail:
            for ph, v in r["phases"].items():
                totals[ph] += v
        denom = sum(totals.values()) or 1.0
        dominant = max(totals, key=lambda ph: totals[ph])
        tail_sorted = sorted(tail, key=lambda r: -r["latency_s"])
        return {"p": p, "n": len(recs), "tail_n": len(tail),
                "cut_s": cut,
                "dominant_phase": dominant,
                "phase_share": {ph: v / denom for ph, v in totals.items()},
                "exemplars": [r["id"] for r in tail_sorted[:8]]}

    # -- exports --------------------------------------------------------
    @staticmethod
    def _rec_json(rec: Dict[str, Any]) -> Dict[str, Any]:
        out = dict(rec)
        out["edges"] = [[t, ph] for t, ph in rec["edges"]]
        out["spans"] = [list(s) for s in rec["spans"]]
        return out

    def snapshot(self, limit: int = 32) -> Dict[str, Any]:
        """The ``/requestz`` JSON body."""
        limit = max(0, int(limit))
        recent = list(self._ring)[-limit:] if limit else []
        return {"enabled": self.enabled,
                "open": self.open_count,
                "open_ids": self.open_ids(),
                "completed_total": self.completed_total,
                "retained": len(self._ring),
                "clock": get_trace_clock_anchor(),
                "tail_attribution": self.tail_attribution(),
                "slowest": [self._rec_json(r)
                            for r in self.slowest(int(limit))],
                "recent": [self._rec_json(r) for r in recent]}

    def perfetto_trace(self,
                       anchor: Optional[Dict[str, Any]] = None
                       ) -> Dict[str, Any]:
        """Trace-event JSON of every retained timeline, timestamped in
        the clock domain of the most recent profiler capture (see module
        docstring): load next to a ``/profilez`` capture in one Perfetto
        session and a request's host spans line up with the device phase
        tracks.  Per request: one thread of phase slices (the edge
        partition) and one of measured dispatch spans."""
        if anchor is None:
            anchor = get_trace_clock_anchor()
        a = anchor["perf"]

        def us(t):
            return round((t - a) * 1e6, 3)

        events: List[Dict[str, Any]] = [
            {"ph": "M", "pid": 1, "name": "process_name",
             "args": {"name": "ds_requests"}}]
        for rec in self.completed():
            rid = rec["id"]
            trace = rec.get("trace") or ""
            t_ph, t_sp = 2 * rid, 2 * rid + 1
            events.append({"ph": "M", "pid": 1, "tid": t_ph,
                           "name": "thread_name",
                           "args": {"name": f"req {rid} phases"}})
            edges = rec["edges"]
            for (t0, phase), (t1, _) in zip(edges, edges[1:]):
                if t1 <= t0:
                    continue
                args = {"request_id": rid, "reason": rec["reason"]}
                if trace:
                    args["trace"] = trace
                events.append({"ph": "X", "pid": 1, "tid": t_ph,
                               "name": phase, "ts": us(t0),
                               "dur": round((t1 - t0) * 1e6, 3),
                               "args": args})
            if rec["spans"]:
                events.append({"ph": "M", "pid": 1, "tid": t_sp,
                               "name": "thread_name",
                               "args": {"name": f"req {rid} spans"}})
            for kind, t0, t1, n, seq in rec["spans"]:
                args = {"request_id": rid, "tokens": n}
                if seq is not None:
                    args["seq"] = seq
                if trace:
                    args["trace"] = trace
                events.append({"ph": "X", "pid": 1, "tid": t_sp,
                               "name": kind, "ts": us(t0),
                               "dur": round(max(0.0, t1 - t0) * 1e6, 3),
                               "args": args})
        return _perfetto_doc(events, anchor)


_TRACER = RequestTracer()


def get_request_tracer() -> RequestTracer:
    """The process-global tracer the serving scheduler and engine record
    into (one per process, like the metrics registry)."""
    return _TRACER


# ---------------------------------------------------------------------------
# training step timeline
# ---------------------------------------------------------------------------


class StepTimeline:
    """Training-side per-boundary timeline — the serve tracer's twin for
    the DeepSpeedEngine (docs/OBSERVABILITY.md "Distributed tracing").

    The engine marks every micro-batch dispatch and every optimizer
    boundary; anomaly skips and elastic resumes land as instant events.
    Each closed step retains its micro spans, the analytic comm plan
    (rendered as byte-weighted OVERLAY slices in the perfetto export —
    attribution, not device truth), and the pipeline ``bubble_share``
    when pipeline parallelism is on.  Exports go through the SAME
    envelope as :meth:`RequestTracer.perfetto_trace`
    (:func:`_perfetto_doc`), so ``tools/trace_report.py --timeline`` and
    ``tools/fleet_dump.py --trace`` render train and serve with one code
    path.

    Disabled (the default) every hook is one attribute-load + branch —
    the monitor/metrics.py hot-path contract.  Single-writer: all hooks
    run on the training (engine) thread; scrapes copy the ring
    GIL-atomically."""

    # dslint DSL006: the completed-step ring is appended by the engine
    # thread and list()-copied by scrape threads — one atomic op per
    # mutation, published records immutable
    _dslint_shared = {"_ring": "atomic"}

    def __init__(self, ring: int = DEFAULT_RING):
        self.enabled = False
        self._ring: deque = deque(maxlen=max(1, int(ring)))
        self._cur: Optional[Dict[str, Any]] = None
        self._t_open: Optional[float] = None   # previous boundary time
        self.steps_total = 0
        reg = get_registry()
        self._m_steps = reg.counter(
            "ds_trace_train_steps_total",
            "optimizer boundaries recorded by the training step timeline")
        self._m_events = reg.counter(
            "ds_trace_train_events_total",
            "instant events (anomaly skips, elastic resumes) recorded on "
            "the training step timeline")

    # -- switches -------------------------------------------------------
    def enable(self) -> "StepTimeline":
        self.enabled = True
        return self

    def disable(self) -> "StepTimeline":
        """Stop recording and drop the open step (its boundary will never
        arrive while disabled); closed steps are kept."""
        self.enabled = False
        self._cur = None
        self._t_open = None
        return self

    def reset(self) -> None:
        self._cur = None
        self._t_open = None
        self._ring.clear()
        self.steps_total = 0

    # -- hot path (engine thread) --------------------------------------
    def _open_step(self, step: Optional[int], t: float) -> Dict[str, Any]:
        t0 = self._t_open if self._t_open is not None else t
        cur = {"step": step, "t0": t0, "micros": [], "events": []}
        self._cur = cur
        return cur

    def micro(self, step: int, idx: int, t: float) -> None:
        """One micro-batch dispatched (called at micro end); the span
        runs from the previous mark (step open / prior micro) to ``t``."""
        if not self.enabled:
            return
        cur = self._cur
        if cur is None:
            cur = self._open_step(step, t)
        last = cur["micros"][-1][2] if cur["micros"] else cur["t0"]
        cur["micros"].append((idx, last, t))

    def event(self, kind: str, t: float, **args: Any) -> None:
        """Instant event (``anomaly_skip`` / ``elastic_resume``), parked
        on the open step (one opens if needed — elastic resumes can land
        between boundaries)."""
        if not self.enabled:
            return
        cur = self._cur
        if cur is None:
            cur = self._open_step(None, t)
        cur["events"].append((kind, t, args))
        self._m_events.inc()

    def boundary(self, step: int, t: float, comm_plan=None,
                 bubble_share=None) -> None:
        """Optimizer boundary: close the open step as ``[t_open, t]``,
        attach the analytic comm plan and the pipeline bubble share, and
        retain it.  ``t`` becomes the next step's open time."""
        if not self.enabled:
            return
        cur = self._cur if self._cur is not None \
            else self._open_step(step, t)
        self._cur = None
        self._t_open = t
        cur["step"] = step
        cur["t1"] = t
        if bubble_share is not None:
            cur["bubble_share"] = bubble_share
        if comm_plan:
            entries = list(comm_plan.get("micro") or []) \
                + list(comm_plan.get("boundary") or [])
            cur["comm_plan"] = [list(e[:5]) for e in entries]
        self._ring.append(cur)
        self.steps_total += 1
        self._m_steps.inc()

    # -- exports --------------------------------------------------------
    def steps(self) -> List[Dict[str, Any]]:
        return list(self._ring)

    def snapshot(self, limit: int = 32) -> Dict[str, Any]:
        limit = max(0, int(limit))
        recent = list(self._ring)[-limit:] if limit else []
        return {"enabled": self.enabled,
                "steps_total": self.steps_total,
                "retained": len(self._ring),
                "clock": get_trace_clock_anchor(),
                "steps": [
                    {**{k: v for k, v in r.items()
                        if k not in ("micros", "events")},
                     "micros": [[i, a, b] for i, a, b in r["micros"]],
                     "events": [[k, t, a] for k, t, a in r["events"]]}
                    for r in recent]}

    def perfetto_trace(self,
                       anchor: Optional[Dict[str, Any]] = None
                       ) -> Dict[str, Any]:
        """Trace-event JSON of every retained step, in the shared clock
        domain (same envelope + anchor contract as the request tracer):
        tid 1 = step slices, tid 2 = micro spans, tid 3 = the analytic
        comm-plan OVERLAY (each step's window split across the plan's
        entries proportional to their payload bytes — attribution, not a
        device measurement), tid 4 = instant events."""
        if anchor is None:
            anchor = get_trace_clock_anchor()
        a = anchor["perf"]

        def us(t):
            return round((t - a) * 1e6, 3)

        events: List[Dict[str, Any]] = [
            {"ph": "M", "pid": 1, "name": "process_name",
             "args": {"name": "ds_train_steps"}}]
        for tid, name in ((1, "steps"), (2, "micros"),
                          (3, "comm plan (analytic)"), (4, "events")):
            events.append({"ph": "M", "pid": 1, "tid": tid,
                           "name": "thread_name", "args": {"name": name}})
        for rec in self.steps():
            t0, t1 = rec["t0"], rec.get("t1", rec["t0"])
            args: Dict[str, Any] = {"step": rec["step"]}
            if "bubble_share" in rec:
                args["bubble_share"] = rec["bubble_share"]
            if t1 > t0:
                events.append({"ph": "X", "pid": 1, "tid": 1,
                               "name": f"step {rec['step']}", "ts": us(t0),
                               "dur": round((t1 - t0) * 1e6, 3),
                               "args": args})
            for idx, m0, m1 in rec["micros"]:
                if m1 <= m0:
                    continue
                events.append({"ph": "X", "pid": 1, "tid": 2,
                               "name": f"micro {idx}", "ts": us(m0),
                               "dur": round((m1 - m0) * 1e6, 3),
                               "args": {"step": rec["step"]}})
            plan = rec.get("comm_plan")
            if plan and t1 > t0:
                total = sum(e[2] for e in plan) or 1
                tc = t0
                for op, calls, nbytes, dtype, world in plan:
                    dur = (t1 - t0) * (nbytes / total)
                    events.append({"ph": "X", "pid": 1, "tid": 3,
                                   "name": op, "ts": us(tc),
                                   "dur": round(dur * 1e6, 3),
                                   "args": {"bytes": nbytes, "calls": calls,
                                            "dtype": str(dtype),
                                            "world": world,
                                            "analytic": True}})
                    tc += dur
            for kind, t, eargs in rec["events"]:
                events.append({"ph": "i", "pid": 1, "tid": 4, "s": "t",
                               "name": kind, "ts": us(t),
                               "args": dict(eargs)})
        return _perfetto_doc(events, anchor)


_TIMELINE = StepTimeline()


def get_step_timeline() -> StepTimeline:
    """The process-global training step timeline the DeepSpeedEngine
    records into (one per process, like the request tracer)."""
    return _TIMELINE
