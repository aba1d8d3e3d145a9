"""GoodputLedger: the process-side wrapper over ``goodput_core``.

Owns the live :class:`~deepspeed_tpu.monitor.goodput_core.LedgerCore`,
its ``runledger.jsonl`` persistence, the ``ds_run_*`` metric export, and
the declarative SLO burn-rate watcher — the run-scope sibling of the
request tracer and step timeline (docs/OBSERVABILITY.md "Goodput
ledger").

Disabled-is-free contract (the repo-wide telemetry discipline): every
hot-path entry point (``push``/``pop``/``shift``/``add_tokens``/
``tick``) is one attribute load + one branch while disabled.  Engines
instrument unconditionally.

Enablement: ``goodput`` config block (training), serving config /
``init_serving``, or the ``DSTPU_RUNLEDGER=<path>`` environment variable
— the supervisors' channel: they export the path + ``DSTPU_RUN_ID`` to
every child incarnation, and each incarnation self-identifies via
``DS_SUPERVISOR_RESTART`` so ``stitch`` can fold the jsonl back into
one run timeline.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, Optional

from deepspeed_tpu.monitor import goodput_core as core
from deepspeed_tpu.monitor.flight_recorder import get_flight_recorder
from deepspeed_tpu.monitor.metrics import get_registry

__all__ = ["GoodputLedger", "SloWatcher", "get_goodput_ledger",
           "CATEGORIES"]

CATEGORIES = core.CATEGORIES

_RUN_GOODPUT_HELP = ("fraction of run wall clock attributed to productive "
                     "compute (goodput ledger)")
_RUN_TIME_HELP = ("run wall-clock seconds attributed to this ledger "
                  "category (sums to run wall time)")
_SLO_BURN_HELP = "SLO burn events emitted by the declarative rule watcher"


class SloWatcher:
    """Declarative burn-rate rules over ledger + registry truths.

    ``rules`` is the ``slo:`` config block: a mapping of rule name ->
    threshold.  Supported rules (docs/OBSERVABILITY.md):

    - ``goodput_ratio`` (MIN): ledger goodput below the threshold burns.
    - ``ttft_p99_s`` (MAX): serving TTFT p99 (``ds_serve_ttft_seconds``)
      above the threshold burns.  That histogram times the first token's
      value on the host; a non-streaming request without EOS contributes
      its whole latency there, so the rule burns on slow batch traffic too.
    - ``shed_ratio`` (MAX): ``ds_serve_shed_total / ds_serve_submitted_total``
      above the threshold burns.

    Each evaluation that breaches emits one flight-recorder ``slo_burn``
    event, increments ``ds_slo_burn_total{rule=}``, and appends an
    ``slo_burn`` jsonl row — evaluations ride the ledger's (rate-limited)
    boundary ticks, so a sustained breach burns at tick cadence, the
    burn-rate framing.
    """

    KNOWN = ("goodput_ratio", "ttft_p99_s", "shed_ratio")

    def __init__(self, rules: Dict[str, float]):
        self.rules = {k: float(v) for k, v in (rules or {}).items()
                      if v is not None and k in self.KNOWN}
        self._counters: Dict[str, Any] = {}

    def _observe(self, rule: str,
                 snapshot: Dict[str, Any]) -> Optional[float]:
        reg = get_registry()
        if rule == "goodput_ratio":
            return float(snapshot.get("goodput_ratio", 0.0))
        if rule == "ttft_p99_s":
            hist = reg.get("ds_serve_ttft_seconds")
            if hist is None or not getattr(hist, "count", 0):
                return None
            return float(hist.quantile(0.99))
        if rule == "shed_ratio":
            shed = reg.get("ds_serve_shed_total")
            sub = reg.get("ds_serve_submitted_total")
            if shed is None or sub is None or not sub.value:
                return None
            return float(shed.value) / float(sub.value)
        return None

    def _breached(self, rule: str, observed: float) -> bool:
        if rule == "goodput_ratio":          # MIN rule
            return observed < self.rules[rule]
        return observed > self.rules[rule]   # MAX rules

    def evaluate(self, snapshot: Dict[str, Any],
                 ledger: "GoodputLedger") -> int:
        """One boundary-tick evaluation; returns breach count."""
        burns = 0
        flight = get_flight_recorder()
        reg = get_registry()
        for rule, target in self.rules.items():
            observed = self._observe(rule, snapshot)
            if observed is None or not self._breached(rule, observed):
                continue
            burns += 1
            c = self._counters.get(rule)
            if c is None:
                c = self._counters[rule] = reg.counter(
                    "ds_slo_burn_total", _SLO_BURN_HELP,
                    labels={"rule": rule})
            c.inc()
            flight.record("slo_burn", rule=rule, observed=round(observed, 6),
                          target=target)
            ledger._append(core.slo_burn_row(
                ledger.run_id, ledger.incarnation, rule, observed, target,
                time.time()))
        return burns


class GoodputLedger:
    """Process-global run ledger; see module docstring."""

    def __init__(self):
        self.enabled = False
        self._core: Optional[core.LedgerCore] = None
        self._path: Optional[str] = None
        self.run_id = ""
        self.incarnation = 0
        self.role = "train"
        self._min_tick_interval_s = 0.0
        self._last_tick_t = float("-inf")
        self._slo: Optional[SloWatcher] = None
        self._lock = threading.Lock()
        self._gauges: Dict[str, Any] = {}
        self._ratio_gauge = None
        self._event_seq = 0

    # -- lifecycle ------------------------------------------------------
    def enable(self, path: Optional[str] = None, run_id: Optional[str] = None,
               role: str = "train", incarnation: Optional[int] = None,
               min_tick_interval_s: Optional[float] = None,
               slo_rules: Optional[Dict[str, float]] = None) -> "GoodputLedger":
        """Idempotent; re-enabling updates the SLO rules/path but keeps
        the running attribution (two engines in one process share one
        run clock)."""
        with self._lock:
            if self._core is None:
                self._core = core.LedgerCore(time.perf_counter())
            self._path = (path or os.environ.get("DSTPU_RUNLEDGER")
                          or self._path)
            self.run_id = (run_id or os.environ.get("DSTPU_RUN_ID")
                           or self.run_id
                           or f"run-{os.getpid()}-{int(time.time())}")
            self.incarnation = int(
                incarnation if incarnation is not None
                else os.environ.get("DS_SUPERVISOR_RESTART", "0") or 0)
            self.role = role
            if min_tick_interval_s is not None:
                self._min_tick_interval_s = float(min_tick_interval_s)
            if slo_rules:
                self._slo = SloWatcher(slo_rules)
            first = not self.enabled
            self.enabled = True
            if first:
                self._start_unix = time.time()
                self._append(core.start_row(self.run_id, self.incarnation,
                                            role, self._start_unix))
        return self

    def disable(self) -> None:
        """Final tick + detach (process exit / test teardown)."""
        if not self.enabled:
            return
        self.tick(force=True)
        with self._lock:
            self.enabled = False
            self._core = None
            self._path = None
            self._slo = None
            self._gauges.clear()
            self._ratio_gauge = None
            self._last_tick_t = float("-inf")

    # -- hot-path attribution ------------------------------------------
    def push(self, category: str) -> None:
        if not self.enabled:
            return
        self._core.push(category, time.perf_counter())

    def pop(self) -> float:
        """Close the innermost region; returns its DIRECT seconds (time
        not attributed to nested regions)."""
        if not self.enabled:
            return 0.0
        return self._core.pop(time.perf_counter())[1]

    def shift(self, src: str, dst: str, seconds: float) -> float:
        if not self.enabled:
            return 0.0
        return self._core.shift(src, dst, seconds)

    def add_tokens(self, n: int) -> None:
        if not self.enabled:
            return
        self._core.tokens += int(n)

    def set_steps(self, n: int) -> None:
        if not self.enabled:
            return
        self._core.steps = int(n)

    # -- reading / exporting -------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        if not self.enabled:
            return {"enabled": False}
        snap = self._core.snapshot(time.perf_counter())
        snap["enabled"] = True
        snap["run_id"] = self.run_id
        snap["incarnation"] = self.incarnation
        snap["role"] = self.role
        snap["path"] = self._path
        return snap

    def note_event(self, event: str, dur_s: float, **extra: Any) -> str:
        """Durable event row sharing an id with the flight recorder
        (the checkpoint reconciliation satellite); returns the id."""
        if not self.enabled:
            return ""
        self._event_seq += 1
        event_id = f"{self.run_id}:{self.incarnation}:{event}:{self._event_seq}"
        self._append(core.event_row(self.run_id, self.incarnation, event,
                                    event_id, time.time(), dur_s=dur_s,
                                    **extra))
        return event_id

    def tick(self, force: bool = False) -> Optional[Dict[str, Any]]:
        """Boundary tick: export gauges, persist a cumulative jsonl row,
        evaluate SLO rules.  Rate-limited by ``min_tick_interval_s``
        (0 = every call)."""
        if not self.enabled:
            return None
        now = time.perf_counter()
        if not force and now - self._last_tick_t < self._min_tick_interval_s:
            return None
        self._last_tick_t = now
        snap = self._core.snapshot(now)
        reg = get_registry()
        if reg.enabled:
            if self._ratio_gauge is None:
                self._ratio_gauge = reg.gauge("ds_run_goodput_ratio",
                                              _RUN_GOODPUT_HELP)
            self._ratio_gauge.set(snap["goodput_ratio"])
            for cat, v in snap["categories"].items():
                g = self._gauges.get(cat)
                if g is None:
                    g = self._gauges[cat] = reg.gauge(
                        "ds_run_time_seconds", _RUN_TIME_HELP,
                        labels={"category": cat})
                g.set(v)
        self._append(core.tick_row(self.run_id, self.incarnation,
                                   time.time(), snap["wall_s"], snap))
        if self._slo is not None:
            self._slo.evaluate(snap, self)
        return snap

    # -- internals ------------------------------------------------------
    def _append(self, row: Dict[str, Any]) -> None:
        if self._path:
            core.append_row(self._path, row)


_ledger: Optional[GoodputLedger] = None
_ledger_lock = threading.Lock()


def get_goodput_ledger() -> GoodputLedger:
    global _ledger
    if _ledger is None:
        with _ledger_lock:
            if _ledger is None:
                _ledger = GoodputLedger()
    return _ledger
