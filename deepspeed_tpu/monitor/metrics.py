"""Zero-dependency serving/inference/training metrics registry.

The serving path (slot-pool KV cache, chunked prefill interleaved with
decode) is the hottest surface in the repo, and phase attribution — queue
wait vs. prefill vs. decode — is exactly what goodput optimization needs
(you cannot overlap phases you cannot see).  This module is the host-side
half of that story: ``Counter`` / ``Gauge`` / log-bucketed ``Histogram``
instruments behind a process-global :class:`MetricsRegistry`, exported as
Prometheus exposition text, a JSON snapshot, or ``MonitorMaster`` events
(CSV/TensorBoard).  The device-side half is the ``ds_serve_*``
``jax.profiler.TraceAnnotation`` ranges (profiling/trace.py), which carry
the same phase names into the xplane trace so host histograms and device
timelines line up.

Design constraints, in order:

- **Disabled is free.**  The registry starts disabled; every ``inc`` /
  ``set`` / ``record`` costs ONE attribute-load + branch and allocates
  nothing.  Serving code can therefore instrument unconditionally.
- **Lock-free single-writer.**  Recording happens on the engine thread;
  scrapes happen on the HTTP thread.  Instruments use plain int/float
  stores (atomic under the GIL) — no lock on the hot path.  Readers get
  snapshot-consistent views: a histogram snapshot copies the bucket list
  in one bytecode op and derives ``count`` from the copy, so ``count ==
  sum(buckets)`` always holds even mid-write (``sum`` may trail by the
  in-flight record; it never tears).
- **One schema.**  Training (wall-clock timers), inference (generate()),
  and serving (request lifecycle) all land in the same registry under the
  ``ds_`` namespace — see docs/OBSERVABILITY.md for the full name/label
  schema; tests/unit/test_metrics.py fails the suite if an undocumented
  or non-``ds_`` name is registered.
"""

from __future__ import annotations

import json
import math
import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "get_registry", "window_delta", "DEFAULT_BUCKETS"]


def _render_labels(labels: Optional[Tuple[Tuple[str, str], ...]]) -> str:
    if not labels:
        return ""
    body = ",".join(f'{k}="{v}"' for k, v in labels)
    return "{" + body + "}"


class _Instrument:
    """Common core: a name, optional static labels, and the enabled check.

    Labels are STATIC (fixed at registration) — per-request dynamic label
    cardinality is a metrics-system footgun this layer deliberately omits;
    register one instrument per label value (e.g. the finish-reason
    counters) instead.
    """

    kind = "untyped"

    def __init__(self, registry: "MetricsRegistry", name: str, help: str = "",
                 labels: Optional[Dict[str, str]] = None):
        self._registry = registry
        self.name = name
        self.help = help
        self.labels = tuple(sorted((labels or {}).items()))

    # exposition -------------------------------------------------------
    def _label_str(self) -> str:
        return _render_labels(self.labels)

    def _event_name(self) -> str:
        """MonitorMaster event name: labels fold into the path."""
        tail = "/".join(v for _, v in self.labels)
        return f"{self.name}/{tail}" if tail else self.name


class Counter(_Instrument):
    """Monotonic count (requests, tokens, compiles)."""

    kind = "counter"

    def __init__(self, registry, name, help="", labels=None):
        super().__init__(registry, name, help, labels)
        self._value = 0

    def inc(self, n: int = 1) -> None:
        if not self._registry._enabled:
            return
        self._value += n

    @property
    def value(self) -> int:
        return self._value

    def _reset(self) -> None:
        self._value = 0

    def _snapshot(self):
        return self._value

    def _prom_lines(self) -> List[str]:
        return [f"{self.name}{self._label_str()} {self._value}"]

    def _events(self, step: int):
        return [(self._event_name(), self._value, step)]


class Gauge(_Instrument):
    """Last-observed value (active slots, queue depth)."""

    kind = "gauge"

    def __init__(self, registry, name, help="", labels=None):
        super().__init__(registry, name, help, labels)
        self._value = 0.0

    def set(self, v: float) -> None:
        if not self._registry._enabled:
            return
        self._value = v

    @property
    def value(self) -> float:
        return self._value

    def _reset(self) -> None:
        self._value = 0.0

    def _snapshot(self):
        return self._value

    def _prom_lines(self) -> List[str]:
        return [f"{self.name}{self._label_str()} {_fmt(self._value)}"]

    def _events(self, step: int):
        return [(self._event_name(), self._value, step)]


def _log_buckets(lo: float, hi: float, per_decade: int) -> Tuple[float, ...]:
    """Log-spaced bucket upper bounds covering [lo, hi]."""
    n = int(math.ceil(per_decade * math.log10(hi / lo))) + 1
    return tuple(lo * 10 ** (i / per_decade) for i in range(n))


# 1us .. 100s at 4 buckets/decade (33 buckets): spans sub-ms decode steps
# through multi-second queue waits with <= ~78% relative bucket width, i.e.
# quantile estimates good to well under 2x — plenty for p50/p90/p99 latency
# attribution, at a fixed 33-slot footprint per histogram.
DEFAULT_BUCKETS = _log_buckets(1e-6, 100.0, 4)


class Histogram(_Instrument):
    """Fixed log-bucketed distribution with cheap quantile estimates.

    Single-writer: ``record`` does a branch, a bisect over the fixed bucket
    bounds, and two scalar stores — no allocation, no lock.  Readers use
    :meth:`snapshot`, which copies the bucket-count list atomically (one
    ``list()`` bytecode op under the GIL) and derives totals from the copy.
    """

    kind = "histogram"

    def __init__(self, registry, name, help="", labels=None,
                 buckets: Sequence[float] = DEFAULT_BUCKETS):
        super().__init__(registry, name, help, labels)
        self.bounds = tuple(float(b) for b in buckets)
        # one extra overflow bucket (> bounds[-1], the +Inf bucket)
        self._counts = [0] * (len(self.bounds) + 1)
        self._sum = 0.0

    def record(self, v: float) -> None:
        if not self._registry._enabled:
            return
        bounds = self.bounds
        lo, hi = 0, len(bounds)
        while lo < hi:              # branchless-ish bisect, no imports
            mid = (lo + hi) // 2
            if v <= bounds[mid]:
                hi = mid
            else:
                lo = mid + 1
        self._counts[lo] += 1
        self._sum += v

    # -- reads ---------------------------------------------------------
    @property
    def count(self) -> int:
        return sum(self._counts)

    @property
    def sum(self) -> float:
        return self._sum

    @property
    def mean(self) -> float:
        n = self.count
        return self._sum / n if n else 0.0

    def snapshot(self) -> Dict[str, object]:
        counts = list(self._counts)          # atomic copy under the GIL
        n = sum(counts)
        q = {p: _quantile_from_counts(self.bounds, counts, p)
             for p in (0.5, 0.9, 0.99)}
        return {"count": n, "sum": self._sum,
                "mean": (self._sum / n if n else 0.0),
                "p50": q[0.5], "p90": q[0.9], "p99": q[0.99],
                "buckets": counts}

    def quantile(self, q: float) -> float:
        return _quantile_from_counts(self.bounds, list(self._counts), q)

    def _reset(self) -> None:
        self._counts = [0] * (len(self.bounds) + 1)
        self._sum = 0.0

    def _snapshot(self):
        return self.snapshot()

    def _prom_lines(self) -> List[str]:
        counts = list(self._counts)
        lines, cum = [], 0
        base = dict(self.labels)
        for b, c in zip(self.bounds, counts):
            cum += c
            labels = _render_labels(tuple(sorted({**base,
                                                  "le": _fmt(b)}.items())))
            lines.append(f"{self.name}_bucket{labels} {cum}")
        labels = _render_labels(tuple(sorted({**base, "le": "+Inf"}.items())))
        lines.append(f"{self.name}_bucket{labels} {cum + counts[-1]}")
        ls = self._label_str()
        lines.append(f"{self.name}_sum{ls} {_fmt(self._sum)}")
        lines.append(f"{self.name}_count{ls} {cum + counts[-1]}")
        return lines

    def _events(self, step: int):
        s = self.snapshot()
        base = self._event_name()
        return [(f"{base}/count", s["count"], step),
                (f"{base}/mean", s["mean"], step),
                (f"{base}/p50", s["p50"], step),
                (f"{base}/p99", s["p99"], step)]


def _quantile_from_counts(bounds: Tuple[float, ...], counts: List[int],
                          q: float) -> float:
    """Quantile estimate: find the bucket holding rank q*n and interpolate
    linearly inside it (the overflow bucket reports its lower bound)."""
    n = sum(counts)
    if n == 0:
        return 0.0
    rank = q * n
    cum = 0
    for i, c in enumerate(counts):
        if c == 0:
            continue
        if cum + c >= rank:
            if i >= len(bounds):         # overflow bucket: no upper bound
                return bounds[-1]
            lo = bounds[i - 1] if i > 0 else 0.0
            frac = (rank - cum) / c
            return lo + frac * (bounds[i] - lo)
        cum += c
    return bounds[-1]


def _fmt(v: float) -> str:
    """Prometheus float formatting: integral values render bare; others at
    9 significant digits (stable across scrapes, and distinct for every
    log bucket bound — adjacent bounds differ by ~78%).  A gauge may hold
    ``inf`` or ``nan`` (a gradient norm on an overflow step): they render
    as ``inf`` / ``nan``, which the text format parses."""
    f = float(v)
    if math.isfinite(f) and f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return format(f, ".9g")


class MetricsRegistry:
    """Process-global instrument registry.

    ``counter`` / ``gauge`` / ``histogram`` create-or-return instruments
    keyed by (name, labels): calling twice with the same key returns the
    SAME instrument (engines re-instantiated in one process share series),
    while re-registering a name as a different kind raises — that is the
    duplicate-name bug the tier-1 guard test exists to catch.

    Registration takes a lock (cold path); recording does not (see module
    docstring).  ``enable()``/``disable()`` flip the one flag every record
    checks, and tell whoever asked with :meth:`on_switch`: the compile
    ledger (``profiling/trace.py``) hangs its ``jax.monitoring`` listeners
    on the process-global registry that way, so a disabled registry leaves
    none behind and a private per-replica registry never has any.
    """

    def __init__(self):
        self._enabled = False
        self._lock = threading.Lock()
        self._metrics: Dict[Tuple[str, Tuple[Tuple[str, str], ...]],
                            _Instrument] = {}
        self._switch_hooks: List[Callable[[bool], None]] = []
        self._statz_extras: Dict[str, Callable[[], object]] = {}

    # -- switch --------------------------------------------------------
    @property
    def enabled(self) -> bool:
        return self._enabled

    def enable(self) -> "MetricsRegistry":
        self._enabled = True
        for hook in self._switch_hooks:
            hook(True)
        return self

    def disable(self) -> "MetricsRegistry":
        self._enabled = False
        for hook in self._switch_hooks:
            hook(False)
        return self

    def on_switch(self, hook: Callable[[bool], None]) -> None:
        """Call ``hook(enabled)`` at every ``enable()`` / ``disable()`` from
        now on, and once now if the registry is already on."""
        self._switch_hooks.append(hook)
        if self._enabled:
            hook(True)

    def add_statz(self, key: str, render: Callable[[], object]) -> None:
        """``/statz`` carries ``render()`` under ``key`` beside
        ``"metrics"``: what a module keeps that is no instrument (the
        compile ledger's rows under ``"jit"``)."""
        self._statz_extras[key] = render

    # -- registration --------------------------------------------------
    def _register(self, cls, name, help, labels, **kw):
        key = (name, tuple(sorted((labels or {}).items())))
        with self._lock:
            inst = self._metrics.get(key)
            if inst is not None:
                if not isinstance(inst, cls):
                    raise ValueError(
                        f"metric {name!r} already registered as {inst.kind}, "
                        f"cannot re-register as {cls.kind}")
                return inst
            existing = None
            for (n, lb), m in self._metrics.items():
                if n == name:
                    existing = (lb, m)
                    break
            if existing is not None:
                lb, m = existing
                if not isinstance(m, cls):
                    raise ValueError(
                        f"metric {name!r} already registered as {m.kind} "
                        f"(with other labels), cannot register as {cls.kind}")
                if bool(lb) != bool(key[1]):
                    # a name must be uniformly labeled or uniformly bare:
                    # mixing makes the snapshot's {name: value-or-family}
                    # shape ambiguous (it would crash or drop series at
                    # SCRAPE time, far from the offending registration)
                    raise ValueError(
                        f"metric {name!r} is already registered "
                        f"{'with' if lb else 'without'} labels; cannot "
                        f"register it {'without' if lb else 'with'} labels")
            inst = cls(self, name, help=help, labels=labels, **kw)
            self._metrics[key] = inst
            return inst

    def counter(self, name: str, help: str = "",
                labels: Optional[Dict[str, str]] = None) -> Counter:
        return self._register(Counter, name, help, labels)

    def gauge(self, name: str, help: str = "",
              labels: Optional[Dict[str, str]] = None) -> Gauge:
        return self._register(Gauge, name, help, labels)

    def histogram(self, name: str, help: str = "",
                  labels: Optional[Dict[str, str]] = None,
                  buckets: Sequence[float] = DEFAULT_BUCKETS) -> Histogram:
        return self._register(Histogram, name, help, labels, buckets=buckets)

    def names(self) -> List[str]:
        with self._lock:
            return sorted({n for n, _ in self._metrics})

    def get(self, name: str,
            labels: Optional[Dict[str, str]] = None) -> Optional[_Instrument]:
        key = (name, tuple(sorted((labels or {}).items())))
        return self._metrics.get(key)

    def reset(self) -> None:
        """Zero every instrument's VALUES; registrations (and instrument
        identity — engines hold direct references) are kept.  Benchmarks
        reset between warm and recorded passes."""
        with self._lock:
            for m in self._metrics.values():
                m._reset()

    # -- export --------------------------------------------------------
    def snapshot(self) -> Dict[str, object]:
        """JSON-able snapshot: {name: value | histogram-dict |
        {label_str: ...} when a name carries labels}."""
        with self._lock:
            items = list(self._metrics.items())
        out: Dict[str, object] = {}
        for (name, labels), m in items:
            v = m._snapshot()
            if labels:
                slot = out.setdefault(name, {})
                slot[_render_labels(labels)] = v
            else:
                out[name] = v
        return out

    def typed_snapshot(self) -> Dict[Tuple[str, str], Tuple[str, object]]:
        """Flat kind-tagged snapshot ``{(name, label_str): (kind, value)}``
        — the ``/statz?window=`` delta endpoint needs kinds to know whether
        to difference (counter/histogram) or report as-is (gauge); the
        plain :meth:`snapshot` erases them."""
        with self._lock:
            items = list(self._metrics.items())
        return {(name, _render_labels(labels)): (m.kind, m._snapshot())
                for (name, labels), m in items}

    def statz_json(self) -> str:
        doc = {"enabled": self._enabled, "metrics": self.snapshot()}
        for key, render in self._statz_extras.items():
            doc[key] = render()
        return json.dumps(doc, sort_keys=True)

    def prometheus_text(self) -> str:
        """Prometheus/OpenMetrics text exposition (one HELP/TYPE block per
        name; instruments sharing a name but differing in labels render
        under one block)."""
        with self._lock:
            items = sorted(self._metrics.items())
        lines: List[str] = []
        seen_header = set()
        for (name, _), m in items:
            if name not in seen_header:
                seen_header.add(name)
                if m.help:
                    lines.append(f"# HELP {name} {m.help}")
                lines.append(f"# TYPE {name} {m.kind}")
            lines.extend(m._prom_lines())
        return "\n".join(lines) + ("\n" if lines else "")

    def publish(self, monitor, step: int) -> None:
        """Bridge to a :class:`deepspeed_tpu.monitor.monitor.MonitorMaster`
        (CSV / TensorBoard / W&B fan-out): counters and gauges emit their
        value, histograms emit count/mean/p50/p99 sub-series."""
        if monitor is None or not getattr(monitor, "enabled", False):
            return
        with self._lock:
            items = sorted(self._metrics.items())
        events = []
        for _, m in items:
            events.extend(m._events(step))
        if events:
            monitor.write_events(events)


def window_delta(prev: Dict[Tuple[str, str], Tuple[str, object]],
                 cur: Dict[Tuple[str, str], Tuple[str, object]],
                 dt: float) -> Dict[str, object]:
    """Difference two :meth:`MetricsRegistry.typed_snapshot` results taken
    ``dt`` seconds apart into the ``/statz?window=`` response shape:

    - counters   -> ``{"delta", "per_sec"}``
    - histograms -> ``{"count_delta", "per_sec", "window_mean"}`` (mean of
      the values recorded *inside* the window)
    - gauges     -> ``{"value"}`` (last observed; deltas are meaningless)

    A series absent from ``prev`` (registered mid-window) baselines at
    zero, so its whole current value is the delta.  A current value BELOW
    the baseline means the registry was reset mid-window (``reset()`` is a
    public API the bench uses between passes) — Prometheus counter
    semantics apply: the baseline clamps to zero rather than emitting a
    negative rate.  Labeled families nest the same way
    :meth:`MetricsRegistry.snapshot` does.
    """
    rate = (1.0 / dt) if dt > 0 else 0.0
    out: Dict[str, object] = {}
    for (name, ls), (kind, v) in cur.items():
        if kind == "counter":
            base = prev.get((name, ls))
            d = v - (base[1] if base else 0)
            if d < 0:                      # reset between scrapes
                d = v
            entry = {"delta": d, "per_sec": d * rate}
        elif kind == "histogram":
            base = prev.get((name, ls))
            pc = base[1] if base else {"count": 0, "sum": 0.0}
            dc = v["count"] - pc["count"]
            ds = v["sum"] - pc["sum"]
            if dc < 0 or ds < 0:           # reset between scrapes
                dc, ds = v["count"], v["sum"]
            entry = {"count_delta": dc, "per_sec": dc * rate,
                     "window_mean": (ds / dc) if dc else 0.0}
        else:
            entry = {"value": v}
        if ls:
            out.setdefault(name, {})[ls] = entry
        else:
            out[name] = entry
    return out


_REGISTRY = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    """The process-global registry every engine records into."""
    return _REGISTRY
