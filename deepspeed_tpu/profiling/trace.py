"""jax.profiler trace capture for a window of train steps.

Role of the reference's NVTX + nsys flow (``deepspeed/utils/nvtx.py``,
SURVEY.md §5.1): ``wall_clock_breakdown: true`` gives coarse host-side
fwd/bwd/step timers; this module additionally dumps an xplane trace
(viewable in XProf/Perfetto/TensorBoard) so collective latency, kernel
times, and host<->device gaps are attributable per step.  Host-side phases
appear as ``jax.profiler.TraceAnnotation`` ranges named after the engine
timers (``ds_forward`` / ``ds_step`` / ...) — the NVTX-range analog — and
device ops carry the ``ds_fwd_bwd`` / ``ds_optimizer_step``
``jax.named_scope`` prefixes from the compiled step functions.
"""

from __future__ import annotations

import os
import re
import threading
import time
from typing import Any, Dict, List, Optional

import jax

from deepspeed_tpu.monitor.metrics import MetricsRegistry, get_registry
from deepspeed_tpu.utils.logging import logger


def annotate(name: str):
    """Host-timeline named range in the xplane trace (the NVTX-range
    analog): ``with annotate("ds_fwd_bwd"): ...``.  Near-free when no
    trace is being captured.  The serving loop's ranges go through
    :class:`phase`, which writes the matching counter too.
    """
    return jax.profiler.TraceAnnotation(name)


class phase:
    """A host span and its counter, written together:
    ``with phase("ds_serve_release"): ...`` opens the ``TraceAnnotation``
    ``<name>`` (a ``StepTraceAnnotation`` where ``step_num`` is given: it
    gives the trace its ``Steps`` line) and, while the registry is enabled,
    adds the elapsed ``perf_counter()`` seconds to the counter
    ``<name>_seconds_total``.

    One helper for both, so a span in the profiler's trace and the counter
    a scrape reads cannot come to time different code.  The counter is
    plain and unlabelled on purpose: it is what a benchmark's snapshot of
    the registry keeps.  A parent's self time is its own seconds minus its
    children's.  How often a phase was entered is not counted here: the
    code inside counts what it does (``ds_serve_steps_total``,
    ``ds_serve_prefill_chunks_total``).  ``meta`` (numbers, strings) goes
    to the annotation as ``step_num`` does: the profiler's host tracer
    keeps the event's name and reads the pairs back as the event's stats,
    which is how a dispatch range says what it enqueued (``seq``,
    ``request_id``).  With no profiler session and the registry disabled
    this is one ``TraceAnnotation`` enter/exit and one branch.

    While the registry is enabled, and only then, an open phase's name is
    on its thread's stack of open phases: the compile ledger below reads it
    to say which ``ds_setup_*`` range a program began in, and the outermost
    ``ds_setup_*`` range adds its seconds to ``ds_setup_seconds_total`` too,
    so that nested ranges of set-up add up without a second counted twice.
    """

    __slots__ = ("_ann", "_seconds", "_t0", "_name")

    def __init__(self, name: str, registry: Optional[MetricsRegistry] = None,
                 step_num: Optional[int] = None, **meta):
        self._ann = (jax.profiler.TraceAnnotation(name, **meta)
                     if step_num is None
                     else jax.profiler.StepTraceAnnotation(
                         name, step_num=step_num, **meta))
        reg = registry if registry is not None else get_registry()
        self._seconds = (reg.counter(name + "_seconds_total")
                         if reg.enabled else None)
        self._name = name

    def __enter__(self) -> "phase":
        self._ann.__enter__()
        if self._seconds is not None:
            _open.names.append(self._name)
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if self._seconds is not None:
            elapsed = time.perf_counter() - self._t0
            self._seconds.inc(elapsed)
            open_phases = _open.names
            if open_phases:        # entered on this thread, as `with` does
                open_phases.pop()
            if self._name.startswith(_SETUP) and \
                    _setup_phase(open_phases) is None:
                self._seconds._registry.counter(
                    "ds_setup_seconds_total", _SETUP_HELP).inc(elapsed)
        self._ann.__exit__(*exc)


_SETUP = "ds_setup_"
_SETUP_HELP = ("seconds inside ds_setup_* ranges, each second once (the "
               "outermost range's)")


class _OpenPhases(threading.local):
    """Names of the phases open on this thread, outermost first (kept only
    while the registry is enabled)."""

    def __init__(self):
        self.names: List[str] = []


_open = _OpenPhases()


def _setup_phase(open_phases: List[str]) -> Optional[str]:
    """The innermost ``ds_setup_*`` phase among ``open_phases``."""
    for name in reversed(open_phases):
        if name.startswith(_SETUP):
            return name
    return None


# -- the compile ledger ----------------------------------------------------
# Where set-up goes is mostly first calls: jax traces a function, lowers the
# jaxpr to a module, and hands the module to XLA (or reads the executable
# from the persistent cache in XLA's place).  jax times all three itself and
# tells ``jax.monitoring``'s listeners, with the function's name; this is
# the listener.  It lives here beside ``phase`` because the two are read
# together: a program's row names the ``ds_setup_*`` phase it began in.

_STAGE_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    # wraps compile_or_get_cached: a cache read is inside it
    "/jax/core/compile/backend_compile_duration": "compile",
}
# what the persistent cache says inside a backend compile, by a row's key
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s"}
_CACHE_COUNT = {"/jax/compilation_cache/cache_hits": "hits",
                "/jax/compilation_cache/cache_misses": "misses"}
_NOT_IN_A_MODULE_NAME = re.compile(r"[^\w.-]")
_JIT_HELP = {
    "ds_jit_trace_seconds_total":
        "tracing functions to jaxprs, self time (no cache keeps it)",
    "ds_jit_lower_seconds_total":
        "lowering jaxprs to modules, self time (no cache keeps it)",
    "ds_jit_compile_seconds_total":
        "XLA backend compiles, or the persistent cache's reads in their "
        "place, self time",
    "ds_jit_cache_read_seconds_total":
        "reading executables from the persistent compilation cache (part "
        "of ds_jit_compile_seconds_total)",
    "ds_jit_in_setup_seconds_total":
        "the part of trace + lower + compile self time that began inside "
        "a ds_setup_* range",
    "ds_jit_programs_total": "programs compiled or read from the cache",
    "ds_jit_cache_hits_total": "persistent compilation cache hits",
    "ds_jit_cache_misses_total":
        "persistent compilation cache misses (entries written)",
}
_STAGE_HELP = ("seconds by stage (trace, lower, compile, cache_read) and "
               "program, INCLUSIVE of what nests inside")
_IMPORT_HELP = ("age of the process when the package's import ended: "
                "interpreter start, jax, the package's own modules")
_import_age_s: Optional[float] = None


def _process_age_s() -> Optional[float]:
    """Seconds since the kernel started this process (None off Linux)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


def stamp_import_age() -> None:
    """Called once, at the end of ``deepspeed_tpu/__init__.py``."""
    global _import_age_s
    if _import_age_s is None:
        _import_age_s = _process_age_s()


def _program_name(fun_name: str) -> str:
    """One name a program: tracing reports ``step``, lowering and compiling
    ``jit(step)``; a profile and an HLO dump say ``jit_step``."""
    api, paren, rest = fun_name.partition("(")
    if paren and rest.endswith(")"):
        fun = rest[:-1]
    else:
        api, fun = "jit", fun_name
    return api + "_" + _NOT_IN_A_MODULE_NAME.sub("_", fun)


class _ThreadSpans(threading.local):
    """What one thread's listeners keep between events."""

    def __init__(self):
        self.pending: List[tuple] = []   # (start, seconds) of finished spans
        self.open = 0                    # spans begun and not yet ended
        self.new_cache()

    def new_cache(self) -> Dict[str, float]:
        """Hand over what the cache said since the last backend compile
        ended (its events carry no name: they fire inside that compile's
        span, on its thread) and start again."""
        said = getattr(self, "cache", None)
        self.cache = {"cache_read_s": 0.0, "saved_s": 0.0, "hits": 0,
                      "misses": 0}
        return said


class CompileLedger:
    """What jax traced, lowered and compiled, by program name.

    Totals go to plain counters of the registry (``ds_jit_*``, the form a
    benchmark's snapshot keeps) and hold SELF time: a span of any stage
    that lies inside another on the same thread (an inner jit traced inside
    an outer one, an eager op compiled while a function is traced) is taken
    from the span around it, so over any interval trace + lower + compile
    is at most the wall time of the thread that compiled.  Rows, one a
    program name and at most ``MAX_PROGRAMS`` of them (the rest under
    ``"other"``), hold INCLUSIVE seconds a stage plus the row's ``self_s``,
    and the ``ds_setup_*`` phase that was open when the program first began.
    Spans arrive on ``time.time()``; rows are on ``perf_counter()``, the
    clock of ``phase`` and of ``Request.t_*``, by one offset taken at
    :meth:`install`.

    What a span holds is known from the spans alone (they end innermost
    first); the begins jax also sends say when a thread has none open, so
    that nothing is kept longer, however many spans one trace holds.

    Listeners exist only between :meth:`install` and :meth:`uninstall`,
    which the process-global registry's ``enable()`` / ``disable()`` call.
    They run when something traces, lowers or compiles: never in a warm
    window.
    """

    MAX_PROGRAMS = 512
    # finished spans a thread keeps for a parent where jax sent no begins
    _PENDING_MAX = 4096

    def __init__(self, registry: MetricsRegistry):
        self._registry = registry
        self._lock = threading.Lock()
        self._tls = _ThreadSpans()
        self.installed = False
        self._offset = 0.0
        self._rows: Dict[str, Dict[str, Any]] = {}
        self._c = {name: registry.counter(name, help)
                   for name, help in _JIT_HELP.items()}

    # -- switch ----------------------------------------------------------
    def install(self) -> None:
        if self.installed:
            return
        from jax import monitoring

        if _import_age_s is not None:
            self._registry.gauge("ds_setup_import_seconds",
                                 _IMPORT_HELP).set(_import_age_s)
        self._offset = time.perf_counter() - time.time()
        self._tls = _ThreadSpans()      # no thread keeps an earlier life's
        monitoring.register_scalar_listener(self._on_begin)
        monitoring.register_event_time_span_listener(self._on_span)
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)
        self.installed = True

    def uninstall(self) -> None:
        if not self.installed:
            return
        from jax import monitoring

        self.installed = False
        monitoring.unregister_scalar_listener(self._on_begin)
        monitoring.unregister_event_time_span_listener(self._on_span)
        monitoring.unregister_event_duration_listener(self._on_duration)
        monitoring.unregister_event_listener(self._on_event)

    def clear(self) -> None:
        """Forget the rows (the registry's ``reset()`` zeroes the totals)."""
        with self._lock:
            self._rows = {}

    # -- listeners -------------------------------------------------------
    def _on_begin(self, event: str, start: float, **_) -> None:
        # jax records a stage's start time as a scalar when it begins
        if event in _STAGE_OF and self._registry._enabled:
            self._tls.open += 1

    def _on_event(self, event: str, **_) -> None:
        key = _CACHE_COUNT.get(event)
        if key is not None and self._registry._enabled:
            self._tls.cache[key] += 1

    def _on_duration(self, event: str, seconds: float, **_) -> None:
        key = _CACHE_SECONDS.get(event)
        if key is not None and self._registry._enabled:
            self._tls.cache[key] += seconds

    def _on_span(self, event: str, start: float, end: float,
                 fun_name: str = "", **_) -> None:
        stage = _STAGE_OF.get(event)
        if stage is None:
            return
        tls = self._tls
        if not self._registry._enabled:
            # the flag went off under a span: its begin may have counted
            tls.open = 0
            tls.pending.clear()
            return
        seconds = end - start
        # spans end innermost first: what this one holds is the tail of the
        # thread's finished spans that began after it did
        pending, inside = tls.pending, 0.0
        while pending and pending[-1][0] >= start:
            inside += pending.pop()[1]
        pending.append((start, seconds))
        if tls.open:
            tls.open -= 1
            if not tls.open:        # nothing open on this thread: no span
                pending.clear()     # is left that could hold these
        elif len(pending) > self._PENDING_MAX:
            # no begin was seen (installed inside a span; a jax that sends
            # none): bounded, and exact while a span has fewer children
            del pending[:self._PENDING_MAX // 2]
        self_s = max(seconds - inside, 0.0)
        cache = tls.new_cache() if stage == "compile" else None
        setup = _setup_phase(_open.names)
        with self._lock:
            self._add(_program_name(fun_name), stage, start + self._offset,
                      seconds, self_s, setup, cache)

    def _add(self, name, stage, t0, seconds, self_s, setup, cache) -> None:
        c = self._c
        c[f"ds_jit_{stage}_seconds_total"].inc(self_s)
        if setup is not None:
            c["ds_jit_in_setup_seconds_total"].inc(self_s)
        row = self._rows.get(name)
        if row is None:
            if len(self._rows) >= self.MAX_PROGRAMS:
                name = "other"
                row = self._rows.get(name)
            if row is None:
                row = self._rows[name] = {
                    "program": name, "phase": setup, "trace": 0, "lower": 0,
                    "compile": 0, "trace_s": 0.0, "lower_s": 0.0,
                    "compile_s": 0.0, "cache_read_s": 0.0, "saved_s": 0.0,
                    "self_s": 0.0, "hits": 0, "misses": 0,
                    "first_start": t0, "last_end": t0 + seconds}
        row[stage] += 1
        row[stage + "_s"] += seconds
        row["self_s"] += self_s
        row["last_end"] = max(row["last_end"], t0 + seconds)
        self._stage_series(stage, name).inc(seconds)
        if cache is not None:
            c["ds_jit_programs_total"].inc()
            c["ds_jit_cache_read_seconds_total"].inc(cache["cache_read_s"])
            c["ds_jit_cache_hits_total"].inc(cache["hits"])
            c["ds_jit_cache_misses_total"].inc(cache["misses"])
            for key, said in cache.items():
                row[key] += said
            if cache["cache_read_s"]:
                self._stage_series("cache_read", name).inc(
                    cache["cache_read_s"])

    def _stage_series(self, stage: str, name: str):
        return self._registry.counter(
            "ds_jit_stage_seconds_total", _STAGE_HELP,
            labels={"stage": stage, "program": name})

    # -- reads -----------------------------------------------------------
    def rows(self) -> List[Dict[str, Any]]:
        """A copy of the rows in the order the programs first began.
        ``trace`` / ``lower`` / ``compile`` count events, ``calls`` is the
        most of the three, ``*_s`` are inclusive seconds (``self_s``
        excepted), ``first_start`` / ``last_end`` are ``perf_counter()``
        readings."""
        with self._lock:
            rows = [dict(r) for r in self._rows.values()]
        for r in rows:
            r["calls"] = max(r["trace"], r["lower"], r["compile"])
        return sorted(rows, key=lambda r: r["first_start"])


_LEDGER = CompileLedger(get_registry())


def compile_ledger() -> CompileLedger:
    """The process's compile ledger (it follows ``get_registry()``)."""
    return _LEDGER


def _follow_registry(enabled: bool) -> None:
    if enabled:
        _LEDGER.install()
    else:
        _LEDGER.uninstall()


get_registry().on_switch(_follow_registry)
get_registry().add_statz("jit", _LEDGER.rows)


def scope(name: str):
    """Device-timeline named range: ``with scope("ds_comm_all_gather"): ...``
    around ops *inside* jit, so the emitted HLO carries the name and the
    xplane device rows line up with the host-side ``ds_comm_*`` series.
    (``annotate`` is the host-timeline analog for eager regions; inside a
    trace it would time tracing, not execution.)  Trace-time metadata only —
    zero runtime cost, and applied unconditionally so toggling telemetry
    never changes the compiled program."""
    return jax.named_scope(name)


class TraceCapture:
    """Start/stop a ``jax.profiler`` trace over steps
    ``[start_step, start_step + num_steps)``.  ``after_step(completed)`` is
    called by the engine after each optimizer step with the number of
    completed steps; the trace starts after step ``start_step - 1`` so the
    captured window contains whole steps (every micro-batch dispatch + the
    update).

    ``perfetto=True`` additionally asks jax for the perfetto trace-event
    JSON (``perfetto_trace.json.gz`` next to the xplane file — stdlib
    gzip+json parseable), which the device-truth post-processor
    (profiling/device_trace.py) consumes.
    """

    def __init__(self, output_path: str, start_step: int = 2,
                 num_steps: int = 2, perfetto: bool = False):
        self.output_path = output_path
        self.start_step = max(1, int(start_step))
        self.num_steps = max(1, int(num_steps))
        self.perfetto = bool(perfetto)
        self.active = False
        self.done = False
        # clock anchor stamped when the window opens: the perfetto file's
        # timestamps are microseconds since the start_trace call, and this
        # records where that epoch sits on perf_counter/unix time — the
        # post-processor and /requestz correlate through it
        self.clock = None

    def _stamp_clock(self) -> None:
        from deepspeed_tpu.monitor.request_trace import \
            set_trace_clock_anchor

        self.clock = set_trace_clock_anchor()

    def maybe_start(self, upcoming_step: int) -> None:
        """Called before the first micro-batch of ``upcoming_step``: opens
        the window so the captured steps include their forward dispatches.
        ``>=`` (not ``==``): a checkpoint-resumed run starts past
        ``start_step`` and should still capture its first steps."""
        if self.done or self.active or upcoming_step < self.start_step:
            return
        import atexit

        os.makedirs(self.output_path, exist_ok=True)
        # anchor IMMEDIATELY before start_trace: the trace file's ts
        # epoch is the session start (measured within ~100us of the call)
        self._stamp_clock()
        jax.profiler.start_trace(self.output_path,
                                 create_perfetto_trace=self.perfetto)
        self.active = True
        # training may end inside the window; close() is idempotent
        atexit.register(self.close)
        self.start_step = upcoming_step  # anchor the window where it opened
        logger.info("profile_trace: capturing steps %d..%d -> %s",
                    self.start_step, self.start_step + self.num_steps - 1,
                    self.output_path)

    def after_step(self, completed_steps: int) -> Optional[str]:
        """Returns the trace directory when the capture just finished."""
        if self.done or not self.active:
            return None
        if completed_steps >= self.start_step + self.num_steps - 1:
            jax.profiler.stop_trace()
            self.active = False
            self.done = True
            logger.info("profile_trace: wrote %s (xplane; open with XProf/"
                        "TensorBoard profile plugin)", self.output_path)
            return self.output_path
        return None

    def close(self) -> None:
        """Stop a still-open trace (training ended inside the window)."""
        if self.active:
            jax.profiler.stop_trace()
            self.active = False
            self.done = True
            logger.info("profile_trace: training ended inside the window; "
                        "wrote partial trace %s", self.output_path)
