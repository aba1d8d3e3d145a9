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
import time
from typing import Optional

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
    """

    __slots__ = ("_ann", "_seconds", "_t0")

    def __init__(self, name: str, registry: Optional[MetricsRegistry] = None,
                 step_num: Optional[int] = None, **meta):
        self._ann = (jax.profiler.TraceAnnotation(name, **meta)
                     if step_num is None
                     else jax.profiler.StepTraceAnnotation(
                         name, step_num=step_num, **meta))
        reg = registry if registry is not None else get_registry()
        self._seconds = (reg.counter(name + "_seconds_total")
                         if reg.enabled else None)

    def __enter__(self) -> "phase":
        self._ann.__enter__()
        if self._seconds is not None:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if self._seconds is not None:
            self._seconds.inc(time.perf_counter() - self._t0)
        self._ann.__exit__(*exc)


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
