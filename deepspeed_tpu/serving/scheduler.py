"""Request queue + iteration-level scheduler (Orca / DeepSpeed-FastGen
dynamic-batching role).

The scheduler is pure host bookkeeping — no jax.  It owns the FIFO wait
queue and the slot table; the :class:`~deepspeed_tpu.serving.engine.
ServingEngine` drives it one *iteration* at a time (admit → prefill chunk →
decode block), so requests join and leave the running batch at token
granularity instead of batch granularity:

- a finished sequence frees its slot at the end of the iteration that
  finished it (early EOS included — no head-of-line blocking on the
  slowest row);
- a queued request is admitted the moment a slot frees, and its prompt is
  prefilled in chunks interleaved with everyone else's decode steps.
"""

from __future__ import annotations

import itertools
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional

import numpy as np

from deepspeed_tpu.monitor.flight_recorder import get_flight_recorder
from deepspeed_tpu.monitor.metrics import get_registry
from deepspeed_tpu.monitor.request_trace import get_request_tracer

# process-global request id sequence: ids must be unique ACROSS engines in
# one process — the request tracer and flight recorder key per-request
# state/events by id, and two schedulers both starting at 0 would corrupt
# open timelines.  FIFO admission order per scheduler is preserved (ids
# are still assigned in submit order).
_REQUEST_IDS = itertools.count()

QUEUED = "queued"          # waiting for a slot
PREFILLING = "prefilling"  # owns a slot; prompt partially in the KV cache
RUNNING = "running"        # decoding
FINISHED = "finished"


class QueueFull(RuntimeError):
    """Admission-control shed: the bounded wait queue is at its
    watermark, so this submit is REFUSED instead of queued (graceful
    degradation — an unbounded queue turns overload into unbounded
    latency for everyone, docs/RESILIENCE.md "Serving fleet").  The HTTP
    surface maps it to ``429 Too Many Requests`` with a ``Retry-After``;
    the router backs off and tries another replica."""

    def __init__(self, msg: str, retry_after_s: float = 1.0):
        super().__init__(msg)
        self.retry_after_s = float(retry_after_s)


@dataclass
class Request:
    """One generation request and its lifecycle bookkeeping."""

    prompt: np.ndarray                  # 1-D int token ids
    max_new_tokens: int
    request_id: int = -1
    eos_token_id: int = -1              # -1 = no EOS stop
    state: str = QUEUED
    slot: int = -1
    prefill_pos: int = 0                # prompt tokens already in the cache
    output_tokens: List[int] = field(default_factory=list)
    # deferred-output refs [(block_idx, n_tokens), ...]: on the no-EOS fast
    # path the engine defers fetching sampled tokens until finish; these
    # name the device token blocks (in order) this request's output spans
    pending_blocks: List = field(default_factory=list)
    t_submit: float = 0.0
    t_admit: float = 0.0                # slot assignment (queue wait ends)
    # where the time in the slot before the first token goes, both since the
    # LAST admission (reset with t_admit): the request's turn came for its
    # first chunk (entry of the engine's _prefill_one_chunk, before any wait
    # for pages), and its last chunk's program was enqueued.  t_first_chunk -
    # t_admit, t_last_chunk - t_first_chunk and t_first_token - t_last_chunk
    # sum to t_first_token - t_admit
    t_first_chunk: float = 0.0
    t_last_chunk: float = 0.0
    # first output token's VALUE on the host (after the fetch returned):
    # the earliest a client can be sent it.  A non-streaming request
    # without an EOS id defers that fetch to its finish (the sync-free
    # path), so there this is stamped at finish.  Not re-stamped on resume.
    t_first_token: float = 0.0
    t_finish: float = 0.0
    # absolute service deadline (perf_counter clock; 0 = none): a request
    # still QUEUED past it is cancelled with reason "deadline" instead of
    # burning a slot on an answer nobody is waiting for
    deadline: float = 0.0
    finish_reason: str = ""             # "eos" | "length" | "cache_budget"
    # which bound produced the engine's position limit (min of request
    # budget and cache budget) — recorded WHERE the limit is computed so
    # finish attribution can't drift from the limit formula
    limit_reason: str = ""
    # paged-KV preempt-and-requeue (engine._preempt): times this request
    # lost its pages to pool pressure and went back to the queue head
    preemptions: int = 0
    # prefix-cache hits (engine._admit_prefix): prefix tokens whose
    # prefill was SKIPPED because their KV pages were adopted from the
    # cache — accumulated across admissions (a preempt-resume that
    # re-prefills through the cache adds its resume hit here too)
    prefix_hit_tokens: int = 0
    # propagated distributed-trace id (the 32-hex trace-id parsed from
    # the router's traceparent header; "" for direct submits): keys this
    # replica's tracer timeline and flight-recorder serve events to the
    # router's hop spans across the process boundary
    trace_id: str = ""
    # streaming front (disaggregated serving): a streaming request joins
    # the lag-1 drain path even without an EOS id so its tokens land in
    # ``output_tokens`` incrementally — the HTTP generator tails the list
    # and ships chunks as they appear (TTFT = first chunk on the wire)
    stream: bool = False
    # prefill-role request: finish at prefill completion (reason
    # "prefill_done") instead of decoding; the engine captures the
    # prompt's KV pages into ``handoff`` for the prefill->decode transfer
    prefill_only: bool = False
    # captured handoff: [(chunk_token_list, page_payload_dict), ...] for
    # every full prompt page, read device->host on the engine thread at
    # release time (set only for prefill_only requests)
    handoff: Optional[List] = None

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])

    @property
    def prefix(self) -> np.ndarray:
        """Tokens that must be cache-resident before decoding (re)starts:
        the prompt, plus — after a preempt-resume — every output token
        already produced (re-prefilling them regenerates the SAME KV state
        the slot held before preemption, so the continuation is
        token-identical under greedy decoding)."""
        if not self.output_tokens:
            return self.prompt
        return np.concatenate([self.prompt,
                               np.asarray(self.output_tokens, np.int32)])

    @property
    def prefix_len(self) -> int:
        return self.prompt_len + len(self.output_tokens)

    @property
    def done(self) -> bool:
        return self.state == FINISHED


class IterationScheduler:
    """FIFO admission over a fixed pool of KV-cache slots.

    ``submit`` enqueues; ``admit`` assigns every free slot to the oldest
    queued requests (called once per engine iteration); ``finish`` frees
    the slot immediately so the next ``admit`` can reuse it.  Completion
    order is recorded in ``finished`` (drain ordering is by finish time,
    not submit time — early-EOS rows drain first).
    """

    def __init__(self, num_slots: int, registry=None,
                 max_queue_depth: int = 0,
                 shed_retry_after_s: float = 1.0):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        self.num_slots = num_slots
        # admission control (0 = unbounded, the pre-overload-protection
        # behavior): submits past the watermark shed with QueueFull
        self.max_queue_depth = int(max_queue_depth)
        self.shed_retry_after_s = float(shed_retry_after_s)
        self._queue: Deque[Request] = deque()
        self._slots: List[Optional[Request]] = [None] * num_slots
        self.finished: List[Request] = []
        # drain support (ServingEngine.drain): while paused, admit() hands
        # out no slots — queued requests wait, occupied slots run dry
        self.admission_paused = False
        self._ids = _REQUEST_IDS
        # per-request span tracing + flight-recorder request events (both
        # disabled-by-default one-branch no-ops; the scheduler owns the
        # queue-side edges, the engine the compute-side ones)
        self._tracer = get_request_tracer()
        self._flight = get_flight_recorder()
        # lifecycle metrics (no-ops while the registry is disabled; the
        # scheduler owns the queue-side spans, the engine owns the
        # compute-side ones — see docs/OBSERVABILITY.md).  A replica-
        # scoped registry may be passed so N engines in one process keep
        # per-replica series (the router's least-loaded signal).
        reg = registry if registry is not None else get_registry()
        self._m_submitted = reg.counter(
            "ds_serve_submitted_total", "requests enqueued")
        self._m_admitted = reg.counter(
            "ds_serve_admitted_total", "requests assigned a KV slot")
        self._m_queue_wait = reg.histogram(
            "ds_serve_queue_wait_seconds", "submit -> slot admission wait")
        self._m_latency = reg.histogram(
            "ds_serve_request_latency_seconds", "submit -> finish wall time")
        self._m_queue_depth = reg.gauge(
            "ds_serve_queue_depth", "requests waiting for a slot")
        self._m_finished: Dict[str, object] = {
            r: reg.counter("ds_serve_finished_total",
                           "finished requests by reason",
                           labels={"reason": r})
            for r in ("eos", "length", "cache_budget", "cancelled",
                      "deadline", "prefill_done", "unknown")}
        self._m_shed = reg.counter(
            "ds_serve_shed_total",
            "submits refused by the bounded admission queue (429)")
        self._m_deadline = reg.counter(
            "ds_serve_deadline_expired_total",
            "queued requests cancelled past their service deadline")

    # -- admission -----------------------------------------------------
    def submit(self, req: Request) -> Request:
        if self.max_queue_depth > 0 \
                and len(self._queue) >= self.max_queue_depth:
            # shed at the watermark: refusing NOW (the caller 429s and
            # the router goes elsewhere) beats queueing work this replica
            # cannot start before everyone's latency blows out
            self._m_shed.inc()
            raise QueueFull(
                f"admission queue full ({len(self._queue)} >= "
                f"max_queue_depth={self.max_queue_depth}); shedding",
                retry_after_s=self.shed_retry_after_s)
        if req.request_id < 0:
            req.request_id = next(self._ids)
        req.state = QUEUED
        req.t_submit = time.perf_counter()
        self._queue.append(req)
        self._tracer.submit(req.request_id, req.t_submit, req.prompt_len,
                            req.max_new_tokens, trace=req.trace_id)
        self._m_submitted.inc()
        self._m_queue_depth.set(len(self._queue))
        return req

    def free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self._slots) if r is None]

    def pause_admission(self) -> None:
        """Stop handing out slots (drain): queued requests stay queued,
        running slots finish naturally.  Reversible via
        :meth:`resume_admission`."""
        self.admission_paused = True

    def resume_admission(self) -> None:
        self.admission_paused = False

    def expire_deadlines(self, now: Optional[float] = None) -> List[Request]:
        """Cancel every QUEUED request whose service deadline has passed
        (reason ``deadline``) — starting work nobody is still waiting for
        wastes the slot AND delays requests that can still make theirs.
        Runs at the top of every :meth:`admit`; bounded by the queue
        depth (itself bounded by ``max_queue_depth`` when shedding is
        on).  Thread-safe against concurrent HTTP-thread ``submit``/
        ``cancel``: the scan walks a GIL-atomic ``list()`` snapshot
        (iterating the live deque raises on concurrent appends), and
        each removal goes through ``deque.remove`` (raising = lost
        race, same as cancel)."""
        now = time.perf_counter() if now is None else now
        expired = [r for r in list(self._queue) if 0 < r.deadline < now]
        out = []
        for req in expired:
            try:
                self._queue.remove(req)
            except ValueError:
                continue                 # admitted/cancelled concurrently
            req.state = FINISHED
            req.finish_reason = "deadline"
            req.t_finish = now
            self._tracer.finish(req.request_id, now, "deadline", 0)
            if self._flight.enabled:
                self._flight.record("serve_deadline", rid=req.request_id,
                                    trace=req.trace_id)
            self._m_finished["deadline"].inc()
            self._m_deadline.inc()
            out.append(req)
        if out:
            self._m_queue_depth.set(len(self._queue))
        return out

    def admit(self) -> List[Request]:
        """Assign free slots to the oldest queued requests (FIFO); returns
        the newly-admitted requests, now in PREFILLING state.  Queued
        requests past their deadline are expired first — they never take
        a slot."""
        self.expire_deadlines()
        if self.admission_paused:
            return []
        admitted = []
        for slot in self.free_slots():
            try:
                req = self._queue.popleft()
            except IndexError:
                # empty — including the race where a cancel() from an
                # HTTP /generate worker removed the last queued request
                # between our emptiness check and the pop
                break
            req.slot = slot
            req.state = PREFILLING
            req.prefill_pos = 0
            req.t_admit = time.perf_counter()
            req.t_first_chunk = req.t_last_chunk = 0.0
            self._slots[slot] = req
            admitted.append(req)
            self._tracer.admit(req.request_id, slot, req.t_admit)
            if self._flight.enabled:
                self._flight.record("serve_admit", rid=req.request_id,
                                    slot=slot, trace=req.trace_id)
            self._m_admitted.inc()
            # queue wait is submit -> FIRST admission only: a re-admission
            # after a paged-KV preempt would otherwise record the whole
            # first run as "queue" time (that wait is the preempted_wait
            # phase, per docs/OBSERVABILITY.md)
            if req.preemptions == 0:
                self._m_queue_wait.record(req.t_admit - req.t_submit)
        if admitted:
            self._m_queue_depth.set(len(self._queue))
        return admitted

    # -- lifecycle -----------------------------------------------------
    def request_in(self, slot: int) -> Optional[Request]:
        return self._slots[slot]

    def prefilling(self) -> List[Request]:
        """Prefilling requests in ADMISSION order (request ids are
        assigned FIFO at submit) — the engine advances a bounded number of
        chunks per iteration, and slot-index order would starve
        high-index slots under churn."""
        return sorted((r for r in self._slots
                       if r is not None and r.state == PREFILLING),
                      key=lambda r: r.request_id)

    def running(self) -> List[Request]:
        return [r for r in self._slots if r is not None and r.state == RUNNING]

    def finish(self, req: Request) -> None:
        """Mark finished and free the slot NOW (iteration-level release:
        the next admit() hands this slot to the head of the queue)."""
        if req.state == FINISHED:
            return
        req.state = FINISHED
        req.t_finish = time.perf_counter()
        if req.slot >= 0 and self._slots[req.slot] is req:
            self._slots[req.slot] = None
        self.finished.append(req)
        # terminal edge: closes the request's span timeline with the SAME
        # timestamp the latency histogram records, so the per-request
        # phase partition reconciles with ds_serve_request_latency exactly
        self._tracer.finish(req.request_id, req.t_finish,
                            req.finish_reason or "unknown",
                            len(req.output_tokens))
        if self._flight.enabled:
            self._flight.record("serve_finish", rid=req.request_id,
                                reason=req.finish_reason or "unknown",
                                trace=req.trace_id)
        self._m_latency.record(req.t_finish - req.t_submit)
        # an unset/novel reason lands in the explicit "unknown" series —
        # a nonzero count there means a release path forgot to attribute,
        # which silent folding into "length" would hide
        self._m_finished.get(req.finish_reason,
                             self._m_finished["unknown"]).inc()

    def cancel(self, req: Request) -> bool:
        """Withdraw a still-QUEUED request (it never ran; no slot, no
        pages, no output).  The router's drain-redistribution path: a
        request parked in a draining replica's queue is cancelled here
        and re-dispatched to a healthy replica, so a drain drops nothing.
        Thread-safe against a concurrent ``admit``: once admit pops the
        request the ``deque.remove`` below raises and this returns False
        (the request runs where it is).  Cancelled requests close their
        trace timeline with reason ``cancelled`` and are NOT appended to
        ``finished`` (they were never served here)."""
        if req.state != QUEUED:
            return False
        try:
            self._queue.remove(req)
        except ValueError:
            return False
        req.state = FINISHED
        req.finish_reason = "cancelled"
        req.t_finish = time.perf_counter()
        self._tracer.finish(req.request_id, req.t_finish, "cancelled", 0)
        if self._flight.enabled:
            self._flight.record("serve_cancel", rid=req.request_id,
                                trace=req.trace_id)
        self._m_finished["cancelled"].inc()
        self._m_queue_depth.set(len(self._queue))
        return True

    def requeue_front(self, req: Request) -> None:
        """Preempt-and-requeue (paged KV pool pressure): the request loses
        its slot and goes back to the HEAD of the wait queue — it resumes
        (re-prefilling its prompt + produced tokens) as soon as capacity
        frees, ahead of requests that never ran.  The engine preempts the
        YOUNGEST-admitted slot, so the oldest request always keeps its
        pages and the pool cannot livelock."""
        if req.slot >= 0 and self._slots[req.slot] is req:
            self._slots[req.slot] = None
        req.slot = -1
        req.state = QUEUED
        req.prefill_pos = 0
        self._queue.appendleft(req)
        self._tracer.preempt(req.request_id, time.perf_counter())
        self._m_queue_depth.set(len(self._queue))

    def drain_finished(self) -> List[Request]:
        """Return-and-clear the finished list.  Long-lived serving loops
        MUST call this (or process the slice ``ServingEngine.step``
        returns and drain between steps): ``finished`` retains every
        completed request — prompt and output included — and grows without
        bound otherwise.  Call between engine iterations, not mid-step."""
        out = self.finished
        self.finished = []
        return out

    # -- introspection -------------------------------------------------
    @property
    def num_queued(self) -> int:
        return len(self._queue)

    @property
    def num_occupied(self) -> int:
        return sum(1 for r in self._slots if r is not None)

    @property
    def has_work(self) -> bool:
        return bool(self._queue) or self.num_occupied > 0
