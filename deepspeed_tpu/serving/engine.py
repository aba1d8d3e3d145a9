"""Continuous-batching serving engine over a paged KV cache.

The static-batch :class:`~deepspeed_tpu.inference.engine.InferenceEngine`
decodes the whole batch in lock-step on one scalar position: no request can
join or leave until the slowest row finishes, and mixed-length traffic
burns most of the batch on padding and head-of-line blocking.  This engine
is the Orca / DeepSpeed-FastGen answer, mapped onto the existing fused
Pallas decode stack:

- a KV cache shared by ``num_slots`` slots: a PAGED pool
  (``serving/paged_kv.py``: fixed-size token pages, per-slot page tables,
  alloc-on-append, free-on-finish, LIFO preempt-and-requeue under pool
  pressure), so HBM tracks the tokens actually live instead of reserving
  ``max_out_tokens`` per slot.  What a page holds, and what else a slot
  carries, is the model's cache kind's business
  (``serving/cache_kind.py``): this module asks it and tests no model flag;
- PER-ROW decode positions: every slot sits at its own depth, threaded
  through ``forward_with_cache`` / ``decode_step`` / the flash-decode
  kernel (which masks, DMA-clamps and page-table-indirects per row);
- iteration-level scheduling: each :meth:`step` admits queued requests
  into freed slots, advances at most ``max_prefill_chunks`` prompt chunks
  (chunked per-slot prefill, interleaved with decode so decode latency
  stays bounded: one chunk a prefilling request in admission order, and
  the places they leave go round again over those with prompt left, so a
  prompt that prefills alone takes the whole budget), then decodes
  ``decode_block_tokens`` tokens for every active slot in one compiled
  program;
- a traced active-slot mask: compiled shapes stay static while occupancy
  varies, so there is exactly ONE decode program regardless of how many
  slots are live.

Sync-free scheduling: the per-slot position AND active mask are
DEVICE-RESIDENT carries of the compiled decode block (EOS termination —
sampled-token-vs-eos — is folded into the compiled step), so the host
scheduler never blocks on the block it just dispatched:

- no-EOS requests: completion is pure position arithmetic; the host runs
  AHEAD of the device, blocks dispatch back-to-back, and sampled tokens
  are fetched lazily (refcounted) when a request finishes;
- EOS requests: token values gate slot turnover, but the device already
  stopped the row the step its EOS appeared — the host merely LEARNS of
  it from a DEFERRED drain: after dispatching block ``i`` it fetches
  block ``i-1``'s (tokens, valid) pair, so the fetch RTT overlaps live
  device work.  Slot frees land at most one decode block late;
- the prefill-sampled first token: the last chunk's program wakes the
  slot itself (``last`` / ``pos`` / ``active`` are its carries too, and
  a first token that IS the EOS never goes active), so the host fetches
  the value only after the iteration's decode block is queued behind
  the chunk — it never waits for a first token with the chip's queue
  empty.

Slot-reuse safety (why freed slots need no cache zeroing): a query at
position p only attends cache rows <= p, and every row <= p has been
written by the CURRENT occupant before it is first attended — prefill
writes [0, S) before the first decode, and each decode step writes its own
row before attending it.  Inactive slots are "parked": they still run in
the compiled step (static shapes) but write their junk K/V at their own
frozen position, which is on the reserved junk page 0 (a released slot's
page table points there).
"""

from __future__ import annotations

import functools
import os
import threading
import time
from collections import deque
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.comm.mesh import replicated
from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.inference.engine import InferenceEngine, pow2_bucket
from deepspeed_tpu.models.decoding import (forward_with_cache,
                                           next_token_logits, sample_token)
from deepspeed_tpu.monitor.flight_recorder import get_flight_recorder
from deepspeed_tpu.monitor.goodput import get_goodput_ledger
from deepspeed_tpu.monitor.health import get_health
from deepspeed_tpu.monitor.metrics import get_registry
from deepspeed_tpu.monitor.request_trace import get_request_tracer
from deepspeed_tpu.profiling.trace import phase
from deepspeed_tpu.serving.cache_kind import FullPages, cache_kind
from deepspeed_tpu.serving.host_tier import HostPageStore
from deepspeed_tpu.serving.paged_kv import PagedKVPool
from deepspeed_tpu.serving.prefix_cache import PrefixCache
from deepspeed_tpu.serving.scheduler import (PREFILLING, QUEUED, RUNNING,
                                             IterationScheduler, QueueFull,
                                             Request)
from deepspeed_tpu.utils.logging import log_dist

# Host phases of one scheduler iteration, all on the engine thread and
# nested: span name (= counter prefix) -> what it wraps.  A span in the
# profiler's trace and ``<name>_seconds_total`` in the registry each
# (profiling/trace.py ``phase``).  The two fetches block on
# the chip; the rest is host work during which the chip runs dry unless
# earlier dispatches still cover it.
SERVE_PHASES = {
    "ds_serve_step": "one scheduler iteration",
    "ds_serve_admit": "slot admission and prefix-cache lookup",
    "ds_serve_prefill": "this iteration's prefill chunks",
    "ds_serve_decode": "the decode block and its lag-1 drain",
    "ds_serve_pages": "page allocation, eviction and preemption",
    "ds_serve_prefill_dispatch": "chunk build and prefill program enqueue",
    "ds_serve_first_token_fetch": "blocking fetch of the prefill-sampled "
                                  "token (stream / EOS / last-token path), "
                                  "after the decode block's enqueue",
    "ds_serve_decode_dispatch": "argument build and decode block enqueue",
    "ds_serve_block_fetch": "blocking fetch of deferred tokens (a block's, "
                            "or the deferred first token)",
    "ds_serve_release": "slot park, prefix-cache insert, page release",
}


# Routing counters of a mixture-of-experts model on the fused decode path,
# counted on the device over LIVE rows by the decode-block program itself
# and fetched with a block's tokens, only while the registry is on.
SERVE_MOE_COUNTERS = {
    "ds_serve_moe_assignments_total":
        "(row, expert) assignments of live decode rows, over layers and steps",
    "ds_serve_moe_expert_hits_total":
        "(layer, step, expert) triples with at least one live row",
    "ds_serve_moe_expert_slots_total":
        "(layer, step, expert) triples run: experts x layers x decode steps",
    "ds_serve_moe_max_load_total":
        "rows of the fullest expert, summed over layers and decode steps",
    "ds_serve_moe_local_assignments_total":
        "of ds_serve_moe_assignments_total, those to an expert this chip "
        "holds (all of them unless the chip holds a share: models/afmoe.py)",
    "ds_serve_moe_group_kept_total":
        "(live row, expert layer, decode step) triples whose kept groups of "
        "experts include a group with an expert this chip holds; moves only "
        "under a group-limited router (moe_n_group > 1: models/afmoe.py), "
        "over assignments / num_experts_per_tok it is how often the limit "
        "leaves this chip's experts in reach of a row",
    "ds_serve_moe_row_tiles_total":
        "(expert layer, decode step) pairs x the row tiles the block's rows "
        "are to fused_moe_mlp (ops/pallas/decode.py:moe_row_tile; one tile "
        "where the kernel runs all rows in one pass)",
    "ds_serve_moe_row_tiles_worked_total":
        "of ds_serve_moe_row_tiles_total, the tiles the kernel's MXU passes "
        "worked: those that hold a live row where it cuts its passes to "
        "them, all of them elsewhere",
}


def _in_phase(name: str):
    """Run a whole engine method inside the host phase ``name``."""
    def wrap(method):
        @functools.wraps(method)
        def inside(self, *args, **kwargs):
            with self._phase(name):
                return method(self, *args, **kwargs)
        return inside
    return wrap


class ServingEngine:
    """Continuous-batching serving over an :class:`InferenceEngine`'s
    weights (plain + kernel-injected views, dtype, mesh all reused).

    Parameters
    ----------
    model / config / params / mesh:
        As :func:`deepspeed_tpu.init_inference`; alternatively pass an
        existing ``engine=`` to share its weights.
    num_slots:
        KV-cache slots = max concurrently-decoding requests (the compiled
        batch).  Defaults to ``config.num_slots``.
    prefill_chunk:
        Max prompt tokens prefilled per scheduler iteration per slot
        (chunked prefill; bounds the decode stall a long prompt causes).
        A chunk runs in a program of its bucket (:meth:`chunk_bucket`),
        compiled once per bucket from the cache kind's floor up: a warm-up
        needs one prompt a power of two from that floor to ``prefill_chunk``
        (shorter ones land in the floor's program).
    decode_block_tokens:
        Decode steps per compiled block (per host dispatch) — the serving
        analog of ``decode_unroll``.

    Paged-KV knobs ride on the config: ``kv_page_tokens`` (page
    granularity), ``kv_pool_tokens`` (total pool capacity — set it below
    ``num_slots * max_out_tokens`` to oversubscribe slots against a fixed
    HBM budget; pool pressure preempts the youngest-admitted slot LIFO and
    requeues it at the queue head).
    """

    # HTTP /generate worker threads share the idempotent-dispatch map
    # with each other (reserve-then-fill): every _idem write holds the
    # lock; the KV-handoff work queue is single-producer-append /
    # engine-thread-popleft, GIL-atomic deque ops only (dslint DSL006,
    # docs/LINT.md)
    _dslint_shared = {"_idem": "lock:_idem_lock",
                      "_idem_order": "lock:_idem_lock",
                      "_handoffs": "atomic"}

    def __init__(self, model=None, config=None, *, engine: Optional[InferenceEngine] = None,
                 num_slots: int = 0, prefill_chunk: int = 0,
                 decode_block_tokens: int = 0, params: Any = None, mesh=None,
                 do_sample: bool = False, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 1.0, registry=None,
                 health=None, role: str = "both",
                 handoff_wire: str = "int8"):
        if engine is None:
            if config is None:
                config = {}
            if not isinstance(config, DeepSpeedInferenceConfig):
                config = DeepSpeedInferenceConfig(**config)
            engine = InferenceEngine(model, config, params=params, mesh=mesh)
        elif any(a is not None for a in (model, config, params, mesh)):
            # silently preferring engine.config over a passed config would
            # discard the caller's settings with no indication
            raise ValueError(
                "pass EITHER engine= (its model/config/params/mesh are "
                "reused) OR model/config/params/mesh, not both")
        self.engine = engine
        self.module = engine.module
        self._config = engine.config
        self.num_slots = int(num_slots or self._config.num_slots)
        self.prefill_chunk = int(prefill_chunk or self._config.prefill_chunk)
        self._K = int(decode_block_tokens or self._config.decode_block_tokens
                      or max(1, self._config.decode_unroll))
        self.max_prefill_chunks = max(1, int(self._config.max_prefill_chunks))
        self._sample = (bool(do_sample), float(temperature), int(top_k),
                        float(top_p))
        # disaggregated serving role (docs/RESILIENCE.md "Disaggregated
        # serving"): "prefill" replicas answer phase-prefill requests and
        # ship KV pages, "decode" replicas adopt them; "both" (the
        # default) serves monolithically.  The role is ADVISORY — every
        # engine can serve every request shape, so a role-split fleet
        # degrades to monolithic service instead of failing.
        if role not in ("both", "prefill", "decode"):
            raise ValueError(f"role must be both|prefill|decode, got {role!r}")
        self.role = role
        if handoff_wire not in ("int8", "raw"):
            raise ValueError(
                f"handoff_wire must be int8|raw, got {handoff_wire!r}")
        self._handoff_wire = handoff_wire
        # replica-scoped observability: by default both land on the
        # process-global registry / health flag (single-replica processes,
        # the existing contract); a multi-replica host passes one
        # MetricsRegistry + HealthState PER engine so the router's /statz
        # poll and /healthz drain signal stay per-replica truths
        self._registry = registry if registry is not None else get_registry()
        self._phase = functools.partial(phase, registry=self._registry)
        self.health = health if health is not None else get_health()
        self.scheduler = IterationScheduler(
            self.num_slots, registry=self._registry,
            max_queue_depth=int(self._config.max_queue_depth),
            shed_retry_after_s=float(self._config.shed_retry_after_s))

        if getattr(self._config, "paged_kv_cache", True) is False:
            # an unknown key only warns (runtime/config_utils.py): this
            # value would silently be served from the pool
            raise ValueError(
                "paged_kv_cache=False: the option and the contiguous "
                "per-slot layout it selected were removed; every engine "
                "serves from the paged pool (serving/paged_kv.py)")
        cfg = self.module.config
        # what a slot's cache is made of: pages of a kind, and whatever else
        # the slot carries (serving/cache_kind.py)
        self.kind = cache_kind(cfg)
        self.kind.check(self._config, role, self.prefill_chunk)
        # ONE placement, from construction on, for what the programs carry
        # from call to call (the pool's arrays, the three carries, the key):
        # over the engine's mesh, as every program hands them back.  jit keys
        # a program on its arguments' mesh as well as on their shapes, so an
        # array fresh from ``jnp.zeros`` (no mesh) made the first program
        # that took it trace, lower and compile a second time
        self._placement = replicated(engine.mesh)
        with self._phase("ds_setup_pool"):
            self._build_pool(engine.dtype)
        # max_out is the configured LOGICAL budget — generation bounds use
        # max_out so serving stays token-identical to generate(), which
        # never sees the physical rounding
        self.max_out = int(self._config.max_out_tokens)
        # Host-side SCHEDULE view of per-slot state.  pos/active mirror the
        # device-resident carries below; for EOS rows the host view is an
        # upper bound (the device may stop a row early — the host learns
        # from the deferred drain), which only ever OVER-allocates pages.
        self._pos = np.zeros(self.num_slots, np.int32)      # cache depth
        self._active = np.zeros(self.num_slots, bool)       # decoding now
        self._limit = np.zeros(self.num_slots, np.int32)    # pos decode bound
        self._eos = np.full(self.num_slots, -1, np.int32)
        self._drained_pos = np.zeros(self.num_slots, np.int32)
        # device-resident decode state: last sampled token, per-row
        # position, per-row active mask — carried (donated) through every
        # chunk program (which wakes the slot it prefilled) and block to
        # block, so neither no-EOS nor EOS scheduling ever syncs per step
        self._last_dev, self._pos_dev, self._act_dev = jax.device_put(
            (jnp.zeros(self.num_slots, jnp.int32),
             jnp.zeros(self.num_slots, jnp.int32),
             jnp.zeros(self.num_slots, bool)), self._placement)
        self._park_fn = jax.jit(
            lambda pos, act, slot: (pos.at[slot].set(0),
                                    act.at[slot].set(False)),
            donate_argnums=(0, 1))
        self._setpos_fn = jax.jit(lambda pos, slot, s: pos.at[slot].set(s),
                                  donate_argnums=(0,))
        self._rng = jax.device_put(
            jax.random.PRNGKey(self._config.seed + 1), self._placement)
        self._block_fn = None
        self._prefill_fns = {}
        self._cow_copy = None    # compiled COW page copy (prefix cache)
        self._host_write = None  # compiled host->device page write (tier)
        # background serving loop (start_loop/stop_loop): drives step()
        # so HTTP /generate handlers can block on request completion
        self._loop_thread: Optional[threading.Thread] = None
        self._loop_stop: Optional[threading.Event] = None
        # set by the loop's crash handler BEFORE health flips: /generate
        # handlers watching a request on a crashed loop hand it back for
        # router re-dispatch instead of stranding it until client timeout
        self._loop_crashed = False
        # idempotent dispatch (docs/RESILIENCE.md "Serving fleet"): a
        # router retry after an ambiguous socket death carries the same
        # idempotency_key, JOINS the original in-flight request here, and
        # cannot double-generate.  Bounded insertion-order map; entries
        # are {"req": Request|None, "ready": Event} — the reservation is
        # taken under the lock BEFORE submit so two racing duplicates
        # cannot both generate.
        self._idem = {}
        self._idem_order = deque()
        self._idem_cap = 4096
        self._idem_lock = threading.Lock()
        # cross-thread abort requests (abort()): consumed at the top of
        # step() so slot/page teardown always runs on the engine thread
        self._aborts = deque()
        # cross-thread KV-handoff work (/kv_offer, /kv_adopt HTTP
        # handlers): prefix-trie mutation and page writes must run on the
        # engine thread, so handlers enqueue {kind, payload, result,
        # done-Event} items consumed right after aborts at the top of
        # step(); the handler blocks on the Event for its answer
        self._handoffs = deque()
        # deferred token blocks: device [K, B] arrays kept un-fetched until
        # scheduling needs their values.  No-EOS requests hold refcounted
        # (idx, n) refs resolved at finish; EOS requests are drain
        # PARTICIPANTS — their share is appended when the block's
        # (toks, valid) pair is drained, one block behind dispatch.
        self._blocks = {}        # idx -> device toks [K, B]
        self._block_valid = {}   # idx -> device valid [K, B] (drain blocks)
        self._block_np = {}      # idx -> (toks np, valid np | None)
        self._block_refs = {}    # idx -> pending consumers (refs + drains)
        self._block_moe = {}     # idx -> device routing counts (registry on)
        # rows a pass of the block's fused_moe_mlp works where it cuts its
        # passes to the live rows' tiles (set with the block program)
        self._moe_row_tile = None
        self._outstanding = deque()   # [(idx, [eos Request, ...])]
        # first tokens owed in this iteration: (Request, device scalar),
        # copy to the host started, value not read yet.  Settled before
        # step() returns (_settle_first_tokens)
        self._owed = deque()
        self._drain_lag = 1
        self._next_block = 0
        # launch number: one for every chunk program and every decode block
        # enqueued, in enqueue order.  Its dispatch range carries it
        # (``seq``), so does the request tracer's span: by it a program on
        # the chip's ``XLA Modules`` line is found from the host's side
        self._launch_seq = 0
        self.steps = 0
        self.metrics_server = None   # attached by init_serving(metrics_port=)
        # /profilez: windowed capture over scheduler iterations (decode
        # blocks), claimed from the process-global broker — one attribute
        # load per step while nothing is requested
        from deepspeed_tpu.profiling.device_trace import get_profile_broker

        self._pz_broker = get_profile_broker()
        self._pz = None              # [TraceCapture, ProfileRequest, done]
        # per-request span tracing (compute-side edges/spans; the
        # scheduler owns the queue-side ones) + flight-recorder request
        # events — both disabled-by-default one-branch no-ops
        self._tracer = get_request_tracer()
        self._flight = get_flight_recorder()
        # continuous profiler (docs/OBSERVABILITY.md "Continuous
        # profiling"): scheduled low-duty-cycle capture windows over
        # scheduler iterations, sharing the /profilez decompose + registry
        # paths.  Default OFF — self._cprof stays None and the steady-state
        # cost is one attribute load + branch per iteration (PR 3 contract).
        self._cprof = None
        cpc = dict(getattr(self._config, "continuous_profiler", None) or {})
        if cpc.get("enabled"):
            from deepspeed_tpu.profiling.continuous import (
                ContinuousProfiler, ensure_registered)

            get_registry().enable()
            ensure_registered(get_registry())
            self._cprof = ContinuousProfiler(
                engine="serving",
                every_steps=int(cpc.get("every_steps", 200)),
                every_seconds=float(cpc.get("every_seconds", 120.0)),
                capture_steps=int(cpc.get("capture_steps", 2)),
                max_duty_cycle=float(cpc.get("max_duty_cycle", 0.01)),
                history_dir=cpc.get("history_dir", "profile_history"),
                max_windows=int(cpc.get("max_windows", 64)),
                max_bytes=int(cpc.get("max_bytes", 4 << 20)),
                regression_tolerance=float(
                    cpc.get("regression_tolerance", 0.25)),
                min_scope_seconds=float(
                    cpc.get("min_scope_seconds", 5e-5)),
                flight=self._flight)
            log_dist("continuous profiler armed (serving): every "
                     f"{self._cprof.every_steps} steps / "
                     f"{self._cprof.every_seconds}s, duty cycle <= "
                     f"{self._cprof.max_duty_cycle:.2%}", ranks=[0])
        # run-level goodput ledger (docs/OBSERVABILITY.md "Goodput
        # ledger"): serving shares the same process-global run clock.
        # Enabled by the DSTPU_RUNLEDGER env (serve_supervisor's channel)
        # or an ``slo``/``goodput`` block in the serving config.
        self._goodput = get_goodput_ledger()
        slo_rules = dict(getattr(self._config, "slo", None) or {})
        gp_cfg = dict(getattr(self._config, "goodput", None) or {})
        if (os.environ.get("DSTPU_RUNLEDGER") or slo_rules
                or gp_cfg.get("enabled")):
            # role-split fleets attribute prefill-side and decode-side
            # wall clock to distinct ledger roles so the run ledger's
            # per-role aggregation keeps the two pools' goodput apart
            self._goodput.enable(
                path=gp_cfg.get("path"),
                role="serve" if self.role == "both" else f"serve-{self.role}",
                min_tick_interval_s=gp_cfg.get("min_tick_interval_s"),
                slo_rules=slo_rules or None)
        # compute-side lifecycle metrics (queue-side spans live in the
        # scheduler; all are one-branch no-ops while the registry is
        # disabled — see docs/OBSERVABILITY.md for the schema)
        reg = self._registry
        self._m_ttft = reg.histogram(
            "ds_serve_ttft_seconds",
            "submit -> first output token's value on the host (for a "
            "non-streaming request without EOS that is its finish)")
        self._m_tpot = reg.histogram(
            "ds_serve_tpot_seconds",
            "per-output-token latency (first token on the host -> finish); "
            "streaming and EOS requests only: the others fetch every token "
            "at their finish")
        # host phases of one scheduler iteration: a span in the profiler's
        # trace and a seconds counter each, written by phase()
        for name, what in SERVE_PHASES.items():
            reg.counter(name + "_seconds_total",
                        f"host seconds inside {name}: {what}")
        self._m_moe = {name: reg.counter(name, what)
                       for name, what in SERVE_MOE_COUNTERS.items()}
        self._m_moe_block = reg.gauge(
            "ds_serve_moe_expert_block_share",
            "the weight block of a grid step of the decode block's "
            "fused_moe_mlp as a share (%) of an expert's bytes: 100 = a "
            "held expert passes through VMEM whole; set when the block "
            "program is built, 0 for a dense model")
        # page and state series: every kind's registered, this kind's moved
        self.kind.attach(reg, self.pool)
        self.kind.cache_gauges(self._cache)
        self._m_loop_passes = reg.counter(
            "ds_serve_loop_passes_total",
            "passes through the layer stack dispatched: total_ut_steps a "
            "prefill chunk and a decode step (decode_block_tokens x "
            "total_ut_steps a block); 1 a chunk and a step unless the stack "
            "is looped")
        self._m_first_overlapped = reg.counter(
            "ds_serve_first_token_overlapped_total",
            "first tokens fetched with a decode block already enqueued "
            "behind their chunk (over finished requests: the share of first "
            "tokens that cost the chip no gap)")
        self._m_first_tokens = reg.counter(
            "ds_serve_first_tokens_total",
            "first tokens fetched right behind their chunk (stream / EOS / "
            "last-token path): what ds_serve_first_token_overlapped_total "
            "is a share of")
        self._m_prefill_turns = reg.counter(
            "ds_serve_prefill_turns_total",
            "request-iterations in PREFILLING: a request that held a slot "
            "with prompt left to compute, once an iteration")
        self._m_prefill_turns_missed = reg.counter(
            "ds_serve_prefill_turns_missed_total",
            "of ds_serve_prefill_turns_total, those given no chunk: beyond "
            "max_prefill_chunks, or refused pages")
        self._m_prefill_chunks = reg.counter(
            "ds_serve_prefill_chunks_total", "prefill chunks dispatched")
        self._m_prefill_chunks_extra = reg.counter(
            "ds_serve_prefill_chunks_extra_total",
            "of ds_serve_prefill_chunks_total, those given to a request "
            "that already had a chunk in the same iteration: places of "
            "max_prefill_chunks the other requests left")
        self._m_prefill_toks = reg.counter(
            "ds_serve_prefill_tokens_total", "prompt tokens prefilled")
        self._m_prefill_pad_rows = reg.counter(
            "ds_serve_prefill_pad_rows_total",
            "rows of the prefill chunks' buckets past their real tokens "
            "(bucket - tokens a chunk): over ds_serve_prefill_tokens_total, "
            "the work the buckets' floor adds")
        self._m_decode_toks = reg.counter(
            "ds_serve_decode_tokens_total", "decode tokens scheduled")
        self._m_row_slots = reg.counter(
            "ds_serve_decode_row_slots_total",
            "rows x steps of the decode blocks' batch: num_slots x "
            "decode_block_tokens a block.  ds_serve_decode_tokens_total over "
            "it is the share of them in which the row decoded, the rows "
            "the decode attention kernels visit (ops/pallas/decode.py)")
        self._m_steps = reg.counter(
            "ds_serve_steps_total", "scheduler iterations")
        self._m_compiles = reg.counter(
            "ds_serve_compiles_total",
            "serving programs compiled (prefill buckets + decode block)")
        self._m_active = reg.gauge(
            "ds_serve_active_slots", "slots decoding right now")
        self._m_occupancy = reg.histogram(
            "ds_serve_occupancy_ratio",
            "per-step occupied-slot fraction (mean = avg occupancy)",
            buckets=tuple(i / 16 for i in range(1, 17)))
        self._m_step_finished = reg.gauge(
            "ds_serve_step_finished", "requests drained by the last step")
        # graceful drain (docs/RESILIENCE.md): 1 for the whole drain()
        # window — the same signal /healthz serves as 503
        self._draining = False
        self._m_draining = reg.gauge(
            "ds_serve_draining",
            "1 while drain() runs (admission stopped, in-flight requests "
            "finishing); 0 otherwise")
        # paged-KV pool health
        self._m_pages_used = reg.gauge(
            "ds_serve_kv_pages_used", "KV pool pages allocated to slots")
        self._m_pages_free = reg.gauge(
            "ds_serve_kv_pages_free", "KV pool pages on the free list")
        self._m_preempted = reg.counter(
            "ds_serve_preempted_total",
            "requests preempted (pages reclaimed, requeued at queue head)")
        self._m_kv_util = reg.histogram(
            "ds_serve_kv_cache_util_ratio",
            "per-step live-tokens / allocated-page-tokens (paged pool)",
            buckets=tuple(i / 16 for i in range(1, 17)))
        # prefix-cache effectiveness (registered unconditionally for the
        # namespace guard; the hit/miss counters only move while a
        # PrefixCache is attached).  hit = prompt tokens whose prefill
        # was SKIPPED (served from cached pages), miss = tokens actually
        # computed — hit / (hit + miss) is the prefix hit ratio
        self._m_prefix_hit = reg.counter(
            "ds_serve_prefix_hit_tokens_total",
            "prefix tokens served from the cache (prefill skipped)")
        self._m_prefix_miss = reg.counter(
            "ds_serve_prefix_miss_tokens_total",
            "prefix tokens computed by prefill (cache miss or cache off)")
        self._m_idem_hits = reg.counter(
            "ds_serve_idem_hits_total",
            "/generate dispatches that joined an existing request via "
            "their idempotency key (router retry de-duplicated)")
        self._m_crash_requeues = reg.counter(
            "ds_serve_crash_requeued_total",
            "in-flight requests handed back (503) because the serving "
            "loop crashed under them")
        # disaggregated prefill/decode serving (docs/RESILIENCE.md):
        # handoff byte/page accounting on the SENDER (wire = what crossed
        # the socket, dense = the same pages at the engine compute
        # dtype), adoption counts on the RECEIVER, and the streaming
        # front's resume counter.  Registered unconditionally for the
        # metric-namespace guard; only a role-split fleet moves them.
        self._m_handoff_bytes = {
            dt: reg.counter(
                "ds_serve_kv_handoff_bytes_total",
                "KV handoff bytes by encoding: wire encodings (int8/raw) "
                "vs the dense twin the same pages would cost at the "
                "compute dtype", labels={"dtype": dt})
            for dt in ("int8", "raw", "dense")}
        self._m_handoff_pages = reg.counter(
            "ds_serve_kv_handoff_pages_total",
            "KV pages shipped to a decode replica (sender side)")
        self._m_adopted_pages = reg.counter(
            "ds_serve_kv_adopted_pages_total",
            "handed-off KV pages adopted into the local prefix cache "
            "(receiver side; offered-but-already-held pages not counted)")
        self._m_stream_resumes = reg.counter(
            "ds_serve_stream_resumes_total",
            "streaming /generate dispatches that entered with "
            "resume_from > 0 (router resumed a broken stream here)")
        self._m_role = reg.gauge(
            "ds_serve_role_info",
            "1 for this replica's serving role (prefill|decode|both)",
            labels={"role": self.role})
        self._m_role.set(1)
        from deepspeed_tpu.models.fused_decode import supports_fused_decode
        fused_ok = (self._config.use_fused_decode is not False
                    and supports_fused_decode(
                        cfg, quantized_kv=self._config.quantize_kv_cache,
                        tp=engine.mesh.shape.get("tp", 1)))
        log_dist("serving engine: "
                 f"{self.kind.layout(self.pool, self.num_slots)}, "
                 f"prefill_chunk={self.prefill_chunk}, "
                 f"decode_block={self._K}, "
                 f"{'fused' if fused_ok else 'unfused'} decode", ranks=[0])

    def _build_pool(self, dtype) -> None:
        """The page pool, the cache kind's tables over it and the prefix
        cache: the ``pool`` part of set-up."""
        self.pool = PagedKVPool(
            self.num_slots, self._config.max_out_tokens,
            page_tokens=self._config.kv_page_tokens,
            pool_tokens=self._config.kv_pool_tokens,
            **self.kind.pool_args(dtype))
        self._cache = jax.device_put(
            self.kind.init_cache(self.pool, self.num_slots, dtype,
                                 self._config.quantize_kv_cache),
            self._placement)
        # per-slot LOGICAL window (page-table depth x page); the PHYSICAL
        # pool may hold fewer tokens than num_slots windows
        self.cache_len = self.pool.cache_len
        # copy-on-write prefix caching over the page pool, for a kind whose
        # pages are a function of the token prefix alone, with an optional
        # HOST TIER: kv_host_tier_pages > 0 bounds an LRU host store that
        # eviction victims demote into (instead of dropping) and admissions
        # promote back out of — the effective prefix cache becomes
        # host-RAM-sized (docs/OBSERVABILITY.md "KV host tier")
        self.host_store = None
        self.prefix_cache = None
        if self._config.prefix_caching:
            why_not = self.kind.cannot.get("prefix_caching")
            if why_not:
                log_dist(f"prefix caching is off for {self.kind.what}: "
                         f"{why_not}", ranks=[0])
            else:
                host_pages = int(getattr(self._config,
                                         "kv_host_tier_pages", 0))
                if host_pages > 0:
                    self.host_store = HostPageStore(host_pages,
                                                    registry=self._registry)
                self.prefix_cache = PrefixCache(
                    self.pool, registry=self._registry,
                    host_store=self.host_store,
                    fetch_page=(self._fetch_page_host
                                if self.host_store is not None else None))

    # ------------------------------------------------------------------
    def set_params(self, params: Any) -> None:
        self.engine.set_params(params)
        self._block_fn = None
        self._prefill_fns = {}

    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 128,
               eos_token_id: Optional[int] = None,
               deadline_s: Optional[float] = None,
               traceparent: Optional[str] = None,
               stream: bool = False,
               prefill_only: bool = False) -> Request:
        """Enqueue one request; returns the live Request handle (its
        ``output_tokens`` fill in as the scheduler serves it).

        ``deadline_s`` (or the config default ``request_deadline_s``)
        sets the request's service deadline: still QUEUED past it, the
        scheduler cancels it with finish reason ``deadline`` instead of
        burning a slot on an answer nobody is waiting for.  Raises
        :class:`~deepspeed_tpu.serving.scheduler.QueueFull` when the
        bounded admission queue (``max_queue_depth``) is at its
        watermark — the overload shed the HTTP surface maps to 429."""
        if self._draining or self.scheduler.admission_paused:
            raise RuntimeError(
                "engine is draining/drained: not admitting new requests "
                "(the router should have stopped sending — /healthz is "
                "503; resume_admission() re-opens)")
        if prefill_only:
            self.kind.refuse("handoff", "prefill_only")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if prompt.size > self.max_out:
            raise ValueError(
                f"prompt length {prompt.size} exceeds the per-slot cache "
                f"budget max_out_tokens={self.max_out}")
        if deadline_s is None:
            cfg_dl = float(self._config.request_deadline_s)
            deadline_s = cfg_dl if cfg_dl > 0 else None
        req = Request(prompt=prompt, max_new_tokens=int(max_new_tokens),
                      eos_token_id=(-1 if eos_token_id is None
                                    else int(eos_token_id)),
                      stream=bool(stream), prefill_only=bool(prefill_only))
        if traceparent:
            # W3C shape "00-<32hex trace>-<16hex span>-01": the 32-hex
            # trace-id is the cross-process join key; a non-conforming
            # header is kept verbatim (still a usable correlation key)
            parts = str(traceparent).split("-")
            req.trace_id = parts[1] if len(parts) == 4 and parts[1] \
                else str(traceparent)
        if deadline_s is not None:
            req.deadline = time.perf_counter() + float(deadline_s)
        return self.scheduler.submit(req)

    # ------------------------------------------------------------------
    def step(self) -> List[Request]:
        """One scheduler iteration: admit → prefill chunk(s) → decode
        block → fetch the chunks' first tokens → drain deferred finish
        events.  Returns the requests that finished during this
        iteration."""
        if self.engine._params is None:
            raise RuntimeError("no weights: set_params() first")
        # ledger: one scheduler iteration is a `compute` region (admit +
        # prefill + decode dispatches); time between step() calls is idle
        # (or `drain` during a drain window).  Ticks ride the same seam.
        self._goodput.push("compute")
        try:
            with self._phase("ds_serve_step", step_num=self.steps):
                return self._step_inner()
        finally:
            self._goodput.pop()
            self._goodput.tick()

    def _step_inner(self) -> List[Request]:
        self._profilez_begin()
        # 0. cross-thread aborts (504'd /generate handlers): tear down on
        #    THIS thread so slot parking / page release / deferred-block
        #    unref never race a dispatch
        while self._aborts:
            self._process_abort(self._aborts.popleft())
        # 0b. KV-handoff work (/kv_offer, /kv_adopt): trie walks + page
        #     writes on THIS thread — the prefix cache is engine-thread-
        #     only by contract
        while self._handoffs:
            self._process_handoff(self._handoffs.popleft())
        done_before = len(self.scheduler.finished)
        # 1. admission: freed slots pick up the oldest queued requests;
        #    a prefix-cache hit pre-populates the slot's page table with
        #    shared pages and moves the prefill frontier past them
        with self._phase("ds_serve_admit"):
            for req in self.scheduler.admit():
                self._pos[req.slot] = 0
                self._active[req.slot] = False
                self._limit[req.slot] = 0
                self.kind.count_admit()
                if self.prefix_cache is not None:
                    self._admit_prefix(req)
        # 2. chunked prefill: max_prefill_chunks PLACES an iteration, one
        #    chunk program each (bounded so running slots' decode latency
        #    stays bounded).  Oldest admissions first, one chunk a request;
        #    the places they leave go round again, in the same order, over
        #    those that were given a chunk and have prompt left
        with self._phase("ds_serve_prefill"):
            waiting = self.scheduler.prefilling()
            places = self.max_prefill_chunks
            going = deque((req, True) for req in waiting[:places])
            while going and places:
                req, first = going.popleft()
                if req.state != PREFILLING:      # preempted by a chunk above
                    continue
                before = req.prefill_pos
                self._prefill_one_chunk(req)
                given = req.prefill_pos > before     # else: refused pages
                if first:                # a turn is counted by its first try
                    self._prefill_turn(missed=not given)
                elif given:
                    self._m_prefill_chunks_extra.inc()
                if given:
                    places -= 1
                    if (req.state == PREFILLING
                            and req.prefill_pos < req.prefix_len):
                        going.append((req, False))
            for req in waiting[self.max_prefill_chunks:]:
                if req.state == PREFILLING:  # not preempted by a chunk above
                    self._prefill_turn(missed=True)
            if not self._active.any():
                # no block will be queued behind the chunks: fetch now
                self._settle_first_tokens()
        # 3. decode one block for every active slot; the first tokens the
        #    chunks owe are fetched right behind its enqueue
        if self._active.any():
            with self._phase("ds_serve_decode"):
                self._decode_block()
        elif self._outstanding:
            # nothing left to dispatch: flush pending finish events so the
            # final EOS slots free and the loop can drain
            self._flush_outstanding()
        self.steps += 1
        self._m_steps.inc()
        self._m_active.set(int(self._active.sum()))
        self._m_occupancy.record(self.scheduler.num_occupied / self.num_slots)
        # cache utilization = live tokens / tokens of the pages granted
        if self.pool.pages_used:
            self._m_kv_util.record(
                self.pool.utilization(int(self._pos.sum())))
        self.kind.count_iteration(self.pool)
        finished = self.scheduler.finished[done_before:]
        self._m_step_finished.set(len(finished))
        self._profilez_end()
        self._cprof_tick()
        return finished

    def run(self) -> List[Request]:
        """Serve to empty: iterate until queue and slots are empty; returns
        finished requests in completion order.  With admission paused (the
        state ``drain()`` leaves behind) and only queued work remaining,
        returns instead of spinning — queued requests cannot be admitted
        until :meth:`resume_admission`."""
        while self.scheduler.has_work:
            if (self.scheduler.admission_paused
                    and self.scheduler.num_occupied == 0
                    and not self._outstanding):
                break
            self.step()
        return self.scheduler.finished

    # ------------------------------------------------------------------
    # graceful drain (docs/RESILIENCE.md; the router drain signal of
    # ROADMAP item 3)
    # ------------------------------------------------------------------
    def drain(self, timeout: Optional[float] = None) -> List[Request]:
        """Stop admission and finish every in-flight request.

        For the whole drain window: ``submit()`` raises, the scheduler
        hands out no new slots, ``/healthz`` reports not-ready (503), and
        ``ds_serve_draining`` reads 1.  Already-admitted requests
        (prefilling or decoding) run to completion TOKEN-IDENTICALLY —
        the per-slot decode path is untouched, admission is the only
        thing gated.  Requests still queued (never admitted) stay in the
        queue for the caller/router to re-dispatch.

        Readiness stays ``not ready`` after the drain completes (the
        process is about to go away); call :meth:`resume_admission` to
        take traffic again.  Returns the requests that finished during
        the drain; with ``timeout`` (seconds) the loop stops early and
        returns what finished, leaving the rest in flight.

        With a background serving loop attached (:meth:`start_loop`) the
        loop keeps stepping and this call only WAITS for occupancy to
        reach zero (two threads must not both dispatch); the loop also
        drains the finished list continuously, so the return value is []
        in that mode — callers watching a loop-driven drain observe
        ``/healthz`` and their own request handles instead."""
        if self._draining:
            return []
        self._draining = True
        self.scheduler.pause_admission()
        self._m_draining.set(1)
        self.health.set_not_ready("draining")
        inflight = self.scheduler.running() + self.scheduler.prefilling()
        if self._flight.enabled:
            self._flight.record("serve_drain_start",
                                occupied=self.scheduler.num_occupied,
                                queued=self.scheduler.num_queued,
                                rids=[r.request_id for r in inflight][:32])
        done_before = len(self.scheduler.finished)
        t0 = time.perf_counter()
        timed_out = False
        loop_is_stepping = self._loop_alive()
        # ledger: the drain window is its own category; step()'s nested
        # `compute` regions carve their time out, so `drain` accumulates
        # only the non-compute remainder (waiting on occupancy).
        self._goodput.push("drain")
        try:
            while self.scheduler.num_occupied > 0:
                if timeout is not None and time.perf_counter() - t0 > timeout:
                    timed_out = True
                    break
                if loop_is_stepping and not self._loop_alive():
                    # the loop thread died mid-drain (stop_loop or a
                    # crash): join so its in-flight step fully retires,
                    # then take over stepping instead of sleeping forever
                    if self._loop_thread is not None:
                        self._loop_thread.join(timeout=30)
                    loop_is_stepping = False
                    if self._loop_crashed:
                        # drain racing a KILL: the loop crashed under the
                        # drain — stepping a crashed engine would only
                        # re-raise, and the in-flight requests are being
                        # handed back (503) to the router by their own
                        # /generate handlers.  Return what finished; the
                        # replica is dead, not draining.
                        timed_out = True
                        break
                if loop_is_stepping:
                    time.sleep(0.002)     # the loop thread dispatches
                else:
                    self.step()
        finally:
            self._goodput.pop()
            self._m_draining.set(0)
            self._draining = False
            finished = self.scheduler.finished[done_before:]
            if self._flight.enabled:
                self._flight.record(
                    "serve_drain_done", finished=len(finished),
                    timed_out=timed_out,
                    queued=self.scheduler.num_queued,
                    seconds=time.perf_counter() - t0,
                    rids=[r.request_id for r in finished][:32])
            log_dist(f"serving drain: {len(finished)} request(s) finished"
                     + (", TIMED OUT with slots still occupied"
                        if timed_out else "")
                     + f"; {self.scheduler.num_queued} left queued "
                     f"(admission stays paused; /healthz not-ready)",
                     ranks=[0])
        return finished

    def resume_admission(self) -> None:
        """Undo :meth:`drain`: admission resumes and ``/healthz`` reports
        ready again (a drained-but-not-terminated replica rejoining the
        router pool)."""
        self.scheduler.resume_admission()
        self.health.set_ready()

    @property
    def draining(self) -> bool:
        return self._draining

    # ------------------------------------------------------------------
    # background serving loop + HTTP /generate handler (the replica side
    # of serving/router.py — docs/OBSERVABILITY.md "Router")
    # ------------------------------------------------------------------
    def _loop_alive(self) -> bool:
        return self._loop_thread is not None and self._loop_thread.is_alive()

    def start_loop(self, idle_sleep: float = 0.002) -> "ServingEngine":
        """Drive :meth:`step` on a daemon thread so requests submitted
        from other threads (the ``POST /generate`` HTTP handler) make
        progress without a caller-owned serving loop.  The loop drains
        ``scheduler.finished`` every iteration (long-lived processes must
        not accumulate history); handlers keep their own Request
        references.  Idempotent; :meth:`stop_loop` stops it."""
        if self._loop_alive():
            return self
        self._loop_crashed = False       # a restart clears the crash latch
        stop = self._loop_stop = threading.Event()

        def loop():
            try:
                while not stop.is_set():
                    idle = True
                    # KV handoffs must progress on an IDLE replica too —
                    # a decode replica with no live requests still
                    # answers /kv_offer + /kv_adopt (the handler blocks
                    # on this drain; without it every handoff to a quiet
                    # replica stalls to the enqueue timeout)
                    while self._handoffs:
                        self._process_handoff(self._handoffs.popleft())
                        idle = False
                    if self.scheduler.has_work and not (
                            self.scheduler.admission_paused
                            and self.scheduler.num_occupied == 0
                            and not self._outstanding):
                        self.step()
                        self.scheduler.drain_finished()
                        idle = False
                    if idle:
                        time.sleep(idle_sleep)
            except Exception as exc:    # noqa: BLE001 - must not die silent
                # a crashed loop is a DEAD replica, not a busy one: flip
                # readiness so the router stops sending (a 200 /healthz
                # over a thread that no longer steps would strand every
                # dispatch in the requeue-grace path forever).  The crash
                # flag goes first: /generate handlers watching admitted
                # requests hand them back (503 requeue) the moment they
                # see it — a dead loop must not strand in-flight work
                # until client timeout (chaos-harness class)
                self._loop_crashed = True
                self.health.set_not_ready(f"serving loop crashed: {exc!r}")
                log_dist(f"serving loop crashed (replica marked not-ready;"
                         f" /healthz 503): {exc!r}", ranks=[0])
                raise

        self._loop_thread = threading.Thread(
            target=loop, name="ds-serving-loop", daemon=True)
        self._loop_thread.start()
        return self

    def stop_loop(self, timeout: float = 30.0) -> None:
        if self._loop_stop is not None:
            self._loop_stop.set()
        if self._loop_thread is not None:
            self._loop_thread.join(timeout=timeout)
        self._loop_thread = None
        self._loop_stop = None

    def abort(self, req: Request) -> None:
        """Request teardown of an abandoned request (the ``/generate``
        handler's 504 path: the client stopped waiting, so decoding to
        ``max_new_tokens`` for nobody would burn the slot).  Safe from
        any thread — the actual cancel/release runs at the next
        :meth:`step` boundary on the engine thread."""
        self._aborts.append(req)

    def _process_abort(self, req: Request) -> None:
        """Engine-thread half of :meth:`abort`: a still-queued request is
        cancelled; an admitted one is released with reason ``cancelled``
        (its deferred token blocks are materialized first so refcounted
        blocks drop; an EOS drain participant released early is already
        skipped-and-unref'd by ``_drain_one``'s state check)."""
        if req.state == QUEUED:
            self.scheduler.cancel(req)
            return
        self._settle_first_tokens()      # it may finish at its first token
        if (req.state in (PREFILLING, RUNNING)
                and req.slot >= 0
                and self.scheduler.request_in(req.slot) is req):
            self._materialize(req)
            self._release(req, "cancelled")

    def _http_generate(self, payload: dict):
        """``POST /generate`` handler (wired by ``init_serving(
        metrics_port=...)``): submit, block this HTTP worker until the
        request finishes, return its tokens.  Returns ``(status, body)``.

        Drain-aware redistribution: while the engine drains, ``submit``
        raises (503 — the router sends elsewhere), and a request that was
        QUEUED but never admitted when the drain hit is CANCELLED and
        503'd back so the router re-dispatches it to a healthy replica —
        zero requests are dropped on a drain.

        Overload protection: a submit shed by the bounded admission
        queue returns ``429`` with ``retry_after_s`` (the server adds the
        ``Retry-After`` header); a request whose service deadline
        (``deadline_s``) expires while queued returns ``504`` with
        ``deadline_expired`` (the router does NOT retry — the deadline
        has passed everywhere).

        Idempotent dispatch: a payload ``idempotency_key`` reserves a
        slot in the engine's bounded dedup map BEFORE submitting; a
        second dispatch with the same key (the router retrying after an
        ambiguous socket death) JOINS the original request instead of
        generating again, and a key whose request already finished
        replays its tokens — one generation per key, however many times
        the network made the router ask."""
        try:
            prompt = payload["prompt"]
            max_new = int(payload.get("max_new_tokens", 128))
            eos = payload.get("eos_token_id")
            timeout = float(payload.get("timeout", 300.0))
            deadline_s = payload.get("deadline_s")
            deadline_s = None if deadline_s is None else float(deadline_s)
            idem = payload.get("idempotency_key")
            if idem is not None and not isinstance(idem, str):
                raise ValueError("idempotency_key must be a string")
            # trace context: the router's traceparent header (injected
            # into the payload by monitor/server.py do_POST) or a
            # caller-supplied payload field
            traceparent = payload.get("traceparent")
            if traceparent is not None and not isinstance(traceparent, str):
                raise ValueError("traceparent must be a string")
            # disaggregated serving: "phase": "prefill" runs admission +
            # chunked prefill only and ships the KV pages to handoff_to;
            # "stream": true returns a chunked ndjson event stream;
            # "resume_from": N streams/returns only tokens[N:] (the
            # router already delivered the first N to the client)
            phase = payload.get("phase")
            if phase not in (None, "prefill"):
                raise ValueError(f"unknown phase {phase!r}")
            prefill_only = phase == "prefill"
            stream = bool(payload.get("stream")) and not prefill_only
            resume_from = int(payload.get("resume_from") or 0)
            if resume_from < 0:
                raise ValueError("resume_from must be >= 0")
            handoff_to = payload.get("handoff_to")
            if handoff_to is not None and not isinstance(handoff_to, str):
                raise ValueError("handoff_to must be a string URL")
        except (KeyError, TypeError, ValueError) as exc:
            return 400, {"error": f"bad /generate payload: {exc!r}"}
        if stream and resume_from:
            self._m_stream_resumes.inc()
        deadline = time.monotonic() + timeout
        # the reservation loop converges: each pass either owns the key
        # (submits exactly once) or joins an existing in-flight entry; a
        # joined entry whose owner FAILED to submit re-loops to take the
        # key over.  Bounded to keep a pathological churn from spinning.
        for _attempt in range(4):
            entry = None
            owner = True
            if idem is not None:
                with self._idem_lock:
                    entry = self._idem.get(idem)
                    if entry is None:
                        entry = {"req": None, "ready": threading.Event()}
                        self._idem[idem] = entry
                        # the order deque holds (key, entry) so cap
                        # eviction can verify IDENTITY: a key that was
                        # dropped and re-reserved appears twice, and
                        # popping the stale first occurrence must not
                        # delete the LIVE entry (that would re-enable
                        # the double-generation this map exists to stop)
                        self._idem_order.append((idem, entry))
                        while len(self._idem_order) > self._idem_cap:
                            old_key, old_entry = self._idem_order.popleft()
                            if self._idem.get(old_key) is old_entry:
                                del self._idem[old_key]
                    else:
                        owner = False
            if not owner:
                self._m_idem_hits.inc()
                if not entry["ready"].wait(
                        max(0.0, deadline - time.monotonic())):
                    return 504, {"error": "timed out joining the "
                                          "in-flight idempotent request",
                                 "idempotency_key": idem}
                req = entry["req"]
                if req is None:
                    continue       # the original submit failed: take over
                if stream:
                    return 200, self._stream_request(
                        req, deadline, owns=False, idem=idem, entry=entry,
                        start=resume_from)
                return self._await_request(req, deadline, owns=False,
                                           idem=idem, entry=entry,
                                           resume_from=resume_from)
            try:
                req = self.submit(prompt, max_new_tokens=max_new,
                                  eos_token_id=eos, deadline_s=deadline_s,
                                  traceparent=traceparent, stream=stream,
                                  prefill_only=prefill_only)
            except QueueFull as exc:       # overload shed -> 429 + backoff
                self._idem_drop(idem, entry)
                return 429, {"error": str(exc), "shed": True,
                             "retry_after_s": exc.retry_after_s}
            except RuntimeError as exc:    # draining: stop-sending signal
                self._idem_drop(idem, entry)
                return 503, {"error": str(exc), "draining": True}
            except (TypeError, ValueError) as exc:
                self._idem_drop(idem, entry)
                return 400, {"error": str(exc)}
            if entry is not None:
                entry["req"] = req         # published by the event below
                entry["ready"].set()
            if stream:
                return 200, self._stream_request(
                    req, deadline, owns=True, idem=idem, entry=entry,
                    start=resume_from)
            return self._await_request(req, deadline, owns=True,
                                       idem=idem, entry=entry,
                                       resume_from=resume_from,
                                       handoff_to=handoff_to)
        return 503, {"error": "idempotency reservation kept churning "
                              "(original submits failing); try again",
                     "requeued": True}

    def _idem_drop(self, idem, entry) -> None:
        """Remove a reservation whose request failed/was torn down, and
        wake joiners (they re-loop and take the key over)."""
        if idem is None or entry is None:
            return
        with self._idem_lock:
            if self._idem.get(idem) is entry:
                del self._idem[idem]
        entry["ready"].set()

    def _await_request(self, req: Request, deadline: float, *, owns: bool,
                       idem=None, entry=None, resume_from: int = 0,
                       handoff_to=None):
        """Block one HTTP worker until ``req`` finishes; maps every
        terminal state to the router-facing status contract.  ``owns``
        is False for a joined idempotent duplicate — it must not abort a
        request another handler owns when ITS deadline passes (and it
        never re-ships a handoff the owner already performed)."""
        now = time.monotonic()
        last_steps, last_progress = self.steps, now
        while not req.done:
            now = time.monotonic()
            if self.steps != last_steps:      # SOMETHING is stepping —
                last_steps = self.steps       # background loop or a
                last_progress = now           # caller-driven step() loop
            if self._loop_crashed:
                # the serving loop DIED under this request (kill/chaos
                # class): hand it back for router re-dispatch instead of
                # stranding it until client timeout.  An admitted
                # request is aborted locally — the teardown runs when
                # the replica revives, so its pages free then.
                if req.state == QUEUED and self.scheduler.cancel(req):
                    self._m_crash_requeues.inc()
                    self._idem_drop(idem, entry)
                    return 503, {"error": "request requeued: serving "
                                          "loop crashed before admission",
                                 "requeued": True}
                if req.state in (PREFILLING, RUNNING):
                    self.abort(req)
                    self._m_crash_requeues.inc()
                    self._idem_drop(idem, entry)
                    return 503, {"error": "request requeued: serving "
                                          "loop crashed mid-request "
                                          "(aborted locally)",
                                 "requeued": True}
            # hand the request back for router re-dispatch when nothing
            # will admit it: immediately on a drain (admission paused),
            # or once no scheduler step has run for a grace second and
            # no loop thread exists — a busy caller-driven loop keeps
            # making steps and is never mistaken for a dead replica
            if req.state == QUEUED and (
                    self.scheduler.admission_paused
                    or (not self._loop_alive()
                        and now - last_progress > 1.0)):
                if self.scheduler.cancel(req):
                    self._idem_drop(idem, entry)
                    return 503, {"error": "request requeued: replica "
                                          "draining/stopped before "
                                          "admission", "requeued": True}
            if now > deadline:
                if not owns:
                    return 504, {"error": "timed out waiting on the "
                                          "in-flight idempotent request "
                                          "(not aborted: another handler "
                                          "owns it)",
                                 "request_id": req.request_id}
                # the client is gone: don't decode to max_new_tokens for
                # nobody — the engine thread tears the request down at
                # its next step boundary and the slot frees
                self.abort(req)
                return 504, {"error": "generation timed out (request "
                                      "aborted; slot reclaimed)",
                             "request_id": req.request_id}
            time.sleep(0.001)
        if req.finish_reason == "deadline":
            # expired while queued: too late everywhere — no retry
            return 504, {"error": "service deadline expired before "
                                  "admission; request cancelled",
                         "deadline_expired": True,
                         "request_id": req.request_id}
        if req.finish_reason == "cancelled":
            # torn down without an answer (abort/crash teardown): let the
            # router re-dispatch; the dropped reservation makes a retry
            # here generate fresh
            self._idem_drop(idem, entry)
            return 503, {"error": "request cancelled before completion",
                         "requeued": True, "request_id": req.request_id}
        if req.finish_reason == "prefill_done":
            # prefill-role completion: no output tokens by design — the
            # OWNER ships the captured KV pages to the decode replica
            # named by the dispatch (a joined duplicate reports success
            # without re-shipping; the transfer is idempotent anyway,
            # the decode side re-offers and takes nothing twice)
            body = {"prefill_done": True, "tokens": [],
                    "request_id": req.request_id,
                    "finish_reason": "prefill_done",
                    "prefix_hit_tokens": req.prefix_hit_tokens}
            if owns and handoff_to:
                body["handoff"] = self._ship_handoff(req, handoff_to)
            if req.trace_id:
                body["trace"] = req.trace_id
            return 200, body
        toks = [int(t) for t in req.output_tokens]
        body = {"tokens": toks[resume_from:] if resume_from else toks,
                "request_id": req.request_id,
                "finish_reason": req.finish_reason,
                "prefix_hit_tokens": req.prefix_hit_tokens}
        if resume_from:
            body["resume_from"] = int(resume_from)
            body["tokens_total"] = len(toks)
        if req.trace_id:
            body["trace"] = req.trace_id
        return 200, body

    def _stream_request(self, req: Request, deadline: float, *, owns: bool,
                        idem=None, entry=None, start: int = 0):
        """Streaming twin of :meth:`_await_request`: a generator of ndjson
        events the HTTP front relays as chunked transfer encoding.  Token
        chunks arrive as ``{"tokens": [...], "n": <cumulative>}`` the
        moment the lag-1 drain lands them in ``output_tokens`` (reading
        the list from this thread is safe: the engine thread only ever
        appends, and list reads are GIL-atomic); the terminal event is
        ``{"done": true, ...}`` with the buffered path's body fields, or
        an ``{"error": ..., "status": ...}`` event mirroring the status
        the buffered path would have returned (the transport already
        committed to 200 + chunked, so the code rides in the event — the
        router's relay turns ``requeued`` errors into a resume on another
        replica).  ``start`` is resume-from-token-N: the client already
        holds the first N tokens, so only the suffix is sent."""
        sent = max(0, int(start))
        last_steps, last_progress = self.steps, time.monotonic()
        while True:
            n = len(req.output_tokens)
            if n > sent:
                chunk = [int(t) for t in req.output_tokens[sent:n]]
                sent = n
                yield {"tokens": chunk, "n": sent}
                continue
            if req.done:
                break
            now = time.monotonic()
            if self.steps != last_steps:
                last_steps, last_progress = self.steps, now
            if self._loop_crashed:
                # same hand-back contract as _await_request: the stream
                # ends with a resumable error and the router re-dispatches
                # with resume_from = tokens already relayed
                if req.state == QUEUED and self.scheduler.cancel(req):
                    self._m_crash_requeues.inc()
                    self._idem_drop(idem, entry)
                    yield {"error": "request requeued: serving loop "
                                    "crashed before admission",
                           "requeued": True, "status": 503, "n": sent}
                    return
                if req.state in (PREFILLING, RUNNING):
                    self.abort(req)
                    self._m_crash_requeues.inc()
                    self._idem_drop(idem, entry)
                    yield {"error": "request requeued: serving loop "
                                    "crashed mid-request (aborted locally)",
                           "requeued": True, "status": 503, "n": sent}
                    return
            if req.state == QUEUED and (
                    self.scheduler.admission_paused
                    or (not self._loop_alive()
                        and now - last_progress > 1.0)):
                if self.scheduler.cancel(req):
                    self._idem_drop(idem, entry)
                    yield {"error": "request requeued: replica draining/"
                                    "stopped before admission",
                           "requeued": True, "status": 503, "n": sent}
                    return
            if now > deadline:
                if owns:
                    self.abort(req)
                yield {"error": "generation timed out"
                                + (" (request aborted; slot reclaimed)"
                                   if owns else ""),
                       "status": 504, "request_id": req.request_id,
                       "n": sent}
                return
            time.sleep(0.001)
        # the finish raced the last length check: flush the tail so the
        # stream is complete before the terminal event
        n = len(req.output_tokens)
        if n > sent:
            yield {"tokens": [int(t) for t in req.output_tokens[sent:n]],
                   "n": n}
            sent = n
        if req.finish_reason == "deadline":
            yield {"error": "service deadline expired before admission; "
                            "request cancelled",
                   "deadline_expired": True, "status": 504,
                   "request_id": req.request_id, "n": sent}
            return
        if req.finish_reason == "cancelled":
            self._idem_drop(idem, entry)
            yield {"error": "request cancelled before completion",
                   "requeued": True, "status": 503,
                   "request_id": req.request_id, "n": sent}
            return
        final = {"done": True, "request_id": req.request_id,
                 "finish_reason": req.finish_reason, "n": sent,
                 "prefix_hit_tokens": req.prefix_hit_tokens}
        if req.trace_id:
            final["trace"] = req.trace_id
        yield final

    # ------------------------------------------------------------------
    # KV-page handoff (disaggregated prefill/decode serving —
    # docs/RESILIENCE.md "Disaggregated serving")
    # ------------------------------------------------------------------
    def _capture_handoff(self, req: Request) -> None:
        """Engine-thread half of the prefill->decode handoff: read the
        request's FULL prompt pages device->host and stash (chunk tokens,
        page payload) pairs on the request — BEFORE release returns the
        pages to the pool (the payloads are host copies, so the release
        is safe)."""
        req.handoff = []
        page = self.pool.page
        resident = min(req.prefill_pos, req.prompt_len)
        full = resident // page
        if not full:
            return
        # ledger: handoff IO is its own category so prefill-role wall
        # clock splits into compute vs handoff in the run ledger
        self._goodput.push("handoff")
        try:
            pages = self.pool.owned(req.slot)[:full]
            for i, pid in enumerate(pages):
                toks = [int(t) for t in req.prompt[i * page:(i + 1) * page]]
                req.handoff.append((toks, self._fetch_page_host(int(pid))))
        finally:
            self._goodput.pop()

    def _ship_handoff(self, req: Request, target: str) -> dict:
        """HTTP-handler half (network IO off the engine thread): offer
        the captured chunk manifest to the decode replica at ``target``,
        ship ONLY the pages it reports missing (shared prefixes transfer
        once, fleet-wide), and account wire vs dense-twin bytes.
        Best-effort by contract: any failure returns an ``error`` field
        and the decode replica re-prefills the prompt itself (monolithic
        fallback) — a handoff can make a request faster, never wrong."""
        import json as _json
        import urllib.request

        from deepspeed_tpu.serving import handoff as hoff

        pages = req.handoff or []
        out = {"pages_offered": len(pages), "pages_shipped": 0,
               "wire_bytes": 0, "dense_bytes": 0}
        if not pages:
            return out

        def post(path, obj):
            data = _json.dumps(obj).encode()
            r = urllib.request.Request(
                target.rstrip("/") + path, data=data,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(r, timeout=30.0) as resp:
                return _json.loads(resp.read().decode())

        try:
            offer = post("/kv_offer", {"chunks": [c for c, _ in pages]})
            need = sorted(int(i) for i in offer.get("need", []))
            dense_item = np.dtype(self.engine.dtype).itemsize
            enc_pages = {}
            for i in need:
                if not 0 <= i < len(pages):
                    continue
                enc = hoff.encode_page(pages[i][1], wire=self._handoff_wire)
                enc_pages[str(i)] = enc
                out["wire_bytes"] += hoff.wire_nbytes(enc)
                out["dense_bytes"] += hoff.dense_twin_nbytes(
                    pages[i][1], dense_item)
            if enc_pages:
                adopt = post("/kv_adopt", {"chunks": [c for c, _ in pages],
                                           "pages": enc_pages})
                out["pages_shipped"] = len(enc_pages)
                out["pages_adopted"] = int(adopt.get("adopted", 0))
                self._m_handoff_pages.inc(len(enc_pages))
                self._m_handoff_bytes[self._handoff_wire].inc(
                    out["wire_bytes"])
                self._m_handoff_bytes["dense"].inc(out["dense_bytes"])
        except Exception as exc:  # noqa: BLE001 - degraded mode by contract
            out["error"] = repr(exc)
        return out

    def _http_kv_offer(self, payload: dict):
        """``POST /kv_offer`` (decode-role side): which of these chunks
        do I lack?  Engine-thread work — the trie walk touches LRU."""
        return self._enqueue_handoff("offer", payload)

    def _http_kv_adopt(self, payload: dict):
        """``POST /kv_adopt`` (decode-role side): decode + write the
        shipped pages and pin them into the local prefix trie."""
        return self._enqueue_handoff("adopt", payload)

    def _enqueue_handoff(self, kind: str, payload: dict,
                         timeout: float = 30.0):
        work = {"kind": kind, "payload": payload, "result": None,
                "done": threading.Event()}
        self._handoffs.append(work)
        if not work["done"].wait(timeout):
            return 503, {"error": f"kv_{kind} timed out waiting for the "
                                  "engine thread (serving loop running?)"}
        res = work["result"]
        if "error" in res:
            return 400, res
        return 200, res

    def _process_handoff(self, work: dict) -> None:
        """Engine-thread half of the /kv_offer and /kv_adopt handlers."""
        try:
            work["result"] = self._handoff_work(work["kind"],
                                                work["payload"])
        except Exception as exc:  # noqa: BLE001 - handler needs an answer
            work["result"] = {"error": repr(exc)}
        finally:
            work["done"].set()

    def _handoff_work(self, kind: str, payload: dict) -> dict:
        chunks = [tuple(int(t) for t in c)
                  for c in (payload.get("chunks") or [])]
        if self.prefix_cache is None:
            # no trie to adopt into: claim everything is held so the
            # sender ships nothing; decode-side admission re-prefills
            return {"need": []} if kind == "offer" else {"adopted": 0}
        if any(len(c) != self.pool.page for c in chunks):
            return {"error": "handoff chunks must be exactly "
                             f"page_tokens={self.pool.page} tokens long"}
        if kind == "offer":
            flat = np.asarray([t for c in chunks for t in c], np.int32)
            m = len(self.prefix_cache.match_nodes(flat))
            return {"need": list(range(m, len(chunks)))}
        from deepspeed_tpu.serving import handoff as hoff

        want = {k for k, v in self._cache.items() if v.ndim == 5}
        self._goodput.push("handoff")
        try:
            payloads = {}
            for key, enc in (payload.get("pages") or {}).items():
                planes = hoff.decode_page(enc)
                if set(planes) != want:
                    return {"error": "KV plane-layout mismatch between "
                                     "roles (quantize_kv_cache and the "
                                     "model config must match fleet-wide)"}
                payloads[int(key)] = {
                    k: np.ascontiguousarray(
                        np.asarray(v).astype(self._cache[k].dtype))
                    for k, v in planes.items()}

            def alloc():
                pid = self.pool.alloc_page()
                while pid is None:
                    if not self.prefix_cache.evict_lru():
                        return None
                    pid = self.pool.alloc_page()
                return pid

            adopted = self.prefix_cache.adopt_chunks(
                chunks, payloads, alloc, self._write_page)
            if adopted:
                self._m_adopted_pages.inc(adopted)
                self._page_gauges()
            return {"adopted": adopted}
        finally:
            self._goodput.pop()

    # ------------------------------------------------------------------
    # /profilez: on-demand device-true capture over scheduler iterations
    # (docs/OBSERVABILITY.md "Device truth")
    # ------------------------------------------------------------------
    def _profilez_begin(self) -> None:
        if self._pz is not None or self._pz_broker.pending is None:
            return
        if self._cprof is not None and self._cprof.active:
            # the operator wins the single global jax profiler session:
            # the abandoned continuous window simply reschedules at its
            # next cadence tick
            self._cprof.close()
        req = self._pz_broker.claim()
        if req is None:
            return
        import tempfile

        from deepspeed_tpu.profiling.trace import TraceCapture

        trace_dir = req.trace_dir or tempfile.mkdtemp(prefix="ds_profilez_")
        cap = TraceCapture(trace_dir, start_step=1, num_steps=req.steps,
                           perfetto=True)
        try:
            cap.maybe_start(1)       # the window opens before this step's
        except Exception as exc:     # dispatches (prefill + decode block)
            self._pz_broker.resolve(req, error=f"trace start failed: {exc}")
            return
        self._pz = [cap, req, 0]

    def _profilez_end(self) -> None:
        if self._pz is None:
            return
        cap, req, done = self._pz
        self._pz[2] = done = done + 1
        trace_dir = cap.after_step(done)
        if trace_dir is None:
            return
        self._pz = None
        from deepspeed_tpu.profiling import device_trace as dtr

        try:
            summary = dtr.analyze_capture(trace_dir, cap.num_steps,
                                          clock=cap.clock,
                                          trigger="profilez",
                                          engine="serving")
        except Exception as exc:
            self._pz_broker.resolve(
                req, error=f"trace post-processing failed: {exc}")
            return
        self._pz_broker.resolve(req, summary=summary)

    def _cprof_tick(self) -> None:
        """End-of-iteration hook of the continuous profiler: close a
        finished window (decompose + history commit run inline here,
        between scheduler iterations), else open the next one when due —
        a window opened now covers the NEXT iteration's dispatches.
        Never opens while an operator /profilez request is pending or
        claimed (jax has one global profiler session; the operator wins).
        One attribute load + one branch when off."""
        cp = self._cprof
        if cp is None:
            return
        if cp.active:
            cp.after_step(self.steps)
            return
        if self._pz is not None or self._pz_broker.pending is not None:
            return
        cp.maybe_begin(self.steps + 1)

    # ------------------------------------------------------------------
    # prefix caching (serving/prefix_cache.py)
    # ------------------------------------------------------------------
    def _admit_prefix(self, req: Request) -> None:
        """Match the request's prefix (prompt — plus produced tokens on a
        preempt-resume) against the cache at admission: fully-matched
        DEVICE-resident pages are ADOPTED into the slot's page table
        read-only (refcounted; the kernel's page-table indirection reads
        them with zero changes), HOST-resident chunks are PROMOTED first
        (a fresh page is allocated and the demoted payload streams back
        host->device — byte-identical KV, then re-pinned and shared), and
        ``prefill_pos`` jumps to the match frontier.  A partially-matched
        boundary page — the page the request will write its first
        computed token into — is COPY-ON-WRITTEN: a private page is
        allocated and the cached KV lands in it (one compiled device page
        copy, or a host->device write when the boundary chunk lives in
        the host tier), so the shared original is never written.  At
        least one prefix token is always left to compute (the final
        chunk's logits feed first-token sampling)."""
        prefix = req.prefix
        n = req.prefix_len
        page = self.pool.page
        nodes = self.prefix_cache.match_nodes(prefix)
        cap = n - 1
        want_full = min(len(nodes), cap // page)
        adopted = 0
        for node in nodes[:want_full]:
            pid = node.page           # read LIVE per iteration: an earlier
            if pid == -2:             # promotion's eviction pressure may
                break                 # have demoted (-1) or pruned (-2,
            if pid < 0:               # tombstone) nodes in this snapshot
                pid = self._promote_node(node)
                if pid is None:       # pool/store pressure: stop here
                    break
            self.pool.append_shared(req.slot, pid)
            adopted += 1
        matched = adopted * page
        r = cap - matched if (adopted == want_full
                              and want_full < len(nodes)) else 0
        if r:
            # boundary-page COW: allocate the private copy now (under
            # light pressure, evict/demote LRU cached pages; if the pool
            # still has nothing, fall back to the page-aligned frontier
            # and recompute the boundary page instead of preempting
            # anyone at admission time)
            boundary = nodes[want_full]
            ok = True
            while not self.pool.ensure(req.slot, matched + 1):
                if not self.prefix_cache.evict_lru():
                    ok = False
                    break
            if ok:
                dst = int(self.pool.page_table[req.slot, adopted])
                if boundary.page >= 0:
                    # even if the eviction loop above just unpinned the
                    # source and handed it back as ``dst``, the copy stays
                    # correct: a freed page's KV is intact until
                    # reallocated, and dst==src copies in place.  (With a
                    # host tier the same race instead demotes the
                    # boundary, which the branch below serves.)
                    self._cache = self._cow_fn()(
                        self._cache, jnp.asarray(dst, jnp.int32),
                        jnp.asarray(boundary.page, jnp.int32))
                    matched += r
                else:
                    payload = self.prefix_cache.host_payload(boundary)
                    if payload is not None:
                        # boundary lives in the host tier: stream it into
                        # the slot's PRIVATE page (the node itself stays
                        # host-resident for future matches)
                        self._write_page(dst, payload)
                        self.host_store.m_promote.inc()
                        matched += r
        if matched <= 0:          # nothing usable survived the pressure
            self._m_prefix_miss.inc(n)
            return
        req.prefill_pos = matched
        req.prefix_hit_tokens += matched
        self._m_prefix_hit.inc(matched)
        self._m_prefix_miss.inc(n - matched)
        # mirror the frontier onto host + device pos: the decode block's
        # parked junk write for this row must land AT the frontier (junk
        # page or the private COW page, both overwritten/never-read
        # before any query attends them) — NEVER inside a shared page
        self._pos[req.slot] = matched
        self._pos_dev = self._setpos_fn(
            self._pos_dev, jnp.asarray(req.slot, jnp.int32),
            jnp.asarray(matched, jnp.int32))
        self._page_gauges()
        self._tracer.span(req.request_id, "prefix_hit", req.t_admit,
                          req.t_admit, matched)

    def _cow_fn(self):
        """One compiled device-side page copy: every 5-dim cache array
        (K/V payloads and, quantized, their scales) copies physical page
        ``src`` over page ``dst``; scalars pass through."""
        if self._cow_copy is None:
            self._m_compiles.inc()

            @functools.partial(jax.jit, donate_argnums=(0,))
            def cow(cache, dst, src):
                return {k: (v.at[:, dst].set(v[:, src]) if v.ndim == 5
                            else v) for k, v in cache.items()}

            self._cow_copy = cow
        return self._cow_copy

    # -- KV host tier (serving/host_tier.py): demote/promote page IO ----
    def _fetch_page_host(self, page: int):
        """Device->host payload of one physical page (every 5-dim cache
        plane) — the demote reader the prefix cache calls from
        ``evict_lru`` when the host tier is attached."""
        return {k: np.asarray(v[:, page])
                for k, v in self._cache.items() if v.ndim == 5}

    def _host_write_fn(self):
        """One compiled host->device page write: the demoted payload
        (K/V planes and, quantized, their scales) lands in physical page
        ``dst``.  The payload is NOT donated — only the cache is (the
        ``_cow_fn`` pattern), so the numpy-aliased host arrays never meet
        a donated argument."""
        if self._host_write is None:
            self._m_compiles.inc()

            @functools.partial(jax.jit, donate_argnums=(0,))
            def wr(cache, dst, payload):
                return {k: (v.at[:, dst].set(payload[k]) if k in payload
                            else v) for k, v in cache.items()}

            self._host_write = wr
        return self._host_write

    def _write_page(self, dst: int, payload) -> None:
        self._cache = self._host_write_fn()(
            self._cache, jnp.asarray(dst, jnp.int32), payload)

    def _promote_node(self, node) -> Optional[int]:
        """Promote one host-resident chunk back to the device tier: pop a
        free page (demoting other LRU cached pages under pressure —
        never this one: a host node is not in the device LRU list, and
        the whole match path was just touched MRU), stream the payload
        in, and re-pin the node onto it.  None = could not promote (pool
        dry with nothing evictable, or the entry aged out of the bounded
        store) — the caller caps the match at the frontier reached."""
        payload = self.prefix_cache.host_payload(node)
        if payload is None:
            return None
        dst = self.pool.alloc_page()
        while dst is None:
            if not self.prefix_cache.evict_lru():
                return None
            if node.host_key is None or node.page != -1:
                # the eviction's demote overflowed the bounded store and
                # pushed out THIS node's entry (deterministic at
                # kv_host_tier_pages=1): the node was pruned from the
                # trie — promoting it would pin an orphan page
                return None
            dst = self.pool.alloc_page()
        self._write_page(dst, payload)
        self.prefix_cache.promote(node, dst)
        self.host_store.m_promote.inc()
        return dst

    # ------------------------------------------------------------------
    # paged-pool allocation + preemption
    # ------------------------------------------------------------------
    @_in_phase("ds_serve_pages")
    def _ensure_pages(self, req: Request, tokens: int) -> bool:
        """Allocate pages so ``req``'s slot covers ``tokens`` tokens.
        Under pool pressure, first drain any deferred finish events (a
        pending EOS release may free pages for free), then evict
        refcount-0 prefix-cache pages (LRU — cached history is
        reclaimed BEFORE any live request suffers), then fetch the first
        tokens still owed (the last thing that can free pages without a
        victim; it waits on the chip with nothing queued behind, and no
        victim is chosen with a first token owed), and only then preempt
        the YOUNGEST-admitted occupant (LIFO — possibly ``req`` itself,
        in which case False is returned and the caller skips this
        dispatch) and requeue it at the queue head.  The oldest request
        always keeps its pages, so progress is guaranteed and the pool
        cannot livelock."""
        while not self.pool.ensure(req.slot, tokens):
            if self._outstanding:
                self._flush_outstanding()
                continue
            if self.prefix_cache is not None and self.prefix_cache.evict_lru():
                continue
            if self._owed:
                self._settle_first_tokens()
                continue
            victim = self._youngest_victim()
            if victim is None:
                # unreachable by construction: the pool holds >= one full
                # slot window, and a lone occupant owns every page it needs
                raise RuntimeError(
                    f"KV page pool exhausted with no preemptible slot "
                    f"(slot {req.slot} needs {tokens} tokens)")
            self._preempt(victim)
            if victim is req:
                return False
        self._page_gauges()
        return True

    def _page_gauges(self) -> None:
        self._m_pages_used.set(self.pool.pages_used)
        self._m_pages_free.set(self.pool.pages_free)
        self.kind.page_gauges(self.pool)

    def _youngest_victim(self) -> Optional[Request]:
        cands = self.scheduler.running() + self.scheduler.prefilling()
        return max(cands, key=lambda r: r.t_admit, default=None)

    def _preempt(self, victim: Request) -> None:
        """Reclaim every page the victim holds and send it back to the
        queue head.  Its produced tokens are materialized first (they
        become part of the resume prefix: re-prefilling prompt + outputs
        rebuilds the identical KV state, so greedy continuations are
        token-identical across the preempt-resume cycle)."""
        self._flush_outstanding()        # retire in-flight blocks first
        if victim.state == RUNNING:
            self._materialize(victim)
        b = victim.slot
        self._active[b] = False
        self._pos[b] = 0
        self._limit[b] = 0
        self._eos[b] = -1
        self._pos_dev, self._act_dev = self._park_fn(
            self._pos_dev, self._act_dev, jnp.asarray(b, jnp.int32))
        # the victim's requeue-front resume (and anyone sharing the prompt)
        # re-prefills through the cache, so LIFO preemption costs the
        # boundary/output tokens, not the whole prompt.  Under the very
        # pressure that triggered this preempt these pages are the NEWEST
        # LRU entries — the requester evicts older history first and takes
        # these only as a last resort.
        self._cache_prompt_pages(victim)
        freed = self.pool.release(b)
        victim.preemptions += 1
        self.scheduler.requeue_front(victim)   # records the preempt edge
        if self._flight.enabled:
            self._flight.record("serve_preempt", rid=victim.request_id,
                                pages_freed=freed,
                                tokens_reclaimed=freed * self.pool.page,
                                trace=victim.trace_id)
        self._m_preempted.inc()
        self._page_gauges()

    # ------------------------------------------------------------------
    def _prefill_turn(self, missed: bool) -> None:
        """One iteration a request spent in PREFILLING, with a chunk or
        without (``missed``: passed over, or refused pages)."""
        self._m_prefill_turns.inc()
        if missed:
            self._m_prefill_turns_missed.inc()

    def _prefill_one_chunk(self, req: Request) -> None:
        """Enqueue ``req``'s next chunk program, unless it is refused pages
        (``prefill_pos`` then does not advance: ``_step_inner`` reads that,
        not a return value, which the benchmark's wrapper of this method,
        ``benchmarks/lib/serve_taps.py``, would drop, and keeps the turns'
        count)."""
        t0 = time.perf_counter()
        if not req.t_first_chunk:
            req.t_first_chunk = t0       # its turn came; pages not yet asked
        slot, off = req.slot, req.prefill_pos
        prefix = req.prefix              # prompt (+ outputs after a resume)
        S = req.prefix_len
        c = min(self.prefill_chunk, S - off)
        if not self._ensure_pages(req, off + c):
            return                       # self-preempted: resumes later
        last_chunk = off + c == S
        wake = False
        if last_chunk and not req.prefill_only:
            # The position bound is ABSOLUTE, so it is invariant across
            # preempt-resume (prefix grows by exactly the tokens produced).
            # limit <= S: the cache budget is already exhausted by the
            # prefix — the prefill-sampled token is the only one left to
            # emit.  The bound is the LOGICAL max_out_tokens, not the
            # page/block-rounded physical depth, so a request emits exactly
            # what generate() would
            req_bound = req.prompt_len + req.max_new_tokens - 1
            limit = min(req_bound, self.max_out - 1)
            req.limit_reason = ("length" if limit == req_bound
                                else "cache_budget")
            # the row decodes unless the host already knows that this token
            # is the request's last (the device adds: unless it is the EOS)
            wake = (len(req.output_tokens) + 1 < req.max_new_tokens
                    and limit > S)
        self._launch_seq += 1
        seq = self._launch_seq
        with self._phase("ds_serve_prefill_dispatch", seq=seq,
                         request_id=req.request_id, last=int(last_chunk)):
            cb = self.chunk_bucket(c, off)
            chunk = np.zeros((1, cb), np.int32)
            chunk[0, :c] = prefix[off:off + c]
            self._rng, srng = jax.random.split(self._rng)
            # one transfer: slot, chunk offset, index of its last real
            # token, wake flag, EOS id (-1: none)
            meta = jnp.asarray(
                [slot, off, c - 1, int(wake), req.eos_token_id], jnp.int32)
            # the table row is a SNAPSHOT: the allocator rewrites the row
            # in place (preempt, release, adopt) while this program may
            # still be queued, and ``jnp.asarray`` of host memory need not
            # copy it (the CPU client aliases a 64-byte aligned array)
            tok_dev, self._cache, carries = self._prefill_fn(cb)(
                self.engine._params, self._cache,
                (self._last_dev, self._pos_dev, self._act_dev),
                jnp.asarray(self.pool.page_table[slot].copy()),
                jnp.asarray(chunk), meta, srng)
            self._last_dev, self._pos_dev, self._act_dev = carries
            req.prefill_pos += c
            self._tracer.span(req.request_id, "prefill_chunk", t0,
                              time.perf_counter(), c, seq=seq)
            self._m_prefill_chunks.inc()
            self._m_loop_passes.inc(self.module.config.total_ut_steps)
            self._m_prefill_toks.inc(c)
            self._m_prefill_pad_rows.inc(cb - c)
            self.kind.count_chunk(self.pool, self._cache, off, c, cb)
            # parked rows write junk at their own pos; keeping pos =
            # prefill progress (host view here, device carry inside the
            # chunk's program) means the NEXT chunk overwrites that row
            # before any query attends it
            self._pos[slot] = req.prefill_pos
        if not last_chunk:
            return
        req.t_last_chunk = time.perf_counter()   # the dispatch range's end
        # prefix fully resident: the next token came out of the final
        # chunk's program, which also woke the slot.  Its VALUE is only
        # fetched when scheduling depends on it (EOS) or a client waits for
        # it (stream), and then after the decode block is queued behind the
        # chunk — otherwise it stays on device and the pipeline keeps
        # flowing.
        if req.prefill_only:
            # prefill-role finish (disaggregated serving): the prompt KV
            # is resident — capture the full prompt pages for the
            # prefill->decode handoff and finish WITHOUT decoding.  The
            # decode replica owns sampling end to end (even token 1 is
            # produced there, from byte-identical KV), so the response
            # cannot depend on which role computed the prefix.
            self._capture_handoff(req)
            self._release(req, "prefill_done")
            return
        if req.eos_token_id >= 0 or req.stream or not wake:
            # streaming requests are owed the value too: the first token IS
            # the first chunk on the wire — deferring it would hold TTFT
            # hostage to the first decode block's drain
            tok_dev.copy_to_host_async()
            self._owed.append((req, tok_dev))
        else:
            req.pending_blocks.append(("tok", tok_dev))
        if wake:
            req.state = RUNNING
            self._drained_pos[slot] = S
            self._limit[slot] = limit
            self._eos[slot] = req.eos_token_id
            self._active[slot] = True
        elif S >= self.cache_len:
            # the prefix fills the slot's window: no row is left for a
            # parked slot to write its junk on while a block runs
            self._settle_first_tokens()

    def _settle_first_tokens(self, overlapped: bool = False) -> None:
        """Fetch the first tokens this iteration's last chunks owe, in
        dispatch order, and make them visible.  The copies were started at
        dispatch, so each value reaches the host when its chunk ends;
        ``overlapped`` says a decode block is already queued behind the
        chunks.  Whether the row decodes was decided on the device (a first
        token that is the EOS never went active) or known before dispatch
        (its last token); the host learns it here and releases the request
        as ``_drain_one`` releases a row whose finish it could not predict:
        its view only over-allocated pages for one block."""
        while self._owed:
            req, tok_dev = self._owed.popleft()
            with self._phase("ds_serve_first_token_fetch"):
                first = int(tok_dev)
            self._first_token_on_host(req)
            req.output_tokens.append(first)
            self._m_first_tokens.inc()
            if overlapped:
                self._m_first_overlapped.inc()
            if req.eos_token_id >= 0 and first == req.eos_token_id:
                self._release(req, "eos")
            elif len(req.output_tokens) >= req.max_new_tokens:
                self._release(req, "length")
            elif req.state != RUNNING:       # never woke: limit <= S
                self._release(req, req.limit_reason)

    def _first_token_on_host(self, req: Request) -> None:
        """The prefill-sampled token's VALUE has just reached the host:
        the one place ``t_first_token``, ``ds_serve_ttft_seconds`` and the
        tracer's prefill -> decode edge are stamped, on every path (right
        after ``int(tok_dev)`` for a stream / EOS / last-token request; at
        the deferred fetch, which is the finish, for the rest).  After a
        preempt-resume the re-prefill's token passes here again: the edge
        is recorded, the first-token stamp is not taken twice."""
        t = time.perf_counter()
        if not req.t_first_token:
            req.t_first_token = t
            self._m_ttft.record(t - req.t_submit)
        self._tracer.decode_start(req.request_id, t)

    def chunk_bucket(self, c: int, off: int) -> int:
        """The bucket a chunk of ``c`` tokens at ``off`` runs in, the one
        place the serve engine's chunk programs are sized: the next power of
        two from the cache kind's floor up (``kind.chunk_rows``: the rows
        its chunk attention pads a bucket to anyway; never more than
        ``prefill_chunk``, and never under the plain kind's 8, the fewest
        rows a chunk program was ever built for), capped at what is left of
        the slot's window."""
        lo = max(FullPages.chunk_rows,
                 min(self.kind.chunk_rows, self.prefill_chunk))
        return pow2_bucket(c, lo=lo, cap=self.cache_len - off)

    def _prefill_fn(self, cb: int):
        """Per-slot chunked prefill, compiled once per bucket from the
        kind's floor up (:meth:`chunk_bucket`):
        ``(params, cache, (last, pos, active), page-table row, chunk [1, cb],
        meta, rng) -> (token, cache, (last, pos, active))``, cache and
        carries donated.

        The slot's rows come out of the pool as the contiguous view the
        model's forward takes (``self.kind.view``: its pages sliced out one
        by one, and whatever else the slot carries), the standard (batch-1)
        prefill forward runs at the chunk's absolute offset, the view goes
        back in place in the donated pool (``self.kind.write_back``), and
        the next token is sampled from the last real position's logits — it
        stays a DEVICE scalar so admission never syncs the host, and the
        decode hot path never builds the view.

        The program also updates the decode block's carries for its slot:
        ``last`` takes the sampled token, ``pos`` the prefill frontier (so
        a parked row's junk write lands where the next chunk or decode step
        overwrites it), and ``active`` is set where ``meta`` says the row
        decodes and the token is not its EOS — the wake costs no dispatch
        of its own and waits for no value on the host."""
        if cb in self._prefill_fns:
            return self._prefill_fns[cb]
        self._m_compiles.inc()
        model, kind = self.module, self.kind
        do_sample, temperature, top_k, top_p = self._sample

        @functools.partial(jax.jit, donate_argnums=(1, 2))
        def prefill(params, cache, carries, pt_row, chunk, meta, srng):
            slot, start, last_idx, wake, eos = meta
            real = ({"valid_len": last_idx + 1} if kind.takes_valid_len
                    else {})
            sub = kind.view(cache, pt_row, slot, start, cb)
            logits, sub = forward_with_cache(model, params, chunk, sub, start,
                                             **real)
            out = kind.write_back(cache, sub, pt_row, slot, start, cb)
            logits = next_token_logits(model.config, jax.lax.dynamic_index_in_dim(
                logits, last_idx, axis=1, keepdims=False))
            tok = sample_token(logits, srng, temperature=temperature,
                               top_k=top_k, top_p=top_p,
                               do_sample=do_sample)[0].astype(jnp.int32)
            last, pos, active = carries
            return tok, out, (
                last.at[slot].set(tok),
                pos.at[slot].set(start + last_idx + 1),
                active.at[slot].set((wake != 0) & (tok != eos)))

        self._prefill_fns[cb] = prefill
        return prefill

    # ------------------------------------------------------------------
    def _decode_block(self) -> None:
        """Dispatch one compiled decode block and schedule its outputs.

        The device carries pos/active itself (EOS folded into the compiled
        step), so dispatches never wait on token values:

        - no-EOS rows: a row emits exactly min(K, limit - pos) tokens —
          the host appends a refcounted (block, n) ref and releases the
          request the moment position arithmetic says it finished (the
          deferred fetch at finish overlaps already-queued blocks);
        - EOS rows: the host registers the request as a DRAIN PARTICIPANT
          of this block and fetches the block's (toks, valid) pair only
          after the NEXT block is dispatched (lag 1) — the fetch RTT
          overlaps live device work, and the valid mask tells exactly how
          many tokens each row emitted before its EOS stopped it.

        Right behind the enqueue the host fetches the first tokens this
        iteration's last chunks owe (``_settle_first_tokens``): the chunks
        are ahead of the block in the chip's queue, so the wait for their
        values is covered by the block."""
        t0 = time.perf_counter()
        running = self.scheduler.running()
        for req in running:
            if req.state != RUNNING:     # preempted by an earlier ensure
                continue
            b = req.slot
            n = int(min(self._K, self._limit[b] - self._pos[b]))
            if n > 0:
                # the block writes rows [pos, pos+n); EOS rows may stop
                # early on device — the host view only over-allocates.
                # A False return = req itself was the youngest and
                # self-preempted; the filter below drops it.
                self._ensure_pages(req, int(self._pos[b]) + n)
        # a preemption above may have demoted someone mid-list
        running = [r for r in running if r.state == RUNNING]
        if not self._active.any():
            self._settle_first_tokens()
            return
        self._launch_seq += 1
        seq = self._launch_seq
        with self._phase("ds_serve_decode_dispatch", seq=seq):
            (toks, valid, self._last_dev, self._pos_dev, self._act_dev,
             self._cache, self._rng, moe) = self._block()(
                self._loop_params(), self._cache, self._last_dev,
                self._pos_dev, self._act_dev,
                # snapshots, as the chunk program's table row is: the host
                # writes all three in place while the block is queued
                jnp.asarray(self._limit.copy()),
                jnp.asarray(self._eos.copy()), self._rng,
                jnp.asarray(self.pool.page_table.copy()))
        t1 = time.perf_counter()
        idx = self._next_block
        self._next_block += 1
        self._m_row_slots.inc(self.num_slots * self._K)
        self._m_loop_passes.inc(self._K * self.module.config.total_ut_steps)
        refs = 0
        drainers: List[Request] = []
        for req in running:
            b = req.slot
            n = int(min(self._K, self._limit[b] - self._pos[b]))
            self.kind.count_rows(int(self._pos[b]), n)
            self._pos[b] += n
            # one span per participating row: the block's host dispatch
            # window with this request's scheduled token count
            self._tracer.span(req.request_id, "decode_block", t0, t1, n,
                              seq=seq)
            self._m_decode_toks.inc(n)
            self._goodput.add_tokens(n)
            refs += 1
            if req.eos_token_id < 0 and not req.stream:
                req.pending_blocks.append((idx, n))
            else:
                # EOS rows need the drain for slot turnover; STREAMING
                # rows ride the same lag-1 drain so their tokens land in
                # output_tokens incrementally — the HTTP stream generator
                # tails the list and ships each block as it drains
                drainers.append(req)
            if self._pos[b] >= self._limit[b]:
                # stop scheduling the row; EOS rows RELEASE at their drain
                # (token values decide), no-EOS rows release below
                self._active[b] = False
        if refs:
            self._blocks[idx] = toks
            self._block_refs[idx] = refs
            if drainers:
                self._block_valid[idx] = valid
            if moe is not None and self._registry.enabled:
                self._block_moe[idx] = moe
        if drainers:
            self._outstanding.append((idx, drainers))
        # the one wait of an iteration that nothing earlier covers comes
        # here, with the block queued behind the chunks it waits for
        self._settle_first_tokens(overlapped=True)
        while len(self._outstanding) > self._drain_lag:
            self._drain_one()
        for req in running:              # finish AFTER refs registered
            if (req.eos_token_id < 0 and not req.stream
                    and not self._active[req.slot]
                    and req.state == RUNNING):
                self._materialize(req)
                self._release(req, req.limit_reason)

    # -- deferred finish-event drain -----------------------------------
    def _fetch_block(self, idx: int):
        """Device -> host fetch of one block's (toks, valid) arrays,
        memoized.  All deferred output flows through here, which is what
        the sync-free tests instrument."""
        entry = self._block_np.get(idx)
        if entry is None:
            with self._phase("ds_serve_block_fetch"):
                toks = np.asarray(self._blocks[idx])  # dslint: disable=DSL002 -- THE deliberate deferred fetch: drains run >=1 block behind dispatch (lag 1), finish-fetches overlap queued blocks; pinned structurally in test_paged_kv
                valid = (np.asarray(self._block_valid[idx])  # dslint: disable=DSL002 -- same deferred-fetch seam (valid mask rides the same memoized entry)
                         if idx in self._block_valid else None)
                moe = self._block_moe.pop(idx, None)
                if moe is not None:
                    moe = [np.asarray(a) for a in moe]  # dslint: disable=DSL002 -- rides the block's own deferred fetch, registry on only
                    if self.module.config.is_moe:
                        self._count_row_tiles(
                            int(moe.pop()) if self._moe_row_tile else None)
                    self._count_moe(*self.kind.count_block(moe))
            entry = self._block_np[idx] = (toks, valid)
        return entry

    def _count_moe(self, per_expert, hits, max_load, offered=None,
                   group_kept=0) -> None:
        """One decode block's routing (``decode_step``'s ``moe_live``
        result, summed over the block's steps) into ``ds_serve_moe_*``.
        ``offered`` (a chip's share of the experts, models/afmoe.py): the
        assignments the live rows made, of which ``per_expert`` holds those
        to experts held here.  ``group_kept``: a group-limited router's
        fifth count."""
        cfg = self.module.config
        m = self._m_moe
        local = int(per_expert.sum())
        m["ds_serve_moe_assignments_total"].inc(
            local if offered is None else int(offered))
        m["ds_serve_moe_local_assignments_total"].inc(local)
        m["ds_serve_moe_expert_hits_total"].inc(int(hits))
        m["ds_serve_moe_expert_slots_total"].inc(
            cfg.num_experts * cfg.num_expert_layers * self._K)
        m["ds_serve_moe_max_load_total"].inc(int(max_load))
        m["ds_serve_moe_group_kept_total"].inc(int(group_kept))

    def _count_row_tiles(self, worked: Optional[int]) -> None:
        """One decode block's row tiles: ``worked``, those the program
        counted over its steps where ``fused_moe_mlp`` cuts its passes to
        the live rows' tiles (one call's; every expert layer's call works
        the same), None where a call is one pass over one tile."""
        tiles = self._K * (self.num_slots // self._moe_row_tile
                           if self._moe_row_tile else 1)
        layers = self.module.config.num_expert_layers
        self._m_moe["ds_serve_moe_row_tiles_total"].inc(layers * tiles)
        self._m_moe["ds_serve_moe_row_tiles_worked_total"].inc(
            layers * (tiles if worked is None else worked))

    def _unref(self, idx: int) -> None:
        self._block_refs[idx] -= 1
        if self._block_refs[idx] == 0:
            for d in (self._blocks, self._block_valid, self._block_np,
                      self._block_refs, self._block_moe):
                d.pop(idx, None)

    def _drain_one(self) -> None:
        """Retire the oldest outstanding block: append each EOS
        participant's share (its valid prefix) and release rows whose
        finish the host could not predict."""
        idx, drainers = self._outstanding.popleft()
        t0 = time.perf_counter()
        toks, valid = self._fetch_block(idx)
        t1 = time.perf_counter()
        for req in drainers:
            b = req.slot
            if req.state != RUNNING:     # released at an earlier drain
                self._unref(idx)         # (its later blocks carry 0 tokens)
                continue
            n = int(valid[:, b].sum())   # valid is monotone within a block
            # the deferred (toks, valid) fetch this EOS participant rode —
            # memoized, so only the first drainer of a block pays the RTT
            self._tracer.span(req.request_id, "drain_fetch", t0, t1, n)
            req.output_tokens.extend(int(t) for t in toks[:n, b])
            self._drained_pos[b] += n
            self._unref(idx)
            if (n and req.eos_token_id >= 0
                    and req.output_tokens[-1] == req.eos_token_id):
                self._release(req, "eos")
            elif len(req.output_tokens) >= req.max_new_tokens:
                self._release(req, "length")
            elif self._drained_pos[b] >= self._limit[b]:
                self._release(req, req.limit_reason)

    def _flush_outstanding(self) -> None:
        while self._outstanding:
            self._drain_one()

    @_in_phase("ds_serve_release")
    def _release(self, req: Request, reason: str) -> None:
        """Finish the request, park its slot at depth 0 (the parked row's
        junk writes land on row 0 / the junk page, overwritten or never
        read before any query can see them, and the slot's stale depth no
        longer inflates the flash-decode loop bound), and return its pages
        to the pool."""
        b = req.slot
        self._active[b] = False
        self._pos[b] = 0
        self._pos_dev, self._act_dev = self._park_fn(
            self._pos_dev, self._act_dev, jnp.asarray(b, jnp.int32))
        self._cache_prompt_pages(req)
        self.pool.release(b)
        self._page_gauges()
        req.finish_reason = reason
        n = len(req.output_tokens)
        # a pace only where tokens reached the host as they were made: a
        # non-streaming request without EOS fetched all of its tokens at
        # one instant, its finish (t_first_token is stamped there too)
        if (n > 1 and req.t_first_token
                and (req.stream or req.eos_token_id >= 0)):
            self._m_tpot.record((time.perf_counter() - req.t_first_token)
                                / (n - 1))
        self.scheduler.finish(req)

    def _cache_prompt_pages(self, req: Request) -> None:
        """Before a release decrefs them, the request's FULL prompt pages
        (every row a prompt token: the boundary page mixes in generated
        tokens and is not cacheable) go into the prefix cache; new pages are
        pinned and survive, already-cached chunks keep their existing page.
        Bounded by the prefill frontier: an ABORTED or preempted mid-prefill
        request must not cache pages it never computed."""
        if self.prefix_cache is None:
            return
        full = min(req.prefill_pos, req.prompt_len) // self.pool.page
        if full:
            self.prefix_cache.insert(req.prompt,
                                     self.pool.owned(req.slot)[:full])

    def _materialize(self, req: Request) -> None:
        """Fetch this request's deferred tokens (the prefill-sampled first
        token + its (block, n) refs) into output_tokens, in order.  Blocks
        are refcounted: a device block is dropped once every consumer has
        drained it.  Only no-EOS requests carry refs (EOS requests drain);
        a ref fetched here may sync on the just-dispatched block — that is
        the existing fetch-at-finish, by which time later blocks are
        already queued behind it."""
        for entry in req.pending_blocks:
            if entry[0] == "tok":                 # prefill-sampled token
                with self._phase("ds_serve_block_fetch"):
                    first = int(entry[1])
                self._first_token_on_host(req)
                req.output_tokens.append(first)
                continue
            idx, n = entry
            toks, _ = self._fetch_block(idx)
            req.output_tokens.extend(int(t) for t in toks[:n, req.slot])
            self._unref(idx)
        req.pending_blocks.clear()

    def _loop_params(self):
        return (self.engine._dparams if self.engine._dparams is not None
                else self.engine._params)

    # ------------------------------------------------------------------
    def _step_fn(self):
        """One decode micro-step at per-row positions: (params, tokens
        [B, 1], cache, pos [B], page_table, live [B]) -> (logits
        [B, V], cache, routing counts of the live rows | None: a
        mixture-of-experts model on the fused path, ``decode_step``, whose
        attention kernels also visit the live rows only)."""
        model = self.module
        if self.engine._dparams is not None:
            from deepspeed_tpu.models.fused_decode import decode_step

            def fused(params, tok, cache, pos, page_table, live):
                return decode_step(model.config, params, tok, cache, pos,
                                   page_table=page_table, moe_live=live)
            return fused

        def unfused(params, tok, cache, pos, page_table, live):
            logits, cache = forward_with_cache(model, params, tok, cache,
                                               pos, page_table=page_table)
            return logits[:, -1], cache, None
        return unfused

    def _block(self):
        """ONE compiled program decoding ``decode_block_tokens`` tokens for
        all slots: lax.scan of per-row-position decode micro-steps with the
        active mask AND positions as device carries (EOS termination folded
        into the step — a row goes inactive the step its EOS is sampled,
        with no host involvement).  Parked rows keep static shapes alive at
        their frozen pos; the host reads (toks, valid) lazily.  The last
        result is the block's routing counts (``SERVE_MOE_COUNTERS``; None
        for a dense model): always computed, fetched only when wanted."""
        if self._block_fn is not None:
            return self._block_fn
        self._m_compiles.inc()
        step_fn = self._step_fn()
        do_sample, temperature, top_k, top_p = self._sample
        K = self._K
        moe0 = None
        cfg = self.module.config
        if self.engine._dparams is not None and (cfg.is_moe or cfg.is_afmoe):
            # a layer_types model's fused path always returns its counts (a
            # dense one's routing counts are empty; the state's ride behind)
            from deepspeed_tpu.models.fused_decode import moe_counts_zero
            moe0 = moe_counts_zero(cfg)
        if moe0 is not None and cfg.is_moe:
            from deepspeed_tpu.ops.pallas.decode import (moe_expert_block,
                                                         moe_row_tile,
                                                         moe_row_tiles_worked)
            ex = self.engine._dparams["experts"]
            (D, F), item = ex["w_up"].shape[-2:], ex["w_up"].dtype.itemsize
            self._m_moe_block.set(100.0 / F * moe_expert_block(
                self.num_slots, D, F, matrices=len(ex), itemsize=item,
                row_itemsize=item)[0])
            # the kernel's row cut is static a program too: where it is
            # taken (by the kernel: its jnp reference runs every row), the
            # tiles a step's call works ride behind the routing counts
            from deepspeed_tpu.ops.pallas.common import default_impl
            if default_impl() != "xla":
                self._moe_row_tile = moe_row_tile(self.num_slots, item)
            if self._moe_row_tile:
                moe0 = moe0 + (jnp.zeros((), jnp.int32),)
        row_tile = self._moe_row_tile

        def body(params, cache, last, pos, active, limit, eos, rng,
                 page_table):
            def sub(carry, _):
                cache, last, pos, act, rng, moe = carry
                valid = act & (pos < limit)
                rng, srng = jax.random.split(rng)
                logits, cache, routed = step_fn(params, last[:, None], cache,
                                                pos, page_table, valid)
                if row_tile:
                    routed = routed + (moe_row_tiles_worked(valid, row_tile),)
                moe = jax.tree.map(jnp.add, moe, routed)
                logits = next_token_logits(self.module.config, logits)
                nxt = sample_token(logits, srng, temperature=temperature,
                                   top_k=top_k, top_p=top_p,
                                   do_sample=do_sample).astype(last.dtype)
                nxt = jnp.where(valid, nxt, last)
                hit = valid & (eos >= 0) & (nxt == eos)
                act = act & ~hit
                pos = pos + valid.astype(pos.dtype)
                return (cache, nxt, pos, act, rng, moe), (nxt, valid)

            (cache, last, pos, act, rng, moe), (toks, valid) = jax.lax.scan(
                sub, (cache, last, pos, active, rng, moe0), None, length=K)
            return toks, valid, last, pos, act, cache, rng, moe

        self._block_fn = jax.jit(body, donate_argnums=(1, 2, 3, 4))
        return self._block_fn

    # ------------------------------------------------------------------
    def close(self) -> None:
        """Release host-side resources: stops the background serving loop
        (if :meth:`start_loop` started one) and the attached metrics HTTP
        server (if ``init_serving(metrics_port=...)`` started one).  The
        device-side state (cache, programs) is freed by GC as usual; a
        dropped engine's server is also stopped by a GC finalizer, so
        ``close()`` is for deterministic shutdown, not a leak guard."""
        self.stop_loop()
        if self._cprof is not None:
            self._cprof.close()
        if self.metrics_server is not None:
            self.metrics_server.stop()
            self.metrics_server = None

    @property
    def config(self):
        return self._config
