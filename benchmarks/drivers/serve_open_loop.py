"""Open-loop serving: offer the cell's requests on their schedule whatever
the server does, and time every token from outside.

One thread: submit what is due, ``ServingEngine.step()``, read which
tokens became visible in ``Request.output_tokens``, stamp them with
``time.perf_counter()``.  (The shape of ``bench.py``'s ``run_continuous``
loop; the clock is the benchmark's, because the program stamps its own
first-token time at dispatch.)  Requests go in with ``stream=True``, the
engine's streaming path: otherwise tokens stay on the device until the
request ends and no first token exists for a client.

A request is timed from the moment it was DUE, so a stall of the server or
of this loop counts against every request it delays.
"""

from __future__ import annotations

import gc
import time
from typing import Any, Dict, List, Optional

import numpy as np

DRAIN_S = 15.0          # after the window: finish what was due, at most
WARM_SECONDS = 1.0      # sub-seed traffic after the per-program warm-up
TPOT_MIN_TOKENS = 8     # requests with fewer output tokens give no gap
VERIFY_SAMPLE = 8       # finished requests compared with the reference
DEFAULT_TRACE_SECONDS = 3.0
HOST_SCOPES = ("ds_serve_admit", "ds_serve_prefill", "ds_serve_decode")


class _Live:
    __slots__ = ("req", "seen", "t_first", "t_last", "marks")

    def __init__(self, req):
        self.req = req
        self.seen, self.t_first, self.t_last = 0, None, None
        self.marks = []                       # (seconds, tokens visible)


def drive(serve, schedule, seconds: float, drain_s: float, tracer=None
          ) -> Dict[str, Any]:
    """Run ``schedule`` against ``serve``.  Returns per-request records
    (``None`` where ``submit`` refused) and per-iteration records."""
    import jax
    from deepspeed_tpu.serving import QueueFull

    n = len(schedule)
    recs: List[Optional[_Live]] = [None] * n
    late = np.zeros(n)
    live: List[_Live] = []
    iters = []       # (begin, end, occupied, queued, context tokens)
    sched = serve.scheduler
    i = 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if tracer is not None:
            tracer.tick(now)
        while i < n and schedule[i].due_s <= now:
            a = schedule[i]
            late[i] = (time.perf_counter() - t0) - a.due_s
            try:
                req = serve.submit(a.prompt, max_new_tokens=a.max_new_tokens,
                                   stream=True)
            except QueueFull:
                pass
            else:
                recs[i] = _Live(req)
                live.append(recs[i])
            i += 1
        if sched.has_work:
            tb = time.perf_counter() - t0
            with jax.profiler.TraceAnnotation("bench_step"):
                serve.step()
            te = time.perf_counter() - t0
            context = 0                       # tokens decode rows attend
            for lv in live:
                k = len(lv.req.output_tokens)
                if k > lv.seen:
                    if lv.seen == 0:
                        lv.t_first = te
                    lv.seen, lv.t_last = k, te
                    lv.marks.append((te, k))
                if lv.seen:
                    context += lv.req.prompt_len + lv.seen
            iters.append((tb, te, sched.num_occupied, sched.num_queued,
                          context))
            live = [lv for lv in live if not lv.req.done]
        elif i >= n:
            break
        else:
            time.sleep(max(0.0, min(1e-3, schedule[i].due_s - now)))
        if now >= seconds + drain_s:
            break
    if tracer is not None:
        tracer.finish(time.perf_counter() - t0)
    return {"records": recs, "late_s": late, "iters": iters,
            "elapsed_s": time.perf_counter() - t0}


def quiet_profile():
    """Profiler options without the Python call tracer: the host ranges the
    reduction reads are ``TraceAnnotation``s, and tracing every Python call
    slows the loop it observes."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


class _Tracer:
    """Profile ``[begin, begin + length)`` of the window, under a host
    range named ``bench_window`` that gives the reduction its bounds."""

    def __init__(self, out_dir: str, begin: float, length: float, on_start):
        self.dir, self.begin, self.end = out_dir, begin, begin + length
        self.on_start = on_start
        self.state = "before"
        self.mark = None
        self.started_at = self.stopped_at = None

    def tick(self, now: float) -> None:
        import jax

        if self.state == "before" and now >= self.begin:
            self.on_start()
            jax.profiler.start_trace(self.dir,
                                     profiler_options=quiet_profile())
            self.mark = jax.profiler.TraceAnnotation("bench_window")
            self.mark.__enter__()
            self.state, self.started_at = "on", now
        elif self.state == "on" and now >= self.end:
            self.finish(now)

    def finish(self, now: float) -> None:
        import jax

        if self.state != "on":
            return
        self.mark.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state, self.stopped_at = "done", now


def warm_up(serve, cell, mix, vocab: int, seed: int) -> None:
    """Touch exactly the programs the mix can reach: one prompt per
    power-of-two prefill bucket from 8 up to ``prefill_chunk``, one longer
    than a chunk where the mix has such prompts (the frontier update
    between chunks), a decode block, the prefix cache's page copy (a
    request preempted under pool pressure can come back through it), a
    second of sub-seed traffic, then the buckets again in steady state."""
    from benchmarks.lib.traffic import Arrival, serve_schedule

    rng = np.random.default_rng([int(seed), 0xA11])
    chunk = serve.prefill_chunk
    lengths, b = [], 8
    while b <= chunk:
        lengths.append(b)
        b *= 2
    longest = int(mix["prompt_tokens"]["max"])
    if longest > chunk:
        lengths.append(min(chunk + 8, longest))
    n_new = serve._K + 2
    first = [Arrival(0.0, rng.integers(0, vocab, n, dtype=np.int32), n_new)
             for n in lengths if n + n_new <= serve.max_out]
    drive(serve, first, 0.0, 600.0)
    page = serve.pool.page if serve.pool is not None else 0
    if serve.prefix_cache is not None and page + 8 + n_new <= serve.max_out:
        # a prompt that ends exactly on a cached page is served by copying
        # that page (one token is always left to compute)
        a = Arrival(0.0, rng.integers(0, vocab, page + 8, dtype=np.int32),
                    n_new)
        drive(serve, [a], 0.0, 600.0)
        drive(serve, [Arrival(0.0, a.prompt[:page], n_new)], 0.0, 600.0)
    sub = serve_schedule(mix, float(cell["rate_rps"]), WARM_SECONDS,
                         seed + 7919, vocab)
    drive(serve, sub, WARM_SECONDS, 600.0)
    # Once more, in steady state.  jit keys a program on how its arguments
    # are placed as well as on their shapes, and the KV cache a bucket's
    # first call saw (fresh from jnp.zeros, or out of the first programs) is
    # not placed like the one later calls see; nor is the cache that comes
    # out of a prefill program placed like the one out of a decode block.
    # (Found on the chip: a bucket warmed once compiled again inside the
    # window, a 4 s stall.)  So each bucket runs right after another
    # request's chunk, and right after a decode block, twice over.
    fresh = lambda n: Arrival(0.0, rng.integers(0, vocab, n, dtype=np.int32),
                              n_new)
    for _ in range(2):
        for a in first:
            n = len(a.prompt)
            drive(serve, [fresh(len(first[-1].prompt)), fresh(n)], 0.0, 600.0)
            drive(serve, [fresh(n)], 0.0, 600.0)


def verify(bench, ctx, params, finished, devices) -> Dict[str, Any]:
    """Every served token of a seeded sample of finished requests must sit
    within ``SERVE_TIE_STEPS`` bf16 steps of the reference forward's best
    logit at its position, the reference teacher-forced with the served
    sequence."""
    from benchmarks.lib.correctness import (SERVE_TIE_STEPS,
                                            bf16_steps_below_best)

    ref = bench.reference(ctx["cell"]["config"])
    rng = np.random.default_rng([int(ctx["seed"]), 0xC0DE])
    pick = rng.permutation(len(finished))[:VERIFY_SAMPLE]
    # one padded length, so one compiled reference; a multiple of the
    # reference's query block where it is longer than one block
    pad = int(ctx["cell"]["engine"]["max_out_tokens"])
    if pad > 1024:
        pad = -(-pad // 512) * 512
    # ... and one number of rows read, the mix's longest answer (the last
    # row again where an answer is shorter): the reference's last steps run
    # op by op, and every new shape would compile each of them anew
    n_rows = int(ctx["mix"]["output_tokens"]["max"])
    worst, checked = 0.0, 0
    for j in pick:
        lv, arrival = finished[j]
        out = list(lv.req.output_tokens)
        seq = np.zeros(pad, np.int32)          # right padding: causal, so
        n = len(arrival.prompt) + len(out)     # it cannot reach the rows read
        seq[:n] = np.concatenate([arrival.prompt, np.asarray(out, np.int32)])
        rows = list(range(len(arrival.prompt) - 1, n - 1))
        rows += rows[-1:] * (n_rows - len(out))
        logits = np.asarray(ref.logits_rows(params, ctx["config"], seq, rows,
                                            devices[0]))[: len(out)]
        worst = max(worst, float(bf16_steps_below_best(logits, out).max()))
        checked += 1
    return {"requests_checked": checked, "worst_steps_below_best": worst,
            "bound": SERVE_TIE_STEPS,
            "ok": checked > 0 and worst <= SERVE_TIE_STEPS}


def build(ctx: Dict[str, Any]) -> Dict[str, Any]:
    """The system under test: the configuration's model with seeded bf16
    weights behind ``init_serving`` with the cell's engine settings."""
    import deepspeed_tpu
    from deepspeed_tpu.comm.mesh import build_mesh

    from benchmarks.lib.models import build_model, seeded_serving_weights

    mesh = build_mesh(devices=list(ctx["devices"][:1]))
    model = build_model(ctx["config"], mesh)
    params = seeded_serving_weights(model, ctx["seed"])
    serve = deepspeed_tpu.init_serving(
        model, config=dict(ctx["cell"]["engine"]), params=params, mesh=mesh)
    return {"serve": serve, "params": params,
            "vocab": model.config.vocab_size}


def client_view(res: Dict[str, Any], schedule, seconds: float
                ) -> Dict[str, Any]:
    """What the clients saw: the end-to-end values, the failures and the
    finished requests."""
    from benchmarks.lib.stats import median

    ttft, tpot, finished = [], [], []
    tokens_in_window = failed = 0
    for lv, a in zip(res["records"], schedule):
        if lv is None or not lv.req.done or \
                len(lv.req.output_tokens) != a.max_new_tokens:
            failed += 1
        else:
            finished.append((lv, a))
        if lv is None:
            continue
        tokens_in_window += max([k for t, k in lv.marks if t <= seconds],
                                default=0)
        if lv.t_first is not None:
            ttft.append((lv.t_first - a.due_s) * 1e3)
            if lv.req.done and lv.seen >= TPOT_MIN_TOKENS:
                tpot.append((lv.t_last - lv.t_first) / (lv.seen - 1) * 1e3)
    values = {"tokens_per_s": tokens_in_window / seconds}
    if ttft:
        values["ttft_p50_ms"] = median(ttft)
    if tpot:
        values["tpot_p50_ms"] = median(tpot)
    return {"values": values, "failed": failed, "finished": finished,
            "n_ttft": len(ttft), "n_tpot": len(tpot)}


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    from deepspeed_tpu.monitor.metrics import get_registry

    from benchmarks.lib.compile_clock import CompileClock
    from benchmarks.lib.device import device_report
    from benchmarks.lib.traffic import serve_schedule

    bench, cell, mix = ctx["bench"], ctx["cell"], ctx["mix"]
    devices, seconds, seed = ctx["devices"], ctx["seconds"], ctx["seed"]
    registry = get_registry()
    if ctx["trace"]:
        registry.enable()      # the program's counters: traced run only
    built = build(ctx)
    serve, params, vocab = built["serve"], built["params"], built["vocab"]
    del built
    warm_up(serve, cell, mix, vocab, seed)
    schedule = serve_schedule(mix, float(cell["rate_rps"]), seconds, seed,
                              vocab)
    gc.collect()
    gc.freeze()                # warm-up garbage is not collected in the window

    counters_at = {}

    def snap(tag):
        counters_at[tag] = {k: v for k, v in registry.snapshot().items()
                            if isinstance(v, (int, float))}

    tracer = None
    if ctx["trace"]:
        length = float(cell.get("trace_seconds", DEFAULT_TRACE_SECONDS))
        length = min(length, seconds / 2)
        tracer = _Tracer(ctx["trace_dir"], seconds - length, length,
                         lambda: snap("trace_start"))
    snap("begin")
    setup_s = ctx["age"]()
    with CompileClock() as clock:
        res = drive(serve, schedule, seconds, DRAIN_S, tracer)
    snap("end")
    report = device_report(devices)
    view = client_view(res, schedule, seconds)

    # -- correct? --------------------------------------------------------
    checks: Dict[str, Any] = {
        "compiles_in_window": clock.compiles, "compiled": clock.names,
        "preemptions": sum(lv.req.preemptions for lv in res["records"] if lv)}
    pool_ok = True
    if view["failed"] == 0 and serve.pool is not None:
        try:
            serve.pool.check_no_leak()
        except AssertionError as e:
            pool_ok, checks["pool"] = False, str(e)
    num_slots = serve.num_slots
    serve.close()
    del serve                  # the pool leaves the chip before the reference
    gc.unfreeze()
    gc.collect()
    t_ref = time.perf_counter()
    checks["reference"] = (
        verify(bench, ctx, params, view["finished"], devices)
        if view["finished"] else {"ok": False, "requests_checked": 0})
    # what a run costs after its window, beside setup_s before it
    checks["after_window_s"] = {"drain": res["elapsed_s"] - seconds,
                                "reference": time.perf_counter() - t_ref}
    correct = (pool_ok and clock.compiles == 0 and checks["reference"]["ok"])

    traced = tracer is not None and tracer.started_at is not None
    return {
        "kind": "serve", "attempted": len(schedule),
        "failed": view["failed"], "correct": bool(correct), "checks": checks,
        "values": view["values"], "setup_s": setup_s, "device": report,
        "loop": {"late_s": res["late_s"], "iters": res["iters"],
                 "schedule": schedule, "records": res["records"],
                 "num_slots": num_slots, "window_s": seconds,
                 # per-layer readers of loop records stop where the
                 # profiler starts: starting and stopping it stalls the loop
                 "until_s": tracer.started_at if traced else seconds,
                 "n_ttft": view["n_ttft"], "n_tpot": view["n_tpot"]},
        "counters": counters_at, "compiles_in_window": clock.compiles,
        "trace_window": ((tracer.started_at, tracer.stopped_at)
                         if traced else None),
        "host_scopes": HOST_SCOPES,
    }
