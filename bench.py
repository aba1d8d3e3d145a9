"""Headline benchmark: GPT-2 125M-class causal-LM training throughput on one
chip (BASELINE.json configs[1] rung; north star = tokens/sec/chip, BASELINE.md).

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "tokens/sec", "vs_baseline": N}

``vs_baseline`` is achieved MFU / 0.40 — the north-star target is matching
A100 ZeRO-3 MFU (~40%) on the same workload class (BASELINE.md).

Timing is a host clock around work that ends in ``jax.block_until_ready``.

A chip belongs to one process at a time, and some rungs run in child
processes.  So this module imports no jax when it is loaded: ``main`` runs
every child rung first and checks that jax is still absent each time it
spawns one, and only then does ``_import_jax`` bind the names below.
"""

from __future__ import annotations

import json
import os
import sys
import time


def _import_jax() -> None:
    """Bind the jax-side module names the rungs use (``jax``, ``jnp``,
    ``deepspeed_tpu``, the mesh and model builders, the peaks table) and
    place the compile cache.  Every function that computes calls this
    first; loading the module must not."""
    global jax, jnp, deepspeed_tpu, build_mesh, set_global_mesh, causal_lm
    global PEAK_FLOPS, peak_flops
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.comm.mesh import build_mesh, set_global_mesh
    from deepspeed_tpu.models import causal_lm
    # one table for the bench headline and the live ds_train_mfu gauge
    from deepspeed_tpu.profiling.flops import PEAK_FLOPS, peak_flops
    from deepspeed_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()


def collect_train_metrics(registry) -> dict:
    """Training-health sub-object for the BENCH_JSON record (the serving
    record's ``metrics`` analog): achieved tflops/mfu gauges, peak HBM, and
    the top-3 collectives by attributed time from the ``ds_comm_*`` series."""
    snap = registry.snapshot()
    out = {}
    if snap.get("ds_train_tflops"):
        out["tflops"] = snap["ds_train_tflops"]
    if snap.get("ds_train_mfu"):
        out["mfu"] = snap["ds_train_mfu"]
    if snap.get("ds_mem_peak_bytes"):
        out["peak_hbm_gb"] = round(snap["ds_mem_peak_bytes"] / 1e9, 3)
    colls = []
    for name, v in snap.items():
        if not (name.startswith("ds_comm_") and name.endswith("_seconds")):
            continue
        if name.endswith("_device_seconds"):
            continue        # device truth rides in the device_profile record
        if not isinstance(v, dict) or not v.get("count"):
            continue
        op = name[len("ds_comm_"): -len("_seconds")]
        byt = snap.get(f"ds_comm_{op}_bytes_total", 0)
        if isinstance(byt, dict):               # {dtype=} labeled family
            byt = sum(b for b in byt.values() if isinstance(b, (int, float)))
        colls.append({"op": op, "time_s": round(v["sum"], 4),
                      "calls": v["count"], "bytes": int(byt)})
    colls.sort(key=lambda c: -c["time_s"])
    if colls:
        out["top_collectives"] = colls[:3]
    return out


def sync(x) -> None:
    """Wait until everything in ``x`` has been computed."""
    jax.block_until_ready(x)


def capture_device_profile(step_fn, steps: int = 2, tag: str = "train"):
    """Windowed perfetto capture around ``steps`` calls of ``step_fn``,
    post-processed into the compact device-profile record the bench
    attaches to its ``metrics`` sub-object (PR 3/4 pattern): per-step
    phase breakdown (``ds_profile_*`` semantics), gap share, top device
    collectives, serving dispatch slack.  A failed analysis returns a
    status record instead of killing the bench."""
    _import_jax()
    from deepspeed_tpu.profiling.trace import TraceCapture
    import tempfile

    from deepspeed_tpu.profiling import device_trace as dtr

    d = tempfile.mkdtemp(prefix=f"ds_bench_trace_{tag}_")
    cap = TraceCapture(d, start_step=1, num_steps=steps, perfetto=True)
    try:
        cap.maybe_start(1)
        for i in range(1, steps + 1):
            step_fn()
            cap.after_step(i)
        cap.close()
        s = dtr.summarize_trace(d, steps=steps)
    except Exception as exc:
        return {"status": f"failed: {type(exc).__name__}: {str(exc)[:120]}"}
    finally:
        cap.close()   # a mid-window raise must release the one global
                      # profiler session or every later capture 409s
    per = s.get("per_step") or s["phases"]
    out = {"steps": steps, "window_s": round(s["window_s"], 6),
           "degraded": s["degraded"],
           "per_step": {k: round(v, 6) for k, v in per.items()},
           "trace_dir": d}
    if s["window_s"] > 0:
        out["gap_share"] = round(s["phases"]["gap_s"] / s["window_s"], 4)
    top = sorted(s.get("comm_device", {}).items(),
                 key=lambda kv: -kv[1]["seconds"])[:3]
    if top:
        out["top_device_collectives"] = [
            {"op": op, "device_s": round(rec["seconds"], 6),
             "spans": rec["count"]} for op, rec in top]
    if s.get("serve"):
        out["serve"] = {k: (round(v, 6) if isinstance(v, float) else v)
                        for k, v in s["serve"].items()}
    return out


def goodput_window(before: dict, after: dict, loop_s: float,
                   tokens_expected: int) -> dict:
    """Delta of two goodput-ledger snapshots bracketing a measured loop
    -> the BENCH_JSON ``goodput`` block.  The ledger wall includes the
    snapshot + final device-sync bookends around the timed loop, so the
    ledger tokens/s agrees with the headline within ~10% (documented
    tolerance) while the token COUNT reconciles exactly — both sides
    count gas*micro*seq per fused step."""
    from deepspeed_tpu.monitor import goodput_core

    cats = {k: after["categories"][k] - before["categories"].get(k, 0.0)
            for k in after["categories"]}
    wall = after["wall_s"] - before["wall_s"]
    toks = after["tokens"] - before["tokens"]
    good = sum(cats[c] for c in goodput_core.GOOD_CATEGORIES)
    return {"wall_s": round(wall, 6),
            "loop_s": round(loop_s, 6),
            "goodput_ratio": round(good / wall, 4) if wall > 0 else 0.0,
            "telescopes": goodput_core.telescopes(
                {"wall_s": wall, "categories": cats}),
            "categories": {k: round(v, 6) for k, v in cats.items()
                           if abs(v) > 1e-9},
            "tokens": toks, "tokens_expected": tokens_expected,
            "tokens_reconcile": toks == tokens_expected,
            "tokens_per_sec": round(toks / wall, 1) if wall > 0 else 0.0}


def bench_8b_rung(budget_s: float = 900.0, int8: bool = True,
                  prefetch: bool = True):
    """Llama-3-8B single-chip rung (BASELINE configs[2] / VERDICT r3 item 1).

    8B bf16 params (16.1GB) exceed the 15.75GB v5e HBM, so this exercises
    the ZeRO-Infinity STREAMED path (runtime/zero/stream_grad.py): weights
    live as host numpy, each layer's params H2D-stream per segment, and
    each layer's grads D2H-stream into host accumulators — no [model]-sized
    buffer (params OR grads) ever exists on device, which is also why the
    whole-program form cannot even compile here (a 16GB grad output cannot
    be placed).  Measured: fwd+bwd tokens/sec per chip.  The ISSUE 11
    streaming layer attacks the host<->device transfer: ``int8`` ships
    each layer as blockwise int8 + scales with a fused on-device dequant
    (~2x fewer relay bytes than bf16), ``prefetch`` double-buffers layer
    i+1's transfer under layer i's compute.  The record carries the
    effective relay MB/s (relay bytes / step wall).  The full CPU-Adam step is not timed: fp32
    master+moments for 8B are 96GB on top of the streaming buffers.
    """
    _import_jax()
    import numpy as np
    import ml_dtypes
    from jax.sharding import PartitionSpec as P

    t_start = time.perf_counter()
    try:
        from deepspeed_tpu.models import causal_lm
        from deepspeed_tpu.monitor.metrics import get_registry
        from deepspeed_tpu.runtime.zero.partition import (params_pspecs,
                                                          shardings_from_pspecs)
        from deepspeed_tpu.runtime.zero.stream_grad import StreamedFwdBwd

        mesh = build_mesh(devices=jax.devices()[:1])
        set_global_mesh(mesh)
        model = causal_lm("llama3-8b", mesh=mesh, remat=True)
        cfg = model.config
        micro, seq = 1, 1024

        # init on HOST, leaf by leaf (a device init would need 32GB fp32)
        rng = np.random.default_rng(0)
        abstract = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                                  jnp.zeros((1, 8), jnp.int32))
        def host_init(s):
            scale = 0.02 if len(s.shape) <= 2 else s.shape[-1] ** -0.5
            arr = (rng.standard_normal(s.shape, dtype=np.float32) * scale)
            return arr.astype(ml_dtypes.bfloat16)
        params_np = jax.tree.map(host_init, abstract)
        n_params = sum(int(x.size) for x in jax.tree.leaves(params_np))

        specs = params_pspecs(params_np, mesh, shard=False)
        seg = model.stream_segments()
        sfb = StreamedFwdBwd.from_param_specs(seg, specs, mesh, gas=1,
                                              use_dropout=False,
                                              int8=int8, prefetch=prefetch)
        # bf16 host accumulators (fp32 would be 32GB on top of the params)
        acc = jax.tree.map(lambda a: np.zeros(a.shape, ml_dtypes.bfloat16),
                           params_np)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (micro, seq), 0,
                                    cfg.vocab_size)
        key = jax.random.PRNGKey(2)
        registry = get_registry()
        was_enabled = registry.enabled
        registry.enable()
        try:
            loss = sfb.run(params_np, tokens, tokens, None, key, acc)
            loss0 = float(loss)           # compile + first step
            registry.reset()
            steps = 0
            t0 = time.perf_counter()
            while steps < 2 and (steps == 0
                                 or time.perf_counter() - t0 < budget_s):
                loss = sfb.run(params_np, tokens, tokens, None, key, acc)
                float(loss)
                steps += 1
            wall = time.perf_counter() - t0
            dt = wall / steps
            snap = registry.snapshot()
        finally:
            # a raise must not leave the process-global registry hot (the
            # 125M headline and later rungs run in this process)
            if not was_enabled:
                registry.disable()
        relay = snap.get("ds_offload_relay_bytes_total", {}) or {}
        h2d = relay.get('{dir="h2d"}', 0)
        d2h = relay.get('{dir="d2h"}', 0)
        tps = micro * seq / dt
        fpt = 6 * n_params + 6 * cfg.num_layers * cfg.hidden_size * seq
        return {"status": "ok", "tokens_per_sec_fwd_bwd": round(tps, 2),
                "params_b": round(n_params / 1e9, 3),
                "micro_batch": micro, "seq": seq, "steps": steps,
                "step_ms": round(dt * 1e3, 1), "loss": round(loss0, 3),
                "mfu_fwd_bwd": round(tps * fpt / peak_flops(), 4),
                "int8_relay": bool(int8), "prefetch": bool(prefetch),
                "relay": {
                    "h2d_bytes_per_step": int(h2d / steps),
                    "d2h_bytes_per_step": int(d2h / steps),
                    "effective_MBps": round((h2d + d2h) / wall / 1e6, 2),
                    "prefetch_hits": int(snap.get(
                        "ds_offload_prefetch_hits_total", 0)),
                },
                "note": ("ZeRO-Infinity streamed fwd+bwd: host-resident "
                         "params stream per layer H2D, grads stream per "
                         "layer D2H into host accumulators. "
                         "Optimizer step not timed: 96GB fp32 Adam states "
                         "(int8_masters would cut that to ~24GB)")}
    except Exception as exc:  # the 125M headline must still be emitted
        return {"status": f"failed: {type(exc).__name__}",
                "error": str(exc)[:200],
                "elapsed_s": round(time.perf_counter() - t_start, 1)}


def bench_streamed_rung(steps: int = 3, warmup: int = 1,
                        tiny: bool = None) -> dict:
    """Offload streaming ablation (ISSUE 11 / ROADMAP item 3): the SAME
    streamed-offload training workload with the bf16 relay vs the int8
    relay (+ int8 host masters), prefetch on both sides.

    Per side: tokens/s, relay bytes per step by direction, effective
    relay MB/s (bytes / wall — on a relay-bound rung the two are equal),
    prefetch hits, final loss.  Headlines: ``streamed_speedup`` (int8 /
    bf16 tokens/s — the acceptance number on relay-bound hardware),
    ``relay_bytes_ratio`` (bf16 / int8 H2D bytes, machine-independent),
    ``loss_parity`` vs a plain NON-offloaded engine at the same seed
    (rtol 5e-2 — int8 masters are a lossy code, the bound is the
    contract), and the device-profile ``gap_share`` on the offload path
    (``ds_profile_gap`` semantics — the overlap headroom the prefetch is
    eating).  On CPU runners the model scales to smoke size (mechanics +
    byte ratios are what the CPU row pins; absolute rates need TPU)."""
    _import_jax()
    import gc

    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import causal_lm
    from deepspeed_tpu.monitor.metrics import get_registry

    t_start = time.perf_counter()
    try:
        on_tpu = jax.default_backend() != "cpu"
        if tiny is None:
            tiny = not on_tpu
        mesh = build_mesh(devices=jax.devices()[:1])
        set_global_mesh(mesh)
        if tiny:
            over = dict(num_layers=4, hidden_size=128, intermediate_size=256,
                        num_heads=4, num_kv_heads=4, vocab_size=512,
                        max_seq_len=128)
            micro, seq = 2, 64
        else:
            over = {}
            micro, seq = 1, 1024
        registry = get_registry()
        was_enabled = registry.enabled
        registry.enable()
        results = {}
        losses = {}
        gap_share = None
        try:
            for side in ("plain", "bf16", "int8"):
                model = causal_lm("llama-1b4", mesh=mesh, **over)
                cfg_m = model.config
                zero = {"stage": 3}
                if side != "plain":
                    zero["offload_optimizer"] = {
                        "device": "cpu", "int8_masters": side == "int8"}
                    zero["offload_param"] = {
                        "device": "cpu", "prefetch": True,
                        "int8_stream": side == "int8"}
                ds_config = {
                    "train_micro_batch_size_per_gpu": micro,
                    "gradient_accumulation_steps": 1,
                    "bf16": {"enabled": True},
                    "zero_optimization": zero,
                    "optimizer": {"type": "AdamW", "params": {"lr": 2e-4}},
                    "gradient_clipping": 1.0, "steps_per_print": 10**9}
                engine, _, _, _ = deepspeed_tpu.initialize(
                    model=model, config=ds_config, mesh=mesh,
                    rng=jax.random.PRNGKey(11))
                tokens = jax.random.randint(jax.random.PRNGKey(1),
                                            (micro, seq), 0,
                                            cfg_m.vocab_size)
                batch = (tokens, tokens)

                def one_step():
                    loss = engine.forward(batch)
                    engine.step()
                    return loss

                for _ in range(warmup):
                    one_step()
                registry.reset()
                t1 = time.perf_counter()
                loss = None
                for _ in range(steps):
                    loss = one_step()
                loss = float(loss)
                wall = time.perf_counter() - t1
                losses[side] = loss
                if side == "plain":
                    engine = model = None
                    gc.collect()
                    continue
                snap = registry.snapshot()
                relay = snap.get("ds_offload_relay_bytes_total", {}) or {}
                h2d = relay.get('{dir="h2d"}', 0)
                d2h = relay.get('{dir="d2h"}', 0)
                row = {
                    "tokens_per_sec": round(steps * micro * seq / wall, 1),
                    "step_ms": round(1e3 * wall / steps, 1),
                    "loss": round(loss, 5),
                    "h2d_bytes_per_step": int(h2d / steps),
                    "d2h_bytes_per_step": int(d2h / steps),
                    "relay_MBps": round((h2d + d2h) / wall / 1e6, 2),
                    "prefetch_hits": int(snap.get(
                        "ds_offload_prefetch_hits_total", 0)),
                    "relay_stall_s": round(
                        (snap.get("ds_offload_relay_seconds") or {}
                         ).get("sum", 0.0), 4),
                }
                if side == "int8":
                    # ds_profile_gap share on the offload path: a short
                    # device capture over the streamed step
                    dp = capture_device_profile(one_step, steps=2,
                                                tag="streamed")
                    if dp and dp.get("gap_share") is not None:
                        gap_share = dp["gap_share"]
                        row["device_profile"] = dp
                results[side] = row
                engine = model = None
                gc.collect()
        finally:
            if not was_enabled:
                registry.disable()
        bf16_b = results["bf16"]["h2d_bytes_per_step"]
        int8_b = results["int8"]["h2d_bytes_per_step"]
        plain = losses["plain"]
        parity = bool(np.isfinite(plain) and abs(losses["int8"] - plain)
                      <= 5e-2 * abs(plain))
        return {"status": "ok", "tiny": bool(tiny), "steps": steps,
                "micro_batch": micro, "seq": seq,
                "backend": jax.default_backend(),
                "bf16": results["bf16"], "int8": results["int8"],
                "loss_plain": round(plain, 5),
                "streamed_speedup": round(
                    results["int8"]["tokens_per_sec"]
                    / max(results["bf16"]["tokens_per_sec"], 1e-9), 3),
                "relay_bytes_ratio": round(bf16_b / max(int8_b, 1), 3),
                "loss_parity": parity,
                "gap_share": gap_share}
    except Exception as exc:
        return {"status": f"failed: {type(exc).__name__}",
                "error": str(exc)[:300],
                "elapsed_s": round(time.perf_counter() - t_start, 1)}


def bench_serving(num_requests: int = 64, num_slots: int = 8, qps: float = 50.0,
                  seed: int = 0, tiny: bool = False) -> dict:
    """Continuous-batching serving scenario: Poisson arrivals, mixed
    prompt/output lengths, reporting goodput tok/s and p50/p99 per-request
    latency for THREE systems replaying the identical arrival trace:

    - ``continuous`` — the PAGED ``ServingEngine`` at an HBM budget EQUAL
      to the fixed-slot layout (``kv_pool_tokens = num_slots * max_out``)
      but DOUBLE the slots: pages are allocated on demand, so the same KV
      memory admits ~2x concurrently-decoding requests, backed by LIFO
      preempt-and-requeue if the bimodal tail ever fills the pool — the
      paged-vs-fixed comparison is equal-HBM, not equal-slots;
    - ``fixed_slot`` — the PR 1 contiguous per-slot cache at ``num_slots``
      (each slot reserves the worst-case ``max_out`` whether used or not);
    - ``static`` — the static-batch ``InferenceEngine`` baseline at equal
      slot count (padded to the batch max prompt, decoded to the batch max
      output — the head-of-line + padding waste iteration-level
      scheduling removes).

    Goodput counts only the tokens each request ASKED for.  Each trace is
    warmed with TWO passes before the recorded third — grow-only cache
    reallocation drops compiled fns mid-first-pass, so one warm pass still
    leaves compiles in the record.  The ``metrics`` sub-object carries the
    paged engine's lifecycle histograms plus {kv_util, preemptions, pages}
    so the goodput delta lands with its memory attribution.
    """
    _import_jax()
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import causal_lm

    mesh = build_mesh(devices=jax.devices()[:1])
    set_global_mesh(mesh)
    rng = np.random.default_rng(seed)
    if tiny:  # CPU smoke scale (tests/perf/test_serving_bench.py)
        model = causal_lm("gpt2-small", mesh=mesh, num_layers=2,
                          hidden_size=128, intermediate_size=256, num_heads=4,
                          vocab_size=512)
        max_out, p_lo, p_hi, n_short, n_long = 64, 4, 24, (4, 12), (24, 32)
    else:
        model = causal_lm("gpt2-small", mesh=mesh, vocab_size=50304)
        max_out, p_lo, p_hi = 1024, 16, 256
        n_short, n_long = (16, 96), (192, 256)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    V = model.config.vocab_size

    prompts = [rng.integers(0, V, size=int(n)).astype(np.int32)
               for n in rng.integers(p_lo, p_hi + 1, size=num_requests)]
    # bimodal output lengths (chat-like: mostly short answers, a heavy
    # long tail) — the head-of-line + padding regime static batching pays
    # for and iteration-level scheduling does not; ALSO the regime where
    # fixed per-slot reservations are mostly dead weight (a 30-token reply
    # pins the same KV as a 2k one), which is the paged pool's win
    long_mask = rng.random(num_requests) < 0.25
    news = np.where(long_mask,
                    rng.integers(n_long[0], n_long[1] + 1, num_requests),
                    rng.integers(n_short[0], n_short[1] + 1,
                                 num_requests)).tolist()
    arrivals = np.cumsum(rng.exponential(1.0 / qps, size=num_requests))
    arrivals -= arrivals[0]  # first request arrives at t=0

    def percentiles(lat):
        return (round(float(np.percentile(lat, 50)), 4),
                round(float(np.percentile(lat, 99)), 4))

    # -- continuous batching: paged (equal HBM) vs fixed-slot ----------
    kv_budget = num_slots * max_out          # the fixed layout's KV tokens

    def make_serve(paged: bool, slots: int):
        cfg = {"dtype": "bfloat16", "max_out_tokens": max_out,
               "paged_kv_cache": paged}
        if paged:
            cfg["kv_pool_tokens"] = kv_budget
        s = deepspeed_tpu.init_serving(model, config=cfg, num_slots=slots,
                                       decode_block_tokens=8)
        s.set_params(params)
        return s

    def run_continuous(serve):
        t0 = time.perf_counter()
        reqs, i = [], 0
        while i < num_requests or serve.scheduler.has_work:
            now = time.perf_counter() - t0
            while i < num_requests and arrivals[i] <= now:
                reqs.append(serve.submit(prompts[i], max_new_tokens=news[i]))
                i += 1
            if not serve.scheduler.has_work:
                time.sleep(max(0.0, arrivals[i] - now))
                continue
            serve.step()
        makespan = time.perf_counter() - t0
        lat = [r.t_finish - (t0 + arrivals[j]) for j, r in enumerate(reqs)]
        toks = sum(len(r.output_tokens) for r in reqs)
        serve.scheduler.drain_finished()
        return toks, makespan, lat

    from deepspeed_tpu.monitor.metrics import get_registry
    from deepspeed_tpu.monitor.request_trace import get_request_tracer

    registry = get_registry()
    was_enabled = registry.enabled
    registry.enable()
    # per-request span tracing for the recorded pass: the ring must hold
    # the whole wave so tail attribution sees every request, not a sample
    tracer = get_request_tracer()
    tracer_was = tracer.enabled
    tracer_ring_was = tracer._ring.maxlen
    tracer.configure(ring=max(2 * num_requests, 256)).enable()
    sides = {}
    serving_metrics = {}
    try:
        # engines are built lazily per side so only ONE KV cache (paged
        # pool or fixed layout, each a full num_slots*max_out budget) is
        # resident at a time — the equal-HBM bench must not itself hold 2x
        for side, build in (("continuous",
                             lambda: make_serve(True, 2 * num_slots)),
                            ("fixed_slot",
                             lambda: make_serve(False, num_slots))):
            serve = build()
            run_continuous(serve)           # compile-warm passes
            run_continuous(serve)
            registry.reset()                # warm passes out of the record
            tracer.reset()
            toks_c, span_c, lat_c = run_continuous(serve)
            p50_c, p99_c = percentiles(lat_c)
            snap = registry.snapshot()
            util = snap.get("ds_serve_kv_cache_util_ratio") or {}
            sides[side] = {
                "goodput_tok_s": round(toks_c / span_c, 1),
                "tokens": toks_c, "makespan_s": round(span_c, 3),
                "p50_latency_s": p50_c, "p99_latency_s": p99_c,
                "slots": serve.num_slots,
                "kv_util": round(util.get("mean", 0.0), 3),
            }
            if side == "continuous":
                # serving-health metrics from the lifecycle registry
                # (host-side histograms over the RECORDED pass only) —
                # tracked per BENCH row so a goodput regression is
                # attributable to admission vs prefill vs decode vs pool
                # pressure, not just visible in the aggregate
                serving_metrics = {
                    "ttft_p50_s":
                        round(snap["ds_serve_ttft_seconds"]["p50"], 4),
                    "ttft_p99_s":
                        round(snap["ds_serve_ttft_seconds"]["p99"], 4),
                    "queue_wait_p99_s":
                        round(snap["ds_serve_queue_wait_seconds"]["p99"], 4),
                    # None here: this wave neither streams nor sets an
                    # EOS, so every token is fetched at a request's finish
                    # and the engine records no per-token pace (and its
                    # TTFT is the request latency)
                    "tpot_p50_s":
                        (round(snap["ds_serve_tpot_seconds"]["p50"], 5)
                         if snap["ds_serve_tpot_seconds"]["count"] else None),
                    "mean_slot_occupancy":
                        round(snap["ds_serve_occupancy_ratio"]["mean"], 3),
                    "kv_util": round(util.get("mean", 0.0), 3),
                    "preemptions":
                        int(snap.get("ds_serve_preempted_total", 0)),
                    "pages": {"pool": serve.pool.num_pages - 1,
                              "page_tokens": serve.pool.page,
                              "budget_tokens": kv_budget},
                }
                # per-request tail attribution over the recorded pass:
                # WHICH phase dominates the requests above the p99
                # latency cut (queue vs prefill vs decode vs preemption
                # wait) — the "why is my p99 slow" row for BENCH_r*.json
                ta = tracer.tail_attribution(p=0.99)
                serving_metrics["tail_attribution"] = {
                    "p": ta["p"], "n": ta["n"], "tail_n": ta["tail_n"],
                    "cut_s": round(ta["cut_s"], 4),
                    "dominant_phase": ta["dominant_phase"],
                    "phase_share": {k: round(v, 4) for k, v in
                                    ta["phase_share"].items()},
                    "exemplars": ta["exemplars"],
                }
                # device-true serving capture: a short burst of live
                # requests under the profiler, post-processed into the
                # decode dispatch-slack record (device decode time vs
                # host dispatch window — the sync-free path's headroom)
                for p, n in list(zip(prompts, news))[: serve.num_slots]:
                    serve.submit(p, max_new_tokens=min(int(n), 16))
                dp = capture_device_profile(serve.step, steps=4,
                                            tag="serving")
                serve.run()                 # drain the burst
                serve.scheduler.drain_finished()
                if dp:
                    serving_metrics["device_profile"] = dp
    finally:
        if not was_enabled:                 # a mid-bench raise must not
            registry.disable()              # leave the registry hot
        if not tracer_was:
            tracer.disable()
        tracer.configure(ring=tracer_ring_was)  # undo the wave-sized ring

    # -- static-batch baseline ----------------------------------------
    engine = deepspeed_tpu.init_inference(
        model, config={"dtype": "bfloat16", "max_out_tokens": max_out})
    engine.set_params(params)

    def run_static():
        t0 = time.perf_counter()
        lat, toks = [], 0
        for lo in range(0, num_requests, num_slots):
            hi = min(lo + num_slots, num_requests)
            # the batch cannot launch before its LAST member arrives
            wait = arrivals[hi - 1] - (time.perf_counter() - t0)
            if wait > 0:
                time.sleep(wait)
            S = max(len(p) for p in prompts[lo:hi])
            batch = np.zeros((hi - lo, S), np.int32)
            for r, p in enumerate(prompts[lo:hi]):
                batch[r, : len(p)] = p       # right-pad to the batch max
            out = engine.generate(batch, max_new_tokens=int(max(news[lo:hi])),
                                  do_sample=False)
            jax.block_until_ready(out)
            t_done = time.perf_counter() - t0
            lat += [t_done - arrivals[j] for j in range(lo, hi)]
            toks += int(sum(news[lo:hi]))    # requested tokens only
        return toks, time.perf_counter() - t0, lat

    run_static()                            # compile-warm passes (the first
    run_static()                            # still recompiles: cache growth
    toks_s, span_s, lat_s = run_static()    # drops compiled fns mid-pass)

    p50_s, p99_s = percentiles(lat_s)
    goodput_c = sides["continuous"]["goodput_tok_s"]
    goodput_f = sides["fixed_slot"]["goodput_tok_s"]
    return {
        "workload": {"num_requests": num_requests, "num_slots": num_slots,
                     "paged_slots": 2 * num_slots,
                     "kv_budget_tokens": kv_budget,
                     "qps": qps, "prompt_len": [p_lo, p_hi],
                     "new_tokens": {"short": list(n_short),
                                    "long": list(n_long), "p_long": 0.25},
                     "arrivals": "poisson", "seed": seed},
        "continuous": sides["continuous"],
        "metrics": serving_metrics,
        "fixed_slot": sides["fixed_slot"],
        "static": {"goodput_tok_s": round(toks_s / span_s, 1),
                   "tokens": toks_s, "makespan_s": round(span_s, 3),
                   "p50_latency_s": p50_s, "p99_latency_s": p99_s},
        "goodput_speedup": round(goodput_c / max(toks_s / span_s, 1e-9), 2),
        # the tentpole attribution: same KV HBM, 2x slots via paging
        "paged_vs_fixed_speedup": round(goodput_c / max(goodput_f, 1e-9), 2),
    }


def bench_prefix_serving(num_requests: int = 48, num_slots: int = 8,
                         qps: float = 50.0, seed: int = 0,
                         tiny: bool = False) -> dict:
    """Shared-prefix serving scenario: copy-on-write prefix caching on vs
    off on ONE identical trace (serving/prefix_cache.py — ROADMAP item 3).

    The trace is the regime the cache exists for: ~70% of requests open
    with one of two shared system prompts (multi-page), the rest are
    cold, output lengths are bimodal chat-like.  Both sides run the PAGED
    engine with identical slots/pool; the only delta is
    ``prefix_caching``.  Recorded per side: goodput, TTFT p50/p99, and
    ``prefill_tokens_computed`` (the host-countable savings — this is the
    first serving speedup PROVABLE on CPU, unlike the TPU-bandwidth-bound
    paged-goodput win).  Headline: ``prefill_savings_ratio`` (acceptance:
    >= 40% fewer prefill tokens computed with the cache on) +
    ``prefix_hit_ratio`` + ``outputs_token_identical`` (greedy outputs
    must not change — the correctness half of the acceptance bar).
    """
    _import_jax()
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import causal_lm
    from deepspeed_tpu.monitor.metrics import get_registry

    mesh = build_mesh(devices=jax.devices()[:1])
    set_global_mesh(mesh)
    rng = np.random.default_rng(seed + 7)
    if tiny:  # CPU smoke scale (tests/perf/test_serving_bench.py)
        model = causal_lm("gpt2-small", mesh=mesh, num_layers=2,
                          hidden_size=128, intermediate_size=256, num_heads=4,
                          vocab_size=512)
        max_out, page_tokens = 96, 16
        sys_len, tail = 48, (4, 12)
        n_short, n_long = (4, 10), (16, 24)
    else:
        model = causal_lm("gpt2-small", mesh=mesh, vocab_size=50304)
        max_out, page_tokens = 1024, 0
        sys_len, tail = 256, (16, 128)
        n_short, n_long = (16, 96), (192, 256)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    V = model.config.vocab_size

    system_prompts = [rng.integers(0, V, size=sys_len).astype(np.int32)
                      for _ in range(2)]
    shared_mask = rng.random(num_requests) < 0.7   # the 60-80% regime
    long_mask = rng.random(num_requests) < 0.25
    prompts, news = [], []
    for i in range(num_requests):
        t = rng.integers(0, V, size=int(rng.integers(tail[0], tail[1] + 1))
                         ).astype(np.int32)
        if shared_mask[i]:
            prompts.append(np.concatenate(
                [system_prompts[int(rng.integers(2))], t]))
        else:  # cold request: unique prompt, roughly half the system size
            prompts.append(rng.integers(
                0, V, size=sys_len // 2 + len(t)).astype(np.int32))
        news.append(int(rng.integers(n_long[0], n_long[1] + 1) if long_mask[i]
                        else rng.integers(n_short[0], n_short[1] + 1)))
    arrivals = np.cumsum(rng.exponential(1.0 / qps, size=num_requests))
    arrivals -= arrivals[0]

    def percentiles(lat):
        return (round(float(np.percentile(lat, 50)), 4),
                round(float(np.percentile(lat, 99)), 4))

    def make_serve(prefix_on: bool):
        s = deepspeed_tpu.init_serving(
            model, config={"dtype": "bfloat16", "max_out_tokens": max_out,
                           "kv_page_tokens": page_tokens,
                           "prefix_caching": prefix_on},
            num_slots=num_slots, decode_block_tokens=8)
        s.set_params(params)
        return s

    def run_trace(serve):
        t0 = time.perf_counter()
        reqs, i = [], 0
        while i < num_requests or serve.scheduler.has_work:
            now = time.perf_counter() - t0
            while i < num_requests and arrivals[i] <= now:
                reqs.append(serve.submit(prompts[i], max_new_tokens=news[i]))
                i += 1
            if not serve.scheduler.has_work:
                time.sleep(max(0.0, arrivals[i] - now))
                continue
            serve.step()
        makespan = time.perf_counter() - t0
        lat = [r.t_finish - (t0 + arrivals[j]) for j, r in enumerate(reqs)]
        outs = [list(r.output_tokens) for r in reqs]
        toks = sum(len(o) for o in outs)
        serve.scheduler.drain_finished()
        return toks, makespan, lat, outs

    registry = get_registry()
    was_enabled = registry.enabled
    registry.enable()
    sides, outputs = {}, {}
    try:
        for side, on in (("cache_on", True), ("cache_off", False)):
            serve = make_serve(on)
            run_trace(serve)            # compile-warm passes
            run_trace(serve)
            if on:
                # measure the INTRA-trace sharing win, not a replay of a
                # fully-warm cache: the warm passes served this same
                # trace, so without a clear even the cold prompts would
                # hit and the savings would read ~100%
                serve.prefix_cache.clear()
            registry.reset()
            toks, span, lat, outs = run_trace(serve)
            outputs[side] = outs
            p50, p99 = percentiles(lat)
            snap = registry.snapshot()
            ttft = snap.get("ds_serve_ttft_seconds") or {}
            entry = {
                "goodput_tok_s": round(toks / span, 1),
                "tokens": toks, "makespan_s": round(span, 3),
                "p50_latency_s": p50, "p99_latency_s": p99,
                "ttft_p50_s": round(ttft.get("p50", 0.0), 4),
                "ttft_p99_s": round(ttft.get("p99", 0.0), 4),
                "prefill_tokens_computed":
                    int(snap.get("ds_serve_prefill_tokens_total", 0)),
            }
            if on:
                hit = int(snap.get("ds_serve_prefix_hit_tokens_total", 0))
                miss = int(snap.get("ds_serve_prefix_miss_tokens_total", 0))
                entry["prefix_hit_ratio"] = round(
                    hit / max(hit + miss, 1), 4)
                entry["prefix_hit_tokens"] = hit
                entry["prefix_evictions"] = int(
                    snap.get("ds_serve_prefix_evictions_total", 0))
                entry["prefix_cache_pages"] = serve.pool.pages_cached
            sides[side] = entry
            serve.close()
    finally:
        if not was_enabled:             # a mid-bench raise must not leave
            registry.disable()          # the registry hot
    on_c = sides["cache_on"]["prefill_tokens_computed"]
    off_c = sides["cache_off"]["prefill_tokens_computed"]
    return {
        "workload": {"num_requests": num_requests, "num_slots": num_slots,
                     "qps": qps, "shared_prefix_frac": 0.7,
                     "system_prompt_tokens": sys_len,
                     "system_prompts": 2,
                     "new_tokens": {"short": list(n_short),
                                    "long": list(n_long), "p_long": 0.25},
                     "arrivals": "poisson", "seed": seed},
        "cache_on": sides["cache_on"],
        "cache_off": sides["cache_off"],
        # the acceptance pair: >= 0.4 savings, outputs unchanged
        "prefill_savings_ratio": round(1.0 - on_c / max(off_c, 1), 4),
        "outputs_token_identical": outputs["cache_on"] ==
                                   outputs["cache_off"],
        "prefix_hit_ratio": sides["cache_on"]["prefix_hit_ratio"],
        "prefix_goodput_speedup": round(
            sides["cache_on"]["goodput_tok_s"]
            / max(sides["cache_off"]["goodput_tok_s"], 1e-9), 2),
    }


def bench_host_tier_serving(num_requests: int = 32, num_slots: int = 4,
                            qps: float = 50.0, seed: int = 0,
                            tiny: bool = False) -> dict:
    """KV host tier at a THRASH-sized pool (ISSUE 11): the identical
    shared-prefix trace with ``kv_host_tier_pages`` off vs on, on a pool
    deliberately too small to keep cached history resident — the regime
    where PR 9's evict-to-drop forgot every cold prefix and the host tier
    keeps them promotable.

    Recorded per side: prefix hit ratio, prefill tokens computed,
    goodput, TTFT p99, demotes/promotes/host pages (tier side).
    Headlines: ``hit_ratio_on`` strictly above ``hit_ratio_off`` +
    ``outputs_token_identical`` (promotion is a byte-identical KV copy,
    so greedy outputs cannot change) — the acceptance pair."""
    _import_jax()
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import causal_lm
    from deepspeed_tpu.monitor.metrics import get_registry

    mesh = build_mesh(devices=jax.devices()[:1])
    set_global_mesh(mesh)
    rng = np.random.default_rng(seed + 13)
    if tiny:  # CPU smoke scale (tests/perf/test_serving_bench.py)
        model = causal_lm("gpt2-small", mesh=mesh, num_layers=2,
                          hidden_size=128, intermediate_size=256,
                          num_heads=4, vocab_size=512)
        max_out, page_tokens = 96, 16
        sys_len, tail = 32, (3, 8)
        n_short, n_long = (4, 8), (10, 16)
        # pool ~ live-slot working set: cached prefixes always under
        # pressure (the drop-vs-demote regime at smoke scale)
        n_prefixes, pool_tokens, host_pages = 4, num_slots * 80, 24
    else:
        model = causal_lm("gpt2-small", mesh=mesh, vocab_size=50304)
        max_out, page_tokens = 1024, 0
        sys_len, tail = 256, (16, 96)
        n_short, n_long = (16, 96), (192, 256)
        # pool = exactly the live-slot budget: every cached page is under
        # pressure the moment slots fill, so cached history always
        # evicts — the drop-vs-demote regime
        n_prefixes, pool_tokens, host_pages = 6, num_slots * 1024, 512
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    V = model.config.vocab_size

    sys_prompts = [rng.integers(0, V, size=sys_len).astype(np.int32)
                   for _ in range(n_prefixes)]
    long_mask = rng.random(num_requests) < 0.25
    prompts, news = [], []
    for i in range(num_requests):
        t = rng.integers(0, V, size=int(rng.integers(tail[0], tail[1] + 1))
                         ).astype(np.int32)
        # round-robin over MANY shared prefixes: each re-visit arrives
        # after the pool pressure evicted the prefix's pages
        prompts.append(np.concatenate([sys_prompts[i % n_prefixes], t]))
        news.append(int(rng.integers(n_long[0], n_long[1] + 1)
                        if long_mask[i]
                        else rng.integers(n_short[0], n_short[1] + 1)))
    arrivals = np.cumsum(rng.exponential(1.0 / qps, size=num_requests))
    arrivals -= arrivals[0]

    def make_serve(host_on: bool):
        s = deepspeed_tpu.init_serving(
            model, config={"dtype": "bfloat16", "max_out_tokens": max_out,
                           "kv_page_tokens": page_tokens,
                           "kv_pool_tokens": pool_tokens,
                           "kv_host_tier_pages": host_pages if host_on
                           else 0},
            num_slots=num_slots, decode_block_tokens=8)
        s.set_params(params)
        return s

    def run_trace(serve):
        t0 = time.perf_counter()
        reqs, i = [], 0
        while i < num_requests or serve.scheduler.has_work:
            now = time.perf_counter() - t0
            while i < num_requests and arrivals[i] <= now:
                reqs.append(serve.submit(prompts[i], max_new_tokens=news[i]))
                i += 1
            if not serve.scheduler.has_work:
                time.sleep(max(0.0, arrivals[i] - now))
                continue
            serve.step()
        makespan = time.perf_counter() - t0
        outs = [list(r.output_tokens) for r in reqs]
        serve.scheduler.drain_finished()
        return sum(len(o) for o in outs), makespan, outs

    registry = get_registry()
    was_enabled = registry.enabled
    registry.enable()
    sides, outputs = {}, {}
    try:
        for side, on in (("tier_off", False), ("tier_on", True)):
            serve = make_serve(on)
            run_trace(serve)            # compile-warm passes
            run_trace(serve)
            serve.prefix_cache.clear()  # measure intra-trace behavior
            registry.reset()
            toks, span, outs = run_trace(serve)
            outputs[side] = outs
            snap = registry.snapshot()
            hit = int(snap.get("ds_serve_prefix_hit_tokens_total", 0))
            miss = int(snap.get("ds_serve_prefix_miss_tokens_total", 0))
            ttft = snap.get("ds_serve_ttft_seconds") or {}
            sides[side] = {
                "goodput_tok_s": round(toks / span, 1),
                "makespan_s": round(span, 3),
                "ttft_p99_s": round(ttft.get("p99", 0.0), 4),
                "prefix_hit_ratio": round(hit / max(hit + miss, 1), 4),
                "prefix_hit_tokens": hit,
                "prefill_tokens_computed":
                    int(snap.get("ds_serve_prefill_tokens_total", 0)),
                "evictions": int(snap.get(
                    "ds_serve_prefix_evictions_total", 0)),
                "demotes": int(snap.get("ds_serve_kv_demote_total", 0)),
                "promotes": int(snap.get("ds_serve_kv_promote_total", 0)),
                "host_pages": int(snap.get("ds_serve_kv_host_pages", 0)),
            }
            serve.pool.check_no_leak()
            serve.prefix_cache.check_no_leak()
            serve.close()
    finally:
        if not was_enabled:
            registry.disable()
    return {
        "workload": {"num_requests": num_requests, "num_slots": num_slots,
                     "qps": qps, "shared_prefixes": n_prefixes,
                     "system_prompt_tokens": sys_len,
                     "pool_tokens": pool_tokens, "host_pages": host_pages,
                     "arrivals": "poisson", "seed": seed},
        "tier_off": sides["tier_off"],
        "tier_on": sides["tier_on"],
        "hit_ratio_on": sides["tier_on"]["prefix_hit_ratio"],
        "hit_ratio_off": sides["tier_off"]["prefix_hit_ratio"],
        "demotes": sides["tier_on"]["demotes"],
        "promotes": sides["tier_on"]["promotes"],
        "outputs_token_identical": outputs["tier_on"] ==
                                   outputs["tier_off"],
        "goodput_speedup": round(
            sides["tier_on"]["goodput_tok_s"]
            / max(sides["tier_off"]["goodput_tok_s"], 1e-9), 2),
    }


def bench_elastic_resume(steps_pre: int = 3, steps_post: int = 3,
                         seed: int = 0, tiny: bool = True) -> dict:
    """Elastic training resilience rung (docs/RESILIENCE.md "Elastic
    training"): save a crash-atomic checkpoint at world W, resume at W/2
    and 2W (clamped to the available device count), and record per resume
    world: RESUME LATENCY (the ``load_checkpoint`` wall — manifest
    verification, resharding reads, and the grad-accum-rescale step
    recompile), the wall time of the ``steps_post`` post-resume steps
    (the FIRST includes any rescale recompile; recorded as
    ``post_steps_s``), and STEPS-TO-RECOVER (post-resume steps whose
    eval loss deviates > 2% from the uninterrupted run's trajectory
    before the first match — 0 means the very first resumed step already
    tracks).  Headlines:
    ``resume_latency_s_max``, ``steps_to_recover_max``, ``loss_parity``
    (every compared step within rtol 1e-3)."""
    _import_jax()
    import numpy as np

    ndev = len(jax.devices())
    w_save = min(4, ndev)
    candidates = sorted({max(1, w_save // 2), min(ndev, w_save * 2)}
                        - {w_save})
    # the divisibility rule up front (docs/RESILIENCE.md): only worlds
    # that can preserve the recorded global batch are resumable; the
    # eval probe (8 rows) must shard over the world too
    tbs_probe = 1 * w_save * 2           # micro * w_save * gas (below)
    worlds = [w for w in candidates if tbs_probe % w == 0 and 8 % w == 0]
    if not worlds:
        return {"status": "skipped",
                "note": f"{ndev} device(s): no different elastic-valid "
                        "world to resume at"}
    layers, hidden = (2, 64) if tiny else (4, 256)
    seq = 32 if tiny else 128
    micro, gas = 1, 2
    tbs = micro * w_save * gas
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, size=(16 * tbs, seq)).astype(np.int32)
    probe = data[:8]

    def make(devs, gas_cfg):
        mesh = build_mesh(devices=jax.devices()[:devs])
        set_global_mesh(mesh)
        model = causal_lm("llama-tiny", mesh=mesh, num_layers=layers,
                          hidden_size=hidden, intermediate_size=2 * hidden,
                          num_heads=2, num_kv_heads=2, vocab_size=256,
                          max_seq_len=seq, remat=False)
        cfg = {"train_batch_size": micro * devs * gas_cfg,
               "train_micro_batch_size_per_gpu": micro,
               "gradient_accumulation_steps": gas_cfg,
               "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
               "zero_optimization": {"stage": 2},
               "steps_per_print": 10**9}
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, config=cfg, mesh=mesh,
            rng=jax.random.PRNGKey(seed))
        return engine

    def eval_loss(engine):
        engine.eval()
        try:
            return float(engine.forward((probe, probe)))
        finally:
            engine.train()

    def run_steps(engine, n, start=0):
        out = []
        for i in range(start, start + n):
            g = engine.config.gradient_accumulation_steps
            per = tbs // g
            for k in range(g):
                lo = (i * tbs + k * per) % (len(data) - per)
                engine.forward((data[lo:lo + per], data[lo:lo + per]))
            engine.step()
            out.append(eval_loss(engine))
        return out

    import tempfile

    with tempfile.TemporaryDirectory() as td:
        e = make(w_save, gas)
        run_steps(e, steps_pre)
        t0 = time.perf_counter()
        e.save_checkpoint(td, tag="elastic")
        save_s = time.perf_counter() - t0
        ref = run_steps(e, steps_post, start=steps_pre)

        resumes = {}
        parity = True
        for devs in worlds:
            # one bad world must not discard the others' measurements
            try:
                er = make(devs, gas)
                er.forward((data[:devs], data[:devs]))   # lazy-init state
                t0 = time.perf_counter()
                ckpt_dir, _ = er.load_checkpoint(td)
                load_s = time.perf_counter() - t0
                assert ckpt_dir is not None
                t0 = time.perf_counter()
                got = run_steps(er, steps_post, start=steps_pre)
                post_steps_s = time.perf_counter() - t0
            except Exception as exc:
                resumes[str(devs)] = {
                    "status": f"failed: {type(exc).__name__}",
                    "error": str(exc)[:160]}
                parity = False
                continue
            recover = 0
            for a, b in zip(ref, got):
                if abs(a - b) <= 0.02 * abs(a):
                    break
                recover += 1
            parity = parity and bool(np.allclose(ref, got, rtol=1e-3))
            resumes[str(devs)] = {
                "resume_latency_s": round(load_s, 4),
                "gas": er.config.gradient_accumulation_steps,
                "post_steps_s": round(post_steps_s, 4),
                "steps_to_recover": recover,
                "eval_loss_ref": [round(x, 6) for x in ref],
                "eval_loss_resumed": [round(x, 6) for x in got]}
        ok = [r for r in resumes.values() if "resume_latency_s" in r]
        if not ok:
            return {"status": "failed", "worlds": worlds,
                    "resumes": resumes}
        return {"status": "ok", "world_save": w_save, "worlds": worlds,
                "global_batch": tbs, "save_s": round(save_s, 4),
                "resume_latency_s_max": max(r["resume_latency_s"]
                                            for r in ok),
                "steps_to_recover_max": max(r["steps_to_recover"]
                                            for r in ok),
                "loss_parity": parity, "resumes": resumes}


def bench_fleet_chaos(num_requests: int = 24, num_slots: int = 2,
                      seed: int = 0, tiny: bool = False) -> dict:
    """Fleet resilience rung (ISSUE 13): the bimodal shared-prefix trace
    through the ROUTER over two live replicas, run twice — a clean pass,
    and a CHAOS pass where replica 1's serving loop is killed mid-trace
    and revived by a supervisor-style watcher (restart + resume; the
    in-process analog of ``tools/serve_supervisor.py``'s process
    restart).  Recorded per side: goodput, client-latency p50/p99, TTFT
    p99 (max over the replicas' registries), answered/shed counts.
    Headlines: ``goodput_retention`` (chaos/clean), ``restarts_observed``
    (must be >= 1 on the chaos side), ``answered_exactly_once`` +
    ``outputs_token_identical`` (every 200 matches ``generate()``;
    200 + 429 partition the trace — zero drops, zero duplicates)."""
    _import_jax()
    import json as _json
    import threading
    import urllib.error
    import urllib.request

    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import causal_lm
    from deepspeed_tpu.monitor.metrics import MetricsRegistry
    from deepspeed_tpu.serving import Router, RouterServer
    from deepspeed_tpu.testing.chaos import crash_on_call

    mesh = build_mesh(devices=jax.devices()[:1])
    set_global_mesh(mesh)
    rng = np.random.default_rng(seed + 17)
    if tiny:  # CPU smoke scale (tests/perf/test_fleet_chaos_bench.py)
        model = causal_lm("gpt2-small", mesh=mesh, num_layers=2,
                          hidden_size=128, intermediate_size=256,
                          num_heads=4, vocab_size=512)
        max_out, page_tokens = 96, 16
        sys_len, tail = 32, (3, 8)
        n_short, n_long = (3, 6), (8, 12)
    else:
        model = causal_lm("gpt2-small", mesh=mesh, vocab_size=50304)
        max_out, page_tokens = 1024, 0
        sys_len, tail = 256, (16, 96)
        n_short, n_long = (16, 64), (128, 192)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    V = model.config.vocab_size

    shared = rng.integers(0, V, size=sys_len).astype(np.int32)
    long_mask = rng.random(num_requests) < 0.25
    prompts, news = [], []
    for i in range(num_requests):
        t = rng.integers(0, V, size=int(rng.integers(tail[0], tail[1] + 1))
                         ).astype(np.int32)
        if rng.random() < 0.7:
            prompts.append(np.concatenate([shared, t]))
        else:
            prompts.append(rng.integers(
                0, V, size=sys_len // 2 + len(t)).astype(np.int32))
        news.append(int(rng.integers(n_long[0], n_long[1] + 1)
                        if long_mask[i]
                        else rng.integers(n_short[0], n_short[1] + 1)))
    ref = deepspeed_tpu.init_inference(
        model, config={"dtype": "bfloat16", "max_out_tokens": max_out})
    ref.set_params(params)
    want = [[int(t) for t in np.asarray(ref.generate(
                p[None], max_new_tokens=n, do_sample=False))[0, len(p):]]
            for p, n in zip(prompts, news)]

    def run_side(kill: bool) -> dict:
        replicas = []
        router = front = None
        try:
            for _ in range(2):
                s = deepspeed_tpu.init_serving(
                    model, config={"dtype": "bfloat16",
                                   "max_out_tokens": max_out,
                                   "kv_page_tokens": page_tokens,
                                   "max_queue_depth": max(4, num_requests // 3),
                                   "shed_retry_after_s": 0.2},
                    num_slots=num_slots, decode_block_tokens=4,
                    metrics_port=0, registry=MetricsRegistry().enable(),
                    private_health=True, serve_loop=True)
                s.set_params(params)
                # warm the serving programs BEFORE the measured trace (one
                # long + one short prompt covers the pow2 prefill buckets +
                # the decode block): the recorded TTFT must not be compile
                # time
                warms = [s.submit(prompts[0], max_new_tokens=2),
                         s.submit(prompts[0][:20], max_new_tokens=2)]
                deadline = time.perf_counter() + 240
                while not all(w.done for w in warms) \
                        and time.perf_counter() < deadline:
                    time.sleep(0.005)
                s._registry.reset()
                replicas.append(s)
            router = Router(
                [f"r{i}={s.metrics_server.url}"
                 for i, s in enumerate(replicas)],
                registry=MetricsRegistry().enable(), dispatch_rounds=8,
                retry_backoff=0.02, poll_interval=0.05, request_timeout=120.0)
            router.refresh()
            router.start()
            front = RouterServer(router).start()
            results = [None] * num_requests
            client_lat = [None] * num_requests

            def client(i):
                # a well-behaved client: waits out 429 Retry-After and backs
                # off on router-level 503 (both mean "no answer produced") —
                # bounded retries, then the last status stands
                t0 = time.perf_counter()
                req = urllib.request.Request(
                    front.url + "/generate",
                    data=_json.dumps(
                        {"prompt": prompts[i].tolist(),
                         "max_new_tokens": news[i],
                         "session": f"sess-{i % 4}",
                         "timeout": 90}).encode(),
                    headers={"Content-Type": "application/json"})
                for _attempt in range(8):
                    try:
                        with urllib.request.urlopen(req, timeout=120) as resp:
                            results[i] = (resp.status, _json.load(resp))
                        break
                    except urllib.error.HTTPError as exc:
                        try:
                            body = _json.load(exc)
                        except Exception:
                            body = {}
                        results[i] = (exc.code, body)
                        if exc.code == 429:
                            time.sleep(min(float(
                                body.get("retry_after_s", 0.2)), 0.5))
                            continue
                        if exc.code == 503:
                            time.sleep(0.2)
                            continue
                        break
                    except OSError:
                        break
                client_lat[i] = time.perf_counter() - t0

            restarts = {"n": 0}
            stop = threading.Event()

            def watcher():
                while not stop.is_set():
                    for s in replicas:
                        if s._loop_crashed and not s._loop_alive():
                            time.sleep(0.1)
                            s.start_loop()
                            s.resume_admission()
                            restarts["n"] += 1
                    time.sleep(0.02)

            wt = threading.Thread(target=watcher, daemon=True)
            wt.start()
            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(num_requests)]
            t0 = time.perf_counter()

            def launch_staggered():
                for t in threads:
                    t.start()
                    time.sleep(0.03)
                for t in threads:
                    t.join(timeout=240)

            try:
                if kill:
                    with crash_on_call(replicas[1], "step", n=3):
                        launch_staggered()
                else:
                    launch_staggered()
            finally:
                stop.set()
                wt.join(timeout=10)
            span = time.perf_counter() - t0
            answered, sheds, identical, toks = 0, 0, True, 0
            for i, r in enumerate(results):
                if r is None:
                    continue
                code, body = r
                if code == 200:
                    answered += 1
                    toks += len(body.get("tokens", []))
                    identical = identical and body.get("tokens") == want[i]
                elif code == 429:
                    sheds += 1
            ttft_p99 = 0.0
            for s in replicas:
                snap = s._registry.snapshot()
                ttft = snap.get("ds_serve_ttft_seconds") or {}
                ttft_p99 = max(ttft_p99, float(ttft.get("p99", 0.0)))
            lat = sorted(x for x in client_lat if x is not None)
            out = {
                "goodput_tok_s": round(toks / max(span, 1e-9), 1),
                "makespan_s": round(span, 3),
                "answered": answered, "shed_429": sheds,
                "exactly_once": answered + sheds == num_requests,
                "token_identical": identical,
                "ttft_p99_s": round(ttft_p99, 4),
                "client_p50_s": round(lat[len(lat) // 2], 4) if lat else 0.0,
                "client_p99_s": round(lat[(len(lat) * 99) // 100], 4)
                if lat else 0.0,
                "restarts_observed": restarts["n"],
                "router_retries": int(
                    router.registry.get("ds_router_retries_total").value),
            }
            return out
        finally:
            # a mid-side exception (client assertion, registry miss)
            # must not leak two live engines + loops + HTTP servers
            # into the rest of the bench run
            if front is not None:
                front.stop()
            if router is not None:
                router.stop()
            for s in replicas:
                s.close()

    clean = run_side(kill=False)
    chaos = run_side(kill=True)
    return {
        "workload": {"num_requests": num_requests, "num_slots": num_slots,
                     "replicas": 2, "shared_prefix_frac": 0.7,
                     "system_prompt_tokens": sys_len, "seed": seed},
        "clean": clean,
        "chaos": chaos,
        "goodput_retention": round(
            chaos["goodput_tok_s"] / max(clean["goodput_tok_s"], 1e-9), 3),
        "ttft_p99_clean_s": clean["ttft_p99_s"],
        "ttft_p99_chaos_s": chaos["ttft_p99_s"],
        "restarts_observed": chaos["restarts_observed"],
        "answered_exactly_once": clean["exactly_once"]
        and chaos["exactly_once"],
        "outputs_token_identical": clean["token_identical"]
        and chaos["token_identical"],
    }


def bench_disagg_serving(num_requests: int = 16, num_slots: int = 4,
                         seed: int = 0, tiny: bool = False) -> dict:
    """Disaggregated prefill/decode serving rung (ISSUE 19): the bimodal
    shared-prefix trace through the router over a MONOLITHIC fleet (2
    ``both`` replicas) and a ROLE-SPLIT fleet (2 prefill + 2 decode,
    int8 KV-page handoff over /kv_offer + /kv_adopt), each driven both
    with plain and with STREAMING ``/generate`` — the role-split ×
    streaming grid.  Recorded per cell: goodput, TTFT p50/p99 (engine
    histogram on the plain sides; client-observed first-chunk latency on
    the streaming sides — the user-visible number streaming exists for),
    token identity vs single-engine ``generate()``.  The role-split
    fleet additionally records the KV handoff ledger: wire bytes (int8 +
    scale planes) vs the dense twin, pages shipped/adopted.  Headlines:
    ``handoff_compression`` (dense/wire, ~2x at bf16), ``ttft_stream_
    over_total`` (first chunk lands well before the full answer), and
    the grid's ``outputs_token_identical`` conjunction."""
    _import_jax()
    import json as _json
    import threading
    import urllib.error
    import urllib.request

    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import causal_lm
    from deepspeed_tpu.monitor.metrics import MetricsRegistry
    from deepspeed_tpu.serving import Router, RouterServer

    mesh = build_mesh(devices=jax.devices()[:1])
    set_global_mesh(mesh)
    rng = np.random.default_rng(seed + 23)
    if tiny:  # CPU smoke scale (tests/perf/test_disagg_serving_bench.py)
        model = causal_lm("gpt2-small", mesh=mesh, num_layers=2,
                          hidden_size=128, intermediate_size=256,
                          num_heads=4, vocab_size=512)
        max_out, page_tokens = 96, 16
        sys_len, tail = 32, (3, 8)
        n_short, n_long = (8, 16), (24, 32)
    else:
        model = causal_lm("gpt2-small", mesh=mesh, vocab_size=50304)
        max_out, page_tokens = 1024, 16
        sys_len, tail = 256, (16, 96)
        n_short, n_long = (16, 64), (128, 192)
    params = jax.jit(model.init)(jax.random.PRNGKey(seed))
    V = model.config.vocab_size

    shared = rng.integers(0, V, size=sys_len).astype(np.int32)
    long_mask = rng.random(num_requests) < 0.25
    prompts, news = [], []
    for i in range(num_requests):
        t = rng.integers(0, V, size=int(rng.integers(tail[0], tail[1] + 1))
                         ).astype(np.int32)
        if rng.random() < 0.7:
            prompts.append(np.concatenate([shared, t]))
        else:
            prompts.append(rng.integers(
                0, V, size=sys_len // 2 + len(t)).astype(np.int32))
        news.append(int(rng.integers(n_long[0], n_long[1] + 1)
                        if long_mask[i]
                        else rng.integers(n_short[0], n_short[1] + 1)))
    # quantize_kv_cache=True everywhere: the cache planes are int8 +
    # scale already, so the int8 wire handoff is LOSSLESS and the
    # role-split outputs must match this reference bit for bit
    cfg_common = {"dtype": "bfloat16", "max_out_tokens": max_out,
                  "kv_page_tokens": page_tokens,
                  "quantize_kv_cache": True}
    ref = deepspeed_tpu.init_inference(model, config=dict(cfg_common))
    ref.set_params(params)
    want = [[int(t) for t in np.asarray(ref.generate(
                p[None], max_new_tokens=n, do_sample=False))[0, len(p):]]
            for p, n in zip(prompts, news)]

    def run_fleet(role_split: bool) -> dict:
        replicas = []
        router = front = None
        roles = (["prefill", "prefill", "decode", "decode"] if role_split
                 else ["both", "both"])
        try:
            for role in roles:
                s = deepspeed_tpu.init_serving(
                    model, config=dict(cfg_common,
                                       max_queue_depth=num_requests + 4),
                    num_slots=num_slots, decode_block_tokens=4,
                    role=role, metrics_port=0,
                    registry=MetricsRegistry().enable(),
                    private_health=True, serve_loop=True)
                s.set_params(params)
                warms = [s.submit(prompts[0], max_new_tokens=2),
                         s.submit(prompts[0][:20], max_new_tokens=2)]
                deadline = time.perf_counter() + 240
                while not all(w.done for w in warms) \
                        and time.perf_counter() < deadline:
                    time.sleep(0.005)
                s._registry.reset()
                replicas.append(s)
            router = Router(
                [f"{r}{i}@{r}={s.metrics_server.url}"
                 for i, (r, s) in enumerate(zip(roles, replicas))],
                registry=MetricsRegistry().enable(), dispatch_rounds=8,
                retry_backoff=0.02, poll_interval=0.05,
                request_timeout=120.0)
            router.refresh()
            router.start()
            front = RouterServer(router).start()
            # warm the FULL dispatch paths through the front (every
            # prefill shape bucket, the handoff path, decode, the
            # stream relay) so the measured variants see steady-state
            # shapes, not XLA compiles
            _drive_trace(front, prompts, [4] * len(prompts), want,
                         False, replicas)
            _drive_trace(front, prompts[:2], news[:2], want[:2],
                         True, replicas)
            out = {}
            for stream in (False, True):
                for s in replicas:
                    s._registry.reset()
                    # the front warm-up filled the decode tries, and
                    # /kv_offer dedupes pages the receiver already
                    # holds — drop the decode-side tries so each
                    # measured variant re-exercises the handoff wire
                    # (XLA shapes stay warm; that was the warm-up's job)
                    if role_split and s.role == "decode":
                        s.prefix_cache.clear()
                router.registry.reset()
                out["stream" if stream else "plain"] = _drive_trace(
                    front, prompts, news, want, stream, replicas)
            # the role-split handoff ledger accumulates across BOTH
            # variants (each reset clears it, so scrape per variant)
            return out
        finally:
            if front is not None:
                front.stop()
            if router is not None:
                router.stop()
            for s in replicas:
                s.close()

    def _drive_trace(front, prompts, news, want, stream, replicas):
        results = [None] * len(prompts)
        client_lat = [None] * len(prompts)
        first_tok = [None] * len(prompts)

        def client(i):
            t0 = time.perf_counter()
            payload = {"prompt": prompts[i].tolist(),
                       "max_new_tokens": news[i],
                       "session": f"sess-{i % 4}", "timeout": 90}
            if stream:
                payload["stream"] = True
            req = urllib.request.Request(
                front.url + "/generate",
                data=_json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"})
            for _attempt in range(8):
                try:
                    with urllib.request.urlopen(req, timeout=120) as resp:
                        if stream:
                            toks = []
                            for line in resp:
                                ev = _json.loads(line)
                                if ev.get("tokens"):
                                    if first_tok[i] is None:
                                        first_tok[i] = (time.perf_counter()
                                                        - t0)
                                    toks.extend(ev["tokens"])
                                elif ev.get("error"):
                                    results[i] = (int(ev.get("status")
                                                      or 503), ev)
                                    break
                                elif ev.get("done"):
                                    results[i] = (200, {"tokens": toks})
                                    break
                        else:
                            results[i] = (resp.status, _json.load(resp))
                    if results[i] is not None and results[i][0] != 503:
                        break
                except urllib.error.HTTPError as exc:
                    try:
                        body = _json.load(exc)
                    except Exception:
                        body = {}
                    results[i] = (exc.code, body)
                    if exc.code in (429, 503):
                        time.sleep(0.2)
                        continue
                    break
                except OSError:
                    break
            client_lat[i] = time.perf_counter() - t0

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
            time.sleep(0.03)
        for t in threads:
            t.join(timeout=240)
        span = time.perf_counter() - t0
        answered, identical, toks = 0, True, 0
        for i, r in enumerate(results):
            if r is None:
                continue
            code, body = r
            if code == 200:
                answered += 1
                toks += len(body.get("tokens", []))
                identical = identical and body.get("tokens") == want[i]
        ratios = []
        if stream:
            ft = sorted(x for x in first_tok if x is not None)
            ttft_p50 = ft[len(ft) // 2] if ft else 0.0
            ttft_p99 = ft[(len(ft) * 99) // 100] if ft else 0.0
            ratios = sorted(f / max(t, 1e-9)
                            for f, t in zip(first_tok, client_lat)
                            if f is not None and t is not None)
        else:
            ttft_p50 = ttft_p99 = 0.0
            for s in replicas:
                snap = s._registry.snapshot()
                h = snap.get("ds_serve_ttft_seconds") or {}
                ttft_p50 = max(ttft_p50, float(h.get("p50", 0.0)))
                ttft_p99 = max(ttft_p99, float(h.get("p99", 0.0)))
        rec = {"goodput_tok_s": round(toks / max(span, 1e-9), 1),
               "makespan_s": round(span, 3),
               "answered": answered,
               "token_identical": identical,
               "ttft_p50_s": round(ttft_p50, 4),
               "ttft_p99_s": round(ttft_p99, 4),
               "client_p50_s": round(sorted(
                   x for x in client_lat if x is not None)
                   [answered // 2], 4) if answered else 0.0}
        if ratios:
            # per-request TTFT / total-latency: the user-visible claim
            # streaming makes — the first chunk lands well before the
            # full answer (median of per-request ratios, not a ratio of
            # mismatched percentiles)
            rec["ttft_over_total_p50"] = round(
                ratios[len(ratios) // 2], 4)
        # KV handoff ledger (role-split fleets only; zero elsewhere)
        wire = dense = shipped = adopted = resumes = 0.0
        for s in replicas:
            snap = s._registry.snapshot()
            fam = snap.get("ds_serve_kv_handoff_bytes_total") or {}
            if isinstance(fam, dict):
                dense += float(fam.get('{dtype="dense"}', 0) or 0)
                wire += sum(float(v or 0) for k, v in fam.items()
                            if k != '{dtype="dense"}')
            shipped += float(snap.get(
                "ds_serve_kv_handoff_pages_total", 0) or 0)
            adopted += float(snap.get(
                "ds_serve_kv_adopted_pages_total", 0) or 0)
            resumes += float(snap.get(
                "ds_serve_stream_resumes_total", 0) or 0)
        if shipped:
            rec.update({"handoff_wire_bytes": int(wire),
                        "handoff_dense_bytes": int(dense),
                        "handoff_pages_shipped": int(shipped),
                        "handoff_pages_adopted": int(adopted)})
        if resumes:
            rec["stream_resumes"] = int(resumes)
        return rec

    mono = run_fleet(role_split=False)
    disagg = run_fleet(role_split=True)
    wire = disagg["stream"].get("handoff_wire_bytes", 0) \
        + disagg["plain"].get("handoff_wire_bytes", 0)
    dense = disagg["stream"].get("handoff_dense_bytes", 0) \
        + disagg["plain"].get("handoff_dense_bytes", 0)
    identical = all(side[v]["token_identical"]
                    for side in (mono, disagg) for v in ("plain", "stream"))
    ttft_over_total = disagg["stream"].get("ttft_over_total_p50", 0.0)
    return {
        "workload": {"num_requests": num_requests, "num_slots": num_slots,
                     "mono_replicas": 2, "prefill_replicas": 2,
                     "decode_replicas": 2, "shared_prefix_frac": 0.7,
                     "system_prompt_tokens": sys_len,
                     "kv_page_tokens": page_tokens, "seed": seed},
        "mono": mono,
        "disagg": disagg,
        "handoff_compression": round(dense / wire, 3) if wire else 0.0,
        "handoff_wire_bytes": int(wire),
        "handoff_dense_bytes": int(dense),
        # like-for-like: role-split vs monolithic, both streaming (the
        # plain sides ride in the record for the off-axis of the grid)
        "disagg_goodput_ratio": round(
            disagg["stream"]["goodput_tok_s"]
            / max(mono["stream"]["goodput_tok_s"], 1e-9), 3),
        # streaming's reason to exist: the first chunk lands well before
        # the full answer (TTFT < total latency, client-observed)
        "ttft_stream_over_total": ttft_over_total,
        "outputs_token_identical": identical,
    }


def bench_overlap_rung(steps: int = 4, warmup: int = 2) -> dict:
    """ZeRO-3 compute/collective overlap on/off ablation on the 1.34B
    training scenario (ROADMAP open item 1; runtime/zero/overlap.py).

    Runs the SAME workload twice over an fsdp mesh spanning every local
    device — once with GSPMD-placed collectives (``overlap_comm: false``),
    once with the layer-chunked explicit schedule (``overlap_comm: true``)
    — and records per side: tokens/sec, MFU (live ``ds_train_mfu`` gauge),
    and the device-profile ``gap_share`` / ``gap_plus_comm_share`` (the
    exact numbers the overlap schedule is supposed to shrink).  The headline
    ``overlap_speedup`` plus the two device-phase rows land in BENCH_JSON.

    On CPU runners the 1.34B architecture is scaled to smoke size (the
    bucket structure, collective schedule, and phase accounting are what
    the CPU row exercises — absolute rates are not comparable to TPU).
    Needs >1 device for the fsdp collectives to exist; the parent launches
    this in a child process so a CPU parent can force a virtual 8-device
    mesh without re-initializing its own backend.
    """
    _import_jax()
    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import causal_lm
    from deepspeed_tpu.monitor.metrics import get_registry

    t0 = time.perf_counter()
    try:
        devs = jax.devices()
        if len(devs) < 2:
            return {"status": "skipped: needs >1 device for fsdp "
                              "collectives", "devices": len(devs)}
        on_tpu = jax.default_backend() != "cpu"
        W = len(devs)
        mesh = build_mesh(fsdp=W, devices=devs)
        set_global_mesh(mesh)
        if on_tpu:
            over = {}
            micro, accum, seq = 2, 2, 1024
            bucket_layers = 2
        else:
            over = dict(num_layers=4, hidden_size=128,
                        intermediate_size=256, num_heads=4, num_kv_heads=4,
                        vocab_size=512, max_seq_len=128)
            micro, accum, seq = 1, 2, 64
            bucket_layers = 1
        registry = get_registry()
        results = {}
        n_params = 0
        for side, overlap in (("off", False), ("on", True)):
            model = causal_lm("llama-1b4", mesh=mesh, **over)
            cfg_m = model.config
            ds_config = {
                "train_micro_batch_size_per_gpu": micro,
                "gradient_accumulation_steps": accum,
                "bf16": {"enabled": bool(on_tpu)},
                "optimizer": {"type": "AdamW", "params": {"lr": 2e-4}},
                "gradient_clipping": 1.0,
                "zero_optimization": {
                    "stage": 3, "overlap_comm": overlap,
                    "overlap_bucket_layers": bucket_layers,
                    "stage3_param_persistence_threshold": 0},
                "comms_logger": {"enabled": True},
                "steps_per_print": 10**9,
            }
            engine, _, _, _ = deepspeed_tpu.initialize(
                model=model, config=ds_config, mesh=mesh,
                rng=jax.random.PRNGKey(11))
            tokens = jax.random.randint(
                jax.random.PRNGKey(1), (accum, micro * W, seq), 0,
                cfg_m.vocab_size)
            batch = (tokens, tokens)
            for _ in range(warmup):
                engine.train_step(batch)
            if overlap and not engine._overlap:
                # a silent fallback here would benchmark off-vs-off and
                # report a bogus ~1.0x speedup with loss_parity true
                return {"status": "failed: overlap_comm did not activate "
                                  "on the 'on' side",
                        "reason": engine._overlap_reason}
            sync(engine.state.params)
            registry.reset()
            engine._flops_meter.reset_clock()
            t1 = time.perf_counter()
            for _ in range(steps):
                engine.train_step(batch)
            sync(engine.state.params)
            dt = (time.perf_counter() - t1) / steps
            n_params = sum(x.size for x in
                           jax.tree.leaves(engine.state.params))
            tps = accum * micro * W * seq / dt
            row = {"tokens_per_sec": round(tps, 1),
                   "step_ms": round(dt * 1e3, 1),
                   "overlap_active": bool(engine._overlap),
                   "loss": round(float(engine._last_loss), 6)}
            tm = collect_train_metrics(registry)
            if tm.get("mfu") is not None:
                row["mfu"] = round(tm["mfu"], 5)
            dp = capture_device_profile(
                lambda: engine.train_step(batch), steps=2,
                tag=f"overlap_{side}")
            if dp and "per_step" in dp:
                row["gap_share"] = dp.get("gap_share")
                per = dp["per_step"]
                win = sum(per.values())
                if win > 0:
                    row["gap_plus_comm_share"] = round(
                        (per["gap_s"] + per["comm_s"]) / win, 4)
                row["device_profile"] = dp
            # comm_s with an explicit source label (ROADMAP bench-honesty
            # note): device-true per-step seconds when a perfetto capture
            # exists (the same spans that fill ds_comm_<op>_device_seconds),
            # else the analytic comm-plan priced at the assumed link
            # bandwidth — never a silent 0 on CPU runners.
            dev_comm = ((dp or {}).get("per_step") or {}).get("comm_s", 0.0)
            if dev_comm > 0.0:
                row["comm_s"] = round(dev_comm, 6)
                row["comm_s_source"] = "device"
            else:
                from deepspeed_tpu.monitor.goodput_core import (
                    analytic_comm_seconds)

                plan = engine._comm_plan or {}
                gbps = engine._gp_comm_gbps
                row["comm_s"] = round(
                    analytic_comm_seconds(plan.get("micro"), gbps) * accum
                    + analytic_comm_seconds(plan.get("boundary"), gbps), 6)
                row["comm_s_source"] = "analytic"
            results[side] = row
            engine = model = None
            import gc

            gc.collect()
        speedup = (results["on"]["tokens_per_sec"]
                   / max(results["off"]["tokens_per_sec"], 1e-9))
        return {"status": "ok", "zero_stage": 3, "devices": W,
                "backend": jax.default_backend(),
                "params_b": round(n_params / 1e9, 4),
                "micro_batch": micro, "grad_accum": accum, "seq": seq,
                "steps": steps, "bucket_layers": bucket_layers,
                "off": results["off"], "on": results["on"],
                "overlap_speedup": round(speedup, 3),
                "loss_parity": bool(np.allclose(
                    results["on"]["loss"], results["off"]["loss"],
                    rtol=1e-3)),
                "scaled_for_cpu": not on_tpu}
    except Exception as exc:
        return {"status": f"failed: {type(exc).__name__}",
                "error": str(exc)[:300],
                "elapsed_s": round(time.perf_counter() - t0, 1)}


def _check_parent_holds_no_jax() -> None:
    """A child rung needs the chip, and a chip belongs to one process: a
    parent that has imported jax may already hold it.  Checked at every
    spawn instead of trusting the order of ``main``."""
    if "jax" in sys.modules:
        raise RuntimeError(
            "bench.py: about to start a child rung, but this process has "
            "already imported jax and may hold the chip the child needs")


def _run_child_rung(env_key: str) -> dict:
    """Run one bench rung in a child process keyed by ``env_key`` (the
    env var naming the child's JSON output file — ``main`` dispatches on
    it): a CPU parent gets a virtual 8-device mesh via XLA_FLAGS (which
    must be set before jax initializes — impossible in-process), and on
    TPU a child abort cannot kill the 125M headline (same isolation
    story as the 1.34B ladder)."""
    import subprocess
    import tempfile

    _check_parent_holds_no_jax()
    fd, out = tempfile.mkstemp(suffix=".json")
    os.close(fd)
    os.unlink(out)
    env = dict(os.environ, **{env_key: out})
    if os.environ.get("JAX_PLATFORMS", "").lower() == "cpu":
        env["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=8 "
            "--xla_cpu_enable_concurrency_optimized_scheduler=false "
            + env.get("XLA_FLAGS", ""))
    try:
        proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                              env=env, timeout=1800, capture_output=True,
                              text=True)
        try:
            with open(out) as fh:
                return json.load(fh)
        except (OSError, json.JSONDecodeError):
            return {"status": f"failed: child exited {proc.returncode} "
                              "without a result",
                    "stderr_tail": proc.stderr[-400:]}
    except subprocess.TimeoutExpired:
        return {"status": "failed: child timeout (1800s)"}


def _run_overlap_subprocess() -> dict:
    return _run_child_rung("DSTPU_BENCH_OVERLAP_OUT")


def bench_quant_comm(steps: int = 3, warmup: int = 1) -> dict:
    """Dense vs int8 quantized-collective ablation (ROADMAP item 2;
    comm/collectives_q.py — ZeRO++ arXiv:2306.10209, EQuARX
    arXiv:2506.17615).

    Two opted-in call-site families on the same tiny-LM workload over
    every local device, each run dense then quantized:

    - ``all_reduce`` — the ZeRO stage-1 boundary gradient sync on a dp
      mesh: dense GSPMD psum vs the engine's manual ``q_all_reduce``
      (error feedback ON — the convergence-safe configuration);
    - ``gather_rs`` — the overlap schedule's per-bucket forward gathers
      + AD-transpose reduce-scatters at ZeRO stage 3 on an fsdp mesh:
      dense vs int8 transport.

    Per side: tokens/s + final loss.  Per quantized op: wire bytes vs
    dense-equivalent bytes — BOTH series recorded on the same trace
    (``ds_comm_<op>_bytes_total`` / ``ds_comm_<op>_dense_bytes_total``)
    — plus the busbw gauge when populated.  Headlines: per-op
    ``compression`` (dense/wire, the ~2-4x acceptance number) and per-
    family ``loss_parity``.  CPU-meaningful: bytes and parity are
    backend-independent; rates are not comparable to TPU.
    """
    _import_jax()
    import gc

    import numpy as np

    import deepspeed_tpu
    from deepspeed_tpu.models import causal_lm
    from deepspeed_tpu.monitor.metrics import get_registry

    t0 = time.perf_counter()
    devs = jax.devices()
    if len(devs) < 2:
        return {"status": "skipped: needs >1 device for collectives",
                "devices": len(devs)}
    W = len(devs)
    on_tpu = jax.default_backend() != "cpu"
    registry = get_registry()

    def fam_sum(metrics, name) -> float:
        v = metrics.get(name, 0)
        if isinstance(v, dict):
            return float(sum(x for x in v.values()
                             if isinstance(x, (int, float))))
        return float(v or 0)

    def snapshot() -> dict:
        return json.loads(registry.statz_json())["metrics"]

    if on_tpu:
        over = {}
        micro, accum, seq = 2, 2, 512
    else:
        over = dict(num_layers=4, hidden_size=128, intermediate_size=256,
                    num_heads=4, vocab_size=512, max_seq_len=128)
        micro, accum, seq = 1, 2, 64

    def run_side(mesh_kw, stage, overlap, quant_cfg, q_active_check):
        mesh = build_mesh(devices=devs, **mesh_kw)
        set_global_mesh(mesh)
        model = causal_lm("gpt2-small", mesh=mesh, **over)
        ds_config = {
            "train_micro_batch_size_per_gpu": micro,
            "gradient_accumulation_steps": accum,
            "optimizer": {"type": "AdamW", "params": {"lr": 2e-4}},
            "gradient_clipping": 1.0,
            "bf16": {"enabled": bool(on_tpu)},
            "zero_optimization": {
                "stage": stage, "overlap_comm": overlap,
                "overlap_bucket_layers": 1,
                "stage3_param_persistence_threshold": 0},
            "comms_logger": {"enabled": True},
            "steps_per_print": 10**9,
        }
        if quant_cfg:
            ds_config["comm_quantization"] = quant_cfg
        registry.reset()
        from deepspeed_tpu.comm.comm import comms_logger
        comms_logger.reset()
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, config=ds_config, mesh=mesh,
            rng=jax.random.PRNGKey(11))
        tokens = jax.random.randint(jax.random.PRNGKey(1),
                                    (accum, micro * W, seq), 0,
                                    model.config.vocab_size)
        batch = (tokens, tokens)
        for _ in range(warmup):
            engine.train_step(batch)
        if quant_cfg:
            err = q_active_check(engine)
            if err:
                return None, {"status": f"failed: {err}"}
        sync(engine.state.params)
        t1 = time.perf_counter()
        for _ in range(steps):
            engine.train_step(batch)
        sync(engine.state.params)
        dt = (time.perf_counter() - t1) / steps
        row = {"tokens_per_sec": round(accum * micro * W * seq / dt, 1),
               "step_ms": round(dt * 1e3, 1),
               "loss": round(float(engine._last_loss), 6)}
        metrics = snapshot()
        engine = model = None
        gc.collect()
        return row, metrics

    def check_qcomm_grads(engine):
        if not engine._qcomm_grads:
            return ("comm_quantization.grad_all_reduce did not activate: "
                    f"{engine._qcomm_grads_reason}")
        return None

    def check_overlap_q(engine):
        if not engine._overlap:
            return f"overlap_comm did not activate: {engine._overlap_reason}"
        plan = engine._comm_plan or {"micro": []}
        if not any(e[0].startswith("q_") for e in plan["micro"]):
            return "overlap comm plan carries no quantized entries"
        return None

    families = {}
    compression = {}
    parity = {}
    for fam, mesh_kw, stage, overlap, qcfg, check, q_ops, dense_op in (
            ("all_reduce", {"dp": W}, 1, False,
             {"grad_all_reduce": True, "error_feedback": True},
             check_qcomm_grads, ("q_all_reduce",), "all_reduce"),
            ("gather_rs", {"fsdp": W}, 3, True,
             {"all_gather": True, "reduce_scatter": True},
             check_overlap_q, ("q_all_gather", "q_reduce_scatter"),
             "all_gather")):
        dense_row, dense_metrics = run_side(mesh_kw, stage, overlap, None,
                                            check)
        if dense_row is None:
            return dense_metrics
        q_row, q_metrics = run_side(mesh_kw, stage, overlap, qcfg, check)
        if q_row is None:
            return q_metrics
        ops = {}
        for op in q_ops:
            wire = fam_sum(q_metrics, f"ds_comm_{op}_bytes_total")
            dense_eq = fam_sum(q_metrics,
                               f"ds_comm_{op}_dense_bytes_total")
            entry = {"wire_bytes": int(wire),
                     "dense_bytes": int(dense_eq)}
            if wire and dense_eq:
                entry["compression"] = round(dense_eq / wire, 3)
                compression[op] = entry["compression"]
            busbw = q_metrics.get(f"ds_comm_{op}_busbw_gbps")
            if busbw:
                entry["busbw_gbps"] = round(float(busbw), 3)
            ops[op] = entry
        dense_bytes_observed = fam_sum(
            dense_metrics, f"ds_comm_{dense_op}_bytes_total")
        lp = abs(q_row["loss"] - dense_row["loss"]) \
            <= 0.05 * max(abs(dense_row["loss"]), 1e-9)
        parity[fam] = bool(lp)
        families[fam] = {
            "dense": dict(dense_row,
                          dense_op_bytes=int(dense_bytes_observed)),
            "int8": q_row, "ops": ops, "loss_parity": bool(lp),
            "speedup": round(q_row["tokens_per_sec"]
                             / max(dense_row["tokens_per_sec"], 1e-9), 4)}
    return {"status": "ok", "devices": W,
            "backend": jax.default_backend(),
            "steps": steps, "micro_batch": micro, "grad_accum": accum,
            "seq": seq,
            "compression": compression,
            "loss_parity": parity,
            "families": families,
            "elapsed_s": round(time.perf_counter() - t0, 1)}


def _run_quant_comm_subprocess() -> dict:
    return _run_child_rung("DSTPU_BENCH_QUANTCOMM_OUT")


def bench_pipe(steps: int = 3, warmup: int = 1) -> dict:
    """Dense vs int8 stage-boundary ablation for the full-manual pipeline
    (ISSUE 16; runtime/pipe/spmd.py — the 1F1B fused schedule with
    ppermute boundary rings).

    pp in {2, 4} over all local devices (fsdp absorbs the rest), each
    depth run with a dense fp32 boundary then the int8 carry codec
    (``comm_quantization.pipeline``).  Per side: tokens/s + final loss;
    per rung: the ANALYTIC schedule bubble share ((pp-1)/T, T =
    M + 2(pp-1) for 1F1B) and the engine-committed boundary byte ledger —
    ``ds_comm_ppermute_bytes_total`` dense vs
    ``ds_comm_q_ppermute_bytes_total`` + its dense-twin series on the
    quantized side.  Headlines: per-rung ``compression`` (dense-
    equivalent / wire, the >=2x acceptance number at fp32 — ~3.9x for
    int8 codes + fp32 block scales) and ``loss_parity``.  CPU-meaningful:
    bytes, bubble share and parity are backend-independent; rates are
    not comparable to TPU.
    """
    _import_jax()
    import gc

    import deepspeed_tpu
    from deepspeed_tpu.models import causal_lm
    from deepspeed_tpu.monitor.metrics import get_registry

    t0 = time.perf_counter()
    devs = jax.devices()
    if len(devs) < 4:
        return {"status": "skipped: needs >=4 devices for pp x fsdp",
                "devices": len(devs)}
    W = len(devs)
    on_tpu = jax.default_backend() != "cpu"
    registry = get_registry()

    def fam_sum(metrics, name) -> float:
        v = metrics.get(name, 0)
        if isinstance(v, dict):
            return float(sum(x for x in v.values()
                             if isinstance(x, (int, float))))
        return float(v or 0)

    # fp32 end to end (no bf16): the acceptance pin is the fp32 boundary's
    # ~3.9x int8 compression, and parity tolerances assume fp32 math
    if on_tpu:
        over = {}
        micro, accum, seq, M = 2, 2, 512, 4
    else:
        over = dict(num_layers=4, hidden_size=128, intermediate_size=256,
                    num_heads=4, num_kv_heads=2, vocab_size=512,
                    max_seq_len=128)
        micro, accum, seq, M = 1, 2, 64, 4

    def run_side(pp, quant):
        mesh = build_mesh(pp=pp, fsdp=W // pp, devices=devs)
        set_global_mesh(mesh)
        model = causal_lm("llama-tiny", mesh=mesh, pp_schedule="1f1b",
                          pp_microbatches=M, **over)
        ds_config = {
            "train_micro_batch_size_per_gpu": micro,
            "gradient_accumulation_steps": accum,
            "optimizer": {"type": "AdamW", "params": {"lr": 2e-4}},
            "gradient_clipping": 1.0,
            "zero_optimization": {"stage": 1},
            "comms_logger": {"enabled": True},
            "steps_per_print": 10**9,
        }
        if quant:
            ds_config["comm_quantization"] = {"pipeline": True}
        registry.reset()
        from deepspeed_tpu.comm.comm import comms_logger
        comms_logger.reset()
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, config=ds_config, mesh=mesh,
            rng=jax.random.PRNGKey(11))
        if quant and not engine.module.config.pp_boundary_q:
            return None, {"status": "failed: comm_quantization.pipeline "
                                    "did not arm pp_boundary_q"}
        tokens = jax.random.randint(jax.random.PRNGKey(1),
                                    (accum, micro * W, seq), 0,
                                    model.config.vocab_size)
        batch = (tokens, tokens)
        for _ in range(warmup):
            engine.train_step(batch)
        sync(engine.state.params)
        t1 = time.perf_counter()
        for _ in range(steps):
            engine.train_step(batch)
        sync(engine.state.params)
        dt = (time.perf_counter() - t1) / steps
        row = {"tokens_per_sec": round(accum * micro * W * seq / dt, 1),
               "step_ms": round(dt * 1e3, 1),
               "loss": round(float(engine._last_loss), 6)}
        metrics = json.loads(registry.statz_json())["metrics"]
        engine = model = None
        gc.collect()
        return row, metrics

    rungs = {}
    compression = {}
    parity = {}
    bubble = {}
    for pp in (2, 4):
        if W % pp or W // pp < 1:
            continue
        dense_row, dense_metrics = run_side(pp, False)
        if dense_row is None:
            return dense_metrics
        q_row, q_metrics = run_side(pp, True)
        if q_row is None:
            return q_metrics
        wire = fam_sum(q_metrics, "ds_comm_q_ppermute_bytes_total")
        dense_eq = fam_sum(q_metrics,
                           "ds_comm_q_ppermute_dense_bytes_total")
        key = f"pp{pp}"
        if wire and dense_eq:
            compression[key] = round(dense_eq / wire, 3)
        # 1F1B schedule: T = M + 2(pp-1) ticks, pp-1 of them idle per stage
        bubble[key] = round((pp - 1) / (M + 2 * (pp - 1)), 4)
        lp = abs(q_row["loss"] - dense_row["loss"]) \
            <= 0.05 * max(abs(dense_row["loss"]), 1e-9)
        parity[key] = bool(lp)
        rungs[key] = {
            "dense": dict(dense_row, boundary_bytes=int(fam_sum(
                dense_metrics, "ds_comm_ppermute_bytes_total"))),
            "int8": dict(q_row, boundary_bytes=int(wire),
                         dense_equiv_bytes=int(dense_eq)),
            "loss_parity": bool(lp),
            "speedup": round(q_row["tokens_per_sec"]
                             / max(dense_row["tokens_per_sec"], 1e-9), 4)}
    return {"status": "ok", "devices": W,
            "backend": jax.default_backend(),
            "steps": steps, "micro_batch": micro, "grad_accum": accum,
            "seq": seq, "microbatches": M, "schedule": "1f1b",
            "compression": compression,
            "loss_parity": parity,
            "bubble_share": bubble,
            "rungs": rungs,
            "elapsed_s": round(time.perf_counter() - t0, 1)}


def _run_pipe_subprocess() -> dict:
    return _run_child_rung("DSTPU_BENCH_PIPE_OUT")


# micro=4 exceeds what the AOT compiler will place at 48 layers (probed:
# fwd+grad compile-OOMs); micro=2 compiles under every policy
LADDER_1B4 = [("mlp_dots", 2), ("dots", 2), ("full", 2), ("full", 1)]


def bench_1b4_rung(policy: str, micro: int, steps: int = 6, warmup: int = 2):
    """ONE rung of the 1.34B ladder (VERDICT r4 item 1: a measured >1B
    tokens/sec + MFU on the real chip; BASELINE north-star is
    tokens/sec/chip at >1B scale).

    Recipe: 15.75GB HBM fits 1.34B params by dropping the fp32 master (bf16
    state + stochastic-rounding updates, ``bf16.master_weights=false``;
    the init program emits bf16 directly so no fp32 tree ever
    materializes), int8 blockwise Adam states (Adam8bit), bf16 gradient
    accumulation, and remat.  Persistent bytes/param: 2 (params) + 2 (acc)
    + ~2.06 (int8 m+v+scales) ~= 6.1 -> ~8.2GB, leaving ~7GB for
    transients + activations.

    The parent walks the (policy, micro) ladder one SUBPROCESS per rung —
    a failed rung's HBM dies with its process instead of poisoning the
    next rung's attempt.
    """
    _import_jax()
    import deepspeed_tpu
    from deepspeed_tpu.models import causal_lm

    t0 = time.perf_counter()
    try:
        mesh = build_mesh(devices=jax.devices()[:1])
        set_global_mesh(mesh)
        accum = 32 // micro  # ~32k tokens/step regardless of micro
        seq = 1024
        model = causal_lm("llama-1b4", mesh=mesh)
        cfg = model.config
        ds_config = {
            "train_batch_size": micro * accum,
            "train_micro_batch_size_per_gpu": micro,
            "gradient_accumulation_steps": accum,
            "bf16": {"enabled": True, "master_weights": False},
            "data_types": {"grad_accum_dtype": "bf16"},
            "optimizer": {"type": "Adam8bit",
                          "params": {"lr": 2e-4, "weight_decay": 0.1}},
            "gradient_clipping": 1.0,
            "activation_checkpointing": {"enabled": True, "policy": policy},
            "comms_logger": {"enabled": True},
            "steps_per_print": 10**9,
        }
        from deepspeed_tpu.monitor.metrics import get_registry

        registry = get_registry()
        engine, _, _, _ = deepspeed_tpu.initialize(model=model,
                                                   config=ds_config,
                                                   mesh=mesh)
        tokens = jax.random.randint(jax.random.PRNGKey(1),
                                    (accum, micro, seq), 0, cfg.vocab_size)
        batch = (tokens, tokens)
        for _ in range(warmup):
            engine.train_step(batch)
        sync(engine.state.params)
        registry.reset()
        engine._flops_meter.reset_clock()
        t1 = time.perf_counter()
        for _ in range(steps):
            engine.train_step(batch)
        sync(engine.state.params)
        dt = (time.perf_counter() - t1) / steps
        n_params = sum(x.size for x in jax.tree.leaves(engine.state.params))
        tps = micro * accum * seq / dt
        fpt = 6 * n_params + 6 * cfg.num_layers * cfg.hidden_size * seq
        mfu = tps * fpt / peak_flops()
        return {"status": "ok", "tokens_per_sec": round(tps, 1),
                "mfu": round(mfu, 4), "params_b": round(n_params / 1e9, 3),
                "micro_batch": micro, "grad_accum": accum, "seq": seq,
                "steps": steps, "step_ms": round(dt * 1e3, 1),
                "metrics": collect_train_metrics(registry),
                "remat_policy": policy,
                "recipe": "bf16 state + stochastic rounding (no fp32 "
                          "master), Adam8bit int8 m/v, bf16 grad accum",
                "loss_final": round(float(engine._last_loss), 3)}
    except Exception as exc:
        msg = str(exc)
        oom = ("RESOURCE_EXHAUSTED" in msg or "Out of memory" in msg
               or "out of memory" in msg)
        return {"status": "oom" if oom else f"failed: {type(exc).__name__}",
                "error": msg[:300],
                "ladder": f"{policy}/micro={micro}",
                "elapsed_s": round(time.perf_counter() - t0, 1)}


def bench_decode(steps: int = 512) -> dict:
    """Decode throughput microbench (VERDICT r4 item 1: the fused Pallas
    decode path).  Rows: GPT-2 125M as bf16 / int8(+int8 KV) / batch-8,
    plus the 1.34B llama-1b4 single-stream (the >1B serving rung).

    Two numbers per row:
    - ``tokens_per_sec`` (raw): one timed generate(), fixed per-call costs
      (dispatch, prefill) included.
    - ``steady_tokens_per_sec``: per-token rate from differencing a long
      and a short generation, which cancels those fixed costs; they are
      reported as ``fixed_call_overhead_s``.
    """
    _import_jax()
    import deepspeed_tpu
    from deepspeed_tpu.models import causal_lm

    mesh = build_mesh(devices=jax.devices()[:1])
    set_global_mesh(mesh)
    out = {}
    rows = (
        ("bf16", "gpt2-small", {"vocab_size": 50304}, 1,
         {"dtype": "bfloat16"}),
        # unfused control: same model/methodology with kernel injection off,
        # so the fused-path speedup is self-contained in this record
        ("bf16_unfused", "gpt2-small", {"vocab_size": 50304}, 1,
         {"dtype": "bfloat16", "use_fused_decode": False}),
        ("int8", "gpt2-small", {"vocab_size": 50304}, 1,
         {"dtype": "int8", "quantize_kv_cache": True}),
        # int8 weights on the FUSED path (dequant in-kernel; bf16 KV) —
        # halves the per-token weight reads of the kernel-injected decode
        ("int8w_fused", "gpt2-small", {"vocab_size": 50304}, 1,
         {"dtype": "int8"}),
        ("bf16_b8", "gpt2-small", {"vocab_size": 50304}, 8,
         {"dtype": "bfloat16"}),
        # >1B serving: 1.34B fits HBM as bf16 (2.7GB) with room for the
        # decode transients
        ("llama1b4_bf16", "llama-1b4", {"remat": False}, 1,
         {"dtype": "bfloat16"}),
        # the decode-bandwidth headline: 1.34B int8 weights on the fused
        # path halve the per-token weight reads
        ("llama1b4_int8w", "llama-1b4", {"remat": False}, 1,
         {"dtype": "int8"}),
    )
    short = steps // 4
    for name, preset, model_over, batch, cfg_over in rows:
        try:
            model = causal_lm(preset, mesh=mesh, **model_over)
            params = jax.jit(model.init)(jax.random.PRNGKey(0))
            engine = deepspeed_tpu.init_inference(
                model, config={"max_out_tokens": 2048, **cfg_over})
            engine.set_params(params)
            prompt = jax.random.randint(jax.random.PRNGKey(1),
                                        (batch, 16), 0,
                                        model.config.vocab_size)
            # TWO warmup calls per length, LONG length first (the short
            # warmup would otherwise allocate a small cache that the
            # long one evicts along with the compiled programs): the
            # first call per length compiles against the fresh
            # (uncommitted) cache/rng, the second recompiles against
            # the committed steady-state layouts the loop outputs
            # carry — only call 3+ measures the cached program
            for n in (steps, short):
                for _ in range(2):
                    sync(engine.generate(prompt, max_new_tokens=n,
                                         do_sample=False))

            def timed(n, reps=2):
                best = 1e9
                for _ in range(reps):
                    t0 = time.perf_counter()
                    sync(engine.generate(prompt, max_new_tokens=n,
                                         do_sample=False))
                    best = min(best, time.perf_counter() - t0)
                return best

            t_short, dt = timed(short), timed(steps)
            per_tok = (dt - t_short) / (steps - short)
            out[name] = {"tokens_per_sec": round(batch * steps / dt, 1),
                         "steady_tokens_per_sec":
                             round(batch / per_tok, 1),
                         "steady_ms_per_token": round(1e3 * per_tok, 3),
                         "fixed_call_overhead_s":
                             round(t_short - short * per_tok, 3),
                         "new_tokens": steps, "batch": batch,
                         "kernel_injected":
                             engine._dparams is not None,
                         "ms_per_token": round(1e3 * dt / steps, 2)}
        except Exception as exc:
            msg = str(exc)
            oom = ("RESOURCE_EXHAUSTED" in msg or "Out of memory" in msg
                   or "out of memory" in msg)
            out[name] = {"status": "oom" if oom
                         else f"failed: {type(exc).__name__}",
                         "error": msg[:200]}
        finally:
            engine = params = model = None
            import gc

            gc.collect()
    out["note"] = ("bf16/bf16_b8/int8w_fused/llama1b4 run the kernel-"
                   "injected fused Pallas decode (4 launches/layer; "
                   "int8w_fused dequantizes in-kernel); int8 (int8 KV) runs "
                   "the unfused fallback; steady_* differencing cancels the "
                   "fixed per-call cost (see bench_decode docstring)")
    return out


def _run_1b4_subprocess() -> dict:
    """Walk the 1.34B ladder, one CHILD PROCESS per rung: a failed rung's
    HBM (and any hard device fault that aborts the process) dies with its
    child instead of poisoning the next rung or the 125M headline."""
    import subprocess
    import tempfile

    attempts = []
    for policy, micro in LADDER_1B4:
        _check_parent_holds_no_jax()
        fd, out = tempfile.mkstemp(suffix=".json")
        os.close(fd)
        os.unlink(out)  # child creates it; absence = child died early
        env = dict(os.environ, DSTPU_BENCH_1B4_OUT=out,
                   DSTPU_BENCH_1B4_LADDER=f"{policy},{micro}")
        try:
            proc = subprocess.run([sys.executable, os.path.abspath(__file__)],
                                  env=env, timeout=1800, capture_output=True,
                                  text=True)
            try:
                with open(out) as fh:
                    result = json.load(fh)
            except (OSError, json.JSONDecodeError):
                result = {"status": f"failed: child exited {proc.returncode} "
                                    "without a (complete) result",
                          "ladder": f"{policy}/micro={micro}",
                          "stderr_tail": proc.stderr[-400:]}
        except subprocess.TimeoutExpired:
            result = {"status": "failed: child timeout (1800s)",
                      "ladder": f"{policy}/micro={micro}"}
        if result.get("status") == "ok":
            if attempts:
                result["ladder_attempts"] = attempts
            return result
        if result.get("status", "").startswith("skipped"):
            return result
        attempts.append({k: result.get(k) for k in
                         ("status", "ladder", "error", "elapsed_s",
                          "stderr_tail") if result.get(k)})
    return {"status": "failed: no ladder rung succeeded",
            "ladder_attempts": attempts}


def bench_continuous_profiler() -> dict:
    """Continuous-profiler rung (ISSUE 20): arm the always-on profiler on
    a tiny training loop at a forced cadence (capture every 2 steps,
    1-step windows, duty cap lifted) and report what the SCHEDULED path
    produced with no operator ``/profilez`` in the loop: the history-ring
    window count, the latest window's per-scope per-step device-seconds,
    whether the phase lanes stay under the per-step wall, and the
    window-over-window differ verdict.  The scheduler, ring, and differ
    are host-side mechanisms, so the CPU smoke row is meaningful; on the
    TPU runner the same rung exercises real device captures."""
    _import_jax()
    import shutil
    import tempfile

    from deepspeed_tpu.profiling.continuous import HistoryRing, diff_windows

    hist = tempfile.mkdtemp(prefix="dstpu_bench_cprof_")
    t_start = time.perf_counter()
    try:
        mesh = build_mesh(devices=jax.devices()[:1])
        set_global_mesh(mesh)
        model = causal_lm("gpt2-small", mesh=mesh, num_layers=2,
                          hidden_size=128, intermediate_size=512,
                          num_heads=4, vocab_size=2048)
        ds_config = {
            "train_batch_size": 2,
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 1,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
            "steps_per_print": 10**9,
            "continuous_profiler": {
                "enabled": True, "every_steps": 2, "every_seconds": 3600.0,
                "capture_steps": 1, "max_duty_cycle": 1.0,
                "history_dir": hist, "max_windows": 8},
        }
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=model, config=ds_config, mesh=mesh)
        rng = jax.random.PRNGKey(7)
        tokens = jax.random.randint(rng, (1, 2, 128), 0, 2048)
        batch = (tokens, tokens)
        ring = HistoryRing(hist)
        n = 0
        while n < 16 and len(ring.paths()) < 2:
            engine.train_step(batch)
            n += 1
        sync(engine.state.params)
        if engine._cprof is not None:
            engine._cprof.close()      # abandon any in-flight window
        wins = ring.latest(4)
        if len(wins) < 2:
            return {"status": f"failed: {len(wins)} windows after {n} steps"}
        prev, cur = wins[-2], wins[-1]
        phase_s = sum(cur["scopes"].get(k, 0.0) for k in
                      ("fwd_bwd", "optimizer", "comm", "other", "gap"))
        per_step_wall = cur["window_s"] / max(1, cur["steps"])
        return {
            "status": "ok",
            "windows": len(ring.paths()),
            "train_steps": n,
            "wall_s": round(time.perf_counter() - t_start, 3),
            "latest": {
                "seq": cur["seq"], "steps": cur["steps"],
                "window_ms": round(1e3 * cur["window_s"], 2),
                "busy_ratio": round(cur["busy_ratio"], 4),
                "coverage_ratio": round(cur["coverage_ratio"], 4),
                "overhead_ratio": round(cur["overhead_ratio"], 4),
                "degraded": cur["degraded"],
                "top_scopes_ms": {
                    k: round(1e3 * v, 3) for k, v in
                    sorted(cur["scopes"].items(), key=lambda kv: -kv[1])[:4]},
            },
            # the five phase lanes partition the per-step wall exactly;
            # float slack only (acceptance: scope sums <= window wall)
            "phases_within_wall": bool(phase_s <= per_step_wall * 1.001),
            "regressions_vs_prev": [r["scope"] for r in
                                    diff_windows(prev, cur)],
        }
    finally:
        shutil.rmtree(hist, ignore_errors=True)


def main():
    child_mode = any(os.environ.get(k) for k in (
        "DSTPU_BENCH_EMIT_ONLY", "DSTPU_BENCH_1B4_OUT",
        "DSTPU_BENCH_OVERLAP_OUT", "DSTPU_BENCH_QUANTCOMM_OUT",
        "DSTPU_BENCH_PIPE_OUT"))
    if child_mode:
        _import_jax()
    if os.environ.get("DSTPU_BENCH_EMIT_ONLY"):
        # subprocess pin for the stdout contract (tests/unit/
        # test_metrics.py): emit a synthetic record through the REAL
        # final-line path and exit — the last stdout line must be the
        # parseable bare BENCH_JSON summary, with nothing after it
        record = {"metric": "emit_selftest", "value": 0.0,
                  "unit": "tokens/sec", "vs_baseline": 0.0,
                  "detail": {"mfu": 0.0, "backend": jax.default_backend(),
                             "note": "DSTPU_BENCH_EMIT_ONLY=1",
                             # oversized filler: the cap must truncate
                             # blocks, never the line
                             "metrics": {"filler": "x" * 4000}}}
        emit_summary(record, None)
        return
    if os.environ.get("DSTPU_BENCH_1B4_OUT"):
        # child mode: run ONE ladder rung, write the result, exit
        if jax.devices()[0].platform != "tpu":
            result = {"status": "skipped: no tpu"}
        else:
            policy, micro = os.environ["DSTPU_BENCH_1B4_LADDER"].split(",")
            result = bench_1b4_rung(policy, int(micro))
        with open(os.environ["DSTPU_BENCH_1B4_OUT"], "w") as fh:
            json.dump(result, fh)
        return
    if os.environ.get("DSTPU_BENCH_OVERLAP_OUT"):
        # child mode: overlap on/off ablation over all local devices (the
        # CPU parent hands this child a virtual 8-device mesh)
        result = bench_overlap_rung()
        with open(os.environ["DSTPU_BENCH_OVERLAP_OUT"], "w") as fh:
            json.dump(result, fh)
        return
    if os.environ.get("DSTPU_BENCH_QUANTCOMM_OUT"):
        # child mode: dense vs int8 quantized-collective ablation
        result = bench_quant_comm()
        with open(os.environ["DSTPU_BENCH_QUANTCOMM_OUT"], "w") as fh:
            json.dump(result, fh)
        return
    if os.environ.get("DSTPU_BENCH_PIPE_OUT"):
        # child mode: pipeline dense-vs-int8 boundary ablation
        result = bench_pipe()
        with open(os.environ["DSTPU_BENCH_PIPE_OUT"], "w") as fh:
            json.dump(result, fh)
        return

    # Every child rung runs BEFORE this process imports jax: a chip belongs
    # to one process at a time (_check_parent_holds_no_jax at each spawn),
    # and a child abort must not kill the headline.  Whether the >1B child
    # is worth starting is read off the environment; the child looks at its
    # own devices.
    rung_1b4 = None
    if os.environ.get("JAX_PLATFORMS", "").lower() != "cpu" \
            and os.environ.get("DSTPU_BENCH_SKIP_1B4") != "1":
        rung_1b4 = _run_1b4_subprocess()

    # overlap on/off ablation (ROADMAP item 1 mechanical acceptance): runs
    # on CPU too — the child gets its own virtual multi-device mesh
    rung_overlap = None
    if os.environ.get("DSTPU_BENCH_SKIP_OVERLAP") != "1":
        rung_overlap = _run_overlap_subprocess()

    # quantized-collective dense-vs-int8 ablation (ROADMAP item 2
    # acceptance: per-op bytes ~2-4x down with loss parity); CPU-meaningful
    rung_quant_comm = None
    if os.environ.get("DSTPU_BENCH_SKIP_QUANTCOMM") != "1":
        rung_quant_comm = _run_quant_comm_subprocess()

    # pipeline dense-vs-int8 boundary ablation (ISSUE 16 acceptance: >=2x
    # fewer boundary bytes at loss parity, bubble share recorded);
    # CPU-meaningful for bytes/parity
    rung_pipe = None
    if os.environ.get("DSTPU_BENCH_SKIP_PIPE") != "1":
        rung_pipe = _run_pipe_subprocess()

    _import_jax()
    on_tpu = jax.devices()[0].platform == "tpu"

    # streamed-offload relay ablation (ISSUE 11 / ROADMAP item 3): bf16 vs
    # int8 relay on the same streamed workload; runs on CPU at smoke scale
    rung_streamed = None
    if os.environ.get("DSTPU_BENCH_SKIP_STREAMED") != "1":
        rung_streamed = bench_streamed_rung()

    # elastic resume: world-size-change restore latency + steps-to-recover
    # (ISSUE 14); meaningful on CPU too — resharding reads + gas-rescale
    # recompile are host-side costs
    rung_elastic = None
    if os.environ.get("DSTPU_BENCH_SKIP_ELASTIC") != "1":
        try:
            rung_elastic = bench_elastic_resume(tiny=not on_tpu)
        except Exception as exc:
            rung_elastic = {"status": f"failed: {type(exc).__name__}",
                            "error": str(exc)[:200]}

    # continuous-profiler rung (ISSUE 20): the scheduled-capture path end
    # to end — >=2 history windows, per-scope device-seconds under the
    # window wall, differ verdict — with no operator /profilez in the
    # loop; host-side mechanism, so CPU-meaningful
    rung_cprof = None
    if os.environ.get("DSTPU_BENCH_SKIP_CPROF") != "1":
        try:
            rung_cprof = bench_continuous_profiler()
        except Exception as exc:
            rung_cprof = {"status": f"failed: {type(exc).__name__}",
                          "error": str(exc)[:200]}

    mesh = build_mesh(devices=jax.devices()[:1])
    set_global_mesh(mesh)

    if on_tpu:
        # micro-batch 12 is the measured sweet spot under mlp_dots + dense
        # CE; deep accumulation amortizes the optimizer step.  Vocab padded
        # 50257 -> 50304 (multiple of 128) for MXU tiling — standard
        # practice (Megatron/DeepSpeed GPT-2 runs pad the same way).
        # dense CE (ce_chunk=0) measured 6% faster than the blockwise path
        # at this size — the [B,S,V] fp32 logits transient fits HBM and
        # skips the chunk scan's recompute.
        micro, accum, seq, steps, warmup = 12, 16, 1024, 8, 2
        model = causal_lm("gpt2-small", mesh=mesh, vocab_size=50304, ce_chunk=0)
    else:  # dev smoke path
        micro, accum, seq, steps, warmup = 2, 1, 256, 3, 1
        model = causal_lm("gpt2-small", mesh=mesh, num_layers=2, hidden_size=128,
                          intermediate_size=512, num_heads=4, vocab_size=2048)
    batch = micro * accum
    cfg = model.config

    ds_config = {
        "train_batch_size": batch,
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": accum,
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 1},
        "optimizer": {"type": "AdamW", "params": {"lr": 6e-4, "weight_decay": 0.1}},
        "gradient_clipping": 1.0,
        # "mlp_dots": attention residuals persist (the flash kernel never
        # re-runs in backward) while the MLP half remats with matmul outputs
        # saved — measured the fastest policy on v5e at this size.
        "activation_checkpointing": {"enabled": True, "policy": "mlp_dots"},
        # model profile printed once during warmup (XLA cost analysis)
        "flops_profiler": {"enabled": True, "profile_step": 2},
        # training-side telemetry: ds_comm_* per-collective accounting +
        # ds_train_tflops/mfu + ds_mem_* (collect_train_metrics reads these)
        "comms_logger": {"enabled": True},
        "steps_per_print": 10**9,
    }
    from deepspeed_tpu.monitor.metrics import get_registry

    registry = get_registry()
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=ds_config, mesh=mesh)

    rng = jax.random.PRNGKey(0)
    tokens = jax.random.randint(rng, (accum, micro, seq), 0, cfg.vocab_size)
    batch_data = (tokens, tokens)  # stacked [gas, micro, seq] for train_step

    def one_step():
        # fused path: ONE dispatch for the whole step (scan over microbatches
        # + update in a single XLA program)
        engine.train_step(batch_data)

    for _ in range(warmup):
        one_step()
    sync(engine.state.params)
    registry.reset()            # warm passes (compiles included) off the record
    engine._flops_meter.reset_clock()
    # run-level goodput ledger bracketing the measured window.  Snapshot
    # DELTAS, so a supervisor-provided ledger (DSTPU_RUNLEDGER) is
    # observed rather than clobbered; a bench-owned enable stays
    # in-memory (no jsonl path).
    gp = engine._goodput
    gp_owned = not gp.enabled
    if gp_owned:
        gp.enable(run_id="bench-train", role="train")
    gp_before = gp.snapshot()

    t0 = time.perf_counter()
    for _ in range(steps):
        one_step()
    sync(engine.state.params)
    dt = time.perf_counter() - t0
    rung_goodput = goodput_window(gp_before, gp.snapshot(), dt,
                                  steps * batch * seq)
    if gp_owned:
        gp.disable()
    train_metrics = collect_train_metrics(registry)
    # device-true phase breakdown over a 2-step post-measurement capture
    # (the /profilez analysis, attached per BENCH row so the gap/overlap
    # headroom and device-vs-analytic comm attribution travel with the
    # throughput number)
    dev_profile = capture_device_profile(one_step, steps=2, tag="train")
    if dev_profile:
        train_metrics["device_profile"] = dev_profile

    # The 8B rung is opt-in (DSTPU_BENCH_8B=1): 16GB of host-tiered params
    # stream to the device every micro-batch, and how long that takes on
    # this machine is not measured.  The param-streaming mechanism itself
    # is exercised by tests/unit/test_param_offload.py on the CPU mesh.
    if on_tpu and os.environ.get("DSTPU_BENCH_8B") == "1":
        rung_8b = bench_8b_rung()
    elif on_tpu:
        rung_8b = {"status": "skipped by default: set DSTPU_BENCH_8B=1",
                   "params_b": 8.03, "hbm_needed_gb": 16.1}
    else:
        rung_8b = None

    # decode microbench (engine freed above keeps HBM available: the train
    # engine's state remains live, but 125M leaves plenty)
    rung_decode = bench_decode() if on_tpu else None

    # continuous-batching serving scenario (Poisson arrivals, mixed
    # lengths) vs the static-batch baseline at equal slot count
    if on_tpu:
        try:
            rung_serving = bench_serving()
        except Exception as exc:
            rung_serving = {"status": f"failed: {type(exc).__name__}",
                            "error": str(exc)[:200]}
        # shared-prefix trace: prefix caching on/off (prefill-token
        # savings are host-counted, so this row is also meaningful on
        # the CPU smoke path — tests/perf runs it tiny)
        try:
            rung_prefix = bench_prefix_serving()
        except Exception as exc:
            rung_prefix = {"status": f"failed: {type(exc).__name__}",
                           "error": str(exc)[:200]}
        # thrash-sized prefix cache: host tier on/off hit-ratio row
        try:
            rung_host_tier = bench_host_tier_serving()
        except Exception as exc:
            rung_host_tier = {"status": f"failed: {type(exc).__name__}",
                              "error": str(exc)[:200]}
        # fleet resilience: goodput + TTFT p99 through the router with
        # and without one replica kill + supervisor restart mid-trace
        try:
            rung_fleet_chaos = bench_fleet_chaos()
        except Exception as exc:
            rung_fleet_chaos = {"status": f"failed: {type(exc).__name__}",
                                "error": str(exc)[:200]}
        # disaggregated prefill/decode: role-split × streaming grid,
        # int8 KV-page handoff wire bytes vs the dense twin
        try:
            rung_disagg = bench_disagg_serving()
        except Exception as exc:
            rung_disagg = {"status": f"failed: {type(exc).__name__}",
                           "error": str(exc)[:200]}
    else:
        rung_serving = None
        rung_prefix = None
        rung_host_tier = None
        rung_fleet_chaos = None
        rung_disagg = None

    tokens_per_step = batch * seq
    tps = steps * tokens_per_step / dt
    n_params = sum(x.size for x in jax.tree.leaves(engine.state.params))
    # fwd+bwd FLOPs/token: 6N matmul + 12*L*D*S attention (causal halves it).
    flops_per_token = 6 * n_params + 6 * cfg.num_layers * cfg.hidden_size * seq
    if on_tpu:
        mfu = tps * flops_per_token / peak_flops()
        headline = {
            "metric": "gpt2_125m_train_tokens_per_sec_per_chip",
            "value": round(tps, 1),
            "unit": "tokens/sec",
            "vs_baseline": round(mfu / 0.40, 4),
            "baseline_def": "mfu / 0.40 MFU north-star target (BASELINE."
                            "json published no measured reference number)"}
        timing = {"mfu": round(mfu, 4),
                  "step_ms": round(1e3 * dt / steps, 2)}
    else:
        # a CPU run shows that the path runs; it yields no rate, no MFU and
        # nothing under a per-chip metric's name
        headline = {"metric": "cpu_smoke_train_steps", "value": steps,
                    "unit": "steps", "vs_baseline": None}
        timing = {"mfu": None}
    record = ({
        **headline,
        "detail": {**timing, "params_m": round(n_params / 1e6, 2),
                   "batch": batch, "micro_batch": micro, "grad_accum": accum,
                   "seq": seq, "steps": steps,
                   "flops_model": "6N + 6*L*D*S per token (dense causal; "
                                  "remat recompute not counted)",
                   "mfu_analysis": (
                       "xplane trace (r5): the step is device-gapless; "
                       "matmul fusions 47% (head GEMM ~89% of peak), Pallas "
                       "kernels 33% (flash bwd measured at parity with "
                       "jax's in-tree TPU kernel; Pallas norms faster than "
                       "XLA-fused norms), data formatting 9%, loop fusions "
                       "7%. The gap to the 1.34B rung's 0.60 MFU is "
                       "architectural: GPT-2-small's head_dim=64 underfills "
                       "the 128-wide MXU contraction in attention, and "
                       "S=1024 attention is a larger share at D=768. "
                       "Probed and rejected by measurement: no-remat "
                       "(0.42, HBM pressure), mlp_only (0.44), XLA norms "
                       "(0.43), XLA attention (compile-OOM), 256-token "
                       "fwd flash blocks (0.42 in-context despite 1.6x "
                       "standalone), micro 8/16 (0.43/0.45)."),
                   "backend": jax.devices()[0].platform,
                   "device": jax.devices()[0].device_kind,
                   "device_count": len(jax.devices()),
                   # training-health metrics (the serving record's analog):
                   # live tflops/mfu gauges, peak HBM, top collectives
                   **({"metrics": train_metrics} if train_metrics else {}),
                   **({"goodput": rung_goodput} if rung_goodput else {}),
                   **({"cprof": rung_cprof} if rung_cprof else {}),
                   **({"llama_1b4": rung_1b4} if rung_1b4 else {}),
                   **({"overlap_1b4": rung_overlap} if rung_overlap
                      else {}),
                   **({"quant_comm": rung_quant_comm} if rung_quant_comm
                      else {}),
                   **({"pipe": rung_pipe} if rung_pipe else {}),
                   **({"llama3_8b": rung_8b} if rung_8b else {}),
                   **({"decode_125m": rung_decode} if rung_decode else {}),
                   **({"serving_125m": rung_serving} if rung_serving
                      else {}),
                   **({"prefix_serving_125m": rung_prefix} if rung_prefix
                      else {}),
                   **({"host_tier_serving": rung_host_tier}
                      if rung_host_tier else {}),
                   **({"fleet_chaos": rung_fleet_chaos}
                      if rung_fleet_chaos else {}),
                   **({"disagg_serving": rung_disagg}
                      if rung_disagg else {}),
                   **({"elastic_resume": rung_elastic}
                      if rung_elastic else {}),
                   **({"streamed_offload": rung_streamed}
                      if rung_streamed else {})},
    })
    emit_summary(record, rung_serving)
    failed = failed_rungs(record["detail"])
    if failed:
        # after the record, so the last stdout line stays the summary
        print(f"bench.py: rungs failed: {failed}", file=sys.stderr)
        sys.exit(1)


def failed_rungs(detail: dict, prefix: str = "") -> list:
    """Names of the rungs (at any depth of ``detail``) whose ``status``
    says they raised, timed out or ran out of memory.  A rung catches its
    own exception so that the other rungs and the record survive it;
    ``main`` turns any of them into a non-zero exit."""
    bad = []
    for key, val in detail.items():
        if not isinstance(val, dict):
            continue
        status = val.get("status")
        if isinstance(status, str) and (status.startswith("failed")
                                        or status == "oom"):
            bad.append(prefix + key)
        else:
            bad.extend(failed_rungs(val, prefix + key + "."))
    return bad


# Hard byte cap on the bare final stdout line.  BENCH_r05 recorded
# ``"parsed": null`` because the runner reads (and truncates around ~2000
# chars) the LAST stdout line: an oversized summary line truncates into
# non-JSON and the whole record is lost.  The cap is enforced by
# progressively dropping the bulkiest optional sub-objects (everything
# still rides, in full, in the first-line record).
BENCH_SUMMARY_MAX_CHARS = 1800


def _strip_bulky(obj):
    """Drop per-capture payloads (device_profile) from a summary
    sub-object — they belong to the record line, not the capped final
    line."""
    if isinstance(obj, dict):
        return {k: _strip_bulky(v) for k, v in obj.items()
                if k != "device_profile"}
    return obj


def run_metadata() -> dict:
    """THE run-environment stamp every BENCH_JSON block carries (one
    shared helper, so no block can drift): git sha, jax/jaxlib versions,
    platform, and the summary ``schema_version`` — ``tools/perf_ledger.py``
    uses it to label a cross-rung perf move that coincides with an
    ENVIRONMENT change (toolchain bump, different backend) instead of
    blaming the code.  Bump ``schema_version`` when the summary's block
    shapes change incompatibly."""
    _import_jax()
    meta = {"schema_version": 1}
    try:
        import subprocess

        meta["git_sha"] = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or None
    except Exception:
        meta["git_sha"] = None
    try:
        import jaxlib

        meta["jax"] = jax.__version__
        meta["jaxlib"] = jaxlib.version.__version__
        meta["platform"] = jax.default_backend()
    except Exception:
        pass
    return meta


def summary_lines(record: dict, rung_serving) -> list:
    """The machine-readable tail of the bench stdout: a human-greppable
    ``BENCH_JSON:``-prefixed line followed by the SAME summary as a bare
    JSON object on the FINAL line — the runner ``json.loads``-parses the
    last stdout line into its ``parsed`` field (a prefixed final line
    parses to nothing, and an oversized line truncates to garbage — both
    are the BENCH_r05 ``"parsed": null`` bug).  The bare line is capped
    at :data:`BENCH_SUMMARY_MAX_CHARS`; tests/unit/test_metrics.py
    round-trips the last line and pins the cap with a real subprocess
    (``DSTPU_BENCH_EMIT_ONLY``)."""
    summary = {"metric": record["metric"], "value": record["value"],
               "unit": record["unit"], "vs_baseline": record["vs_baseline"],
               "mfu": record["detail"]["mfu"],
               "backend": record["detail"]["backend"]}
    # environment stamp (schema_version, git sha, jax/jaxlib, platform):
    # perf_ledger separates toolchain moves from code regressions
    summary["run_meta"] = run_metadata()
    if record["detail"].get("metrics"):
        summary["train_metrics"] = _strip_bulky(record["detail"]["metrics"])
    ov = record["detail"].get("overlap_1b4")
    if ov and "overlap_speedup" in ov:
        # the ROADMAP item 1 acceptance row: both ablation sides' device
        # phase shares + MFU travel with the headline speedup
        summary["overlap_speedup"] = ov["overlap_speedup"]
        summary["overlap_ablation"] = {
            side: {k: ov[side][k] for k in
                   ("tokens_per_sec", "mfu", "gap_share",
                    "gap_plus_comm_share", "comm_s", "comm_s_source",
                    "loss")
                   if k in ov[side]}
            for side in ("off", "on")}
        summary["overlap_loss_parity"] = ov.get("loss_parity")
    gpb = record["detail"].get("goodput")
    if gpb:
        # the ISSUE 18 run-level goodput row: measured-window wall-clock
        # attribution (ratio + nonzero categories), the telescoping bit,
        # and the exact token reconciliation against the headline
        summary["goodput"] = {
            "goodput_ratio": gpb["goodput_ratio"],
            "telescopes": gpb["telescopes"],
            "tokens_reconcile": gpb["tokens_reconcile"],
            "tokens_per_sec": gpb["tokens_per_sec"],
            "categories": gpb["categories"],
        }
    if rung_serving and "goodput_speedup" in rung_serving:
        summary["serving_goodput_tok_s"] = \
            rung_serving["continuous"]["goodput_tok_s"]
        summary["serving_goodput_speedup"] = rung_serving["goodput_speedup"]
        summary["serving_p99_latency_s"] = \
            rung_serving["continuous"]["p99_latency_s"]
        # equal-HBM paged-vs-fixed attribution (the paged-KV tentpole row)
        if rung_serving.get("paged_vs_fixed_speedup") is not None:
            summary["serving_paged_vs_fixed"] = \
                rung_serving["paged_vs_fixed_speedup"]
        # serving-health row (TTFT/queue-wait/occupancy from the metrics
        # registry) so BENCH_r*.json tracks latency attribution, not just
        # aggregate goodput
        summary["serving_metrics"] = _strip_bulky(
            rung_serving.get("metrics"))
    pf = record["detail"].get("prefix_serving_125m")
    if pf and "prefill_savings_ratio" in pf:
        # the prefix-caching acceptance row: prefill-token savings (>=
        # 0.4 target), hit ratio, and the token-identity bit travel with
        # the headline (docs/OBSERVABILITY.md "Serving — prefix cache")
        summary["serving_prefix"] = {
            "prefill_savings_ratio": pf["prefill_savings_ratio"],
            "prefix_hit_ratio": pf["prefix_hit_ratio"],
            "outputs_token_identical": pf["outputs_token_identical"],
            "goodput_speedup": pf["prefix_goodput_speedup"],
            "ttft_p99_on_s": pf["cache_on"]["ttft_p99_s"],
            "ttft_p99_off_s": pf["cache_off"]["ttft_p99_s"],
        }
    qc = record["detail"].get("quant_comm")
    if qc and qc.get("status") == "ok":
        # the ROADMAP item 2 acceptance row: per-op compression (dense-
        # equivalent bytes / wire bytes, both from ONE trace) + per-family
        # loss parity + throughput ratios travel with the headline
        summary["quant_comm"] = {
            "compression": qc["compression"],
            "loss_parity": qc["loss_parity"],
            "speedup": {fam: f["speedup"]
                        for fam, f in qc["families"].items()},
        }
    pi = record["detail"].get("pipe")
    if pi and pi.get("status") == "ok":
        # the ISSUE 16 pipeline acceptance row: per-depth boundary
        # compression (dense-equivalent / wire bytes off the engine's
        # analytic ledger), loss parity, the analytic 1F1B bubble share
        # and the dense-vs-int8 throughput ratio travel with the headline
        summary["pipe"] = {
            "compression": pi["compression"],
            "loss_parity": pi["loss_parity"],
            "bubble_share": pi["bubble_share"],
            "speedup": {r: v["speedup"] for r, v in pi["rungs"].items()},
        }
    st = record["detail"].get("streamed_offload")
    if st and st.get("status") == "ok":
        # the ISSUE 11 streamed-rung acceptance row: relay MB/s + bytes
        # ratio + speedup + loss parity travel with the headline
        summary["streamed_offload"] = {
            k: st[k] for k in ("streamed_speedup", "relay_bytes_ratio",
                               "loss_parity", "gap_share")
            if st.get(k) is not None}
        summary["streamed_offload"]["relay_MBps"] = {
            side: st[side].get("relay_MBps")
            for side in ("bf16", "int8") if isinstance(st.get(side), dict)}
    ht = record["detail"].get("host_tier_serving")
    if ht and "hit_ratio_on" in ht:
        # the KV-host-tier acceptance row: strictly-higher hit ratio at a
        # thrash-sized pool, with token-identical outputs
        summary["serving_host_tier"] = {
            k: ht[k] for k in ("hit_ratio_on", "hit_ratio_off",
                               "outputs_token_identical", "demotes",
                               "promotes", "goodput_speedup")
            if ht.get(k) is not None}
    fc = record["detail"].get("fleet_chaos")
    if fc and "goodput_retention" in fc:
        # the ISSUE 13 resilience row: goodput/TTFT with vs without a
        # replica kill + supervisor restart mid-trace, and the
        # exactly-once / token-identity acceptance bits
        summary["fleet_chaos"] = {
            "goodput_retention": fc["goodput_retention"],
            "goodput_clean_tok_s": fc["clean"]["goodput_tok_s"],
            "goodput_chaos_tok_s": fc["chaos"]["goodput_tok_s"],
            "ttft_p99_clean_s": fc["ttft_p99_clean_s"],
            "ttft_p99_chaos_s": fc["ttft_p99_chaos_s"],
            "restarts_observed": fc["restarts_observed"],
            "shed_429": fc["chaos"]["shed_429"],
            "answered_exactly_once": fc["answered_exactly_once"],
            "outputs_token_identical": fc["outputs_token_identical"],
        }
    dg = record["detail"].get("disagg_serving")
    if dg and "handoff_compression" in dg:
        # the ISSUE 19 disaggregation row: role-split goodput vs the
        # monolithic fleet, user-visible TTFT from streaming, int8 KV
        # handoff wire bytes vs the dense twin, and token identity
        # across the whole role-split × streaming grid
        summary["disagg_serving"] = {
            "disagg_goodput_ratio": dg["disagg_goodput_ratio"],
            "ttft_stream_p50_s": dg["disagg"]["stream"]["ttft_p50_s"],
            "ttft_stream_over_total": dg["ttft_stream_over_total"],
            "handoff_compression": dg["handoff_compression"],
            "outputs_token_identical": dg["outputs_token_identical"],
        }
    er = record["detail"].get("elastic_resume")
    if er and er.get("status") == "ok":
        # the ISSUE 14 elastic-training acceptance row: resume latency +
        # steps-to-recover across the world change, with loss parity
        summary["elastic_resume"] = {
            "resume_latency_s": er["resume_latency_s_max"],
            "steps_to_recover": er["steps_to_recover_max"],
            "loss_parity": er["loss_parity"],
            "world_save": er["world_save"],
            "worlds": er["worlds"],
        }
    line = json.dumps(summary, separators=(",", ":"))
    # enforce the final-line cap: drop the bulkiest optional blocks first
    # (the record line keeps everything); the minimal summary always fits
    for victim in ("serving_metrics", "train_metrics", "overlap_ablation",
                   "goodput", "serving_prefix", "streamed_offload",
                   "serving_host_tier", "fleet_chaos", "disagg_serving",
                   "elastic_resume", "quant_comm", "pipe", "run_meta"):
        if len(line) <= BENCH_SUMMARY_MAX_CHARS:
            break
        if summary.pop(victim, None) is not None:
            summary.setdefault("truncated", []).append(victim)
            line = json.dumps(summary, separators=(",", ":"))
    return ["BENCH_JSON: " + line, line]


def emit_summary(record: dict, rung_serving) -> None:
    """THE bench stdout contract: the full record line, the
    ``BENCH_JSON:``-prefixed summary, then the SAME summary as the
    literal LAST stdout line — every line flushed, and nothing may print
    after this (the runner parses the final line).  ``main`` calls this
    as its last statement."""
    print(json.dumps(record), flush=True)
    for line in summary_lines(record, rung_serving):
        print(line, flush=True)
    sys.stdout.flush()


if __name__ == "__main__":
    main()
