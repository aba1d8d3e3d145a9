"""Continuous-batching serving layer (serving/): scheduler unit behavior
(admission, early-EOS slot free, drain ordering) and greedy-decode PARITY —
a mixed-length request set served through the iteration-level scheduler
must produce token-identical outputs to one-at-a-time ``generate()`` calls.
Runs on the CPU mesh at tiny config (tier-1: the serving path is exercised
on every PR).  Engines are module-scoped: compiles dominate tier-1 wall
time on small hosts, and the serving engine is built to be reused across
request waves anyway (that IS the product behavior under test)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.comm.mesh import build_mesh, set_global_mesh
from deepspeed_tpu.models import causal_lm
from deepspeed_tpu.serving import (FINISHED, IterationScheduler, Request,
                                   ServingEngine)


@pytest.fixture(autouse=True)
def _no_unknown_finish_reasons():
    """Tier-1 assertion: ``ds_serve_finished_total{reason="unknown"}`` must
    stay ZERO across the whole serving suite — a nonzero count means a
    release path finished a request without attributing why (a scheduler
    bug signal, per docs/OBSERVABILITY.md), and it must fail loudly here
    rather than ship as a mystery series in production scrapes."""
    from deepspeed_tpu.monitor.metrics import get_registry

    yield
    c = get_registry().get("ds_serve_finished_total",
                           labels={"reason": "unknown"})
    assert c is None or c.value == 0, (
        f"{c.value} request(s) finished with reason='unknown' — some "
        "release path forgot to set finish_reason (unattributed release)")


@pytest.fixture(autouse=True)
def _span_completeness_guard():
    """Tier-1 span-completeness assertion (mirror of the unknown-reason
    guard, for the request tracer): after any test, every request the
    tracer recorded must have reached its terminal finish edge — zero
    timelines remain open once the test's requests are drained, and every
    retained completion carries the terminal data `/requestz` and the
    phase histograms key on.  An open timeline here means some release
    path finished a request without closing its span record."""
    from deepspeed_tpu.monitor.request_trace import PHASES, \
        get_request_tracer

    tracer = get_request_tracer()
    yield
    assert tracer.open_count == 0, (
        f"request timelines left open after the test: "
        f"{tracer.open_ids()} — a release path finished these requests "
        "without recording the terminal finish edge")
    for rec in tracer.completed():
        assert rec["edges"][-1][1] == "finish", rec
        assert "reason" in rec and "latency_s" in rec, rec
        assert set(rec["phases"]) == set(PHASES), rec


# ---------------------------------------------------------------------------
# scheduler unit tests (pure host logic, no jax)
# ---------------------------------------------------------------------------

def _req(n=4, max_new=4, eos=-1):
    return Request(prompt=np.arange(1, n + 1, dtype=np.int32),
                   max_new_tokens=max_new, eos_token_id=eos)


def test_scheduler_fifo_admission():
    s = IterationScheduler(2)
    reqs = [s.submit(_req()) for _ in range(5)]
    admitted = s.admit()
    assert [r.request_id for r in admitted] == [reqs[0].request_id,
                                               reqs[1].request_id]
    assert {r.slot for r in admitted} == {0, 1}
    assert s.num_queued == 3
    assert s.admit() == []  # no free slots -> nothing admitted


def test_scheduler_early_finish_frees_slot_immediately():
    s = IterationScheduler(2)
    reqs = [s.submit(_req()) for _ in range(3)]
    s.admit()
    # the engine contract: finish_reason is attributed BEFORE finish()
    # (an unset reason lands in the "unknown" bug-signal series)
    reqs[0].finish_reason = "eos"
    s.finish(reqs[0])              # early EOS on slot 0
    assert s.free_slots() == [0]
    nxt = s.admit()
    assert len(nxt) == 1 and nxt[0] is reqs[2] and nxt[0].slot == 0
    assert s.num_queued == 0


def test_scheduler_drain_ordering_by_finish_time():
    s = IterationScheduler(3)
    reqs = [s.submit(_req()) for _ in range(3)]
    s.admit()
    for r in (reqs[1], reqs[2], reqs[0]):
        r.finish_reason = "length"
        s.finish(r)
    assert [r.request_id for r in s.finished] == \
        [reqs[1].request_id, reqs[2].request_id, reqs[0].request_id]
    assert not s.has_work
    assert all(r.state == FINISHED for r in reqs)
    # long-lived serving: finished history is drainable (else it grows
    # without bound)
    assert s.drain_finished() == [reqs[1], reqs[2], reqs[0]]
    assert s.finished == [] and s.drain_finished() == []


# ---------------------------------------------------------------------------
# end-to-end serving on the CPU mesh (shared module-scoped engines)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served(devices):
    """(model, params, ref InferenceEngine, ServingEngine) — one compile
    set shared by every e2e test; the serving engine is reused across
    request waves exactly as in production."""
    mesh = build_mesh(fsdp=8, devices=devices)
    set_global_mesh(mesh)
    model = causal_lm("llama-tiny", mesh=mesh, num_layers=2, hidden_size=64,
                      intermediate_size=128, num_heads=4, num_kv_heads=2,
                      vocab_size=256, remat=False)
    rng = jax.random.PRNGKey(0)
    params = model.init(rng, jnp.zeros((1, 8), jnp.int32))
    ref = deepspeed_tpu.init_inference(
        model, config={"dtype": "float32", "max_out_tokens": 64})
    ref.set_params(params)
    # kv_page_tokens=16 -> 4 pages per 64-token slot window: every e2e
    # test in this module runs the PAGED cache with real multi-page
    # tables (page indirection is trivial at one page per slot)
    serve = deepspeed_tpu.init_serving(
        model, config={"dtype": "float32", "max_out_tokens": 64,
                       "kv_page_tokens": 16},
        num_slots=2, prefill_chunk=4, decode_block_tokens=3)
    serve.set_params(params)
    return model, params, ref, serve


def _mixed_requests(rng, n=6):
    """Mixed prompt/output lengths: exercises queueing (n > num_slots),
    chunked prefill (prompts > prefill_chunk), and early slot turnover."""
    lens = [3, 5, 9, 12, 4, 7][:n]
    news = [4, 7, 3, 6, 8, 2][:n]
    keys = jax.random.split(rng, n)
    prompts = [np.asarray(jax.random.randint(keys[i], (lens[i],), 0, 256))
               for i in range(n)]
    return prompts, news


def test_continuous_batching_greedy_parity(served, rng):
    """Tokens served through the continuous-batching scheduler (2 slots,
    4-token prefill chunks, per-row decode positions) must equal
    one-at-a-time generate() for every request."""
    _, _, ref, serve = served
    prompts, news = _mixed_requests(rng)
    want = [np.asarray(ref.generate(p[None], max_new_tokens=n,
                                    do_sample=False))[0, len(p):]
            for p, n in zip(prompts, news)]
    reqs = [serve.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
    finished = serve.run()
    assert len(finished) >= len(reqs)
    for i, (req, w) in enumerate(zip(reqs, want)):
        np.testing.assert_array_equal(
            np.asarray(req.output_tokens), w,
            err_msg=f"request {i} (prompt {len(prompts[i])}, "
                    f"max_new {news[i]}) diverged from generate()")


@pytest.mark.parametrize("places", [1, 4])
def test_greedy_parity_whatever_the_chunk_places(served, rng, monkeypatch,
                                                 places):
    """``max_prefill_chunks`` places an iteration, one (a prompt never has
    two chunks in an iteration) or four (a prompt that prefills alone takes
    all four, back to back with no decode block between): the same tokens
    as generate(), for prompts of one to nine chunks."""
    _, _, ref, serve = served
    monkeypatch.setattr(serve, "max_prefill_chunks", places)
    lens, news = [33, 3, 14, 26, 5, 19], [5, 7, 3, 6, 8, 2]
    prompts = [np.asarray(jax.random.randint(k, (n,), 0, 256))
               for k, n in zip(jax.random.split(rng, len(lens)), lens)]
    lone = serve.submit(prompts[0], max_new_tokens=news[0])
    serve.run()                      # alone in PREFILLING: every place its own
    reqs = [lone] + [serve.submit(p, max_new_tokens=n)
                     for p, n in zip(prompts[1:], news[1:])]
    serve.run()
    for p, n, req in zip(prompts, news, reqs):
        want = np.asarray(ref.generate(p[None], max_new_tokens=n,
                                       do_sample=False))[0, len(p):]
        np.testing.assert_array_equal(
            np.asarray(req.output_tokens), want,
            err_msg=f"prompt {len(p)}, max_new {n} diverged from generate()")


def test_serving_early_eos_frees_slot_and_admits_queue(served, rng):
    """A request whose greedy continuation hits EOS early must free its
    slot mid-flight so a queued request is admitted and completes."""
    _, _, ref, serve = served
    prompts, news = _mixed_requests(rng, n=4)
    # request 0's actual first greedy token becomes its EOS -> finishes
    # after ONE token while others still want up to 8
    eos = int(ref.generate(prompts[0][None], max_new_tokens=1)[0, -1])
    base = len(serve.scheduler.finished)
    r0 = serve.submit(prompts[0], max_new_tokens=8, eos_token_id=eos)
    rest = [serve.submit(p, max_new_tokens=8) for p in prompts[1:]]
    finished = serve.run()[base:]
    assert r0.output_tokens == [eos]
    assert finished[0] is r0                      # early-EOS drains first
    assert all(len(r.output_tokens) == 8 for r in rest)
    assert len(finished) == 4


def test_serving_respects_cache_budget(served, rng):
    """A prompt near max_out_tokens truncates generation at the cache
    bound instead of corrupting neighbor slots; oversized prompts raise."""
    _, _, _, serve = served
    prompt = np.asarray(jax.random.randint(rng, (62,), 0, 256))
    req = serve.submit(prompt, max_new_tokens=32)
    serve.run()
    assert req.done
    # cache_len 64: 1 prefill-sampled token + decode up to pos 63 -> 2
    assert 1 <= len(req.output_tokens) <= 2
    # a prompt filling the whole cache emits exactly the prefill token
    full = serve.submit(np.asarray(jax.random.randint(rng, (64,), 0, 256)),
                        max_new_tokens=8)
    serve.run()
    assert full.done and len(full.output_tokens) == 1
    with pytest.raises(ValueError):
        serve.submit(np.zeros(65, np.int32), max_new_tokens=1)


def test_serving_logical_budget_not_physical_rounding(devices):
    """init_kv_cache rounds the physical depth up to a flash-decode block
    multiple; generation bounds must use the LOGICAL max_out_tokens so
    serving emits exactly what generate() would (which never sees the
    rounding).  Pure bookkeeping — no weights/compiles."""
    mesh = build_mesh(fsdp=8, devices=devices)
    set_global_mesh(mesh)
    model = causal_lm("llama-tiny", mesh=mesh, num_layers=2, hidden_size=64,
                      intermediate_size=128, num_heads=4, num_kv_heads=2,
                      vocab_size=256, remat=False)
    serve = ServingEngine(model, {"dtype": "float32",
                                  "max_out_tokens": 300}, num_slots=1)
    assert serve.cache_len == 512          # physical: rounded to 256-mult
    assert serve.max_out == 300            # logical: the configured budget
    with pytest.raises(ValueError, match="max_out_tokens=300"):
        serve.submit(np.zeros(301, np.int32), max_new_tokens=1)


def test_serving_smoke_single_program(served):
    """Fast smoke: occupancy varies (1 -> 2 -> 1 -> 0 slots) while the
    decode block stays ONE compiled program (static shapes + active mask)."""
    _, _, _, serve = served
    base = len(serve.scheduler.finished)
    serve.submit(np.asarray([1, 2, 3], np.int32), max_new_tokens=6)
    serve.step()
    calls = {"n": 0}
    real = serve._block()

    def counted(*a, **k):
        calls["n"] += 1
        return real(*a, **k)

    serve._block_fn = counted
    serve.submit(np.asarray([4, 5], np.int32), max_new_tokens=7)
    serve.run()
    serve._block_fn = real
    assert calls["n"] >= 2          # ran decode blocks through the wrapper
    assert len(serve.scheduler.finished) - base == 2
    assert not serve.scheduler.has_work


def test_serving_metrics_enabled_parity_and_live_endpoints(served, rng):
    """The acceptance loop for the observability layer: with the metrics
    registry ENABLED and the HTTP exporter LIVE (init_serving(
    metrics_port=0) -> ephemeral port), a mixed request wave must (a) stay
    token-identical to sequential generate(), (b) fill the TTFT /
    queue-wait / per-token-decode histograms, and (c) serve /metrics
    (Prometheus text) + /statz (JSON) mid-loop while requests are still
    in flight."""
    import json
    import urllib.error
    import urllib.request

    import deepspeed_tpu
    from deepspeed_tpu.monitor.metrics import get_registry

    _, _, ref, _ = served
    reg = get_registry()
    reg.enable()
    # share the fixture InferenceEngine's weights; the ephemeral-port
    # exporter comes up with the engine
    serve = deepspeed_tpu.init_serving(
        engine=ref, num_slots=2, prefill_chunk=4,
        decode_block_tokens=3, metrics_port=0)
    try:
        reg.reset()                   # this wave only
        prompts, news = _mixed_requests(rng)
        want = [np.asarray(ref.generate(p[None], max_new_tokens=n,
                                        do_sample=False))[0, len(p):]
                for p, n in zip(prompts, news)]
        reqs = [serve.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, news)]
        # scrape MID-LOOP: step until something is in flight, then GET
        serve.step()
        url = serve.metrics_server.url
        prom = urllib.request.urlopen(url + "/metrics").read().decode()
        assert "# TYPE ds_serve_ttft_seconds histogram" in prom
        assert "ds_serve_queue_wait_seconds_bucket" in prom
        serve.run()
        statz = json.loads(
            urllib.request.urlopen(url + "/statz").read().decode())
        m = statz["metrics"]
        n = len(reqs)
        assert m["ds_serve_ttft_seconds"]["count"] == n
        assert m["ds_serve_queue_wait_seconds"]["count"] == n
        # no stream, no EOS: every token is fetched at the finish, so a
        # request has a latency and no per-token pace to record
        assert m["ds_serve_tpot_seconds"]["count"] == 0
        assert m["ds_serve_decode_tokens_total"] > 0
        assert m["ds_serve_submitted_total"] == n
        reasons = m["ds_serve_finished_total"]
        assert sum(reasons.values()) == n
        assert reasons['{reason="length"}'] == n          # no EOS stops here
        # 404 for anything else
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(url + "/nope")
        # (a) token parity with metrics enabled + exporter live
        for i, (req, w) in enumerate(zip(reqs, want)):
            np.testing.assert_array_equal(
                np.asarray(req.output_tokens), w,
                err_msg=f"request {i} diverged with metrics enabled")
    finally:
        serve.close()                 # stops the exporter (port released)
        assert serve.metrics_server is None
        reg.disable()


def test_request_spans_reconcile_with_latency(served, rng):
    """The ISSUE 7 reconciliation contract: with the request tracer on,
    every finished request's four-phase edge partition must telescope to
    exactly its latency, the ``ds_serve_phase_*_seconds`` histograms must
    see one observation per finished request (same count as the latency
    histogram), and the four phase sums must add up to the latency
    histogram's sum — the aggregate and per-request views agree."""
    from deepspeed_tpu.monitor.metrics import get_registry
    from deepspeed_tpu.monitor.request_trace import (PHASES,
                                                     get_request_tracer)

    _, _, _, serve = served
    reg = get_registry()
    reg.enable()
    reg.reset()
    tracer = get_request_tracer()
    tracer.reset()
    tracer.enable()
    prompts, news = _mixed_requests(rng)
    reqs = [serve.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, news)]
    serve.run()
    n = len(reqs)
    by_id = {r["id"]: r for r in tracer.completed()}
    for req in reqs:
        rec = by_id[req.request_id]
        # per-request: the edge partition telescopes to the latency
        assert sum(rec["phases"].values()) == pytest.approx(
            rec["latency_s"], rel=1e-9, abs=1e-12)
        assert rec["latency_s"] == req.t_finish - req.t_submit
        assert rec["reason"] == req.finish_reason
        assert rec["tokens_out"] == len(req.output_tokens)
        # the measured dispatch spans rode along with token counts
        kinds = {s[0] for s in rec["spans"]}
        assert "prefill_chunk" in kinds and "decode_block" in kinds
        assert sum(s[3] for s in rec["spans"]
                   if s[0] == "prefill_chunk") == req.prompt_len
    # aggregate: one observation per request in every phase histogram,
    # and the phase sums reconcile with the latency histogram's sum
    m = reg.snapshot()
    lat = m["ds_serve_request_latency_seconds"]
    assert lat["count"] == n
    phase_sum = 0.0
    for p in PHASES:
        h = m[f"ds_serve_phase_{p}_seconds"]
        assert h["count"] == n, (p, h)
        phase_sum += h["sum"]
    assert phase_sum == pytest.approx(lat["sum"], rel=1e-9)
    # the tail-attribution summary is non-degenerate over a real wave
    ta = tracer.tail_attribution(p=0.5)
    assert ta["tail_n"] >= 1 and ta["dominant_phase"] in PHASES
    assert sum(ta["phase_share"].values()) == pytest.approx(1.0)


def test_requestz_live_endpoint_and_profilez_clock_agreement(served, rng):
    """The ISSUE 7 acceptance e2e: against ONE live serving run,
    ``/requestz?format=perfetto`` and a ``/profilez?steps=N`` capture
    must share a clock domain — the tracer's anchor is stamped at
    ``start_trace`` (source ``trace_session``), the capture summary
    carries the same anchor, and the request spans recorded during the
    capture overlap the capture's ``[window_lo_us, window_hi_us]``
    device window, so both files load in one Perfetto session with
    aligned timelines."""
    import json
    import threading
    import urllib.request

    from deepspeed_tpu.monitor.metrics import get_registry
    from deepspeed_tpu.monitor.request_trace import get_request_tracer
    _, _, ref, _ = served
    reg = get_registry()
    reg.enable()
    serve = deepspeed_tpu.init_serving(
        engine=ref, num_slots=2, prefill_chunk=4, decode_block_tokens=3,
        metrics_port=0, request_trace=True)
    tracer = get_request_tracer()
    tracer.reset()
    stop = threading.Event()

    def waves():
        while not stop.is_set():
            for _ in range(2):
                serve.submit(np.asarray([1, 2, 3], np.int32),
                             max_new_tokens=5)
            serve.run()

    t = threading.Thread(target=waves, daemon=True)
    t.start()
    try:
        url = serve.metrics_server.url
        with urllib.request.urlopen(
                f"{url}/profilez?steps=3&timeout=120", timeout=150) as r:
            summary = json.load(r)
        with urllib.request.urlopen(
                f"{url}/requestz?format=perfetto", timeout=10) as r:
            trace = json.load(r)
    finally:
        stop.set()
        t.join(timeout=30)
        serve.close()
    # both surfaces carry the SAME trace-session anchor
    assert summary["clock"]["source"] == "trace_session"
    other = trace["otherData"]
    assert other["clock_source"] == "trace_session"
    assert other["clock_anchor_unix"] == summary["clock"]["anchor_unix"]
    # clock-domain agreement: request spans recorded while the capture
    # was open land inside (overlap) the capture's device window, in the
    # file's own microsecond domain — the one-Perfetto-session contract
    lo, hi = summary["window_lo_us"], summary["window_hi_us"]
    assert hi > lo
    xs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
    assert xs, "no request spans exported during a live run"
    overlapping = [e for e in xs
                   if e["ts"] < hi and e["ts"] + e["dur"] > lo]
    assert overlapping, (
        f"no request span overlaps the capture window [{lo}, {hi}]us — "
        f"the /requestz and /profilez clock domains diverged")


@pytest.mark.parametrize("position,fused", [("learned", False),
                                            ("rope", False),
                                            ("alibi", True)])
def test_continuous_batching_parity_other_paths(devices, rng, position,
                                                fused):
    """Per-row positions must stay exact for every position scheme AND on
    both decode implementations: the fused Pallas decode_step (per-row
    kernel mask/clamp) and the unfused forward_with_cache vector branch
    (per-row gather/scatter).  The main parity test covers rope+fused."""
    mesh = build_mesh(fsdp=8, devices=devices)
    set_global_mesh(mesh)
    model = causal_lm("llama-tiny", mesh=mesh, num_layers=2, hidden_size=64,
                      intermediate_size=128, num_heads=4, num_kv_heads=2,
                      vocab_size=256, remat=False, position=position,
                      max_seq_len=64)
    prompts, news = _mixed_requests(rng, n=3)
    params = model.init(rng, jnp.asarray(prompts[0])[None])
    cfg = {"dtype": "float32", "max_out_tokens": 64,
           "use_fused_decode": fused, "kv_page_tokens": 16}
    ref = deepspeed_tpu.init_inference(model, config=cfg)
    ref.set_params(params)
    want = [np.asarray(ref.generate(p[None], max_new_tokens=n,
                                    do_sample=False))[0, len(p):]
            for p, n in zip(prompts, news)]
    serve = deepspeed_tpu.init_serving(
        model, config=cfg, num_slots=2, prefill_chunk=4,
        decode_block_tokens=3)
    serve.set_params(params)
    assert (serve.engine._dparams is not None) == fused
    reqs = [serve.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
    serve.run()
    for i, (req, w) in enumerate(zip(reqs, want)):
        np.testing.assert_array_equal(np.asarray(req.output_tokens), w,
                                      err_msg=f"{position} request {i}")
