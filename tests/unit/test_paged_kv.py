"""Paged KV cache (serving/paged_kv.py + the paged serving engine):
allocator unit behavior (alloc/free/LIFO reuse, exhaustion, leak probe),
pool-pressure preempt-and-resume staying token-identical to sequential
``generate()``, the chunk program's view of a slot, and the sync-free EOS
decode (finish events drained one block BEHIND dispatch — no per-step
host-device sync).  Engines are module-scoped where possible: compiles
dominate tier-1 wall time."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.comm.mesh import build_mesh, set_global_mesh
from deepspeed_tpu.models import causal_lm
from deepspeed_tpu.serving import PagedKVPool


@pytest.fixture(autouse=True)
def _no_unknown_finish_reasons():
    """Same tier-1 guard as test_serving: every release path must
    attribute its finish reason."""
    from deepspeed_tpu.monitor.metrics import get_registry

    yield
    c = get_registry().get("ds_serve_finished_total",
                           labels={"reason": "unknown"})
    assert c is None or c.value == 0


# ---------------------------------------------------------------------------
# allocator unit tests (pure host bookkeeping, no jax)
# ---------------------------------------------------------------------------

def test_pool_alloc_free_reuse():
    pool = PagedKVPool(2, 64, page_tokens=16)
    assert pool.page == 16 and pool.slot_pages == 4 and pool.cache_len == 64
    assert pool.num_pages == 9                # 2 x 4 usable + junk page 0
    assert pool.ensure(0, 1) and pool.slot_pages_used(0) == 1
    assert pool.ensure(0, 16) and pool.slot_pages_used(0) == 1   # same page
    assert pool.ensure(0, 17) and pool.slot_pages_used(0) == 2   # crosses
    assert 0 not in pool.page_table[0, :2]    # junk page never allocated
    assert (pool.page_table[0, 2:] == 0).all()  # unallocated -> junk
    assert pool.ensure(1, 64)
    assert pool.pages_used == 6 and pool.pages_free == 2
    assert pool.ensure(0, 64) and pool.pages_free == 0
    with pytest.raises(ValueError):           # beyond the per-slot budget
        pool.ensure(0, 65)
    assert pool.release(1) == 4
    assert (pool.page_table[1] == 0).all() and pool.pages_free == 4
    lifo_next = pool._free[-1]                # most recently freed
    assert pool.ensure(1, 1) and pool.page_table[1, 0] == lifo_next
    pool.check_no_leak()


def test_pool_exhaustion_keeps_partial_grant():
    pool = PagedKVPool(2, 64, page_tokens=16, pool_tokens=80)  # 5 usable
    assert pool.ensure(0, 64)                 # 4 pages
    assert not pool.ensure(1, 32)             # needs 2, only 1 free
    assert pool.slot_pages_used(1) == 1       # the grant sticks
    pool.release(0)
    assert pool.ensure(1, 32)                 # satisfiable after release
    pool.check_no_leak()


def test_pool_sizing_defaults():
    pool = PagedKVPool(4, 300)
    # page = flash-decode block; window rounds 300 up to a page multiple
    assert pool.page == 256 and pool.slot_pages == 2
    assert pool.cache_len == 512
    assert pool.num_pages == 4 * 2 + 1
    assert PagedKVPool(4, 64).page == 64      # capped at pow2(max_out)
    # the pool never drops below one full slot window (no self-deadlock)
    assert PagedKVPool(4, 64, page_tokens=16,
                       pool_tokens=16).num_pages == 4 + 1


# ---------------------------------------------------------------------------
# end-to-end paged serving on the CPU mesh
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def setup(devices):
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
    return model, params, ref


def _serve(model, params, **over):
    cfg = {"dtype": "float32", "max_out_tokens": 64, "kv_page_tokens": 16,
           **over}
    s = deepspeed_tpu.init_serving(model, config=cfg, num_slots=2,
                                   prefill_chunk=4, decode_block_tokens=3)
    s.set_params(params)
    return s


def _ref_out(ref, prompt, n):
    return np.asarray(ref.generate(np.asarray(prompt)[None],
                                   max_new_tokens=n,
                                   do_sample=False))[0, len(prompt):]


def _truncate(seq, eos):
    """``seq`` up to and including its first ``eos``."""
    out = []
    for t in seq:
        out.append(int(t))
        if int(t) == eos:
            break
    return out


def test_pool_pressure_preempts_and_resumes_token_identical(setup, rng):
    """An oversubscribed pool (5 pages for two 3-page requests) must
    preempt the YOUNGEST slot, requeue it at the queue head, and resume it
    token-identically (the resume re-prefills prompt + produced tokens, so
    the greedy continuation cannot drift) — and no page may leak."""
    model, params, ref = setup
    serve = _serve(model, params, kv_pool_tokens=80)   # 5 usable pages
    assert serve.pool.num_pages == 6
    k1, k2 = jax.random.split(rng)
    prompts = [np.asarray(jax.random.randint(k1, (8,), 0, 256)),
               np.asarray(jax.random.randint(k2, (9,), 0, 256))]
    want = [_ref_out(ref, p, 40) for p in prompts]     # pos -> 47/48: 3 pages
    reqs = [serve.submit(p, max_new_tokens=40) for p in prompts]
    serve.run()
    assert sum(r.preemptions for r in reqs) >= 1, \
        "5-page pool serving two 3-page requests must preempt"
    for i, (req, w) in enumerate(zip(reqs, want)):
        np.testing.assert_array_equal(
            np.asarray(req.output_tokens), w,
            err_msg=f"request {i} diverged across the preempt-resume cycle")
    # free-on-finish freed everything; the allocator leaked nothing
    assert serve.pool.pages_used == 0
    serve.pool.check_no_leak()
    assert serve.scheduler.drain_finished()            # history drainable
    serve.pool.check_no_leak()


def test_preempt_correlates_flight_events_with_request_timelines(setup,
                                                                 rng):
    """ISSUE 7 correlation contract: with the flight recorder AND the
    request tracer on, a pool-pressure preempt-resume run must leave
    ``serve_admit`` / ``serve_preempt`` / ``serve_finish`` events whose
    ``rid`` fields match the tracer's completed timelines — a
    watchdog-tripped flight dump and ``/requestz`` exemplars join by id.
    The preempt event carries the reclaim size; the preempted request's
    timeline shows the ``preempted_wait`` phase."""
    from deepspeed_tpu.monitor.flight_recorder import get_flight_recorder
    from deepspeed_tpu.monitor.metrics import get_registry
    from deepspeed_tpu.monitor.request_trace import get_request_tracer

    model, params, ref = setup
    flight = get_flight_recorder()
    tracer = get_request_tracer()
    reg = get_registry()
    serve = _serve(model, params, kv_pool_tokens=80)    # 5 usable pages
    flight.enable()
    flight.reset()      # the ring is process-global: drop any residue a
    reg.enable()        # previous test's enabled window left behind
    reg.reset()
    tracer.reset()
    tracer.enable()
    try:
        k1, k2 = jax.random.split(rng)
        prompts = [np.asarray(jax.random.randint(k1, (8,), 0, 256)),
                   np.asarray(jax.random.randint(k2, (9,), 0, 256))]
        reqs = [serve.submit(p, max_new_tokens=40) for p in prompts]
        serve.run()
        assert sum(r.preemptions for r in reqs) >= 1
        evs = flight.events()
        by_kind = {}
        for e in evs:
            by_kind.setdefault(e["kind"], []).append(e)
        rids = {r.request_id for r in reqs}
        # every lifecycle event names its request; ids line up with the
        # tracer's completed timelines
        assert {e["rid"] for e in by_kind["serve_finish"]} == rids
        assert {e["rid"] for e in by_kind["serve_admit"]} >= rids
        pre = by_kind["serve_preempt"]
        assert pre and all(e["rid"] in rids for e in pre)
        assert all(e["pages_freed"] > 0 and e["tokens_reclaimed"] > 0
                   for e in pre)
        timelines = {r["id"]: r for r in tracer.completed()}
        assert set(timelines) == rids
        for e in pre:
            rec = timelines[e["rid"]]
            assert rec["preemptions"] >= 1
            assert rec["phases"]["preempted_wait"] > 0
        for e in by_kind["serve_finish"]:
            assert timelines[e["rid"]]["reason"] == e["reason"]
        # queue wait is recorded once per REQUEST, not per admission: a
        # preempt's re-admission wait is the preempted_wait phase, never
        # a second (run-length-sized) queue_wait observation
        assert reg.get("ds_serve_queue_wait_seconds").count == len(reqs)
    finally:
        flight.disable()
        tracer.disable()
        reg.reset()


def test_eos_decode_runs_sync_free(setup, rng):
    """EOS workloads must not sync the host per decode block: every fetch
    of a block's (toks, valid) pair happens either at least one block
    BEHIND dispatch (the deferred drain — its RTT overlaps live device
    work) or after the host has nothing left to dispatch (tail flush).
    Instrumented at ``_fetch_block``, the single device->host readback
    point — the same style of structural assertion the no-EOS fast path's
    smoke test uses on ``_block``.  Outputs must equal the no-EOS greedy
    trajectory truncated at the first EOS occurrence (inclusive)."""
    model, params, ref = setup
    serve = _serve(model, params)                      # ample pool
    prompts = [np.asarray(jax.random.randint(k, (n,), 0, 256))
               for k, n in zip(jax.random.split(rng, 3), (3, 5, 7))]
    news = [8, 8, 8]
    base = [_ref_out(ref, p, n) for p, n in zip(prompts, news)]
    # request 0 stops mid-decode; request 1's eos never fires (drain
    # releases it by length); request 2 stops near the tail
    eos_ids = [int(base[0][3]),
               int((set(range(256)) - set(base[1].tolist())).pop()),
               int(base[2][-2])]

    want = [_truncate(b, e) for b, e in zip(base, eos_ids)]
    fetches = []
    real_fetch = serve._fetch_block

    def probing(idx):
        fetches.append((idx, serve._next_block, bool(serve._active.any())))
        return real_fetch(idx)

    serve._fetch_block = probing
    try:
        reqs = [serve.submit(p, max_new_tokens=n, eos_token_id=e)
                for p, n, e in zip(prompts, news, eos_ids)]
        serve.run()
    finally:
        del serve.__dict__["_fetch_block"]
    assert fetches, "EOS workload must flow through the deferred drain"
    for idx, next_block, active in fetches:
        assert idx < next_block - 1 or not active, (
            f"block {idx} was fetched the same iteration it was dispatched "
            f"(next_block={next_block}) with rows still active — a "
            f"per-step host-device sync")
    for i, (req, w) in enumerate(zip(reqs, want)):
        assert req.output_tokens == w, (
            f"eos request {i}: {req.output_tokens} != {w}")
    assert reqs[0].finish_reason == "eos"
    assert reqs[1].finish_reason == "length"
    assert reqs[2].finish_reason == "eos"


def test_int8_kv_paged_parity(setup, rng):
    """Quantized KV + paged pool (the unfused gather path carries the
    int8 payloads AND their fp32 scales through the same page tables):
    token-identical to the int8-KV ``generate()``."""
    model, params, _ = setup
    cfg = {"dtype": "float32", "max_out_tokens": 64,
           "quantize_kv_cache": True, "kv_page_tokens": 16}
    ref = deepspeed_tpu.init_inference(model, config=cfg)
    ref.set_params(params)
    serve = deepspeed_tpu.init_serving(model, config=cfg, num_slots=2,
                                       prefill_chunk=4,
                                       decode_block_tokens=3)
    serve.set_params(params)
    assert serve.engine._dparams is None        # int8 KV = unfused path
    prompts = [np.asarray(jax.random.randint(k, (n,), 0, 256))
               for k, n in zip(jax.random.split(rng, 3), (3, 6, 9))]
    news = [5, 7, 4]
    want = [_ref_out(ref, p, n) for p, n in zip(prompts, news)]
    reqs = [serve.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
    serve.run()
    for i, (req, w) in enumerate(zip(reqs, want)):
        np.testing.assert_array_equal(np.asarray(req.output_tokens), w,
                                      err_msg=f"int8-KV paged request {i}")


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8kv"])
def test_chunk_program_through_a_scattered_table_row(setup, rng, quantized):
    """ISSUE 37: the chunk program builds a slot's view from its pages by
    slices (``cache_kind._slot_view`` / ``_slot_write_back``).  Through a
    row whose pages lie out of order in a pool full of other slots' rows,
    with its unallocated tail all naming junk page 0, a 16-token chunk across
    a page boundary and then a 5-token last chunk give the tokens and the K /
    V rows (int8 KV: the scales too) that ``forward_with_cache`` gives on a
    contiguous ``init_kv_cache`` cache at the same positions, and no page of
    another slot changes by a bit."""
    from deepspeed_tpu.models.decoding import (forward_with_cache,
                                               init_kv_cache,
                                               next_token_logits)

    model, params, _ = setup
    paged = deepspeed_tpu.init_serving(
        model, config={"dtype": "bfloat16", "max_out_tokens": 64,
                       "kv_page_tokens": 8, "quantize_kv_cache": quantized},
        num_slots=3, prefill_chunk=16, decode_block_tokens=3)
    paged.set_params(params)
    slot, row = 1, np.array([5, 2, 7, 0, 0, 0, 0, 0], np.int32)
    assert paged.pool.slot_pages == len(row) and paged.pool.page == 8
    keys = iter(jax.random.split(rng, 8))

    def junk(x):                         # finite rows of other requests
        if x.dtype == jnp.int8:
            return jax.random.randint(next(keys), x.shape, -127, 128,
                                      jnp.int8)
        return jax.random.uniform(next(keys), x.shape, jnp.float32,
                                  0.5, 1.5).astype(x.dtype)

    pool = {k: (junk(v) if v.ndim == 5 else v)
            for k, v in paged._cache.items()}
    before = {k: np.asarray(v) for k, v in pool.items() if v.ndim == 5}
    prompt = np.asarray(jax.random.randint(next(keys), (21,), 0, 256),
                        np.int32)
    chunks = []
    for start, cb, c in ((0, 16, 16), (16, 8, 5)):
        chunk = np.zeros((1, cb), np.int32)
        chunk[0, :c] = prompt[start:start + c]
        chunks.append((start, c, jnp.asarray(chunk)))

    toks_p, cache = [], pool
    carries = (paged._last_dev, paged._pos_dev, paged._act_dev)
    for start, c, chunk in chunks:
        meta = jnp.asarray([slot, start, c - 1, 0, -1], jnp.int32)
        tok, cache, carries = paged._prefill_fn(chunk.shape[1])(
            paged.engine._params, cache, carries, jnp.asarray(row), chunk,
            meta, jax.random.PRNGKey(0))
        toks_p.append(int(tok))
        assert int(carries[0][slot]) == toks_p[-1]
        assert int(carries[1][slot]) == start + c
    after = {k: np.asarray(v) for k, v in cache.items() if v.ndim == 5}

    toks_c = []
    flat = init_kv_cache(model.config, 1, 64, dtype=paged.engine.dtype,
                         quantized=quantized)
    step = jax.jit(lambda p, t, c, s: forward_with_cache(model, p, t, c, s))
    for start, c, chunk in chunks:
        logits, flat = step(paged.engine._params, chunk, flat, start)
        toks_c.append(int(jnp.argmax(
            next_token_logits(model.config, logits[:, c - 1]), -1)[0]))
    assert toks_p == toks_c
    assert set(after) == ({"k", "v", "k_scale", "v_scale"} if quantized
                          else {"k", "v"})
    theirs = [p for p in range(paged.pool.num_pages)
              if p not in set(row.tolist())]
    for name, pages in after.items():
        L, _, Hkv, page, D = pages.shape
        mine = pages[:, row[:3]].transpose(0, 2, 1, 3, 4).reshape(
            L, Hkv, 3 * page, D)
        np.testing.assert_array_equal(
            mine[:, :, :21], np.asarray(flat[name])[:, 0, :, :21],
            err_msg=name)
        np.testing.assert_array_equal(pages[:, theirs],
                                      before[name][:, theirs], err_msg=name)
    paged.close()


# ---------------------------------------------------------------------------
# first tokens fetched behind the decode block (ISSUE 28): the last chunk's
# program wakes its slot on the device and the host reads the value only
# after the iteration's block is enqueued.  Served tokens must be what they
# were when the host read each first token right behind its chunk: equal
# to generate() (greedy), and equal to the same engine run in that older
# order from the same key (greedy and sampled; same programs, same splits).
# ---------------------------------------------------------------------------

OVERLAP_PATHS = ["stream", "eos_later", "eos_first", "max_new_1",
                 "window_less_one", "window_full", "two_last_chunks",
                 "abort_owed"]


@pytest.fixture(scope="module", params=[16, 8], ids=["page16", "page8"])
def overlap(request, setup):
    """One greedy and one sampling engine per page size, two slots, ample
    pool; a reference with room for one token past the serving window.
    Pages of 8: the 9-token prompt's last chunk and every request's decode
    blocks cross a page between a first token and its fetch."""
    model, params, _ = setup
    cfg = {"dtype": "float32", "max_out_tokens": 64,
           "kv_page_tokens": request.param}
    engines = {}
    for name, kw in (("greedy", {}),
                     ("sampled", {"do_sample": True, "temperature": 0.8,
                                  "top_k": 24})):
        engines[name] = deepspeed_tpu.init_serving(
            model, config=dict(cfg), num_slots=2, prefill_chunk=4,
            decode_block_tokens=3, **kw)
        engines[name].set_params(params)
    ref = deepspeed_tpu.init_inference(
        model, config={"dtype": "float32", "max_out_tokens": 128})
    ref.set_params(params)
    yield engines, ref
    for e in engines.values():
        e.close()


def _fetch_behind_each_chunk(serve, monkeypatch):
    """The order before ISSUE 28: every first token is read right behind
    its own chunk's enqueue, before anything else is dispatched."""
    real = serve._prefill_one_chunk

    def chunk_then_fetch(req):
        real(req)
        serve._settle_first_tokens()

    monkeypatch.setattr(serve, "_prefill_one_chunk", chunk_then_fetch)


def _serve_from_key(serve, submits, hook=None):
    """Serve ``submits`` ([(prompt, kwargs)]) from a fixed sampling key;
    returns [(tokens, finish reason)]."""
    serve._rng = jax.random.PRNGKey(28)
    while serve.prefix_cache is not None and serve.prefix_cache.evict_lru():
        pass              # an earlier run's prompts: every run computes all
    reqs = [serve.submit(p, **kw) for p, kw in submits]
    if hook is not None:
        hook(reqs)
    serve.run()
    assert not serve._owed and not serve._outstanding
    assert serve.pool.pages_used == 0
    serve.pool.check_no_leak()
    assert all(r.done for r in reqs)
    return [(list(r.output_tokens), r.finish_reason) for r in reqs]


@pytest.mark.parametrize("sampling", ["greedy", "sampled"])
@pytest.mark.parametrize("path", OVERLAP_PATHS)
def test_first_token_behind_the_block_serves_the_same_tokens(
        overlap, monkeypatch, path, sampling):
    engines, ref = overlap
    serve = engines[sampling]
    rng = np.random.default_rng(OVERLAP_PATHS.index(path))
    prompt = lambda n: rng.integers(0, 256, n, dtype=np.int32)
    # what the three prompts emit undisturbed (request 0's chunk is the
    # first program from the key, so its first tokens hold in any mix)
    prompts = [prompt(n) for n in (3, 9, 6)]
    base = _serve_from_key(
        serve, [(p, dict(max_new_tokens=8, stream=True)) for p in prompts])
    unseen = int((set(range(256))
                  - {t for toks, _ in base for t in toks}).pop())
    hook, want_reasons = None, None
    if path == "stream":
        submits = [(p, dict(max_new_tokens=8, stream=True)) for p in prompts]
        want_reasons = ["length"] * 3
    elif path == "eos_later":
        submits = [(prompts[0], dict(max_new_tokens=8,
                                     eos_token_id=base[0][0][3])),
                   (prompts[1], dict(max_new_tokens=8, eos_token_id=unseen)),
                   (prompts[2], dict(max_new_tokens=8, stream=True))]
    elif path == "eos_first":
        submits = [(prompts[0], dict(max_new_tokens=8,
                                     eos_token_id=base[0][0][0])),
                   (prompts[1], dict(max_new_tokens=8, stream=True)),
                   (prompts[2], dict(max_new_tokens=8))]
    elif path == "max_new_1":
        submits = [(prompts[0], dict(max_new_tokens=1)),
                   (prompts[1], dict(max_new_tokens=1, stream=True)),
                   (prompts[2], dict(max_new_tokens=1, eos_token_id=unseen))]
        want_reasons = ["length"] * 3
    elif path in ("window_less_one", "window_full"):
        # limit <= S: the prefix leaves the window no room to decode; a
        # full window also leaves no row for the parked slot's junk
        n = serve.max_out - (path == "window_less_one")
        submits = [(prompt(n), dict(max_new_tokens=8, stream=True)),
                   (prompts[1], dict(max_new_tokens=8, stream=True))]
        want_reasons = ["cache_budget", "length"]
    elif path == "two_last_chunks":
        # both fit one chunk: two last chunks in the first iteration, and
        # again when the third takes a freed slot beside a running one
        submits = [(prompt(4), dict(max_new_tokens=5, stream=True)),
                   (prompt(2), dict(max_new_tokens=7, eos_token_id=unseen)),
                   (prompt(3), dict(max_new_tokens=4, stream=True))]
        want_reasons = ["length"] * 3
    else:                                       # abort_owed
        submits = [(prompts[0], dict(max_new_tokens=8, stream=True)),
                   (prompts[1], dict(max_new_tokens=8, stream=True))]
        want_reasons = ["cancelled", "length"]

        def hook(reqs):
            # an abort that meets the request between its last chunk and
            # the block: prompt 0 is one chunk, so that is iteration one
            real = serve._decode_block

            def abort_then_decode():
                monkeypatch.setattr(serve, "_decode_block", real)
                serve._process_abort(reqs[0])
                assert reqs[0].output_tokens and not serve._owed
                real()

            monkeypatch.setattr(serve, "_decode_block", abort_then_decode)

    got = _serve_from_key(serve, submits, hook)
    with monkeypatch.context() as m:
        _fetch_behind_each_chunk(serve, m)
        before = _serve_from_key(serve, submits, hook)
    if (path, sampling) == ("eos_first", "sampled"):
        # the one difference: the host learns of an EOS at the first token
        # with a block already enqueued (as of any EOS, one block late), and
        # that block's key splits move the LATER draws of a sampling engine
        assert got[0] == before[0]
        assert [(len(t), r) for t, r in got] == \
            [(len(t), r) for t, r in before]
    else:
        assert got == before
    if want_reasons is not None:
        assert [r for _, r in got] == want_reasons
    if path == "eos_first":
        assert got[0] == ([base[0][0][0]], "eos")
    if path == "eos_later":
        assert got[0] == (base[0][0][:4], "eos")
    if path == "abort_owed":
        assert got[0][0] == base[0][0][:1]
    if sampling == "greedy":
        for (p, kw), (toks, reason) in zip(submits, got):
            if reason == "cancelled":
                continue
            want = _ref_out(ref, p, kw["max_new_tokens"])
            want = _truncate(want, kw.get("eos_token_id", -1))
            if reason == "cache_budget":
                want = want[:len(toks)]
                assert len(toks) == 1
            assert toks == want


@pytest.mark.parametrize("sampling", ["greedy", "sampled"])
def test_pool_pressure_meets_an_owed_first_token(setup, monkeypatch,
                                                 sampling):
    """Preemption with a first token owed: a dry pool reads the owed
    value before it chooses a victim (before ISSUE 28 it had been read
    behind its chunk), so the same requests are preempted and resume
    token-identically."""
    model, params, ref = setup
    kw = ({} if sampling == "greedy"
          else {"do_sample": True, "temperature": 0.8, "top_k": 24})
    serve = deepspeed_tpu.init_serving(
        model, config={"dtype": "float32", "max_out_tokens": 64,
                       "kv_page_tokens": 16, "kv_pool_tokens": 64},
        num_slots=2, prefill_chunk=4, decode_block_tokens=3, **kw)
    serve.set_params(params)
    rng = np.random.default_rng(7)
    # four pages of 16: while the short request decodes inside two pages
    # the long one's 32 tokens fill two, a chunk an iteration; the block
    # behind its last chunk needs a fifth page with its first token owed
    submits = [(rng.integers(0, 256, n, dtype=np.int32),
                dict(max_new_tokens=m, stream=True))
               for n, m in ((3, 40), (32, 10))]
    met = []
    real_ensure, real_preempt = serve._ensure_pages, serve._preempt

    def ensure(req, tokens):
        owed = len(serve._owed)
        ok = real_ensure(req, tokens)
        if owed and not serve._owed:
            met.append(owed)
        return ok

    def preempt(victim):
        assert not serve._owed
        real_preempt(victim)

    monkeypatch.setattr(serve, "_ensure_pages", ensure)
    monkeypatch.setattr(serve, "_preempt", preempt)
    reqs = []
    got = _serve_from_key(serve, submits, reqs.extend)
    preempted = [r.preemptions for r in reqs]
    assert sum(preempted) >= 1 and met, (preempted, met)
    with monkeypatch.context() as m:
        _fetch_behind_each_chunk(serve, m)
        reqs = []
        before = _serve_from_key(serve, submits, reqs.extend)
    assert got == before and [r.preemptions for r in reqs] == preempted
    if sampling == "greedy":
        for (p, kw), (toks, reason) in zip(submits, got):
            assert toks == _ref_out(ref, p, kw["max_new_tokens"]).tolist()
            assert reason == "length"
    serve.close()


# ---------------------------------------------------------------------------
# the decode attention kernels visit the rows that decode (ISSUE 39): the
# block hands its live mask down to them.  What the benchmark's cells check
# on the chip: the served tokens are what they are without the mask.
# ---------------------------------------------------------------------------

def test_served_tokens_do_not_depend_on_the_live_mask(setup, rng,
                                                      monkeypatch):
    """Three slots through the Pallas kernels (interpret mode; pages of
    128): two requests of unequal length, one across
    a page, a third that arrives once the first has left, so rows park, wake
    and prefill while others decode and one block runs with no live row's
    neighbour.  Served once with the block's mask handed to ``decode_step``
    and once with it withheld (every row visited, as before ISSUE 39): the
    same tokens, and ``generate()``'s."""
    from deepspeed_tpu.models import fused_decode
    from deepspeed_tpu.monitor.metrics import MetricsRegistry
    from deepspeed_tpu.ops.pallas import common

    model, params, _ = setup
    monkeypatch.setattr(common, "default_impl", lambda: "interpret")
    prompts = [np.asarray(jax.random.randint(k, (n,), 0, 256))
               for k, n in zip(jax.random.split(rng, 3), (9, 125, 30))]
    news = [4, 8, 6]

    def served(registry):
        serve = deepspeed_tpu.init_serving(
            model, config={"dtype": "float32", "max_out_tokens": 256,
                           "kv_page_tokens": 128},
            num_slots=3, prefill_chunk=64, decode_block_tokens=3,
            registry=registry)
        serve.set_params(params)
        assert serve.engine._dparams is not None          # the fused path
        reqs = [serve.submit(p, max_new_tokens=n)
                for p, n in zip(prompts[:2], news)]
        while not reqs[0].done:
            serve.step()
        reqs.append(serve.submit(prompts[2], max_new_tokens=news[2]))
        serve.run()
        serve.close()
        return [list(r.output_tokens) for r in reqs]

    registry = MetricsRegistry().enable()
    masked = served(registry)
    # the counter behind decode_rows_live_share: rows x steps of the blocks'
    # batch, of which the scheduled tokens are the rows that decoded
    snap = registry.snapshot()
    slots = snap["ds_serve_decode_row_slots_total"]
    assert slots % (3 * 3) == 0
    assert 0 < snap["ds_serve_decode_tokens_total"] < slots
    step = fused_decode.decode_step

    def unmasked(*args, moe_live=None, **kwargs):
        return (*step(*args, **kwargs), None)

    with monkeypatch.context() as m:
        m.setattr(fused_decode, "decode_step", unmasked)
        assert served(MetricsRegistry()) == masked
    long_ref = deepspeed_tpu.init_inference(
        model, config={"dtype": "float32", "max_out_tokens": 256})
    long_ref.set_params(params)
    for got, prompt, n in zip(masked, prompts, news):
        np.testing.assert_array_equal(got, _ref_out(long_ref, prompt, n))
