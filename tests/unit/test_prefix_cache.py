"""Copy-on-write prefix caching (serving/prefix_cache.py + the refcounted
paged pool + the engine's admission match): trie/allocator unit behavior,
greedy-decode PARITY with the cache warm (outputs must be token-identical
to cold runs and to ``generate()``), COW divergence (live requests sharing
cached pages then diverging), eviction-before-preemption ordering, and the
preempt-resume path re-prefilling THROUGH the cache.  The leak probe
(``PagedKVPool.check_no_leak``) runs after every scenario — finish,
eviction, preempt-resume, and ``drain_finished()`` must all keep the
page accounting exact."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.comm.mesh import build_mesh, set_global_mesh
from deepspeed_tpu.models import causal_lm
from deepspeed_tpu.serving import PagedKVPool, PrefixCache


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
# trie + refcounted-pool units (pure host bookkeeping, no jax)
# ---------------------------------------------------------------------------

def _toks(*vals):
    return np.asarray(vals, np.int32)


def test_trie_match_insert_page_granular():
    pool = PagedKVPool(2, 64, page_tokens=4)
    cache = PrefixCache(pool)
    prompt = np.arange(1, 11, dtype=np.int32)          # 10 tokens, 2.5 pages
    assert cache.match(prompt) == []
    # simulate a finished request: pages 1,2 hold the two FULL pages
    assert pool.ensure(0, 10)
    pages = pool.owned(0)
    added = cache.insert(prompt, pages[:2])
    assert added == 2 and len(cache) == 2
    assert pool.pages_cached == 2
    # full-page match only; a diverging second page stops the walk
    assert cache.match(prompt) == pages[:2]
    assert cache.match(prompt[:7]) == pages[:1]        # 1 full page + tail
    assert cache.match(prompt[:3]) == []               # below one page
    div = prompt.copy()
    div[5] = 99
    assert cache.match(div) == pages[:1]
    # duplicate insert keeps the EXISTING node's page (the newcomer's
    # duplicate page is simply not pinned)
    assert pool.ensure(1, 8)
    dup = pool.owned(1)
    assert cache.insert(prompt, dup[:2]) == 0
    assert cache.match(prompt) == pages[:2]
    pool.release(0)
    pool.release(1)
    # cached pages survive their request's release, off the free list
    assert pool.pages_cached == 2 and pool.pages_free == pool.num_pages - 3
    pool.check_no_leak()


def test_pool_refcounts_adopt_share_release():
    pool = PagedKVPool(3, 64, page_tokens=16)
    assert pool.ensure(0, 48)                          # 3 private pages
    shared = pool.owned(0)
    cache = PrefixCache(pool)
    cache.insert(np.arange(48, dtype=np.int32), shared)
    # slot 1 adopts the cached pages read-only: refcounts go to 2
    pool.adopt(1, shared[:2])
    assert [pool.ref(p) for p in shared] == [2, 2, 1]
    assert (pool.page_table[1, :2] == shared[:2]).all()
    assert pool.pages_used == 3                        # distinct pages
    # slot 1 then grows privately past the shared prefix
    assert pool.ensure(1, 48)
    assert pool.slot_pages_used(1) == 3
    assert pool.page_table[1, 2] not in shared
    pool.check_no_leak()
    # releasing the ORIGINAL owner keeps shared pages alive (ref 1 +
    # cache pin); releasing the adopter parks them as cached-only
    assert pool.release(0) == 0                        # all cached/shared
    assert [pool.ref(p) for p in shared] == [1, 1, 0]
    pool.check_no_leak()
    freed = pool.release(1)
    assert freed == 1                                  # only the private page
    assert pool.pages_cached == 3 and pool.pages_used == 0
    pool.check_no_leak()
    # eviction (LRU) hands cached pages back to the free list
    evicted = 0
    while cache.evict_lru():
        evicted += 1
        pool.check_no_leak()
    assert evicted == 3 and pool.pages_cached == 0
    assert pool.pages_free == pool.num_pages - 1
    pool.check_no_leak()


def test_eviction_lru_order_and_ref_protection():
    pool = PagedKVPool(2, 64, page_tokens=4)
    cache = PrefixCache(pool)
    old = np.arange(100, 108, dtype=np.int32)          # 2 pages
    new = np.arange(200, 208, dtype=np.int32)
    assert pool.ensure(0, 8)
    cache.insert(old, pool.owned(0))
    pool.release(0)
    assert pool.ensure(0, 8)
    cache.insert(new, pool.owned(0))
    pool.release(0)
    new_pages = cache.match(new)                       # touches 'new' (LRU)
    # leaf-first + LRU: 'old' leaf goes before anything of 'new'
    old_pages = cache.match(old)
    _ = cache.match(new)                               # make 'new' freshest
    assert cache.evict_lru() == 1
    assert cache.match(old) == old_pages[:1]           # lost its leaf only
    # a page a live slot references is never evicted: adopt 'new' pages
    pool.adopt(1, new_pages)
    while cache.evict_lru():
        pool.check_no_leak()
    assert cache.match(new) == new_pages               # survived eviction
    assert cache.match(old) == []
    pool.release(1)
    pool.check_no_leak()


# ---------------------------------------------------------------------------
# end-to-end serving parity on the CPU mesh
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
                                   prefill_chunk=8, decode_block_tokens=3)
    s.set_params(params)
    return s


def _ref_out(ref, prompt, n):
    return np.asarray(ref.generate(np.asarray(prompt)[None],
                                   max_new_tokens=n,
                                   do_sample=False))[0, len(prompt):]


def _shared_prefix_prompts(rng, prefix_len=48, tails=(4, 7, 2)):
    keys = jax.random.split(rng, len(tails) + 1)
    prefix = np.asarray(jax.random.randint(keys[0], (prefix_len,), 0, 256))
    prompts = [np.concatenate(
        [prefix, np.asarray(jax.random.randint(k, (t,), 0, 256))])
        for k, t in zip(keys[1:], tails)]
    return prefix, prompts


def test_shared_prefix_parity_and_prefill_savings(setup, rng):
    """The tentpole acceptance shape at tier-1 size: a shared-prefix wave
    through a WARM cache must stay token-identical to generate() while
    computing under 60% of the prefill tokens a cold engine pays (every
    follow-up request shares a 3-page prefix, so savings are
    deterministic)."""
    from deepspeed_tpu.monitor.metrics import get_registry

    model, params, ref = setup
    reg = get_registry()
    reg.enable()
    serve = _serve(model, params)
    try:
        prefix, prompts = _shared_prefix_prompts(rng)
        news = [6, 5, 7]
        want = [_ref_out(ref, p, n) for p, n in zip(prompts, news)]
        # wave 1: cold — request 0 warms the cache at its finish
        warm = serve.submit(prompts[0], max_new_tokens=news[0])
        serve.run()
        assert warm.prefix_hit_tokens == 0
        np.testing.assert_array_equal(np.asarray(warm.output_tokens), want[0])
        assert serve.prefix_cache is not None and len(serve.prefix_cache) == 3
        serve.pool.check_no_leak()
        # wave 2: every request (including an exact re-ask of prompt 0)
        # shares the cached 48-token prefix
        reg.reset()
        reqs = [serve.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, news)]
        serve.run()
        for i, (req, w) in enumerate(zip(reqs, want)):
            np.testing.assert_array_equal(
                np.asarray(req.output_tokens), w,
                err_msg=f"request {i} diverged with a warm prefix cache")
        snap = reg.snapshot()
        hit = snap["ds_serve_prefix_hit_tokens_total"]
        miss = snap["ds_serve_prefix_miss_tokens_total"]
        total = sum(len(p) for p in prompts)
        assert hit + miss == total
        # the acceptance floor, deterministically beaten here: 3 x 48
        # shared tokens of 167 total prompt tokens
        assert hit / total >= 0.4, (hit, miss)
        assert snap["ds_serve_prefill_tokens_total"] == miss
        assert all(r.prefix_hit_tokens >= 32 for r in reqs)
        serve.scheduler.drain_finished()
        serve.pool.check_no_leak()
    finally:
        reg.reset()
        reg.disable()
        serve.close()


def test_cow_divergence_two_live_requests(setup, rng):
    """Two LIVE requests adopt the same cached pages (one an exact
    duplicate of the cached prompt — the partial-boundary COW path — one
    diverging mid-prefix) and must both match their cold-run outputs:
    shared pages are read-only, each divergent continuation writes only
    its own private/COW pages."""
    model, params, ref = setup
    serve = _serve(model, params)
    try:
        prefix, prompts = _shared_prefix_prompts(rng, prefix_len=48,
                                                 tails=(6,))
        base = prompts[0]                      # 54 tokens
        fork = base.copy()
        fork[40] = (fork[40] + 1) % 256        # diverges INSIDE page 2
        want_base = _ref_out(ref, base, 8)
        want_fork = _ref_out(ref, fork, 8)
        cow_calls = {"n": 0}
        real_cow = serve._cow_fn()

        def counting_cow(*a):
            cow_calls["n"] += 1
            return real_cow(*a)

        serve._cow_copy = counting_cow
        warm = serve.submit(base, max_new_tokens=8)
        serve.run()
        np.testing.assert_array_equal(np.asarray(warm.output_tokens),
                                      want_base)
        # both live at once (2 slots): the duplicate fully matches the
        # cached pages -> boundary page 3 (rows 48..53) is only partially
        # needed... base re-ask matches 3 full pages = 48 aligned tokens;
        # an exact 48-token prompt would COW.  Drive the COW explicitly:
        exact = serve.submit(prefix, max_new_tokens=8)      # prompt == cache
        forked = serve.submit(fork, max_new_tokens=8)
        serve.run()
        want_exact = _ref_out(ref, prefix, 8)
        np.testing.assert_array_equal(
            np.asarray(exact.output_tokens), want_exact,
            err_msg="exact-duplicate prompt diverged through the COW page")
        np.testing.assert_array_equal(
            np.asarray(forked.output_tokens), want_fork,
            err_msg="mid-prefix fork diverged over shared pages")
        # the exact duplicate matched 47 of its 48 tokens: pages 0,1
        # shared outright, page 2 copy-on-written (one device page copy)
        assert exact.prefix_hit_tokens == 47
        assert cow_calls["n"] >= 1, "exact-duplicate admission must COW"
        # the fork matched the aligned 2-page prefix only
        assert forked.prefix_hit_tokens == 32
        serve.scheduler.drain_finished()
        serve.pool.check_no_leak()
    finally:
        serve.close()


def test_eviction_before_preemption(setup, rng):
    """Pool pressure must reclaim refcount-0 cached pages (LRU) BEFORE
    any live request is preempted: a pool whose free list is exhausted by
    cached history serves a fresh 2-request wave with evictions and ZERO
    preemptions."""
    from deepspeed_tpu.monitor.metrics import get_registry

    model, params, ref = setup
    reg = get_registry()
    reg.enable()
    # 6 usable pages; two 3-page requests fit EXACTLY with nothing spare
    serve = _serve(model, params, kv_pool_tokens=96)
    try:
        assert serve.pool.num_pages == 7
        k1, k2, k3 = jax.random.split(rng, 3)
        warm_p = np.asarray(jax.random.randint(k1, (37,), 0, 256))
        warm = serve.submit(warm_p, max_new_tokens=4)    # 3 pages, 2 cached
        serve.run()
        assert warm.done and serve.pool.pages_cached == 2
        reg.reset()
        prompts = [np.asarray(jax.random.randint(k, (24,), 0, 256))
                   for k in (k2, k3)]
        want = [_ref_out(ref, p, 20) for p in prompts]   # pos -> 44: 3 pages
        reqs = [serve.submit(p, max_new_tokens=20) for p in prompts]
        serve.run()
        snap = reg.snapshot()
        assert snap["ds_serve_prefix_evictions_total"] == 2, \
            "cached pages must be evicted under pool pressure"
        assert snap.get("ds_serve_preempted_total", 0) == 0, \
            "eviction must satisfy pressure BEFORE preempting live slots"
        assert sum(r.preemptions for r in reqs) == 0
        for req, w in zip(reqs, want):
            np.testing.assert_array_equal(np.asarray(req.output_tokens), w)
        serve.scheduler.drain_finished()
        serve.pool.check_no_leak()
    finally:
        reg.reset()
        reg.disable()
        serve.close()


def test_preempt_resume_re_prefills_through_cache(setup, rng):
    """LIFO preemption gets cheaper: the victim's prompt pages are
    inserted into the cache at preempt time, so its requeue-front resume
    re-prefills through the cache — prefill tokens are SAVED on resume
    (asserted), and the continuation stays token-identical."""
    model, params, ref = setup
    serve = _serve(model, params, kv_pool_tokens=80)     # 5 usable pages
    try:
        assert serve.pool.num_pages == 6
        k1, k2 = jax.random.split(rng)
        prompts = [np.asarray(jax.random.randint(k1, (18,), 0, 256)),
                   np.asarray(jax.random.randint(k2, (19,), 0, 256))]
        want = [_ref_out(ref, p, 30) for p in prompts]   # pos -> 48/49
        reqs = [serve.submit(p, max_new_tokens=30) for p in prompts]
        serve.run()
        assert sum(r.preemptions for r in reqs) >= 1, \
            "5-page pool serving two 3-page requests must preempt"
        victims = [r for r in reqs if r.preemptions]
        # the resume matched the victim's own just-cached prompt page(s):
        # at least one full prompt page (16 tokens) was NOT recomputed
        assert all(v.prefix_hit_tokens >= 16 for v in victims), \
            [v.prefix_hit_tokens for v in victims]
        for i, (req, w) in enumerate(zip(reqs, want)):
            np.testing.assert_array_equal(
                np.asarray(req.output_tokens), w,
                err_msg=f"request {i} diverged across preempt-resume "
                        f"through the prefix cache")
        serve.scheduler.drain_finished()
        serve.pool.check_no_leak()
    finally:
        serve.close()


def _on_64_bytes(a):
    """A copy of ``a`` that starts on a 64-byte boundary, where the CPU
    client takes host memory as the device's own instead of copying it."""
    raw = np.zeros(a.nbytes + 64, np.uint8)
    off = -raw.ctypes.data % 64
    out = raw[off:off + a.nbytes].view(a.dtype).reshape(a.shape)
    out[...] = a
    return out


def test_programs_get_snapshots_of_the_hosts_tables(setup, rng, monkeypatch):
    """ISSUE 61: the page table, the position bounds and the EOS ids are host
    arrays that the engine writes in place (ensure, preempt, release, adopt,
    park) while the programs it handed them to may still be queued, and
    ``jnp.asarray`` of host memory need not copy: the CPU client aliases an
    array on a 64-byte boundary, which numpy's allocator gives about one
    array in four.  So what a chunk program and a decode block are handed
    shares no memory with the host's arrays, and with every one of them
    ON such a boundary the preempt-resume schedule of the case above (red
    about one process in eight before, on the same schedule) serves the
    reference's tokens."""
    model, params, ref = setup
    serve = _serve(model, params, kv_pool_tokens=80)
    try:
        serve.pool.page_table = _on_64_bytes(serve.pool.page_table)
        serve._limit = _on_64_bytes(serve._limit)
        serve._eos = _on_64_bytes(serve._eos)
        hosts = (serve.pool.page_table, serve._limit, serve._eos)
        handed = []

        def watched(program, positions):
            def call(*args):
                handed.extend(
                    any(np.shares_memory(np.asarray(args[i]), h)
                        for h in hosts) for i in positions)
                return program(*args)
            return call

        chunk_fn, block = serve._prefill_fn, serve._block
        # (params, cache, carries, table row, chunk, meta, rng)
        monkeypatch.setattr(serve, "_prefill_fn",
                            lambda cb: watched(chunk_fn(cb), (3,)))
        # (params, cache, last, pos, active, limit, eos, rng, table)
        monkeypatch.setattr(serve, "_block",
                            lambda: watched(block(), (5, 6, 8)))
        k1, k2 = jax.random.split(rng)
        prompts = [np.asarray(jax.random.randint(k1, (18,), 0, 256)),
                   np.asarray(jax.random.randint(k2, (19,), 0, 256))]
        reqs = [serve.submit(p, max_new_tokens=30) for p in prompts]
        serve.run()
        assert sum(r.preemptions for r in reqs) >= 1
        assert len(handed) > 8 and not any(handed), \
            "a program was handed the host's own array, not a snapshot"
        for req, p in zip(reqs, prompts):
            np.testing.assert_array_equal(np.asarray(req.output_tokens),
                                          _ref_out(ref, p, 30))
    finally:
        serve.close()


def test_prefix_cache_off_serves_the_same_tokens(setup, rng):
    """``prefix_caching=False`` serves token-identically with zero cache
    state."""
    model, params, ref = setup
    prefix, prompts = _shared_prefix_prompts(rng, tails=(5, 3))
    news = [5, 4]
    want = [_ref_out(ref, p, n) for p, n in zip(prompts, news)]
    off = _serve(model, params, prefix_caching=False)
    try:
        assert off.prefix_cache is None
        for _ in range(2):                      # repeat wave: nothing cached
            reqs = [off.submit(p, max_new_tokens=n)
                    for p, n in zip(prompts, news)]
            off.run()
            for req, w in zip(reqs, want):
                np.testing.assert_array_equal(
                    np.asarray(req.output_tokens), w)
                assert req.prefix_hit_tokens == 0
        off.pool.check_no_leak()
    finally:
        off.close()


@pytest.mark.parametrize("position,fused", [("learned", False),
                                            ("rope", False),
                                            ("alibi", True)])
def test_warm_cache_parity_other_paths(devices, rng, position, fused):
    """Cache-on-vs-off token identity must hold for every position
    scheme AND both decode implementations (the adopted pages' KV is
    position-absolute, so rope/learned/alibi all reuse it exactly; the
    fused Pallas kernel and the unfused gather path both read shared
    pages through the same page-table indirection)."""
    mesh = build_mesh(fsdp=8, devices=devices)
    set_global_mesh(mesh)
    model = causal_lm("llama-tiny", mesh=mesh, num_layers=2, hidden_size=64,
                      intermediate_size=128, num_heads=4, num_kv_heads=2,
                      vocab_size=256, remat=False, position=position,
                      max_seq_len=64)
    prefix, prompts = _shared_prefix_prompts(rng, prefix_len=32,
                                             tails=(5, 9))
    news = [6, 4]
    params = model.init(rng, jnp.asarray(prompts[0])[None])
    cfg = {"dtype": "float32", "max_out_tokens": 64,
           "use_fused_decode": fused, "kv_page_tokens": 16}
    ref = deepspeed_tpu.init_inference(model, config=cfg)
    ref.set_params(params)
    want = [_ref_out(ref, p, n) for p, n in zip(prompts, news)]
    serve = deepspeed_tpu.init_serving(model, config=cfg, num_slots=2,
                                       prefill_chunk=8,
                                       decode_block_tokens=3)
    serve.set_params(params)
    assert (serve.engine._dparams is not None) == fused
    try:
        # wave 1 warms the cache; wave 2 serves the same prompts hot
        for wave in range(2):
            reqs = [serve.submit(p, max_new_tokens=n)
                    for p, n in zip(prompts, news)]
            serve.run()
            for i, (req, w) in enumerate(zip(reqs, want)):
                np.testing.assert_array_equal(
                    np.asarray(req.output_tokens), w,
                    err_msg=f"{position}/fused={fused} request {i} "
                            f"wave {wave}")
            if wave:
                assert all(r.prefix_hit_tokens >= 16 for r in reqs)
            serve.scheduler.drain_finished()
            serve.pool.check_no_leak()
    finally:
        serve.close()
