"""EvaByte (``attention="eva"``) at a tiny size on the CPU, seeded weights:
window 32, chunk 4, page 8, 2 layers, 4 heads, 8 prediction heads, float32.

The plain reference (``benchmarks/reference/evabyte.py``, written from the
issue's equations) against each of the three forwards on all ``8 x V``
logits, the pool's two page kinds through finish, preemption and failure,
and what the model refuses.

TOLERANCE.  Everything here runs in float32, program and reference, so the
two differ by summation order only: about 2e-6 on logits of magnitude 4
(measured).  ``ATOL = 2e-5`` leaves that ten times its size and is tight
enough that each of the three faults below misses it by orders of
magnitude (``test_the_tolerance_catches_*``): the residual carried in bf16
(about 3e-2), the chunk summaries lost (about 1e-1), a window page read one
window stale (about 1e-1).
"""

import importlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import CausalLM, ModelConfig, eva
from deepspeed_tpu.models.decoding import (_scatter_view, forward_with_cache,
                                           paged_logical_view)
from deepspeed_tpu.models.fused_decode import (decode_step,
                                               inject_decode_params)
from deepspeed_tpu.serving.paged_kv import PagedKVPool, init_paged_kv_cache
from tests.unit._serving import as_found

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ATOL = 2e-5
V, W, C, PAGE = 40, 32, 4, 8
TINY = dict(vocab_size=V, hidden_size=64, intermediate_size=96, num_layers=2,
            num_heads=4, head_dim=16, max_seq_len=256, rope_theta=100000.0,
            attention="eva", eva_window=W, eva_chunk=C, num_pred_heads=8,
            norm_add_unit_offset=True, fp32_residual=True)
REF_CONFIG = dict(num_hidden_layers=2, num_attention_heads=4,
                  rms_norm_eps=1e-5, rope_theta=100000.0, window_size=W,
                  chunk_size=C, vocab_size=V)
ENGINE = dict(dtype="float32", num_slots=3, prefill_chunk=16,
              max_prefill_chunks=2, decode_block_tokens=4,
              max_out_tokens=160, kv_page_tokens=PAGE)


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "_ref_evabyte",
        os.path.join(REPO, "benchmarks", "reference", "evabyte.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model():
    return CausalLM(ModelConfig(**TINY))


@pytest.fixture(scope="module")
def params(model):
    """Seeded weights with gains and pooling vectors away from their
    initial values, so that ``1 + g`` and both pooling softmaxes matter."""
    p = model.init(jax.random.PRNGKey(0))
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 8))
    for name in ("attn_norm", "mlp_norm"):
        g = p["layers"][name]["scale"]
        p["layers"][name]["scale"] = 0.1 * jax.random.normal(next(keys),
                                                             g.shape)
    p["final_norm"]["scale"] = 0.1 * jax.random.normal(next(keys), (64,))
    for name in ("eva_mu", "eva_phi"):
        p["layers"]["attn"][name] = p["layers"]["attn"][name] * 40.0
    return p


def ref_logits(ref, params, seq, rows=None, **kw):
    rows = list(range(len(seq))) if rows is None else rows
    return np.asarray(ref.logits_rows(params, REF_CONFIG, np.asarray(seq),
                                      rows, jax.devices()[0], all_heads=True,
                                      **kw))


TOKENS = np.random.default_rng(0).integers(0, V, size=104)


# -- the reference itself ---------------------------------------------------

def test_reference_windows_equal_bruteforce_mask(ref, params):
    """Step 3 by windows and query blocks against ONE masked O(T^2) softmax
    over every key and every chunk summary."""
    a = ref_logits(ref, params, TOKENS)
    b = ref_logits(ref, params, TOKENS, attention=ref.attention_bruteforce)
    assert a.shape == (len(TOKENS), 8 * V)
    np.testing.assert_allclose(a, b, atol=ATOL)


def test_summarize_is_step_two(ref, params):
    """The summariser alone against step 2 written out a chunk at a time."""
    rng = np.random.default_rng(3)
    k = rng.normal(size=(4, 24, 16)).astype(np.float32)
    v = rng.normal(size=(4, 24, 16)).astype(np.float32)
    mu = rng.normal(size=(4, 16)).astype(np.float32)
    phi = rng.normal(size=(4, 16)).astype(np.float32)
    ks, vs = eva.summarize(jnp.asarray(k), jnp.asarray(v), jnp.asarray(mu),
                           jnp.asarray(phi), C)
    for h in range(4):
        for c in range(24 // C):
            kk, vv = k[h, C * c:C * c + C], v[h, C * c:C * c + C]
            wk = np.exp(kk @ mu[h]); wk /= wk.sum()
            wv = np.exp(kk @ phi[h]); wv /= wv.sum()
            np.testing.assert_allclose(ks[h, c], wk @ kk, atol=1e-5)
            np.testing.assert_allclose(vs[h, c], wv @ vv, atol=1e-5)
    rk, rv = ref.chunk_summaries(jnp.asarray(k), jnp.asarray(v),
                                 jnp.asarray(mu), jnp.asarray(phi), C)
    np.testing.assert_allclose(ks, rk, atol=1e-5)
    np.testing.assert_allclose(vs, rv, atol=1e-5)


# -- the three forwards -------------------------------------------------------

def test_apply_matches_reference_on_all_heads(ref, model, params):
    """``CausalLM.apply`` (no cache), three windows and a part."""
    got = np.asarray(model.apply(params, jnp.asarray(TOKENS)[None]))[0]
    np.testing.assert_allclose(got, ref_logits(ref, params, TOKENS),
                               atol=ATOL)


def _prefill_paged(model, params, pool, cache, tokens, chunk=16):
    """``tokens`` into slot 0 in chunks through ``forward_with_cache`` on the
    slot's gathered view, as the engine's chunk program does.  Returns the
    chunks' logits [len(tokens), 8 V] and the cache."""
    logits = []
    for off in range(0, len(tokens), chunk):
        part = tokens[off:off + chunk]
        assert pool.ensure(0, off + len(part))
        pt = jnp.asarray(pool.page_table[:1])
        view = {n: jax.vmap(lambda b: paged_logical_view(b, pt))(cache[n])
                for n in ("k", "v")}
        lg, view = forward_with_cache(model, params,
                                      jnp.asarray(part)[None], view, off)
        cache = {n: jax.vmap(lambda b, s: _scatter_view(b, s, pt))(
            cache[n], view[n]) for n in ("k", "v")}
        logits.append(np.asarray(lg[0]))
    return np.concatenate(logits), cache


@pytest.mark.parametrize("path", ["fused", "unfused"])
def test_prefill_chunks_then_decode_across_two_boundaries(ref, model, params,
                                                          path):
    """40 tokens prefilled in chunks of 16 (one window closes in prefill),
    then 64 decoded one at a time through the paged pool, across the
    boundaries at 64 and 96, teacher-forced: every position's ``8 x V``
    logits against the reference's one full forward."""
    cfg = model.config
    pool = PagedKVPool(1, 160, page_tokens=PAGE, window_tokens=W,
                       chunk_tokens=C)
    cache = init_paged_kv_cache(cfg, pool.num_pages, PAGE, dtype=jnp.float32)
    n_prompt = 40
    got, cache = _prefill_paged(model, params, pool, cache, TOKENS[:n_prompt])
    dparams = inject_decode_params(params, cfg)
    # one program for the 64 steps, position and table its operands (as the
    # engine's decode block has them), not 64 op-by-op dispatches
    if path == "fused":
        step = jax.jit(lambda tok, cache, pos, pt: decode_step(
            cfg, dparams, tok, cache, pos, page_table=pt))
    else:
        step = jax.jit(lambda tok, cache, pos, pt: forward_with_cache(
            model, params, tok, cache, pos, page_table=pt))
    rows = [got]
    for p in range(n_prompt, len(TOKENS)):
        assert pool.ensure(0, p + 1)
        pt = jnp.asarray(pool.page_table.copy())
        tok, pos = jnp.asarray(TOKENS[p:p + 1])[None], jnp.asarray([p])
        lg, cache = step(tok, cache, pos, pt)
        rows.append(np.asarray(lg if path == "fused" else lg[:, -1]))
    # 4 window pages, and the summary pages of the three closed windows
    assert pool.slot_pages_used(0) == 4 + -(-3 * (W // C) // PAGE)
    np.testing.assert_allclose(np.concatenate(rows),
                               ref_logits(ref, params, TOKENS), atol=ATOL)


@pytest.mark.parametrize("fault", ["bf16_residual", "no_summaries",
                                   "stale_window"])
def test_the_tolerance_catches(ref, params, fault):
    """Each fault, put into the reference (the difference is symmetric),
    moves some logit past ``ATOL`` by a wide margin."""
    want = ref_logits(ref, params, TOKENS)
    if fault == "bf16_residual":
        got = ref_logits(ref, params, TOKENS, stream_dtype=jnp.bfloat16)
    else:
        def wrong(q, k, v, mu, phi, window, chunk):
            return _attend(ref, q, k, v,
                           *ref.chunk_summaries(k, v, mu, phi, chunk),
                           window, chunk, stale=fault == "stale_window",
                           summaries=fault != "no_summaries")
        got = ref_logits(ref, params, TOKENS, attention=wrong)
    assert np.abs(got - want).max() > 100 * ATOL


def _attend(ref, q, k, v, ks, vs, window, chunk, *, stale, summaries):
    """Step 3 as one masked softmax with given summaries; ``stale`` reads a
    query's exact set one window back (a window page not overwritten), and
    without ``summaries`` the summary set is empty."""
    H, S, d = q.shape
    i = jnp.arange(S)[:, None]
    j = jnp.arange(S)[None, :]
    c = jnp.arange(S // chunk)[None, :]
    w = i // window
    back = jnp.where(stale & (w > 0), window, 0)
    ok = jnp.concatenate([(j >= w * window - back) & (j <= i - back),
                          (c < w * (window // chunk)) & summaries], axis=-1)
    s = jnp.einsum("hqd,hkd->hqk", q, jnp.concatenate([k, ks], 1)) \
        / jnp.sqrt(jnp.float32(d))
    p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,hkd->hqd", p, jnp.concatenate([v, vs], 1))


# -- the Pallas kernels against their XLA forms (interpret mode) --------------

def _pool_arrays(rng, L=2, P=12, H=4, page=128, Dh=32):
    mk = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    return mk(L, P, H, page, Dh), mk(L, P, H, page, Dh)


@pytest.mark.parametrize("pos", [[300, 255, 17], [511, 767, 130]])
def test_eva_decode_kernel_matches_xla(pos):
    """Window 256, chunk 8, page 128: two window pages and one summary page
    a row; rows in windows 0, 1 and 2."""
    from deepspeed_tpu.ops.pallas.decode import eva_decode_paged

    rng = np.random.default_rng(0)
    kc, vc = _pool_arrays(rng, Dh=128)
    q = jnp.asarray(rng.normal(size=(3, 4, 128)), jnp.float32)
    pt = jnp.asarray([[1, 2, 3], [4, 5, 6], [7, 8, 9]], jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    kw = dict(layer=1, window=256, chunk=8)
    np.testing.assert_allclose(
        eva_decode_paged(q, kc, vc, pos, pt, impl="interpret", **kw),
        eva_decode_paged(q, kc, vc, pos, pt, impl="xla", **kw), atol=1e-5)


@pytest.mark.parametrize("layer", ["static", "traced"])
@pytest.mark.parametrize("live,pos", [
    # a row before its first closed window beside one after it, parked rows
    # between them whose stale pos is deeper than either
    ([False, True, False, True], [767, 130, 700, 300]),
    ([True, True, True, False], [17, 255, 511, 767]),      # all but one
    ([False] * 4, [300, 255, 17, 600]),                    # no live row
    ([True] * 4, [300, 255, 17, 600]),                     # every row
    (None, [300, 255, 17, 600]),                           # no mask given
], ids=["interleaved", "one_parked", "none_live", "all_live", "no_mask"])
def test_eva_decode_kernel_visits_live_rows(live, pos, layer):
    """The grid follows ``live``: live rows equal the XLA form whichever
    side of their first window close they stand; a row that does not decode
    is never visited (every page its table names is NaN) and gets its ``q``
    back; no live row at all is a kernel of no steps.  The layer a Python
    int, and a traced scalar that rides with the table."""
    from deepspeed_tpu.ops.pallas.decode import eva_decode_paged

    rng = np.random.default_rng(2)
    kc, vc = _pool_arrays(rng, P=13, Dh=128)
    q = jnp.asarray(rng.normal(size=(4, 4, 128)), jnp.float32)
    pt = jnp.asarray(np.arange(1, 13).reshape(4, 3), jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    rows = np.flatnonzero(np.ones(4) if live is None else live)
    parked = np.setdiff1d(np.arange(4), rows)
    poison = np.asarray(pt)[parked].reshape(-1)
    # the other layer is poison all over: the call reads layer 1
    kx = kc.at[:, poison].set(jnp.nan).at[0].set(jnp.nan)
    vx = vc.at[:, poison].set(jnp.nan).at[0].set(jnp.nan)
    kw = dict(window=256, chunk=8)
    call = lambda at: eva_decode_paged(
        q, kx, vx, pos, pt, impl="interpret", layer=at,
        live=None if live is None else jnp.asarray(live), **kw)
    got = jax.jit(call)(jnp.int32(1)) if layer == "traced" else call(1)
    want = eva_decode_paged(q, kc, vc, pos, pt, impl="xla", layer=1, **kw)
    np.testing.assert_allclose(got[rows], want[rows], atol=1e-5)
    np.testing.assert_array_equal(got[parked], q[parked])


# -- the decode kernel walks a row's window pages, then its summary pages ------
# window 512 over pages of 256 with a summary a 16 positions: two window
# pages and 32 summaries a window, two summary pages a row
WALK = dict(window=512, chunk=16)
WALK_PAGE, WALK_COLS = 256, 4
# where the first row stands (what its two runs of pages then end in)
WALK_POSITIONS = {
    "before_its_first_close": 300,              # no second run of pages
    "first_row_of_a_window": 2 * 512,           # one piece; 64 summaries
    "last_row_of_a_page": 3 * 512 + 255,        # a whole page; 96: 2 pieces
    "first_row_of_a_page": 4 * 512 + 256,       # a page and a piece; half one
    "last_row_of_a_window": 8 * 512 + 511,      # two pages; a whole page
    "summaries_no_multiple_of_a_piece": 512 + 63,       # 32 of a piece's 64
    "a_second_summary_page": 9 * 512 + 64,      # a page and 32 rows
    "position_0": 0,
}


def _poisoned_eva_pool(rng, pos, live, H, dtype=jnp.float32, layer=1):
    """Stacked pools [2, P, H, page, 128], clean and poisoned, and the table
    [B, 2 window + 2 summary pages] of shuffled pages: the poisoned pair is
    NaN wherever no live row's step counts a row: window rows past ``pos %
    W``, summary rows past the closed windows', the pages past either run,
    the pages of rows that do not decode, the junk page 0, the pages no
    table names and the whole of the other layer."""
    B, page, W = len(pos), WALK_PAGE, WALK["window"]
    P = B * WALK_COLS + 3
    mk = lambda: jnp.asarray(rng.normal(size=(2, P, H, page, 128)), dtype)
    k, v = mk(), mk()
    pt = 1 + rng.permutation(B * WALK_COLS).reshape(B, WALK_COLS)
    counts = np.zeros((P, page), bool)
    for b in np.flatnonzero(live):
        runs = ((0, pos[b] % W + 1),
                (W // page, pos[b] // W * (W // WALK["chunk"])))
        for first, n in runs:
            r = np.arange(n)
            counts[pt[b, first + r // page], r % page] = True
    keep = jnp.asarray(counts)[None, :, None, :, None] \
        & (jnp.arange(2) == layer)[:, None, None, None, None]
    nan = jnp.asarray(jnp.nan, dtype)
    return (k, v, jnp.where(keep, k, nan), jnp.where(keep, v, nan),
            jnp.asarray(pt, jnp.int32))


@pytest.mark.parametrize("case", sorted(WALK_POSITIONS))
def test_eva_walk_attends_the_rows_that_count_in_a_poisoned_pool(
        case, chips_interpreter):
    """ISSUE 62: a grid step walks the row's window pages and then its
    summary pages, the last page of either in pieces of 64 rows, under
    jax's TPU interpreter (NaN in VMEM until a copy is waited for, races
    looked for, every semaphore 0 at the end).  Past the rows that count a
    window page holds the window before's keys, a summary page whatever the
    pool holds, a buffer an earlier page: all NaN here, all weigh 0, and
    their values are selected away (``0 x NaN`` is NaN)."""
    from deepspeed_tpu.ops.pallas.decode import eva_decode_paged

    rng = np.random.default_rng(3)
    pos = [WALK_POSITIONS[case], 5 * 512 + 200, 300]
    k, v, kx, vx, pt = _poisoned_eva_pool(rng, pos, [True] * 3, H=4)
    assert np.isnan(np.asarray(kx[1])).mean() > 0.3
    q = jnp.asarray(rng.normal(size=(3, 4, 128)), jnp.float32)
    pos = jnp.asarray(pos, jnp.int32)
    got = eva_decode_paged(q, kx, vx, pos, pt, layer=1, impl="interpret",
                           **WALK)
    want = eva_decode_paged(q, k, v, pos, pt, layer=1, impl="xla", **WALK)
    assert np.all(np.isfinite(np.asarray(got)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    chips_interpreter()


@pytest.mark.parametrize("layer", ["static", "traced"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_eva_walk_over_two_head_groups_and_parked_rows(dtype, layer,
                                                       chips_interpreter):
    """Two head groups a row (32 bf16 / 16 float32 heads over pages of 256:
    16 / 8 a grid step), so a row's walk hands its first page to its own
    second group and that one to the next live row; a parked row between
    them, its pages NaN, is never visited."""
    from deepspeed_tpu.ops.pallas.decode import (_kv_heads_per_step,
                                                 eva_decode_paged)

    H = 32 if dtype == jnp.bfloat16 else 16
    assert H == 2 * _kv_heads_per_step(H, WALK_PAGE, 128,
                                       jnp.dtype(dtype).itemsize)
    rng = np.random.default_rng(4)
    pos, live = [4 * 512 + 300, 8 * 512 + 5, 40, 512 + 255], \
        [True, False, True, True]
    k, v, kx, vx, pt = _poisoned_eva_pool(rng, pos, live, H=H, dtype=dtype)
    q = jnp.asarray(rng.normal(size=(4, H, 128)), dtype)
    pos = jnp.asarray(pos, jnp.int32)
    call = lambda at: eva_decode_paged(
        q, kx, vx, pos, pt, layer=at, live=jnp.asarray(live),
        impl="interpret", **WALK)
    got = jax.jit(call)(jnp.int32(1)) if layer == "traced" else call(1)
    got = np.asarray(got, np.float32)
    want = np.asarray(eva_decode_paged(q, k, v, pos, pt, layer=1, impl="xla",
                                       **WALK), np.float32)
    rows = np.flatnonzero(live)
    tol = 2e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got[rows], want[rows], rtol=tol, atol=tol)
    np.testing.assert_array_equal(got[1], np.asarray(q[1], np.float32))
    chips_interpreter()


def test_eva_decode_under_the_lane_tile_takes_the_xla_form():
    """A head dim that does not fill the 128 lanes: the kernel copies its
    pages itself and can slice no padded pool, ``eva_reference_reason``
    says so, and the call is the jnp form (no second EVA kernel stays for
    it); the pooling kernel, which copies nothing itself, takes it."""
    from deepspeed_tpu.ops.pallas.decode import (eva_decode_paged,
                                                 eva_reference_reason)

    assert eva_reference_reason(256, 2048, 16, 128) is None
    assert eva_reference_reason(256, 2048, 16) is None
    assert "128 lanes" in eva_reference_reason(256, 2048, 16, 64)
    rng = np.random.default_rng(0)
    kc, vc = _pool_arrays(rng, Dh=64)
    q = jnp.asarray(rng.normal(size=(3, 4, 64)), jnp.float32)
    pt = jnp.asarray([[1, 2, 3], [4, 5, 6], [7, 8, 9]], jnp.int32)
    pos = jnp.asarray([300, 255, 17], jnp.int32)
    call = lambda impl: eva_decode_paged(q, kc, vc, pos, pt, impl=impl,
                                         layer=1, window=256, chunk=8)
    assert "pallas_call" not in str(jax.make_jaxpr(
        lambda: call("interpret"))())
    np.testing.assert_array_equal(call("interpret"), call("xla"))


@pytest.mark.parametrize("pos", [[300, 255, 17], [511, 767, 130],
                                 [40, 600, 13]])
def test_eva_summarize_kernel_matches_xla_and_touches_only_closers(pos):
    from deepspeed_tpu.ops.pallas.decode import eva_summarize_paged

    rng = np.random.default_rng(1)
    kc, vc = _pool_arrays(rng)
    mu = jnp.asarray(rng.normal(size=(4, 32)), jnp.float32)
    phi = jnp.asarray(rng.normal(size=(4, 32)), jnp.float32)
    pt = jnp.asarray([[1, 2, 3], [4, 5, 6], [7, 8, 9]], jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    kw = dict(layer=1, window=256, chunk=8)
    ka, va = eva_summarize_paged(kc, vc, mu, phi, pos, pt, impl="xla", **kw)
    kb, vb = eva_summarize_paged(kc, vc, mu, phi, pos, pt, impl="interpret",
                                 **kw)
    np.testing.assert_allclose(ka, kb, atol=1e-5)
    np.testing.assert_allclose(va, vb, atol=1e-5)
    closing = (np.asarray(pos) + 1) % 256 == 0
    changed = np.abs(np.asarray(kb) - np.asarray(kc)).max(axis=(0, 2, 3, 4))
    # only the summary pages of the rows that closed a window were written
    assert set(np.flatnonzero(changed)) == \
        {int(pt[b, 2]) for b in np.flatnonzero(closing)}


# -- the prefill chunk's attention: the flash kernel against eva.cached_attention

def _chunk_inputs(s, window, chunk, windows, heads=2, batch=1, Dh=128,
                  dtype=jnp.float32, seed=3):
    """q of one chunk and the slot's logical views, sized for ``windows``
    windows' summaries."""
    rng = np.random.default_rng(seed)
    rows = window + windows * (window // chunk)
    mk = lambda n: jnp.asarray(rng.normal(size=(batch, heads, n, Dh)), dtype)
    return mk(s), mk(rows), mk(rows)


def _chunk_both(q, k, v, start, window, chunk):
    from deepspeed_tpu.ops.pallas.flash_attention import eva_chunk_attention

    got = jax.jit(lambda q, k, v, at: eva_chunk_attention(
        q, k, v, at, window=window, chunk=chunk, impl="interpret"))(
            q, k, v, jnp.int32(start))
    want = eva.cached_attention(q, k, v, start + jnp.arange(q.shape[2]),
                                window=window, chunk=chunk,
                                scale=q.shape[-1] ** -0.5)
    return got, want


# (start, s, window, eva chunk, windows the view holds summaries of): a case
# a branch of the schedule
CHUNK_CASES = {
    # the first window: the diagonal tile alone, no summary row
    "offset_0": (0, 128, 256, 16, 8),
    # strips wholly under the chunk's first query, then the diagonal tile
    "half_window": (128, 128, 256, 16, 8),
    # 16 and 48 summary rows: one partial summary strip, masked by row
    "window_1": (256, 128, 256, 16, 8),
    "window_3_half": (3 * 256 + 128, 128, 256, 16, 8),
    # 160 summary rows in strips of 128: one whole strip and a partial one
    "window_5_whole_summary_strip": (5 * 256, 128, 256, 8, 12),
    # 256 of them: two whole strips and no partial one
    "window_8_no_partial_strip": (8 * 256 + 128, 128, 256, 8, 12),
    # the chunk bucket up to half a window, at both window offsets
    "bucket_256_offset_0": (2 * 512, 256, 512, 16, 4),
    "bucket_256_half_window": (2 * 512 + 256, 256, 512, 16, 4),
    # a first query that is not a strip's first row (no engine chunk starts
    # so; forward_with_cache's other callers may): every strip from the
    # first one the diagonal touches is masked whole
    "unaligned_start": (256 + 64, 128, 256, 16, 8),
}


@pytest.mark.parametrize("case", CHUNK_CASES)
def test_eva_chunk_kernel_matches_xla(case):
    start, s, window, chunk, windows = CHUNK_CASES[case]
    got, want = _chunk_both(*_chunk_inputs(s, window, chunk, windows), start,
                            window, chunk)
    np.testing.assert_allclose(got, want, atol=ATOL)


@pytest.mark.parametrize("budget,heads_a_step", [(None, 4), ("one_head", 1)])
def test_eva_chunk_kernel_heads_a_grid_step(monkeypatch, budget,
                                            heads_a_step):
    """Two rows of two heads: all four in one grid step where they fit the
    VMEM budget, one a step where the budget holds one head's blocks."""
    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    q, k, v = _chunk_inputs(128, 256, 16, 8, batch=2)
    plan = fa._chunk_plan(128, k.shape[2], 256, 128, 4)
    if budget:
        monkeypatch.setattr(fa, "_VMEM_BLOCK_BYTES", plan.per_head)
    assert fa._heads(4, plan.per_head) == heads_a_step
    got, want = _chunk_both(q, k, v, 256 + 128, 256, 16)
    np.testing.assert_allclose(got, want, atol=ATOL)


def test_eva_chunk_kernel_two_query_tiles(monkeypatch):
    """A chunk longer than the Q tile: the second tile's first query sits a
    tile further into the window."""
    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    monkeypatch.setattr(fa, "DEFAULT_BLOCK_Q", 128)
    got, want = _chunk_both(*_chunk_inputs(256, 512, 16, 4), 512 + 256, 512,
                            16)
    np.testing.assert_allclose(got, want, atol=ATOL)


def test_eva_chunk_kernel_bf16_operands():
    """The chip's arithmetic: bf16 operands on both matmuls (the
    probabilities rounded to bf16 ahead of ``PV``), float32 sums."""
    got, want = _chunk_both(
        *_chunk_inputs(128, 256, 16, 8, dtype=jnp.bfloat16), 3 * 256 + 128,
        256, 16)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(np.float32),
                               want.astype(np.float32), atol=2e-2)


def test_eva_chunk_kernel_padded_last_chunk():
    """A bucket of 128 holding 37 real tokens: whatever the pad rows hold
    (their queries, and the K and V rows the chunk wrote for them) stays out
    of the real queries' outputs, and every output is finite."""
    start, real, window = 256 + 128, 37, 256
    q, k, v = _chunk_inputs(128, window, 16, 8)
    w0 = start % window
    outs = []
    for junk in (1e3, -7.0):
        pad = lambda t, lo: t.at[:, :, lo + real:lo + 128].set(junk)
        got, want = _chunk_both(pad(q, 0), pad(k, w0), pad(v, w0), start,
                                window, 16)
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(got[:, :, :real], want[:, :, :real],
                                   atol=ATOL)
        outs.append(np.asarray(got[:, :, :real]))
    np.testing.assert_array_equal(*outs)


# (s, window, eva chunk, windows, Dh, what the reason names)
CHUNK_REFUSALS = {
    "chunk_under_the_tile": (64, 256, 16, 8, 128, "chunk of 64"),
    "window_off_the_tile": (128, 192, 16, 8, 128, "window of 192"),
    "summary_rows_off_the_tile": (128, 256, 16, 4, 128,
                                  "summary rows of 64"),
    "head_dim_off_the_tile": (128, 256, 16, 8, 64, "head dim of 64"),
    "view_over_the_vmem_budget": (128, 4096, 16, 8, 128, "VMEM"),
}


@pytest.mark.parametrize("case", CHUNK_REFUSALS)
def test_eva_chunk_refusals_run_the_xla_form(case):
    from deepspeed_tpu.ops.pallas.flash_attention import (
        eva_chunk_reference_reason, eva_chunk_schedule)

    s, window, chunk, windows, Dh, names = CHUNK_REFUSALS[case]
    q, k, v = _chunk_inputs(s, window, chunk, windows, Dh=Dh)
    reason = eva_chunk_reference_reason(s, window, k.shape[2], Dh,
                                        itemsize=4)
    assert names in reason
    sch = eva_chunk_schedule(window, s, window=window, chunk=chunk,
                             rows=k.shape[2], head_dim=Dh, itemsize=4,
                             impl="interpret")
    assert sch["reason"] == reason and sch["visited"] == s * k.shape[2]
    got, want = _chunk_both(q, k, v, window, window, chunk)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("case,visited", [
    ("offset_0", 128 * 128),                  # the diagonal strip
    ("half_window", 2 * 128 * 128),           # + one strip under it
    ("window_1", (1 + 1) * 128 * 128),        # diagonal + a summary strip
    ("window_3_half", (2 + 1) * 128 * 128),
    ("window_5_whole_summary_strip", (1 + 2) * 128 * 128),
    ("window_8_no_partial_strip", (2 + 2) * 128 * 128),
    # strips of 256 rows: the diagonal tile alone is one strip
    ("bucket_256_offset_0", 256 * 256 + 128 * 256),
    ("bucket_256_half_window", 2 * 256 * 256 + 128 * 256),
    ("unaligned_start", (2 + 1) * 128 * 128),  # two strips masked whole
])
def test_eva_chunk_schedule_counts_against_the_mask(case, visited):
    """``kept`` is the mask of ``eva.cached_attention`` summed (over the
    real queries where the bucket is padded); ``visited`` the strips the
    kernel's loops walk, counted by hand."""
    from deepspeed_tpu.ops.pallas.flash_attention import eva_chunk_schedule

    start, s, window, chunk, windows = CHUNK_CASES[case]
    rows = window + windows * (window // chunk)
    pos = start + np.arange(s)[:, None]
    r = np.arange(rows)[None]
    ok = np.where(r < window, r < pos % window + 1,
                  r - window < (pos // window) * (window // chunk))
    kw = dict(window=window, chunk=chunk, rows=rows)
    for real in (s, 37):
        sch = eva_chunk_schedule(start, s, real=real, impl="interpret", **kw)
        assert sch["reason"] is None
        assert sch["kept"] == ok[:real].sum()
        assert sch["visited"] == visited and sch["dense"] == s * rows
        assert sch["kept"] <= sch["visited"] <= sch["dense"]
    # where the dense form runs it computes the whole bucket x view
    assert eva_chunk_schedule(start, s, impl="xla", **kw)["visited"] == \
        s * rows


# -- the pool: two page kinds, one free list ---------------------------------

def test_pages_needed_follow_the_position_not_the_length():
    pool = PagedKVPool(2, 160, page_tokens=PAGE, window_tokens=W,
                       chunk_tokens=C)
    assert (pool.window_pages, pool.summary_pages) == (4, 5)
    assert pool.slot_pages == 9 and pool.cache_len == 160
    per_window = W // C                                   # 8 summary rows
    for tokens, want in [(1, 1), (8, 1), (9, 2), (31, 4), (32, 4 + 1),
                         (33, 5), (63, 5), (64, 4 + 2), (159, 4 + 4),
                         (160, 4 + 5)]:
        assert pool.pages_for(tokens) == want, tokens
        assert pool.pages_for(tokens) == -(-min(tokens, W) // PAGE) + \
            -(-(tokens // W) * per_window // PAGE)
    assert pool.ensure(0, 70) and pool.ensure(1, 20)
    assert pool.pages_used_by_kind() == {"window": 4 + 3, "summary": 2}
    assert pool.release(0) == 6 and pool.release(1) == 3
    pool.check_no_leak()
    assert pool.pages_free == pool.num_pages - 1


def _serve(model, params, **over):
    return deepspeed_tpu.init_serving(model, config={**ENGINE, **over},
                                      params=params)


@pytest.fixture(scope="module")
def engine(model, params):
    """One engine at ``ENGINE`` for the cases that differ in their requests
    alone."""
    serve = _serve(model, params)
    yield serve
    serve.close()


def _prompts():
    rng = np.random.default_rng(5)
    return ([rng.integers(0, V, size=n) for n in (45, 70, 20, 33)],
            (70, 40, 30, 64))


def _served_tokens_are_the_references_best(ref, params, prompt, out):
    seq = np.concatenate([prompt, out])
    rows = list(range(len(prompt) - 1, len(seq) - 1))
    lg = ref_logits(ref, params, seq, rows)[:, :V]
    assert (lg.max(-1) - lg[np.arange(len(out)), out]).max() <= ATOL


@pytest.mark.parametrize("fused", [True, False])
def test_engine_serves_across_windows_and_returns_both_page_kinds(
        ref, model, params, engine, fused):
    """Four requests on three slots through ``init_serving`` and ``step()``:
    chunked prefill, continuous batching, windows closing in prefill and in
    decode; every served token is the reference's best of head 0, and the
    pool is whole afterwards."""
    assert engine.engine._dparams is not None  # ENGINE's own is fused
    serve = engine if fused else _serve(model, params, use_fused_decode=False)
    assert serve.prefix_cache is None          # switched off, not refused
    prompts, news = _prompts()
    with as_found(serve):
        reqs = [serve.submit(p, max_new_tokens=n, stream=True)
                for p, n in zip(prompts, news)]
        serve.run()
    assert serve.pool.pages_used == 0
    for p, r, n in zip(prompts, reqs, news):
        assert len(r.output_tokens) == n and r.finish_reason == "length"
        _served_tokens_are_the_references_best(
            ref, params, p, np.asarray(r.output_tokens))
    if not fused:
        serve.close()


@pytest.mark.parametrize("places", [1, 2, 4])
def test_chunks_of_one_iteration_close_windows_for_each_other(ref, model,
                                                              params, places):
    """``max_prefill_chunks`` places an iteration: a prompt that prefills
    alone takes them all, so a chunk closes a window (16-token chunks, one of
    32) and the next chunk PROGRAM reads the summaries it wrote with no
    decode block between, twice over under four places; then three requests
    share the places.  Every served token is the reference's best."""
    from deepspeed_tpu.monitor.metrics import get_registry

    reg = get_registry()
    reg.enable()
    reg.reset()
    serve = _serve(model, params, max_prefill_chunks=places)
    prompts, news = _prompts()
    order = [1, 0, 2, 3]                   # the 70-token prompt goes alone
    reqs = [serve.submit(prompts[1], max_new_tokens=news[1], stream=True)]
    serve.run()
    chunks = -(-len(prompts[1]) // ENGINE["prefill_chunk"])
    assert reg.get("ds_serve_prefill_chunks_extra_total").value == \
        chunks - -(-chunks // places)
    reqs += [serve.submit(prompts[i], max_new_tokens=news[i], stream=True)
             for i in order[1:]]
    serve.run()
    serve.pool.check_no_leak()
    for i, r in zip(order, reqs):
        assert len(r.output_tokens) == news[i]
        _served_tokens_are_the_references_best(
            ref, params, prompts[i], np.asarray(r.output_tokens))
    serve.close()
    reg.disable()


def test_preempted_request_resumes_to_the_same_tokens(model, params, engine):
    """A pool too small for three long requests: the youngest is preempted,
    gives back every page of both kinds, resumes by recompute and yields the
    tokens an unpressed engine yields."""
    prompts, news = _prompts()
    with as_found(engine) as calm:
        want = [calm.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, news)]
        calm.run()
    # 9 pages a slot at most; 14 usable pages for three slots
    tight = _serve(model, params, kv_pool_tokens=14 * PAGE)
    reqs = [tight.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
    tight.run()
    assert sum(r.preemptions for r in reqs) > 0
    tight.pool.check_no_leak()
    assert tight.pool.pages_used == 0
    for a, b in zip(want, reqs):
        assert a.output_tokens == b.output_tokens
    tight.close()


def test_eva_counters_count_attended_rows_and_closes(model, params):
    from deepspeed_tpu.monitor.metrics import MetricsRegistry

    reg = MetricsRegistry().enable()
    serve = deepspeed_tpu.init_serving(model, config=dict(ENGINE),
                                       params=params, registry=reg)
    prompt = np.arange(30) % V
    serve.submit(prompt, max_new_tokens=40)
    serve.run()
    snap = reg.snapshot()
    # decode steps sit at positions 30 .. 68 (39 of them: the first token
    # comes from the prefill chunk); windows close at 31 and 63, in decode
    pos = np.arange(30, 69)
    assert snap["ds_serve_eva_window_rows_total"] == int((pos % W + 1).sum())
    assert snap["ds_serve_eva_summary_rows_total"] == \
        int((pos // W).sum()) * (W // C)
    assert snap["ds_serve_eva_window_closes_total"] == 2
    serve.close()


def _kept_by_the_mask(chunks, window, per):
    """Scores ``eva.cached_attention``'s mask keeps for the real tokens of
    ``chunks`` [(offset, real tokens)], counted position by position."""
    return sum(p % window + 1 + (p // window) * per
               for off, c in chunks for p in range(off, off + c))


def test_eva_prefill_score_counters_against_a_brute_force_count(model,
                                                                params):
    """The tiny model's sizes are under the kernel's tiles, so the dense
    form runs and computes every bucket x the whole view."""
    from deepspeed_tpu.monitor.metrics import MetricsRegistry

    reg = MetricsRegistry().enable()
    serve = deepspeed_tpu.init_serving(model, config=dict(ENGINE),
                                       params=params, registry=reg)
    serve.submit(np.arange(45) % V, max_new_tokens=2)
    serve.run()
    snap = reg.snapshot()
    # chunks of 16, 16 and 13 tokens (the last in a bucket of 16)
    assert snap["ds_serve_eva_prefill_scores_total"] == _kept_by_the_mask(
        [(0, 16), (16, 16), (32, 13)], W, W // C)
    rows = serve.pool.slot_pages * PAGE
    assert rows == W + 5 * (W // C)          # five windows in 160 tokens
    assert snap["ds_serve_eva_prefill_scores_visited_total"] == 3 * 16 * rows
    serve.close()


def test_engine_serves_the_same_tokens_through_the_chunk_kernel(monkeypatch):
    """A model at the kernel's smallest tiles (window 256, chunk 16, head
    dim 128, prefill chunks of 128; a view of 2 window pages and one summary
    page) served with every kernel in interpret mode and with the XLA forms:
    the same tokens.  The prompts' chunks sit at both window offsets of
    windows 0 and 1; one last chunk is a padded bucket of 128 (the kernel),
    the other a bucket of 64 (under the tile: the dense form)."""
    from deepspeed_tpu.monitor.metrics import MetricsRegistry
    from deepspeed_tpu.ops.pallas import common

    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    model = CausalLM(ModelConfig(**dict(
        TINY, hidden_size=256, num_heads=2, head_dim=128, num_layers=1,
        max_seq_len=2048, eva_window=256, eva_chunk=16)))
    params = model.init(jax.random.PRNGKey(2))
    for name in ("eva_mu", "eva_phi"):
        params["layers"]["attn"][name] = params["layers"]["attn"][name] * 40.0
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, V, size=n) for n in (484, 300)]

    def served(registry):
        serve = deepspeed_tpu.init_serving(
            model, config=dict(ENGINE, num_slots=2, prefill_chunk=128,
                               kv_page_tokens=128, max_out_tokens=2048),
            params=params, registry=registry)
        reqs = [serve.submit(p, max_new_tokens=6) for p in prompts]
        serve.run()
        serve.close()
        return [list(r.output_tokens) for r in reqs]

    want = served(MetricsRegistry())
    buckets = []
    kernel = fa._eva_chunk_kernel

    def spy(*refs, p, **kw):
        buckets.append(p.bq)
        return kernel(*refs, p=p, **kw)

    monkeypatch.setattr(common, "default_impl", lambda: "interpret")
    monkeypatch.setattr(fa, "_eva_chunk_kernel", spy)
    reg = MetricsRegistry().enable()
    assert served(reg) == want
    assert set(buckets) == {128}     # the bucket of 64 runs the XLA form
    # 484 = 3 x 128 + 100 in a bucket of 128; 300 = 2 x 128 + 44 in one of 64
    chunks = [(0, 128), (128, 128), (256, 128), (384, 100),
              (0, 128), (128, 128), (256, 44)]
    snap = reg.snapshot()
    assert snap["ds_serve_eva_prefill_scores_total"] == _kept_by_the_mask(
        chunks, 256, 16)
    strip = 128 * 128    # the diagonal alone; + one under it; + summaries
    assert snap["ds_serve_eva_prefill_scores_visited_total"] == (
        (1 + 2 + 2 + 3) * strip + (1 + 2) * strip + 64 * 384)


# -- what it refuses ----------------------------------------------------------

def test_config_checks():
    with pytest.raises(ValueError, match="multiple of"):
        ModelConfig(**{**TINY, "eva_chunk": 5})
    with pytest.raises(ValueError, match="attention must be"):
        ModelConfig(**{**TINY, "attention": "linear"})


@pytest.mark.parametrize("over,match", [
    (dict(role="prefill"), "handoff.py"),
    (dict(role="decode"), "handoff.py"),
    (dict(kv_host_tier_pages=4), "host_tier.py"),
    (dict(quantize_kv_cache=True), "decoding.py"),
    (dict(prefill_chunk=24), "prefill_chunk"),
    (dict(prefill_chunk=64), "prefill_chunk"),
])
def test_init_serving_refuses(model, params, over, match):
    kw = {k: over.pop(k) for k in ("role",) if k in over}
    with pytest.raises((NotImplementedError, ValueError), match=match):
        deepspeed_tpu.init_serving(model, config={**ENGINE, **over},
                                   params=params, **kw)


def test_static_batch_generate_refused(model, params):
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.inference.engine import InferenceEngine

    engine = InferenceEngine(model, DeepSpeedInferenceConfig(dtype="float32"),
                             params=params)
    with pytest.raises(NotImplementedError, match="init_serving"):
        engine.generate(np.arange(8)[None], max_new_tokens=4)


def test_tp_refused_and_training_loss_refused(params):
    from deepspeed_tpu.comm.mesh import build_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    mesh = build_mesh(tp=2, devices=jax.devices()[:2])
    tp_model = CausalLM(ModelConfig(**TINY), mesh)
    with pytest.raises(NotImplementedError, match="tp > 1"):
        tp_model.apply(params, jnp.asarray(TOKENS[:32])[None])
    with pytest.raises(NotImplementedError, match="num_pred_heads"):
        CausalLM(ModelConfig(**TINY)).apply(
            params, jnp.asarray(TOKENS[:32])[None],
            labels=jnp.asarray(TOKENS[:32])[None])
