"""Fused decode kernel parity (VERDICT r4 item 1: the Pallas decode path).

Each kernel is checked in interpret mode against its jnp reference on the
8-device CPU backend, over the feature matrix the model zoo exercises
(layernorm/rmsnorm, bias/no-bias, GLU/plain MLP, GQA, parallel residual,
position edge cases for the length-aware attention)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas.decode import (
    _flash_decode_ref, _mlp_ref, _norm_qkv_ref, _proj_norm_ref,
    flash_decode, fused_mlp, fused_norm_qkv, fused_proj_norm,
    paged_kv_append)


def _rand(key, *shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype) * 0.5


@pytest.mark.parametrize("kind", ["layernorm", "rmsnorm"])
@pytest.mark.parametrize("with_bias", [True, False])
def test_norm_qkv_parity(kind, with_bias):
    B, D, N = 2, 256, 768
    x = _rand(0, B, D)
    scale = 1.0 + 0.1 * _rand(1, D)
    bias = _rand(2, D)
    w = _rand(3, D, N)
    bq = _rand(4, N) if with_bias else None
    got = fused_norm_qkv(x, scale, bias, w, bq, kind=kind, eps=1e-5,
                         impl="interpret")
    want = _norm_qkv_ref(x, scale, bias, w, bq, kind=kind, eps=1e-5)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_norm_qkv_blocked_grid():
    """N large enough to split into several column blocks."""
    B, D, N = 1, 2048, 6144
    x = _rand(0, B, D, dtype=jnp.bfloat16)
    scale = jnp.ones((D,), jnp.bfloat16)
    bias = jnp.zeros((D,), jnp.bfloat16)
    w = _rand(1, D, N, dtype=jnp.bfloat16)
    got = fused_norm_qkv(x, scale, bias, w, None, kind="rmsnorm",
                         impl="interpret")
    want = _norm_qkv_ref(x, scale, bias, w, None, kind="rmsnorm", eps=1e-5)
    np.testing.assert_allclose(np.float32(got), np.float32(want),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("pos", [0, 5, 255, 256, 300, 767])
@pytest.mark.parametrize("rep", [1, 4])
def test_flash_decode_positions(pos, rep):
    """Length-aware masking at block boundaries, GQA included."""
    B, Hkv, Smax, Dh = 2, 3, 768, 64
    H = Hkv * rep
    q = _rand(0, B, H, Dh)
    k = _rand(1, B, Hkv, Smax, Dh)
    v = _rand(2, B, Hkv, Smax, Dh)
    got = flash_decode(q, k, v, pos, impl="interpret")
    want = _flash_decode_ref(q, k, v, jnp.int32(pos), scale=Dh ** -0.5)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def _paged_from_logical(k, v, maxp, page, seed=7):
    """Scatter a logical [B, Hkv, maxp*page, Dh] cache into a paged pool
    [P, Hkv, page, Dh] under a SHUFFLED page assignment (page 0 = junk)."""
    B, Hkv, Smax, Dh = k.shape
    assert Smax == maxp * page
    P = B * maxp + 1
    order = np.random.RandomState(seed).permutation(B * maxp) + 1
    pt = order.reshape(B, maxp).astype(np.int32)
    kp = np.zeros((P, Hkv, page, Dh), np.float32)
    vp = np.zeros((P, Hkv, page, Dh), np.float32)
    for b in range(B):
        for j in range(maxp):
            kp[pt[b, j]] = np.asarray(k[b, :, j * page:(j + 1) * page])
            vp[pt[b, j]] = np.asarray(v[b, :, j * page:(j + 1) * page])
    return jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(pt)


# (Hkv, rep, Dh) of the serving configurations: the benchmark's GQA cell,
# gpt2-xl (25 heads: five per grid step by the VMEM rule) and gpt2-small
# and Ouro's / OLMoE's MHA 16 x 128 (float32 here: eight KV heads a grid step,
# so a head-group axis of two).  Head dim 128 walks a row's pages inside a
# grid step, head dim 64 keeps a page a grid step (``decode.walks_pages``)
PAGED_WIDTHS = [(8, 4, 128), (25, 1, 64), (12, 1, 64), (16, 1, 128)]


@pytest.mark.parametrize("Dh", [64, 128])
@pytest.mark.parametrize("pos", [[5, 300], [255, 256], [767, 0]])
@pytest.mark.parametrize("alibi", [False, True])
def test_flash_decode_paged_matches_logical(pos, alibi, Dh):
    """The page-table-indirected index map (head dim 64) and the pages a
    grid step walks through the table itself (128) must reproduce the
    contiguous kernel exactly: a shuffled physical page assignment with
    per-row positions against the dense reference over the logical view."""
    B, Hkv, rep, page, maxp = 2, 2, 2, 256, 3
    H = Hkv * rep
    q = _rand(0, B, H, Dh)
    k = _rand(1, B, Hkv, maxp * page, Dh)
    v = _rand(2, B, Hkv, maxp * page, Dh)
    kp, vp, pt = _paged_from_logical(k, v, maxp, page)
    posv = jnp.asarray(pos, jnp.int32)
    got = flash_decode(q, kp, vp, posv, page_table=pt, alibi=alibi,
                       impl="interpret")
    want = _flash_decode_ref(q, k, v, posv, scale=Dh ** -0.5, alibi=alibi)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def _park(pt, row):
    """Row ``row`` as the serving engine parks a slot: every logical page
    on junk page 0 (the caller gives it ``pos`` 0)."""
    return pt.at[row].set(0)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("alibi", [False, True])
@pytest.mark.parametrize("Hkv,rep,Dh", PAGED_WIDTHS)
def test_flash_decode_paged_mixed_batch(Hkv, rep, Dh, alibi, dtype):
    """One batch as a serving step sees it, at the widths that are served:
    a parked row on junk page 0, a row at ``pos`` 0, the two sides of a
    page boundary and a row in the last page, under a shuffled
    (non-monotone) page table.  In bf16 the kernel multiplies q and K as
    stored; the reference multiplies their float32 copies: the same
    products, summed in another order."""
    page, maxp = 256, 3
    pos = [0, 0, 255, 256, 700]
    B, H = len(pos), Hkv * rep
    q = _rand(0, B, H, Dh, dtype=dtype)
    k = _rand(1, B, Hkv, maxp * page, Dh, dtype=dtype)
    v = _rand(2, B, Hkv, maxp * page, Dh, dtype=dtype)
    kp, vp, pt = _paged_from_logical(k.astype(jnp.float32),
                                     v.astype(jnp.float32), maxp, page)
    kp, vp = kp.astype(dtype), vp.astype(dtype)
    pt = _park(pt, 0)
    assert np.any(np.diff(np.asarray(pt[1:]), axis=1) < 0)   # shuffled
    posv = jnp.asarray(pos, jnp.int32)
    got = flash_decode(q, kp, vp, posv, page_table=pt, alibi=alibi,
                       impl="interpret")
    want = _flash_decode_ref(q, k, v, posv, scale=Dh ** -0.5, alibi=alibi)
    tol = 2e-4 if dtype == jnp.float32 else 2e-2
    # row 0 is parked: it attends the junk page and nobody reads it
    np.testing.assert_allclose(np.float32(got[1:]), np.float32(want[1:]),
                               rtol=tol, atol=tol)
    assert np.all(np.isfinite(np.float32(got[0])))


def _stacked_pools(B, Hkv, Dh, page, maxp, L, seed=3):
    ks, vs, pools = [], [], []
    for l in range(L):
        k = _rand(10 + l, B, Hkv, maxp * page, Dh)
        v = _rand(20 + l, B, Hkv, maxp * page, Dh)
        kp, vp, pt = _paged_from_logical(k, v, maxp, page, seed=seed)
        ks.append(k); vs.append(v); pools.append((kp, vp))
    return (ks, vs, jnp.stack([p[0] for p in pools]),
            jnp.stack([p[1] for p in pools]), pt)


@pytest.mark.parametrize("Dh", [64, 128])
def test_flash_decode_paged_layer_stacked(Dh):
    """decode_step reads the stacked [L, P, Hkv, page, Dh] pool at a
    static layer offset through the index map — no slice materializes."""
    B, Hkv, page, maxp, L = 2, 2, 256, 2, 2
    ks, vs, kp_all, vp_all, pt = _stacked_pools(B, Hkv, Dh, page, maxp, L)
    q = _rand(0, B, Hkv, Dh)
    posv = jnp.asarray([300, 511], jnp.int32)
    for l in range(L):
        got = flash_decode(q, kp_all, vp_all, posv, layer=l, page_table=pt,
                           impl="interpret")
        want = _flash_decode_ref(q, ks[l], vs[l], posv, scale=Dh ** -0.5)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("alibi", [False, True])
@pytest.mark.parametrize("Hkv,rep,Dh", PAGED_WIDTHS)
def test_flash_decode_paged_layer_stacked_widths(Hkv, rep, Dh, alibi):
    """The stacked pool at the served widths: the layer offset counts
    whole pages of ALL KV heads, whatever share of them a grid step takes,
    and a parked row rides along."""
    B, page, maxp, L = 3, 256, 2, 2
    ks, vs, kp_all, vp_all, pt = _stacked_pools(B, Hkv, Dh, page, maxp, L)
    pt = _park(pt, 1)
    q = _rand(0, B, Hkv * rep, Dh)
    posv = jnp.asarray([300, 0, 511], jnp.int32)
    live = np.asarray([0, 2])
    for l in range(L):
        got = flash_decode(q, kp_all, vp_all, posv, layer=l, page_table=pt,
                           alibi=alibi, impl="interpret")
        want = _flash_decode_ref(q, ks[l], vs[l], posv, scale=Dh ** -0.5,
                                 alibi=alibi)
        np.testing.assert_allclose(got[live], want[live], rtol=2e-4,
                                   atol=2e-4)


@pytest.mark.parametrize("Hkv,page,Dh,want", [
    (8, 256, 128, 8), (25, 256, 64, 5), (12, 256, 64, 12), (25, 128, 64, 25),
    (8, 1024, 128, 4), (7, 2048, 128, 1)])
def test_kv_heads_per_step_follows_the_vmem_budget(Hkv, page, Dh, want):
    """``hb`` comes from the shapes: the largest divisor of Hkv whose four
    K/V buffers (Dh padded to 128 lanes, bf16) fit the stated budget."""
    from deepspeed_tpu.ops.pallas.decode import (_DECODE_KV_VMEM_BYTES,
                                                 _kv_heads_per_step)

    hb = _kv_heads_per_step(Hkv, page, Dh, 2)
    assert hb == want and Hkv % hb == 0
    assert hb == 1 or hb * 4 * page * 128 * 2 <= _DECODE_KV_VMEM_BYTES


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_paged_kv_append_matches_scatter(dtype):
    """The in-place append kernel writes exactly what the XLA scatter
    writes: one row per (slot, head) at its page and depth, nothing else —
    rows at the start, middle and end of a page and of a tile group, and
    two parked slots that share the junk page 0."""
    L, P, Hkv, page, Dh, B = 3, 5, 2, 128, 64, 4
    kc = _rand(0, L, P, Hkv, page, Dh, dtype=dtype)
    vc = _rand(1, L, P, Hkv, page, Dh, dtype=dtype)
    k = _rand(2, B, Hkv, Dh, dtype=dtype)
    v = _rand(3, B, Hkv, Dh, dtype=dtype)
    pt = jnp.asarray([[3, 1], [0, 0], [2, 4], [0, 0]], jnp.int32)
    pos = jnp.asarray([0, 17, 128 + 127, 63], jnp.int32)
    want = paged_kv_append(kc, vc, k, v, pos, pt, layer=1, impl="xla")
    got = paged_kv_append(kc, vc, k, v, pos, pt, layer=1, impl="interpret")
    for g, w in zip(got, want):
        # page 0 is the junk page: two parked slots wrote to it, and which
        # of them wins is nobody's business
        np.testing.assert_array_equal(np.asarray(g[:, 1:], np.float32),
                                      np.asarray(w[:, 1:], np.float32))
    assert np.array_equal(np.asarray(got[0][1, 3, :, 0], np.float32),
                          np.asarray(k[0], np.float32))
    assert np.array_equal(np.asarray(got[1][1, 4, :, 127], np.float32),
                          np.asarray(v[2], np.float32))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("Hkv,Dh", [(8, 128), (25, 64)])
def test_paged_kv_append_touches_only_its_rows(Hkv, Dh, dtype):
    """At the served KV widths, rows whose ``pos % page`` falls in
    different 16-row (bf16) / 8-row (float32) tile groups: the written rows
    hold the new K/V of every head, and every other element of the pool —
    other rows of the same tile group, other pages, other layers, the junk
    page no row here parks on — is bit-identical to what went in."""
    L, P, page, layer = 2, 6, 128, 1
    pt = jnp.asarray([[3, 1], [5, 2], [4, 0], [2, 5]], jnp.int32)
    pos = jnp.asarray([0, 15, 16, 128 + 77], jnp.int32)   # tiles 0, 0, 1, 4
    pt = pt.at[3, 1].set(1)                # row 3's second page: page 1
    B = pos.shape[0]
    kc = _rand(0, L, P, Hkv, page, Dh, dtype=dtype)
    vc = _rand(1, L, P, Hkv, page, Dh, dtype=dtype)
    k = _rand(2, B, Hkv, Dh, dtype=dtype)
    v = _rand(3, B, Hkv, Dh, dtype=dtype)
    got = paged_kv_append(kc, vc, k, v, pos, pt, layer=layer,
                          impl="interpret")
    bits = lambda a: np.asarray(a.astype(jnp.float32))
    for new, old, out in ((k, kc, got[0]), (v, vc, got[1])):
        want = bits(old).copy()
        for b in range(B):
            pp = int(pt[b, int(pos[b]) // page])
            want[layer, pp, :, int(pos[b]) % page, :] = bits(new[b])
        np.testing.assert_array_equal(bits(out), want)
        assert out.dtype == old.dtype and out.shape == old.shape


def test_flash_decode_paged_small_page_falls_back():
    """Pages below the 128-lane tile route to the gathered dense
    reference (the CPU / tiny-config path) — and still match."""
    B, Hkv, Dh, page, maxp = 1, 2, 64, 16, 4
    q = _rand(0, B, Hkv, Dh)
    k = _rand(1, B, Hkv, maxp * page, Dh)
    v = _rand(2, B, Hkv, maxp * page, Dh)
    kp, vp, pt = _paged_from_logical(k, v, maxp, page)
    got = flash_decode(q, kp, vp, jnp.asarray([33], jnp.int32),
                       page_table=pt, impl="interpret")
    want = _flash_decode_ref(q, k, v, jnp.asarray([33], jnp.int32),
                             scale=Dh ** -0.5)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_flash_decode_odd_cache_falls_back():
    """Cache lengths that are not a block multiple route to the dense
    reference (a non-tile-aligned Pallas block would be handed to Mosaic
    otherwise) — and still produce the right numbers."""
    B, Hkv, Smax, Dh = 1, 2, 145, 64
    q = _rand(0, B, Hkv, Dh)
    k = _rand(1, B, Hkv, Smax, Dh)
    v = _rand(2, B, Hkv, Smax, Dh)
    got = flash_decode(q, k, v, 100, impl="interpret")
    want = _flash_decode_ref(q, k, v, jnp.int32(100), scale=Dh ** -0.5)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_short_generation_small_cache():
    """A default-sized generate (cache under one decode block) works on the
    fused path end-to-end (exercises the odd-Smax fallback in situ)."""
    import deepspeed_tpu
    from deepspeed_tpu.models import causal_lm

    model = causal_lm("llama-tiny", num_layers=2, vocab_size=256,
                      max_seq_len=64)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    engine = deepspeed_tpu.init_inference(
        model, config={"max_out_tokens": 64, "dtype": "float32"})
    engine.set_params(params)
    assert engine._dparams is not None
    out = np.asarray(engine.generate(np.array([[3, 1, 4]]),
                                     max_new_tokens=12, do_sample=False))
    assert out.shape == (1, 15)


@pytest.mark.parametrize("pos", [5, 300])
def test_flash_decode_alibi(pos):
    """ALiBi bias in the decode kernel matches the biased dense reference."""
    B, Hkv, Smax, Dh = 1, 6, 512, 64   # 6 heads: non-power-of-2 slopes
    q = _rand(0, B, Hkv, Dh)
    k = _rand(1, B, Hkv, Smax, Dh)
    v = _rand(2, B, Hkv, Smax, Dh)
    got = flash_decode(q, k, v, pos, alibi=True, impl="interpret")
    want = _flash_decode_ref(q, k, v, jnp.int32(pos), scale=Dh ** -0.5,
                             alibi=True)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_flash_decode_stacked_layer_offset():
    """layer= reads the right slice of a stacked [L, B, Hkv, Smax, Dh]
    cache through the index-map offset."""
    L, B, Hkv, Smax, Dh = 3, 2, 2, 512, 64
    q = _rand(0, B, 2 * Hkv, Dh)
    k = _rand(1, L, B, Hkv, Smax, Dh)
    v = _rand(2, L, B, Hkv, Smax, Dh)
    for l in range(L):
        got = flash_decode(q, k, v, 300, layer=l, impl="interpret")
        want = _flash_decode_ref(q, k[l], v[l], jnp.int32(300),
                                 scale=Dh ** -0.5)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_proj_norm_blocked_grid():
    """M large enough that the out-projection is tiled over its rows and
    accumulated across grid steps (the D=4096 presets on the chip)."""
    B, M, D = 2, 6144, 2048
    ctx = _rand(0, B, M, dtype=jnp.bfloat16)
    resid = _rand(1, B, D, dtype=jnp.bfloat16)
    wo = (_rand(2, M, D) * M ** -0.5).astype(jnp.bfloat16)
    scale = jnp.ones((D,), jnp.bfloat16)
    bias = jnp.zeros((D,), jnp.bfloat16)
    got = fused_proj_norm(ctx, resid, wo, None, scale, bias, kind="rmsnorm",
                          impl="interpret")
    want = _proj_norm_ref(ctx, resid, wo, None, scale, bias, kind="rmsnorm",
                          eps=1e-5, parallel=False)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.float32(g), np.float32(w),
                                   rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("kind,parallel", [("layernorm", False),
                                           ("rmsnorm", False),
                                           ("layernorm", True)])
def test_proj_norm_parity(kind, parallel):
    B, M, D = 2, 192, 256
    ctx = _rand(0, B, M)
    resid = _rand(1, B, D)
    wo = _rand(2, M, D)
    bo = _rand(3, D)
    scale = 1.0 + 0.1 * _rand(4, D)
    bias = _rand(5, D)
    got_r, got_h = fused_proj_norm(ctx, resid, wo, bo, scale, bias,
                                   kind=kind, parallel=parallel,
                                   impl="interpret")
    want_r, want_h = _proj_norm_ref(ctx, resid, wo, bo, scale, bias,
                                    kind=kind, eps=1e-5, parallel=parallel)
    np.testing.assert_allclose(got_r, want_r, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got_h, want_h, rtol=2e-5, atol=2e-5)


def _generate(preset, fused, prompt, dtype="float32", unroll=4, **overrides):
    import deepspeed_tpu
    from deepspeed_tpu.models import causal_lm

    model = causal_lm(preset, **overrides)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    engine = deepspeed_tpu.init_inference(
        model, config={"max_out_tokens": 128, "dtype": dtype,
                       "use_fused_decode": fused, "decode_unroll": unroll})
    engine.set_params(params)
    if fused:
        assert engine._dparams is not None, "injection should be active"
    else:
        assert engine._dparams is None
    return np.asarray(engine.generate(prompt, max_new_tokens=24,
                                      do_sample=False))


@pytest.mark.parametrize("preset,overrides", [
    ("gpt2-small", dict(num_layers=2, hidden_size=128, num_heads=4,
                        vocab_size=512, max_seq_len=128)),
    ("llama-tiny", dict(num_layers=2, vocab_size=512, max_seq_len=128)),
])
def test_fused_generation_matches_unfused(preset, overrides):
    """Kernel-injected decode produces the same greedy tokens as the
    reference-shaped unfused loop (end-to-end injection parity, the
    containers-level check the other import families get)."""
    prompt = np.array([[5, 17, 200, 3, 42, 7, 11, 23]])
    plain = _generate(preset, False, prompt, **overrides)
    fused = _generate(preset, True, prompt, **overrides)
    np.testing.assert_array_equal(plain, fused)


def test_int8_kernels_match_refs():
    """In-kernel dequant (wscale=...) matches the reference path that
    dequantizes before the matmul, for all three weight-bearing kernels."""
    from deepspeed_tpu.models.quant import quantize_weight

    B, D, N, F = 2, 256, 384, 512
    x = _rand(0, B, D)
    scale = 1.0 + 0.1 * _rand(1, D)
    bias = _rand(2, D)
    wq = quantize_weight(_rand(3, D, N))
    got = fused_norm_qkv(x, scale, bias, wq.q, None, kind="layernorm",
                         wscale=wq.scale, impl="interpret")
    want = _norm_qkv_ref(x, scale, bias, wq.astype(x.dtype), None,
                         kind="layernorm", eps=1e-5)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    ctx = _rand(4, B, N)
    wo = quantize_weight(_rand(5, N, D))
    got_r, got_h = fused_proj_norm(ctx, x, wo.q, None, scale, bias,
                                   kind="layernorm", wscale=wo.scale,
                                   impl="interpret")
    want_r, want_h = _proj_norm_ref(ctx, x, wo.astype(x.dtype), None, scale,
                                    bias, kind="layernorm", eps=1e-5,
                                    parallel=False)
    np.testing.assert_allclose(got_r, want_r, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(got_h, want_h, rtol=2e-5, atol=2e-5)

    wu = quantize_weight(_rand(6, D, F))
    wg = quantize_weight(_rand(7, D, F))
    wd = quantize_weight(_rand(8, F, D))
    got = fused_mlp(x, x, wu.q, wd.q, wg.q, act="silu",
                    wscales=(wu.scale, wg.scale, wd.scale),
                    impl="interpret")
    want = _mlp_ref(x, x, wu.astype(x.dtype), wg.astype(x.dtype),
                    wd.astype(x.dtype), None, None, None, act="silu")
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)


def test_int8_weights_fused_generation():
    """int8 weight serving rides the kernel-injected path (dequant
    in-kernel) and matches the unfused int8 loop."""
    import deepspeed_tpu
    from deepspeed_tpu.models import causal_lm

    outs = {}
    for fused in (True, False):
        model = causal_lm("llama-tiny", num_layers=2, vocab_size=512,
                          max_seq_len=512)
        params = jax.jit(model.init)(jax.random.PRNGKey(0))
        engine = deepspeed_tpu.init_inference(
            model, config={"max_out_tokens": 512, "dtype": "int8",
                           "use_fused_decode": fused})
        engine.set_params(params)
        assert (engine._dparams is not None) == fused
        outs[fused] = np.asarray(engine.generate(
            np.array([[5, 17, 200, 3]]), max_new_tokens=280,
            do_sample=False))
    agree = (outs[True] == outs[False]).mean()
    assert agree > 0.9, agree                     # bf16 reorder tolerance
    np.testing.assert_array_equal(outs[True][:, :12], outs[False][:, :12])


def test_unroll_tail_exact():
    """decode_unroll > 1 must not change the produced token count or the
    tokens themselves when max_new_tokens is not a multiple of the unroll."""
    overrides = dict(num_layers=2, hidden_size=128, num_heads=4,
                     vocab_size=512, max_seq_len=128)
    import deepspeed_tpu
    from deepspeed_tpu.models import causal_lm

    outs = []
    for unroll in (1, 3):
        model = causal_lm("gpt2-small", **overrides)
        params = jax.jit(model.init)(jax.random.PRNGKey(0))
        engine = deepspeed_tpu.init_inference(
            model, config={"max_out_tokens": 64, "dtype": "float32",
                           "use_fused_decode": False,
                           "decode_unroll": unroll})
        engine.set_params(params)
        outs.append(np.asarray(engine.generate(
            np.array([[5, 17, 200]]), max_new_tokens=7, do_sample=False)))
    assert outs[0].shape == outs[1].shape == (1, 10)
    np.testing.assert_array_equal(outs[0], outs[1])


@pytest.mark.parametrize("glu", [True, False])
@pytest.mark.parametrize("with_bias", [True, False])
def test_mlp_parity(glu, with_bias):
    B, D, F = 2, 256, 1024
    h = _rand(0, B, D)
    r = _rand(1, B, D)
    w_up = _rand(2, D, F)
    w_gate = _rand(3, D, F) if glu else None
    w_down = _rand(4, F, D)
    b_up = _rand(5, F) if with_bias else None
    b_gate = _rand(6, F) if (glu and with_bias) else None
    b_down = _rand(7, D) if with_bias else None
    act = "silu" if glu else "gelu"
    got = fused_mlp(h, r, w_up, w_down, w_gate, b_up, b_gate, b_down,
                    act=act, impl="interpret")
    want = _mlp_ref(h, r, w_up, w_gate, w_down, b_up, b_gate, b_down, act=act)
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)


# which rows decode, and where each row's ``pos`` stands (page 256, 3 pages
# a row): the batches a decode block hands the attention kernels
LIVE_BATCHES = {
    # parked rows between the live ones, their stale pos deeper than any
    "interleaved": ([False, True, False, True, False],
                    [700, 5, 767, 300, 600]),
    "none_live": ([False] * 4, [5, 300, 0, 767]),
    "all_live": ([True] * 4, [5, 300, 0, 767]),
    "one_and_all_pages": ([True, True, False], [3, 767, 400]),
    "no_mask": (None, [5, 300, 0, 767]),
}


@pytest.mark.parametrize("layout", ["paged", "contiguous", "paged128"])
@pytest.mark.parametrize("case", sorted(LIVE_BATCHES))
def test_flash_decode_visits_live_rows(case, layout):
    """The grid follows ``live``: the rows that decode equal the dense
    reference, the others come back finite (their ``q``: they were never
    visited, so a NaN in every page a PARKED row's table and ``pos`` name
    is never read), and no live row at all is a kernel of no steps."""
    mask, pos = LIVE_BATCHES[case]
    B, Hkv, rep, page, maxp = len(pos), 2, 2, 256, 3
    Dh = 128 if layout == "paged128" else 64    # pages walked in a grid step
    q = _rand(0, B, Hkv * rep, Dh)
    k = _rand(1, B, Hkv, maxp * page, Dh)
    v = _rand(2, B, Hkv, maxp * page, Dh)
    live = None if mask is None else jnp.asarray(mask)
    rows = np.flatnonzero(np.ones(B) if mask is None else mask)
    parked = np.setdiff1d(np.arange(B), rows)
    # whatever a parked row could reach is poison
    kx, vx = k.at[parked].set(jnp.nan), v.at[parked].set(jnp.nan)
    posv = jnp.asarray(pos, jnp.int32)
    if layout.startswith("paged"):
        kp, vp, pt = _paged_from_logical(kx, vx, maxp, page)
        got = flash_decode(q, kp, vp, posv, page_table=pt, live=live,
                           impl="interpret")
    else:
        got = flash_decode(q, kx, vx, posv, live=live, impl="interpret")
    want = _flash_decode_ref(q, k, v, posv, scale=Dh ** -0.5)
    np.testing.assert_allclose(got[rows], want[rows], rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(got[parked], q[parked])


# -- pages walked inside a grid step (head dims that fill the lanes) -----------
def _poisoned_pool(k, v, pos, live, maxp, page, seed=7):
    """:func:`_paged_from_logical` with NaN wherever no live row attends:
    past every row's ``pos`` inside its pages, in every page of a row that
    does not decode, in the junk page and in the pages nobody owns."""
    B = k.shape[0]
    at = jnp.arange(maxp * page)[None, None, :, None]
    keep = (at <= jnp.asarray(pos)[:, None, None, None]) \
        & jnp.asarray(live)[:, None, None, None]
    kp, vp, pt = _paged_from_logical(jnp.where(keep, k, jnp.nan),
                                     jnp.where(keep, v, jnp.nan), maxp, page,
                                     seed=seed)
    pad = jnp.full((2,) + kp.shape[1:], jnp.nan, kp.dtype)
    return (jnp.concatenate([kp.at[0].set(jnp.nan), pad]),
            jnp.concatenate([vp.at[0].set(jnp.nan), pad]), pt)


# page 256, 4 pages a row: every piece boundary of a last page, both sides of
# a page boundary, a page multiple less one, the table's last row
WALK_POSITIONS = [0, 63, 64, 255, 256, 300, 767, 1023]
WALK_LIVE = {
    "all": [True] * 4, "one": [False, False, True, False],
    "alternating": [True, False, True, False], "none": [False] * 4}


@pytest.mark.parametrize("alibi", [False, True])
@pytest.mark.parametrize("pos", WALK_POSITIONS)
def test_walked_pages_attend_up_to_pos_in_a_poisoned_pool(pos, alibi):
    """The stale-V hazard: a row's last page comes in pieces of 64 tokens up
    to ``pos``, so its buffer holds an earlier page's rows (or nothing yet)
    past the last piece and the pool's own rows between ``pos`` and the
    piece's end.  Both are NaN here; the scores there are masked, so their
    weight is exactly 0, and the values are selected by ``key_pos <= pos``
    (``0 x NaN`` is NaN): the output is finite and the reference's over the
    attended rows."""
    B, Hkv, rep, Dh, page, maxp = 3, 2, 2, 128, 256, 4
    posv = [pos, 300, 1023 - pos]
    q = _rand(0, B, Hkv * rep, Dh)
    k = _rand(1, B, Hkv, maxp * page, Dh)
    v = _rand(2, B, Hkv, maxp * page, Dh)
    kp, vp, pt = _poisoned_pool(k, v, posv, [True] * B, maxp, page)
    assert np.isnan(np.asarray(kp)).mean() > 0.3
    got = flash_decode(q, kp, vp, jnp.asarray(posv, jnp.int32), page_table=pt,
                       alibi=alibi, impl="interpret")
    want = _flash_decode_ref(q, k, v, jnp.asarray(posv, jnp.int32),
                             scale=Dh ** -0.5, alibi=alibi)
    assert np.all(np.isfinite(np.asarray(got)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("layer", ["static", "traced", None])
@pytest.mark.parametrize("case", sorted(WALK_LIVE))
def test_walked_pages_follow_the_live_mask_in_a_poisoned_pool(case, layer):
    """Live masks over rows one, two and four pages deep, MHA with two head
    groups a row, the pool a stacked one read at a static and at a TRACED
    layer (Ouro's cache-layer offset) and a flat one: a live row equals the
    reference, a parked row (its pages, ``pos`` and the junk page all NaN)
    gets its ``q`` back, no live row is a kernel of no steps."""
    live = WALK_LIVE[case]
    B, Hkv, rep, Dh, page, maxp, L = 4, 16, 1, 128, 256, 4, 2
    posv = [255, 700, 64, 1023]
    q = _rand(0, B, Hkv * rep, Dh)
    k = _rand(1, B, Hkv, maxp * page, Dh)
    v = _rand(2, B, Hkv, maxp * page, Dh)
    kp, vp, pt = _poisoned_pool(k, v, posv, live, maxp, page)
    if layer is None:
        at = None
    else:               # layer 0 is poison all over; the call reads layer 1
        kp = jnp.stack([jnp.full_like(kp, jnp.nan), kp])
        vp = jnp.stack([jnp.full_like(vp, jnp.nan), vp])
        at = 1
    call = lambda at: flash_decode(
        q, kp, vp, jnp.asarray(posv, jnp.int32), layer=at, page_table=pt,
        live=jnp.asarray(live), impl="interpret")
    got = jax.jit(call)(jnp.int32(at)) if layer == "traced" else call(at)
    want = _flash_decode_ref(q, k, v, jnp.asarray(posv, jnp.int32),
                             scale=Dh ** -0.5)
    rows = np.flatnonzero(live)
    parked = np.setdiff1d(np.arange(B), rows)
    np.testing.assert_allclose(got[rows], want[rows], rtol=2e-4, atol=2e-4)
    np.testing.assert_array_equal(got[parked], q[parked])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("Hkv,rep", [(8, 4), (16, 1)])
def test_walked_pages_under_the_chips_interpreter(Hkv, rep, dtype,
                                                  chips_interpreter):
    """The copies' bookkeeping, under jax's TPU interpreter (the
    ``chips_interpreter`` fixture of ``conftest.py``): a page scored before
    its copy was waited for would read NaN, a copy started and never waited
    for leaves its semaphore above 0 at the kernel's end."""
    B, Dh, page, maxp = 4, 128, 256, 3
    posv, live = [300, 63, 767, 5], [True, True, False, True]
    q = _rand(0, B, Hkv * rep, Dh, dtype=dtype)
    k = _rand(1, B, Hkv, maxp * page, Dh, dtype=dtype)
    v = _rand(2, B, Hkv, maxp * page, Dh, dtype=dtype)
    kp, vp, pt = _poisoned_pool(k.astype(jnp.float32), v.astype(jnp.float32),
                                posv, live, maxp, page)
    got = flash_decode(q, kp.astype(dtype), vp.astype(dtype),
                       jnp.asarray(posv, jnp.int32), page_table=pt,
                       live=jnp.asarray(live), impl="interpret")
    got = np.asarray(got, np.float32)
    want = _flash_decode_ref(q, k, v, jnp.asarray(posv, jnp.int32),
                             scale=Dh ** -0.5)
    rows = np.flatnonzero(live)
    tol = 2e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got[rows], np.float32(want)[rows], rtol=tol,
                               atol=tol)
    np.testing.assert_array_equal(got[2], np.float32(q[2]))
    chips_interpreter()


def _pallas_calls(jaxpr):
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                out += _pallas_calls(getattr(inner, "jaxpr", inner))
    return out


def _decode_call(name):
    """One decode attention call of each cache layout, traced with a live
    mask: two rows, four pages of 256 a row."""
    from deepspeed_tpu.ops.pallas.decode import (eva_decode_paged,
                                                 mla_decode_paged)

    B = 2
    pos, live = jnp.zeros((B,), jnp.int32), jnp.ones((B,), bool)
    pt = jnp.zeros((B, 4), jnp.int32)
    pool = lambda dh, hkv=2: jnp.zeros((2, 9, hkv, 256, dh))
    q = lambda dh: jnp.zeros((B, 4, dh))
    return {
        "contiguous": lambda: flash_decode(
            q(128), jnp.zeros((B, 2, 512, 128)), jnp.zeros((B, 2, 512, 128)),
            pos, live=live, impl="interpret"),
        "latent": lambda: mla_decode_paged(
            jnp.zeros((B, 8, 256)), pool(256, 1), pos, pt, layer=1,
            sm_scale=0.1, live=live, impl="interpret"),
        "eva": lambda: eva_decode_paged(
            q(128), pool(128), pool(128), pos, pt, layer=1, window=512,
            chunk=16, live=live, impl="interpret"),
        "paged": lambda: flash_decode(
            q(128), pool(128), pool(128), pos, layer=1, page_table=pt,
            live=live, impl="interpret"),
        "paged_head_dim_64": lambda: flash_decode(
            q(64), pool(64), pool(64), pos, layer=1, page_table=pt,
            live=live, impl="interpret"),
    }[name]


@pytest.mark.parametrize("call,name,grid_rank,operands,blocks", [
    ("contiguous", "flash_decode", 3, 8, 5),
    ("latent", "mla_decode_paged", 3, 8, 4),
    ("paged_head_dim_64", "flash_decode_paged", 3, 9, 5),
    ("paged", "flash_decode_paged", 2, 9, 5),
    ("eva", "eva_decode_paged", 2, 9, 5)])
def test_the_schedule_is_chosen_by_the_cache_layout(call, name, grid_rank,
                                                    operands, blocks):
    """ISSUE 60, 62: per-head K and V pools under one page table, at a head
    dim that fills the lanes, take the schedule that walks a row's pages
    inside a grid step (grid (live rows, head groups): rank 2, ONE run-time
    extent before rows, pos, the layer's first page, the table, q, K, V and
    the slopes), full pages as one run and EVA's window and summary pages as
    two; every other caller
    keeps :func:`_decode_attention`'s, a page a grid step, by name,
    grid rank, operand count and blocks.  One kernel a call, whatever the
    schedule."""
    (eqn,) = _pallas_calls(jax.make_jaxpr(_decode_call(call))().jaxpr)
    gm = eqn.params["grid_mapping"]
    assert (eqn.params["name"], len(gm.grid), len(eqn.invars),
            len(gm.block_mappings)) == (name, grid_rank, operands, blocks)
    if grid_rank == 2:      # the live rows, read at run time; the groups
        assert [isinstance(g, int) for g in gm.grid] == [False, True]


# -- a traced cache layer (a looped stack's ``pass * layers + layer``) ---------
@pytest.mark.parametrize("Dh", [64, 128])
@pytest.mark.parametrize("impl", ["interpret", "xla"])
def test_a_traced_cache_layer_appends_and_attends_as_the_int_layer_does(impl,
                                                                        Dh):
    """``paged_kv_append`` and ``flash_decode(page_table=)`` inside a rolled
    loop over the cache layers, the layer a traced scalar (folded into the
    scalar-prefetched page numbers), against one call a Python-int layer:
    the same pools bit for bit, the same attention."""
    B, Hkv, page, maxp, L = 3, 2, 128, 2, 3
    ks, vs, kp_all, vp_all, pt = _stacked_pools(B, Hkv, Dh, page, maxp, L,
                                                seed=3)
    pt = _park(pt, 1)
    q = _rand(0, B, 2 * Hkv, Dh)
    k, v = _rand(1, L, B, Hkv, Dh), _rand(2, L, B, Hkv, Dh)
    pos = jnp.asarray([130, 0, 255], jnp.int32)
    live = jnp.asarray([True, False, True])

    def step(layer, kc, vc):
        kc, vc = paged_kv_append(kc, vc, k[layer], v[layer], pos, pt,
                                 layer=layer, impl=impl)
        return kc, vc, flash_decode(q, kc, vc, pos, layer=layer,
                                    page_table=pt, live=live, impl=impl)

    def rolled(kc, vc):
        def body(layer, carry):
            kc, vc, outs = carry
            kc, vc, o = step(layer, kc, vc)
            return kc, vc, outs.at[layer].set(o)
        return jax.lax.fori_loop(0, L, body,
                                 (kc, vc, jnp.zeros((L,) + q.shape, q.dtype)))

    got_k, got_v, got = jax.jit(rolled)(kp_all, vp_all)
    want_k, want_v, want = kp_all, vp_all, []
    for layer in range(L):
        want_k, want_v, o = step(layer, want_k, want_v)
        want.append(o)
    rows = np.asarray([0, 2])                   # row 1 is parked on page 0
    np.testing.assert_array_equal(np.asarray(got_k[:, 1:]),
                                  np.asarray(want_k[:, 1:]))
    np.testing.assert_array_equal(np.asarray(got_v[:, 1:]),
                                  np.asarray(want_v[:, 1:]))
    np.testing.assert_allclose(np.asarray(got)[:, rows],
                               np.asarray(jnp.stack(want))[:, rows],
                               rtol=1e-6, atol=1e-6)
    # ... and each layer read ITS pool: another layer's rows give another o
    assert np.abs(np.asarray(got)[0, rows] - np.asarray(got)[1, rows]).max() \
        > 1e-2


def test_a_traced_layer_offsets_the_contiguous_cache():
    """``flash_decode(layer=)`` over a stacked [L, B, Hkv, Smax, Dh] cache
    with the layer traced: its offset rides as a prefetched scalar."""
    L, B, Hkv, Smax, Dh = 3, 2, 2, 512, 64
    q = _rand(0, B, 2 * Hkv, Dh)
    k, v = _rand(1, L, B, Hkv, Smax, Dh), _rand(2, L, B, Hkv, Smax, Dh)
    pos = jnp.asarray([300, 17], jnp.int32)
    got = jax.jit(lambda layer: flash_decode(q, k, v, pos, layer=layer,
                                             impl="interpret"))
    for l in range(L):
        want = flash_decode(q, k, v, pos, layer=l, impl="interpret")
        np.testing.assert_allclose(got(jnp.int32(l)), want, rtol=1e-6,
                                   atol=1e-6)
