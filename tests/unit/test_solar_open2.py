"""Linear-attention layers beside per-head ``full_attention`` layers
(``models/kda_mla.py``; Solar-Open2) at a tiny size on the CPU: hidden 64,
four KDA heads of 16 with a convolution of 4 taps and ``beta`` in (0, 2),
four query heads over two key-value heads of 16 with the elementwise gate and
no position encoding, page 8, chunks of 16, a router of 16 experts of which
4 (or 2) are held, top-8, every layer an expert layer, pattern ``[f | k, k,
k | f]`` (one published period and the next one's full layer, so that two
cache layers lie under the page table).  The plain reference
(``benchmarks/reference/solar_open2.py``) against the three forwards; the
delta rule's three forms on rows whose ``beta`` exceeds 1; the share of the
experts against the whole; the seventh cache kind; the refusals."""

import dataclasses
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm.mesh import build_mesh
from deepspeed_tpu.models import CausalLM, ModelConfig, afmoe, kda_mla
from deepspeed_tpu.serving import cache_kind
from deepspeed_tpu.serving.paged_kv import PagedKVPool
from tests.unit._serving import (as_found, read_served, tapped_engine,
                                  with_noise)

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
PATTERN = ("full_attention",) + ("linear_attention",) * 3 \
    + ("full_attention",)
FIELDS = dict(
    vocab_size=96, hidden_size=64, intermediate_size=48, num_layers=5,
    num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=256,
    layer_types=PATTERN, num_dense_layers=0, attn_output_gate=True,
    kda_num_heads=4, kda_head_dim=16, kda_conv_kernel=4, kda_gate_rank=16,
    kda_neg_eigval=True, moe_score_func="sigmoid", moe_route_scale=1.0,
    moe_select_bias=True, num_shared_experts=1, num_experts=4,
    moe_router_experts=16, moe_first_expert=0, num_experts_per_tok=8,
    moe_drop_tokens=False)
# the configuration file's keys the reference reads, at the same size
REF_CONFIG = {
    "num_hidden_layers": 5, "gqa_layers": [0, 4],
    "linear_attn_config": {"num_heads": 4, "head_dim": 16,
                           "short_conv_kernel_size": 4},
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "num_experts_per_tok": 8,
    "routed_scaling_factor": 1, "norm_topk_prob": True,
    "expert_parallel": {"first_expert": 0}}
ENGINE = dict(num_slots=3, prefill_chunk=16, max_prefill_chunks=2,
              decode_block_tokens=4, max_out_tokens=96, kv_page_tokens=8,
              dtype="float32")


@pytest.fixture(scope="module")
def ref():
    if REPO not in sys.path:          # the reference imports its siblings
        sys.path.insert(0, REPO)
    spec = importlib.util.spec_from_file_location(
        "_solar_ref", os.path.join(REPO, "benchmarks", "reference",
                                   "solar_open2.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model():
    m = CausalLM(ModelConfig(**FIELDS),
                 build_mesh(devices=jax.devices()[:1]))
    # gains of exactly 1 would hide a dropped norm
    return m, with_noise(m.init(jax.random.PRNGKey(0)))


def ref_logits(ref, params, seq, rows, config=REF_CONFIG, **kw):
    return np.asarray(ref.logits_rows(params, config, np.asarray(seq), rows,
                                      jax.devices()[0], **kw))


def serve_of(model, **kw):
    m, params = model
    return deepspeed_tpu.init_serving(m, config=dict(ENGINE, **kw),
                                      params=params, mesh=m.mesh)


@pytest.fixture(scope="module")
def tapped(model):
    """One engine at ``ENGINE``, its programs traced under the serve taps,
    for the cases that differ in their requests alone."""
    yield from tapped_engine(lambda: serve_of(model))


# ------------------------------------------- (a) system against reference
def test_reference_agrees_with_the_no_cache_forward(ref, model):
    """``CausalLM.apply`` (the chunkwise recurrence over 83 + 45 pad rows,
    ``afmoe.attend`` over the whole sequence) against the reference's
    one-step recurrence and plain softmax, both float32: 2e-4 is what the
    UT transform's forward substitution and the online softmax leave at
    hidden 64 (Kimi's test of the same name reads the same)."""
    m, params = model
    seq = np.random.default_rng(1).integers(0, 96, 83)
    got = np.asarray(jax.jit(m.apply)(params, seq[None]))[0]
    # the reference's OWN routing, stated: no row takes a near-tie's other side
    _, _, own = ref.hidden_states(params, REF_CONFIG, seq, jax.devices()[0],
                                  return_routing=True)
    want = ref_logits(ref, params, seq, list(range(83)), routing=list(own))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("prompts", [(27, 16), (37, 5), (48, 17)],
                         ids=["16_and_11_then_on_a_chunk",
                              "two_chunks_and_a_pad_bucket_then_tiny",
                              "three_chunks_then_one_past"])
def test_chunked_prefill_then_decode_is_the_references_one_forward(
        ref, model, tapped, prompts):
    """Prefill in chunks of UNEQUAL size (16 then 11 real rows in a bucket
    of 16: the state and the tails carried over, the later chunk's queries
    attending the K/V rows the earlier one wrote through the page table),
    then decode through the slot state and the full layers' pages on the
    fused path, two requests in flight, float32: the program's LOGITS at
    every generated position are the reference's full forward's under the
    program's routing (5e-4: the same two approximations as above plus the
    cache's round trip, no bf16 anywhere), and every token is the argmax of
    the reference under its OWN routing."""
    ps = [np.random.default_rng(n).integers(0, 96, n) for n in prompts]
    served = read_served(tapped, ps, [21, 13])
    assert tapped[1].pool.pages_used == 0
    for p, rec in zip(ps, served):
        seq = np.concatenate([p, rec["tokens"]])
        rows = list(range(len(p) - 1, len(seq) - 1))
        want = ref_logits(ref, model[1], seq, rows, routing=rec["routing"])
        np.testing.assert_allclose(rec["logits"], want, rtol=5e-4, atol=5e-4)
        free = ref_logits(ref, model[1], seq, rows)
        assert list(free.argmax(-1)) == list(rec["tokens"])


def test_the_served_logits_tell_a_beta_without_the_two(ref, model):
    """The control of the comparison above: the reference with ``beta =
    sigmoid`` (Kimi's form) is far from the program's logits, and the
    program with ``kda_neg_eigval`` off is far from the reference."""
    m, params = model
    seq = np.random.default_rng(2).integers(0, 96, 40)
    rows = list(range(40))
    _, _, own = ref.hidden_states(params, REF_CONFIG, seq, jax.devices()[0],
                                  return_routing=True)
    want = ref_logits(ref, params, seq, rows, routing=list(own))
    # (the broken reference routes by its own stream, as the broken model)
    _, _, its = ref.hidden_states(params, REF_CONFIG, seq, jax.devices()[0],
                                  return_routing=True,
                                  variant=("beta_sigmoid",))
    wrong = ref_logits(ref, params, seq, rows, routing=list(its),
                       variant=("beta_sigmoid",))
    assert np.abs(want - wrong).max() > 0.05
    plain = CausalLM(dataclasses.replace(m.config, kda_neg_eigval=False),
                     m.mesh)
    got = np.asarray(jax.jit(plain.apply)(params, seq[None]))[0]
    np.testing.assert_allclose(got, wrong, rtol=2e-4, atol=2e-4)
    assert np.abs(got - want).max() > 0.05


def test_preempt_and_resume_are_token_identical(model, tapped):
    """A pool of twelve pages for three slots: the youngest is preempted,
    re-prefills prompt + outputs onto a zeroed state and fresh pages, and
    every request still gets the tokens an unpressed engine gives it."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 96, n) for n in (22, 30, 17)]
    news = (30, 24, 36)
    tight = serve_of(model, kv_pool_tokens=96)
    with as_found(tapped[1]) as easy:
        want = [easy.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, news)]
        easy.run()
    got = [tight.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
    tight.run()
    tight.pool.check_no_leak()
    assert sum(r.preemptions for r in got) > 0
    for w, g in zip(want, got):
        assert list(g.output_tokens) == list(w.output_tokens)
    tight.close()


def test_bf16_serving_stays_within_the_drivers_bound(ref, model):
    """What the benchmark's ``verify`` checks, at the serving dtype, with the
    state float32 whatever the stream's and the K/V pages in the stream's."""
    m, params = model
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    serve = deepspeed_tpu.init_serving(
        m, config=dict(ENGINE, dtype="bfloat16"), params=params, mesh=m.mesh)
    assert serve._cache["state"].dtype == jnp.float32
    assert serve._cache["k"].dtype == serve._cache["tail"].dtype \
        == jnp.bfloat16
    p = np.random.default_rng(3).integers(0, 96, 29)
    r = serve.submit(p, max_new_tokens=24)
    serve.run()
    seq = np.concatenate([p, r.output_tokens])
    want = ref_logits(ref, params, seq, list(range(28, len(seq) - 1)))
    best = want.max(-1)
    got = want[np.arange(24), r.output_tokens]
    step = 2.0 ** (np.floor(np.log2(np.abs(best))) - 7)
    assert ((best - got) / step).max() <= 12.0   # hidden 64: 4.0 on the chip
    serve.close()


# ------------------------------------------------- (b) the eight shares
def test_sum_of_the_eight_shares_is_the_whole_layer(ref, model):
    """The ranks' routed parts of one expert layer + the shared expert once
    = the uncut layer at top-8 of 16 (the reference with every expert
    held), for a layer under a full layer and one under a linear layer."""
    both = dict(FIELDS, moe_router_experts=16)
    cfg = ModelConfig(**dict(both, num_experts=2))
    whole_cfg = ModelConfig(**dict(both, num_experts=16))
    whole = kda_mla.init_params(whole_cfg, jax.random.PRNGKey(4))
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 23, 64))
    for l in (0, 2):
        lp, _ = kda_mla.layer_params(whole_cfg, whole, l)
        shared = afmoe.glu_mlp(h[0], lp["mlp"]["shared"])
        total = -7.0 * shared           # each share adds the shared expert
        for r in range(8):
            mine = {k: whole["layers"]["mlp"][k][:, 2 * r:2 * r + 2]
                    for k in ("w_up", "w_gate", "w_down")}
            total = total + afmoe.mlp(
                dataclasses.replace(cfg, moe_first_expert=2 * r), lp, h,
                mine, l)[0]
        w = ref.layer_weights(whole, REF_CONFIG, l, jax.devices()[0])
        with jax.default_matmul_precision("highest"):
            _, local, fullest, _ = ref.route(
                h[0], w, None, 23, top_k=8, first=0, route_scale=1.0,
                route_norm=True)
            want = ref.expert_close(
                jnp.zeros_like(h[0]), h[0], w, local, eps=1e-5, cap=32,
                variant=("no_post_norm",))
        assert int(fullest) > 0
        np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-4)


# ----------------------------------------------- (c) beta between 1 and 2
def _recurrence_inputs(s, H, d, seed=0):
    """A state to start from and ``s`` rows whose ``beta`` lies in (1, 2):
    every step reflects the state along k (``1 - beta`` in (-1, 0))."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n = lambda k, sh: jax.random.normal(k, sh)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    g = jnp.broadcast_to(jnp.log(jnp.geomspace(0.999, 0.1, d)), (s, H, d)) \
        * (1 + 0.1 * jax.random.uniform(ks[3], (s, H, d)))
    return (n(ks[5], (H, d, d)), unit(n(ks[0], (s, H, d))),
            unit(n(ks[1], (s, H, d))), n(ks[2], (s, H, d)), g,
            1.0 + jax.nn.sigmoid(n(ks[4], (s, H))))


def _by_the_reference(ref, S0, q, k, v, g, beta):
    """The reference's per-token rule, a Python loop."""
    S, out = S0, []
    for t in range(q.shape[0]):
        o, S = ref.delta_step(S, q[t], k[t], v[t], g[t], beta[t])
        out.append(o)
    return S, jnp.stack(out)


@pytest.mark.parametrize("form", ["chunkwise", "one_step", "decode_kernel"])
def test_the_delta_rule_with_beta_past_one_is_the_references(ref, form):
    """The chunkwise form (``kda_chunk``: its forward substitution divides
    by nothing that ``beta`` > 1 could turn), ``kda_step`` and
    ``kda_decode_step`` (interpret mode, 16 heads of 128) against the
    reference's per-token rule on rows whose ``beta`` lies in (1, 2); the
    same rows with ``beta / 2`` (the sigmoid without its 2) are far off."""
    if form == "decode_kernel":
        from deepspeed_tpu.ops.pallas.decode import kda_decode_step

        S0, q, k, v, g, beta = _recurrence_inputs(3, 16, 128, seed=2)
        state = jnp.stack([S0, 2 * S0, -S0])[None]     # [1, B = 3, H, d, d]

        def run(beta):
            o, new, _ = kda_decode_step(state, q, k, v, g, beta, layer=0,
                                        impl="interpret")
            return new[0], o

        with jax.default_matmul_precision("highest"):
            want = [ref.delta_step(state[0, b], q[b], k[b], v[b], g[b],
                                   beta[b]) for b in range(3)]
        want = (jnp.stack([s for _, s in want]),
                jnp.stack([o for o, _ in want]))
    else:
        s = 192 if form == "chunkwise" else 24
        S0, q, k, v, g, beta = _recurrence_inputs(s, 2, 16)

        def run(beta):
            if form == "chunkwise":
                return kda_mla.kda_chunk(S0, q, k, v, g, beta)

            def one(S, x):
                o, S = kda_mla.kda_step(S, *x)
                return S, o
            return jax.lax.scan(one, S0, (q, k, v, g, beta))

        with jax.default_matmul_precision("highest"):
            want = _by_the_reference(ref, S0, q, k, v, g, beta)
    assert float(beta.min()) > 1.0
    with jax.default_matmul_precision("highest"):
        got, half = run(beta), run(beta / 2)
    for a, b in zip(got, want):
        assert bool(jnp.isfinite(a).all())
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)
    assert float(jnp.abs(half[1] - want[1]).max()) > 0.05


def test_kda_activate_doubles_beta_only_where_the_model_says():
    cfg = ModelConfig(**FIELDS)
    a = jax.tree.map(lambda t: t[0],
                     kda_mla.init_params(cfg, jax.random.PRNGKey(0))["kda"])
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    c = jax.random.normal(ks[0], (7, 192))
    low = jax.random.normal(ks[1], (7, 16))
    b_raw = 3.0 * jax.random.normal(ks[2], (7, 4))
    on = kda_mla.kda_activate(cfg, a, c, low, low, b_raw)
    off = kda_mla.kda_activate(dataclasses.replace(cfg, kda_neg_eigval=False),
                               a, c, low, low, b_raw)
    np.testing.assert_allclose(on[4], 2.0 * off[4], rtol=1e-6)
    assert float(on[4].max()) > 1.0 and float(off[4].max()) < 1.0
    for i in (0, 1, 2, 3, 5):
        np.testing.assert_array_equal(on[i], off[i])


# ------------------------------------------- the kernels, interpret mode
@pytest.mark.parametrize("live", [
    [True, True, True], [True, False, True], [False, False, False]],
    ids=["all", "parked_between", "none"])
def test_fused_layers_through_the_kernels_match_their_references(live):
    """The decode step with every Pallas kernel in interpret mode against
    the same step on the kernels' jnp references, at tile widths (16 KDA
    heads of 128, four query heads over two key-value heads of 128, pages of
    128): live rows agree; a parked row's state and tail are bit-identical
    to what they were."""
    cfg = ModelConfig(**dict(
        FIELDS, hidden_size=128, num_layers=2, head_dim=128,
        layer_types=("full_attention", "linear_attention"),
        kda_num_heads=16, kda_head_dim=128, kda_gate_rank=32,
        intermediate_size=128))
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                          kda_mla.init_params(cfg, jax.random.PRNGKey(9)))
    dparams = kda_mla.inject(cfg, params)
    pos = jnp.asarray((300, 130, 40), jnp.int32)
    table = jnp.asarray([[1, 2, 3], [4, 5, 0], [6, 0, 0]], jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(10), 5)
    state, tail = kda_mla.state_shapes(cfg, 3)
    cache = {"k": jax.random.normal(ks[0], (1, 7, 2, 128, 128), jnp.bfloat16),
             "v": jax.random.normal(ks[4], (1, 7, 2, 128, 128), jnp.bfloat16),
             "state": jax.random.normal(ks[1], state),
             "tail": jax.random.normal(ks[2], tail, jnp.bfloat16)}
    x = jax.random.normal(ks[3], (3, 128), jnp.bfloat16)
    mask = jnp.asarray(live)
    step = lambda impl: kda_mla.fused_layers(
        cfg, dparams, x, cache, pos, table, moe_live=mask, impl=impl)
    (x_ref, c_ref, s_ref), (x_k, c_k, s_k) = step("xla"), step("interpret")
    assert set(c_k) == set(cache)
    rows = np.flatnonzero(live)
    np.testing.assert_allclose(np.asarray(x_k, np.float32)[rows],
                               np.asarray(x_ref, np.float32)[rows],
                               rtol=0.05, atol=0.05)
    assert np.isfinite(np.asarray(x_k, np.float32)).all()
    np.testing.assert_allclose(c_k["state"][0][rows], c_ref["state"][0][rows],
                               rtol=1e-4, atol=1e-4)
    parked = np.flatnonzero(~np.asarray(live))
    for key in ("state", "tail"):
        np.testing.assert_array_equal(
            np.asarray(c_k[key], np.float32)[:, parked],
            np.asarray(cache[key], np.float32)[:, parked])
    # a live row's new K row lands on the page of its position (a parked
    # row's lands on the junk page its table names in the engine)
    if live[0]:
        assert (np.asarray(c_k["k"], np.float32)[0, 3, :, 300 - 256]
                != np.asarray(cache["k"], np.float32)[0, 3, :, 300 - 256]).any()
    assert list(s_k[4]) == [len(rows)] * 2
    assert list(s_ref[4]) == [len(rows), 3]


@pytest.mark.parametrize("rows,hidden,cols,x_bytes,block", [
    (128, 4096, 18432, 2, 512),      # this cell's full layer: 768 overflowed
    (128, 4096, 25088, 2, 512),      # ... and its linear layers' w_in
    (64, 4096, 6144, 2, 768),        # Mistral's chat cells: as before
    (128, 2304, 12800, 2, 1280),     # Kimi's linear layers: as before
    (32, 4096, 6144, 4, 768),        # EvaByte's float32 stream: as before
    (32, 7168, 2560, 2, 256),        # A.X-K1: as before
], ids=["solar_full", "solar_linear", "mistral", "kimi", "evabyte", "axk1"])
def test_the_projection_tile_gives_way_to_128_rows_of_4096(rows, hidden, cols,
                                                            x_bytes, block):
    """``fused_norm_qkv`` keeps the rows (double-buffered) and their normed
    copy in VMEM beside two weight tiles: at 128 rows of 4,096 a 6 MB tile
    no longer fits the chip's 16 MB (found on the chip, PR 59), and the
    tile shrinks; every older cell's rows leave two full tiles."""
    from deepspeed_tpu.ops.pallas.decode import _col_block

    resident = rows * hidden * (2 * x_bytes + 2)
    assert _col_block(hidden, cols, 2, resident=resident) == block
    if (rows, hidden) != (128, 4096):
        assert _col_block(hidden, cols, 2) == block


def test_parked_rows_keep_their_state_across_a_decode_block(model):
    serve = serve_of(model)
    rng = np.random.default_rng(6)
    for n in (12, 20):
        serve.submit(rng.integers(0, 96, n), max_new_tokens=30)
    for _ in range(3):
        serve.step()
    idle = [b for b in range(3) if not serve._active[b]]
    assert len(idle) == 1
    marked = {k: serve._cache[k].at[:, idle[0]].set(0.25)
              for k in ("state", "tail")}
    serve._cache = {**serve._cache, **marked}
    for _ in range(4):
        serve.step()
    for k in ("state", "tail"):
        assert (np.asarray(serve._cache[k][:, idle[0]], np.float32)
                == 0.25).all()
    serve.run()
    serve.pool.check_no_leak()
    serve.close()


# ------------------------------------------------ (d) the seventh cache kind
def test_the_kind_its_arrays_and_a_token_at_the_full_layers_bytes(model):
    """K and V pages in the two full layers only, the state and tails of the
    three linear layers by slot, and the pool's gauges at those sizes."""
    from deepspeed_tpu.monitor.metrics import MetricsRegistry

    m, params = model
    cfg = m.config
    kind = cache_kind.cache_kind(cfg)
    assert type(kind) is cache_kind.FullPagesAndState
    assert cache_kind.KINDS[-1] is cache_kind.FullPagesAndState
    assert (cfg.cache_layers, cfg.num_layers) == (2, 5)
    assert kind.pool_args(jnp.float32) == {
        "slot_state_bytes": 3 * (4 * 16 * 16 * 4 + 3 * 192 * 4)}
    pool = PagedKVPool(3, 96, page_tokens=8, **kind.pool_args(jnp.float32))
    cache = kind.init_cache(pool, 3, jnp.float32, False)
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (2, pool.num_pages, 2, 8, 16), "v": (2, pool.num_pages, 2, 8, 16),
        "state": (3, 3, 4, 16, 16), "tail": (3, 3, 3, 192)}
    assert kind.takes_valid_len and kind.pages_by_kind
    assert kind.chunk_rows == 64      # one sub-chunk of the recurrence
    assert "pages in 2 of 5 layers" in kind.layout(pool, 3)
    reg = MetricsRegistry().enable()
    serve = deepspeed_tpu.init_serving(m, config=ENGINE, params=params,
                                       mesh=m.mesh, registry=reg)
    # a token: K and V rows of two key-value heads of 16 in TWO layers
    assert reg.get("ds_serve_kv_bytes_per_token").value == 2 * 2 * 2 * 16 * 4
    assert reg.get("ds_serve_kv_cache_layers").value == 2
    assert reg.get("ds_serve_state_bytes").value == serve.pool.state_bytes
    serve.close()


OPTIONS = {
    "handoff": (dict(role="decode"), "handoff.py"),
    "kv_host_tier_pages": (dict(kv_host_tier_pages=4), "host_tier.py"),
    "quantize_kv_cache": (dict(quantize_kv_cache=True), "models/decoding.py"),
    "use_fused_decode": (dict(use_fused_decode=False),
                         "kda_mla.py:fused_layers"),
}


@pytest.mark.parametrize("option", [*OPTIONS, "prefix_caching"])
def test_every_cannot_entry_is_refused_by_name(model, option):
    m, params = model
    kind = cache_kind.cache_kind(m.config)
    assert set(kind.cannot) == {*OPTIONS, "prefix_caching"}
    if option == "prefix_caching":          # turned off, with the reason
        serve = serve_of(model, prefix_caching=True)
        assert serve.prefix_cache is None
        assert "state snapshots at page boundaries" in kind.cannot[option]
        serve.close()
        return
    asked, names = OPTIONS[option]
    role = {k: asked[k] for k in ("role",) if k in asked}
    config = dict(ENGINE, **{k: v for k, v in asked.items() if k != "role"})
    with pytest.raises(NotImplementedError) as err:
        deepspeed_tpu.init_serving(m, config=config, params=params,
                                   mesh=m.mesh, **role)
    assert kind.cannot[option] in str(err.value) and names in str(err.value)


def test_generate_training_and_prefill_only_are_refused(model, tapped):
    m, params = model
    with as_found(tapped[1]) as serve:
        with pytest.raises(NotImplementedError, match="prefill_only"):
            serve.submit([1, 2, 3], prefill_only=True)
        with pytest.raises(NotImplementedError, match="init_serving"):
            serve.engine.generate(np.zeros((1, 4), np.int32),
                                  max_new_tokens=2)
    with pytest.raises(NotImplementedError, match="served only"):
        m.apply(params, np.zeros((1, 4), np.int32),
                labels=np.zeros((1, 4), np.int32))


def test_counters_count_state_steps_resets_and_kv_rows(model):
    from deepspeed_tpu.monitor.metrics import MetricsRegistry

    m, params = model
    reg = MetricsRegistry().enable()
    serve = deepspeed_tpu.init_serving(m, config=ENGINE, params=params,
                                       mesh=m.mesh, registry=reg)
    p = np.random.default_rng(8).integers(0, 96, 10)
    serve.submit(p, max_new_tokens=21)
    for _ in range(2):
        serve.step()
    serve.kind.page_gauges(serve.pool)
    held = {k: reg.get("ds_serve_kv_pages_used_by_kind", {"kind": k}).value
            for k in ("window", "full", "index", "summary")}
    assert held["full"] == serve.pool.pages_used > 0
    assert held["window"] == held["index"] == held["summary"] == 0
    serve.run()
    snap = {k: v for k, v in reg.snapshot().items()
            if isinstance(v, (int, float))}
    # 20 decode steps from position 10, three linear layers
    assert snap["ds_serve_state_row_steps_total"] == 20 * 3
    # the CPU's reference form visits every slot: 3 slots x 3 layers x steps
    assert snap["ds_serve_state_row_steps_visited_total"] == 3 * 3 * 20
    assert snap["ds_serve_state_resets_total"] == 1
    # pos + 1 rows a step in each of the two full layers
    assert snap["ds_serve_full_kv_rows_read_total"] == \
        2 * (np.arange(10, 30) + 1).sum()
    offered = snap["ds_serve_moe_assignments_total"]
    assert offered == 20 * 8 * 5         # steps x choices x expert layers
    assert 0 < snap["ds_serve_moe_local_assignments_total"] < offered
    # another kind's series is registered here and does not move
    assert snap["ds_serve_mla_rows_written_total"] == 0
    serve.close()


def test_another_models_engine_registers_the_new_series_and_leaves_it_still():
    from deepspeed_tpu.models import causal_lm
    from deepspeed_tpu.monitor.metrics import MetricsRegistry

    mesh = build_mesh(devices=jax.devices()[:1])
    m = causal_lm("llama-tiny", mesh=mesh, num_layers=2, hidden_size=64,
                  intermediate_size=128, num_heads=4, num_kv_heads=2,
                  vocab_size=96, remat=False)
    reg = MetricsRegistry().enable()
    serve = deepspeed_tpu.init_serving(
        m, config=ENGINE, params=m.init(jax.random.PRNGKey(0)), mesh=mesh,
        registry=reg)
    serve.submit(np.arange(12), max_new_tokens=9)
    serve.run()
    assert reg.get("ds_serve_full_kv_rows_read_total").value == 0
    assert reg.get("ds_serve_state_bytes").value == 0
    serve.close()


# ------------------------------------------------------ (e) ModelConfig
def test_the_mix_is_accepted_and_is_the_sibling_forms():
    cfg = ModelConfig(**FIELDS)
    assert cfg.is_kda_mla and afmoe.form(cfg) is kda_mla
    assert kda_mla.full_layers(cfg) == [0, 4]
    assert kda_mla.kind_layers(cfg) == ([1, 2, 3], [])
    assert kda_mla.cache_key(cfg) == "k"
    # Trinity's pattern stays afmoe's own
    assert not ModelConfig(**dict(
        FIELDS, layer_types=("full_attention",) * 5, kda_num_heads=0,
        kda_head_dim=0, kda_conv_kernel=0, kda_gate_rank=0,
        kda_neg_eigval=False)).is_kda_mla


@pytest.mark.parametrize("fields,match", [
    (dict(layer_types=("sliding_attention",) + PATTERN[1:], sliding_window=8),
     "both of"),
    (dict(layer_types=("latent_attention",) + PATTERN[1:], mla_kv_rank=32,
          mla_nope_dim=16, mla_rot_dim=8, mla_v_dim=16), "both of"),
    (dict(layer_types=("linear_attention",) * 5), "attn_output_gate to a "
                                                  "model with full_attention"),
    (dict(mla_kv_rank=32), "the mla_\\* sizes and forms belong to latent"),
    (dict(mla_head_gate=True), "the mla_\\* sizes and forms belong to latent"),
    (dict(layer_types=("full_attention",) * 5), "belong to linear_attention"),
    (dict(layer_types=("full_attention",) * 5, kda_num_heads=0,
          kda_head_dim=0, kda_conv_kernel=0, kda_gate_rank=0),
     "belong to linear_attention"),
    (dict(kda_head_dim=0), "needs"),
    (dict(qk_norm_per_head=True), "plain pre-norm"),
    (dict(sandwich_norm=True), "plain pre-norm"),
    (dict(moe_drop_tokens=True), "dropless"),
    (dict(layer_types=None), "layer_types"),
], ids=["linear_beside_sliding", "latent_beside_per_head_full",
        "the_gate_without_full_layers", "a_latent_size", "a_latent_form",
        "kda_sizes_without_linear_layers", "neg_eigval_without_linear_layers",
        "a_missing_kda_size", "head_norms", "sandwich", "dropping_experts",
        "no_pattern"])
def test_config_says_what_it_cannot_build(fields, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig(**dict(FIELDS, **fields))


def test_the_published_widths_give_the_published_parameter_count():
    import json

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "solar-open2-L4-ep8.json")) as f:
        c = json.load(f)
    cfg = ModelConfig(**c["model_config"])
    shapes = jax.eval_shape(CausalLM(cfg).init, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == c["parameters"]
    assert shapes["kda"]["wq"].shape == (3, 4096, 8192)
    assert shapes["gqa"]["wk"].shape == (1, 4096, 1024)
    assert shapes["gqa"]["wg"].shape == (1, 4096, 8192)
    assert shapes["layers"]["mlp"]["w_up"].shape == (4, 40, 4096, 1280)
    # a token's pages: K and V of 8 heads of 128 in ONE layer, bf16
    assert cfg.cache_layers == 1
    assert 2 * cfg.cache_layers * cfg.num_kv_heads * cfg.head_dim * 2 == 4096
    assert kda_mla.slot_state_bytes(cfg, jnp.bfloat16) == \
        3 * (64 * 128 * 128 * 4 + 3 * 3 * 8192 * 2)
