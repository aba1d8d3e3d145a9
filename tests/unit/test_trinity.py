"""The AFMoE layer form (``models/afmoe.py``; Trinity) at a tiny size on the
CPU: hidden 64, 4 / 2 heads x 16, window 16, page 8, a router of 8 experts of
which 4 (or 1) are held, pattern ``[s | s, s, s, f]``.  The plain reference
(``benchmarks/reference/trinity.py``) against the three forwards; the share
of the experts against the whole; the two page budgets; the refusals."""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm.mesh import build_mesh
from deepspeed_tpu.models import CausalLM, ModelConfig, afmoe
from deepspeed_tpu.serving.paged_kv import PagedKVPool

REPO = os.path.join(os.path.dirname(__file__), os.pardir, os.pardir)
PATTERN = ("sliding_attention",) * 4 + ("full_attention",)
FIELDS = dict(
    vocab_size=96, hidden_size=64, intermediate_size=48, num_layers=5,
    num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=256,
    layer_types=PATTERN, sliding_window=16, num_dense_layers=1,
    dense_intermediate_size=128, qk_norm_per_head=True, attn_output_gate=True,
    sandwich_norm=True, embed_scale=8.0, moe_score_func="sigmoid",
    moe_route_scale=2.448, moe_select_bias=True, num_shared_experts=1,
    num_experts=4, moe_router_experts=8, moe_first_expert=0,
    num_experts_per_tok=4, moe_drop_tokens=False)
# the configuration file's keys the reference reads, at the same size
REF_CONFIG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "rms_norm_eps": 1e-5, "rope_theta": 10000, "sliding_window": 16,
    "layer_types": list(PATTERN), "num_dense_layers": 1,
    "num_experts_per_tok": 4, "route_scale": 2.448, "route_norm": True,
    "expert_parallel": {"first_expert": 0}}
ENGINE = dict(num_slots=3, prefill_chunk=8, max_prefill_chunks=2,
              decode_block_tokens=4, max_out_tokens=96, kv_pool_tokens=160,
              kv_page_tokens=8, dtype="float32")


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "_trinity_ref", os.path.join(REPO, "benchmarks", "reference",
                                     "trinity.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model():
    m = CausalLM(ModelConfig(**FIELDS),
                 build_mesh(devices=jax.devices()[:1]))
    return m, m.init(jax.random.PRNGKey(0))


def ref_logits(ref, params, seq, rows, config=REF_CONFIG, **kw):
    return np.asarray(ref.logits_rows(params, config, np.asarray(seq), rows,
                                      jax.devices()[0], **kw))


def test_reference_agrees_with_the_no_cache_forward(ref, model):
    m, params = model
    seq = np.random.default_rng(1).integers(0, 96, 45)
    got = np.asarray(jax.jit(m.apply)(params, seq[None]))[0]
    # the reference's OWN routing, stated: no row takes a near-tie's other side
    _, _, own = ref.hidden_states(params, REF_CONFIG, seq, jax.devices()[0],
                                  return_routing=True)
    want = ref_logits(ref, params, seq, list(range(45)), routing=list(own))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("pool_tokens,prompts,news", [
    # chunks of 8 over a window of 16: inside it, across it, with a pad
    # bucket (37 = 4 x 8 + 5), far past it; decode steps that cross it (14)
    (160, (5, 13, 37, 50, 14), (30, 10, 20, 12, 40)),
    # a full budget of 13 pages for three slots: the youngest is preempted
    # and resumes through both budgets
    (104, (30, 41, 22), (40, 30, 50)),
])
def test_served_tokens_are_the_references_argmax(ref, model, pool_tokens,
                                                 prompts, news):
    """Prefill-then-decode through the two budgets, in float32: every served
    token is the argmax of the reference's logits at its position."""
    m, params = model
    serve = deepspeed_tpu.init_serving(
        m, config=dict(ENGINE, kv_pool_tokens=pool_tokens), params=params,
        mesh=m.mesh)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 96, n) for n in prompts]
    reqs = [serve.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
    serve.run()
    serve.pool.check_no_leak()
    assert serve.pool.pages_used == 0
    if pool_tokens < 160:
        assert sum(r.preemptions for r in reqs) > 0
    for r, p in zip(reqs, prompts):
        seq = np.concatenate([p, r.output_tokens])
        want = ref_logits(ref, params, seq,
                          list(range(len(p) - 1, len(seq) - 1)))
        assert list(want.argmax(-1)) == list(r.output_tokens)
    serve.close()


@pytest.mark.parametrize("places", [1, 2, 4])
def test_chunks_of_one_iteration_cross_the_ring(ref, model, places):
    """``max_prefill_chunks`` places an iteration: a prompt that prefills
    alone takes them all, so the chunk that wraps the ring (chunks of 8, a
    window of 16) reads what the chunk program just before it wrote, with no
    decode block between; then three requests share the places.  The same
    argmax of the reference, whatever the places."""
    m, params = model
    serve = deepspeed_tpu.init_serving(
        m, config=dict(ENGINE, max_prefill_chunks=places), params=params,
        mesh=m.mesh)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, 96, n) for n in (50, 37, 13, 29)]
    reqs = [serve.submit(prompts[0], max_new_tokens=20)]
    serve.run()
    reqs += [serve.submit(p, max_new_tokens=12) for p in prompts[1:]]
    serve.run()
    serve.pool.check_no_leak()
    for r, p in zip(reqs, prompts):
        assert not r.preemptions
        seq = np.concatenate([p, r.output_tokens])
        want = ref_logits(ref, params, seq,
                          list(range(len(p) - 1, len(seq) - 1)))
        assert list(want.argmax(-1)) == list(r.output_tokens)
    serve.close()


def test_bf16_serving_stays_within_the_drivers_bound(ref, model):
    """What the benchmark's ``verify`` checks, at the serving dtype."""
    m, params = model
    # the weights both sides read: rounded to bf16 once
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    serve = deepspeed_tpu.init_serving(
        m, config=dict(ENGINE, dtype="bfloat16"), params=params, mesh=m.mesh)
    p = np.random.default_rng(3).integers(0, 96, 29)
    r = serve.submit(p, max_new_tokens=24)
    serve.run()
    seq = np.concatenate([p, r.output_tokens])
    want = ref_logits(ref, params, seq, list(range(28, len(seq) - 1)))
    best = want.max(-1)
    got = want[np.arange(24), r.output_tokens]
    step = 2.0 ** (np.floor(np.log2(np.abs(best))) - 7)
    assert ((best - got) / step).max() <= 4.0
    serve.close()


def test_sum_of_the_eight_shares_is_the_whole_layer(ref, model):
    """The ranks' routed parts of one expert layer + the shared expert once
    = the uncut layer (the reference with every expert held)."""
    cfg = ModelConfig(**dict(FIELDS, num_experts=1, moe_router_experts=8))
    whole = afmoe.init_params(
        ModelConfig(**dict(FIELDS, num_experts=8, moe_router_experts=8)),
        jax.random.PRNGKey(4))
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 23, 64))
    lp, _ = afmoe.layer_params(
        ModelConfig(**dict(FIELDS, num_experts=8)), whole, 2)
    shared = afmoe.glu_mlp(h[0], lp["mlp"]["shared"])
    total = -7.0 * shared               # each share adds the shared expert
    for r in range(8):
        mine = {k: whole["layers"]["mlp"][k][:, r:r + 1]
                for k in ("w_up", "w_gate", "w_down")}
        total = total + afmoe.mlp(
            dataclasses.replace(cfg, moe_first_expert=r), lp, h, mine, 1)[0]
    w = ref.layer_weights(whole, 2, 1, jax.devices()[0])
    with jax.default_matmul_precision("highest"):
        _, local, fullest, _ = ref.route(
            h[0], w, None, 23, top_k=4, first=0, route_scale=2.448,
            route_norm=True)
        want = ref.expert_close(jnp.zeros_like(h[0]), h[0], w, local,
                                eps=1e-5, cap=32, variant=("no_post_norm",))
    assert int(fullest) > 0
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-4)


def test_the_bias_changes_the_selection_and_not_the_weights(model):
    m, params = model
    cfg = m.config
    h = jax.random.normal(jax.random.PRNGKey(6), (11, 64))
    gate_w = params["layers"]["mlp"]["gate_w"][0]
    zero = jnp.zeros((8,))
    w0, i0, _ = afmoe.route(cfg, h, gate_w, zero)
    push = zero.at[5].set(10.0).at[2].set(-10.0)
    w1, i1, _ = afmoe.route(cfg, h, gate_w, push)
    assert (np.asarray(i1) == 5).any(-1).all() and not (np.asarray(i1) == 2).any()
    assert not (np.asarray(i0) == 5).any(-1).all()
    # the weights are the sigmoid scores of the chosen, normalised, scaled
    s = jax.nn.sigmoid(h @ gate_w)
    picked = np.take_along_axis(np.asarray(s), np.asarray(i1), -1)
    np.testing.assert_allclose(
        w1, picked / picked.sum(-1, keepdims=True) * 2.448, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w1).sum(-1), 2.448, rtol=1e-5)


@pytest.mark.parametrize("sliding", [True, False])
def test_only_a_sliding_layer_sees_positions(model, sliding):
    """No position encoding in a global layer: its q and k do not change
    under a shift of positions; a sliding layer's do."""
    m, params = model
    lp, _ = afmoe.layer_params(m.config, params, 2)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 9, 64))
    at = lambda p0: afmoe._project(
        m.config, lp, x, *afmoe.angles(m.config, p0 + jnp.arange(9)),
        sliding)[:2]
    (q0, k0), (q1, k1) = at(0), at(7)
    same = np.allclose(q0, q1) and np.allclose(k0, k1)
    assert same != sliding


def test_ring_positions_name_what_each_row_holds():
    # before a chunk that starts at 21 a ring of 8 holds 13 .. 20
    assert list(afmoe.ring_positions(jnp.asarray(21), 8)) == [
        16, 17, 18, 19, 20, 13, 14, 15]
    assert list(afmoe.ring_positions(jnp.asarray(3), 8)) == [
        0, 1, 2, -5, -4, -3, -2, -1]


# ---------------------------------------------------------------- the pool
def two_budgets(**kw):
    return PagedKVPool(**{**dict(num_slots=3, max_out_tokens=64,
                                 page_tokens=8, pool_tokens=96,
                                 ring_tokens=16), **kw})


@pytest.mark.parametrize("tokens,window,full", [
    (0, 0, 0), (1, 1, 1), (8, 1, 1), (9, 2, 2), (16, 2, 2), (17, 2, 3),
    (48, 2, 6)])
def test_pages_for_answers_per_kind(tokens, window, full):
    pool = two_budgets()
    assert pool.pages_for(tokens, "window") == window
    assert pool.pages_for(tokens, "full") == full
    assert pool.pages_for(tokens) == window + full


def test_a_request_of_three_windows_holds_one_ring():
    pool = two_budgets()
    assert pool.ensure(0, 48)
    assert pool.pages_used_by_kind() == {"window": 2, "full": 6}
    assert pool.slot_pages_used(0) == 8
    # window columns first, ids of the window budget; then the full ones
    assert list(pool.page_table[0]) == [1, 2, 1, 2, 3, 4, 5, 6, 0, 0]
    pool.check_no_leak()


@pytest.mark.parametrize("dry", ["window", "full"])
def test_either_budget_dry_grants_nothing(dry):
    pool = two_budgets(window_pool_tokens=24) if dry == "window" \
        else two_budgets(pool_tokens=64)
    assert pool.ensure(0, 40)            # 2 ring pages + 5 full pages
    before = (pool.pages_used_by_kind(), pool.pages_free)
    assert not pool.ensure(1, 40)        # 1 ring page left | 3 full pages
    assert (pool.pages_used_by_kind(), pool.pages_free) == before
    assert pool.slot_pages_used(1) == 0 and not pool.page_table[1].any()
    pool.check_no_leak()
    assert pool.release(0) == 7
    assert pool.ensure(1, 40)
    pool.check_no_leak()
    pool.release(1)
    assert pool.pages_used == 0
    pool.check_no_leak()


def test_check_no_leak_sees_a_lost_window_page():
    pool = two_budgets()
    pool.ensure(0, 20)
    pool._free_win.pop()
    with pytest.raises(AssertionError, match="window"):
        pool.check_no_leak()


def test_one_budget_pools_are_as_they_were():
    pool = PagedKVPool(2, 64, page_tokens=8)
    assert pool.ensure(0, 20) and pool.num_window_pages == 0
    assert pool.pages_used_by_kind() == {"window": 3, "summary": 0}
    pool.check_no_leak()


# ------------------------------------------------------------ the refusals
@pytest.mark.parametrize("kw,match", [
    (dict(config={}, role="prefill"), "handoff"),
    (dict(config=dict(kv_host_tier_pages=4)), "host_tier"),
    (dict(config=dict(quantize_kv_cache=True)), "quantize_kv_cache"),
    (dict(config=dict(use_fused_decode=False)), "use_fused_decode=False"),
])
def test_what_the_model_is_not_served_with_is_refused_by_name(model, kw,
                                                              match):
    from deepspeed_tpu.serving.engine import ServingEngine

    m, params = model
    kw = dict(kw, config=dict(ENGINE, **kw["config"]))
    with pytest.raises(NotImplementedError, match=match):
        ServingEngine(m, params=params, mesh=m.mesh, **kw)


def test_prefill_chunk_longer_than_the_window_is_refused(model):
    from deepspeed_tpu.serving.engine import ServingEngine

    m, params = model
    with pytest.raises(ValueError, match="sliding_window"):
        ServingEngine(m, dict(ENGINE, prefill_chunk=32), params=params,
                      mesh=m.mesh)


def test_prefix_cache_is_off_and_generate_and_training_are_refused(model):
    m, params = model
    serve = deepspeed_tpu.init_serving(
        m, config=dict(ENGINE, prefix_caching=True), params=params,
        mesh=m.mesh)
    assert serve.prefix_cache is None
    with pytest.raises(NotImplementedError, match="prefill_only"):
        serve.submit([1, 2, 3], prefill_only=True)
    with pytest.raises(NotImplementedError, match="init_serving"):
        serve.engine.generate(np.zeros((1, 4), np.int32), max_new_tokens=2)
    with pytest.raises(NotImplementedError, match="served only"):
        m.apply(params, np.zeros((1, 4), np.int32),
                labels=np.zeros((1, 4), np.int32))
    serve.close()


@pytest.mark.parametrize("fields,match", [
    (dict(layer_types=None), "layer_types"),
    (dict(layer_types=PATTERN[:3]), "one of"),
    (dict(moe_first_expert=6), "do not lie"),
    (dict(sliding_window=0), "sliding_window"),
    (dict(moe_drop_tokens=True), "dropless"),
    (dict(moe_score_func="tanh"), "moe_score_func"),
])
def test_config_says_what_it_cannot_build(fields, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig(**dict(FIELDS, **fields))


# ------------------------------------------------------------ the counters
def test_counters_count_attended_rows_pages_and_local_choices(model):
    from deepspeed_tpu.monitor.metrics import MetricsRegistry

    m, params = model
    reg = MetricsRegistry().enable()
    serve = deepspeed_tpu.init_serving(m, config=ENGINE, params=params,
                                       mesh=m.mesh, registry=reg)
    p = np.random.default_rng(8).integers(0, 96, 10)
    serve.submit(p, max_new_tokens=21)
    serve.run()
    snap = {k: v for k, v in reg.snapshot().items()
            if isinstance(v, (int, float))}
    pos = np.arange(10, 30) + 1          # 20 decode steps from position 10
    assert snap["ds_serve_attn_full_rows_total"] == int(pos.sum())
    assert snap["ds_serve_attn_window_rows_total"] == int(
        np.minimum(pos, 16).sum())
    held, one = (snap["ds_serve_kv_page_steps_total"],
                 snap["ds_serve_kv_page_steps_one_budget_total"])
    assert 0 < held < one
    offered = snap["ds_serve_moe_assignments_total"]
    assert offered == 20 * 4 * 4         # steps x choices x expert layers
    assert 0 < snap["ds_serve_moe_local_assignments_total"] < offered
    assert snap["ds_serve_moe_expert_slots_total"] == 4 * 4 * 20
    serve.close()


@pytest.mark.parametrize("routing", ["the_routers", "every_choice_held"])
def test_chunk_programs_serve_the_same_tokens_under_a_tile_of_pad(
        model, monkeypatch, routing):
    """A chunk program's expert blocks put a tile of pad rows behind their
    sorted rows (``sharded_moe.ROW_TILE``, cut to 16 here so that a bucket
    of 8 tokens x 4 choices is two tiles and gets a third): the served
    tokens are those of the N*k rows alone, the parent's form."""
    from deepspeed_tpu.moe import sharded_moe

    m, params = model
    if routing == "every_choice_held":
        monkeypatch.setattr(afmoe, "held", lambda cfg, weight, idx: (
            weight, idx % cfg.num_experts))
    prompts = [np.random.default_rng(s).integers(0, 96, n)
               for s, n in ((8, 10), (9, 37))]

    def served(tile):
        monkeypatch.setattr(sharded_moe, "ROW_TILE", tile)
        serve = deepspeed_tpu.init_serving(m, config=ENGINE, params=params,
                                           mesh=m.mesh)
        for p in prompts:
            serve.submit(p, max_new_tokens=6)
        tokens = [r.output_tokens for r in sorted(
            serve.run(), key=lambda r: r.request_id)]
        serve.close()
        return tokens

    assert served(16) == served(1 << 30)


# ------------------------------------------- the kernels, interpret mode
@pytest.mark.parametrize("live", [
    [True, True, True], [True, False, True], [False, True, False],
    [False, False, False]], ids=["all", "ring_and_short", "parked_between",
                                 "none"])
def test_fused_layers_through_the_kernels_match_their_references(live):
    """The decode step over two page budgets with every Pallas kernel in
    interpret mode against the same step on the kernels' jnp references: a
    GQA group of 3 (not a multiple of the 8 sublanes), pages of 128 (the
    lane tile), a ring that has wrapped for two rows and not for the third.
    The attention kernels visit the ``live`` rows only (a wrapped ring row
    beside a row of one full page; a deeper row that does not decode between
    them): those equal the references, the others stay finite."""
    cfg = ModelConfig(**dict(
        FIELDS, hidden_size=128, num_heads=6, num_kv_heads=2, head_dim=128,
        num_layers=3, layer_types=("sliding_attention",) * 2
        + ("full_attention",), sliding_window=256, intermediate_size=128,
        dense_intermediate_size=256))
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                          afmoe.init_params(cfg, jax.random.PRNGKey(9)))
    dparams = afmoe.inject(cfg, params)
    depths = (300, 480, 40)
    pool = PagedKVPool(3, 512, page_tokens=128, ring_tokens=256)
    pos = jnp.asarray(depths, jnp.int32)
    for b, p in enumerate(depths):
        assert pool.ensure(b, p + 1)
    fill = lambda key, L, P: jax.random.normal(
        key, (L, P, 2, 128, 128), jnp.bfloat16)
    ks = jax.random.split(jax.random.PRNGKey(10), 5)
    cache = {"k_win": fill(ks[0], 2, pool.num_window_pages),
             "v_win": fill(ks[1], 2, pool.num_window_pages),
             "k_full": fill(ks[2], 1, pool.num_pages),
             "v_full": fill(ks[3], 1, pool.num_pages)}
    x = jax.random.normal(ks[4], (3, 128), jnp.bfloat16)
    table = jnp.asarray(pool.page_table)
    run = lambda impl: afmoe.fused_layers(cfg, dparams, x, cache, pos, table,
                                          moe_live=jnp.asarray(live),
                                          impl=impl)
    (xa, ca, sa), (xb, cb, sb) = run("interpret"), run("xla")
    xa, xb = xa.astype(np.float32), xb.astype(np.float32)
    np.testing.assert_allclose(xa[np.asarray(live)], xb[np.asarray(live)],
                               rtol=2e-2, atol=2e-2)
    assert np.isfinite(xa).all()
    # what a row that does not decode appends past the first layer is
    # computed from its unvisited attention output: junk in both, not equal
    wp = pool.window_pages
    parked = np.asarray(table)[~np.asarray(live)]
    for k in ca:
        keep = np.setdiff1d(np.arange(ca[k].shape[1]), parked[:, :wp]
                            if k.endswith("win") else parked[:, wp:])
        np.testing.assert_array_equal(np.asarray(ca[k], np.float32)[:, keep],
                                      np.asarray(cb[k], np.float32)[:, keep])
    assert all((np.asarray(a) == np.asarray(b)).all() for a, b in zip(sa, sb))
