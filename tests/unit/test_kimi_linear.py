"""Linear-attention and latent-attention layers (``models/kda_mla.py``;
Kimi-Linear) at a tiny size on the CPU: hidden 64, four KDA heads of 16 with
a convolution of 4 taps, a latent of 32 + 8 under four query heads of 16 + 8,
page 8, chunks of 16, a router of 16 experts of which 4 (or 2) are held,
top-8, pattern ``[k | k, k, m, k]``.  The plain reference
(``benchmarks/reference/kimi_linear.py``) against the three forwards; the
chunkwise recurrence against the one-step one; the absorbed latent attention
against the decompressed; the slot state's reset, its stillness under parked
rows and pad rows; the share of the experts against the whole; the
refusals."""

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
from tests.unit._serving import as_found, with_noise

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
PATTERN = ("linear_attention",) * 3 + ("latent_attention",
                                        "linear_attention")
FIELDS = dict(
    vocab_size=96, hidden_size=64, intermediate_size=48, num_layers=5,
    num_heads=4, max_seq_len=256, layer_types=PATTERN, num_dense_layers=1,
    dense_intermediate_size=128, kda_num_heads=4, kda_head_dim=16,
    kda_conv_kernel=4, kda_gate_rank=16, mla_kv_rank=32, mla_nope_dim=16,
    mla_rot_dim=8, mla_v_dim=16, moe_score_func="sigmoid",
    moe_route_scale=2.446, moe_select_bias=True, num_shared_experts=1,
    num_experts=4, moe_router_experts=16, moe_first_expert=0,
    num_experts_per_tok=8, moe_drop_tokens=False)
# the configuration file's keys the reference reads, at the same size
REF_CONFIG = {
    "num_hidden_layers": 5, "first_k_dense_replace": 1,
    "linear_attn_config": {"kda_layers": [1, 2, 3, 5],
                           "full_attn_layers": [4], "num_heads": 4,
                           "head_dim": 16, "short_conv_kernel_size": 4},
    "num_attention_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_lora_rank": 32, "rms_norm_eps": 1e-5,
    "rope_theta": 10000, "num_experts_per_token": 8,
    "routed_scaling_factor": 2.446, "moe_renormalize": True,
    "expert_parallel": {"first_expert": 0}}
ENGINE = dict(num_slots=3, prefill_chunk=16, max_prefill_chunks=2,
              decode_block_tokens=4, max_out_tokens=96, kv_page_tokens=8,
              dtype="float32")


@pytest.fixture(scope="module")
def ref():
    if REPO not in sys.path:          # the reference imports its sibling
        sys.path.insert(0, REPO)
    spec = importlib.util.spec_from_file_location(
        "_kimi_ref", os.path.join(REPO, "benchmarks", "reference",
                                  "kimi_linear.py"))
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
def engine(model):
    """One engine at ``ENGINE`` for the cases that differ in their requests
    alone."""
    serve = serve_of(model)
    yield serve
    serve.close()


@pytest.fixture
def shared(engine):
    with as_found(engine):
        yield engine


def test_reference_agrees_with_the_no_cache_forward(ref, model):
    m, params = model
    seq = np.random.default_rng(1).integers(0, 96, 83)
    got = np.asarray(jax.jit(m.apply)(params, seq[None]))[0]
    # the reference's OWN routing, stated: no row takes a near-tie's other side
    _, _, own = ref.hidden_states(params, REF_CONFIG, seq, jax.devices()[0],
                                  return_routing=True)
    want = ref_logits(ref, params, seq, list(range(83)), routing=list(own))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("prompt", [16, 15, 17, 37, 48, 5],
                         ids=["on_a_chunk", "one_short", "one_past",
                              "padded_bucket", "three_chunks", "tiny"])
def test_served_tokens_are_the_references_argmax(ref, model, shared,
                                                 prompt):
    """Prefill in chunks of 16 (the state carried from chunk to chunk, pad
    rows of the last bucket idle), then decode through the slot state and the
    latent pages, in float32: every served token is the argmax of the
    reference's full forward at its position."""
    serve = shared
    p = np.random.default_rng(prompt).integers(0, 96, prompt)
    r = serve.submit(p, max_new_tokens=21)
    serve.run()
    serve.pool.check_no_leak()
    assert serve.pool.pages_used == 0
    seq = np.concatenate([p, r.output_tokens])
    want = ref_logits(ref, model[1], seq, list(range(prompt - 1, len(seq) - 1)))
    assert list(want.argmax(-1)) == list(r.output_tokens)


@pytest.mark.parametrize("places", [1, 2, 4])
def test_chunks_of_one_iteration_hand_the_state_on(ref, model, places):
    """``max_prefill_chunks`` places an iteration: a prompt that prefills
    alone takes them all, so a chunk program reads the KDA state and the
    convolution tails the chunk program just before it left, with no decode
    block between (five chunks of 16: all of one iteration's under four
    places but the last); then three requests share the places.  The same
    argmax of the reference, whatever the places."""
    serve = serve_of(model, max_prefill_chunks=places)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 96, n) for n in (70, 37, 16, 45)]
    reqs = [serve.submit(prompts[0], max_new_tokens=21)]
    serve.run()
    reqs += [serve.submit(p, max_new_tokens=12) for p in prompts[1:]]
    serve.run()
    serve.pool.check_no_leak()
    for r, p in zip(reqs, prompts):
        assert not r.preemptions
        seq = np.concatenate([p, r.output_tokens])
        want = ref_logits(ref, model[1], seq,
                          list(range(len(p) - 1, len(seq) - 1)))
        assert list(want.argmax(-1)) == list(r.output_tokens)
    serve.close()


def test_preempt_and_resume_are_token_identical(model, shared):
    """A pool of nine pages for three slots: the youngest is preempted,
    re-prefills prompt + outputs onto a zeroed state, and every request
    still gets the tokens an unpressed engine gives it."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 96, n) for n in (22, 30, 17)]
    news = (30, 24, 36)
    easy, tight = shared, serve_of(model, kv_pool_tokens=96)
    want = [easy.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
    got = [tight.submit(p, max_new_tokens=n) for p, n in zip(prompts, news)]
    easy.run()
    tight.run()
    tight.pool.check_no_leak()
    assert sum(r.preemptions for r in got) > 0
    for w, g in zip(want, got):
        assert list(g.output_tokens) == list(w.output_tokens)
    tight.close()


def test_bf16_serving_stays_within_the_drivers_bound(ref, model):
    """What the benchmark's ``verify`` checks, at the serving dtype, with the
    state float32 whatever the stream's."""
    m, params = model
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    serve = deepspeed_tpu.init_serving(
        m, config=dict(ENGINE, dtype="bfloat16"), params=params, mesh=m.mesh)
    assert serve._cache["state"].dtype == jnp.float32
    assert serve._cache["latent"].dtype == jnp.bfloat16
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


# ------------------------------------------------------------ the pieces
def _recurrence_inputs(s, H, d, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n = lambda k, sh: jax.random.normal(k, sh)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    # one head whose channels decay from 0.999 to 0.1 a step
    g = jnp.broadcast_to(jnp.log(jnp.geomspace(0.999, 0.1, d)), (s, H, d)) \
        * (1 + 0.1 * jax.random.uniform(ks[3], (s, H, d)))
    return (n(ks[5], (H, d, d)), unit(n(ks[0], (s, H, d))),
            unit(n(ks[1], (s, H, d))), n(ks[2], (s, H, d)), g,
            jax.nn.sigmoid(n(ks[4], (s, H))))


@pytest.mark.parametrize("s", [8, 64, 192])
def test_chunkwise_recurrence_is_the_one_step_recurrence(s):
    """Decays from 0.999 to 0.1 in one head (e^-147 over a sub-chunk of 64:
    a quotient of decays would overflow float32), a state to start from."""
    S0, q, k, v, g, beta = _recurrence_inputs(s, 2, 16)

    def one(S, x):
        o, S = kda_mla.kda_step(S, *x)
        return S, o

    with jax.default_matmul_precision("highest"):
        S1, o1 = jax.lax.scan(one, S0, (q, k, v, g, beta))
        S2, o2 = kda_mla.kda_chunk(S0, q, k, v, g, beta)
    assert bool(jnp.isfinite(o2).all())
    np.testing.assert_allclose(o2, o1, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(S2, S1, rtol=1e-4, atol=1e-5)


def test_pad_rows_leave_state_and_tail_where_the_last_real_row_left_them():
    """``beta = 0`` and ``g = 0`` past row 5 of a sub-chunk, and a tail cut
    at ``valid_len``: what 5 rows alone give."""
    S0, q, k, v, g, beta = _recurrence_inputs(8, 2, 16, seed=1)
    real = jnp.arange(8) < 5
    with jax.default_matmul_precision("highest"):
        S_pad, _ = kda_mla.kda_chunk(
            S0, q, k, v, jnp.where(real[:, None, None], g, 0.0),
            jnp.where(real[:, None], beta, 0.0))
        S_five, _ = kda_mla.kda_chunk(S0, *(t[:5] for t in (q, k, v, g, beta)))
    np.testing.assert_allclose(S_pad, S_five, rtol=1e-5, atol=1e-6)
    u = jax.random.normal(jax.random.PRNGKey(2), (1, 8, 6))
    tail = jax.random.normal(jax.random.PRNGKey(3), (1, 3, 6))
    w = jax.random.normal(jax.random.PRNGKey(4), (6, 4))
    c8, t8 = kda_mla.short_conv(u, tail, w, valid_len=5)
    c5, t5 = kda_mla.short_conv(u[:, :5], tail, w)
    np.testing.assert_array_equal(t8, t5)
    np.testing.assert_array_equal(t8, u[:, 2:5])
    np.testing.assert_allclose(c8[:, :5], c5, rtol=1e-6)
    # fewer real rows than taps: the old tail's end stays in front
    _, t1 = kda_mla.short_conv(u, tail, w, valid_len=1)
    np.testing.assert_array_equal(
        t1, jnp.concatenate([tail[:, 1:], u[:, :1]], axis=1))


def test_absorbed_latent_attention_is_the_decompressed(model):
    """A decode step's ``softmax(q' . rows) rows`` through ``Wkvb_v`` against
    per-head keys and values decompressed from the same rows."""
    m, params = model
    cfg = m.config
    a = jax.tree.map(lambda t: t[0], params["mla"])
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    h = jax.random.normal(ks[0], (3, 64))
    hist = jax.random.normal(ks[1], (3, 40, 64))
    kd = cfg.mla_kind("latent_attention")     # the sizes are the KIND's
    q, row, _ = kda_mla.mla_project(kd, a, h, None)              # [3, H, 24]
    _, rows, _ = kda_mla.mla_project(kd, a, hist, None)         # [3, 40, W]
    assert rows.shape[-1] == kda_mla.row_width(cfg) == 128
    assert not np.asarray(rows[..., 40:]).any()                # the padding
    rows = jnp.concatenate([rows, row[:, None]], axis=1)       # own row last
    with jax.default_matmul_precision("highest"):
        k, v = kda_mla.mla_decompress(rows, *kda_mla._wkvb(kd, a),
                                      cfg.mla_rot_dim)         # [3, 41, H, .]
        s = jnp.einsum("bhd,bjhd->bhj", q, k) * 24 ** -0.5
        want = jnp.einsum("bhj,bjhv->bhv", jax.nn.softmax(s, -1), v)
        qa = kda_mla.mla_absorb(kd, a, q)
        p = jax.nn.softmax(jnp.einsum("bhw,bjw->bhj", qa, rows)
                           * 24 ** -0.5, -1)
        got = kda_mla.mla_unabsorb(kd, a, jnp.einsum("bhj,bjw->bhw", p,
                                                     rows))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_sum_of_the_eight_shares_is_the_whole_layer(ref, model):
    """The ranks' routed parts of one expert layer + the shared expert once
    = the uncut layer at top-8 of 16 (the reference with every expert
    held)."""
    both = dict(FIELDS, moe_router_experts=16)
    cfg = ModelConfig(**dict(both, num_experts=2))
    whole = kda_mla.init_params(ModelConfig(**dict(both, num_experts=16)),
                                jax.random.PRNGKey(4))
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 23, 64))
    lp, _ = kda_mla.layer_params(ModelConfig(**dict(both, num_experts=16)),
                                 whole, 2)
    shared = afmoe.glu_mlp(h[0], lp["mlp"]["shared"])
    total = -7.0 * shared               # each share adds the shared expert
    for r in range(8):
        mine = {k: whole["layers"]["mlp"][k][:, 2 * r:2 * r + 2]
                for k in ("w_up", "w_gate", "w_down")}
        total = total + afmoe.mlp(
            dataclasses.replace(cfg, moe_first_expert=2 * r), lp, h, mine,
            1)[0]
    w = ref.layer_weights(whole, REF_CONFIG, 2, jax.devices()[0])
    with jax.default_matmul_precision("highest"):
        _, local, fullest, _ = ref.route(
            h[0], w, None, 23, top_k=8, first=0, route_scale=2.446,
            route_norm=True)
        want = ref.expert_close(jnp.zeros_like(h[0]), h[0], w, local,
                                eps=1e-5, cap=32, variant=("no_post_norm",))
    assert int(fullest) > 0
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-4)


# ------------------------------------------- the kernels, interpret mode
@pytest.mark.parametrize("live", [
    [True, True, True], [True, False, True], [False, False, False]],
    ids=["all", "parked_between", "none"])
def test_fused_layers_through_the_kernels_match_their_references(live):
    """The decode step with every Pallas kernel in interpret mode against the
    same step on the kernels' jnp references, at tile widths (16 KDA heads of
    128, a latent row of 128 + 64 padded to 256, pages of 128): live rows
    agree; a parked row's state, tail and latent pages are bit-identical to
    what they were (the state kernel and the latent kernel visit live rows
    only)."""
    cfg = ModelConfig(**dict(
        FIELDS, hidden_size=128, num_layers=2, num_dense_layers=1,
        layer_types=("linear_attention", "latent_attention"),
        kda_num_heads=16, kda_head_dim=128, kda_gate_rank=32,
        mla_kv_rank=128, mla_nope_dim=32, mla_rot_dim=64, mla_v_dim=32,
        intermediate_size=128, dense_intermediate_size=256))
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                          kda_mla.init_params(cfg, jax.random.PRNGKey(9)))
    dparams = kda_mla.inject(cfg, params)
    depths = (300, 130, 40)
    pos = jnp.asarray(depths, jnp.int32)
    table = jnp.asarray([[1, 2, 3], [4, 5, 0], [6, 0, 0]], jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(10), 4)
    state, tail = kda_mla.state_shapes(cfg, 3)
    cache = {"latent": jax.random.normal(ks[0], (1, 7, 1, 128, 256),
                                         jnp.bfloat16),
             "state": jax.random.normal(ks[1], state),
             "tail": jax.random.normal(ks[2], tail, jnp.bfloat16)}
    x = jax.random.normal(ks[3], (3, 128), jnp.bfloat16)
    mask = jnp.asarray(live)
    step = lambda impl: kda_mla.fused_layers(
        cfg, dparams, x, cache, pos, table, moe_live=mask, impl=impl)
    (x_ref, c_ref, s_ref), (x_k, c_k, s_k) = step("xla"), step("interpret")
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
    # rows visited: the kernel's grid is the live rows, the reference
    # updates every slot and keeps the old state where a row is parked
    assert list(s_k[4]) == [len(rows)] * 2
    assert list(s_ref[4]) == [len(rows), 3]


@pytest.mark.parametrize("start,valid", [(0, 16), (48, 11)],
                         ids=["at_zero", "inside_a_strip"])
def test_cached_layers_through_the_chunk_kernel_match_the_jnp_path(start,
                                                                   valid):
    """A chunk program's layer stack (a linear layer, then a latent one,
    unrotated) with ``mla_chunk_attention`` in interpret mode against the
    same stack on ``afmoe.attend(expand=)``, at tile widths: the stream, the
    rows written and the carried state agree."""
    cfg = ModelConfig(**dict(
        FIELDS, hidden_size=128, num_layers=2, num_dense_layers=1,
        layer_types=("linear_attention", "latent_attention"),
        mla_kv_rank=128, mla_nope_dim=128, mla_rot_dim=64, mla_v_dim=128,
        intermediate_size=128, dense_intermediate_size=256))
    params = kda_mla.init_params(cfg, jax.random.PRNGKey(9))
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    state, tail = kda_mla.state_shapes(cfg, 1)
    cache = {"latent": jax.random.normal(ks[0], (1, 1, 1, 256, 256)),
             "state": jax.random.normal(ks[1], state),
             "tail": jax.random.normal(ks[2], tail)}
    x = jax.random.normal(ks[3], (1, 16, 128))
    run = lambda impl: kda_mla.cached_layers(cfg, params, x, cache, start,
                                             valid, impl=impl)
    (x_ref, c_ref), (x_k, c_k) = run("xla"), run("interpret")
    np.testing.assert_allclose(x_k, x_ref, rtol=2e-4, atol=2e-4)
    for key in ("latent", "state", "tail"):
        np.testing.assert_allclose(c_k[key], c_ref[key], rtol=2e-4,
                                   atol=2e-4)


def test_parked_rows_keep_their_state_across_a_decode_block(model):
    """Two requests decoding, one slot empty: the empty slot's state and
    tail come out of the engine's decode blocks bit for bit as they went
    in."""
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


# ------------------------------------------------------------ the refusals
@pytest.mark.parametrize("kw,match", [
    (dict(config={}, role="prefill"), "handoff"),
    (dict(config=dict(kv_host_tier_pages=4)), "host_tier"),
    (dict(config=dict(quantize_kv_cache=True)), "models/decoding.py"),
    (dict(config=dict(use_fused_decode=False)), "kda_mla.py:fused_layers"),
])
def test_what_the_model_is_not_served_with_is_refused_by_name(model, kw,
                                                              match):
    from deepspeed_tpu.serving.engine import ServingEngine

    m, params = model
    kw = dict(kw, config=dict(ENGINE, **kw["config"]))
    with pytest.raises(NotImplementedError, match=match):
        ServingEngine(m, params=params, mesh=m.mesh, **kw)


def test_prefix_cache_is_off_and_generate_and_training_are_refused(model):
    m, params = model
    serve = serve_of(model, prefix_caching=True)
    assert serve.prefix_cache is None
    with pytest.raises(NotImplementedError, match="prefill_only"):
        serve.submit([1, 2, 3], prefill_only=True)
    with pytest.raises(NotImplementedError, match="init_serving"):
        serve.engine.generate(np.zeros((1, 4), np.int32), max_new_tokens=2)
    with pytest.raises(NotImplementedError, match="served only"):
        m.apply(params, np.zeros((1, 4), np.int32),
                labels=np.zeros((1, 4), np.int32))
    serve.close()


@pytest.mark.parametrize("axis", ["tp", "ep", "sp", "pp"])
def test_a_split_mesh_is_refused_by_name(axis):
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(2), (axis,))
    with pytest.raises(NotImplementedError, match="models/afmoe.py"):
        afmoe.refuse_parallel(ModelConfig(**FIELDS), mesh, "CausalLM.apply")


@pytest.mark.parametrize("fields,match", [
    (dict(layer_types=PATTERN[:3]), "one of"),
    (dict(layer_types=("sliding_attention",) + PATTERN[1:]), "kda_mla.py"),
    (dict(kda_head_dim=0), "needs"),
    (dict(mla_kv_rank=0), "needs"),
    (dict(sandwich_norm=True), "plain pre-norm"),
    (dict(embed_scale=2.0), "plain pre-norm"),
    (dict(moe_drop_tokens=True), "dropless"),
    (dict(layer_types=None), "layer_types"),
])
def test_config_says_what_it_cannot_build(fields, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig(**dict(FIELDS, **fields))


def test_the_published_widths_give_the_published_parameter_count():
    import json

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "kimi-linear-L5-ep8.json")) as f:
        c = json.load(f)
    shapes = jax.eval_shape(CausalLM(ModelConfig(**c["model_config"])).init,
                            jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == c["parameters"] \
        == 1_281_928_064
    assert shapes["kda"]["wq"].shape == (4, 2304, 4096)
    assert shapes["mla"]["wkva"].shape == (1, 2304, 576)


# ------------------------------------------------------------ the counters
def test_counters_count_state_steps_and_resets(model):
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
    # 20 decode steps from position 10
    assert snap["ds_serve_state_row_steps_total"] == 20 * 4
    # the CPU's reference form visits every slot: 3 slots x 4 layers x steps
    assert snap["ds_serve_state_row_steps_visited_total"] == 3 * 4 * 20
    assert snap["ds_serve_state_resets_total"] == 1
    assert snap["ds_serve_state_bytes"] == serve.pool.state_bytes == 3 * (
        4 * (4 * 16 * 16 * 4 + 3 * 192 * 4))
    assert snap["ds_serve_kv_pages_used"] == 0           # all released
    offered = snap["ds_serve_moe_assignments_total"]
    assert offered == 20 * 8 * 4         # steps x choices x expert layers
    assert 0 < snap["ds_serve_moe_local_assignments_total"] < offered
    serve.close()


@pytest.mark.parametrize("routing", ["the_routers", "every_choice_held"])
def test_chunk_programs_serve_the_same_tokens_under_a_tile_of_pad(
        model, monkeypatch, routing):
    """``test_trinity.py``'s case of the same name through this form's chunk
    programs (``afmoe.mlp_block`` from ``kda_mla.cached_layers``): buckets
    of 16 and 8 tokens x 8 choices, a tile of 16 rows."""
    from deepspeed_tpu.moe import sharded_moe

    m, params = model
    if routing == "every_choice_held":
        monkeypatch.setattr(afmoe, "held", lambda cfg, weight, idx: (
            weight, idx % cfg.num_experts))
    prompt = np.random.default_rng(8).integers(0, 96, 37)

    def served(tile):
        monkeypatch.setattr(sharded_moe, "ROW_TILE", tile)
        serve = deepspeed_tpu.init_serving(m, config=ENGINE, params=params,
                                           mesh=m.mesh)
        serve.submit(prompt, max_new_tokens=6)
        (req,) = serve.run()
        serve.close()
        return req.output_tokens

    assert served(16) == served(1 << 30)
