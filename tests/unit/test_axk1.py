"""Latent-attention layers only, rotated, behind a low-rank query, under a
group-limited router (``models/kda_mla.py``, ``models/afmoe.py:route``;
A.X-K1) at a tiny size on the CPU: hidden 64, four query heads of 16 + 8
behind a bottleneck of 24, a latent of 32 + 8, YaRN over an original length
of 16, page 8, chunks of 16, a router of 16 experts in 4 groups of which 2
are kept, 2 experts held, top-4.  The plain reference
(``benchmarks/reference/axk1.py``) against the three forwards; YaRN and the
group limit against hand numbers; the absorbed form against the
decompressed; the share of the experts against the whole; the latent-only
cache kind."""

import dataclasses
import importlib.util
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm.mesh import build_mesh
from deepspeed_tpu.models import CausalLM, ModelConfig, afmoe, kda_mla
from deepspeed_tpu.ops.pallas.flash_attention import mla_chunk_schedule
from deepspeed_tpu.serving import cache_kind
from tests.unit._serving import (as_found, read_served, tapped_engine,
                                  with_noise)

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
ROPE = {"theta": 10000, "factor": 32, "original_max_position_embeddings": 16,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
PUBLISHED_ROPE = dict(ROPE, original_max_position_embeddings=4096)
FIELDS = dict(
    vocab_size=96, hidden_size=64, intermediate_size=48, num_layers=5,
    num_heads=4, max_seq_len=256, layer_types=("latent_attention",) * 5,
    num_dense_layers=1, dense_intermediate_size=128, norm_eps=1e-6,
    mla_kv_rank=32, mla_nope_dim=16, mla_rot_dim=8, mla_v_dim=16,
    mla_q_rank=24, mla_rope=ROPE, moe_score_func="sigmoid",
    moe_route_scale=2.5, num_shared_experts=1, num_experts=2,
    moe_router_experts=16, moe_first_expert=0, moe_n_group=4,
    moe_topk_group=2, num_experts_per_tok=4, moe_drop_tokens=False)
# the configuration file's keys the reference reads, at the same size
REF_CONFIG = {
    "num_hidden_layers": 5, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_lora_rank": 32, "q_lora_rank": 24,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": dict({k: v for k, v in ROPE.items() if k != "theta"},
                         type="yarn"),
    "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "expert_parallel": {"first_expert": 0}}
ENGINE = dict(num_slots=3, prefill_chunk=16, max_prefill_chunks=2,
              decode_block_tokens=4, max_out_tokens=96, kv_page_tokens=8,
              dtype="float32")


def _load(name, path):
    if REPO not in sys.path:          # the reference imports its sibling
        sys.path.insert(0, REPO)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("_axk1_ref", os.path.join(REPO, "benchmarks", "reference",
                                           "axk1.py"))


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


# ----------------------------------------- the three forwards, by logits
def test_reference_agrees_with_the_no_cache_forward(ref, model):
    m, params = model
    seq = np.random.default_rng(1).integers(0, 96, 83)
    got = np.asarray(jax.jit(m.apply)(params, seq[None]))[0]
    # the reference's OWN routing, stated: no row takes a near-tie's other side
    _, _, own = ref.hidden_states(params, REF_CONFIG, seq, jax.devices()[0],
                                  return_routing=True)
    want = ref_logits(ref, params, seq, list(range(83)), routing=list(own))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("variant", ["no_rope", "plain_freq", "no_mscale",
                                     "no_q_norm", "unrotated_cache_key"])
def test_each_control_of_the_reference_moves_the_logits(ref, model, variant):
    """The agreement tool's negative controls break what they name: the
    no-cache forward is far from each."""
    m, params = model
    seq = np.random.default_rng(1).integers(0, 96, 83)
    got = np.asarray(jax.jit(m.apply)(params, seq[None]))[0]
    _, _, own = ref.hidden_states(params, REF_CONFIG, seq, jax.devices()[0],
                                  return_routing=True)
    wrong = ref_logits(ref, params, seq, list(range(83)), routing=list(own),
                       variant=(variant,))
    assert np.abs(got - wrong).max() > 0.05


@pytest.mark.parametrize("prompts", [(16, 15), (17, 37), (48, 5)],
                         ids=["on_a_chunk_and_one_short",
                              "one_past_and_a_padded_bucket",
                              "three_chunks_and_tiny"])
def test_chunked_prefill_then_decode_is_the_references_one_forward(
        ref, model, tapped, prompts):
    """Prefill in chunks of 16 (a later chunk attends the ROTATED key parts
    the earlier ones wrote), then decode through the latent pages on the
    fused path (the absorbed query carries the rotated part), in float32,
    two requests in flight: the program's LOGITS at every generated position
    are the reference's full forward's, and so is every token."""
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


def test_bf16_serving_stays_within_the_drivers_bound(ref, model):
    """What the benchmark's ``verify`` checks, at the serving dtype."""
    m, params = model
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    serve = deepspeed_tpu.init_serving(
        m, config=dict(ENGINE, dtype="bfloat16"), params=params, mesh=m.mesh)
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


# ----------------------------------------------------------------- YaRN
def test_yarn_at_the_published_numbers_by_hand():
    """dim 64, base 10000, s = 32, L0 = 4096, beta 32 / 1: the ramp runs
    from pair 10 to pair 23; pair 0 keeps its frequency, pair 31 is slowed
    32-fold, pair 16 lies 6 / 13 of the way; m^2 = 1.81326."""
    inv_freq, on_cos_sin, m = kda_mla.yarn(PUBLISHED_ROPE, 64)
    assert inv_freq.shape == (32,) and inv_freq.dtype == np.float32
    f = lambda i: 10000.0 ** (-i / 32)
    np.testing.assert_allclose(inv_freq[0], 1.0, rtol=1e-6)
    np.testing.assert_allclose(inv_freq[:11], [f(i) for i in range(11)],
                               rtol=1e-6)                  # lo = 10
    np.testing.assert_allclose(inv_freq[23:], [f(i) / 32 for i in
                                               range(23, 32)], rtol=1e-6)
    assert inv_freq[22] > f(22) / 32 * (1 + 1e-3)          # hi = 23, not 22
    assert inv_freq[11] < f(11) * (1 - 1e-3)
    np.testing.assert_allclose(inv_freq[16], 0.01 * (7 / 13)
                               + 0.01 / 32 * (6 / 13), rtol=1e-6)
    np.testing.assert_allclose(inv_freq[16], 0.00552885, rtol=1e-5)
    np.testing.assert_allclose(inv_freq[31], 4.16726e-6, rtol=1e-5)
    assert on_cos_sin == 1.0
    assert m == pytest.approx(0.1 * math.log(32) + 1) == pytest.approx(
        1.346574, rel=1e-6)
    assert m * m == pytest.approx(1.81326, rel=1e-5)
    cfg = ModelConfig(**dict(FIELDS, mla_rope=PUBLISHED_ROPE,
                             mla_nope_dim=128, mla_rot_dim=64))
    assert kda_mla._mla_scale(cfg.mla_kind("latent_attention")) == \
        pytest.approx(1.81326 / math.sqrt(192), rel=1e-5)
    # the factors: cos and sin are scaled where mscale != mscale_all_dim,
    # and the softmax scale only where mscale_all_dim is set
    _, ratio, m0 = kda_mla.yarn(dict(PUBLISHED_ROPE, mscale_all_dim=0), 64)
    assert (ratio, m0) == (pytest.approx(1.346574, rel=1e-6), 1.0)
    # factor 1: plain RoPE
    plain, _, m1 = kda_mla.yarn(dict(PUBLISHED_ROPE, factor=1), 64)
    np.testing.assert_allclose(plain, [f(i) for i in range(32)], rtol=1e-6)
    assert m1 == 1.0


def test_rotation_turns_pairs_and_scores_see_only_the_distance():
    cfg = ModelConfig(**FIELDS).mla_kind("latent_attention")
    t = jax.random.normal(jax.random.PRNGKey(0), (5, 3, 8))
    pos = jnp.asarray([0, 1, 7, 40, 200])
    got = np.asarray(kda_mla.rotate(cfg, t, pos[:, None]))
    inv_freq, _, _ = kda_mla.yarn(ROPE, 8)
    z = np.asarray(t)[..., 0::2] + 1j * np.asarray(t)[..., 1::2]
    z = z * np.exp(1j * np.asarray(pos, np.float64)[:, None, None] * inv_freq)
    np.testing.assert_allclose(got[..., 0::2], z.real, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got[..., 1::2], z.imag, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(got[0], np.asarray(t)[0])    # position 0
    q, k = t[0, 0], t[1, 1]
    dot = lambda a, b: float((kda_mla.rotate(cfg, q, jnp.asarray(a))
                              * kda_mla.rotate(cfg, k, jnp.asarray(b))).sum())
    assert dot(50, 43) == pytest.approx(dot(7, 0), rel=1e-4)
    assert abs(dot(50, 43) - dot(50, 40)) > 1e-3
    # no position encoding: the values pass as they are
    plain = ModelConfig(**dict(FIELDS, mla_rope=None)).mla_kind(
        "latent_attention")
    assert kda_mla.rotate(plain, t, pos[:, None]) is t
    assert kda_mla._mla_scale(plain) == pytest.approx(24 ** -0.5)


def test_absorbed_latent_attention_is_the_decompressed(model):
    """A decode step's ``softmax(q' . rows) rows`` through ``Wkvb_v`` against
    per-head keys and values decompressed from the same rows, each row
    holding the key part ROTATED at its own position, the query's rotated at
    its own."""
    m, params = model
    cfg = m.config
    a = jax.tree.map(lambda t: t[0], params["mla"])
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    h = jax.random.normal(ks[0], (3, 64))
    hist = jax.random.normal(ks[1], (3, 40, 64))
    kd = cfg.mla_kind("latent_attention")     # the sizes are the KIND's
    q, row, _ = kda_mla.mla_project(kd, a, h, jnp.full((3,), 40))
    _, rows, _ = kda_mla.mla_project(kd, a, hist, jnp.arange(40)[None])
    assert q.shape == (3, 4, 24)
    assert rows.shape[-1] == kda_mla.row_width(cfg) == 128
    assert not np.asarray(rows[..., 40:]).any()                # the padding
    # the row holds the rotated key part, not the projection's
    raw = (hist @ a["wkva"])[..., 32:]
    np.testing.assert_array_equal(rows[:, 0, 32:40], raw[:, 0])
    assert np.abs(np.asarray(rows[:, 5:, 32:40] - raw[:, 5:])).max() > 1e-2
    rows = jnp.concatenate([rows, row[:, None]], axis=1)       # own row last
    scale = kda_mla._mla_scale(kd)
    assert scale == pytest.approx(1.81326 / math.sqrt(24), rel=1e-5)
    with jax.default_matmul_precision("highest"):
        k, v = kda_mla.mla_decompress(rows, *kda_mla._wkvb(kd, a),
                                      cfg.mla_rot_dim)         # [3, 41, H, .]
        s = jnp.einsum("bhd,bjhd->bhj", q, k) * scale
        want = jnp.einsum("bhj,bjhv->bhv", jax.nn.softmax(s, -1), v)
        qa = kda_mla.mla_absorb(kd, a, q)
        p = jax.nn.softmax(jnp.einsum("bhw,bjw->bhj", qa, rows) * scale, -1)
        got = kda_mla.mla_unabsorb(kd, a, jnp.einsum("bhj,bjw->bhw", p,
                                                     rows))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


# ------------------------------------------------------------ the router
def _route_cfg(**kw):
    return ModelConfig(**dict(FIELDS, **kw))


def test_group_limit_against_a_hand_made_score_table():
    """Eight experts in 4 groups of 2, 2 groups kept, top-3.  Row 0: group
    sums 1.3, 1.1, 0.9, 0.2 keep groups 0 and 1, so expert 4 (0.8, the best
    single score) is out.  Row 1: sums 0.2, 0.95, 0.98, 0.97 drop the group
    of the best single score (0.9) for two groups of middling pairs."""
    cfg = _route_cfg(moe_router_experts=8, num_experts=2, moe_n_group=4,
                     moe_topk_group=2, num_experts_per_tok=3,
                     moe_norm_topk_prob=False, moe_route_scale=1.0)
    s = jnp.asarray([[0.7, 0.6, 0.6, 0.5, 0.8, 0.1, 0.1, 0.1],
                     [0.1, 0.1, 0.9, 0.05, 0.5, 0.48, 0.45, 0.52]])
    kept = np.asarray(afmoe.kept_groups(cfg, s))
    assert kept.tolist() == [[True, True, False, False],
                             [False, False, True, True]]
    # route over logits whose sigmoids are the table
    logits = jnp.log(s) - jnp.log1p(-s)
    w, idx, kept2 = afmoe.route(cfg, jnp.eye(2), logits)
    np.testing.assert_array_equal(kept2, kept)
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 1, 2]
    assert sorted(np.asarray(idx[1]).tolist()) == [4, 5, 7]
    assert (np.asarray(idx[1]) != 2).all()       # 0.9 lies in a dropped group
    np.testing.assert_allclose(np.sort(np.asarray(w[0])), [0.6, 0.6, 0.7],
                               rtol=1e-5)
    # the chip's experts [0, 2) are group 0: in reach of row 0 only
    assert np.asarray(afmoe.held_group_kept(cfg, kept)).tolist() == [
        True, False]
    # experts [2, 6) span groups 1 and 2
    wide = dataclasses.replace(cfg, moe_first_expert=2, num_experts=4)
    assert np.asarray(afmoe.held_group_kept(wide, kept)).tolist() == [
        True, True]


def test_one_group_is_bit_equal_to_the_route_before_groups():
    """``moe_n_group`` 1 (Trinity's and Kimi's routers): the weights and the
    choice of the function as it stood before the group limit, to the bit,
    with and without a selection bias."""
    from deepspeed_tpu.moe import sharded_moe

    def route_before(cfg, h, gate_w, gate_bias=None):
        logits = jnp.dot(h.astype(jnp.float32), gate_w.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        s = jax.nn.sigmoid(logits)
        sel = s + gate_bias.astype(jnp.float32) if cfg.moe_select_bias else s
        _, idx = sharded_moe.topk_weights(sel, cfg.num_experts_per_tok, False)
        w = jnp.take_along_axis(s, idx, axis=-1)
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
        return w * cfg.moe_route_scale, idx

    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    h = jax.random.normal(ks[0], (37, 64))
    gate_w = jax.random.normal(ks[1], (64, 16)) * 0.3
    bias = jax.random.normal(ks[2], (16,)) * 0.05
    for select_bias in (False, True):
        cfg = _route_cfg(moe_n_group=1, moe_topk_group=1,
                         moe_select_bias=select_bias)
        w, idx, kept = afmoe.route(cfg, h, gate_w, bias)
        w0, idx0 = route_before(cfg, h, gate_w, bias)
        assert kept is None
        np.testing.assert_array_equal(idx, idx0)
        np.testing.assert_array_equal(w, w0)
        assert len(afmoe.moe_counts_zero(cfg)) == 4
    assert len(afmoe.moe_counts_zero(_route_cfg())) == 5
    # the limit changes the choice where groups are kept
    _, idx_g, kept = afmoe.route(_route_cfg(), h, gate_w)
    assert (np.sort(idx_g, -1) != np.sort(idx0, -1)).any()
    assert (np.asarray(kept).sum(-1) == 2).all()
    groups = np.asarray(idx_g) // 4
    assert np.take_along_axis(np.asarray(kept), groups, -1).all()


def test_sum_of_the_ranks_shares_is_the_whole_layer(ref, model):
    """The ranks' routed parts of one expert layer + the shared expert once
    = the uncut layer at top-4 of 16 in 2 of 4 groups (the reference with
    every expert held): eight ranks of 2 here, sixteen of 12 in the cell."""
    both = dict(FIELDS, moe_router_experts=16)
    cfg = ModelConfig(**dict(both, num_experts=2))
    every = ModelConfig(**dict(both, num_experts=16))
    whole = kda_mla.init_params(every, jax.random.PRNGKey(4))
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 23, 64))
    lp, _ = kda_mla.layer_params(every, whole, 2)
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
            h[0], w, None, 23, top_k=4, first=0, n_group=4, topk_group=2,
            route_scale=2.5, route_norm=True)
        want = ref.expert_close(jnp.zeros_like(h[0]), h[0], w, local,
                                eps=1e-6, cap=32, variant=("no_post_norm",))
    assert int(fullest) > 0
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-4)


def test_the_references_group_flip_is_the_other_side_of_a_group_tie(ref):
    """The reference's new exchange: where the 2nd and 3rd group scores are
    near-tied and a held group is one of the two, ``swap = GROUP_FLIP`` keeps
    the other one, and every choice moves with it."""
    s = np.asarray([[0.7, 0.6, 0.2, 0.2, 0.6, 0.598, 0.1, 0.1] + [0.05] * 8,
                    [0.9, 0.8, 0.2, 0.2, 0.5, 0.3, 0.1, 0.1] + [0.05] * 8],
                   np.float32)
    h = jnp.eye(2)
    w = {"router": jnp.log(s) - jnp.log1p(-s),
         "e_up": jnp.zeros((4, 2, 3))}       # experts [4, 8) = group 1 held
    kw = dict(top_k=3, first=4, n_group=4, topk_group=1, route_scale=1.0,
              route_norm=True)
    chosen, local, _, (margin, held) = ref.route(h, w, None, 2, **kw)
    assert sorted(np.asarray(chosen[0]).tolist()) == [0, 1, 2] or \
        sorted(np.asarray(chosen[0]).tolist()) == [0, 1, 3]
    flip = ref.GROUP_FLIP - 1
    assert float(margin[0, flip]) == pytest.approx(1.3 - 1.198, abs=1e-5)
    assert bool(held[0, flip]) and float(margin[0, flip]) > ref.NEAR_TIE
    assert not np.asarray(local).any()
    flipped, local, _, _ = ref.route(
        h, w, None, 2, swap=jnp.asarray([ref.GROUP_FLIP, 0]), **kw)
    assert sorted(np.asarray(flipped[0]).tolist()) == [4, 5, 6] or \
        sorted(np.asarray(flipped[0]).tolist()) == [4, 5, 7]
    np.testing.assert_array_equal(flipped[1], chosen[1])
    assert (np.asarray(local[0]) != 0).sum() == 3 and not np.asarray(
        local[1]).any()


# ---------------------------------------------- the kernels, interpret mode
@pytest.mark.parametrize("live", [[True, True, True], [True, False, True]],
                         ids=["all", "parked_between"])
def test_fused_layers_through_the_kernels_match_their_references(live):
    """The decode step with every Pallas kernel in interpret mode against the
    same step on the kernels' jnp references, at tile widths (a bottleneck
    of 256 behind ``fused_norm_qkv`` twice, a latent row of 128 + 64 padded
    to 256, pages of 128, positions on both sides of a page): live rows
    agree; a parked row's latent pages are what they were; no state, no
    tail, no fifth count but the group limit's."""
    cfg = ModelConfig(**dict(
        FIELDS, hidden_size=128, num_layers=2, num_dense_layers=1,
        layer_types=("latent_attention",) * 2, mla_kv_rank=128,
        mla_nope_dim=32, mla_rot_dim=64, mla_v_dim=32, mla_q_rank=256,
        intermediate_size=128, dense_intermediate_size=256))
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                          kda_mla.init_params(cfg, jax.random.PRNGKey(9)))
    assert "kda" not in params
    dparams = kda_mla.inject(cfg, params)
    assert {"w_in", "wqb", "q_norm", "kv_norm", "wkvb", "wo"} <= set(
        dparams["layers"][0])
    assert dparams["layers"][0]["w_in"].shape == (128, 512)   # 256 + 192 -> 512
    pos = jnp.asarray((300, 130, 40), jnp.int32)
    table = jnp.asarray([[1, 2, 3], [4, 5, 0], [6, 0, 0]], jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(10), 2)
    cache = {"latent": jax.random.normal(ks[0], (2, 7, 1, 128, 256),
                                         jnp.bfloat16)}
    x = jax.random.normal(ks[1], (3, 128), jnp.bfloat16)
    mask = jnp.asarray(live)
    step = lambda impl: kda_mla.fused_layers(
        cfg, dparams, x, cache, pos, table, moe_live=mask, impl=impl)
    (x_ref, c_ref, s_ref), (x_k, c_k, s_k) = step("xla"), step("interpret")
    assert set(c_k) == {"latent"} and len(s_k) == 5
    rows = np.flatnonzero(live)
    np.testing.assert_allclose(np.asarray(x_k, np.float32)[rows],
                               np.asarray(x_ref, np.float32)[rows],
                               rtol=0.05, atol=0.05)
    for a, b in zip(s_k, s_ref):
        np.testing.assert_array_equal(a, b)
    assert 0 <= int(s_k[4]) <= len(rows)
    # row 1 (page 5 at position 130): the appended row went to its place
    if live[1]:
        assert (np.asarray(c_k["latent"][:, 5, 0, 2], np.float32)
                != np.asarray(cache["latent"][:, 5, 0, 2], np.float32)).any()


@pytest.mark.parametrize("start,valid", [(0, 16), (48, 11), (128, 16)],
                         ids=["at_zero", "inside_a_strip", "at_a_strip"])
def test_cached_layers_through_the_chunk_kernel_match_the_jnp_path(start,
                                                                   valid):
    """A chunk program's layer stack with ``mla_chunk_attention`` in
    interpret mode against the same stack on ``afmoe.attend(expand=)``, its
    jnp reference, at tile widths (a latent row of 128 + 64 padded to 256,
    heads of 128 + 64 with values of 128, a view of 256 rows with earlier
    rows in it): the stream and the rows written agree."""
    from deepspeed_tpu.ops.pallas.common import reference_selections

    cfg = ModelConfig(**dict(
        FIELDS, hidden_size=128, num_layers=2, num_dense_layers=1,
        layer_types=("latent_attention",) * 2, mla_kv_rank=128,
        mla_nope_dim=128, mla_rot_dim=64, mla_v_dim=128, mla_q_rank=256,
        intermediate_size=128, dense_intermediate_size=256))
    params = kda_mla.init_params(cfg, jax.random.PRNGKey(9))
    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    cache = {"latent": jax.random.normal(ks[0], (2, 1, 1, 256, 256))}
    x = jax.random.normal(ks[1], (1, 16, 128))
    before = len(reference_selections())
    run = lambda impl: kda_mla.cached_layers(cfg, params, x, cache, start,
                                             valid, impl=impl)
    (x_ref, c_ref), (x_k, c_k) = run("xla"), run("interpret")
    assert len(reference_selections()) == before     # the kernel itself ran
    np.testing.assert_allclose(x_k, x_ref, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(c_k["latent"], c_ref["latent"], rtol=2e-4,
                               atol=2e-4)
    assert (np.asarray(c_k["latent"][:, 0, 0, start:start + 16])
            != np.asarray(cache["latent"][:, 0, 0, start:start + 16])).any()


# --------------------------------------------------------- the cache kind
def test_a_latent_only_model_has_pages_and_no_slot_state(model, tapped):
    m, params = model
    kind = cache_kind.cache_kind(m.config)
    assert type(kind) is cache_kind.LatentPages
    assert isinstance(cache_kind.LatentPagesAndState(m.config),
                      cache_kind.LatentPages)
    assert kind.pool_args(jnp.bfloat16) == {} and not kind.takes_valid_len
    with as_found(tapped[1]) as serve:
        assert set(serve._cache) == {"latent"}
        assert serve._cache["latent"].shape == (5, serve.pool.num_pages, 1,
                                                8, 128)
        assert serve.pool.state_bytes == 0
        assert len(kda_mla.moe_counts_zero(m.config)) == 5
        rng = np.random.default_rng(6)
        reqs = [serve.submit(rng.integers(0, 96, n), max_new_tokens=k)
                for n, k in ((12, 30), (40, 9), (20, 17), (33, 5))]
        serve.run()
        assert serve.pool.pages_used == 0
        assert [len(r.output_tokens) for r in reqs] == [30, 9, 17, 5]


def test_kimis_engine_still_builds_pages_and_state_with_its_series():
    from deepspeed_tpu.monitor.metrics import MetricsRegistry

    from .test_kimi_linear import ENGINE as KIMI_ENGINE, FIELDS as KIMI

    cfg = ModelConfig(**KIMI)
    kind = cache_kind.cache_kind(cfg)
    assert type(kind) is cache_kind.LatentPagesAndState
    assert kind.takes_valid_len and kind.what == \
        "linear_attention / latent_attention layers"
    assert "a recurrent state is not a page" in kind.cannot["handoff"]
    assert "LatentPages" in kind.cannot["prefix_caching"]
    assert len(kda_mla.moe_counts_zero(cfg)) == 5      # the state's, last
    assert kda_mla.moe_counts_zero(cfg)[-1].shape == (2,)
    m = CausalLM(cfg, build_mesh(devices=jax.devices()[:1]))
    reg = MetricsRegistry().enable()
    serve = deepspeed_tpu.init_serving(m, config=KIMI_ENGINE,
                                       params=m.init(jax.random.PRNGKey(0)),
                                       mesh=m.mesh, registry=reg)
    assert set(serve._cache) == {"latent", "state", "tail"}
    serve.submit(np.arange(40) % 96, max_new_tokens=9)
    serve.run()
    snap = reg.snapshot()
    assert snap["ds_serve_state_row_steps_total"] == 8 * 4
    assert snap["ds_serve_state_resets_total"] == 1
    assert snap["ds_serve_state_bytes"] == serve.pool.state_bytes > 0
    # one latent layer: chunks of 16, 16 and 8 rows over a view of 96 rows
    assert snap["ds_serve_mla_rows_written_total"] == 40
    assert snap["ds_serve_mla_rows_expanded_total"] == 3 * 96
    assert snap["ds_serve_moe_group_kept_total"] == 0      # one group
    serve.close()


@pytest.mark.parametrize("kw,match", [
    (dict(config={}, role="prefill"), "handoff.py ships pages as per-head"),
    (dict(config=dict(kv_host_tier_pages=4)), "host_tier.py"),
    (dict(config=dict(quantize_kv_cache=True)), "no int8 form"),
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
    assert set(cache_kind.LatentPages.cannot) == {
        "handoff", "kv_host_tier_pages", "prefix_caching",
        "quantize_kv_cache", "use_fused_decode"}
    assert "boundary-page copy" in cache_kind.LatentPages.cannot[
        "prefix_caching"]
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


@pytest.mark.parametrize("fields,match", [
    (dict(mla_rope=dict(ROPE, scale=2)), "mla_rope names"),
    (dict(mla_rope={"theta": 10000}), "mla_rope names"),
    (dict(mla_rot_dim=7), "even mla_rot_dim"),
    (dict(moe_n_group=3), "equal groups"),
    (dict(moe_topk_group=5), "moe_topk_group"),
    (dict(moe_topk_group=0), "moe_topk_group"),
    (dict(moe_n_group=8, moe_topk_group=1), "must hold the top-4"),
    (dict(mla_kv_rank=0), "needs"),
    (dict(layer_types=None), "layer_types"),
    (dict(layer_types=("full_attention",) * 5), "kda_mla.py"),
])
def test_config_says_what_it_cannot_build(fields, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig(**dict(FIELDS, **fields))


def test_latent_layers_alone_validate_with_every_kda_field_zero():
    cfg = ModelConfig(**FIELDS)
    assert cfg.is_kda_mla and not any(
        getattr(cfg, f.name) for f in dataclasses.fields(cfg)
        if f.name.startswith("kda_"))
    assert kda_mla.kind_layers(cfg) == ([], [0, 1, 2, 3, 4])
    # a full-rank, unrotated, one-group model of latent layers is one too
    plain = ModelConfig(**dict(FIELDS, mla_q_rank=0, mla_rope=None,
                               moe_n_group=1, moe_topk_group=1))
    p = kda_mla.init_params(plain, jax.random.PRNGKey(0))
    assert "wq" in p["mla"] and "wqa" not in p["mla"]
    with pytest.raises(ValueError, match="mla_q_rank"):
        ModelConfig(vocab_size=96, hidden_size=64, num_layers=2, num_heads=4,
                    mla_q_rank=8)
    with pytest.raises(ValueError, match="moe_n_group"):
        ModelConfig(vocab_size=96, hidden_size=64, num_layers=2, num_heads=4,
                    moe_n_group=2)


def test_the_published_widths_give_the_published_parameter_count():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "axk1-L5-ep16.json")) as f:
        c = json.load(f)
    model = CausalLM(ModelConfig(**c["model_config"]))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == c["parameters"] \
        == 3_491_257_344
    assert shapes["mla"]["wqa"].shape == (5, 7168, 1536)
    assert shapes["mla"]["wqb"].shape == (5, 1536, 64 * 192)
    assert shapes["mla"]["wkva"].shape == (5, 7168, 576)
    assert shapes["mla"]["wo"].shape == (5, 64 * 128, 7168)
    assert shapes["layers"]["mlp"]["w_up"].shape == (4, 12, 7168, 2048)
    assert shapes["layers"]["mlp"]["gate_w"].shape == (4, 7168, 192)
    assert "gate_bias" not in shapes["layers"]["mlp"] and "kda" not in shapes
    assert kda_mla.row_width(model.config) == 640


# ------------------------------------------------------------ the counters
def test_counters_count_expanded_rows_and_kept_groups(model):
    from deepspeed_tpu.monitor.metrics import MetricsRegistry

    m, params = model
    reg = MetricsRegistry().enable()
    serve = deepspeed_tpu.init_serving(m, config=ENGINE, params=params,
                                       mesh=m.mesh, registry=reg)
    p = np.random.default_rng(8).integers(0, 96, 40)
    serve.submit(p, max_new_tokens=21)
    serve.run()
    snap = {k: v for k, v in reg.snapshot().items()
            if isinstance(v, (int, float))}
    # five latent layers; chunks of 16, 16 and 8 real rows, each in the
    # bucket of 16 (the floor of a latent kind's buckets is its kernels' 128
    # rows, and never more than ``prefill_chunk``); the slot's view
    # is 96 rows, one key block, which every chunk expands whole: the counter
    # reads mla_chunk_schedule, and on the CPU (and at these widths on any
    # device) the schedule is that of afmoe.attend, the loop that runs
    assert snap["ds_serve_mla_rows_written_total"] == 5 * 40
    assert snap["ds_serve_mla_rows_expanded_total"] == 5 * 3 * 96
    sch = lambda off, cb, **kw: mla_chunk_schedule(
        off, cb, 96, heads=4, nope=16, rot=8, v_dim=16, row_width=128,
        itemsize=4, kv=32, **kw)
    assert [sch(off, cb)["visited"] for off, cb in
            ((0, 16), (16, 16), (32, 16))] == [96] * 3
    assert snap["ds_serve_prefill_pad_rows_total"] == 16 - 8
    assert "latent rank of 32" in sch(0, 16, impl="pallas")["reason"]
    # 20 decode steps x 4 expert layers x 4 choices offered
    offered = snap["ds_serve_moe_assignments_total"]
    assert offered == 20 * 4 * 4
    kept = snap["ds_serve_moe_group_kept_total"]
    assert 0 < kept < 20 * 4              # 2 of 4 groups, the chip's in one
    # a choice lands here only in a row whose kept groups hold the chip's
    assert snap["ds_serve_moe_local_assignments_total"] <= 2 * kept
    assert "ds_serve_state_row_steps_total" in snap       # every kind's series
    assert snap["ds_serve_state_row_steps_total"] == 0
    serve.close()
    # at the cell's widths the kernel's strips: a last chunk's bucket of 256
    # at 7,168 stops at 7,424 where attend's key block ends at 8,192
    cell = lambda off, cb, impl: mla_chunk_schedule(
        off, cb, 16384, heads=64, kv=512, nope=128, rot=64, v_dim=128,
        row_width=640, impl=impl)["visited"]
    assert (cell(7168, 256, "pallas"), cell(7168, 256, "xla")) == (7424, 8192)
    assert cell(7168, 1024, "pallas") == cell(7168, 1024, "xla") == 8192
    # the host's copy of attend's loop bound, by hand: key blocks of 1,024
    assert afmoe.keys_visited(16384, 1024) == 1024
    assert afmoe.keys_visited(16384, 1025) == 2048
    assert afmoe.keys_visited(16384, 6 * 1024) == 6144
    assert afmoe.keys_visited(16384, 20000) == 16384
    assert afmoe.keys_visited(96, 16) == 96
