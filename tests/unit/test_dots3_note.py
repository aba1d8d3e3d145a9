"""Latent attention under a learned selection of keys beside window latent
layers of other sizes (``models/kda_mla.py``; dots3-note-prev) at a tiny
size on the CPU: hidden 64; full layers of four heads of 16 + 8 behind a
bottleneck of 24, a latent of 32, sixteen index heads of 16 that select 16
keys; sliding layers of two heads of 24 + 8, a latent of 48, a window of 13
(no multiple of the page of 8: the ring is 16 rows); headwise gates, the
low-rank rescale, a router of 16 experts of which 2 are held, top-4.  The
plain reference (``benchmarks/reference/dots3_note.py``) against the three
forwards; the selection and the window at their edges; the share of the
experts against the whole; the kernels against their references."""

import dataclasses
import hashlib
import importlib
import importlib.util
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm.mesh import build_mesh
from deepspeed_tpu.models import CausalLM, ModelConfig, afmoe, kda_mla
from tests.unit._serving import read_served, tapped_engine, with_noise

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
ROPE = lambda theta: {"theta": theta, "factor": 1,
                      "original_max_position_embeddings": 64, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1, "mscale_all_dim": 0}
TYPES = ("latent_attention",) * 2 + ("latent_sliding_attention",) * 3
SLIDING = {"num_heads": 2, "kv_rank": 48, "nope_dim": 24, "rot_dim": 8,
           "v_dim": 16, "q_rank": 24, "rope": ROPE(5e4)}
FIELDS = dict(
    vocab_size=96, hidden_size=64, intermediate_size=48, num_layers=5,
    num_heads=4, max_seq_len=256, layer_types=TYPES, num_dense_layers=1,
    dense_intermediate_size=128, norm_eps=1e-5, mla_kv_rank=32,
    mla_nope_dim=16, mla_rot_dim=8, mla_v_dim=16, mla_q_rank=24,
    mla_rope=ROPE(8e7), sliding_window=13, mla_sliding=SLIDING,
    mla_index_heads=16, mla_index_dim=16, mla_index_topk=16,
    mla_head_gate=True, mla_lora_rescale=True, moe_score_func="sigmoid",
    moe_select_bias=True, moe_route_scale=1.0, num_shared_experts=1,
    num_experts=2, moe_router_experts=16, moe_first_expert=0,
    num_experts_per_tok=4, moe_drop_tokens=False)
# the configuration file's keys the reference reads, at the same size
REF_CONFIG = {
    "num_hidden_layers": 5, "first_k_dense_replace": 1, "hidden_size": 64,
    "layer_types": ["full_attention"] * 2 + ["sliding_attention"] * 3,
    "num_attention_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_lora_rank": 32, "q_lora_rank": 24,
    "rope_theta": 8e7, "swa_num_attention_heads": 2,
    "swa_qk_nope_head_dim": 24, "swa_qk_rope_head_dim": 8,
    "swa_v_head_dim": 16, "swa_kv_lora_rank": 48, "swa_q_lora_rank": 24,
    "swa_rope_theta": 5e4, "sliding_window_size": 13, "index_n_heads": 16,
    "index_head_dim": 16, "index_topk": 16,
    "apply_mla_qkv_lora_rescale": True, "rms_norm_eps": 1e-5,
    "num_experts_per_tok": 4, "routed_scaling_factor": 1.0,
    "norm_topk_prob": True, "expert_parallel": {"first_expert": 0}}
ENGINE = dict(num_slots=3, prefill_chunk=16, max_prefill_chunks=2,
              decode_block_tokens=4, max_out_tokens=96, kv_page_tokens=8,
              dtype="float32")
CONTROLS = ["no_selection", "top_1024", "no_relu", "no_index_weight",
            "unrotated_index_key", "no_index_norm", "no_rescale", "no_gate",
            "gate_wrong_head", "window_512", "full_base",
            "unrotated_cache_key"]


def _load(name, path):
    if REPO not in sys.path:          # the reference imports its sibling
        sys.path.insert(0, REPO)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("_dots3_ref", os.path.join(REPO, "benchmarks", "reference",
                                            "dots3_note.py"))


def _noisy(m, key=1):
    """Gains of exactly 1 would hide a dropped norm."""
    return with_noise(m.init(jax.random.PRNGKey(0)), key, keys=96)


@pytest.fixture(scope="module")
def model():
    m = CausalLM(ModelConfig(**FIELDS),
                 build_mesh(devices=jax.devices()[:1]))
    return m, _noisy(m)


def own_choices(ref, params, seq, config=REF_CONFIG):
    """The reference's OWN routing and selections, stated: no row then takes
    a near-tie's other side."""
    _, _, used, selected = ref.hidden_states(
        params, config, seq, jax.devices()[0], return_choices=True)
    return dict(routing=list(used), selection=selected)


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
    want = ref_logits(ref, params, seq, list(range(83)),
                      **own_choices(ref, params, seq))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("variant", CONTROLS)
def test_each_control_of_the_reference_moves_the_logits(ref, model, variant):
    """The agreement tool's negative controls break what they name: the
    no-cache forward is far from each (the selections are the reference's
    own under the control: a control of the indexer selects other keys)."""
    m, params = model
    seq = np.random.default_rng(1).integers(0, 96, 83)
    got = np.asarray(jax.jit(m.apply)(params, seq[None]))[0]
    wrong = ref_logits(ref, params, seq, list(range(83)),
                       routing=own_choices(ref, params, seq)["routing"],
                       variant=(variant,))
    assert np.abs(got - wrong).max() > 0.03


@pytest.mark.parametrize("prompts", [(16, 15), (17, 37), (48, 5), (12, 14)],
                         ids=["on_the_selection_and_one_short",
                              "one_past_and_three_chunks",
                              "three_chunks_and_tiny",
                              "the_window_less_one_and_plus_one"])
def test_chunked_prefill_then_decode_is_the_references_one_forward(
        ref, model, tapped, prompts):
    """Prefill in chunks of 16 (a later chunk scores the index keys and
    attends the selected rows the earlier ones wrote, and reads the ring
    they left), then decode through index pages, selected rows and rings on
    the fused path, in float32, two requests in flight: the program's LOGITS
    at every generated position are the reference's full forward's.  The
    prompts put the selection's 16 keys and the window's 13 on both sides of
    a prompt's end and of a chunk's; the answers run the rings (16 rows)
    round more than once, past rows that are no page's first."""
    m, params = model
    ps = [np.random.default_rng(n).integers(0, 96, n) for n in prompts]
    served = read_served(tapped, ps, [41, 23])
    for p, rec in zip(ps, served):
        seq = np.concatenate([p, rec["tokens"]])
        rows = list(range(len(p) - 1, len(seq) - 1))
        want = ref_logits(ref, params, seq, rows,
                          **own_choices(ref, params, seq))
        np.testing.assert_allclose(rec["logits"], want, rtol=3e-4, atol=3e-4)
        assert list(rec["tokens"]) == list(want.argmax(-1))


def test_a_slot_reused_by_a_shorter_request_reads_nothing_of_the_longer(
        model):
    """One slot, a long request then a short one: the short one's tokens are
    those it gets alone on a fresh engine (no index key, latent row or ring
    row of the longer request is read: a ring row is masked by the position
    it holds, a page by the row's depth)."""
    long = np.random.default_rng(7).integers(0, 96, 61)
    short = np.random.default_rng(8).integers(0, 96, 9)
    serve = serve_of(model, num_slots=1)
    a = serve.submit(long, max_new_tokens=20)
    serve.run()
    b = serve.submit(short, max_new_tokens=20)
    serve.run()
    fresh = serve_of(model, num_slots=1)
    c = fresh.submit(short, max_new_tokens=20)
    fresh.run()
    assert len(a.output_tokens) == 20
    assert list(b.output_tokens) == list(c.output_tokens)


# ------------------------------------------------ the pieces at their edges
@pytest.mark.parametrize("keys", [15, 16, 17, 40])
def test_the_selection_is_exact_on_both_sides_of_its_size(keys):
    """``select_keys`` (a chunk: a threshold by bisection) and
    ``select_positions`` (a decode step: a top-k) pick the same keys as a
    sort: all of them up to 16, the 16 best past it."""
    rng = np.random.default_rng(keys)
    P, s, k = 64, 8, 16
    start = keys - s
    pos = start + np.arange(s)
    sc = rng.normal(size=(P, s)).astype(np.float32)
    sc = np.where(np.arange(P)[:, None] <= pos[None, :], sc, afmoe.NEG_INF)
    keep = np.asarray(kda_mla.select_keys(jnp.asarray(sc), jnp.asarray(pos),
                                          k)) == 0
    for t in range(s):
        n = pos[t] + 1
        want = set(np.argsort(-sc[:n, t], kind="stable")[:k].tolist())
        assert set(np.flatnonzero(keep[:, t]).tolist()) == want
    sel, n_sel = kda_mla.select_positions(jnp.asarray(sc.T), jnp.asarray(pos),
                                          k)
    for t in range(s):
        assert int(n_sel[t]) == min(pos[t] + 1, k)
        assert set(np.asarray(sel)[t, :int(n_sel[t])].tolist()) == set(
            np.flatnonzero(keep[:, t]).tolist())


@pytest.mark.parametrize("start,valid", [(0, 5), (11, 16), (29, 7), (40, 16)])
def test_the_ring_holds_the_last_positions_that_land_on_each_row(start,
                                                                 valid):
    """``ring_after`` writes a chunk's REAL rows at ``position % rows`` (a
    chunk longer than the ring leaves its last rows), ``ring_before`` reads
    the window's earlier rows back oldest first, by position."""
    R, W, s, window = 12, 4, 16, 13
    rows = lambda p: np.stack([np.asarray(p, np.float32)] * W, -1)
    ring = np.full((R, W), -1.0, np.float32)
    for p in range(start):                       # the earlier positions
        ring[p % R] = p
    new = kda_mla.ring_after(jnp.asarray(ring),
                             jnp.asarray(rows(start + np.arange(s))), start,
                             valid)
    held = np.asarray(new)[:, 0]
    last = start + valid - 1
    for i in range(R):
        cands = [p for p in range(last + 1) if p % R == i]
        assert held[i] == (cands[-1] if cands else -1.0)
    before, p = kda_mla.ring_before(new, last + 1, window)
    ok = np.asarray(p) >= 0
    assert list(np.asarray(before)[ok, 0]) == list(np.asarray(p)[ok])
    assert list(np.asarray(p)) == list(range(last + 2 - window, last + 1))


@pytest.mark.parametrize("pos", [11, 12, 13, 30])
def test_the_window_is_thirteen_keys_at_a_decode_step(model, pos):
    """``ring_decode`` attends exactly the keys ``0 <= t - j < 13`` that
    exist: a row's output does not move with any other ring row."""
    m, _ = model
    kd = m.config.mla_kind("latent_sliding_attention")
    R, W = 16, kd.row_width
    rng = np.random.default_rng(pos)
    q = jnp.asarray(rng.normal(size=(1, kd.heads, W)), jnp.float32)
    ring = rng.normal(size=(1, R, W)).astype(np.float32)
    out = np.asarray(kda_mla.ring_decode(kd, q, jnp.asarray(ring),
                                         jnp.asarray([pos])))
    for i in range(R):
        held = pos - (pos - i) % R          # the position ring row i holds
        moved = ring.copy()
        moved[0, i] += 1.0
        new = np.asarray(kda_mla.ring_decode(kd, q, jnp.asarray(moved),
                                             jnp.asarray([pos])))
        attended = held >= 0 and pos - held < 13
        assert (np.abs(new - out).max() > 1e-6) == attended, (i, held)


def test_two_kinds_of_latent_sizes_in_one_model(model):
    m, params = model
    cfg = m.config
    full, sw = (cfg.mla_kind(t) for t in ("latent_attention",
                                          "latent_sliding_attention"))
    assert (full.heads, full.kv, full.nope, full.window, full.index) == (
        4, 32, 16, 0, (16, 16, 16))
    assert (sw.heads, sw.kv, sw.nope, sw.window, sw.index) == (
        2, 48, 24, 13, None)
    assert full.q_scale == (64 / 24) ** 0.5 and full.kv_scale == 2 ** 0.5
    assert sw.kv_scale == (64 / 48) ** 0.5
    assert (full.row_width, sw.row_width) == (128, 128)
    assert params["mla"]["wkvb"].shape == (2, 32, 4 * 32)
    assert params["mla_sw"]["wkvb"].shape == (3, 48, 2 * 40)
    assert params["mla"]["wg"].shape == (2, 64, 4)
    assert params["mla_sw"]["wg"].shape == (3, 64, 2)
    assert "wiq" in params["mla"] and "wiq" not in params["mla_sw"]
    assert kda_mla.kind_layers(cfg) == ([], [0, 1])
    assert kda_mla.sliding_layers(cfg) == [2, 3, 4]
    assert kda_mla.ring_rows(cfg, 8) == 16


def test_the_sixteen_shares_and_what_every_chip_computes_make_the_layer():
    """The share tied to the model: an expert layer's routed part summed
    over the EIGHT ranks that hold 2 of the router's 16 experts each, plus
    what every rank computes alike (attention, the shared expert, the
    residual) counted once, is the uncut layer's output."""
    mesh = build_mesh(devices=jax.devices()[:1])
    whole = ModelConfig(**dict(FIELDS, num_experts=16))
    params = _noisy(CausalLM(whole, mesh))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 24, 64))
    lp, le = kda_mla.layer_params(whole, params, 1)
    h = afmoe.rms(x, lp["mlp_norm"]["scale"], whole.norm_eps)
    experts = afmoe._experts(params)
    full = afmoe.mlp(whole, lp, h, experts, le)
    shared = afmoe.glu_mlp(h.reshape(-1, 64), lp["mlp"]["shared"]).reshape(
        h.shape)
    total = jnp.zeros_like(full)
    for rank in range(8):
        part = dataclasses.replace(whole, num_experts=2,
                                   moe_first_expert=2 * rank)
        held = {k: v[:, 2 * rank:2 * rank + 2] for k, v in experts.items()}
        total = total + afmoe.mlp(part, lp, h, held, le) - shared
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(full),
                               rtol=2e-4, atol=2e-5)


# ----------------------------------------- the kernels, in interpret mode
@pytest.fixture(scope="module")
def kernels():
    return (importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention"),
            importlib.import_module("deepspeed_tpu.ops.pallas.decode"))


@pytest.mark.parametrize("start,s", [(0, 128), (384, 128), (896, 128),
                                     (700, 40)])
def test_the_chunk_kernels_are_their_references(kernels, start, s):
    """``dsa_index_scores_chunk`` and ``dsa_chunk_attention`` in interpret
    mode against the ``jnp`` forms, at a chunk that starts the view, one in
    its middle, one that ends it, and a short bucket (padded queries)."""
    fa, _ = kernels
    rng = np.random.default_rng(start)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    G, d, P, L = 8, 128, 1024, 2
    q, w, keys = f(s, G, d), f(s, G), f(L, P, d)
    a = fa.dsa_index_scores_chunk(q, w, keys, start, layer=1, impl="xla")
    b = fa.dsa_index_scores_chunk(q, w, keys, start, layer=1,
                                  impl="interpret")
    np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-5,
                               atol=1e-4)
    H, kv, n, r, v, W = 4, 128, 128, 64, 128, 256
    qq, rows, wkvb = f(s, H, n + r), f(L, P, W), f(kv, H, n + v) * 0.1
    keep = kda_mla.select_keys(a, start + jnp.arange(s), 200)
    x = fa.dsa_chunk_attention(qq, rows, wkvb, keep, start, nope=n,
                               scale=0.07, layer=1, impl="xla")
    y = fa.dsa_chunk_attention(qq, rows, wkvb, keep, start, nope=n,
                               scale=0.07, layer=1, impl="interpret")
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), rtol=1e-5,
                               atol=1e-5)


def test_the_decode_kernels_are_their_references(kernels):
    """``dsa_index_scores_paged`` (live rows only, pages through the table)
    and ``dsa_decode_selected`` in interpret mode against the ``jnp``
    forms."""
    _, dec = kernels
    rng = np.random.default_rng(0)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    B, G, d, page, cols, Pg, L, H, W = 4, 8, 128, 128, 4, 20, 2, 4, 256
    q, w, cache = f(B, G, d), f(B, G), f(L, Pg, 1, page, d)
    pt = jnp.asarray(rng.permutation(Pg - 1)[:B * cols].reshape(B, cols) + 1,
                     jnp.int32)
    pos = jnp.asarray([5, 130, 511, 300], jnp.int32)
    live = jnp.asarray([True, True, False, True])
    a = dec.dsa_index_scores_paged(q, w, cache, pos, pt, layer=1, live=live,
                                   impl="xla")
    b = dec.dsa_index_scores_paged(q, w, cache, pos, pt, layer=1, live=live,
                                   impl="interpret")
    np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-5,
                               atol=1e-4)
    assert float(a[2].max()) < afmoe.NEG_INF / 2       # the parked row
    sel, n_sel = kda_mla.select_positions(a, pos, 128)
    assert list(np.asarray(n_sel)) == [6, 128, 128, 128]
    lat, qa = f(L, Pg, 1, page, W), f(B, H, W)
    x = dec.dsa_decode_selected(qa, lat, sel, n_sel, pt, layer=1,
                                sm_scale=0.07, impl="xla")
    y = dec.dsa_decode_selected(qa, lat, sel, n_sel, pt, layer=1,
                                sm_scale=0.07, impl="interpret")
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), rtol=1e-5,
                               atol=1e-5)


# ------------------- the three configurations that share kda_mla / afmoe
# sha256 of the StableHLO text (locations stripped) of the chunk program
# and of the decode block of the three tiny models of test_kimi_linear.py,
# test_axk1.py and test_trinity.py, lowered on the tree BEFORE ISSUE 52
# (commit b58439d): the move of the ``mla_*`` sizes from the configuration
# to the layer kind changes none of their programs.  A later PR that changes
# one of these programs ON PURPOSE replaces its line here and says so.
PARENT_PROGRAMS = {
    ("test_kimi_linear", "chunk"): "11fef933a4ea6828",
    ("test_kimi_linear", "block"): "7d70faa6a6fb72d1",
    ("test_axk1", "chunk"): "5847b34ca1fd81e1",
    ("test_axk1", "block"): "6dbbe1918ac08793",
    ("test_trinity", "chunk"): "bc3b54789391cf3e",
    ("test_trinity", "block"): "3257c38fbcf7183e",
}


def _lowered(fields, engine):
    """(chunk program, decode block) of a tiny model's engine as StableHLO
    text, from shapes."""
    from deepspeed_tpu.models.fused_decode import inject_decode_params
    from deepspeed_tpu.serving.engine import ServingEngine

    model = CausalLM(ModelConfig(**fields),
                     build_mesh(devices=jax.devices()[:1]))
    s = ServingEngine(model, dict(engine))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    sds = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    carries = sds((s._last_dev, s._pos_dev, s._act_dev))
    bucket = engine["prefill_chunk"]
    chunk = s._prefill_fn(bucket).lower(
        params, sds(s._cache), carries, i32(s.pool.slot_pages),
        i32(1, bucket), i32(5), sds(s._rng)).as_text()
    s.engine._dparams = jax.eval_shape(
        lambda p: inject_decode_params(p, model.config), params)
    block = s._block().lower(
        s.engine._dparams, sds(s._cache), *carries, i32(s.num_slots),
        i32(s.num_slots), sds(s._rng),
        i32(s.num_slots, s.pool.slot_pages)).as_text()
    return {"chunk": chunk, "block": block}


@pytest.mark.parametrize("name", ["test_kimi_linear", "test_axk1",
                                  "test_trinity"])
def test_the_older_configurations_lower_to_the_parents_programs(name):
    t = _load("_older_" + name, os.path.join(os.path.dirname(__file__),
                                             name + ".py"))
    for what, text in _lowered(t.FIELDS, t.ENGINE).items():
        text = re.sub(r"loc\(.*?\)", "", text)
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
            PARENT_PROGRAMS[name, what], (name, what)


# ------------------------ the selection follows the rows that decode (PR 58)
def _mask(*live):
    m = np.zeros(16, bool)
    m[list(live)] = True
    return m


# the groups are of 8 rows (the sort) and of 1 (the lookup, the gather and
# the kernel): counts at, and one past, both
LIVE_MASKS = {
    "none": _mask(), "one_row": _mask(11), "two_rows": _mask(2, 14),
    "five_rows": _mask(0, 3, 4, 10, 15),
    "a_sort_group": _mask(0, 2, 3, 5, 8, 11, 12, 15),
    "a_sort_group_and_one": _mask(1, 2, 4, 6, 7, 9, 10, 13, 14),
    "every_other": _mask(*range(1, 16, 2)), "all": _mask(*range(16)),
    "no_mask": None}


@pytest.mark.parametrize("impl", ["xla", "interpret"])
@pytest.mark.parametrize("gather_group", [1, 4])
@pytest.mark.parametrize("mask", sorted(LIVE_MASKS))
def test_the_selection_works_the_rows_that_decode(kernels, monkeypatch, mask,
                                                  gather_group, impl):
    """``select_positions`` (told the parked rows by a negative position)
    and ``dsa_decode_selected(live=)`` in groups of live rows against ONE
    ``jax.lax.top_k`` and ``_selected_ref`` over all sixteen rows: a live
    row's positions and count, and its output BY SLOT (bit-equal on the
    ``jnp`` path); a row that does not decode: no position, zeros.  The
    second loop at its group of one row and at a group that pads."""
    _, dec = kernels
    assert (kda_mla.SORT_GROUP, dec.GATHER_GROUP) == (8, 1)
    monkeypatch.setattr(dec, "GATHER_GROUP", gather_group)
    from deepspeed_tpu.models.decoding import paged_logical_view

    rng = np.random.default_rng(3)
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    B, page, cols, L, H, W, K = 16, 128, 4, 2, 4, 256, 128
    Pg = B * cols + 1
    live = LIVE_MASKS[mask]
    on = np.ones(B, bool) if live is None else live
    pt = jnp.asarray(rng.permutation(Pg - 1).reshape(B, cols) + 1, jnp.int32)
    pos = rng.integers(K, page * cols, size=B)
    pos[[0, 5, 11]] = [3, 127, 128]        # under, at and past the top-k
    scores = np.where((np.arange(page * cols)[None] <= pos[:, None])
                      & on[:, None], rng.normal(size=(B, page * cols)),
                      afmoe.NEG_INF).astype(np.float32)
    told = pos if live is None else np.where(live, pos, -1)
    sel, n_sel = jax.jit(kda_mla.select_positions, static_argnums=2)(
        jnp.asarray(scores), jnp.asarray(told, jnp.int32), K)
    _, want = jax.lax.top_k(jnp.asarray(scores), K)
    want_n = np.minimum(pos + 1, K)
    sel, n_sel, want = np.asarray(sel), np.asarray(n_sel), np.asarray(want)
    for b in range(B):
        if on[b]:
            assert n_sel[b] == want_n[b]
            assert set(sel[b, :n_sel[b]]) == set(want[b, :want_n[b]]), b
        else:
            assert n_sel[b] == 0 and not sel[b].any()
    lat, q = f(L, Pg, 1, page, W), f(B, H, W)
    got = np.asarray(jax.jit(
        lambda *a: dec.dsa_decode_selected(
            *a, layer=1, sm_scale=0.07,
            live=None if live is None else jnp.asarray(live), impl=impl))(
        q, lat, jnp.asarray(sel), jnp.asarray(n_sel), pt))
    rows = jnp.take_along_axis(paged_logical_view(lat[1], pt)[:, 0],
                               jnp.asarray(want)[:, :, None], axis=1)
    ref = np.asarray(dec._selected_ref(q, rows, jnp.asarray(want_n),
                                       scale=0.07))
    assert np.isfinite(got).all()
    assert not got[~on].any()
    if impl == "xla":
        # the same rows in the same order through the same arithmetic
        np.testing.assert_array_equal(sel[on], want[on])
        np.testing.assert_array_equal(got[on], ref[on])
    else:
        np.testing.assert_allclose(got[on], ref[on], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("levels", [2, 5, 11])
def test_a_tie_at_the_edge_of_the_top_k_is_cut_as_top_k_cuts_it(levels):
    """Scores of few distinct values (four index heads give one key in
    sixteen a score of exactly 0): every row of a group selects the SET
    ``jax.lax.top_k`` selects on that row alone, whatever the group's other
    rows hold."""
    rng = np.random.default_rng(levels)
    B, S, K = 16, 512, 64
    scores = rng.integers(0, levels, size=(B, S)).astype(np.float32)
    pos = rng.integers(K, S, size=B)
    live = _mask(0, 1, 4, 6, 7, 9, 10, 12, 13, 15)
    scores = np.where((np.arange(S)[None] <= pos[:, None]) & live[:, None],
                      scores, afmoe.NEG_INF).astype(np.float32)
    sel, n = kda_mla.select_positions(
        jnp.asarray(scores), jnp.asarray(np.where(live, pos, -1), jnp.int32),
        K)
    cut = 0
    for b in np.flatnonzero(live):
        _, alone = jax.lax.top_k(jnp.asarray(scores[b]), K)
        alone = np.asarray(alone)
        # where the k-th score is tied the cut is by index order
        kth = scores[b][alone[-1]]
        cut += (scores[b] == kth).sum() > (scores[b][alone] == kth).sum()
        assert int(n[b]) == K
        assert set(np.asarray(sel)[b].tolist()) == set(alone.tolist()), b
    assert cut >= 8


@pytest.mark.parametrize("short", [3, 5])
def test_the_selections_rows_are_counted_on_the_device(model, short):
    """Four requests that reach their limit inside the first decode block
    beside one that decodes for two blocks: ``ds_serve_dsa_rows_sorted_total``
    and ``_gathered_total`` grow by every step's live rows rounded up to
    whole groups (six slots: a sort group is the batch, a gather group one
    row), ``ds_serve_decode_tokens_total`` by the live pairs."""
    from deepspeed_tpu.monitor.metrics import MetricsRegistry
    from deepspeed_tpu.ops.pallas.decode import GATHER_GROUP

    m, params = model
    reg = MetricsRegistry().enable()
    serve = deepspeed_tpu.init_serving(
        m, config=dict(ENGINE, num_slots=6, max_prefill_chunks=8),
        params=params, mesh=m.mesh, registry=reg)
    rng = np.random.default_rng(short)
    for new in (short, short, short, short, 9):
        serve.submit(rng.integers(0, 96, 12), max_new_tokens=new)
    serve.run()
    snap = reg.snapshot()
    # a request's first token is its last chunk's: ``new - 1`` decode steps
    live = [5] * (short - 1) + [1] * (8 - (short - 1))
    up = lambda n, G: -(-n // G) * G
    Gs, Gg = min(kda_mla.SORT_GROUP, 6), min(GATHER_GROUP, 6)
    assert snap["ds_serve_decode_tokens_total"] == sum(live)
    assert snap["ds_serve_dsa_rows_sorted_total"] == sum(
        up(n, Gs) for n in live) == 8 * 6
    assert snap["ds_serve_dsa_rows_gathered_total"] == sum(
        up(n, Gg) for n in live)
    serve.close()
