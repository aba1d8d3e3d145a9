"""A looped stack (Ouro) through the program against its plain reference
(``benchmarks/reference/ouro.py``), at a tiny size on the CPU: one stack of
layers run ``total_ut_steps`` times with tied weights, a cache layer a (pass,
layer) pair under one page table, a norm on both sides of each sub-block, the
final norm closing every pass, the exit gate; the pass loop ROLLED in the
decode block.

Float32 program against float32 reference.  TOLERANCE: ``rtol`` 2e-4 with an
``atol`` of 2e-5 on logits: both sides are float32 and differ in the order of
their sums (kernel tiles, chunks of a prefill) through 4 x 2 layer
applications and a 64-wide head.  The three wrong programs of (d) miss it by
three orders of magnitude.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.comm.mesh import build_mesh
from deepspeed_tpu.models import CausalLM, ModelConfig, fused_decode
from deepspeed_tpu.models.fused_decode import inject_decode_params
from deepspeed_tpu.monitor.metrics import MetricsRegistry
from deepspeed_tpu.ops.pallas import common
from deepspeed_tpu.serving import handoff
from deepspeed_tpu.serving.cache_kind import FullPages, cache_kind
from tests.unit._serving import with_noise

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RTOL, ATOL = 2e-4, 2e-5
VOCAB, T, L = 256, 4, 2
FIELDS = dict(vocab_size=VOCAB, hidden_size=64, intermediate_size=160,
              num_layers=L, num_heads=4, num_kv_heads=4, head_dim=16,
              max_seq_len=512, norm_eps=1e-6, rope_theta=1e6,
              total_ut_steps=T, sandwich_norm=True, loop_exit_gate=True)
ENGINE = {"dtype": "float32", "num_slots": 4, "prefill_chunk": 64,
          "decode_block_tokens": 4, "max_out_tokens": 256,
          "kv_page_tokens": 128, "kv_pool_tokens": 1024}


def _load(rel, name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "benchmarks", *rel.split("/")))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load("reference/ouro.py", "_ref_ouro")
ref_mistral = _load("reference/mistral.py", "_ref_mistral_for_ouro")
taps_lib = _load("lib/serve_taps.py", "_serve_taps_for_ouro")


def tiny(**over):
    return ModelConfig(**dict(FIELDS, **over))


def ref_config(cfg):
    """The keys of the published configuration the reference reads."""
    return dict(num_hidden_layers=cfg.num_layers,
                total_ut_steps=cfg.total_ut_steps,
                num_attention_heads=cfg.num_heads,
                num_key_value_heads=cfg.num_kv_heads,
                rms_norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta)


def seeded(model, seed=0):
    """Weights with every norm gain off 1, so a dropped gain shows."""
    return with_noise(model.init(jax.random.PRNGKey(seed)), seed + 1)


def tokens_of(n, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, n, dtype=np.int32)


def ref_logits(params, cfg, tokens, rows):
    return np.asarray(ref.logits_rows(params, ref_config(cfg), tokens,
                                      list(rows), jax.devices()[0]))


def close_to(got, want):
    return np.allclose(got, want, rtol=RTOL, atol=ATOL)


@pytest.fixture
def interpret_kernels(monkeypatch):
    monkeypatch.setattr(common, "default_impl", lambda: "interpret")


@pytest.fixture(scope="module")
def made():
    mesh = build_mesh(devices=jax.devices()[:1])
    model = CausalLM(tiny(), mesh)
    return model, seeded(model), mesh


def serve_of(made, registry=None, **kw):
    model, params, mesh = made
    return deepspeed_tpu.init_serving(
        model, params=params, mesh=mesh, config=dict(ENGINE, **kw),
        **({"registry": registry} if registry is not None else {}))


# prompts that end inside a page and a chunk (150, 37), on a page AND a
# chunk boundary (128) and on a chunk boundary alone (64)
PROMPTS = [tokens_of(150, 1), tokens_of(128, 2), tokens_of(64, 3),
           tokens_of(37, 4)]
NEW = [6, 9, 5, 4]


def served_logits(made, prompts=PROMPTS, new=NEW, **kw):
    with taps_lib.ServeTaps() as taps:
        serve = serve_of(made, **kw)
        assert serve.engine._dparams is not None          # the fused path
        served = taps_lib.serve_and_read(taps, serve, prompts, new)
        serve.pool.check_no_leak()
        serve.close()
    return served


def against_reference(served, params, cfg, prompts=PROMPTS):
    """[(program logits, reference logits)] a request, the reference
    teacher-forced with the served tokens."""
    out = []
    for rec, prompt in zip(served, prompts):
        seq = np.concatenate([prompt, np.asarray(rec["tokens"], np.int32)])
        rows = range(len(prompt) - 1, len(seq) - 1)
        out.append((rec["logits"], ref_logits(params, cfg, seq, rows)))
    return out


# -- (a) the full forward ------------------------------------------------------
def test_apply_logits_and_exit_distribution_match_the_reference(made):
    model, params, _ = made
    toks = tokens_of(48)
    with jax.default_matmul_precision("highest"):
        logits, p = model.apply(params, toks[None], exit_distribution=True)
    np.testing.assert_allclose(
        np.asarray(logits)[0], ref_logits(params, model.config, toks,
                                          range(48)), rtol=RTOL, atol=ATOL)
    _, _, gates = ref.hidden_states(params, ref_config(model.config), toks,
                                    jax.devices()[0])
    want = np.asarray(ref.exit_distribution(gates))
    assert want.shape == (48, T)
    np.testing.assert_allclose(np.asarray(p)[0], want, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(want.sum(-1), 1.0, rtol=1e-5)
    # a threshold of 1: every token leaves after the last pass; a lower one
    # would let some leave earlier (the rule, which no program runs)
    assert (np.asarray(ref.exit_pass(want, 1.0)) == T - 1).all()
    assert (np.asarray(ref.exit_pass(want, 0.5)) < T - 1).any()


@pytest.mark.parametrize("field,other", [
    ("total_ut_steps", 3), ("sandwich_norm", False)])
def test_each_new_field_changes_the_logits(made, field, other):
    model, params, _ = made
    toks = tokens_of(32)[None]
    a = np.asarray(model.apply(params, toks))
    b = np.asarray(CausalLM(tiny(**{field: other}), None).apply(params, toks))
    assert np.abs(a - b).max() > 1e-2


# -- (b) chunked prefill, then decode through the pool -------------------------
def test_served_logits_match_the_reference_at_every_generated_position(
        made, interpret_kernels):
    model, params, _ = made
    served = served_logits(made)
    for (got, want), n in zip(against_reference(served, params,
                                                model.config), NEW):
        assert got.shape == (n, VOCAB)
        np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


# -- (c) one pass and no post-norms IS the Llama backbone ----------------------
def test_one_pass_without_post_norms_is_mistrals_forward():
    cfg = tiny(total_ut_steps=1, sandwich_norm=False, loop_exit_gate=False,
               num_kv_heads=2)
    assert cfg.cache_layers == cfg.num_layers and not cfg.is_looped
    model = CausalLM(cfg, None)
    params = seeded(model)
    assert "exit_gate" not in params and \
        "attn_post_norm" not in params["layers"]
    toks = tokens_of(40)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(params, toks[None]))[0]
    rc = {k: v for k, v in ref_config(cfg).items() if k != "total_ut_steps"}
    want = np.asarray(ref_mistral.logits_rows(params, rc, toks,
                                              list(range(40)),
                                              jax.devices()[0]))
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


# -- (d) three wrong programs fail the same limits ------------------------------
def test_control_one_cache_layer_for_every_pass_fails(made, interpret_kernels,
                                                      monkeypatch):
    """Every pass reading and writing cache layer ``l`` (not ``t * L + l``):
    the keys of earlier positions are then the LAST pass's."""
    model, params, _ = made
    append, attend = fused_decode.paged_kv_append, fused_decode.flash_decode
    shared = lambda layer: layer % L
    monkeypatch.setattr(
        fused_decode, "paged_kv_append", lambda *a, layer, **kw:
        append(*a, layer=shared(layer), **kw))
    monkeypatch.setattr(
        fused_decode, "flash_decode", lambda *a, layer, **kw:
        attend(*a, layer=shared(layer), **kw))
    pairs = against_reference(served_logits(made), params, model.config)
    # the first token comes out of the chunk program, untouched
    assert all(close_to(got[:1], want[:1]) for got, want in pairs)
    assert not any(close_to(got[1:], want[1:]) for got, want in pairs)
    assert min(np.abs(got[1:] - want[1:]).max() for got, want in pairs) > 0.05


def test_control_one_pass_fewer_fails(made, interpret_kernels):
    model, params, mesh = made
    fewer = CausalLM(tiny(total_ut_steps=T - 1), mesh)
    served = served_logits((fewer, params, mesh))
    pairs = against_reference(served, params, model.config)
    assert not any(close_to(got, want) for got, want in pairs)
    assert min(np.abs(got - want).max() for got, want in pairs) > 0.05


def test_control_the_final_norm_outside_the_loop_fails(made,
                                                       interpret_kernels):
    """``T x L`` layers with the weights tiled and ONE pass: the same layer
    applications over the same cache layers, the final norm once at the end
    in place of once a pass."""
    model, params, mesh = made
    flat = CausalLM(tiny(total_ut_steps=1, loop_exit_gate=False,
                         num_layers=T * L), mesh)
    tiled = {k: v for k, v in params.items() if k != "exit_gate"}
    tiled["layers"] = jax.tree.map(
        lambda a: jnp.tile(a, (T,) + (1,) * (a.ndim - 1)), params["layers"])
    served = served_logits((flat, tiled, mesh))
    pairs = against_reference(served, params, model.config)
    assert not any(close_to(got, want) for got, want in pairs)
    assert min(np.abs(got - want).max() for got, want in pairs) > 0.05


# -- (e) the tie is structural ---------------------------------------------------
def test_parameters_are_one_stacks_and_the_injected_view_holds_L_dicts(made):
    model, params, _ = made
    cfg = model.config
    D, F, H, Dh = cfg.hidden_size, cfg.intermediate_size, cfg.num_heads, \
        cfg.head_dim
    layer = 4 * D * H * Dh + 3 * D * F + 4 * D
    count = sum(a.size for a in jax.tree.leaves(params))
    assert count == L * layer + 2 * VOCAB * D + D + (D + 1)
    assert all(a.shape[0] == L for a in jax.tree.leaves(params["layers"]))
    assert params["exit_gate"]["w"].shape == (D, 1)
    dparams = inject_decode_params(params, cfg)
    assert len(dparams["layers"]) == L and cfg.cache_layers == T * L
    assert {"n1_post", "n2_post"} <= set(dparams["layers"][0])


# -- (f) the pass loop is rolled -------------------------------------------------
def block_args(serve):
    return (serve._loop_params(), serve._cache, serve._last_dev,
            serve._pos_dev, serve._act_dev, jnp.asarray(serve._limit),
            jnp.asarray(serve._eos), serve._rng,
            jnp.asarray(serve.pool.page_table))


def block_jaxpr(serve):
    return str(jax.make_jaxpr(serve._block())(*block_args(serve)))


def test_the_decode_block_has_the_pallas_calls_of_the_one_pass_model(
        made, interpret_kernels):
    model, params, mesh = made
    looped = serve_of(made)
    once = serve_of((CausalLM(tiny(total_ut_steps=1, loop_exit_gate=False),
                              mesh),
                     {k: v for k, v in params.items() if k != "exit_gate"},
                     mesh))
    calls = {name: block_jaxpr(s).count("pallas_call")
             for name, s in (("looped", looped), ("once", once))}
    # norm + QKV, append, attention, projection + post-norm, MLP: five a
    # layer; and the final norm, once in the pass loop or once behind it
    assert calls["looped"] == calls["once"] == 5 * L + 1
    # the pass body's scope is in the program's metadata
    assert "ds_loop_pass" in looped._block().lower(
        *block_args(looped)).as_text(debug_info=True)
    assert looped._cache["k"].shape[0] == T * L == T * once._cache[
        "k"].shape[0]
    looped.close(), once.close()


def test_the_decode_block_compiles_once_over_the_warm_up_sequence(
        made, interpret_kernels):
    reg = MetricsRegistry().enable()
    serve = serve_of(made, registry=reg)
    fresh = lambda n, s: serve.submit(tokens_of(n, s), max_new_tokens=6)
    for i, n in enumerate((8, 16, 32, 64, 72)):
        fresh(72, i), fresh(n, 10 + i)
        serve.run()
        fresh(n, 20 + i)
        serve.run()
    serve.pool.check_no_leak()
    assert serve._block_fn._cache_size() == 1
    assert {f._cache_size() for f in serve._prefill_fns.values()} == {1}
    # T passes a chunk and a decode step
    chunks = reg.get("ds_serve_prefill_chunks_total").value
    blocks = reg.get("ds_serve_decode_row_slots_total").value / (4 * 4)
    assert reg.get("ds_serve_loop_passes_total").value == T * (
        chunks + 4 * blocks)
    serve.close()


# -- (g) the pool, and what it is served with -----------------------------------
def test_the_pool_has_a_layer_a_pass_and_layer_and_says_so(made):
    model, _, _ = made
    kind = cache_kind(model.config)
    assert type(kind) is FullPages and kind.cannot == {}
    reg = MetricsRegistry().enable()
    serve = serve_of(made, registry=reg)
    P = serve.pool.num_pages
    assert {k: v.shape for k, v in serve._cache.items()} == {
        "k": (T * L, P, 4, 128, 16), "v": (T * L, P, 4, 128, 16)}
    assert reg.get("ds_serve_kv_cache_layers").value == T * L
    # K and V rows of 4 heads x 16 in float32, in T * L layers
    assert reg.get("ds_serve_kv_bytes_per_token").value == \
        2 * T * L * 4 * 16 * 4
    serve.close()


def generated(made, prompts, new, **kw):
    serve = serve_of(made, **kw)
    reqs = [serve.submit(p, max_new_tokens=n) for p, n in zip(prompts, new)]
    serve.run()
    serve.pool.check_no_leak()
    out = [list(r.output_tokens) for r in reqs]
    return out, serve


@pytest.fixture(scope="module")
def plain_tokens(made):
    out, serve = generated(made, PROMPTS, NEW)
    serve.close()
    return out


@pytest.mark.parametrize("option", [
    "use_fused_decode", "kv_host_tier_pages", "quantize_kv_cache"])
def test_options_other_kinds_refuse_serve_the_same_tokens(made, plain_tokens,
                                                          option):
    """Position-pure pages in ``cache_layers`` layers refuse nothing: the
    unfused decode (the pass loop of ``forward_with_cache`` over the pool),
    the host tier and the int8 cache all serve; the first two the very
    tokens, the int8 cache tokens of its own rounding."""
    kw = {"use_fused_decode": dict(use_fused_decode=False),
          "kv_host_tier_pages": dict(kv_host_tier_pages=4),
          "quantize_kv_cache": dict(quantize_kv_cache=True)}[option]
    out, serve = generated(made, PROMPTS, NEW, **kw)
    assert (serve.engine._dparams is None) == (option != "kv_host_tier_pages")
    assert serve._cache["k"].shape[0] == T * L
    if option == "quantize_kv_cache":
        assert serve._cache["k"].dtype == jnp.int8
        assert [len(o) for o in out] == NEW
    else:
        assert out == plain_tokens
    serve.close()


def test_a_prefix_hit_serves_the_same_tokens(made, plain_tokens):
    """The second request of a prompt skips the pages the first left in the
    prefix cache, in every one of the T * L cache layers."""
    reg = MetricsRegistry().enable()
    serve = serve_of(made, registry=reg)
    for _ in range(2):
        req = serve.submit(PROMPTS[0], max_new_tokens=NEW[0])
        serve.run()
        assert list(req.output_tokens) == plain_tokens[0]
    assert reg.get("ds_serve_prefix_hit_tokens_total").value == 128
    serve.pool.check_no_leak()
    serve.close()


def test_a_handed_off_prefix_serves_the_same_tokens(made, plain_tokens):
    """``prefill_only`` on one engine, its pages adopted by another: the
    pages cross with all their cache layers."""
    sender, receiver = serve_of(made), serve_of(made)
    req = sender.submit(PROMPTS[0], prefill_only=True)
    sender.run()
    assert req.finish_reason == "prefill_done" and len(req.handoff) == 1
    chunk, payload = req.handoff[0]
    assert payload["k"].shape[0] == payload["v"].shape[0] == T * L
    adopted = receiver._handoff_work("adopt", {
        "chunks": [chunk], "pages": {0: handoff.encode_page(payload, "raw")}})
    assert adopted == {"adopted": 1}
    got = receiver.submit(PROMPTS[0], max_new_tokens=NEW[0])
    receiver.run()
    assert list(got.output_tokens) == plain_tokens[0]
    sender.close(), receiver.close()


def test_generate_decodes_over_the_contiguous_cache_of_cache_layers(
        made, plain_tokens, interpret_kernels):
    """``InferenceEngine.generate``: the fixed cache has ``cache_layers``
    layers and the fused step's rolled loop offsets into it by a traced
    layer."""
    model, params, mesh = made
    engine = deepspeed_tpu.init_inference(
        model, params=params, mesh=mesh,
        config={"dtype": "float32", "max_out_tokens": 256})
    assert engine._dparams is not None
    out = np.asarray(engine.generate(PROMPTS[3][None],
                                     max_new_tokens=NEW[3]))[0]
    assert engine._cache["k"].shape[0] == T * L
    assert list(out[len(PROMPTS[3]):]) == plain_tokens[3]


# -- what is refused ---------------------------------------------------------------
@pytest.mark.parametrize("fields,error,words", [
    (dict(early_exit_threshold=0.5), NotImplementedError,
     "leaves the pass loop early is not built"),
    (dict(total_ut_steps=1), ValueError, "gate of a looped stack"),
    (dict(total_ut_steps=0, loop_exit_gate=False), ValueError, ">= 1"),
    (dict(num_experts=4), NotImplementedError, "a looped stack beside"),
    (dict(attention="eva", eva_window=64), NotImplementedError,
     "a looped stack beside"),
    (dict(norm="layernorm", total_ut_steps=1, loop_exit_gate=False),
     NotImplementedError, "sandwich_norm outside layer_types"),
])
def test_model_config_refuses_by_name(fields, error, words):
    with pytest.raises(error, match=words):
        tiny(**fields)


def test_the_training_loss_is_refused(made):
    model, params, _ = made
    toks = tokens_of(16)[None]
    with pytest.raises(NotImplementedError, match="served only"):
        model.apply(params, toks, labels=toks)
