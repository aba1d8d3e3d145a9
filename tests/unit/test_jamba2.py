"""Mamba-1 layers beside multi-query attention, a dense MLP behind every
mixer (``models/ssm_moe.py`` kinds ``mamba1`` and ``mlp``; AI21 Jamba2-3B) at
a tiny size on the CPU that keeps the published pattern: 14 layers with
attention at layer 7 (28 one-mixer layers: seven ``[mamba1, mlp]`` pairs
rolled, the attention layer and its MLP, six pairs rolled), hidden 32, 64
channels over a state of 16, a ``dt`` bottleneck of 6 (no power of two), 4
query heads over ONE key-value head of 8, a tied head, page 8, chunks of 16.
The plain reference (``benchmarks/reference/jamba2.py``: the recurrence one
step a token) against the three forwards; the two kernels in interpret mode
against their XLA forms and against a loop; the stacked-weight option of the
three fused kernels; pad rows, parked rows, a slot used again; the tied head;
the refusals; the cache kind's arrays and counters."""

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
from deepspeed_tpu.models import CausalLM, ModelConfig, afmoe, ssm_moe
from deepspeed_tpu.monitor.metrics import get_registry
from deepspeed_tpu.ops.pallas import common, decode
from deepspeed_tpu.ops.pallas import selective_scan as scan
from deepspeed_tpu.serving import cache_kind
from tests.unit._serving import as_found, read_served, tapped_engine, \
    with_noise

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))


def pattern(layers, period, offset):
    out = []
    for i in range(layers):
        out += ["full_attention" if i % period == offset else "mamba1", "mlp"]
    return tuple(out)


FIELDS = dict(
    vocab_size=96, hidden_size=32, intermediate_size=48, num_layers=28,
    num_heads=4, num_kv_heads=1, head_dim=8, max_seq_len=256,
    norm_eps=1e-6, activation="silu", glu=True, tie_embeddings=True,
    layer_types=pattern(14, 14, 7), ssm_inner_size=64, ssm_dt_rank=6,
    ssm_inner_norms=True, ssm_state_size=16, ssm_conv_kernel=4, ssm_chunk=8,
    moe_drop_tokens=False)
# the configuration file's keys the reference reads, at the same size
REF_CONFIG = {"num_hidden_layers": 14, "attn_layer_period": 14,
              "attn_layer_offset": 7, "num_attention_heads": 4,
              "rms_norm_eps": 1e-6, "mamba_dt_rank": 6}
ENGINE = dict(num_slots=3, prefill_chunk=16, max_prefill_chunks=2,
              decode_block_tokens=4, max_out_tokens=96, kv_page_tokens=8,
              dtype="float32")


@pytest.fixture(scope="module")
def ref():
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    spec = importlib.util.spec_from_file_location(
        "_jamba2_ref", os.path.join(REPO, "benchmarks", "reference",
                                    "jamba2.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def model():
    m = CausalLM(ModelConfig(**FIELDS),
                 build_mesh(devices=jax.devices()[:1]))
    # gains of exactly 1 would hide a dropped norm
    return m, with_noise(m.init(jax.random.PRNGKey(0)))


def ref_logits(ref, params, seq, rows, **kw):
    return np.asarray(ref.logits_rows(params, REF_CONFIG, np.asarray(seq),
                                      rows, jax.devices()[0], **kw))


def serve_of(model, **kw):
    m, params = model
    return deepspeed_tpu.init_serving(m, config=dict(ENGINE, **kw),
                                      params=params, mesh=m.mesh)


@pytest.fixture(scope="module")
def tapped(model):
    yield from tapped_engine(lambda: serve_of(model))


# ------------------------------------------- (a) system against reference
def test_reference_agrees_with_the_no_cache_forward(ref, model):
    """``CausalLM.apply`` (two rolled runs of pairs around the unrolled
    attention layer; the scan over 83 + 5 pad rows) against the reference's
    one-step recurrence and plain softmax, both float32: 2e-4 is float32
    rounding through 28 sub-layers (3e-6 measured), far under what any
    broken equation moves (the controls below)."""
    m, params = model
    assert ssm_moe.runs(m.config) == [(0, 7), (14, 0), (15, 0), (16, 6)]
    seq = np.random.default_rng(1).integers(0, 96, 83)
    got = np.asarray(jax.jit(m.apply)(params, seq[None]))[0]
    want = ref_logits(ref, params, seq, list(range(83)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("prompts", [(27, 16), (37, 5)],
                         ids=["16_and_11_then_on_a_chunk",
                              "two_chunks_and_a_pad_bucket_then_tiny"])
def test_chunked_prefill_then_decode_is_the_references_one_forward(
        ref, model, tapped, prompts):
    """Prefill in chunks of UNEQUAL size (16 then 11 real rows in a bucket
    of 16: the state and the convolution's tail carried over and left as of
    the last REAL row, the later chunk's queries attending the K/V rows the
    earlier one wrote), then decode through the slot state and the attention
    layer's pages on the fused path, two requests in flight, float32: the
    program's LOGITS at every generated position are the reference's full
    forward's (5e-4: float32 through two kinds of program), and every token
    is the reference's argmax."""
    ps = [np.random.default_rng(n).integers(0, 96, n) for n in prompts]
    served = read_served(tapped, ps, [21, 13])
    assert tapped[1].pool.pages_used == 0
    for p, rec in zip(ps, served):
        seq = np.concatenate([p, rec["tokens"]])
        rows = list(range(len(p) - 1, len(seq) - 1))
        want = ref_logits(ref, model[1], seq, rows)
        np.testing.assert_allclose(rec["logits"], want, rtol=5e-4, atol=5e-4)
        assert list(want.argmax(-1)) == list(rec["tokens"])


@pytest.mark.parametrize("variant,least", [
    ("bf16_state", 1e-3), ("bf16_a", 5e-4), ("no_dt_norm", 0.05),
    ("no_dt_bias", 0.05)])
def test_the_reference_tells_a_broken_equation(ref, model, variant, least):
    """The controls of the comparison above: a state rounded to bfloat16
    after every token, ``A`` rounded to bfloat16, a dropped inner norm, ``dt``
    without its bias each differ from the program by more than the program
    differs from the sound reference (2e-4)."""
    m, params = model
    seq = np.random.default_rng(2).integers(0, 96, 80)
    got = np.asarray(jax.jit(m.apply)(params, seq[None]))[0]
    rows = list(range(80))
    assert np.abs(got - ref_logits(ref, params, seq, rows)).max() < 2e-4
    wrong = ref_logits(ref, params, seq, rows, variant=(variant,))
    assert np.abs(got - wrong).max() > least


def test_the_slot_state_is_the_references_recurrence(ref, model, tapped):
    """The slot's state after a request (never freed: as of the last token
    the program fed, the last output but one) against the reference's state
    after that token, every Mamba layer, in the cache's packed layout."""
    taps, serve = tapped
    p = np.random.default_rng(8).integers(0, 96, 29)
    with as_found(serve):
        r = serve.submit(p, max_new_tokens=9)
        serve.run()
        got = np.asarray(serve._cache["state"][:, r.slot])
    seq = np.concatenate([p, r.output_tokens])
    states = {}
    ref_logits(ref, model[1], seq, [len(seq) - 2], states=states)
    want = np.stack([np.asarray(scan.mamba1_pack(states[i], 64))
                     for i in sorted(states)])
    assert want.shape == got.shape == (13, 1, 16, 64)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_a_slot_used_again_starts_from_zero(model, tapped):
    """A short request in the slot a LONGER one left (its state and tail
    never cleared): the tokens of a fresh engine's."""
    taps, serve = tapped
    rng = np.random.default_rng(9)
    long, short = rng.integers(0, 96, 41), rng.integers(0, 96, 7)
    with as_found(serve):
        a = serve.submit(long, max_new_tokens=12)
        serve.run()
        b = serve.submit(short, max_new_tokens=12)
        serve.run()
        assert a.slot == b.slot
    fresh = serve_of(model)
    c = fresh.submit(short, max_new_tokens=12)
    fresh.run()
    assert list(b.output_tokens) == list(c.output_tokens)
    fresh.close()


def test_bf16_serving_stays_within_the_drivers_bound(ref, model):
    """What the benchmark's ``verify`` checks, at the serving dtype, with the
    state float32 whatever the stream's and tail and K/V pages in the
    stream's."""
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
    assert ((best - got) / step).max() <= 12.0   # hidden 32
    serve.close()


# --------------------------------------------------- (b) the two kernels
def _inputs(s, T=2, N=16, W=128, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[5], (T, N, W)),
            jax.random.normal(k[0], (s, T * W)),
            jax.nn.softplus(jax.random.normal(k[1], (s, T * W)) - 2.0),
            -jnp.exp(jax.random.normal(k[2], (T, N, W))),
            jax.random.normal(k[3], (s, N)), jax.random.normal(k[4], (s, N)))


def _one_by_one(S, u, dt, A, Bm, Cm):
    """The recurrence as a loop over tokens in numpy float64, on the
    published orientation [d_inner, N]."""
    T, N, W = A.shape
    flat = lambda t: np.asarray(t, np.float64).transpose(0, 2, 1).reshape(
        T * W, N)
    S, A = flat(S), flat(A)
    u, dt, Bm, Cm = (np.asarray(t, np.float64) for t in (u, dt, Bm, Cm))
    ys = []
    for t in range(u.shape[0]):
        S = np.exp(dt[t][:, None] * A) * S \
            + (dt[t] * u[t])[:, None] * Bm[t][None]
        ys.append(S @ Cm[t])
    return S.reshape(T, W, N).transpose(0, 2, 1), np.stack(ys)


@pytest.mark.parametrize("s,impl", [(8, "interpret"), (24, "interpret"),
                                    (24, "xla"), (21, "interpret")])
def test_the_chunk_scan_is_the_recurrence(s, impl):
    """``selective_scan_chunk`` (the kernel in interpret mode: one block of
    eight rows and several; 21 rows are no whole blocks and take the XLA
    form by ``selective_scan_reference_reason``) against the loop."""
    S0, u, dt, A, Bm, Cm = _inputs(s)
    S, y = scan.selective_scan_chunk(S0, u, dt, A, Bm, Cm, impl=impl)
    S_want, y_want = _one_by_one(S0, u, dt, A, Bm, Cm)
    np.testing.assert_allclose(S, S_want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(y, y_want, rtol=2e-5, atol=2e-5)
    assert (scan.selective_scan_reference_reason(s, 16, 128) is None) \
        == (s % 8 == 0)


def test_a_state_carried_over_chunks_is_the_whole_sequences():
    """Chunks of 16, 8 and 16 rows, the state handed on, and pad rows (``dt``
    0) behind the last real one: the whole sequence's state and rows."""
    S0, u, dt, A, Bm, Cm = _inputs(40, seed=1)
    S, ys, at = S0, [], 0
    for c in (16, 8, 16):
        cut = lambda t: t[at:at + c]
        S, y = scan.selective_scan_chunk(S, cut(u), cut(dt), A, cut(Bm),
                                         cut(Cm), impl="interpret")
        ys.append(y)
        at += c
    S_want, y_want = _one_by_one(S0, u, dt, A, Bm, Cm)
    np.testing.assert_allclose(S, S_want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.concatenate(ys), y_want, rtol=2e-5,
                               atol=2e-5)
    padded = scan.selective_scan_chunk(
        S0, *(jnp.pad(t, ((0, 8), (0, 0))) for t in (u, dt)), A,
        *(jnp.pad(t, ((0, 8), (0, 0)), constant_values=3.0)
          for t in (Bm, Cm)), impl="interpret")[0]
    np.testing.assert_allclose(padded, S_want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layer", [1, "traced"])
def test_the_decode_step_visits_the_live_rows_in_place(layer):
    """``mamba1_decode_step`` (interpret) against its XLA form and the loop:
    the live rows' states of ONE layer of the stack move, parked rows and
    the other layers stay bit for bit, and a traced layer index (a rolled
    run's counter) reads the same tile of ``A``."""
    L, B = 3, 5
    _, u, dt, _, Bm, Cm = _inputs(B, seed=2)
    state = jax.random.normal(jax.random.PRNGKey(9), (L, B, 2, 16, 128))
    A = -jnp.exp(jax.random.normal(jax.random.PRNGKey(8), (L, 2, 16, 128)))
    live = jnp.array([True, False, True, True, False])
    step = lambda l, impl: scan.mamba1_decode_step(
        state, u, dt, A, Bm, Cm, layer=l, live=live, impl=impl)
    if layer == "traced":
        y, new, visited = jax.jit(lambda l: step(l, "interpret"))(
            jnp.int32(1))
    else:
        y, new, visited = step(1, "interpret")
    y_x, new_x, visited_x = step(1, "xla")
    assert (int(visited), int(visited_x)) == (3, 5)
    np.testing.assert_allclose(new, new_x, rtol=2e-6, atol=2e-6)
    on = np.asarray(live)
    np.testing.assert_allclose(np.asarray(y)[on], np.asarray(y_x)[on],
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(new)[1][~on],
                                  np.asarray(state)[1][~on])
    np.testing.assert_array_equal(np.asarray(new)[[0, 2]],
                                  np.asarray(state)[[0, 2]])
    for b in np.flatnonzero(on):
        S_want, y_want = _one_by_one(state[1, b], u[b:b + 1], dt[b:b + 1],
                                     A[1], Bm[b:b + 1], Cm[b:b + 1])
        np.testing.assert_allclose(new[1, b], S_want, rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(y[b], y_want[0], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kernel", ["fused_norm_qkv", "fused_proj_norm",
                                    "fused_mlp", "fused_mlp_two_matrices"])
def test_a_fused_kernel_reads_a_layer_of_a_stack_in_place(kernel):
    """``layer=`` (static and traced): the kernel on the whole stack at that
    layer is the kernel on the layer's own slice (to float32 rounding: the
    interpreter runs the two grid forms as two programs)."""
    k = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(k[0], (4, 128))
    ctx = jax.random.normal(k[1], (4, 256))
    gain = 1 + 0.1 * jax.random.normal(k[2], (128,))
    w = 0.1 * jax.random.normal(k[3], (3, 128, 256))
    wo = 0.1 * jax.random.normal(k[4], (3, 256, 128))

    def call(w, wo, layer):
        kw = dict(layer=layer, impl="interpret")
        if kernel == "fused_norm_qkv":
            return decode.fused_norm_qkv(x, gain, None, w, kind="rmsnorm",
                                         **kw)
        if kernel == "fused_proj_norm":
            return jnp.concatenate(decode.fused_proj_norm(
                ctx, x, wo, None, gain, kind="rmsnorm", **kw), -1)
        gate = w if kernel == "fused_mlp" else None
        return decode.fused_mlp(x, x, w, wo, gate, act="silu", **kw)

    want = call(w[2], wo[2], None)
    np.testing.assert_allclose(call(w, wo, 2), want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        jax.jit(lambda l: call(w, wo, l))(jnp.int32(2)), want, rtol=1e-5,
        atol=1e-5)
    other = call(w, wo, 1)
    assert np.abs(np.asarray(other) - np.asarray(want)).max() > 1e-2


def test_the_engine_on_the_kernels_serves_the_xla_forms_tokens(monkeypatch):
    """The whole decode block and the chunk programs with every kernel in
    interpret mode (128 channels: one whole lane tile, so neither kernel
    falls back; the rolled runs hand the kernels a traced layer) against
    the same engine on the XLA forms."""
    fields = dict(FIELDS, num_layers=10, layer_types=pattern(5, 5, 2),
                  hidden_size=128, ssm_inner_size=128, head_dim=32,
                  intermediate_size=128)
    m = CausalLM(ModelConfig(**fields), build_mesh(devices=jax.devices()[:1]))
    params = with_noise(m.init(jax.random.PRNGKey(1)))
    ps = [np.random.default_rng(n).integers(0, 96, n) for n in (19, 8)]

    def served():
        serve = deepspeed_tpu.init_serving(m, config=dict(ENGINE),
                                           params=params, mesh=m.mesh)
        reqs = [serve.submit(p, max_new_tokens=6) for p in ps]
        serve.run()
        serve.close()
        return [list(r.output_tokens) for r in reqs]

    want = served()
    monkeypatch.setattr(common, "default_impl", lambda: "interpret")
    assert served() == want
    assert not [r for r in common.reference_selections()
                if r[0] in ("mamba1_decode_step", "selective_scan_chunk")
                and "16 x 128" in r[1]]


# ------------------------------------------------- (c) the form's pieces
def test_pad_rows_leave_state_and_tail_as_of_the_last_real_row(model):
    """A bucket of 16 of which 11 rows are real (``valid_len``) against the
    11 rows alone padded to the scan's 16: state and tail equal, and the
    real rows' streams too."""
    m, params = model
    cfg = m.config
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 16, 32))
    state, tail = ssm_moe.state_shapes(cfg, 1)
    cache = {"k": jnp.zeros((1, 1, 1, 32, 8)), "v": jnp.zeros((1, 1, 1, 32, 8)),
             "state": jnp.ones(state), "tail": jnp.ones(tail)}
    noisy = x.at[:, 11:].set(9.0)
    a, ca = ssm_moe.cached_layers(cfg, params, noisy, cache, 0, 11)
    b, cb = ssm_moe.cached_layers(cfg, params, x.at[:, 11:].set(-3.0), cache,
                                  0, 11)
    for key in ("state", "tail"):
        np.testing.assert_array_equal(ca[key], cb[key])
    np.testing.assert_array_equal(a[:, :11], b[:, :11])
    # position 0: whatever the slot held (ones) was read as zeros
    zero = dict(cache, state=jnp.zeros(state), tail=jnp.zeros(tail))
    c, cc = ssm_moe.cached_layers(cfg, params, noisy, zero, 0, 11)
    np.testing.assert_array_equal(ca["state"], cc["state"])


def test_the_head_is_the_embedding(model):
    """No ``lm_head`` anywhere: the logits are the final norm's rows against
    the embedding, in ``apply`` and in the injected view."""
    m, params = model
    assert "lm_head" not in params and m.config.tie_embeddings
    dparams = ssm_moe.inject(m.config, params)
    assert "lm_head" not in dparams
    assert dparams["mlp"]["w_up"] is params["mlp"]["w_up"]
    assert dparams["ssm1"]["w_in"] is params["ssm1"]["w_in"]
    assert dparams["ssm1"]["rates"].shape == (13, 1, 16, 64)
    seq = np.random.default_rng(5).integers(0, 96, 12)
    logits = jax.jit(m.apply)(params, seq[None])[0]
    x = afmoe.embed(m.config, params["embed"]["tok"], seq[None], jnp.float32)
    x = ssm_moe.apply_layers(m.config, params, x)[0]
    want = afmoe.rms(x, params["final_norm"]["scale"], 1e-6) \
        @ params["embed"]["tok"].T
    np.testing.assert_allclose(logits, want, rtol=1e-5, atol=1e-5)


def test_the_cache_kind_and_its_counters(model):
    """One cache kind (``FullPagesAndState``), its state and tail the
    module's shapes, pages in the ONE attention layer; the chunk programs
    move ``ds_serve_mamba1_chunk_rows_total`` (bucket x 13 layers a chunk)
    and the decode blocks the state row steps, live = visited on the XLA
    form's every row."""
    reg = get_registry()
    was = reg.enabled
    reg.enable()
    try:
        serve = serve_of(model)
        assert isinstance(serve.kind, cache_kind.FullPagesAndState)
        assert serve.kind.what == "mamba1 / full_attention layers"
        assert serve.kind.chunk_rows == 8
        assert serve._cache["state"].shape == (13, 3, 1, 16, 64)
        assert serve._cache["tail"].shape == (13, 3, 3, 64)
        assert serve._cache["k"].shape[0] == 1
        value = lambda n: reg.snapshot().get(n, 0)
        before = {n: value(n) for n in (
            "ds_serve_mamba1_chunk_rows_total", "ds_serve_ssm_chunk_rows_total",
            "ds_serve_state_row_steps_total",
            "ds_serve_state_row_steps_visited_total")}
        r = serve.submit(np.arange(21) % 96, max_new_tokens=9)
        serve.run()
        grew = {n: value(n) - v for n, v in before.items()}
        assert grew["ds_serve_mamba1_chunk_rows_total"] == (16 + 8) * 13
        assert grew["ds_serve_ssm_chunk_rows_total"] == 0
        assert grew["ds_serve_state_row_steps_total"] == 8 * 13
        assert grew["ds_serve_state_row_steps_visited_total"] >= 8 * 13
        assert len(r.output_tokens) == 9
        serve.close()
    finally:
        if not was:
            reg.disable()


# ------------------------------------------------------- (d) the refusals
@pytest.mark.parametrize("change,error", [
    (dict(layer_types=("mamba1", "mlp", "sliding_attention", "mlp"),
          num_layers=4, sliding_window=8), ValueError),
    (dict(layer_types=("linear_attention", "mlp"), num_layers=2), ValueError),
    (dict(layer_types=("full_attention", "mlp"), num_layers=2), ValueError),
    (dict(layer_types=("mamba1", "mamba2"), num_layers=2),
     NotImplementedError),
    (dict(ssm_dt_rank=0), ValueError),
    (dict(ssm_inner_size=0), ValueError),
    (dict(ssm_num_heads=4), ValueError),
    (dict(activation="gelu"), ValueError),
    (dict(layer_types=("mamba1", "experts"), num_layers=2, num_experts=4),
     ValueError),
    (dict(sandwich_norm=True), ValueError),
], ids=["beside_a_sliding_layer", "beside_a_linear_layer",
        "without_a_state_space_layer", "both_state_space_kinds",
        "no_dt_rank", "no_inner_size", "a_mamba2_size", "another_activation",
        "gated_experts", "post_norms"])
def test_the_new_kinds_are_refused_elsewhere(change, error):
    with pytest.raises(error):
        ModelConfig(**dict(FIELDS, **change))


def test_the_published_mlp_letter_of_the_two_matrix_form_builds():
    """Nemotron-H's ``-`` (a dense relu^2 MLP layer) beside mamba2 layers:
    the ``mlp`` kind without a gate."""
    cfg = ModelConfig(**dict(
        FIELDS, layer_types=("mamba2", "mlp", "full_attention", "mlp"),
        num_layers=4, activation="relu2", glu=False, tie_embeddings=False,
        ssm_inner_size=0, ssm_dt_rank=0, ssm_inner_norms=False,
        ssm_num_heads=4, ssm_head_dim=16, ssm_groups=2))
    m = CausalLM(cfg, build_mesh(devices=jax.devices()[:1]))
    params = m.init(jax.random.PRNGKey(0))
    assert set(params["mlp"]) == {"w_up", "w_down"} and "lm_head" in params
    out = jax.jit(m.apply)(params, jnp.arange(12)[None])
    assert out.shape == (1, 12, 96) and bool(jnp.isfinite(out).all())


def test_parallel_axes_are_refused(model):
    m, params = model
    if len(jax.devices()) < 2:
        pytest.skip("one device")
    mesh = build_mesh(devices=jax.devices()[:2], tp=2)
    with pytest.raises(NotImplementedError):
        ssm_moe.apply_layers(m.config, params, jnp.zeros((1, 8, 32)), mesh)
    with pytest.raises(NotImplementedError):
        m.apply(params, jnp.zeros((1, 8), jnp.int32),
                labels=jnp.zeros((1, 8), jnp.int32))


def test_the_configuration_file_builds_the_published_model():
    """``benchmarks/configs/jamba2-3b.json``: every key of the catalog row,
    nothing reduced, and its ``model_config`` group is the published model
    (shapes only: 3,029,337,472 parameters, 9.32 MB of state a slot)."""
    import json

    with open(os.path.join(REPO, "benchmarks", "configs",
                           "jamba2-3b.json")) as f:
        config = json.load(f)
    assert config["reduced"] == {} and config["num_hidden_layers"] == 28
    cfg = ModelConfig(**config["model_config"])
    assert cfg.layer_types == pattern(28, config["attn_layer_period"],
                                      config["attn_layer_offset"])
    assert (cfg.hidden_size, cfg.ssm_inner_size, cfg.ssm_dt_rank,
            cfg.ssm_state_size, cfg.intermediate_size, cfg.vocab_size) == (
        config["hidden_size"], config["mamba_expand"] * config["hidden_size"],
        config["mamba_dt_rank"], config["mamba_d_state"],
        config["intermediate_size"], config["vocab_size"])
    shapes = jax.eval_shape(
        lambda k: ssm_moe.init_params(cfg, k), jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) \
        == config["parameters"] == 3029337472
    assert ssm_moe.runs(cfg) == [(0, 7), (14, 0), (15, 0), (16, 13),
                                 (42, 0), (43, 0), (44, 6)]
    assert ssm_moe.slot_state_bytes(cfg, jnp.bfloat16) \
        == 26 * (5120 * 16 * 4 + 3 * 5120 * 2)
    assert dataclasses.replace(cfg, tie_embeddings=False).tie_embeddings \
        is False


# --------------------------- the older one-mixer configuration's programs
# sha256 of the StableHLO text (locations stripped) of the chunk program and
# of the decode block of the tiny Nemotron-3-Nano of test_nemotron3_nano.py,
# lowered on the tree BEFORE ISSUE 66 (commit 002e36a): the two new kinds,
# the rolled runs and the fused kernels' ``layer=`` change neither (its call
# of ``ssm_decode_step`` is the parent's).  A later PR that changes one of
# these programs ON PURPOSE replaces its line here and says so.  ISSUE 67
# did, both: the tiny model's routed experts are stored 128 wide (one lane
# tile for their 48 columns) where they were 512, and nothing else moved
# (3 float32 slots: ``fused_moe_mlp`` keeps its one pass over all rows).
NEMOTRON_PROGRAMS = {"chunk": "7ae28079d054fd31",
                     "block": "ab638f2e745e2017"}


def test_nemotron_lowers_to_the_parents_programs():
    import hashlib
    import re

    here = os.path.dirname(__file__)

    def load(name):
        spec = importlib.util.spec_from_file_location(
            "_jamba2_" + name, os.path.join(here, name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    nemotron = load("test_nemotron3_nano")
    lowered = load("test_dots3_note")._lowered(nemotron.FIELDS,
                                               nemotron.ENGINE)
    for what, text in lowered.items():
        text = re.sub(r"loc\(.*?\)", "", text)
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
            NEMOTRON_PROGRAMS[what], what


def test_mamba_without_inner_norms_and_a_long_chunk_build():
    """Mamba's own form (no RMSNorm on dt, B, C: ``ssm_inner_norms`` off)
    has no such gains, and a sequence longer than ``KERNEL_ROWS`` takes the
    fused kernels' XLA forms: the same logits as the rows' own."""
    cfg = ModelConfig(**dict(FIELDS, ssm_inner_norms=False, num_layers=6,
                             layer_types=pattern(3, 3, 1)))
    m = CausalLM(cfg, build_mesh(devices=jax.devices()[:1]))
    params = m.init(jax.random.PRNGKey(2))
    assert not {"dt_norm", "b_norm", "c_norm"} & set(params["ssm1"])
    seq = np.random.default_rng(4).integers(0, 96, ssm_moe.KERNEL_ROWS + 16)
    long = np.asarray(jax.jit(m.apply)(params, seq[None]))[0]
    short = np.asarray(jax.jit(m.apply)(params, seq[None, :40]))[0]
    np.testing.assert_allclose(long[:40], short, rtol=2e-4, atol=2e-4)
