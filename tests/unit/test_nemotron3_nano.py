"""Layers that are ONE mixer each (``models/ssm_moe.py``; Nemotron-3-Nano) at
a tiny size on the CPU: hidden 64, four Mamba-2 heads of 16 in two groups over
a state of 16 with a convolution of 4 taps and blocks of 8 rows, four query
heads over two key-value heads of 16 without a position encoding, page 8,
chunks of 16, a router of 8 experts of which 4 are held, top-2, experts of two
matrices and relu^2, pattern ``M E M * E M``.  The plain reference
(``benchmarks/reference/nemotron3_nano.py``: the recurrence one step a token)
against the three forwards; the chunked scan, the decode kernel, the gated
grouped norm and the group map each against a loop; the relu^2 experts
through the two MLP kernels; the share of the experts and of the vocabulary
against the whole; the cache kind's arrays; the refusals; the counters."""

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
from deepspeed_tpu.ops.pallas import decode
from deepspeed_tpu.serving import cache_kind
from tests.unit._serving import as_found, read_served, tapped_engine, \
    with_noise

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
PATTERN = ("mamba2", "experts", "mamba2", "full_attention", "experts",
           "mamba2")
FIELDS = dict(
    vocab_size=96, hidden_size=64, intermediate_size=48, num_layers=6,
    num_heads=4, num_kv_heads=2, head_dim=16, max_seq_len=256,
    layer_types=PATTERN, activation="relu2", glu=False, ssm_num_heads=4,
    ssm_head_dim=16, ssm_groups=2, ssm_state_size=16, ssm_conv_kernel=4,
    ssm_chunk=8, moe_score_func="sigmoid", moe_route_scale=2.5,
    moe_select_bias=True, num_shared_experts=1, shared_intermediate_size=96,
    num_experts=4, moe_router_experts=8, moe_first_expert=0,
    num_experts_per_tok=2, moe_drop_tokens=False)
# the configuration file's keys the reference reads, at the same size
REF_CONFIG = {
    "hybrid_override_pattern": "MEM*EM", "mamba_num_heads": 4,
    "n_groups": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
    "layer_norm_epsilon": 1e-5, "rope_theta": 10000,
    "num_experts_per_tok": 2, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "expert_parallel": {"first_expert": 0}}
ENGINE = dict(num_slots=3, prefill_chunk=16, max_prefill_chunks=2,
              decode_block_tokens=4, max_out_tokens=96, kv_page_tokens=8,
              dtype="float32")


@pytest.fixture(scope="module")
def ref():
    if REPO not in sys.path:          # the reference imports its siblings
        sys.path.insert(0, REPO)
    spec = importlib.util.spec_from_file_location(
        "_nemotron_ref", os.path.join(REPO, "benchmarks", "reference",
                                      "nemotron3_nano.py"))
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
    yield from tapped_engine(lambda: serve_of(model))


# ------------------------------------------- (a) system against reference
def test_reference_agrees_with_the_no_cache_forward(ref, model):
    """``CausalLM.apply`` (the chunked scan over 83 + 5 pad rows in blocks of
    8, ``afmoe.attend``) against the reference's one-step recurrence and
    plain softmax, both float32."""
    m, params = model
    seq = np.random.default_rng(1).integers(0, 96, 83)
    got = np.asarray(jax.jit(m.apply)(params, seq[None]))[0]
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
    of 16: the state and the convolution's tail carried over and left as of
    the last REAL row, the later chunk's queries attending the K/V rows the
    earlier one wrote), then decode through the slot state and the attention
    layer's pages on the fused path, two requests in flight, float32: the
    program's LOGITS at every generated position are the reference's full
    forward's under the program's routing, and every token is the argmax of
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


@pytest.mark.parametrize("variant", ["gate_after_norm", "no_skip",
                                     "no_conv_bias", "no_dt_bias",
                                     "silu_experts", "rope_on_attn"])
def test_the_reference_tells_a_broken_equation(ref, model, variant):
    """The controls of the comparison above, each far from the program's
    forward."""
    m, params = model
    seq = np.random.default_rng(2).integers(0, 96, 40)
    got = np.asarray(jax.jit(m.apply)(params, seq[None]))[0]
    _, _, own = ref.hidden_states(params, REF_CONFIG, seq, jax.devices()[0],
                                  return_routing=True)
    wrong = ref_logits(ref, params, seq, list(range(40)), routing=list(own),
                       variant=(variant,))
    assert np.abs(got - wrong).max() > 0.05


def test_the_state_in_bf16_fails_a_float32_tolerance(ref, model):
    """The precision control: the reference with its state rounded to bf16
    after every token differs from the program (state float32) by far more
    than the program differs from the sound reference."""
    m, params = model
    seq = np.random.default_rng(2).integers(0, 96, 80)
    got = np.asarray(jax.jit(m.apply)(params, seq[None]))[0]
    _, _, own = ref.hidden_states(params, REF_CONFIG, seq, jax.devices()[0],
                                  return_routing=True)
    rows = list(range(80))
    sound = ref_logits(ref, params, seq, rows, routing=list(own))
    rounded = ref_logits(ref, params, seq, rows, routing=list(own),
                         variant=("bf16_state",))
    assert np.abs(got - sound).max() < 2e-4
    assert np.abs(got - rounded).max() > 2e-4


def test_replay_without_an_exchange_is_the_main_pass(ref, model):
    """The reference's second evaluation of single rows (the near-tie
    search's tool: the state BEFORE the row and the convolution's three rows
    before it, out of what the main pass kept) gives the main pass's hidden
    states where no exchange is made."""
    _, params = model
    dev = jax.devices()[0]
    seq = np.random.default_rng(4).integers(0, 96, 70)
    keep = {}
    x, _ = ref.hidden_states(params, REF_CONFIG, seq, dev, keep=keep)
    rows = [0, 1, 2, 3, 9, 40, 69]
    ref.keep_states(params, REF_CONFIG, rows, keep, dev)
    again, _ = ref.replay(params, REF_CONFIG, np.asarray(rows),
                          np.zeros((len(rows), 2), np.int64), keep, dev)
    np.testing.assert_allclose(again, np.asarray(x)[rows], atol=2e-5)


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
    assert ((best - got) / step).max() <= 12.0   # hidden 64
    serve.close()


# ------------------------------------------------- (b) the two shares
def test_the_two_ranks_shares_sum_to_the_whole_layer(ref, model):
    """Rank 0's and rank 1's routed parts of one expert layer + the shared
    expert ONCE = the uncut layer at top-2 of 8 (the reference with every
    expert held)."""
    both = dict(FIELDS, moe_router_experts=8)
    cfg = ModelConfig(**dict(both, num_experts=4))
    whole_cfg = ModelConfig(**dict(both, num_experts=8))
    whole = ssm_moe.init_params(whole_cfg, jax.random.PRNGKey(4))
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 23, 64))
    config = dict(REF_CONFIG, expert_parallel={"first_expert": 0})
    for l, j in ((1, 0), (4, 1)):
        _, a, kind, i = ssm_moe.layer_params(whole_cfg, whole, l)
        assert (kind, i) == ("experts", j)
        shared = afmoe.glu_mlp(h[0], a["shared"], "relu2")
        total = -shared                 # each share adds the shared expert
        for r in range(2):
            mine = {k: whole["layers"]["mlp"][k][:, 4 * r:4 * r + 4]
                    for k in ("w_up", "w_down")}
            total = total + afmoe.mlp(
                dataclasses.replace(cfg, moe_first_expert=4 * r),
                {"mlp": a}, h, mine, i)[0]
        w = ref.layer_weights(whole, config, l, jax.devices()[0])
        with jax.default_matmul_precision("highest"):
            _, local, fullest, _ = ref.route(
                h[0], w, None, 23, top_k=2, first=0, route_scale=2.5,
                route_norm=True)
            want = ref.expert_close(jnp.zeros_like(h[0]), h[0], w, local,
                                    cap=32)
        assert int(fullest) > 0
        np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-4)


def test_the_two_vocabulary_slices_concatenate_to_the_whole_head(model):
    """The same weights with the head's columns (and the embedding's rows)
    cut in two: each half's logits are the whole head's at its columns."""
    m, params = model
    seq = np.random.default_rng(6).integers(0, 48, 30)   # ids of rank 0
    whole = np.asarray(jax.jit(m.apply)(params, seq[None]))[0]
    half = CausalLM(dataclasses.replace(m.config, vocab_size=48), m.mesh)
    parts = []
    for r in range(2):
        cut = dict(params, lm_head=params["lm_head"][:, 48 * r:48 * r + 48],
                   embed={"tok": params["embed"]["tok"][:48]})
        parts.append(np.asarray(jax.jit(half.apply)(cut, seq[None]))[0])
    np.testing.assert_allclose(np.concatenate(parts, -1), whole, atol=1e-5)


# ------------------------------------------------ (c) the Mamba-2 pieces
def _inputs(s, H=4, P=16, G=2, N=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (s, H, P)),
            jax.nn.softplus(jax.random.normal(k[1], (s, H)) - 2.0),
            -jnp.exp(jax.random.normal(k[2], (H,))),
            jax.random.normal(k[3], (s, G, N)),
            jax.random.normal(k[4], (s, G, N)),
            jax.random.normal(k[5], (H, P, N)))


def _one_by_one(S, x, dt, a, Bm, Cm):
    """The recurrence as a loop over tokens, heads and groups in numpy."""
    S = np.array(S, np.float64)
    x, dt, a, Bm, Cm = (np.asarray(t, np.float64) for t in (x, dt, a, Bm, Cm))
    H, rep = x.shape[1], x.shape[1] // Bm.shape[1]
    ys = []
    for t in range(x.shape[0]):
        y = np.zeros(x.shape[1:])
        for h in range(H):
            g = h // rep                       # head i reads group i // rep
            S[h] = np.exp(dt[t, h] * a[h]) * S[h] \
                + np.outer(dt[t, h] * x[t, h], Bm[t, g])
            y[h] = S[h] @ Cm[t, g]
        ys.append(y)
    return S, np.stack(ys)


@pytest.mark.parametrize("s,block", [(8, 8), (24, 8), (5, 8), (32, 16)])
def test_the_chunked_scan_is_the_recurrence(s, block):
    """Lengths that are one block, several, and less than one; the B / C
    group map against a loop over heads."""
    x, dt, a, Bm, Cm, S0 = _inputs(s)
    S, y = ssm_moe.ssm_chunk_scan(S0, x, dt, a, Bm, Cm, block)
    S_want, y_want = _one_by_one(S0, x, dt, a, Bm, Cm)
    np.testing.assert_allclose(S, S_want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(y, y_want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("chunks", [(16, 16), (16, 8, 16), (8, 16, 8)])
def test_a_state_carried_over_chunks_is_the_whole_sequences(chunks):
    x, dt, a, Bm, Cm, S0 = _inputs(sum(chunks), seed=1)
    S, ys, at = S0, [], 0
    for c in chunks:
        cut = lambda t: t[at:at + c]
        S, y = ssm_moe.ssm_chunk_scan(S, cut(x), cut(dt), a, cut(Bm),
                                      cut(Cm), 8)
        ys.append(y)
        at += c
    S_want, y_want = _one_by_one(S0, x, dt, a, Bm, Cm)
    np.testing.assert_allclose(S, S_want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.concatenate(ys), y_want, rtol=2e-5,
                               atol=2e-5)


def test_pad_rows_leave_state_and_tail_as_of_the_last_real_row(model):
    """A bucket of 16 of which 11 rows are real (``valid_len``) against the
    11 rows alone in a bucket of their own... of 16 with other pad tokens:
    the views that come back are the same, and they are those of 11 real
    rows (the recurrence against a chunk cut at 11, through the tail's
    ``valid_len`` and ``dt = 0``)."""
    m, params = model
    cfg = m.config
    state, tail = ssm_moe.state_shapes(cfg, 1)
    kv = (1, 1, 2, 32, 16)
    cache = {"k": jnp.zeros(kv), "v": jnp.zeros(kv),
             "state": jax.random.normal(jax.random.PRNGKey(7), state),
             "tail": jax.random.normal(jax.random.PRNGKey(8), tail)}
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 16, 64))
    other = x.at[:, 11:].set(3.0)
    run = jax.jit(lambda x, start: ssm_moe.cached_layers(
        cfg, params, x, cache, start, 11))
    for start in (0, 16):
        (y1, c1), (y2, c2) = run(x, start), run(other, start)
        np.testing.assert_allclose(y1[:, :11], y2[:, :11], atol=1e-5)
        for k in ("state", "tail"):
            np.testing.assert_allclose(c1[k], c2[k], atol=1e-5)
    # at position 0 the slot's old state and tail are not read
    fresh = dict(cache, state=jnp.zeros(state), tail=jnp.zeros(tail))
    y3, c3 = ssm_moe.cached_layers(cfg, params, x, fresh, 0, 11)
    np.testing.assert_allclose(run(x, 0)[0], y3, atol=1e-5)
    np.testing.assert_allclose(run(x, 0)[1]["state"], c3["state"], atol=1e-5)


def test_the_state_is_kept_packed_and_unpacks_to_itself():
    S = jax.random.normal(jax.random.PRNGKey(0), (3, 8, 16, 32))
    for pk in (1, 2, 4):
        packed = decode.ssm_state_pack(S, pk)
        assert packed.shape == (3, 8 // pk, 32, pk * 16)
        np.testing.assert_array_equal(decode.ssm_state_unpack(packed, pk), S)
    # the published sizes: two heads of 64 across the 128 lanes
    assert decode.ssm_heads_per_tile(64, 64, 8) == 2
    assert decode.ssm_heads_per_tile(4, 16, 2) == 2
    assert decode.ssm_heads_per_tile(8, 128, 8) == 1


@pytest.mark.parametrize("live", [
    [True, False, True, True, False], [False] * 5, None],
    ids=["live_and_parked", "none_live", "no_mask"])
def test_the_decode_kernel_is_one_step_of_the_recurrence(live):
    """``ssm_decode_step`` in interpret mode against one step of the loop,
    on the packed state in place: a parked row's state is neither read nor
    written, and the kernel visits the live rows only."""
    B, H, P, G, N, L = 5, 4, 64, 2, 128, 3      # whole (8, 128) tiles
    k = jax.random.split(jax.random.PRNGKey(3), 6)
    x = jax.random.normal(k[0], (B, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (B, H)) - 2.0)
    a = -jnp.exp(jax.random.normal(k[2], (H,)))
    Bm, Cm = (jax.random.normal(k[i], (B, G, N)) for i in (3, 4))
    S0 = jax.random.normal(k[5], (L, B, H, P, N))
    pk = decode.ssm_heads_per_tile(H, P, G)
    state = decode.ssm_state_pack(S0, pk)
    mask = None if live is None else jnp.asarray(live)
    y, new, visited = jax.jit(lambda s: decode.ssm_decode_step(
        s, x, dt, a, Bm, Cm, layer=1, live=mask, impl="interpret"))(state)
    y_x, new_x, visited_x = decode.ssm_decode_step(
        state, x, dt, a, Bm, Cm, layer=1, live=mask, impl="xla")
    rows = range(B) if live is None else np.flatnonzero(live)
    assert int(visited) == len(rows) and int(visited_x) == B
    got = decode.ssm_state_unpack(new, pk)
    np.testing.assert_array_equal(new[0], state[0])      # other layers
    np.testing.assert_array_equal(new[2], state[2])
    for b in range(B):
        if b in rows:
            S_want, y_want = _one_by_one(S0[1, b], x[b][None], dt[b][None],
                                         a, Bm[b][None], Cm[b][None])
            np.testing.assert_allclose(got[1, b], S_want, rtol=2e-5,
                                       atol=2e-5)
            np.testing.assert_allclose(y[b], y_want[0], rtol=2e-5, atol=2e-5)
            np.testing.assert_allclose(y_x[b], y_want[0], rtol=2e-5,
                                       atol=2e-5)
        else:
            np.testing.assert_array_equal(new[1, b], state[1, b])
            np.testing.assert_array_equal(new_x[1, b], state[1, b])


def test_the_gated_grouped_norm_is_a_loop_over_groups():
    cfg = ModelConfig(**FIELDS)
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    y, z = (jax.random.normal(k[i], (7, 64)) for i in (0, 1))
    a = {"o_norm": 1.0 + 0.1 * jax.random.normal(k[2], (64,)),
         "wo": jnp.zeros((64, 64))}
    got = ssm_moe.gated_group_norm(cfg, a, y, z)
    g = np.asarray(y * jax.nn.silu(z), np.float64)     # the gate first
    want = np.zeros_like(g)
    for r in range(7):
        for grp in range(2):                           # 32 channels a group
            v = g[r, 32 * grp:32 * grp + 32]
            want[r, 32 * grp:32 * grp + 32] = v / np.sqrt(
                (v * v).mean() + 1e-5)
    np.testing.assert_allclose(got, want * np.asarray(a["o_norm"]),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------- (d) relu^2 through the kernels
@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_relu2_experts_through_the_two_mlp_kernels(impl):
    k = jax.random.split(jax.random.PRNGKey(2), 8)
    B, D, F, E = 6, 128, 256, 4
    h, r = (jax.random.normal(k[i], (B, D)) for i in (0, 1))
    wu, wd = (jax.random.normal(k[2], (2, E, D, F)) * D ** -0.5,
              jax.random.normal(k[3], (2, E, F, D)) * F ** -0.5)
    combine = jax.nn.relu(jax.random.normal(k[4], (B, E)))
    relu2 = lambda u: jnp.square(jax.nn.relu(u))
    with jax.default_matmul_precision("highest"):
        want = r + relu2(h @ wu[1, 0]) @ wd[1, 0]
        got = decode.fused_mlp(h, r, wu[1, 0], wd[1, 0], None, act="relu2",
                               impl=impl)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
        want = r + sum(combine[:, e:e + 1] * (relu2(h @ wu[1, e]) @ wd[1, e])
                       for e in range(E))
        got = decode.fused_moe_mlp(h, r, combine, wu, wd, None, layer=1,
                                   act="relu2", impl=impl)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_the_experts_are_stored_padded_with_zeros():
    cfg = ModelConfig(**FIELDS)
    p = ssm_moe.init_params(cfg, jax.random.PRNGKey(0))["layers"]["mlp"]
    # the routed experts to the lane tile, the shared one to 512
    assert p["w_up"].shape == (2, 4, 64, 128) \
        and p["w_down"].shape == (2, 4, 128, 64)
    assert p["shared"]["w_up"].shape == (2, 64, 512)
    assert not np.asarray(p["w_up"][..., 48:]).any() \
        and not np.asarray(p["w_down"][..., 48:, :]).any() \
        and np.asarray(p["w_up"][..., :48]).all()
    assert not np.asarray(p["shared"]["w_up"][..., 96:]).any()


@pytest.mark.parametrize("width,tile,stored", [
    (1856, ssm_moe.LANE_TILE, 1920),      # the routed experts: 15 lane tiles
    (3712, ssm_moe.WIDTH_TILE, 4096),     # the shared expert: 29 is a prime
    (1920, ssm_moe.LANE_TILE, 1920), (1024, ssm_moe.LANE_TILE, 1024)])
def test_the_stored_widths_of_the_published_model(width, tile, stored):
    assert ssm_moe.padded_width(width, tile) == stored
    assert ssm_moe.padded_width(width) == ssm_moe.padded_width(
        width, ssm_moe.LANE_TILE)


@pytest.mark.parametrize("n_live", [0, 50, 128, 200])
def test_the_expert_block_at_256_rows_works_the_live_rows_tiles(n_live):
    """``afmoe.fused_experts`` (shared expert, router, this chip's share of
    the routed experts) on 256 bf16 rows, where ``fused_moe_mlp`` cuts its
    passes to the live rows' tiles: the live rows as the XLA forms give
    them, and the routing counts the same."""
    cfg = ModelConfig(**dict(FIELDS, hidden_size=128, intermediate_size=200,
                             shared_intermediate_size=128))
    params = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16),
        with_noise(ssm_moe.init_params(cfg, jax.random.PRNGKey(9))))
    assert params["layers"]["mlp"]["w_up"].shape[-1] == 256
    dparams = ssm_moe.inject(cfg, params)
    lp = next(lp for lp in dparams["layers"] if "gate_w" in lp)
    B = 256
    assert decode.moe_row_tile(B, 2) == 128
    k = jax.random.split(jax.random.PRNGKey(n_live), 2)
    h, x = (jax.random.normal(k[i], (B, 128), jnp.bfloat16) for i in (0, 1))
    live = np.zeros(B, bool)
    live[np.random.RandomState(n_live).permutation(B)[:n_live]] = True
    block = lambda impl: afmoe.fused_experts(
        cfg, dparams, lp, 1, h, x, afmoe.moe_counts_zero(cfg),
        jnp.asarray(live), impl)
    (y1, s1), (y2, s2) = block("interpret"), block("xla")
    np.testing.assert_allclose(np.asarray(y1, np.float32)[live],
                               np.asarray(y2, np.float32)[live],
                               rtol=3e-2, atol=3e-2)
    for a, b in zip(s1, s2):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("live", [[True, False, True], [True, True, True]],
                         ids=["a_parked_row", "all_live"])
def test_fused_layers_through_the_kernels_match_their_references(live):
    """One decode step of the whole stack with every kernel in interpret mode
    against the XLA forms, at sizes the kernels take (a head of 64 in tiles
    of two, a state of 128)."""
    cfg = ModelConfig(**dict(
        FIELDS, hidden_size=128, ssm_num_heads=4, ssm_head_dim=64,
        ssm_groups=2, ssm_state_size=128, num_heads=2, num_kv_heads=1,
        head_dim=128, intermediate_size=128, shared_intermediate_size=128))
    params = with_noise(ssm_moe.init_params(cfg, jax.random.PRNGKey(9)))
    dparams = ssm_moe.inject(cfg, params)
    B, page = 3, 8
    state, tail = ssm_moe.state_shapes(cfg, B)
    k = jax.random.split(jax.random.PRNGKey(1), 4)
    cache = {"k": jax.random.normal(k[0], (1, 7, 1, page, 128)),
             "v": jax.random.normal(k[1], (1, 7, 1, page, 128)),
             "state": jax.random.normal(k[2], state),
             "tail": jax.random.normal(k[3], tail)}
    x = jax.random.normal(jax.random.PRNGKey(2), (B, 128))
    pos = jnp.asarray([3, 9, 12], jnp.int32)
    table = jnp.asarray([[1, 2], [3, 4], [5, 6]], jnp.int32)
    mask = jnp.asarray(live)
    step = lambda impl: ssm_moe.fused_layers(
        cfg, dparams, x, dict(cache), pos, table, moe_live=mask, impl=impl)
    (x1, c1, s1), (x2, c2, s2) = step("interpret"), step("xla")
    rows = np.flatnonzero(live)
    np.testing.assert_allclose(x1[rows], x2[rows], rtol=2e-4, atol=2e-4)
    for name in ("state", "tail"):
        np.testing.assert_allclose(c1[name], c2[name], rtol=2e-4, atol=2e-4)
    # (a parked row appends whatever its stream holds to its own page)
    pages = np.asarray(table)[rows].ravel()
    for name in ("k", "v"):
        np.testing.assert_allclose(c1[name][:, pages], c2[name][:, pages],
                                   rtol=2e-4, atol=2e-4)
    # a parked row's state and tail did not move
    for b in np.flatnonzero(~np.asarray(live)):
        np.testing.assert_array_equal(c1["state"][:, b], cache["state"][:, b])
        np.testing.assert_array_equal(c1["tail"][:, b], cache["tail"][:, b])
    # the routing counts agree; the state's: live pairs, and rows visited
    for a, b in zip(s1[:-1], s2[:-1]):
        np.testing.assert_array_equal(a, b)
    n = int(mask.sum())
    assert list(s1[-1]) == [3 * n, 3 * n] and list(s2[-1]) == [3 * n, 3 * B]


# ------------------------------------------------ (e) the kind, the refusals
def test_the_kind_its_arrays_and_a_token_at_the_attention_layers_bytes(model):
    m, _ = model
    kind = cache_kind.cache_kind(m.config)
    assert type(kind) is cache_kind.FullPagesAndState
    # one block of the chunked scan, the kinds of layer that keep a cache:
    # the model module's and the pattern's, not the cache kind's
    assert kind.chunk_rows == ssm_moe.chunk_rows(m.config) == 8
    assert kind.what == "mamba2 / full_attention layers"
    assert m.config.cache_layers == 1 and m.config.num_expert_layers == 2
    state, tail = ssm_moe.state_shapes(m.config, 3)
    assert state == (3, 3, 2, 16, 32) and tail == (3, 3, 3, 128)
    assert ssm_moe.slot_state_bytes(m.config, jnp.bfloat16) \
        == 3 * (4 * 16 * 16 * 4 + 3 * 128 * 2)
    serve = serve_of(model)
    assert serve._cache["state"].shape == state
    assert serve._cache["k"].shape[0] == 1
    assert serve.prefix_cache is None
    serve.close()
    # the published widths: [4, slots, 32, 128, 128] float32, 2 MB a layer
    real = ModelConfig(**dict(
        FIELDS, ssm_num_heads=64, ssm_head_dim=64, ssm_groups=8,
        ssm_state_size=128, layer_types=("mamba2",) * 4 + ("experts",) * 2))
    state, tail = ssm_moe.state_shapes(real, 256)
    assert state == (4, 256, 32, 128, 128) and tail == (4, 256, 3, 6144)
    assert ssm_moe.slot_state_bytes(real, jnp.bfloat16) \
        == 4 * (2097152 + 36864)


OPTIONS = {
    "handoff": dict(role="prefill"),
    "kv_host_tier_pages": dict(config=dict(kv_host_tier_pages=4)),
    "quantize_kv_cache": dict(config=dict(quantize_kv_cache=True)),
    "use_fused_decode": dict(config=dict(use_fused_decode=False)),
}


@pytest.mark.parametrize("option", list(OPTIONS))
def test_every_cannot_entry_is_refused_by_name(model, option):
    m, params = model
    kw = dict(OPTIONS[option])
    config = dict(ENGINE, **kw.pop("config", {}))
    with pytest.raises(NotImplementedError) as err:
        deepspeed_tpu.init_serving(m, config=config, params=params,
                                   mesh=m.mesh, **kw)
    assert "mamba2 / full_attention layers" in str(err.value)
    assert cache_kind.FullPagesAndState.cannot[option] in str(err.value)


def test_training_and_parallel_meshes_are_refused(model):
    m, params = model
    seq = np.zeros((1, 8), np.int32)
    with pytest.raises(NotImplementedError, match="training loss"):
        m.apply(params, seq, labels=seq)
    mesh = build_mesh(devices=jax.devices()[:2], tp=2)
    with pytest.raises(NotImplementedError, match="tp, ep, sp or pp"):
        CausalLM(m.config, mesh).apply(params, seq)


def test_counters_count_state_steps_resets_and_scanned_rows(model):
    from deepspeed_tpu.monitor.metrics import get_registry

    reg = get_registry()
    was = reg.enabled
    reg.enable()
    try:
        before = dict(reg.snapshot())
        serve = serve_of(model)
        rng = np.random.default_rng(5)
        reqs = [serve.submit(rng.integers(0, 96, n), max_new_tokens=9)
                for n in (20, 7)]
        serve.run()
        snap = reg.snapshot()
        grew = lambda k: snap[k] - before.get(k, 0)
        # chunks: 16 + 4 (a bucket of 8) and 7 (a bucket of 8): 32 rows in
        # each of the three mamba2 layers, 32 - 31 = 1 of them... pad
        assert grew("ds_serve_ssm_chunk_rows_total") == 3 * (16 + 8 + 8)
        assert grew("ds_serve_prefill_pad_rows_total") == (8 - 4) + (8 - 7)
        assert grew("ds_serve_state_resets_total") == 2
        # 8 decode steps a request after its chunk's token, three layers
        assert grew("ds_serve_state_row_steps_total") == 3 * 2 * 8
        assert grew("ds_serve_state_row_steps_visited_total") \
            >= grew("ds_serve_state_row_steps_total")
        assert snap["ds_serve_state_bytes"] == 3 * ssm_moe.slot_state_bytes(
            model[0].config, jnp.float32)
        assert grew("ds_serve_moe_assignments_total") == 2 * 2 * 2 * 8
        # three float32 slots: a call is one pass over one tile, worked
        assert grew("ds_serve_moe_row_tiles_total") \
            == grew("ds_serve_moe_row_tiles_worked_total") \
            == grew("ds_serve_moe_expert_slots_total") / 4
        assert all(len(r.output_tokens) == 9 for r in reqs)
        serve.close()
    finally:
        if not was:
            reg.disable()


def test_the_mix_is_accepted_and_is_its_own_form():
    cfg = ModelConfig(**FIELDS)
    assert cfg.is_afmoe and cfg.is_mixer and not cfg.is_kda_mla
    assert afmoe.form(cfg) is ssm_moe
    assert ssm_moe.kinds(cfg) == [("mamba2", 0), ("experts", 0),
                                  ("mamba2", 1), ("full_attention", 0),
                                  ("experts", 1), ("mamba2", 2)]


@pytest.mark.parametrize("fields,match", [
    (dict(layer_types=("experts",) * 6), "layer_types must name"),
    (dict(layer_types=("mamba2", "linear_attention") * 3),
     "layer_types must name"),
    (dict(ssm_state_size=0), "need"),
    (dict(ssm_groups=3), "whole groups"),
    (dict(glu=True), "one-mixer form"),
    (dict(activation="silu"), "one-mixer form"),
    (dict(attn_output_gate=True), "attn_output_gate"),
    (dict(sliding_window=16), "sliding_window"),
    (dict(num_dense_layers=1, dense_intermediate_size=8), "num_dense_layers"),
    (dict(num_experts=0, moe_router_experts=0), "experts layers need"),
    (dict(moe_drop_tokens=True), "one-mixer form"),
])
def test_config_says_what_it_cannot_build(fields, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig(**dict(FIELDS, **fields))


def test_other_forms_refuse_the_mixers_fields():
    with pytest.raises(ValueError, match="one-mixer layer form"):
        ModelConfig(layer_types=("full_attention",) * 2, num_layers=2,
                    num_experts=2, moe_drop_tokens=False, ssm_num_heads=4)
    with pytest.raises(ValueError, match="belong to the layer form"):
        ModelConfig(ssm_state_size=16)
