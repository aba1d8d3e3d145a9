"""MoE tests (reference analog: tests/unit/moe/test_moe.py, SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.comm.mesh import build_mesh, set_global_mesh
from deepspeed_tpu.models import causal_lm
from deepspeed_tpu.moe import MoE, compute_capacity, moe_mlp, topk_gating
from deepspeed_tpu.moe import sharded_moe
from deepspeed_tpu.moe.sharded_moe import ROW_TILE, _moe_grouped


def test_topk_gating_properties(rng):
    N, E, k = 64, 8, 2
    gates = jax.nn.softmax(jax.random.normal(rng, (N, E)), axis=-1)
    C = compute_capacity(N, E, k, capacity_factor=1.25)
    combine, dispatch, aux = topk_gating(gates, k, C)
    assert combine.shape == (N, E, C)
    assert (np.asarray(dispatch.sum(axis=2)) <= 1).all()  # one slot per (token, expert)
    per_expert = np.asarray(dispatch.sum(axis=(0, 2)))
    assert (per_expert <= C).all()  # capacity respected
    # kept tokens have combine weights normalized to ~1
    w = np.asarray(combine.sum(axis=(1, 2)))
    kept = np.asarray(dispatch.sum(axis=(1, 2))) == k  # tokens with all k slots kept
    np.testing.assert_allclose(w[kept], 1.0, rtol=1e-5)
    assert np.isfinite(float(aux)) and float(aux) > 0


def test_aux_loss_uniform_is_one(rng):
    # perfectly uniform routing -> aux loss == 1 (E * E * (1/E) * (1/E))
    N, E = 64, 8
    gates = jnp.full((N, E), 1.0 / E)
    # break argmax ties deterministically with tiny noise on distinct experts
    gates = gates + jax.nn.one_hot(jnp.arange(N) % E, E) * 1e-6
    _, _, aux = topk_gating(gates, 1, compute_capacity(N, E, 1, 2.0))
    np.testing.assert_allclose(float(aux), 1.0, rtol=1e-3)


def test_single_expert_equals_dense(rng):
    """E=1, k=1, ample capacity: MoE must reproduce the dense MLP exactly."""
    from types import SimpleNamespace
    B, S, D, F = 2, 16, 8, 32
    x = jax.random.normal(rng, (B, S, D))
    k1, k2, k3 = jax.random.split(rng, 3)
    w_up = jax.random.normal(k1, (1, D, F)) * 0.1
    w_gate = jax.random.normal(k2, (1, D, F)) * 0.1
    w_down = jax.random.normal(k3, (1, F, D)) * 0.1
    params = {"gate_w": jnp.zeros((D, 1)), "w_up": w_up, "w_gate": w_gate,
              "w_down": w_down}
    cfg = SimpleNamespace(num_experts=1, num_experts_per_tok=1,
                          moe_capacity_factor=1.0, activation="silu", glu=True)
    y, aux = moe_mlp(params, x, cfg, mesh=None)
    dense = (jax.nn.silu(x @ w_gate[0]) * (x @ w_up[0])) @ w_down[0]
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense), rtol=1e-4, atol=1e-5)


def test_moe_layer_api(rng):
    layer = MoE(hidden_size=16, num_experts=4, k=2, intermediate_size=32)
    params = layer.init(rng)
    x = jax.random.normal(rng, (2, 8, 16))
    y, aux = layer.apply(params, x)
    assert y.shape == x.shape
    assert np.isfinite(float(aux))


def test_mixtral_training_on_ep_mesh(devices, rng):
    """Mixtral-family model trains on an ep=4 mesh; loss decreases."""
    import deepspeed_tpu

    mesh = build_mesh(fsdp=2, ep=4, devices=devices)
    set_global_mesh(mesh)
    model = causal_lm("mixtral-tiny", mesh=mesh, num_layers=2, hidden_size=64,
                      intermediate_size=128, num_heads=4, num_kv_heads=2,
                      vocab_size=256, num_experts=4)
    ds_config = {"train_batch_size": 8, "gradient_accumulation_steps": 1,
                 "zero_optimization": {"stage": 1},
                 "optimizer": {"type": "Adam", "params": {"lr": 3e-3}},
                 "steps_per_print": 1000}
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=ds_config, mesh=mesh)
    toks = jax.random.randint(rng, (8, 64), 0, 256)
    losses = []
    for _ in range(5):
        loss = engine.forward((toks, toks))
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_split_params_moe_vs_dense_mask(rng, devices):
    """Structural classification: only true MoE blocks (with a router) are
    masked as expert params; dense MLPs using the same leaf names are not."""
    mesh = build_mesh(fsdp=8, devices=devices)
    set_global_mesh(mesh)
    from deepspeed_tpu.moe import split_params_into_moe_groups

    toks = jnp.zeros((2, 32), jnp.int32)
    dense = causal_lm("llama-tiny", mesh=mesh, num_layers=2, hidden_size=64,
                      intermediate_size=128, num_heads=4, num_kv_heads=2,
                      vocab_size=128)
    mask = split_params_into_moe_groups(dense.init(rng, toks))
    assert not any(jax.tree.leaves(mask))  # dense model: nothing is expert

    moe = causal_lm("mixtral-tiny", mesh=mesh, num_layers=2, hidden_size=64,
                    intermediate_size=128, num_heads=4, num_kv_heads=2,
                    vocab_size=128, num_experts=4)
    p = moe.init(rng, toks)
    m = split_params_into_moe_groups(p)
    assert m["layers"]["mlp"]["w_up"] and m["layers"]["mlp"]["w_down"]
    assert not m["layers"]["mlp"]["gate_w"]       # router is non-expert
    assert not m["layers"]["attn"]["wq"]


def test_top1_keeps_gate_gradient(rng):
    """k=1 combine weights must equal the raw gate prob (router gets task
    gradient), not be normalized to 1."""
    N, E = 32, 4
    gates = jax.nn.softmax(jax.random.normal(rng, (N, E)), axis=-1)
    combine, dispatch, _ = topk_gating(gates, 1, compute_capacity(N, E, 1, 2.0))
    w = np.asarray(combine.sum(axis=(1, 2)))
    kept = np.asarray(dispatch.sum(axis=(1, 2))) == 1
    top1 = np.asarray(gates.max(axis=-1))
    np.testing.assert_allclose(w[kept], top1[kept], rtol=1e-5)


def test_scatter_dispatch_matches_einsum(rng):
    """The O(N·k·D) scatter path must reproduce the GShard one-hot einsum
    path exactly (VERDICT r2 weak #9)."""
    from dataclasses import replace

    from deepspeed_tpu.models.config import ModelConfig
    from deepspeed_tpu.moe.sharded_moe import moe_mlp

    cfg = ModelConfig(num_experts=4, num_experts_per_tok=2, hidden_size=16,
                      intermediate_size=32, num_layers=1, num_heads=2,
                      vocab_size=64)
    x = jax.random.normal(rng, (2, 8, 16))
    params = {
        "gate_w": jax.random.normal(jax.random.fold_in(rng, 1), (16, 4)) * 0.1,
        "w_up": jax.random.normal(jax.random.fold_in(rng, 2), (4, 16, 32)) * 0.1,
        "w_gate": jax.random.normal(jax.random.fold_in(rng, 3), (4, 16, 32)) * 0.1,
        "w_down": jax.random.normal(jax.random.fold_in(rng, 4), (4, 32, 16)) * 0.1,
    }
    cfg.moe_dispatch = "scatter"
    y_s, aux_s = moe_mlp(params, x, cfg)
    cfg.moe_dispatch = "einsum"
    y_e, aux_e = moe_mlp(params, x, cfg)
    np.testing.assert_allclose(np.asarray(y_s), np.asarray(y_e),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(aux_s), float(aux_e), rtol=1e-6)

    # gradients agree too (dispatch/combine both differentiable)
    def loss(p, mode):
        cfg.moe_dispatch = mode
        y, aux = moe_mlp(p, x, cfg)
        return (y.astype(jnp.float32) ** 2).sum() + aux

    gs = jax.grad(lambda p: loss(p, "scatter"))(params)
    ge = jax.grad(lambda p: loss(p, "einsum"))(params)
    for a, b in zip(jax.tree.leaves(gs), jax.tree.leaves(ge)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def _moe_fixture(rng, E=4, k=2, D=16, F=32, B=2, S=8):
    from deepspeed_tpu.models.config import ModelConfig

    cfg = ModelConfig(num_experts=E, num_experts_per_tok=k, hidden_size=D,
                      intermediate_size=F, num_layers=1, num_heads=2,
                      vocab_size=64)
    x = jax.random.normal(rng, (B, S, D))
    params = {
        "gate_w": jax.random.normal(jax.random.fold_in(rng, 1), (D, E)) * 0.1,
        "w_up": jax.random.normal(jax.random.fold_in(rng, 2), (E, D, F)) * 0.1,
        "w_gate": jax.random.normal(jax.random.fold_in(rng, 3), (E, D, F)) * 0.1,
        "w_down": jax.random.normal(jax.random.fold_in(rng, 4), (E, F, D)) * 0.1,
    }
    return cfg, x, params


def _dense_mixture(params, x, cfg):
    """Reference: every expert applied to every token, combined by the
    normalized top-k router weights — what no-drop MoE must equal."""
    B, S, D = x.shape
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    xt = x.reshape(-1, D)
    gates = jax.nn.softmax(
        xt.astype(jnp.float32) @ params["gate_w"].astype(jnp.float32), -1)
    import numpy as _np

    act = jax.nn.silu
    up = jnp.einsum("nd,edf->enf", xt, params["w_up"])
    gate = jnp.einsum("nd,edf->enf", xt, params["w_gate"])
    per_e = jnp.einsum("enf,efd->end", act(gate) * up, params["w_down"])
    topv, topi = jax.lax.top_k(gates, k)
    w = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    sel = jnp.take_along_axis(per_e.transpose(1, 0, 2),
                              topi[:, :, None], axis=1)        # [N, k, D]
    y = (sel * w[..., None]).sum(1)
    return y.reshape(B, S, D)


@pytest.mark.parametrize("dispatch", ["scatter", "einsum"])
def test_no_drop_matches_dense_mixture(rng, dispatch):
    """drop_tokens=False (VERDICT r4 item 6): with capacity covering every
    token, the MoE output equals the dense top-k mixture exactly, even at a
    capacity factor that would otherwise drop most tokens."""
    from deepspeed_tpu.moe.sharded_moe import moe_mlp

    cfg, x, params = _moe_fixture(rng)
    cfg.moe_dispatch = dispatch
    cfg.moe_capacity_factor = 0.25        # would drop heavily if honored
    cfg.moe_drop_tokens = False
    y, aux = moe_mlp(params, x, cfg)
    want = _dense_mixture(params, x, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    # and with dropping at that factor the outputs must NOT match (the
    # no-drop path is doing real work)
    cfg.moe_drop_tokens = True
    y_drop, _ = moe_mlp(params, x, cfg)
    assert np.abs(np.asarray(y_drop) - np.asarray(want)).max() > 1e-3


def test_rts_noop_when_capacity_ample(rng):
    """Random token selection reorders only the capacity contest: with room
    for every token the result is identical to sequential selection."""
    from deepspeed_tpu.moe.sharded_moe import moe_mlp

    cfg, x, params = _moe_fixture(rng)
    cfg.moe_capacity_factor = 100.0
    y0, aux0 = moe_mlp(params, x, cfg)
    cfg.moe_use_rts = True
    y1, aux1 = moe_mlp(params, x, cfg, rng=jax.random.PRNGKey(5))
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(aux0), float(aux1), rtol=1e-6)


def test_rts_randomizes_overflow_victims(rng):
    """Under a tight capacity, sequence order decides the dropped tokens;
    RTS decides randomly — different keys must drop different tokens, and
    late-sequence tokens must stop being the systematic victims."""
    from deepspeed_tpu.moe.sharded_moe import moe_mlp

    cfg, x, params = _moe_fixture(rng, B=1, S=32)
    cfg.moe_capacity_factor = 0.25
    cfg.moe_use_rts = True

    def kept_mask(key):
        from deepspeed_tpu.moe.sharded_moe import (compute_capacity,
                                                   topk_assignments)
        xt = x.reshape(-1, cfg.hidden_size)
        gates = jax.nn.softmax(
            xt.astype(jnp.float32) @ params["gate_w"].astype(jnp.float32), -1)
        C = compute_capacity(xt.shape[0], cfg.num_experts,
                             cfg.num_experts_per_tok,
                             cfg.moe_capacity_factor)
        _, pos, w, _ = topk_assignments(gates, cfg.num_experts_per_tok, C,
                                        key, True)
        return np.asarray((w > 0).any(-1))

    m1, m2 = kept_mask(jax.random.PRNGKey(0)), kept_mask(jax.random.PRNGKey(9))
    assert m1.shape == (32,)
    assert not np.array_equal(m1, m2), "different keys must change victims"
    # model-level: rng=None still works (content-derived key)
    y, _ = moe_mlp(params, x, cfg)
    assert np.isfinite(np.asarray(y)).all()


# ----------------------------------------------------------------------
# a chip's share of the experts: _moe_grouped(assign=) hands the grouped
# matmuls an odd number of ROW_TILE-row tiles
# ----------------------------------------------------------------------
def _held_share_case(n_held, N=256, k=4, E=4, D=32, F=16, L=3, seed=0):
    """Stacked experts and an assignment of which ``n_held`` of the N*k
    choices, scattered over the tokens, are of experts held here."""
    from types import SimpleNamespace

    cfg = SimpleNamespace(num_experts=E, num_experts_per_tok=k,
                          activation="silu", glu=True)
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    stack = {n: jax.random.normal(key, (L, E) + shape) * 0.2
             for n, key, shape in (("w_up", keys[0], (D, F)),
                                   ("w_gate", keys[1], (D, F)),
                                   ("w_down", keys[2], (F, D)))}
    x = jax.random.normal(keys[3], (N, D))
    draw = np.random.RandomState(seed + n_held)
    flat = np.full(N * k, E, np.int32)
    flat[draw.permutation(N * k)[:n_held]] = draw.randint(0, E, n_held)
    local = jnp.asarray(flat.reshape(N, k))
    weight = jnp.where(local < E, jax.random.uniform(keys[4], (N, k)), 0.0)
    return cfg, stack, x, (weight, local)


def _lhs_rows(fn, *args):
    """The ``lhs`` lengths of the grouped matmuls ``fn`` traces."""
    return [e.invars[0].aval.shape[0] for e in jax.make_jaxpr(fn)(
        *args).jaxpr.eqns if e.primitive.name == "ragged_dot_general"]


# (held rows, tokens, layer of the stack | None, lhs rows of the matmuls)
HELD_SHARE_CASES = {
    "no_row_held": (0, 256, None, 1024 + ROW_TILE),
    "one_row_held": (1, 256, None, 1024 + ROW_TILE),
    "the_mean_share": (128, 256, None, 1024 + ROW_TILE),
    "all_but_one_row_held": (1023, 256, None, 1024 + ROW_TILE),
    "every_row_held": (1024, 256, None, 1024 + ROW_TILE),
    "stacked_layer_2": (150, 256, 2, 1024 + ROW_TILE),
    "stacked_layer_1_every_row": (1024, 256, 1, 1024 + ROW_TILE),
    "two_tiles_become_three": (30, 64, 0, 3 * ROW_TILE),
    "an_odd_number_of_tiles_as_it_is": (200, 96, 2, 3 * ROW_TILE),
    "rows_no_multiple_of_a_tile": (500, 250, 2, 1000),
    "bucket_under_a_tile": (10, 8, 1, 32)}


@pytest.mark.parametrize("n_held,N,layer,lhs", HELD_SHARE_CASES.values(),
                         ids=HELD_SHARE_CASES.keys())
def test_held_share_pad_is_the_parents_form(n_held, N, layer, lhs,
                                            monkeypatch):
    """A tile of pad rows behind the sorted rows against the parent's form
    (the N*k sorted rows and no more; a tile no row count is a multiple of
    gives it): equal to 0.0 for every count of held rows, and the matmuls'
    ``lhs`` is an odd number of tiles wherever N*k is a whole number."""
    cfg, stack, x, assign = _held_share_case(n_held, N=N)
    p = stack if layer is not None else {n: a[1] for n, a in stack.items()}
    ly = None if layer is None else jnp.asarray(layer, jnp.int32)

    def block():                # a new function a call: traced anew
        return lambda p, x, a: _moe_grouped(
            p, x, None, cfg, False, layer=ly, assign=a)[0]

    got = jax.jit(block())(p, x, assign)
    assert _lhs_rows(block(), p, x, assign) == [lhs] * 3
    assert lhs % ROW_TILE or lhs // ROW_TILE % 2
    monkeypatch.setattr(sharded_moe, "ROW_TILE", 1 << 30)
    n_rows = N * cfg.num_experts_per_tok
    assert _lhs_rows(block(), p, x, assign) == [n_rows] * 3
    want = jax.jit(block())(p, x, assign)
    assert float(jnp.abs(got - want).max()) == 0.0
    if n_held:
        assert float(jnp.abs(want).max()) > 0.0


@pytest.mark.parametrize("n_held", [0, 100, 385, 1024])
def test_held_share_against_a_loop_over_experts(n_held):
    """Independent of ``ragged_dot``: each held expert applied densely to
    every token, weighted by the token's assignments to it."""
    cfg, stack, x, (weight, local) = _held_share_case(n_held, seed=3)
    got, _ = _moe_grouped(stack, x, None, cfg, False,
                          layer=jnp.asarray(2, jnp.int32),
                          assign=(weight, local))
    want = jnp.zeros_like(x)
    for e in range(cfg.num_experts):
        w = {n: a[2, e] for n, a in stack.items()}
        y = (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]
        want = want + y * jnp.sum(jnp.where(local == e, weight, 0.0), -1,
                                  keepdims=True)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("stacked", [False, True])
def test_held_share_pad_leaves_the_routers_own_path_alone(stacked):
    """Without ``assign`` (OLMoE's chunk programs, ``moe_mlp``'s dropless
    training path) the N*k sorted rows go through the three grouped matmuls
    as they are, and nothing is padded: the parent's program (its jaxpr and
    the lowered text of the OLMoE cell's chunk programs are the parent's to
    the byte, PERF.md Findings PR 46)."""
    cfg, stack, x, assign = _held_share_case(100)
    n_rows = x.shape[0] * cfg.num_experts_per_tok
    assert n_rows % (2 * ROW_TILE) == 0
    p = stack if stacked else {n: a[1] for n, a in stack.items()}
    ly = jnp.asarray(1, jnp.int32) if stacked else None
    gates = jax.nn.softmax(x[:, :cfg.num_experts], axis=-1)
    routed = jax.make_jaxpr(lambda p, x, g: _moe_grouped(
        p, x, g, cfg, False, layer=ly))(p, x, gates)
    assert _lhs_rows(lambda p, x, g: _moe_grouped(
        p, x, g, cfg, False, layer=ly), p, x, gates) == [n_rows] * 3
    assert "pad" not in {e.primitive.name for e in routed.jaxpr.eqns}
    assert _lhs_rows(lambda p, x, a: _moe_grouped(
        p, x, None, cfg, False, layer=ly, assign=a), p, x, assign) == [
            n_rows + ROW_TILE] * 3
