"""Pipeline-parallel tests (reference analog: tests/unit/runtime/pipe/,
SURVEY.md §4): parity of the SPMD pipeline against sequential execution,
and end-to-end training of the built-in model over a pp mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.comm.mesh import build_mesh, set_global_mesh
from deepspeed_tpu.runtime.pipe import (LayerSpec, PipelineModule, spmd_pipeline)


class TanhLayer:
    def __init__(self, dim):
        self.dim = dim

    def init(self, rng, x):
        return {"w": jax.random.normal(rng, (self.dim, self.dim)) * 0.3}

    def apply(self, params, x):
        return jnp.tanh(x @ params["w"])


def test_spmd_pipeline_matches_sequential(devices, rng):
    mesh = build_mesh(fsdp=2, pp=4, devices=devices)
    set_global_mesh(mesh)
    L, D, B, M = 8, 16, 8, 4
    w = jax.random.normal(rng, (L, D, D)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(1), (B, D))

    def stage_fn(wl, xmb, _scan, *bcast):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        y, _ = jax.lax.scan(body, xmb, wl)
        return y, jnp.zeros((), jnp.float32)

    def sequential(w, x):
        for i in range(L):
            x = jnp.tanh(x @ w[i])
        return x

    y, aux = jax.jit(lambda w, x: spmd_pipeline(stage_fn, w, x, mesh,
                                                num_microbatches=M))(w, x)
    ref = sequential(w, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-5, atol=1e-5)

    # gradients through the pipeline == sequential gradients
    gp = jax.jit(jax.grad(lambda w: jnp.sum(
        spmd_pipeline(stage_fn, w, x, mesh, num_microbatches=M)[0] ** 2)))(w)
    gs = jax.jit(jax.grad(lambda w: jnp.sum(sequential(w, x) ** 2)))(w)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gs), rtol=1e-4, atol=1e-5)


def test_pipeline_module_api(devices, rng):
    mesh = build_mesh(fsdp=2, pp=4, devices=devices)
    set_global_mesh(mesh)
    D = 16
    module = PipelineModule([LayerSpec(TanhLayer, D) for _ in range(8)], mesh=mesh)
    x = jax.random.normal(rng, (8, D))
    params = module.init(rng, x)
    assert jax.tree.leaves(params)[0].shape[0] == 8  # stacked layer dim
    y = jax.jit(module.apply)(params, x)
    xs = x
    for i in range(8):
        xs = jnp.tanh(xs @ jax.tree.map(lambda a: a[i], params)["w"])
    np.testing.assert_allclose(np.asarray(y), np.asarray(xs), rtol=1e-5, atol=1e-5)


def test_model_trains_on_pp_mesh(devices, rng):
    """Llama-family model end-to-end on pp=2 × fsdp=2 × tp=2."""
    import deepspeed_tpu
    from deepspeed_tpu.models import causal_lm

    mesh = build_mesh(pp=2, fsdp=2, tp=2, devices=devices)
    set_global_mesh(mesh)
    model = causal_lm("llama-tiny", mesh=mesh, num_layers=4, hidden_size=64,
                      intermediate_size=128, num_heads=4, num_kv_heads=2,
                      vocab_size=256)
    ds_config = {"train_batch_size": 8, "gradient_accumulation_steps": 1,
                 "zero_optimization": {"stage": 1},
                 "optimizer": {"type": "Adam", "params": {"lr": 3e-3}},
                 "steps_per_print": 1000}
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=ds_config, mesh=mesh)
    toks = jax.random.randint(rng, (8, 64), 0, 256)
    losses = []
    for _ in range(4):
        loss = engine.forward((toks, toks))
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    assert losses[-1] < losses[0]


def test_pp_forward_matches_no_pp(devices, rng):
    """Same params, same tokens: pipelined forward == unpipelined forward."""
    from deepspeed_tpu.models import causal_lm

    toks = jax.random.randint(rng, (4, 32), 0, 128)
    kw = dict(num_layers=4, hidden_size=32, intermediate_size=64, num_heads=2,
              num_kv_heads=2, vocab_size=128, remat=False)

    mesh1 = build_mesh(fsdp=8, devices=devices)
    set_global_mesh(mesh1)
    m1 = causal_lm("llama-tiny", mesh=mesh1, **kw)
    params = m1.init(rng, toks)
    ref = jax.jit(m1.apply)(params, toks)

    mesh2 = build_mesh(pp=4, fsdp=2, devices=devices)
    set_global_mesh(mesh2)
    m2 = causal_lm("llama-tiny", mesh=mesh2, **kw)
    out = jax.jit(m2.apply)(params, toks)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_1f1b_matches_gpipe(devices, rng):
    """VERDICT r4 item 2 done-criterion (parity): the fused 1F1B schedule
    produces the same loss and parameter gradients as the autodiff GPipe
    path on a real model at pp=4, M=16."""
    from deepspeed_tpu.models import causal_lm

    toks = jax.random.randint(rng, (16, 32), 0, 128)
    kw = dict(num_layers=8, hidden_size=32, intermediate_size=64, num_heads=2,
              num_kv_heads=2, vocab_size=128, remat=False, pp_microbatches=16)
    mesh = build_mesh(pp=4, fsdp=2, devices=devices)
    set_global_mesh(mesh)

    m_g = causal_lm("llama-tiny", mesh=mesh, pp_schedule="gpipe", **kw)
    params = m_g.init(rng, toks)
    loss_g, grads_g = jax.jit(jax.value_and_grad(
        lambda p: m_g.apply(p, toks, labels=toks)))(params)

    m_f = causal_lm("llama-tiny", mesh=mesh, pp_schedule="1f1b", **kw)
    loss_f, grads_f = jax.jit(jax.value_and_grad(
        lambda p: m_f.apply(p, toks, labels=toks)))(params)

    np.testing.assert_allclose(float(loss_f), float(loss_g),
                               rtol=1e-5, atol=1e-6)
    for (kg, gg), (_, gf) in zip(
            jax.tree_util.tree_leaves_with_path(grads_g),
            jax.tree_util.tree_leaves_with_path(grads_f)):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gg),
                                   rtol=2e-4, atol=2e-5,
                                   err_msg=jax.tree_util.keystr(kg))


def test_engine_trains_with_1f1b_schedule(devices, rng):
    """ds_config pipeline.schedule="1f1b" reaches the model and the engine
    trains through the fused schedule (reference PipelineEngine +
    TrainSchedule wiring)."""
    import deepspeed_tpu
    from deepspeed_tpu.models import causal_lm

    mesh = build_mesh(pp=2, fsdp=2, tp=2, devices=devices)
    set_global_mesh(mesh)
    model = causal_lm("llama-tiny", mesh=mesh, num_layers=4, hidden_size=64,
                      intermediate_size=128, num_heads=4, num_kv_heads=2,
                      vocab_size=256)
    ds_config = {"train_batch_size": 8, "gradient_accumulation_steps": 1,
                 "zero_optimization": {"stage": 1},
                 "pipeline": {"schedule": "1f1b", "micro_batches": 4},
                 "optimizer": {"type": "Adam", "params": {"lr": 3e-3}},
                 "steps_per_print": 1000}
    engine, _, _, _ = deepspeed_tpu.initialize(model=model, config=ds_config,
                                               mesh=mesh)
    assert model.config.pp_schedule == "1f1b"
    assert model.config.pp_microbatches == 4
    toks = jax.random.randint(rng, (8, 64), 0, 256)
    losses = []
    for _ in range(4):
        loss = engine.forward((toks, toks))
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    assert losses[-1] < losses[0]
    with pytest.raises(ValueError, match="schedule"):
        deepspeed_tpu.initialize(
            model=causal_lm("llama-tiny", mesh=mesh, num_layers=4,
                            hidden_size=64, intermediate_size=128,
                            num_heads=4, num_kv_heads=2, vocab_size=256),
            config={**ds_config, "pipeline": {"schedule": "interleaved"}},
            mesh=mesh)


def test_1f1b_bounds_inflight_boundaries(devices, rng):
    """VERDICT r4 item 2 done-criterion (memory): at pp=4, M=16 the fused
    1F1B program's live boundary stash is the 2pp-1 circular buffer, not
    the GPipe scan's M+pp-1 saved steps — measured with the compiled
    memory_analysis (the technique from test_param_offload.py)."""
    from deepspeed_tpu.models import causal_lm

    B, S, M = 32, 512, 32
    toks = jax.random.randint(rng, (B, S), 0, 256)
    # boundary-dominant shapes: each stashed boundary is 1x512x512 fp32
    # (1MB), so the GPipe scan's 35 saved steps vs 1F1B's 7 circular slots
    # is the dominant temp-pool difference
    kw = dict(num_layers=4, hidden_size=512, intermediate_size=512,
              num_heads=4, num_kv_heads=4, vocab_size=256, remat=False,
              pp_microbatches=M)
    mesh = build_mesh(pp=4, fsdp=2, devices=devices)
    set_global_mesh(mesh)

    def temp_bytes(schedule):
        m = causal_lm("llama-tiny", mesh=mesh, pp_schedule=schedule, **kw)
        params = jax.eval_shape(m.init, rng, toks)
        params = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), params)
        fn = jax.jit(jax.value_and_grad(lambda p: m.apply(p, toks,
                                                          labels=toks)))
        ma = fn.lower(params).compile().memory_analysis()
        if ma is None or not hasattr(ma, "temp_size_in_bytes"):
            pytest.skip("backend exposes no memory analysis")
        return ma.temp_size_in_bytes

    gpipe, f1b = temp_bytes("gpipe"), temp_bytes("1f1b")
    # the live boundary stash shrinks from the GPipe scan's (M+pp-1)=35
    # saved steps to the 1F1B circular buffer's (2pp-1)=7 slots.  Assert
    # the temp-pool DELTA accounts for most of that slot-count shrink (the
    # x/gx/grad pools are shared between the two programs and dominate the
    # absolute numbers, so a ratio would mostly measure the model, not the
    # schedule).
    slot = 1 * S * 512 * 4  # one boundary microbatch [mb=1, S, D] fp32
    shrink = ((M + 4 - 1) - (2 * 4 - 1)) * slot
    assert f1b < gpipe, (f1b, gpipe)
    assert gpipe - f1b > 0.7 * shrink, (f1b, gpipe, shrink)


def _walk_eqns(jaxpr, acc):
    for eqn in jaxpr.eqns:
        acc.append(eqn)
        for v in eqn.params.values():
            for u in (v if isinstance(v, (tuple, list)) else [v]):
                inner = getattr(u, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    _walk_eqns(inner, acc)
                elif hasattr(u, "eqns"):
                    _walk_eqns(u, acc)
    return acc


def test_pp_boundary_crosses_in_bf16(devices, rng):
    """VERDICT r3 weak #2 done-criterion: with the TPU boundary mode
    (boundary_fp32=False) no non-scalar fp32 tensor crosses the pp axis —
    ppermute and psum payloads stay bf16, halving stage-to-stage ICI bytes.
    Trace-only: executing bf16 boundary psum CHECK-crashes the XLA *CPU*
    backend (the reason the gate exists), so this asserts on the jaxpr."""
    mesh = build_mesh(fsdp=2, pp=4, devices=devices)
    set_global_mesh(mesh)
    L, D, B, M = 8, 16, 32, 16
    w = jax.random.normal(rng, (L, D, D)).astype(jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, D)).astype(jnp.bfloat16)

    def stage_fn(wl, xmb, _scan, *bcast):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        y, _ = jax.lax.scan(body, xmb, wl)
        return y, jnp.zeros((), jnp.float32)

    def loss(w, x):
        y, _ = spmd_pipeline(stage_fn, w, x, mesh, num_microbatches=M,
                             boundary_fp32=False)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    eqns = _walk_eqns(jax.make_jaxpr(jax.grad(loss))(w, x).jaxpr, [])
    comm = [e for e in eqns if e.primitive.name in ("ppermute", "psum",
                                                    "psum_invariant")]
    assert comm, "no collectives found in pipelined jaxpr"
    for e in comm:
        for v in e.invars:
            aval = v.aval
            # scalar carries (aux/loss accumulators, promoted to (1,) to
            # keep scan residuals rank>=1) may be fp32
            if getattr(aval, "size", 1) > 1:
                assert aval.dtype == jnp.bfloat16, (
                    f"{e.primitive.name} carries {aval.dtype}{aval.shape}")


def test_pipeline_remat_bounds_residuals(devices, rng):
    """VERDICT r3 weak #3 done-criterion: pp=4, M=16 — with remat_stage the
    scan's backward residuals are bounded by the boundary tensors, not the
    stage-body internals."""
    from jax._src.ad_checkpoint import saved_residuals

    mesh = build_mesh(fsdp=2, pp=4, devices=devices)
    set_global_mesh(mesh)
    L, D, B, M = 8, 16, 64, 16
    w = jax.random.normal(rng, (L, D, D)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(1), (B, D))

    def stage_fn(wl, xmb, _scan, *bcast):
        def body(c, wi):
            h = jnp.tanh(c @ wi)
            return jnp.tanh(h @ wi.T) + c, None
        y, _ = jax.lax.scan(body, xmb, wl)
        return y, jnp.zeros((), jnp.float32)

    def loss(w, remat):
        y, _ = spmd_pipeline(stage_fn, w, x, mesh, num_microbatches=M,
                             remat_stage=remat)
        return jnp.sum(y ** 2)

    def res_bytes(remat):
        res = saved_residuals(lambda w: loss(w, remat), w)
        return sum(int(np.prod(r[0].shape)) * r[0].dtype.itemsize for r in res)

    full, bounded = res_bytes(False), res_bytes(True)
    # full saves the two tanh internals per layer per step; bounded saves the
    # per-step boundary input (plus loop constants).  Empirically ~4x here;
    # assert a conservative 2.5x so dtype/layout drift doesn't flake.
    assert bounded * 2.5 < full, (full, bounded)

    # remat changes memory, never math
    gp = jax.jit(jax.grad(lambda w: loss(w, True)))(w)
    gs = jax.jit(jax.grad(lambda w: loss(w, False)))(w)
    np.testing.assert_allclose(np.asarray(gp), np.asarray(gs), rtol=1e-4,
                               atol=1e-5)


def test_pp_loss_matches_no_pp(devices, rng):
    """Loss-in-pipeline (scalar reduction on the last stage) must equal the
    unpipelined loss — and the pipelined program must NOT materialize the
    replicated [B, S, D] hidden buffer (VERDICT r2 weak #5)."""
    from deepspeed_tpu.models import causal_lm

    toks = jax.random.randint(rng, (8, 32), 0, 256)
    kw = dict(num_layers=4, hidden_size=64, intermediate_size=128,
              num_heads=4, num_kv_heads=2, vocab_size=256, remat=False,
              ce_chunk=0)
    mesh_pp = build_mesh(pp=2, fsdp=2, tp=2, devices=devices)
    set_global_mesh(mesh_pp)
    model_pp = causal_lm("llama-tiny", mesh=mesh_pp, **kw)
    params = model_pp.init(jax.random.PRNGKey(3), toks)
    loss_pp = jax.jit(lambda p: model_pp.apply(p, toks, labels=toks))(params)

    mesh1 = build_mesh(fsdp=8, devices=devices)
    set_global_mesh(mesh1)
    model1 = causal_lm("llama-tiny", mesh=mesh1, **kw)
    loss1 = jax.jit(lambda p: model1.apply(p, toks, labels=toks))(params)
    np.testing.assert_allclose(float(loss_pp), float(loss1), rtol=2e-5)


# ---------------------------------------------------------------------------
# PartitionId-class retirement (ISSUE 16): loss/grad parity matrix across
# pp degrees x microbatch counts, INCLUDING an uneven last microbatch (the
# transformer pads the batch to a multiple of M with label=-1 / mask=0 rows)
# ---------------------------------------------------------------------------

def _tiny_lm_kw():
    return dict(num_layers=4, hidden_size=64, intermediate_size=128,
                num_heads=4, num_kv_heads=2, vocab_size=256, remat=False,
                ce_chunk=0)


@pytest.mark.parametrize("pp,fsdp,M,B,schedule", [
    (2, 4, 2, 8, "gpipe"),    # even split
    (2, 4, 3, 8, "gpipe"),    # uneven: 8 % 3 -> pad to 9, mb=3
    (4, 2, 4, 8, "gpipe"),    # even, deeper pipeline
    (4, 2, 5, 8, "gpipe"),    # uneven: 8 % 5 -> pad to 10, mb=2
    (2, 4, 3, 8, "1f1b"),     # uneven through the fused fwd+bwd scan
    (4, 2, 4, 8, "1f1b"),     # even through the fused scan, pp=4
])
def test_pp_loss_grad_parity_matrix(devices, pp, fsdp, M, B, schedule):
    """Full-manual pipelined loss AND parameter grads match the
    unpipelined fsdp=8 reference on the same params — across pipeline
    depths, microbatch counts (uneven last microbatch included) and both
    schedules.  This is the real retirement of the 9 PartitionId tier-1
    failures: the programs now compile AND are numerically right."""
    from deepspeed_tpu.models import causal_lm

    toks = jax.random.randint(jax.random.PRNGKey(11), (B, 32), 0, 256)
    kw = _tiny_lm_kw()
    mesh_pp = build_mesh(pp=pp, fsdp=fsdp, devices=devices)
    set_global_mesh(mesh_pp)
    model_pp = causal_lm("llama-tiny", mesh=mesh_pp, pp_microbatches=M,
                         pp_schedule=schedule, **kw)
    params = model_pp.init(jax.random.PRNGKey(3), toks)

    def loss_pp(p):
        return model_pp.apply(p, toks, labels=toks)

    lp, gp = jax.jit(jax.value_and_grad(loss_pp))(params)

    mesh1 = build_mesh(fsdp=8, devices=devices)
    set_global_mesh(mesh1)
    model1 = causal_lm("llama-tiny", mesh=mesh1, **kw)

    def loss1(p):
        return model1.apply(p, toks, labels=toks)

    l1, g1 = jax.jit(jax.value_and_grad(loss1))(params)
    np.testing.assert_allclose(float(lp), float(l1), rtol=3e-5)
    flat_p, _ = jax.tree.flatten(gp)
    flat_1, _ = jax.tree.flatten(g1)
    for a, b in zip(flat_p, flat_1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-6)


# ---------------------------------------------------------------------------
# quantized stage boundary: parity + the double byte ledger on one trace
# ---------------------------------------------------------------------------

def test_pp_quantized_boundary_parity_and_ledger(devices, rng):
    """int8 boundary rings track the dense pipeline closely (one blockwise
    quantization error per hop) and the trace-time double ledger pins the
    wire reduction: q_ppermute moves >=2x fewer bytes than its dense twin
    (int8 codes + fp32 block scales vs the fp32 activation)."""
    from deepspeed_tpu.monitor.comms import CommMetrics
    from deepspeed_tpu.monitor.metrics import MetricsRegistry
    import deepspeed_tpu.comm.collectives_q as cq_mod

    mesh = build_mesh(fsdp=2, pp=4, devices=devices)
    set_global_mesh(mesh)
    L, D, B, M = 8, 256, 8, 4
    # 0.15 keeps the tanh stack roughly norm-preserving; at 0.3 each
    # matmul amplifies the per-hop quantization error ~0.3*sqrt(D) ~ 4.8x
    # and the test would measure the toy network's conditioning, not the
    # boundary codec
    w = jax.random.normal(rng, (L, D, D)) * 0.15
    x = jax.random.normal(jax.random.PRNGKey(1), (B, D))

    def stage_fn(wl, xmb, _scan, *bcast):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        y, _ = jax.lax.scan(body, xmb, wl)
        return y, jnp.zeros((), jnp.float32)

    def run(w, x, quant):
        return spmd_pipeline(stage_fn, w, x, mesh, num_microbatches=M,
                             quantize_boundary=quant)[0]

    # per-element error is amplified by the downstream tanh(c @ w) layers
    # (~0.3*sqrt(D) per matmul), so the parity contract is LOSS parity,
    # not elementwise activation identity
    y_d = jax.jit(lambda w, x: run(w, x, False))(x=x, w=w)
    y_q = jax.jit(lambda w, x: run(w, x, True))(x=x, w=w)
    diff = np.asarray(y_q) - np.asarray(y_d)
    assert 0 < float(np.abs(diff).max()) < 0.5   # perturbed, not broken
    ld = float(np.mean(np.asarray(y_d) ** 2))
    lq = float(np.mean(np.asarray(y_q) ** 2))
    assert abs(lq - ld) < 0.02 * abs(ld), (lq, ld)

    # grads flow through the quantized reverse ring and stay close in L2
    gd = np.asarray(jax.jit(jax.grad(
        lambda w: jnp.mean(run(w, x, False) ** 2)))(w))
    gq = np.asarray(jax.jit(jax.grad(
        lambda w: jnp.mean(run(w, x, True) ** 2)))(w))
    rel = np.linalg.norm(gq - gd) / np.linalg.norm(gd)
    assert rel < 0.05, rel

    # double ledger: wire vs dense-twin bytes off ONE trace
    reg = MetricsRegistry().enable()
    cm = CommMetrics(registry=reg)
    cm.configure(enabled=True)
    orig = cq_mod.comm_metrics
    cq_mod.comm_metrics = cm
    try:
        jax.eval_shape(lambda w, x: run(w, x, True), w, x)
    finally:
        cq_mod.comm_metrics = orig
    import json as _json
    metrics = _json.loads(reg.statz_json())["metrics"]

    def fam(name):
        v = metrics.get(name, 0)
        if isinstance(v, dict):
            return sum(x for x in v.values() if isinstance(x, (int, float)))
        return v or 0

    wire = fam("ds_comm_q_ppermute_bytes_total")
    dense = fam("ds_comm_q_ppermute_dense_bytes_total")
    assert dense > 0 and wire > 0
    assert dense >= 2 * wire, (wire, dense)
