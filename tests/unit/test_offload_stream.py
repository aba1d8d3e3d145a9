"""Offload streaming layer (ISSUE 11 / ROADMAP item 3): blockwise int8
codec, ParamStreamer staging/prefetch, int8 host masters, and the relay
metrics ledger.

The contracts pinned here:
- prefetch on/off is loss-IDENTICAL (transport order never changes math);
- int8 masters / int8 stream train to loss PARITY with fp32 masters
  within an rtol bound (the codec is lossy by design; the bound is the
  contract), and the H2D relay ships measurably fewer bytes;
- the persistent staging ring actually recycles its buffers (pointer
  cycling under jit-only consumption);
- ``ds_offload_*`` series populate on both the streamed and the
  optimizer-boundary relay.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.comm.mesh import build_mesh, set_global_mesh
from deepspeed_tpu.comm.quant import (dequantize_blockwise,
                                      dequantize_blockwise_np,
                                      dequantize_tree_np,
                                      quantize_blockwise,
                                      quantize_blockwise_np,
                                      quantize_tree_np)
from deepspeed_tpu.models import causal_lm
from deepspeed_tpu.monitor.metrics import get_registry


# ---------------------------------------------------------------------------
# comm/quant.py codec units
# ---------------------------------------------------------------------------

def test_quant_roundtrip_error_bound(rng):
    x = np.asarray(jax.random.normal(rng, (1000,))) * 3.0
    q, s = quantize_blockwise_np(x, block=128)
    assert q.dtype == np.int8 and q.shape == (8, 128)
    back = dequantize_blockwise_np(q, s, x.size)
    # absmax scaling: error <= scale/2 = blockwise absmax / 254
    for b in range(8):
        bound = np.abs(x[b * 128:(b + 1) * 128]).max() / 254 + 1e-7
        assert np.abs(back[b * 128:(b + 1) * 128]
                      - x[b * 128:(b + 1) * 128]).max() <= bound
    # exact zeros stay exact; an all-zero block has scale 0
    zq, zs = quantize_blockwise_np(np.zeros(300), block=128)
    assert (dequantize_blockwise_np(zq, zs, 300) == 0).all()
    # requantizing a dequantized block is (near-)lossless
    q2, s2 = quantize_blockwise_np(back, block=128)
    back2 = dequantize_blockwise_np(q2, s2, x.size)
    np.testing.assert_allclose(back2, back, rtol=1e-6, atol=1e-7)


def test_quant_np_and_jnp_twins_agree(rng):
    x = np.asarray(jax.random.normal(rng, (7, 33)), np.float32)
    qn, sn = quantize_blockwise_np(x, block=64)
    qj, sj = jax.jit(lambda a: quantize_blockwise(a, block=64))(x)
    np.testing.assert_array_equal(qn, np.asarray(qj))
    np.testing.assert_allclose(sn, np.asarray(sj), rtol=1e-6)
    back_j = jax.jit(lambda q, s: dequantize_blockwise(q, s, x.shape))(qn, sn)
    np.testing.assert_allclose(dequantize_blockwise_np(
        qn, sn, x.size).reshape(x.shape), np.asarray(back_j), rtol=1e-6)


def test_quant_sqrt_space_nonnegative(rng):
    v = np.abs(np.asarray(jax.random.normal(rng, (500,)))) ** 2
    q, s = quantize_blockwise_np(v, block=128, sqrt_space=True)
    back = dequantize_blockwise_np(q, s, v.size, sqrt_space=True)
    assert (back >= 0).all()
    # sqrt-space code: relative error on the sqrt is bounded, so large
    # values come back tight
    big = v > 0.1 * v.max()
    np.testing.assert_allclose(back[big], v[big], rtol=3e-2)


def test_quant_tree_roundtrip(rng):
    tree = {"a": np.asarray(jax.random.normal(rng, (3, 5)), np.float32),
            "b": {"c": np.ones((130,), np.float32)}}
    qt = quantize_tree_np(tree, block=64)
    assert qt.nbytes < sum(a.nbytes for a in jax.tree.leaves(tree))
    back = dequantize_tree_np(qt)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=np.abs(a).max() / 120)


# ---------------------------------------------------------------------------
# ParamStreamer transport
# ---------------------------------------------------------------------------

def _streamer(**kw):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.runtime.zero.streaming import ParamStreamer

    mesh = build_mesh(devices=jax.devices()[:1])
    sh = {"w": NamedSharding(mesh, P()), "b": NamedSharding(mesh, P())}
    np_layers = {"w": np.arange(6 * 4 * 8, dtype=np.float32
                                ).reshape(6, 4, 8),
                 "b": np.ones((6, 8), np.float32)}
    s = ParamStreamer(sh, **kw)
    s.refresh(np_layers)
    return s, np_layers


def test_staging_ring_recycles_buffers(monkeypatch):
    """The persistent staging ring: consumed payloads cycle over exactly
    ``staging_slots`` device buffers (jit-only consumption — a numpy view
    would pin the buffer externally and legitimately break reuse).

    The ring is the one-memory-space route; jax 0.9.0's CPU client
    advertises ``pinned_host``, which routes around it, so the test turns
    that off.  Asserted on the ring's own accounting, not on what the
    allocator does with a freed pointer: every payload IS the ring's
    slot, and the slot's previous occupant was consumed by the donation
    (nothing but the ring holds a layer-sized buffer)."""
    from deepspeed_tpu.accelerator import real_accelerator

    monkeypatch.setattr(real_accelerator, "supports_pinned_host",
                        lambda: False)
    s, np_layers = _streamer(staging_slots=2)
    assert not s.pinned
    read = jax.jit(lambda t: t["w"].sum() + t["b"].sum())
    sums = []
    for i in range(6):
        # the first put builds the ring; from then on the slot about to
        # be written holds the payload of layer i - 2
        old = s._slots[s._slot_idx] if s._slots else None
        s.prefetch(i)
        lp = s.take(i)
        sums.append(float(read(lp)))
        assert len(s._slots) == 2
        assert all(a is b for a, b in zip(jax.tree.leaves(lp),
                                          jax.tree.leaves(s._slots[i % 2])))
        if old is not None:
            assert all(a.is_deleted() for a in jax.tree.leaves(old)), \
                f"slot {i % 2} was not donated at layer {i}"
        del lp, old
    want = [float(np_layers["w"][i].sum() + np_layers["b"][i].sum())
            for i in range(6)]
    assert sums == pytest.approx(want)


def test_streamer_prefetch_hit_miss_accounting():
    reg = get_registry()
    reg.enable()
    try:
        reg.reset()
        s, _ = _streamer(staging_slots=2)
        s.prefetch(0)
        s.take(0)                     # hit
        s.take(1)                     # demand miss
        s.prefetch(2)
        s.prefetch(2)                 # idempotent
        s.take(2)                     # hit
        snap = reg.snapshot()
        assert snap["ds_offload_prefetch_hits_total"] == 2
        assert snap["ds_offload_prefetch_misses_total"] == 1
        fam = snap["ds_offload_relay_bytes_total"]
        per_layer = 4 * 8 * 4 + 8 * 4
        assert fam['{dir="h2d"}'] == 3 * per_layer
        assert snap["ds_offload_relay_seconds"]["count"] == 3
    finally:
        reg.reset()
        reg.disable()


def test_streamer_int8_payload_and_materialize():
    s, np_layers = _streamer(int8=True, quant_block=32)
    s.prefetch(1)
    lp = s.take(1)
    assert set(lp) == {"q", "scale"}
    assert all(a.dtype == jnp.int8 for a in jax.tree.leaves(lp["q"]))
    out = jax.jit(s.materialize)(lp)
    np.testing.assert_allclose(np.asarray(out["w"]), np_layers["w"][1],
                               atol=np.abs(np_layers["w"][1]).max() / 120)
    np.testing.assert_allclose(np.asarray(out["b"]), np_layers["b"][1],
                               atol=0.02)


def test_streamer_prefetch_disabled_is_demand_only():
    reg = get_registry()
    reg.enable()
    try:
        reg.reset()
        s, np_layers = _streamer(prefetch=False)
        s.prefetch(0)                 # no-op
        lp = s.take(0)
        assert float(jax.jit(lambda t: t["w"][0, 0])(lp)) == \
            float(np_layers["w"][0, 0, 0])
        snap = reg.snapshot()
        assert snap["ds_offload_prefetch_hits_total"] == 0
        assert snap["ds_offload_prefetch_misses_total"] == 1
    finally:
        reg.reset()
        reg.disable()


# ---------------------------------------------------------------------------
# OffloadedOptimizer int8 masters
# ---------------------------------------------------------------------------

def _host_params(rng):
    k1, k2 = jax.random.split(rng)
    return {"w": np.asarray(jax.random.normal(k1, (300,)), np.float32),
            "b": np.asarray(jax.random.normal(k2, (40,)), np.float32)}


def test_int8_masters_step_parity_with_fp32(rng):
    from deepspeed_tpu.runtime.zero.offload import OffloadedOptimizer

    params = _host_params(rng)
    opts = {name: OffloadedOptimizer(params, lr=1e-2, int8_masters=int8,
                                     quant_block=64)
            for name, int8 in (("fp32", False), ("int8", True))}
    assert opts["int8"].int8_masters and opts["int8"]._master is None
    gk = jax.random.PRNGKey(3)
    sizes = opts["fp32"]._sizes          # grads follow tree-leaf order
    for step in range(5):
        gk, sub = jax.random.split(gk)
        grads = [np.asarray(jax.random.normal(jax.random.fold_in(sub, j),
                                              (s,)), np.float32)
                 for j, s in enumerate(sizes)]
        outs = {name: opt.step([g.copy() for g in grads])
                for name, opt in opts.items()}
    for a, b in zip(outs["fp32"], outs["int8"]):
        # multi-step drift bound: the int8 code quantizes master AND
        # moments each step
        np.testing.assert_allclose(a, b, rtol=0.1, atol=0.05)
    # the relay payload really is int8 + scales
    q, s = opts["int8"].relay_leaf(0)
    assert q.dtype == np.int8 and s.dtype == np.float32
    assert q.nbytes + s.nbytes < params["w"].nbytes / 2


def test_int8_masters_state_dict_roundtrip(rng):
    from deepspeed_tpu.runtime.zero.offload import OffloadedOptimizer

    params = _host_params(rng)
    opt = OffloadedOptimizer(params, lr=1e-2, int8_masters=True,
                             quant_block=64)
    opt.step([np.ones(s, np.float32) for s in opt._sizes])
    sd = opt.state_dict()
    assert sd["master"][0].dtype == np.float32   # format-compatible
    other = OffloadedOptimizer(params, lr=1e-2, int8_masters=True,
                               quant_block=64)
    other.load_state_dict(sd)
    assert other.step_count == opt.step_count
    for i in range(2):
        # dequantized values are exact scale multiples: requant on load
        # reproduces the store
        np.testing.assert_allclose(other._dequant_master(i),
                                   opt._dequant_master(i), rtol=1e-6)
        for a, b in zip(other._dequant_aux(i), opt._dequant_aux(i)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)


def test_int8_masters_rejects_nvme():
    from deepspeed_tpu.runtime.zero.offload import OffloadedOptimizer

    with pytest.raises(ValueError, match="int8_masters"):
        OffloadedOptimizer({"w": np.ones(8, np.float32)}, backend="nvme",
                           int8_masters=True, swap_dir="/tmp/x")


# ---------------------------------------------------------------------------
# engine integration: streamed + boundary relays
# ---------------------------------------------------------------------------

def _engine(mesh, *, int8_masters=False, int8_stream=False, prefetch=True,
            param_offload=True, gas=1):
    model = causal_lm("llama-tiny", mesh=mesh, num_layers=4, hidden_size=64,
                      intermediate_size=128, num_heads=4, num_kv_heads=2,
                      vocab_size=256, max_seq_len=64, remat=False)
    zero = {"stage": 3,
            "offload_optimizer": {"device": "cpu",
                                  "int8_masters": int8_masters,
                                  "quant_block": 64}}
    if param_offload:
        zero["offload_param"] = {"device": "cpu", "prefetch": prefetch,
                                 "int8_stream": int8_stream}
    cfg = {"train_batch_size": 8 * gas, "train_micro_batch_size_per_gpu": 1,
           "gradient_accumulation_steps": gas, "bf16": {"enabled": True},
           "zero_optimization": zero,
           "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
           "gradient_clipping": 1.0, "steps_per_print": 10**9}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=cfg, mesh=mesh, rng=jax.random.PRNGKey(5))
    return engine


def _losses(engine, toks, steps=3):
    out = []
    for _ in range(steps):
        loss = engine.forward((toks, toks))
        engine.step()
        out.append(float(loss))
    return out


def test_prefetch_on_off_loss_identical(mesh8, rng):
    """The streamed transport order must never change the math: the same
    training run with prefetch on and off is bit-identical."""
    set_global_mesh(mesh8)
    toks = jax.random.randint(rng, (8, 32), 0, 256)
    on = _losses(_engine(mesh8, prefetch=True), toks)
    off = _losses(_engine(mesh8, prefetch=False), toks)
    assert on == off, (on, off)
    assert on[-1] < on[0]


def test_int8_stream_loss_parity_and_relay_bytes(mesh8, rng):
    """int8 host masters + int8 layer relay: the loss trajectory stays
    within the rtol contract of the fp32-master run, and the H2D layer
    relay ships measurably fewer bytes (the whole point)."""
    set_global_mesh(mesh8)
    reg = get_registry()
    reg.enable()
    try:
        toks = jax.random.randint(rng, (8, 32), 0, 256)
        runs, h2d = {}, {}
        for name, int8 in (("fp32", False), ("int8", True)):
            reg.reset()
            e = _engine(mesh8, int8_masters=int8, int8_stream=int8)
            runs[name] = _losses(e, toks, steps=4)
            # engine state is lazily materialized at the first forward
            assert e._streamed is not None
            assert e._streamed.streamer.int8 == int8
            h2d[name] = reg.snapshot()[
                "ds_offload_relay_bytes_total"]['{dir="h2d"}']
        for a, b in zip(runs["fp32"], runs["int8"]):
            assert abs(a - b) <= 5e-2 * abs(a), (runs["fp32"], runs["int8"])
        assert runs["int8"][-1] < runs["int8"][0]
        # layer payloads halve; embed/head stay bf16, so the total drops
        # by the layers' share (> 1.3x at this tiny arch, ~2x at scale)
        assert h2d["fp32"] / h2d["int8"] > 1.3, h2d
    finally:
        reg.reset()
        reg.disable()


def test_boundary_relay_int8_offload_no_param_tiering(devices, rng):
    """ZeRO-Offload WITHOUT param tiering: the optimizer-boundary relay
    ships int8+scales and dequantizes on device — loss parity with the
    fp32-master engine within rtol, fewer H2D bytes, ds_offload_* series
    populated."""
    mesh = build_mesh(fsdp=8, devices=devices)
    set_global_mesh(mesh)
    reg = get_registry()
    reg.enable()
    try:
        toks = jax.random.randint(rng, (8, 32), 0, 256)
        runs, h2d = {}, {}
        for name, int8 in (("fp32", False), ("int8", True)):
            reg.reset()
            e = _engine(mesh, int8_masters=int8, param_offload=False)
            assert e._offload and not e._param_offload
            runs[name] = _losses(e, toks, steps=4)
            snap = reg.snapshot()
            h2d[name] = snap["ds_offload_relay_bytes_total"]['{dir="h2d"}']
            assert snap["ds_offload_relay_bytes_total"]['{dir="d2h"}'] > 0
            assert snap["ds_offload_relay_seconds"]["count"] == 4
        for a, b in zip(runs["fp32"], runs["int8"]):
            assert abs(a - b) <= 5e-2 * abs(a), (runs["fp32"], runs["int8"])
        assert runs["int8"][-1] < runs["int8"][0]
        assert h2d["fp32"] / h2d["int8"] > 1.5, h2d
    finally:
        reg.reset()
        reg.disable()


def test_int8_offload_checkpoint_roundtrip(tmp_path, mesh8, rng):
    """write_state/read_state stays format-compatible under int8 masters
    (fp32 on disk; requantized losslessly on load)."""
    set_global_mesh(mesh8)
    toks = jax.random.randint(rng, (8, 32), 0, 256)
    e = _engine(mesh8, int8_masters=True, int8_stream=True)
    _losses(e, toks, steps=2)
    e.save_checkpoint(str(tmp_path), tag="t")
    saved = jax.device_get(e.state.params)
    other = _engine(mesh8, int8_masters=True, int8_stream=True)
    _losses(other, toks, steps=1)
    other.load_checkpoint(str(tmp_path), tag="t")
    for a, b in zip(jax.tree.leaves(saved),
                    jax.tree.leaves(jax.device_get(other.state.params))):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
