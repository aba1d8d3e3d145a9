"""Training-side comm accounting (monitor/comms.py) + FLOPs/MFU + HBM
telemetry: byte/bandwidth golden values, the disabled-path cost contract
(one branch, no allocation), quantized-collective series, and the
acceptance smoke — a ZeRO-3 training run with telemetry on exposes nonzero
``ds_comm_all_gather_*`` bytes/latency and a ``ds_train_mfu`` gauge via
``/statz``, while disabling telemetry is loss-identical."""

import json
import sys
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import deepspeed_tpu
from deepspeed_tpu.monitor.comms import CommMetrics, busbw_factor, comm_metrics
from deepspeed_tpu.monitor.metrics import MetricsRegistry, get_registry


# ---------------------------------------------------------------------------
# bandwidth / byte math golden values
# ---------------------------------------------------------------------------


def test_busbw_factor_golden():
    # NCCL-tests ring factors at P=8
    assert busbw_factor("all_reduce", 8) == pytest.approx(2 * 7 / 8)
    assert busbw_factor("compressed_allreduce", 8) == pytest.approx(2 * 7 / 8)
    assert busbw_factor("all_gather", 8) == pytest.approx(7 / 8)
    assert busbw_factor("reduce_scatter", 8) == pytest.approx(7 / 8)
    assert busbw_factor("q_reduce_scatter", 8) == pytest.approx(7 / 8)
    assert busbw_factor("all_to_all", 8) == pytest.approx(7 / 8)
    assert busbw_factor("zpp_q_all_gather_hpz", 4) == pytest.approx(3 / 4)
    assert busbw_factor("ppermute", 8) == 1.0
    assert busbw_factor("broadcast", 8) == 1.0
    # a world of one moves nothing over links
    assert busbw_factor("all_reduce", 1) == 1.0


def test_trace_time_record_bytes_and_dtype_label():
    reg = MetricsRegistry().enable()
    cm = CommMetrics(registry=reg)
    cm.configure(enabled=True)
    cm.record("all_gather", "fsdp", jnp.zeros((4, 4), jnp.float32))  # 64 B
    cm.record("all_gather", "fsdp", jnp.zeros((8,), jnp.bfloat16))   # 16 B
    assert reg.get("ds_comm_all_gather_calls_total").value == 2
    assert reg.get("ds_comm_all_gather_bytes_total",
                   labels={"dtype": "float32"}).value == 64
    assert reg.get("ds_comm_all_gather_bytes_total",
                   labels={"dtype": "bfloat16"}).value == 16
    # the back-compat dict ledger records the same volume per op@axis
    assert cm.bytes["all_gather@fsdp"] == 80
    assert cm.counts["all_gather@fsdp"] == 2


def test_commit_bandwidth_golden():
    """8 GB moved in a 1.0s window at P=8: algbw == 8 GB/s exactly,
    busbw == algbw * (P-1)/P for all_gather."""
    reg = MetricsRegistry().enable()
    cm = CommMetrics(registry=reg)
    cm.configure(enabled=True)
    cm.commit([("all_gather", 3, 8_000_000_000, "float32", 8)], seconds=1.0)
    assert reg.get("ds_comm_all_gather_calls_total").value == 3
    assert reg.get("ds_comm_all_gather_bytes_total",
                   labels={"dtype": "float32"}).value == 8_000_000_000
    assert reg.get("ds_comm_all_gather_algbw_gbps").value == pytest.approx(8.0)
    assert reg.get("ds_comm_all_gather_busbw_gbps").value == pytest.approx(7.0)
    h = reg.get("ds_comm_all_gather_seconds")
    assert h.count == 1 and h.sum == pytest.approx(1.0)
    # two ops sharing one window: latency attribution is byte-weighted
    cm.commit([("all_gather", 1, 3_000_000, "float32", 8),
               ("reduce_scatter", 1, 1_000_000, "float32", 8)], seconds=0.4)
    assert reg.get("ds_comm_all_gather_seconds").sum == pytest.approx(1.3)
    assert reg.get("ds_comm_reduce_scatter_seconds").sum == pytest.approx(0.1)


def test_eager_span_records_latency():
    reg = MetricsRegistry().enable()
    cm = CommMetrics(registry=reg)
    cm.configure(enabled=True)
    with cm.span("broadcast", 1024, "uint8", world=4):
        pass
    h = reg.get("ds_comm_broadcast_seconds")
    assert h.count == 1 and h.sum > 0
    assert reg.get("ds_comm_broadcast_calls_total").value == 1
    assert reg.get("ds_comm_broadcast_busbw_gbps").value == \
        reg.get("ds_comm_broadcast_algbw_gbps").value  # factor 1.0


def test_disabled_path_no_accounting_no_allocation():
    """While comm accounting is off, record()/commit()/span() are one
    branch and allocate nothing (PR 2's no-alloc assertion style)."""
    reg = MetricsRegistry()                      # disabled
    cm = CommMetrics(registry=reg)               # disabled
    x = np.zeros((4, 4), np.float32)
    entries = [("all_gather", 1, 64, "float32", 8)]
    cm.record("all_gather", "fsdp", x)           # warm any lazy machinery
    cm.commit(entries, 0.1)
    before = sys.getallocatedblocks()
    for _ in range(5000):
        cm.record("all_gather", "fsdp", x)
        cm.commit(entries, 0.1)
    delta = sys.getallocatedblocks() - before
    assert delta < 100, f"disabled comm accounting allocated {delta} blocks"
    assert not cm.counts and not cm.bytes
    assert reg.get("ds_comm_all_gather_calls_total") is None
    # enabled comm logger + DISABLED registry: dict ledger only, and the
    # registry instruments created must still record nothing
    cm.configure(enabled=True)
    cm.record("all_gather", "fsdp", x)
    assert cm.counts["all_gather@fsdp"] == 1
    inst = reg.get("ds_comm_all_gather_calls_total")
    assert inst is None or inst.value == 0


def test_quantized_collective_series_present(mesh8):
    """Tracing the quantized ZeRO++ collectives lands their ds_comm_q_*
    series in the registry (eval_shape traces without compiling)."""
    from deepspeed_tpu.runtime.comm.quantized import (quantized_all_gather,
                                                      quantized_reduce_scatter)

    reg = get_registry()
    was = reg.enabled
    reg.enable()
    comm_metrics.configure(enabled=True)
    try:
        def body(x):
            g = quantized_all_gather(x, "fsdp")
            return quantized_reduce_scatter(g, "fsdp")

        fn = jax.shard_map(body, mesh=mesh8, in_specs=P("fsdp"),
                           out_specs=P("fsdp"), check_vma=False)
        jax.eval_shape(fn, jax.ShapeDtypeStruct((8, 512), jnp.float32))
        assert reg.get("ds_comm_q_all_gather_calls_total").value >= 1
        q_bytes = reg.get("ds_comm_q_all_gather_bytes_total",
                          labels={"dtype": "int8"})
        assert q_bytes is not None and q_bytes.value > 0
        assert reg.get("ds_comm_q_reduce_scatter_calls_total").value >= 1
    finally:
        comm_metrics.configure(enabled=False)
        comm_metrics.reset()
        reg.reset()
        if not was:
            reg.disable()


# ---------------------------------------------------------------------------
# acceptance smoke: ZeRO-3 training with telemetry on, scraped via /statz
# ---------------------------------------------------------------------------


def _tiny_lm_engine(mesh, telemetry: bool):
    from deepspeed_tpu.models import causal_lm

    model = causal_lm("llama-tiny", mesh=mesh, num_layers=2, hidden_size=32,
                      intermediate_size=64, num_heads=2, num_kv_heads=1,
                      vocab_size=128, remat=False)
    cfg = {"train_micro_batch_size_per_gpu": 1,
           "gradient_accumulation_steps": 1,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
           "zero_optimization": {"stage": 3,
                                 "stage3_param_persistence_threshold": 0},
           "steps_per_print": 10**9}
    if telemetry:
        cfg["comms_logger"] = {"enabled": True}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config=cfg, mesh=mesh, rng=jax.random.PRNGKey(3))
    return engine


def _run_steps(engine, steps=3, seq=16):
    tokens = np.asarray(
        jax.random.randint(jax.random.PRNGKey(0), (8, seq), 0, 128),
        dtype=np.int32)
    losses = []
    for _ in range(steps):
        losses.append(float(engine.forward((tokens, tokens))))
        engine.step()
    return losses


def test_zero3_training_smoke_exposes_comm_and_mfu_via_statz(mesh8):
    from deepspeed_tpu.monitor.server import MetricsServer

    reg = get_registry()
    was = reg.enabled
    reg.reset()
    engine = _tiny_lm_engine(mesh8, telemetry=True)
    assert reg.enabled, "comms_logger block must enable the registry"
    server = MetricsServer(reg, port=0).start()
    try:
        losses_on = _run_steps(engine)
        # the training step timeline rides the same master switch: each
        # boundary retained its micro spans + the analytic comm plan
        from deepspeed_tpu.monitor.request_trace import get_step_timeline

        tl = get_step_timeline()
        assert tl.enabled and tl.steps_total >= 3
        last = tl.steps()[-1]
        assert last["micros"] and last.get("comm_plan")
        assert any(e[0] == "all_gather" for e in last["comm_plan"])
        with urllib.request.urlopen(f"{server.url}/statz", timeout=5) as r:
            snap = json.load(r)["metrics"]
        # nonzero all_gather bytes + latency (ZeRO-3 gathers 2x/micro)
        byt = snap["ds_comm_all_gather_bytes_total"]
        total = sum(v for v in byt.values()) if isinstance(byt, dict) else byt
        assert total > 0
        assert snap["ds_comm_all_gather_calls_total"] > 0
        assert snap["ds_comm_all_gather_seconds"]["count"] >= 3
        assert snap["ds_comm_all_gather_seconds"]["sum"] > 0
        assert snap["ds_comm_reduce_scatter_bytes_total"]
        # TFLOPS gauge: set from the 2nd boundary on.  MFU needs a peak,
        # and the CPU has none on record, so that gauge never moves here
        assert snap["ds_train_tflops"] > 0
        assert not snap["ds_train_mfu"]
        # ISSUE 7 step-numerics gauges: loss + grad norm at the boundary
        # (values the engine already computed for _report)
        assert snap["ds_train_loss"] == pytest.approx(losses_on[-1])
        assert snap["ds_train_grad_norm"] > 0
        # shard-group byte breakdown was recorded at init
        assert snap["ds_mem_param_shard_bytes"] > 0
        # the engine timers still bridge (PR 2 behavior intact)
        assert snap["ds_train_forward_seconds"]["count"] >= 3
    finally:
        server.stop()
        comm_metrics.configure(enabled=False)
        comm_metrics.reset()
        from deepspeed_tpu.monitor.request_trace import get_step_timeline

        get_step_timeline().disable()
        get_step_timeline().reset()
        reg.reset()
        if not was:
            reg.disable()

    # telemetry OFF: identical loss trajectory (token/loss-identical)
    engine_off = _tiny_lm_engine(mesh8, telemetry=False)
    losses_off = _run_steps(engine_off)
    assert losses_on == pytest.approx(losses_off, rel=1e-6, abs=1e-7)
    assert reg.get("ds_train_tflops") is None or \
        reg.get("ds_train_tflops").value == 0


def test_metrics_dump_comms_table(tmp_path):
    """tools/metrics_dump.py --comms renders the per-collective summary."""
    import os

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                    "tools"))
    try:
        import metrics_dump
    finally:
        sys.path.pop(0)
    reg = MetricsRegistry().enable()
    cm = CommMetrics(registry=reg)
    cm.configure(enabled=True)
    cm.commit([("all_gather", 4, 1 << 20, "float32", 8)], seconds=0.5)
    reg.gauge("ds_mem_peak_bytes").set(3 * (1 << 30))
    snap = tmp_path / "statz.json"
    snap.write_text(reg.statz_json())
    metrics = metrics_dump.load_snapshot(str(snap))
    table = metrics_dump.render_comms(metrics_dump.comms_rows(metrics))
    assert "all_gather" in table and "4" in table
    assert "1.00 MiB" in table and "GB/s" in table
    # ds_mem_* byte gauges humanize in the main table
    main_table = metrics_dump.render(metrics_dump.rows_from_snapshot(metrics))
    assert "3.00 GiB" in main_table


def test_metrics_dump_comms_compression_column(tmp_path):
    """The quantized transports' per-op compression column (quantized
    wire bytes vs the dense-twin series, both from ONE trace —
    comm/collectives_q.py): rendered as `<ratio>x`, blank for dense
    ops."""
    import os

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                    "tools"))
    try:
        import metrics_dump
    finally:
        sys.path.pop(0)
    reg = MetricsRegistry().enable()
    cm = CommMetrics(registry=reg)
    cm.configure(enabled=True)
    # a quantized op: wire = int8 codes + fp32 scales, dense twin = fp32
    cm.commit([("q_all_reduce", 2, 1_000_000, "int8", 8, 3_500_000)],
              seconds=0.1)
    # a dense op on the same snapshot: no compression column
    cm.commit([("all_reduce", 2, 4_000_000, "float32", 8)], seconds=0.1)
    snap = tmp_path / "statz.json"
    snap.write_text(reg.statz_json())
    metrics = metrics_dump.load_snapshot(str(snap))
    rows = metrics_dump.comms_rows(metrics)
    by_op = {r[0]: r for r in rows}
    assert by_op["q_all_reduce"][3] == "3.50x"
    assert by_op["all_reduce"][3] == ""
    table = metrics_dump.render_comms(rows)
    assert "compress" in table and "3.50x" in table
