"""Device-truth layer tests (ISSUE 5 tentpole).

Golden synthetic perfetto traces exercise the post-processor's track
classification (device process vs host threads vs CPU hlo_op proxy rows),
scope matching (event names AND tf_op-style args), interval-union phase
arithmetic (fwd_bwd/optimizer/comm/other/gap partition the window), the
registry backfill (``ds_comm_*_device_seconds`` distinct from the analytic
series), graceful degradation on host-only traces, and the live
``/profilez`` endpoint against a real CPU training engine.
"""

import gzip
import json
import threading
import urllib.error
import urllib.request

import pytest

import jax

import deepspeed_tpu
from deepspeed_tpu.monitor.metrics import MetricsRegistry, get_registry
from deepspeed_tpu.profiling import device_trace
from tests.unit.simple_model import SimpleModel, random_dataset

# ---------------------------------------------------------------------------
# synthetic trace builder
# ---------------------------------------------------------------------------

DEV_PID, HOST_PID = 1, 2
OPS_TID, SCOPE_TID, STEPS_TID, PY_TID = 10, 11, 12, 20


def _meta(pid, pname, threads):
    evs = [{"ph": "M", "pid": pid, "name": "process_name",
            "args": {"name": pname}}]
    for tid, tname in threads:
        evs.append({"ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
                    "args": {"name": tname}})
    return evs


def _x(name, pid, tid, ts, dur, args=None):
    e = {"ph": "X", "name": name, "pid": pid, "tid": tid,
         "ts": float(ts), "dur": float(dur)}
    if args:
        e["args"] = args
    return e


def _write(tmp_path, events, name="perfetto_trace.json.gz"):
    p = tmp_path / name
    with gzip.open(p, "wt") as fh:
        json.dump({"displayTimeUnit": "ns", "traceEvents": events}, fh)
    return str(p)


def golden_trace(tmp_path):
    """Two 100us steps on a TPU-style device process, plus host ranges.

    Step layout (us), identical at offsets 0 and 100:
      [0, 60)  fwd/bwd ops (scope via tf_op arg), containing
      [20, 40) an all_gather comm op (nested inside fwd_bwd)
      [60, 80) optimizer-step fusion (scope via the name-scope lane)
      [80, 90) a reduce_scatter comm op (outside fwd_bwd)
      [90,100) device idle (the gap)
    """
    evs = _meta(DEV_PID, "/device:TPU:0", [
        (OPS_TID, "XLA Ops"), (SCOPE_TID, "TensorFlow Name Scope"),
        (STEPS_TID, "Steps")])
    evs += _meta(HOST_PID, "/host:CPU", [(PY_TID, "python")])
    for base in (0, 100):
        evs.append(_x("fusion.1", DEV_PID, OPS_TID, base + 0, 20,
                      {"tf_op": "jit_step/ds_fwd_bwd/fusion.1"}))
        evs.append(_x("all-gather-start.2", DEV_PID, OPS_TID, base + 20, 20,
                      {"tf_op": "jit_step/ds_fwd_bwd/ds_comm_all_gather/"
                                "all-gather.2"}))
        evs.append(_x("fusion.3", DEV_PID, OPS_TID, base + 40, 20,
                      {"tf_op": "jit_step/ds_fwd_bwd/fusion.3"}))
        evs.append(_x("fusion.4", DEV_PID, OPS_TID, base + 60, 20))
        # optimizer scope carried by the dedicated name-scope lane, not args
        evs.append(_x("ds_optimizer_step", DEV_PID, SCOPE_TID, base + 60, 20))
        evs.append(_x("reduce-scatter.5", DEV_PID, OPS_TID, base + 80, 10,
                      {"tf_op": "jit_step/ds_comm_reduce_scatter/rs.5"}))
        # a whole-step summary row that must NOT inflate the busy union
        evs.append(_x("step", DEV_PID, STEPS_TID, base, 100))
        # host-side dispatch range (python thread)
        evs.append(_x("ds_fwd_bwd", HOST_PID, PY_TID, base + 0, 55))
    return _write(tmp_path, evs)


# ---------------------------------------------------------------------------
# parser / summarizer
# ---------------------------------------------------------------------------


def test_golden_phase_breakdown(tmp_path):
    s = device_trace.summarize_trace(golden_trace(tmp_path), steps=2)
    assert not s["degraded"]
    assert s["steps"] == 2
    us = 1e-6
    # window spans first device-op start .. last device-op end = [0, 190]us
    assert s["window_s"] == pytest.approx(190 * us)
    ph = s["phases"]
    # per step: fwd_bwd 60 minus nested 20us comm = 40; optimizer 20;
    # comm 20 (nested all_gather) + 10 (reduce_scatter) = 30; gap 10us
    # between steps (90..100); nothing unclaimed
    assert ph["fwd_bwd_s"] == pytest.approx(2 * 40 * us)
    assert ph["optimizer_s"] == pytest.approx(2 * 20 * us)
    assert ph["comm_s"] == pytest.approx(2 * 30 * us)
    assert ph["other_s"] == pytest.approx(0.0, abs=1e-12)
    assert ph["gap_s"] == pytest.approx(10 * us)  # one inter-step idle
    # the five phases partition the window exactly
    assert sum(ph.values()) == pytest.approx(s["window_s"])
    assert s["per_step"]["fwd_bwd_s"] == pytest.approx(40 * us)


def test_lane_rows_padding_past_ops_keep_partition_exact(tmp_path):
    """Name-scope lane spans can pad past the op rows and bridge the idle
    between them (real xplane exports merge adjacent same-scope ops into
    one lane span); scopes must clamp to the busy union so phases + gap
    still partition the window exactly."""
    evs = _meta(DEV_PID, "/device:TPU:0", [
        (OPS_TID, "XLA Ops"), (SCOPE_TID, "TensorFlow Name Scope")])
    evs.append(_x("fusion.1", DEV_PID, OPS_TID, 100, 300))
    evs.append(_x("all-gather.2", DEV_PID, OPS_TID, 500, 100))
    # lane spans 0..800: pads before/after the ops AND bridges 400..500 idle
    evs.append(_x("ds_fwd_bwd", DEV_PID, SCOPE_TID, 0, 800))
    evs.append(_x("ds_comm_all_gather", DEV_PID, SCOPE_TID, 450, 200))
    s = device_trace.summarize_trace(_write(tmp_path, evs), steps=1)
    us = 1e-6
    ph = s["phases"]
    assert s["window_s"] == pytest.approx(500 * us)    # ops span 100..600
    assert ph["comm_s"] == pytest.approx(100 * us)     # busy inside the lane
    assert ph["fwd_bwd_s"] == pytest.approx(300 * us)  # busy - comm
    assert ph["gap_s"] == pytest.approx(100 * us)      # the 400..500 idle
    assert sum(ph.values()) == pytest.approx(s["window_s"])


def test_comm_scope_entirely_over_idle_is_dropped(tmp_path):
    """A comm name-scope lane span lying wholly over device-idle time
    clips to nothing against the busy union — it must vanish from
    comm_device, not crash the summarizer (max() over an empty union)."""
    evs = _meta(DEV_PID, "/device:TPU:0", [
        (OPS_TID, "XLA Ops"), (SCOPE_TID, "TensorFlow Name Scope")])
    evs.append(_x("fusion.1", DEV_PID, OPS_TID, 0, 100))
    # comm lane over 200..300: no op row anywhere under it
    evs.append(_x("ds_comm_all_reduce", DEV_PID, SCOPE_TID, 200, 100))
    s = device_trace.summarize_trace(_write(tmp_path, evs), steps=1)
    assert "all_reduce" not in s["comm_device"]
    assert s["phases"]["comm_s"] == pytest.approx(0.0, abs=1e-12)


def test_golden_comm_device_series_and_backfill(tmp_path):
    s = device_trace.summarize_trace(golden_trace(tmp_path), steps=2)
    cd = s["comm_device"]
    assert cd["all_gather"]["seconds"] == pytest.approx(40e-6)
    assert cd["all_gather"]["count"] == 2
    assert cd["reduce_scatter"]["seconds"] == pytest.approx(20e-6)

    reg = MetricsRegistry().enable()
    # analytic series pre-exists and must be untouched by the backfill
    analytic = reg.histogram("ds_comm_all_gather_seconds")
    analytic.record(0.123)
    device_trace.publish_summary(
        s, reg, bytes_per_op={"all_gather": (4_000_000, 8)})
    h = reg.get("ds_comm_all_gather_device_seconds")
    assert h is not None and h.count == 1
    assert h.sum == pytest.approx(40e-6)
    assert analytic.count == 1 and analytic.sum == pytest.approx(0.123)
    # busbw recomputed from device time: 4MB / 40us = 100 GB/s alg,
    # x (8-1)/8 ring factor
    bw = reg.get("ds_comm_all_gather_device_busbw_gbps").value
    assert bw == pytest.approx(100.0 * 7 / 8, rel=1e-6)
    assert reg.get("ds_profile_gap_seconds").value == pytest.approx(
        s["per_step"]["gap_s"])


def test_golden_overlapped_comm_not_double_subtracted(tmp_path):
    """Comm rows CONCURRENT with compute rows (a second device op lane —
    what the layer-chunked overlap schedule produces): the exclusive
    partition must claim the overlapped time for ``comm`` exactly once
    (never subtract it from gap, which is computed against the busy
    union), phases + gap must still sum to the window, and the
    comm∩compute time must surface as ``overlapped_comm_s`` feeding the
    ``ds_overlap_hidden_comm_seconds_est`` gauge.

    Layout (us), one step, two op lanes:
      lane A [0,100)   fwd/bwd fusion
      lane B [40,80)   all_gather CONCURRENT with fwd/bwd   (hidden, 40)
      lane B [100,120) all_gather after compute             (exposed, 20)
      idle   [120,130)                                      (gap, 10)
      lane A [130,150) optimizer fusion
      lane B [140,150) reduce_scatter CONCURRENT with optimizer (hidden, 10)
    """
    LANE_B = 13
    evs = _meta(DEV_PID, "/device:TPU:0", [
        (OPS_TID, "XLA Ops"), (LANE_B, "XLA Ops c1")])
    evs.append(_x("fusion.1", DEV_PID, OPS_TID, 0, 100,
                  {"tf_op": "jit_step/ds_fwd_bwd/fusion.1"}))
    evs.append(_x("all-gather.2", DEV_PID, LANE_B, 40, 40,
                  {"tf_op": "jit_step/ds_fwd_bwd/ds_comm_all_gather/ag.2"}))
    evs.append(_x("all-gather.3", DEV_PID, LANE_B, 100, 20,
                  {"tf_op": "jit_step/ds_comm_all_gather/ag.3"}))
    evs.append(_x("fusion.4", DEV_PID, OPS_TID, 130, 20,
                  {"tf_op": "jit_step/ds_optimizer_step/fusion.4"}))
    evs.append(_x("reduce-scatter.5", DEV_PID, LANE_B, 140, 10,
                  {"tf_op": "jit_step/ds_optimizer_step/"
                            "ds_comm_reduce_scatter/rs.5"}))
    s = device_trace.summarize_trace(_write(tmp_path, evs), steps=1)
    us = 1e-6
    ph = s["phases"]
    assert s["window_s"] == pytest.approx(150 * us)
    # comm union claims hidden + exposed once: 40 + 20 + 10
    assert ph["comm_s"] == pytest.approx(70 * us)
    # fwd_bwd = its 100us minus the 40us concurrent comm — subtracted ONCE
    assert ph["fwd_bwd_s"] == pytest.approx(60 * us)
    assert ph["optimizer_s"] == pytest.approx(10 * us)
    assert ph["other_s"] == pytest.approx(0.0, abs=1e-12)
    # gap is true idle only — overlapped comm must NOT eat into it
    assert ph["gap_s"] == pytest.approx(10 * us)
    assert sum(ph.values()) == pytest.approx(s["window_s"])
    # the hidden-comm measurement: comm ∩ (fwd_bwd ∪ optimizer)
    assert s["overlapped_comm_s"] == pytest.approx(50 * us)

    reg = MetricsRegistry().enable()
    device_trace.publish_summary(s, reg)
    assert reg.get("ds_overlap_hidden_comm_seconds_est").value == \
        pytest.approx(50 * us)


def test_cpu_proxy_rows_classify_as_device(tmp_path):
    """CPU traces have no /device process; XLA-runtime rows tagged with
    args.hlo_op count as device-proxy op rows, and a scope with host
    ranges but no device matches (the CPU export drops scope paths) gets
    the device-busy time INSIDE its host ranges, flagged host_scoped."""
    evs = _meta(HOST_PID, "/host:CPU", [
        (PY_TID, "python"), (30, "tf_XLATfrtCpuClient/1")])
    evs.append(_x("dot.3", HOST_PID, 30, 0, 50,
                  {"hlo_module": "jit_step", "hlo_op": "dot.3"}))
    evs.append(_x("dot.9", HOST_PID, 30, 70, 20,
                  {"hlo_module": "jit_step", "hlo_op": "dot.9"}))
    evs.append(_x("ds_fwd_bwd", HOST_PID, PY_TID, 0, 60))
    s = device_trace.summarize_trace(_write(tmp_path, evs))
    assert not s["degraded"]
    assert s["device_rows"] == 2
    assert s["device_busy_s"] == pytest.approx(70e-6)
    assert s["host_scoped"] == ["ds_fwd_bwd"]
    # device rows inside the host fwd_bwd range -> fwd_bwd; the row
    # outside any scope stays "other"; gap = [50,70) idle
    assert s["phases"]["fwd_bwd_s"] == pytest.approx(50e-6)
    assert s["phases"]["other_s"] == pytest.approx(20e-6)
    assert s["phases"]["gap_s"] == pytest.approx(20e-6)


def test_degrades_to_host_ranges_without_device_rows(tmp_path):
    """A trace with only host annotation ranges still yields a labeled
    (degraded) phase breakdown instead of crashing or reporting zeros."""
    evs = _meta(HOST_PID, "/host:CPU", [(PY_TID, "python")])
    evs.append(_x("ds_fwd_bwd", HOST_PID, PY_TID, 0, 70))
    evs.append(_x("ds_optimizer_step", HOST_PID, PY_TID, 70, 20))
    s = device_trace.summarize_trace(_write(tmp_path, evs), steps=1)
    assert s["degraded"]
    assert s["phases"]["fwd_bwd_s"] == pytest.approx(70e-6)
    assert s["phases"]["optimizer_s"] == pytest.approx(20e-6)
    assert s["phases"]["gap_s"] == pytest.approx(0.0, abs=1e-12)
    assert sum(s["phases"].values()) == pytest.approx(s["window_s"])


def test_serving_dispatch_slack(tmp_path):
    """Host ds_serve_decode ranges vs device rows inside them: the slack
    (host dispatch window minus device busy) is the sync-free headroom."""
    evs = _meta(DEV_PID, "/device:TPU:0", [(OPS_TID, "XLA Ops")])
    evs += _meta(HOST_PID, "/host:CPU", [(PY_TID, "python")])
    evs.append(_x("ds_serve_decode", HOST_PID, PY_TID, 0, 100))
    evs.append(_x("fusion.9", DEV_PID, OPS_TID, 10, 60))
    s = device_trace.summarize_trace(_write(tmp_path, evs))
    assert s["serve"]["decode_blocks"] == 1
    assert s["serve"]["decode_host_s"] == pytest.approx(100e-6)
    assert s["serve"]["decode_device_s"] == pytest.approx(60e-6)
    assert s["serve"]["dispatch_slack_s"] == pytest.approx(40e-6)
    reg = MetricsRegistry().enable()
    device_trace.publish_summary(s, reg)
    assert reg.get("ds_profile_serve_dispatch_slack_seconds").value == \
        pytest.approx(40e-6)


def test_metrics_dump_device_columns(tmp_path):
    """tools/metrics_dump.py --comms renders the device-truth series next
    to the analytic attribution (side-by-side error reading)."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                    "tools"))
    try:
        import metrics_dump
    finally:
        sys.path.pop(0)
    reg = MetricsRegistry().enable()
    reg.counter("ds_comm_all_gather_calls_total").inc(4)
    reg.counter("ds_comm_all_gather_bytes_total",
                labels={"dtype": "float32"}).inc(1 << 20)
    reg.histogram("ds_comm_all_gather_seconds").record(0.004)
    reg.histogram("ds_comm_all_gather_device_seconds").record(0.001)
    reg.gauge("ds_comm_all_gather_device_busbw_gbps").set(123.0)
    snap = json.loads(reg.statz_json())["metrics"]
    rows = metrics_dump.comms_rows(snap)
    table = metrics_dump.render_comms(rows)
    assert "dev_p50_s" in table and "dev_busbw" in table
    row = rows[0]
    assert row[0] == "all_gather"
    assert row[3] == ""   # dense op: no compression column
    assert row[7] != "" and float(row[7]) == pytest.approx(0.001, rel=0.5)
    assert "123" in row[8]


def test_interval_helpers():
    m = device_trace._merge([(5, 7), (0, 3), (2, 4)])
    assert m == [(0, 4), (5, 7)]
    assert device_trace._union_len([(0, 3), (2, 4), (5, 7)]) == 6
    assert device_trace._subtract([(0, 10)], [(2, 4), (6, 8)]) == \
        [(0, 2), (4, 6), (8, 10)]
    assert device_trace._subtract([(0, 4)], [(0, 10)]) == []


def test_missing_trace_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        device_trace.summarize_trace(str(tmp_path))


# ---------------------------------------------------------------------------
# CPU e2e: live /profilez against a real training engine
# ---------------------------------------------------------------------------


def test_profilez_live_training_engine(tmp_path):
    """`/profilez?steps=2` against a stepping engine returns a JSON phase
    summary; ds_fwd_bwd appears (host annotation ranges on CPU); the
    analytic ds_comm series is not touched by the device-truth layer."""
    from deepspeed_tpu.monitor.server import MetricsServer

    x, y = random_dataset(n=16)
    cfg = {"train_micro_batch_size_per_gpu": 2,
           "gradient_accumulation_steps": 1,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
           "comms_logger": {"enabled": True},
           "steps_per_print": 10**9}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=8), config=cfg,
        rng=jax.random.PRNGKey(0))
    server = MetricsServer(get_registry(), port=0).start()
    analytic_before = get_registry().get("ds_comm_all_gather_seconds")
    analytic_count = analytic_before.count if analytic_before else 0

    stop = threading.Event()

    def train():
        while not stop.is_set():
            loss = engine.forward((x[:8], y[:8]))
            engine.backward(loss)
            engine.step()

    t = threading.Thread(target=train, daemon=True)
    t.start()
    try:
        with urllib.request.urlopen(
                f"{server.url}/profilez?steps=2&timeout=120",
                timeout=150) as resp:
            summary = json.load(resp)
    finally:
        stop.set()
        t.join(timeout=30)
        server.stop()
    assert summary["steps"] == 2
    assert summary["window_s"] > 0
    ph = summary["phases"]
    # the breakdown partitions the captured window (within float noise)
    assert sum(ph.values()) == pytest.approx(summary["window_s"], rel=1e-6)
    # ds_fwd_bwd is visible: the engine emits host annotation ranges around
    # the accum dispatch (device named scopes don't survive the CPU export)
    assert ph["fwd_bwd_s"] > 0, summary
    # the device-truth layer never writes the analytic host-window series
    analytic_after = get_registry().get("ds_comm_all_gather_seconds")
    if analytic_after is not None:
        got = analytic_after.count
        # the training thread keeps committing analytic entries; the check
        # is that publish_summary added nothing beyond those commits —
        # device time landed ONLY in the _device_ series
        assert got >= analytic_count
    dev = get_registry().get("ds_profile_window_seconds")
    assert dev is not None and dev.value > 0


def test_profilez_no_engine_times_out():
    """Without a stepping engine the request must clear cleanly (504) and
    leave the broker reusable."""
    from deepspeed_tpu.monitor.server import MetricsServer

    server = MetricsServer(MetricsRegistry().enable(), port=0).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                f"{server.url}/profilez?steps=1&timeout=0.2", timeout=10)
        assert ei.value.code == 504
        broker = device_trace.get_profile_broker()
        assert broker.pending is None
    finally:
        server.stop()
