"""Metrics registry (monitor/metrics.py): histogram bucket/quantile
correctness, snapshot consistency under concurrent writes, the Prometheus
exposition golden format, the disabled-path cost contract (one branch, no
allocation), the MonitorMaster bridge, the metrics_dump renderings, and
the tier-1 NAMESPACE GUARD — every metric the suite registers must live in
the ``ds_`` namespace and be documented in docs/OBSERVABILITY.md."""

import json
import os
import re
import sys
import threading
import types

import pytest

from deepspeed_tpu.monitor.metrics import MetricsRegistry, get_registry

# ---------------------------------------------------------------------------
# instruments
# ---------------------------------------------------------------------------


def test_counter_gauge_basic():
    reg = MetricsRegistry().enable()
    c = reg.counter("ds_t_reqs_total")
    g = reg.gauge("ds_t_depth")
    c.inc()
    c.inc(4)
    g.set(3)
    g.set(7.5)
    assert c.value == 5
    assert g.value == 7.5
    # create-or-return: same (name, labels) is the same instrument
    assert reg.counter("ds_t_reqs_total") is c
    reg.reset()
    assert c.value == 0 and g.value == 0.0


def test_histogram_bucket_assignment():
    reg = MetricsRegistry().enable()
    h = reg.histogram("ds_t_lat_seconds", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 1.5, 3.0, 5.0):   # le semantics: 1.0 -> first bucket
        h.record(v)
    assert h._counts == [2, 1, 1, 1]
    assert h.count == 5
    assert h.sum == pytest.approx(11.0)


def test_histogram_quantiles_land_in_the_right_bucket():
    reg = MetricsRegistry().enable()
    h = reg.histogram("ds_t_lat_seconds")   # default log buckets 1us..100s
    for _ in range(100):
        h.record(0.01)
    for _ in range(100):
        h.record(1.0)
    # p50 must fall inside the bucket containing 0.01, p90 inside the one
    # containing 1.0 (log buckets at 4/decade: bucket width <= ~78%)
    assert 0.005 <= h.quantile(0.5) <= 0.02
    assert 0.5 <= h.quantile(0.9) <= 1.0 + 1e-9
    assert h.mean == pytest.approx(0.505)
    s = h.snapshot()
    assert s["count"] == 200 and s["p99"] <= 1.0 + 1e-9
    # all mass past the last bound: the overflow bucket reports the bound
    h2 = reg.histogram("ds_t_over_seconds", buckets=(1.0, 2.0))
    h2.record(100.0)
    assert h2.quantile(0.5) == 2.0


def test_histogram_snapshot_consistent_under_writes():
    """Reader thread sees count == sum(buckets) on EVERY snapshot while a
    writer hammers record() — the lock-free single-writer contract."""
    reg = MetricsRegistry().enable()
    h = reg.histogram("ds_t_lat_seconds")
    stop = threading.Event()

    def writer():
        while not stop.is_set():
            h.record(0.37)

    t = threading.Thread(target=writer, daemon=True)
    t.start()
    try:
        last = 0
        for _ in range(300):
            s = h.snapshot()
            assert s["count"] == sum(s["buckets"])
            assert s["count"] >= last      # monotone under a single writer
            last = s["count"]
    finally:
        stop.set()
        t.join(timeout=5)
    assert h.count > 0


def test_disabled_path_records_nothing_and_allocates_nothing():
    reg = MetricsRegistry()                 # disabled by default
    c = reg.counter("ds_t_total")
    h = reg.histogram("ds_t_lat_seconds")
    v = 0.125
    c.inc()
    h.record(v)                             # warm any lazy machinery
    vals = [v] * 5000
    before = sys.getallocatedblocks()
    for x in vals:
        h.record(x)
        c.inc()
    delta = sys.getallocatedblocks() - before
    assert c.value == 0 and h.count == 0
    # one branch, no allocation per record: the block count may wiggle a
    # few blocks from interpreter internals, never per-call
    assert delta < 100


def test_duplicate_name_different_kind_raises():
    reg = MetricsRegistry()
    reg.counter("ds_t_thing")
    with pytest.raises(ValueError, match="already registered"):
        reg.histogram("ds_t_thing")
    # a name is uniformly labeled or uniformly bare: mixing would make the
    # snapshot shape ambiguous (crash/drop at scrape time otherwise)
    with pytest.raises(ValueError, match="without labels"):
        reg.counter("ds_t_thing", labels={"reason": "eos"})
    reg.counter("ds_t_fam", labels={"reason": "eos"})
    reg.counter("ds_t_fam", labels={"reason": "length"})  # fine: one kind
    with pytest.raises(ValueError, match="with labels"):
        reg.counter("ds_t_fam")
    # ...and the name still cannot cross kinds through a labeled variant
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("ds_t_fam", labels={"reason": "x"})


# ---------------------------------------------------------------------------
# exposition
# ---------------------------------------------------------------------------

GOLDEN = """\
# TYPE ds_t_depth gauge
ds_t_depth 2
# HELP ds_t_finished_total by reason
# TYPE ds_t_finished_total counter
ds_t_finished_total{reason="eos"} 2
ds_t_finished_total{reason="length"} 1
# HELP ds_t_lat_seconds latency
# TYPE ds_t_lat_seconds histogram
ds_t_lat_seconds_bucket{le="0.1"} 1
ds_t_lat_seconds_bucket{le="1"} 2
ds_t_lat_seconds_bucket{le="10"} 3
ds_t_lat_seconds_bucket{le="+Inf"} 4
ds_t_lat_seconds_sum 55.55
ds_t_lat_seconds_count 4
# HELP ds_t_reqs_total help text
# TYPE ds_t_reqs_total counter
ds_t_reqs_total 3
"""


def test_prometheus_exposition_golden():
    reg = MetricsRegistry().enable()
    reg.counter("ds_t_reqs_total", "help text").inc(3)
    reg.gauge("ds_t_depth").set(2)
    h = reg.histogram("ds_t_lat_seconds", "latency", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.record(v)
    reg.counter("ds_t_finished_total", "by reason",
                labels={"reason": "eos"}).inc(2)
    reg.counter("ds_t_finished_total", labels={"reason": "length"}).inc()
    assert reg.prometheus_text() == GOLDEN


@pytest.mark.parametrize("value, text", [(float("inf"), "inf"),
                                         (float("-inf"), "-inf"),
                                         (float("nan"), "nan")])
def test_prometheus_exposition_renders_non_finite_gauges(value, text):
    """A gauge left at ``inf`` (a gradient norm on an overflow step) must
    not take the whole scrape down with an OverflowError."""
    reg = MetricsRegistry().enable()
    reg.gauge("ds_t_norm").set(value)
    assert f"ds_t_norm {text}\n" in reg.prometheus_text()


def test_statz_json_roundtrip():
    reg = MetricsRegistry().enable()
    reg.counter("ds_t_reqs_total").inc(2)
    reg.histogram("ds_t_lat_seconds", buckets=(1.0,)).record(0.5)
    reg.counter("ds_t_finished_total", labels={"reason": "eos"}).inc()
    snap = json.loads(reg.statz_json())
    assert snap["enabled"] is True
    m = snap["metrics"]
    assert m["ds_t_reqs_total"] == 2
    assert m["ds_t_lat_seconds"]["count"] == 1
    assert m["ds_t_finished_total"]['{reason="eos"}'] == 1


def test_monitor_master_bridge():
    """registry.publish fans counters/gauges/histogram summaries out as
    MonitorMaster events (CSV/TensorBoard backends see the same schema)."""
    reg = MetricsRegistry().enable()
    reg.counter("ds_t_reqs_total").inc(4)
    reg.gauge("ds_t_depth").set(3)
    h = reg.histogram("ds_t_lat_seconds", buckets=(1.0, 2.0))
    h.record(0.5)
    h.record(1.5)

    class FakeMonitor:
        enabled = True

        def __init__(self):
            self.events = []

        def write_events(self, events):
            self.events.extend(events)

    mon = FakeMonitor()
    reg.publish(mon, step=7)
    ev = {name: (value, step) for name, value, step in mon.events}
    assert ev["ds_t_reqs_total"] == (4, 7)
    assert ev["ds_t_depth"] == (3, 7)
    assert ev["ds_t_lat_seconds/count"][0] == 2
    assert ev["ds_t_lat_seconds/mean"][0] == pytest.approx(1.0)
    # disabled monitor: no events
    mon2 = FakeMonitor()
    mon2.enabled = False
    reg.publish(mon2, step=8)
    assert mon2.events == []


# ---------------------------------------------------------------------------
# /statz?window= rate deltas (two scrapes -> rates without Prometheus)
# ---------------------------------------------------------------------------


def test_statz_window_two_scrapes():
    """First scrape of a window key primes it; the second returns
    counter/histogram deltas + per-second rates over the real elapsed
    time.  Distinct keys keep independent baselines."""
    import time
    import urllib.request

    from deepspeed_tpu.monitor.server import MetricsServer

    reg = MetricsRegistry().enable()
    c = reg.counter("ds_t_reqs_total")
    h = reg.histogram("ds_t_lat_seconds", buckets=(1.0, 2.0))
    g = reg.gauge("ds_t_depth")
    c.inc(5)
    h.record(0.5)
    server = MetricsServer(reg, port=0).start()
    try:
        def scrape(q):
            with urllib.request.urlopen(f"{server.url}/statz?{q}",
                                        timeout=5) as r:
                return json.load(r)

        first = scrape("window=5")
        assert first["primed"] is True and first["metrics"] == {}
        c.inc(7)
        h.record(1.5)
        h.record(1.5)
        g.set(3)
        time.sleep(0.05)
        second = scrape("window=5")
        assert second["primed"] is False
        assert second["window_s"] > 0
        m = second["metrics"]
        assert m["ds_t_reqs_total"]["delta"] == 7
        assert m["ds_t_reqs_total"]["per_sec"] == pytest.approx(
            7 / second["window_s"], rel=0.2)
        assert m["ds_t_lat_seconds"]["count_delta"] == 2
        assert m["ds_t_lat_seconds"]["window_mean"] == pytest.approx(1.5)
        assert m["ds_t_depth"]["value"] == 3
        # a different key has its own baseline: full values as the delta
        other = scrape("window=60")
        assert other["primed"] is True
        c.inc(1)
        assert scrape("window=60")["metrics"]["ds_t_reqs_total"]["delta"] == 1
        # plain /statz is unchanged by windowed scrapes
        with urllib.request.urlopen(f"{server.url}/statz", timeout=5) as r:
            assert json.load(r)["metrics"]["ds_t_reqs_total"] == 13
    finally:
        server.stop()


def test_metrics_dump_serving_prefix_hit_ratio_line():
    """--serving renders the prefix-cache hit-ratio line from the
    ds_serve_prefix_* series (and omits it when the cache never ran)."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                    "tools"))
    try:
        import metrics_dump
    finally:
        sys.path.pop(0)
    m = {"ds_serve_kv_pages_used": 6, "ds_serve_kv_pages_free": 2,
         "ds_serve_preempted_total": 1,
         "ds_serve_prefix_hit_tokens_total": 300,
         "ds_serve_prefix_miss_tokens_total": 100,
         "ds_serve_prefix_cache_pages": 7,
         "ds_serve_prefix_evictions_total": 2}
    out = metrics_dump.serving_kv_summary(m)
    assert "kv pages: 6 used / 2 free (8 total)" in out
    assert "prefix cache: 75.0% hit ratio (300 hit / 100 computed" in out
    assert "7 cached pages" in out and "2 evictions" in out
    # cache never ran (off or fixed-slot): no prefix line at all
    cold = metrics_dump.serving_kv_summary(
        {"ds_serve_kv_pages_used": 1, "ds_serve_kv_pages_free": 7})
    assert "prefix cache" not in cold and "host tier" not in cold
    # host tier ran: one line with resident/demoted/promoted counts
    tier = metrics_dump.serving_kv_summary(
        {**m, "ds_serve_kv_host_pages": 3, "ds_serve_kv_demote_total": 9,
         "ds_serve_kv_promote_total": 6})
    assert "kv host tier: 3 pages resident, 9 demoted, 6 promoted" in tier


def test_metrics_dump_offload_relay_line():
    """--comms renders the offload relay one-liner from ds_offload_*
    (and nothing when the offload path never ran)."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                    "tools"))
    try:
        import metrics_dump
    finally:
        sys.path.pop(0)
    m = {"ds_offload_relay_bytes_total": {'{dir="h2d"}': 3 * 2**20,
                                          '{dir="d2h"}': 2**20},
         "ds_offload_prefetch_hits_total": 30,
         "ds_offload_prefetch_misses_total": 10,
         "ds_offload_relay_seconds": {"count": 40, "sum": 0.25}}
    line = metrics_dump.offload_relay_line(m)
    assert "3.00 MiB h2d / 1.00 MiB d2h" in line
    assert "prefetch 75% hit (30/40)" in line
    assert "0.25s stalled" in line
    assert metrics_dump.offload_relay_line({}) == ""
    assert metrics_dump.offload_relay_line(
        {"ds_offload_relay_bytes_total": {}}) == ""


def test_metrics_dump_renders_snapshot_and_csv(tmp_path):
    """tools/metrics_dump.py renders /statz snapshots and csvMonitor dirs
    as terminal tables (stdlib-only; used against live ports in ops)."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                    "tools"))
    try:
        import metrics_dump
    finally:
        sys.path.pop(0)
    reg = MetricsRegistry().enable()
    reg.counter("ds_t_reqs_total").inc(5)
    reg.histogram("ds_t_lat_seconds", buckets=(1.0, 2.0)).record(0.5)
    reg.counter("ds_t_finished_total", labels={"reason": "eos"}).inc(2)
    snap = tmp_path / "statz.json"
    snap.write_text(reg.statz_json())
    table = metrics_dump.render(metrics_dump.rows_from_snapshot(
        metrics_dump.load_snapshot(str(snap))))
    assert "ds_t_reqs_total" in table and "5" in table
    assert 'ds_t_finished_total{reason="eos"}' in table
    # csvMonitor dir: last value per series
    mon = tmp_path / "mon"
    mon.mkdir()
    (mon / "Train_loss.csv").write_text("step,Train/loss\n1,2.5\n2,2.25\n")
    table = metrics_dump.render(metrics_dump.rows_from_snapshot(
        metrics_dump.load_snapshot(str(mon))))
    assert "Train_loss" in table and "2.25 @ step 2" in table

    # --comms overlap on/off indicator (docs/OBSERVABILITY.md "Overlap")
    assert metrics_dump.overlap_line({}) == \
        "overlap: off (GSPMD-placed collectives)"
    line = metrics_dump.overlap_line({"ds_overlap_buckets": 4.0,
                                      "ds_overlap_hidden_comm_seconds_est":
                                      0.0})
    assert line == "overlap: on (4 buckets, no device capture yet)"
    # a capture that MEASURED zero hidden comm is not "no capture"
    line = metrics_dump.overlap_line({"ds_overlap_buckets": 4.0,
                                      "ds_overlap_hidden_comm_seconds_est":
                                      0.0,
                                      "ds_profile_window_seconds": 1.5})
    assert line == "overlap: on (4 buckets, 0s comm hidden in last capture)"
    line = metrics_dump.overlap_line({"ds_overlap_buckets": 4.0,
                                      "ds_overlap_hidden_comm_seconds_est":
                                      0.0125})
    assert "overlap: on (4 buckets" in line and "0.0125s/step" in line
    # csvMonitor-directory snapshots carry {"last": ...} series dicts
    line = metrics_dump.overlap_line(
        {"ds_overlap_buckets": {"last": 4.0, "step": 3, "events": 3},
         "ds_overlap_hidden_comm_seconds_est": {"last": 0.0125, "step": 3,
                                                "events": 3}})
    assert "overlap: on (4 buckets" in line and "0.0125s/step" in line


# ---------------------------------------------------------------------------
# tier-1 namespace guard
# ---------------------------------------------------------------------------

_DOC = os.path.join(os.path.dirname(__file__), "..", "..", "docs",
                    "OBSERVABILITY.md")


@pytest.mark.parametrize("num_devices", [1, 4])
def test_train_mfu_divides_by_the_whole_meshs_peak(monkeypatch, num_devices):
    """The step's FLOPs are the global batch's, so utilisation is against
    one device's peak times the devices in the mesh."""
    from deepspeed_tpu.profiling import flops

    monkeypatch.setattr(flops, "_peak_or_none", lambda: 100e12)
    clock = iter([10.0, 12.0])
    monkeypatch.setattr(flops, "time", types.SimpleNamespace(
        perf_counter=lambda: next(clock)))
    reg = MetricsRegistry().enable()
    meter = flops.TrainFlopsMeter(reg, num_devices=num_devices)
    meter.observe_boundary(80e12)          # arms the clock
    meter.observe_boundary(80e12)          # 80 TFLOP in 2 s = 40 TFLOP/s
    assert reg.get("ds_train_tflops").value == pytest.approx(40.0)
    assert reg.get("ds_train_mfu").value == pytest.approx(0.4 / num_devices)


def test_namespace_guard_all_metrics_documented(devices):
    """Fails the suite if ANY registered metric leaves the ``ds_``
    namespace or is missing from docs/OBSERVABILITY.md (docs drift =
    red).  Registers the full engine surface first so the guard holds
    regardless of test order."""
    from deepspeed_tpu.comm.mesh import build_mesh, set_global_mesh
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
    from deepspeed_tpu.models import causal_lm
    from deepspeed_tpu.monitor.comms import comm_metrics
    from deepspeed_tpu.monitor.memory import MemoryTelemetry
    from deepspeed_tpu.profiling.flops import TrainFlopsMeter
    from deepspeed_tpu.serving.engine import ServingEngine
    from deepspeed_tpu.utils.timer import SynchronizedWallClockTimer

    # instantiate every instrument owner (no weights/compiles needed)
    mesh = build_mesh(fsdp=8, devices=devices)
    set_global_mesh(mesh)
    model = causal_lm("llama-tiny", mesh=mesh, num_layers=1, hidden_size=32,
                      intermediate_size=64, num_heads=2, num_kv_heads=1,
                      vocab_size=64, remat=False)
    InferenceEngine(model, DeepSpeedInferenceConfig(dtype="float32"))
    ServingEngine(model, {"dtype": "float32", "max_out_tokens": 32},
                  num_slots=1)
    timers = SynchronizedWallClockTimer()
    for n in (timers.FORWARD, timers.BACKWARD, timers.STEP, timers.BATCH):
        timers(n)
    # PR 3 families: the full comm-op instrument surface, HBM gauges, and
    # the FLOPs/MFU gauges — all must be documented too (guard EXTENDED,
    # not weakened)
    comm_metrics.ensure_registered()
    MemoryTelemetry()
    TrainFlopsMeter()
    # ISSUE 5 device-truth families: the ds_profile_* gauges and every
    # ds_comm_<op>_device_* series must be documented too
    from deepspeed_tpu.profiling import device_trace

    device_trace.ensure_registered(get_registry())
    # ISSUE 20 families: the continuous-profiler ds_prof_* window gauges
    # and counters (the labeled scope/regression series register at first
    # use with labels, exercised by tests/unit/test_continuous_profiler)
    from deepspeed_tpu.profiling import continuous

    continuous.ensure_registered(get_registry())
    get_registry().gauge("ds_prof_scope_device_seconds",
                         labels={"scope": "fwd_bwd"}).set(0.0)
    get_registry().counter("ds_prof_regressions_total",
                           labels={"scope": "comm"})
    # ISSUE 7 families: the per-request phase-attribution histograms
    # (registered at tracer construction) and the training-numerics
    # step gauges (registered lazily at the optimizer boundary, so the
    # guard registers them explicitly here)
    from deepspeed_tpu.monitor.request_trace import PHASES, \
        get_request_tracer
    from deepspeed_tpu.runtime.engine import TRAIN_STEP_GAUGES

    get_request_tracer()
    for _n, _h in TRAIN_STEP_GAUGES.items():
        get_registry().gauge(_n, _h)

    with open(_DOC) as fh:
        documented = set(re.findall(r"ds_[a-z0-9_]+", fh.read()))
    # every phase in the edge partition must have its histogram
    # documented BY NAME (not as a pattern): the fleet/router consumers
    # key on the exact series names
    for _p in PHASES:
        assert f"ds_serve_phase_{_p}_seconds" in documented, (
            f"ds_serve_phase_{_p}_seconds is part of the request-span "
            f"edge partition but is not documented in "
            f"docs/OBSERVABILITY.md")
    name_re = re.compile(r"^ds_[a-z0-9_]+$")
    train_re = re.compile(r"^ds_train_[a-z0-9_]+_seconds$")
    # ds_comm_<op>_<suffix>: the suffix schema is documented as a table;
    # every OP SLUG must additionally appear in the documented op list
    # (written there as `ds_comm_<op>_` tokens).  The device-truth
    # suffixes (_device_seconds / _device_busbw_gbps) are part of the
    # schema and additionally require their suffix token documented —
    # no blanket exemption for the new family.
    comm_re = re.compile(r"^ds_comm_([a-z0-9_]+?)_"
                         r"(calls_total|bytes_total|dense_bytes_total|"
                         r"seconds|algbw_gbps|"
                         r"busbw_gbps|device_seconds|device_busbw_gbps)$")
    # the quantized dense-twin suffix is part of the schema: its name must
    # be documented like the device-truth suffixes (guard extended)
    assert any(d.endswith("dense_bytes_total") for d in documented), (
        "the ds_comm_*_dense_bytes_total schema is registered but no "
        "*_dense_bytes_total name is documented in docs/OBSERVABILITY.md")
    for suffix in ("device_seconds", "device_busbw_gbps"):
        assert any(d.endswith(suffix) for d in documented), (
            f"the ds_comm_*_{suffix} schema is registered but no "
            f"*_{suffix} name is documented in docs/OBSERVABILITY.md")
    names = get_registry().names()
    assert names, "no metrics registered — instrumentation went missing?"
    bad_ns = [n for n in names if not name_re.match(n)]
    assert not bad_ns, f"metrics outside the ds_ namespace: {bad_ns}"
    undoc = []
    for n in names:
        if train_re.match(n):
            continue
        m = comm_re.match(n)
        if m:
            if f"ds_comm_{m.group(1)}_" not in documented:
                undoc.append(n)
            continue
        if n not in documented:
            undoc.append(n)
    assert not undoc, (f"metrics not documented in docs/OBSERVABILITY.md: "
                       f"{undoc} (the ds_train_*_seconds family is exempt "
                       f"— it is documented as a pattern; ds_comm op slugs "
                       f"must appear in the documented op list)")
