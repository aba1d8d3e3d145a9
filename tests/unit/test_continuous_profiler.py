"""Continuous-profiler tests (ISSUE 20 tentpole).

The offline half (history ring, window schema, differ, duty-cycle
scheduler) is exercised with synthetic summaries and a fake clock; the
live half runs real scheduled TraceCaptures against CPU training AND
serving engines at a forced cadence and checks the acceptance contract:
>=2 persisted windows, per-scope device-seconds bounded by the window
wall, telescoping capture wall, and the registry/flight commits — all
with no operator ``/profilez`` anywhere.  The disabled default must keep
the compiled step program byte-identical and allocate nothing.
"""

import json
import os
import sys
import urllib.error
import urllib.request

import pytest

import jax
import jax.numpy as jnp
import numpy as np

import deepspeed_tpu
from deepspeed_tpu.comm.mesh import build_mesh, set_global_mesh
from deepspeed_tpu.models import causal_lm
from deepspeed_tpu.monitor.metrics import MetricsRegistry, get_registry
from deepspeed_tpu.profiling import continuous
from tests.unit.hlo_text import program_text
from tests.unit.simple_model import SimpleModel, random_dataset

PHASES = ("fwd_bwd", "optimizer", "comm", "other", "gap")


def _summary(fwd=0.010, opt=0.002, comm=0.001, other=0.0005, gap=0.0005,
             steps=2, lo=100.0, ag=None):
    """Synthetic ``summarize_trace`` result: per-step phase seconds that
    partition the per-step wall, one all_gather device collective."""
    per_step_wall = fwd + opt + comm + other + gap
    window = per_step_wall * steps
    per = {"fwd_bwd_s": fwd, "optimizer_s": opt, "comm_s": comm,
           "other_s": other, "gap_s": gap}
    return {"steps": steps, "window_s": window,
            "device_busy_s": window - gap * steps,
            "phases": {k: v * steps for k, v in per.items()},
            "per_step": per,
            "comm_device": {"all_gather": {
                "seconds": (comm if ag is None else ag) * steps,
                "count": 2 * steps}},
            "clock": {"anchor_unix": lo, "window_unix_lo": lo,
                      "window_unix_hi": lo + window},
            "degraded": False, "source": "synthetic"}


def _window(tmp=None, seq=None, **kw):
    w = continuous.build_window(_summary(**kw), engine="train",
                                step=10, capture_wall_s=0.05,
                                coverage_ratio=0.01, overhead_ratio=0.02)
    if seq is not None:
        w["seq"] = seq
    return w


# ---------------------------------------------------------------------------
# history ring
# ---------------------------------------------------------------------------


def test_history_ring_roundtrip_seq_and_atomicity(tmp_path):
    ring = continuous.HistoryRing(str(tmp_path / "hist"))
    assert ring.paths() == [] and ring.latest(3) == []
    p1 = ring.append(_window())
    p2 = ring.append(_window())
    assert [os.path.basename(p) for p in ring.paths()] == \
        ["ds_prof_window_00000001.json", "ds_prof_window_00000002.json"]
    assert (p1, p2) == tuple(ring.paths())
    # atomic writes: no .tmp litter ever visible
    assert not [n for n in os.listdir(ring.directory) if n.endswith(".tmp")]
    wins = ring.latest(5)
    assert [w["seq"] for w in wins] == [1, 2]   # oldest-first
    # a torn file (crashed writer) loads as None and is skipped
    with open(ring.paths()[0], "w") as fh:
        fh.write('{"seq": 1, "scopes": {')
    assert continuous.HistoryRing.load(ring.paths()[0]) is None
    assert [w["seq"] for w in ring.latest(5)] == [2]


def test_history_ring_retention_by_count_and_bytes(tmp_path):
    ring = continuous.HistoryRing(str(tmp_path), max_windows=3)
    for _ in range(5):
        ring.append(_window())
    assert [w["seq"] for w in ring.latest(9)] == [3, 4, 5]
    # bytes cap: every file is several hundred bytes, so a 1KB budget
    # keeps at most a couple of windows regardless of max_windows
    ring_b = continuous.HistoryRing(str(tmp_path / "b"), max_windows=99,
                                    max_bytes=1024)
    for _ in range(6):
        ring_b.append(_window())
    paths = ring_b.paths()
    assert len(paths) < 6
    assert sum(os.path.getsize(p) for p in paths) <= 1024
    # the NEWEST window survives pruning
    assert ring_b.latest(1)[0]["seq"] == 6


# ---------------------------------------------------------------------------
# window schema + differ
# ---------------------------------------------------------------------------


def test_build_window_scopes_partition_per_step_wall():
    w = _window()
    per_step_wall = w["window_s"] / w["steps"]
    assert sum(w["scopes"][p] for p in PHASES) == \
        pytest.approx(per_step_wall)
    # device collectives ride as per-step comm_<op> lanes
    assert w["scopes"]["comm_all_gather"] == pytest.approx(0.001)
    assert w["busy_ratio"] < 1.0 and w["clock"]["window_unix_lo"] == 100.0


def test_diff_windows_flags_seeded_comm_regression():
    prev = _window()
    # 8x per-step comm: the lane itself AND the per-step wall (0.014 ->
    # 0.021, +50%) both clear the 25% default tolerance
    cur = _window(comm=0.008)
    regs = continuous.diff_windows(prev, cur)
    names = [r["scope"] for r in regs]
    assert "comm" in names and "comm_all_gather" in names
    # the slowdown also moves the synthesized per-step wall lane
    assert "step_time" in names
    top = regs[0]
    assert top["cur_s"] > top["prev_s"]
    assert top["rel"] > top["tol"]
    # clean twin: byte-equal scopes produce no findings
    assert continuous.diff_windows(prev, _window()) == []


def test_diff_windows_tolerance_rules_and_noise_floor():
    # gap is a noisy remainder lane: default bar is 50%, so +40% passes
    prev = _window(gap=0.0010)
    cur = _window(gap=0.0014)
    assert continuous.diff_windows(prev, cur) == []
    assert [r["scope"] for r in
            continuous.diff_windows(prev, _window(gap=0.0016))] == ["gap"]
    # shared-substring override: the caller's rule comes first and wins
    assert continuous.tolerance_for("comm_all_gather",
                                    [("all_gather", 0.9)]) == 0.9
    assert continuous.tolerance_for("gap") == 0.50
    assert continuous.tolerance_for("fwd_bwd") == continuous.DEFAULT_TOLERANCE
    # sub-floor lanes never alert (5e-5s default): a 10x move on a
    # nanoseconds-scale scope is measurement noise
    prev = _window(other=1e-6)
    assert not [r for r in continuous.diff_windows(prev, _window(other=1e-5))
                if r["scope"] == "other"]


# ---------------------------------------------------------------------------
# scheduler: cadence + duty cycle (fake clock, no real captures)
# ---------------------------------------------------------------------------


def test_due_every_n_steps_or_t_seconds(tmp_path):
    t = [0.0]
    prof = continuous.ContinuousProfiler(
        engine="sched-test", every_steps=10, every_seconds=5.0,
        history_dir=str(tmp_path), clock=lambda: t[0])
    try:
        assert not prof.due(5)
        assert prof.due(10)          # step cadence
        t[0] = 6.0
        assert prof.due(1)           # time cadence fires first
    finally:
        with continuous._ACTIVE_LOCK:
            continuous._ACTIVE.pop("sched-test", None)


def test_duty_cycle_defers_and_counts(tmp_path):
    t = [100.0]
    prof = continuous.ContinuousProfiler(
        engine="duty-test", every_steps=1, max_duty_cycle=0.01,
        history_dir=str(tmp_path), clock=lambda: t[0])
    try:
        assert prof._duty_ok()       # first window: nothing measured yet
        # book one expensive window: 1s of overhead over 10s of run is a
        # 10% duty cycle — 10x over the 1% cap
        prof.windows = 1
        prof._overhead_s = 1.0
        t[0] = 110.0
        assert prof.due(50)
        assert not prof.maybe_begin(50)      # deferred BEFORE any capture
        assert prof.skipped_duty == 1
        assert prof._last_t == 110.0         # timer cadence pushed back
        # budget recovers as wall clock accrues: 1s + 1s est over 300s
        t[0] = 400.0
        assert prof._duty_ok()
    finally:
        with continuous._ACTIVE_LOCK:
            continuous._ACTIVE.pop("duty-test", None)


# ---------------------------------------------------------------------------
# regression publish: registry counter + flight event
# ---------------------------------------------------------------------------


class _FakeFlight:
    def __init__(self):
        self.events = []

    def record(self, kind, **fields):
        self.events.append((kind, fields))


def test_publish_commits_gauges_counters_and_flight(tmp_path):
    reg = MetricsRegistry().enable()
    continuous.ensure_registered(reg)
    flight = _FakeFlight()
    prof = continuous.ContinuousProfiler(
        engine="pub-test", history_dir=str(tmp_path), registry=reg,
        flight=flight)
    try:
        prev, cur = _window(), _window(comm=0.004)
        regs = continuous.diff_windows(prev, cur)
        prof._publish(cur, regs)
        snap = json.loads(reg.statz_json())["metrics"]
        assert snap["ds_prof_window_seconds"] == \
            pytest.approx(cur["window_s"])
        assert snap["ds_prof_windows_total"] == 1
        assert '{scope="fwd_bwd"}' in snap["ds_prof_scope_device_seconds"]
        assert {'{scope="comm"}', '{scope="comm_all_gather"}'} <= \
            set(snap["ds_prof_regressions_total"])
        kinds = [k for k, _ in flight.events]
        assert "prof_regression" in kinds
        ev = dict(flight.events)[("prof_regression")]
        assert ev["engine"] == "pub-test" and ev["rel"] > ev["tol"]
    finally:
        with continuous._ACTIVE_LOCK:
            continuous._ACTIVE.pop("pub-test", None)


# ---------------------------------------------------------------------------
# disabled default: one branch, zero allocation, identical programs
# ---------------------------------------------------------------------------


def _train_cfg(extra=None):
    cfg = {"train_micro_batch_size_per_gpu": 2,
           "gradient_accumulation_steps": 1,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
           "steps_per_print": 10**9}
    cfg.update(extra or {})
    return cfg


def test_disabled_default_off_contract(tmp_path):
    x, y = random_dataset(n=8)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=8), config=_train_cfg(),
        rng=jax.random.PRNGKey(0))
    assert engine._cprof is None
    before = set(get_registry().snapshot())
    for _ in range(2):
        loss = engine.forward((x, y))
        engine.backward(loss)
        engine.step()
    # zero captures, zero new ds_prof series, no history dir anywhere
    new = {k for k in set(get_registry().snapshot()) - before
           if k.startswith("ds_prof_")}
    assert new == set()
    # the compiled step program is the same as an armed-but-idle engine's
    # (source locations aside: the two are lowered from two lines here):
    # the profiler lives entirely OUTSIDE the jit boundary
    hist = str(tmp_path / "hist")
    armed, _, _, _ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=8),
        config=_train_cfg({"continuous_profiler": {
            "enabled": True, "every_steps": 10**6,
            "every_seconds": 10**6, "history_dir": hist}}),
        rng=jax.random.PRNGKey(0))
    assert armed._cprof is not None and not armed._cprof.active
    loss = armed.forward((x, y))
    armed.backward(loss)
    armed.step()
    rng = jax.random.PRNGKey(1)
    txt_off = program_text(engine._accum_fn.lower(
        engine.state, (x, y), rng).compile())
    txt_on = program_text(armed._accum_fn.lower(
        armed.state, (x, y), rng).compile())
    assert "ENTRY" in txt_off and txt_off == txt_on
    with continuous._ACTIVE_LOCK:
        continuous._ACTIVE.pop("train", None)


# ---------------------------------------------------------------------------
# live e2e: scheduled windows from real CPU training / serving loops
# ---------------------------------------------------------------------------


def _assert_window_contract(w, engine):
    assert w["engine"] == engine and w["schema_version"] == 1
    per_step_wall = w["window_s"] / max(1, w["steps"])
    phase_s = sum(w["scopes"].get(p, 0.0) for p in PHASES)
    # the five phase lanes partition the per-step wall (float slack)
    assert phase_s <= per_step_wall * 1.001
    assert 0.0 < w["coverage_ratio"] <= 1.0
    assert w["coverage_ratio"] <= w["overhead_ratio"] <= 1.0


def test_training_engine_produces_scheduled_windows(tmp_path):
    """A stepping CPU engine with the profiler armed at forced cadence
    commits >=2 history windows with NOBODY calling /profilez."""
    hist = str(tmp_path / "hist")
    x, y = random_dataset(n=8)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=8),
        config=_train_cfg({"continuous_profiler": {
            "enabled": True, "every_steps": 2, "every_seconds": 3600.0,
            "capture_steps": 1, "max_duty_cycle": 1.0,
            "history_dir": hist}}),
        rng=jax.random.PRNGKey(0))
    try:
        assert engine._cprof is not None
        # one CPU capture costs seconds (profiler start/stop) against
        # millisecond steps, so after the first window the projected
        # overhead exceeds ANY duty cap <= 1 and the second is deferred;
        # the duty policy has its own fake-clock tests above, so lift the
        # cap here (as the serving twin does) and test only the cadence
        engine._cprof.max_duty_cycle = 100.0
        ring = engine._cprof.ring
        n = 0
        import time as _time
        t0 = _time.perf_counter()
        while n < 16 and len(ring.paths()) < 2:
            loss = engine.forward((x, y))
            engine.backward(loss)
            engine.step()
            n += 1
        wall = _time.perf_counter() - t0
        wins = ring.latest(4)
        assert len(wins) >= 2, f"{len(wins)} windows after {n} steps"
        for w in wins:
            _assert_window_contract(w, "train")
        # telescoping: capture wall summed over windows fits the run wall
        assert sum(w["capture_wall_s"] for w in wins) <= wall
        assert wins[-1]["trigger"] == "continuous"
        snap = get_registry().snapshot()
        assert snap.get("ds_prof_windows_total", 0) >= 2
        assert snap.get("ds_prof_window_seconds", 0) > 0
        # in-flight capture dir is cleaned up after each decompose
        assert not os.path.exists(os.path.join(hist, "_capture"))
    finally:
        if engine._cprof is not None:
            engine._cprof.close()
        with continuous._ACTIVE_LOCK:
            continuous._ACTIVE.pop("train", None)


def test_serving_engine_produces_scheduled_windows(tmp_path, devices):
    hist = str(tmp_path / "hist")
    mesh = build_mesh(fsdp=8, devices=devices)
    set_global_mesh(mesh)
    model = causal_lm("llama-tiny", mesh=mesh, num_layers=2, hidden_size=64,
                      intermediate_size=128, num_heads=4, num_kv_heads=2,
                      vocab_size=256, remat=False)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    serve = deepspeed_tpu.init_serving(
        model, config={"dtype": "float32", "max_out_tokens": 64,
                       "continuous_profiler": {
                           "enabled": True, "every_steps": 2,
                           "every_seconds": 3600.0, "capture_steps": 1,
                           "max_duty_cycle": 1.0, "history_dir": hist}},
        num_slots=2, prefill_chunk=4, decode_block_tokens=3)
    serve.set_params(params)
    try:
        assert serve._cprof is not None
        # the first CPU window tends to span slot-program compiles (a
        # seconds-long capture), which poisons the measured per-window
        # overhead estimate; the duty-cycle policy has its own dedicated
        # test above, so lift the cap here and test only the cadence
        serve._cprof.max_duty_cycle = 100.0
        ring = serve._cprof.ring
        rng = jax.random.PRNGKey(3)
        waves = 0
        while waves < 6 and len(ring.paths()) < 2:
            keys = jax.random.split(rng, 7)
            rng = keys[0]
            for k in keys[1:]:
                serve.submit(np.asarray(jax.random.randint(k, (5,), 0, 256)),
                             max_new_tokens=12)
            serve.run()
            waves += 1
        wins = ring.latest(4)
        assert len(wins) >= 2, \
            f"{len(wins)} windows after {waves} request waves"
        for w in wins:
            _assert_window_contract(w, "serving")
    finally:
        serve.close()
        with continuous._ACTIVE_LOCK:
            continuous._ACTIVE.pop("serving", None)


# ---------------------------------------------------------------------------
# readers: /profilez/history + metrics_dump --profile
# ---------------------------------------------------------------------------


def _tools_import(name):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                    "tools"))
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


def test_profilez_history_endpoint_and_dump_profile(tmp_path):
    from deepspeed_tpu.monitor.server import MetricsServer

    hist = str(tmp_path / "hist")
    prof = continuous.ContinuousProfiler(engine="hist-test",
                                         history_dir=hist)
    prof.ring.append(_window())
    prof.ring.append(_window(comm=0.002))
    server = MetricsServer(MetricsRegistry().enable(), port=0).start()
    try:
        with urllib.request.urlopen(f"{server.url}/profilez/history?n=4",
                                    timeout=10) as resp:
            snap = json.load(resp)
        assert "hist-test" in snap["engines"]
        assert [w["seq"] for w in snap["windows"]
                if w["engine"] == "train"] == [1, 2]
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{server.url}/profilez/history?n=bogus",
                                   timeout=10)
        assert ei.value.code == 400

        # metrics_dump --profile over BOTH sources: live URL and ring dir
        metrics_dump = _tools_import("metrics_dump")
        for src in (server.url, hist):
            loaded = metrics_dump.load_profile_history(src)
            assert len(loaded["windows"]) == 2
            text = metrics_dump.render_profile(loaded)
            assert "fwd_bwd" in text and "comm_all_gather" in text
            assert "window #2" in text
        rows = metrics_dump.profile_rows(loaded["windows"][-1])
        assert rows[0][0] == "fwd_bwd"      # sorted by share, descending
        shares = [float(r[2].rstrip("%")) for r in rows]
        assert shares == sorted(shares, reverse=True)
    finally:
        server.stop()
        with continuous._ACTIVE_LOCK:
            continuous._ACTIVE.pop("hist-test", None)


def test_history_snapshot_orders_and_limits(tmp_path):
    prof = continuous.ContinuousProfiler(engine="snap-test",
                                         history_dir=str(tmp_path))
    try:
        for _ in range(3):
            prof.ring.append(_window())
        snap = continuous.history_snapshot(limit=2)
        ours = [w for w in snap["windows"] if w["engine"] == "train"]
        assert [w["seq"] for w in ours] == [2, 3]
    finally:
        with continuous._ACTIVE_LOCK:
            continuous._ACTIVE.pop("snap-test", None)
