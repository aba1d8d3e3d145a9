"""Run-level goodput ledger (ISSUE 18): telescoping wall-clock
attribution, restart-aware stitching, SLO burn-rate alerts.

Three layers: (1) ``goodput_core`` units — the attribution state machine
(stack + cursor + idle residual) and the stitcher's gap arithmetic;
(2) ``GoodputLedger`` process wiring — gauges, jsonl persistence, the
SLO watcher, ``/goodputz``; (3) engine e2e — a real train engine's
seams feed the ledger, checkpoint flight events reconcile with ledger
event rows by id, and THE chaos acceptance: kill → restart → resume →
anomaly rollback stitches into one telescoping run with nonzero
``restart_downtime`` and ``rollback``.
"""

import json
import os
import sys
import time
import urllib.request

import numpy as np
import pytest

import jax

import deepspeed_tpu
from deepspeed_tpu.monitor import goodput_core as core
from deepspeed_tpu.monitor.flight_recorder import get_flight_recorder
from deepspeed_tpu.monitor.goodput import (GoodputLedger, SloWatcher,
                                           get_goodput_ledger)
from deepspeed_tpu.monitor.metrics import get_registry
from deepspeed_tpu.testing import chaos
from tests.unit.simple_model import SimpleModel, random_dataset

_TOOLS = os.path.join(os.path.dirname(__file__), "..", "..", "tools")

X, Y = random_dataset(n=32)


def _tool(name):
    sys.path.insert(0, _TOOLS)
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


# ---------------------------------------------------------------------------
# LedgerCore units (jax-free attribution arithmetic)
# ---------------------------------------------------------------------------


def test_core_telescopes_with_nested_regions():
    """Synthetic clock: nested regions attribute to the INNERMOST open
    region, pop returns direct seconds (nested time excluded), idle is
    the residual, and the snapshot telescopes exactly."""
    c = core.LedgerCore(start=100.0)
    c.push("compute", 101.0)            # [100, 101) idle
    c.push("checkpoint_save", 103.0)    # [101, 103) compute
    cat, direct = c.pop(104.5)          # [103, 104.5) checkpoint_save
    assert cat == "checkpoint_save" and direct == pytest.approx(1.5)
    cat, direct = c.pop(106.0)          # [104.5, 106) compute again
    assert cat == "compute"
    assert direct == pytest.approx(3.5)  # 2.0 + 1.5, MINUS the nested 1.5
    snap = c.snapshot(110.0)            # [106, 110) idle
    assert snap["wall_s"] == pytest.approx(10.0)
    assert snap["categories"]["compute"] == pytest.approx(3.5)
    assert snap["categories"]["checkpoint_save"] == pytest.approx(1.5)
    assert snap["categories"]["idle"] == pytest.approx(5.0)
    assert core.telescopes(snap)
    assert snap["goodput_ratio"] == pytest.approx(0.35)
    # snapshot with a region still OPEN telescopes too (open accrual
    # counts toward its category, not idle)
    c.push("recompile", 110.0)
    snap = c.snapshot(112.0)
    assert snap["categories"]["recompile"] == pytest.approx(2.0)
    assert snap["open_regions"] == ["recompile"]
    assert core.telescopes(snap)


def test_core_shift_clamps_and_preserves_sum():
    c = core.LedgerCore(start=0.0)
    c.push("compute", 0.0)
    c.pop(4.0)
    assert c.shift("compute", "exposed_comm", 1.5) == pytest.approx(1.5)
    # clamped at what src holds: asking for 10 moves only the 2.5 left
    assert c.shift("compute", "anomaly_skip", 10.0) == pytest.approx(2.5)
    snap = c.snapshot(4.0)
    assert snap["categories"]["compute"] == 0.0
    assert snap["categories"]["exposed_comm"] == pytest.approx(1.5)
    assert snap["categories"]["anomaly_skip"] == pytest.approx(2.5)
    assert core.telescopes(snap)
    with pytest.raises(ValueError):
        c.shift("compute", "nonsense", 1.0)


def test_core_crash_tolerance_edges():
    """Pop with nothing open is a no-op; a retreating clock attributes
    nothing (never negative); unknown categories are a closed-set error."""
    c = core.LedgerCore(start=0.0)
    assert c.pop(1.0) == (None, 0.0)
    c.push("compute", 2.0)
    c.pop(1.5)                           # clock retreat: 0 attributed
    assert c.totals["compute"] == 0.0
    with pytest.raises(ValueError):
        c.push("espresso_break", 3.0)
    assert core.telescopes(c.snapshot(5.0))


def test_stitch_filters_run_id_for_fleet_jsonl(tmp_path):
    """A serve fleet shares ONE jsonl with per-replica run ids
    (``<run>-r<i>``): stitch(run_id=) folds each replica independently
    and ignores the others' rows."""
    path = str(tmp_path / "fleet.jsonl")
    for rid, up, comp in (("s-r0", 10.0, 9.0), ("s-r1", 8.0, 4.0)):
        snap = {"categories": {"compute": comp, "idle": up - comp},
                "goodput_ratio": comp / up, "tokens": 100, "steps": 5}
        core.append_row(path, core.start_row(rid, 0, "serve", 1000.0))
        core.append_row(path, core.tick_row(rid, 0, 1000.0 + up, up, snap))
    r0 = core.stitch(core.read_rows(path), run_id="s-r0")
    r1 = core.stitch(core.read_rows(path), run_id="s-r1")
    assert r0["wall_s"] == pytest.approx(10.0)
    assert r1["wall_s"] == pytest.approx(8.0)
    assert r0["goodput_ratio"] == pytest.approx(0.9)
    assert r1["goodput_ratio"] == pytest.approx(0.5)
    assert core.telescopes(r0) and core.telescopes(r1)


# ---------------------------------------------------------------------------
# GoodputLedger wiring: gauges, jsonl, SLO watcher, /goodputz
# ---------------------------------------------------------------------------


def test_ledger_disabled_is_free_and_inert():
    gp = GoodputLedger()
    gp.push("compute")
    assert gp.pop() == 0.0
    assert gp.shift("compute", "exposed_comm", 1.0) == 0.0
    gp.add_tokens(100)
    assert gp.snapshot() == {"enabled": False}
    assert gp.note_event("checkpoint_save", 1.0) == ""
    assert gp.tick(force=True) is None


def test_ledger_gauges_jsonl_and_slo_burn(tmp_path):
    """One enabled ledger: a compute region + tokens, then a forced tick
    exports ``ds_run_goodput_ratio`` + ``ds_run_time_seconds{category=}``,
    persists start/tick rows, and the ``goodput_ratio`` MIN rule (set
    impossibly high) burns — counter + flight event + jsonl row."""
    reg = get_registry()
    reg.enable()
    flight = get_flight_recorder()
    flight.enable(capacity=64)
    path = str(tmp_path / "runledger.jsonl")
    gp = GoodputLedger()
    gp.enable(path=path, run_id="t1", role="train", incarnation=0,
              slo_rules={"goodput_ratio": 0.9999})
    try:
        gp.push("compute")
        time.sleep(0.02)
        gp.pop()
        gp.add_tokens(512)
        gp.set_steps(2)
        snap = gp.tick(force=True)
        assert snap is not None and core.telescopes(snap)
        assert snap["categories"]["compute"] > 0.0
        assert reg.get("ds_run_goodput_ratio").value == pytest.approx(
            snap["goodput_ratio"])
        assert reg.get("ds_run_time_seconds",
                       {"category": "compute"}).value > 0.0
        # the MIN rule burned (a mostly-idle run cannot hit 0.9999)
        assert reg.get("ds_slo_burn_total",
                       {"rule": "goodput_ratio"}).value >= 1
        assert any(e["kind"] == "slo_burn" and e["rule"] == "goodput_ratio"
                   for e in flight.events())
        rows = core.read_rows(path)
        kinds = [r["kind"] for r in rows]
        assert kinds[0] == "start" and "tick" in kinds
        assert "slo_burn" in kinds
        rep = core.stitch(rows)
        assert rep["run_id"] == "t1" and core.telescopes(rep)
        assert rep["slo_burns"]["goodput_ratio"] >= 1
        assert rep["tokens"] == 512 and rep["steps"] == 2
    finally:
        gp.disable()
        flight.disable()
        reg.disable()


def test_slo_watcher_serving_rules():
    """ttft_p99_s (MAX, off the serving TTFT histogram) and shed_ratio
    (MAX, shed/submitted counters) burn only when breached; absent
    series are skipped, not burned."""
    reg = get_registry()
    reg.enable()
    reg.reset()     # the registry is the process's: an earlier file's serving
    try:            # series on this worker must not read as this test's
        w = SloWatcher({"ttft_p99_s": 0.1, "shed_ratio": 0.25,
                        "unknown_rule": 1.0})
        assert set(w.rules) == {"ttft_p99_s", "shed_ratio"}
        gp = GoodputLedger()
        gp.enable(run_id="slo-t", role="serve", incarnation=0)
        try:
            # no serving series yet: nothing to observe, no burns
            assert w.evaluate({"goodput_ratio": 1.0}, gp) == 0
            hist = reg.histogram("ds_serve_ttft_seconds")
            for _ in range(20):
                hist.record(0.5)             # p99 far above the 0.1 target
            shed = reg.counter("ds_serve_shed_total")
            sub = reg.counter("ds_serve_submitted_total")
            sub.inc(10)
            shed.inc(1)                      # 0.1 <= 0.25: healthy
            assert w.evaluate({"goodput_ratio": 1.0}, gp) == 1   # ttft only
            shed.inc(9)                      # 10/19 > 0.25: both burn
            assert w.evaluate({"goodput_ratio": 1.0}, gp) == 2
            assert reg.get("ds_slo_burn_total",
                           {"rule": "ttft_p99_s"}).value == 2
            assert reg.get("ds_slo_burn_total",
                           {"rule": "shed_ratio"}).value == 1
        finally:
            gp.disable()
    finally:
        reg.disable()


def test_goodputz_endpoint():
    """GET /goodputz serves the live process-global ledger snapshot."""
    from deepspeed_tpu.monitor.metrics import MetricsRegistry
    from deepspeed_tpu.monitor.server import MetricsServer

    reg = MetricsRegistry().enable()
    gp = get_goodput_ledger()
    gp.enable(run_id="zz-run", role="train", incarnation=0)
    server = MetricsServer(reg, port=0).start()
    try:
        gp.push("compute")
        time.sleep(0.01)
        gp.pop()
        with urllib.request.urlopen(f"{server.url}/goodputz",
                                    timeout=5) as r:
            snap = json.load(r)
        assert snap["enabled"] is True and snap["run_id"] == "zz-run"
        assert snap["categories"]["compute"] > 0.0
        assert core.telescopes(snap)
        # the endpoint is listed on the index page
        with urllib.request.urlopen(server.url + "/", timeout=5) as r:
            assert b"/goodputz" in r.read()
    finally:
        server.stop()
        gp.disable()


def test_goodput_report_tool_selftest():
    """tools/goodput_report.py --selftest: synth ledger -> stitch ->
    telescoping + render/diff + CLI + torn-line tolerance (and DSL003
    keeps its import closure jax-free)."""
    rep = _tool("goodput_report")
    assert rep.selftest() == 0


# ---------------------------------------------------------------------------
# engine e2e: real seams feed the ledger
# ---------------------------------------------------------------------------


def _make_engine(tmp_path, ledger_path, extra=None):
    cfg = {"train_micro_batch_size_per_gpu": 1,
           "gradient_accumulation_steps": 1,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
           "zero_optimization": {"stage": 0}, "steps_per_print": 10**9,
           "goodput": {"enabled": True, "path": ledger_path},
           "flight_recorder": {"enabled": True, "dump_dir": str(tmp_path)}}
    cfg.update(extra or {})
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=16), config=cfg,
        rng=jax.random.PRNGKey(3))
    return engine


def _step(engine, i):
    lo = (i % 4) * 8
    loss = engine.forward((X[lo:lo + 8], Y[lo:lo + 8]))
    engine.step()
    return float(loss)


def test_engine_feeds_ledger_and_checkpoint_events_reconcile(tmp_path,
                                                             monkeypatch):
    """A real engine with the ``goodput`` config block: compute +
    recompile accrue from the step seams, the snapshot telescopes, and
    the flight ``checkpoint`` record carries the SAME event_id + dur_s
    as the ledger's durable event row (the reconciliation satellite)."""
    monkeypatch.setenv("DSTPU_RUN_ID", "eng-run")
    flight = get_flight_recorder()
    flight.reset()
    path = str(tmp_path / "runledger.jsonl")
    engine = _make_engine(tmp_path, path)
    gp = get_goodput_ledger()
    try:
        assert gp.enabled and gp.run_id == "eng-run"
        for i in range(3):
            _step(engine, i)
        engine.save_checkpoint(str(tmp_path / "ck"), tag="t0")
        snap = gp.snapshot()
        assert core.telescopes(snap)
        assert snap["categories"]["compute"] > 0.0
        assert snap["categories"]["recompile"] > 0.0
        assert snap["categories"]["checkpoint_save"] > 0.0
        assert snap["tokens"] > 0 and snap["steps"] == 3
        # flight <-> ledger reconciliation by event id
        fl = [e for e in flight.events() if e["kind"] == "checkpoint"]
        assert fl and fl[-1]["op"] == "save" and fl[-1]["dur_s"] > 0.0
        rows = [r for r in core.read_rows(path)
                if r["kind"] == "event" and r["event"] == "checkpoint_save"]
        assert rows, "ledger event row missing for the checkpoint save"
        by_id = {r["event_id"]: r for r in rows}
        led = by_id[fl[-1]["event_id"]]
        assert led["dur_s"] == fl[-1]["dur_s"]
        # the ledger's attributed seconds cover the event's duration
        assert snap["categories"]["checkpoint_save"] >= 0.5 * led["dur_s"]
    finally:
        gp.disable()
        flight.disable()


def test_chaos_kill_restart_rollback_stitches(tmp_path, monkeypatch):
    """THE ISSUE 18 chaos acceptance, in-process: incarnation 0 trains
    + checkpoints and dies (final tick, disable); after a real gap,
    incarnation 1 resumes from the checkpoint, takes a gradient bomb
    through the anomaly skip -> ROLLBACK ladder, and recovers.  The
    stitched jsonl telescopes with nonzero ``restart_downtime``,
    ``rollback``, ``checkpoint_save`` and ``checkpoint_load``."""
    monkeypatch.setenv("DSTPU_RUN_ID", "chaos-run")
    monkeypatch.setenv("DS_SUPERVISOR_RESTART", "0")
    reg = get_registry()
    reg.enable()
    flight = get_flight_recorder()
    flight.reset()
    path = str(tmp_path / "runledger.jsonl")
    ck = tmp_path / "ck"
    anomaly = {"anomaly_detection": {"enabled": True, "factor": 5.0,
                                     "window": 8, "warmup": 3,
                                     "patience": 2, "rollback": True,
                                     "max_rollbacks": 3,
                                     "save_dir": str(ck)}}
    gp = get_goodput_ledger()
    try:
        # -- incarnation 0: train, checkpoint, die ----------------------
        engine = _make_engine(tmp_path, path, extra=anomaly)
        for i in range(5):
            _step(engine, i)
        engine.save_checkpoint(str(ck), tag="good")
        gp.disable()                     # process death: final forced tick
        engine = None

        time.sleep(0.06)                 # the supervisor restart gap

        # -- incarnation 1: restart, resume, bomb -> rollback -----------
        monkeypatch.setenv("DS_SUPERVISOR_RESTART", "1")
        engine = _make_engine(tmp_path, path, extra=anomaly)
        assert gp.enabled and gp.incarnation == 1
        _step(engine, 0)                 # lazy state init (load needs it)
        load_path, _ = engine.load_checkpoint(str(ck), tag="good")
        assert load_path is not None
        for i in range(4):               # arm the detector (warmup=3)
            _step(engine, i)
        rb0 = reg.counter("ds_train_anomaly_rollback_total").value
        with chaos.gradient_bomb(engine, scale=1e18, on_call=1, n=3):
            for i in range(3):
                _step(engine, 5 + i)
        assert reg.counter("ds_train_anomaly_rollback_total").value \
            - rb0 == 1
        _step(engine, 0)                 # post-rollback recovery step
        gp.disable()

        # -- the stitched run -------------------------------------------
        rep = core.stitch(core.read_rows(path), run_id="chaos-run")
        assert len(rep["incarnations"]) == 2
        assert core.telescopes(rep), rep["categories"]
        assert rep["restart_gaps_s"][0] > 0.0
        cats = rep["categories"]
        assert cats["restart_downtime"] > 0.0
        assert cats["rollback"] > 0.0
        assert cats["checkpoint_save"] > 0.0
        assert cats["checkpoint_load"] > 0.0
        assert cats["compute"] > 0.0
        assert rep["goodput_ratio"] > 0.0
        # the offline reader renders the stitched run (both incarnations
        # + the gap line), jax-free
        text = "\n".join(core.render_lines(rep))
        assert "incarnation 0" in text and "incarnation 1" in text
        assert "restart gap 0" in text and "telescopes: True" in text
    finally:
        gp.disable()
        flight.disable()
        reg.disable()
