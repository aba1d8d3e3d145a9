"""Step-time watchdog units: median tracking, one-shot arming, no
re-trigger storm, the falling-median re-anchor, and the steady-state cost
contract (one deque append + one comparison + a countdown — no suspect-path
median recompute, no per-call allocation growth)."""

import sys

import pytest

from deepspeed_tpu.monitor.watchdog import StepWatchdog


def test_median_tracking_and_trip():
    wd = StepWatchdog(factor=10.0, window=16, warmup=4)
    for _ in range(8):
        assert wd.observe(0.1) is False
    assert wd.median == pytest.approx(0.1)
    # 5x median: suspect, but below factor -> no trip, bound refreshed
    assert wd.observe(0.5) is False
    assert not wd.fired
    # 20x median: trips exactly once, with the anomaly excluded from its
    # own median
    assert wd.observe(2.0) is True
    assert wd.fired
    assert wd.last_trip["median"] == pytest.approx(0.1, rel=0.3)
    assert wd.last_trip["ratio"] > 10.0


def test_one_shot_no_retrigger_storm():
    wd = StepWatchdog(factor=10.0, window=16, warmup=4)
    for _ in range(6):
        wd.observe(0.1)
    assert wd.observe(5.0) is True
    # a stalled run keeps producing slow steps: NONE of them re-trip
    for _ in range(20):
        assert wd.observe(5.0) is False
    assert wd.fired
    # reset re-arms (fresh warmup)
    wd.reset()
    assert not wd.fired
    for _ in range(6):
        wd.observe(0.1)
    assert wd.observe(5.0) is True


def test_warmup_never_trips():
    wd = StepWatchdog(factor=10.0, window=16, warmup=8)
    # wild variance during warmup (compiles!) must not fire
    for v in (10.0, 0.1, 5.0, 0.1, 0.1, 0.1, 0.1):
        assert wd.observe(v) is False
    assert not wd.fired


def test_median_drift_refreshes_bound():
    """A workload that legitimately slows (longer seqs) raises the bound
    via the suspect path instead of firing."""
    wd = StepWatchdog(factor=10.0, window=8, warmup=4)
    for _ in range(8):
        wd.observe(0.1)
    for _ in range(8):
        assert wd.observe(0.4) is False   # 4x: suspects, never trips
    assert not wd.fired
    # the new normal is cheap again: 0.4-based median, 0.5 doesn't suspect
    before = wd.median_recomputes
    assert wd.observe(0.45) is False
    assert wd.median_recomputes == before


def test_falling_median_still_trips():
    """Compile-inflated warmup must not park the bound out of reach: after
    the median falls to the real step time (and a window of fast samples
    re-anchors the bound), a genuine stall vs the NEW median trips.
    Observed live before the fix: 2s compile warmup -> 20s bound; a 3s
    stall at 150x the 20ms steady median never fired."""
    wd = StepWatchdog(factor=10.0, window=8, warmup=3)
    for _ in range(3):
        wd.observe(2.0)            # compiles dominate warmup
    for _ in range(10):            # > window fast steps: bound re-anchors
        assert wd.observe(0.02) is False
    assert wd.bound_refreshes >= 1
    assert wd.observe(3.0) is True # 150x the fast median
    assert wd.last_trip["median"] == pytest.approx(0.02)


def test_steady_state_cost_contract():
    """After warmup, observe() is one append + one comparison: zero median
    recomputes across steady traffic, method rebound to the steady path,
    and no per-call allocation growth (PR 2 getallocatedblocks style)."""
    wd = StepWatchdog(factor=10.0, window=64, warmup=5)
    v = 0.1
    for _ in range(10):
        wd.observe(v)
    assert wd.observe == wd._observe_steady  # warmup branch is GONE
    assert wd.median_recomputes == 0
    vals = [v] * 5000
    before = sys.getallocatedblocks()
    for x in vals:
        wd.observe(x)
    delta = sys.getallocatedblocks() - before
    assert wd.median_recomputes == 0, "steady state must not sort"
    assert delta < 100, f"per-call allocation on the steady path: {delta}"


def test_bad_factor_rejected():
    with pytest.raises(ValueError):
        StepWatchdog(factor=1.0)


def test_warmup_clamped_to_window():
    """warmup > window could never arm (the deque caps at window samples)
    — it must clamp instead of silently disarming the watchdog."""
    wd = StepWatchdog(factor=10.0, window=4, warmup=16)
    assert wd.warmup == 4
    for _ in range(6):
        wd.observe(0.1)
    assert wd.observe == wd._observe_steady   # armed
    assert wd.observe(5.0) is True


def test_engine_trip_one_capture_one_dump(tmp_path):
    """ISSUE 5 acceptance: an injected 10x slow step triggers exactly ONE
    flight-recorder dump and arms exactly ONE post-anomaly trace capture;
    further slow steps don't re-trigger."""
    import glob
    import os

    import jax

    import deepspeed_tpu
    from deepspeed_tpu.monitor.flight_recorder import get_flight_recorder
    from tests.unit.simple_model import SimpleModel, random_dataset

    x, y = random_dataset(n=16)
    dump_dir = str(tmp_path / "flight")
    trace_dir = str(tmp_path / "wd_trace")
    cfg = {"train_micro_batch_size_per_gpu": 2,
           "gradient_accumulation_steps": 1,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
           "flight_recorder": {"enabled": True, "dump_dir": dump_dir},
           "watchdog": {"enabled": True, "factor": 5.0, "warmup": 3,
                        "window": 16, "capture_steps": 1,
                        "output_path": trace_dir},
           "steps_per_print": 10**9}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=8), config=cfg,
        rng=jax.random.PRNGKey(0))
    assert engine._watchdog is not None
    rec = get_flight_recorder()
    try:
        def one_step():
            loss = engine.forward((x[:8], y[:8]))
            engine.backward(loss)
            engine.step()

        for _ in range(6):           # warmup + steady median
            one_step()
        assert not engine._watchdog.fired
        # inject a 10x-slow step: backdate the boundary clock so the next
        # observed dt dwarfs the median
        engine._wd_last_t -= 50.0
        one_step()
        assert engine._watchdog.fired
        dumps = glob.glob(os.path.join(dump_dir, "ds_flight_*.json"))
        assert len(dumps) == 1, dumps
        armed = engine._aux_trace
        assert armed is not None and armed[1] == "watchdog"
        # keep stepping: no re-trigger storm — still exactly one dump, and
        # the armed capture closes into a summary
        for _ in range(3):
            one_step()
        assert len(glob.glob(os.path.join(dump_dir,
                                          "ds_flight_*.json"))) == 1
        assert engine._aux_trace is None
        assert os.path.exists(os.path.join(
            trace_dir, "ds_watchdog_summary.json"))
    finally:
        rec.disable()
        rec.reset()
