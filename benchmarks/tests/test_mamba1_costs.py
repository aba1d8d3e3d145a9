"""``lib/mamba1_costs.py`` and the four readers it feeds: the bytes a live
row a layer a step and a chunk's real rows must move at the published
widths; ``mamba1_decode_roofline`` and ``selective_scan_roofline`` on a
hand-made trace; the two shares; the readers return nothing, and do not
raise, for a program without the kernels (the parent commit) and for a
configuration without mamba1 layers."""

import os

import pytest

from conftest import BENCH_DIR, _load

REAL = "jamba2-3b.serve-reason-768"
CONFIG = "jamba2-3b"
METRICS = ("mamba1_decode_share", "mamba1_decode_roofline",
           "selective_scan_share", "selective_scan_roofline")


def _mc(name=CONFIG):
    return _load(os.path.join(BENCH_DIR, "configs",
                              name + ".json"))["model_config"]


def test_bytes_at_the_published_widths():
    from benchmarks.lib import mamba1_costs

    mc = _mc()
    assert mamba1_costs.mamba1_layers(mc) == 26
    # ISSUE 66: the state [5,120, 16] float32 read and written once, u, dt,
    # y [5,120], B and C [16]: 716,928 B a live row a layer a step, 18.6 MB
    # over the 26 layers
    assert mamba1_costs.state_bytes(mc) == 2 * 5120 * 16 * 4 == 655360
    assert mamba1_costs.row_bytes(mc) == 4 * (3 * 5120 + 2 * 16) == 61568
    assert mamba1_costs.state_step_bytes(mc) == 716928
    assert 26 * mamba1_costs.state_step_bytes(mc) == pytest.approx(18.6e6,
                                                                   rel=0.01)
    # a chunk program of 170 real rows: every layer's rows and its state
    assert mamba1_costs.scan_chunk_bytes(mc, 170, 26) == \
        26 * (170 * 61568 + 655360)


@pytest.mark.parametrize("other", ["mistral-7b-L8", "solar-open2-L4-ep8",
                                   "nemotron3-nano-L9-ep2"])
def test_no_other_configuration_has_such_layers(other):
    from benchmarks.lib import mamba1_costs

    assert mamba1_costs.mamba1_layers(_mc(other)) == 0


def _ctx(bench, kernels, config=CONFIG, programs=None, marks=True,
         prefilled=340):
    from benchmarks.lib.peaks import peaks

    class Lv:
        # a request that shows 1 token after the iteration at 0.4 s and 9
        # after the one at 0.5 s: eight decode steps inside the window
        marks = [(0.4, 1), (0.5, 9)]

    class Arrival:
        prompt = list(range(100))

    P = "ds_serve_prefill_tokens_total"
    return {"bench": bench, "peaks": peaks("TPU v5 lite"),
            "trace_window": (0.45, 1.0), "cell": bench.cell(REAL),
            "config": bench.config(config),
            "counters": {"trace_start": {P: 1000},
                         "end": {P: 1000 + prefilled}},
            "trace": None if kernels is None else {
                "kernels": kernels, "programs": programs or {}},
            "loop": {"records": [Lv] if marks else [],
                     "schedule": [Arrival] if marks else []}}


def test_the_decode_roofline_counts_the_live_rows_states_once_a_layer():
    """Eight steps of one live row, the kernel traced for exactly the time
    819 GB/s needs for the 26 layers' states: 100%; for twice that: 50%."""
    from benchmarks.lib import mamba1_costs
    from benchmarks.lib.manifest import Bench
    from benchmarks.lib.peaks import peaks

    bench = Bench()
    nbytes = 8 * 26 * mamba1_costs.state_step_bytes(_mc())
    least = nbytes / peaks("TPU v5 lite")["hbm_bytes_per_s"]
    reader = bench.reader("mamba1_decode_roofline")
    for factor in (1, 2):
        ctx = _ctx(bench, {"mamba1_decode_step": {
            "seconds": factor * least, "count": 8 * 26}})
        assert reader.read(ctx) == pytest.approx(100.0 / factor)


def test_the_scan_roofline_counts_real_rows_and_a_state_a_call():
    """Two chunk programs (52 calls) over 340 real prompt rows, the kernel
    traced for ten times what the bytes need: 10%."""
    from benchmarks.lib import mamba1_costs
    from benchmarks.lib.manifest import Bench
    from benchmarks.lib.peaks import peaks

    bench = Bench()
    nbytes = mamba1_costs.scan_chunk_bytes(_mc(), 340, 52)
    least = nbytes / peaks("TPU v5 lite")["hbm_bytes_per_s"]
    reader = bench.reader("selective_scan_roofline")
    ctx = _ctx(bench, {"selective_scan_chunk": {"seconds": 10 * least,
                                                "count": 52}})
    assert reader.read(ctx) == pytest.approx(10.0)
    # nothing prefilled in the window: nothing to hold the time against
    assert reader.read(_ctx(bench, {"selective_scan_chunk": {
        "seconds": 1.0, "count": 52}}, prefilled=0)) is None


def test_the_shares_are_kernel_time_over_their_programs_busy_time():
    from benchmarks.lib.manifest import Bench

    bench = Bench()
    ctx = _ctx(bench, {"mamba1_decode_step": {"seconds": 0.03, "count": 32},
                       "selective_scan_chunk": {"seconds": 0.02,
                                                "count": 26}},
               programs={"jit_body": {"busy_s": 0.1},
                         "jit_prefill": {"busy_s": 0.05}})
    assert bench.reader("mamba1_decode_share").read(ctx) == \
        pytest.approx(30.0)
    assert bench.reader("selective_scan_share").read(ctx) == \
        pytest.approx(40.0)


@pytest.mark.parametrize("metric", METRICS)
def test_the_readers_return_none_for_a_program_without_the_kernels(metric):
    """What the parent commit, an untraced run and a cell of another
    configuration give the new readers."""
    from benchmarks.lib.manifest import Bench

    bench = Bench()
    reader = bench.reader(metric)
    busy = {"jit_body": {"busy_s": 0.1}, "jit_prefill": {"busy_s": 0.1}}
    other = {"ssm_decode_step": {"seconds": 1.0, "count": 3},
             "fused_mlp": {"seconds": 1.0, "count": 3}}
    assert reader.read(_ctx(bench, other, programs=busy)) is None
    assert reader.read(_ctx(bench, None)) is None
    assert reader.read(_ctx(bench, {}, programs={}, marks=False)) is None
    if "roofline" in metric:
        both = dict(other, mamba1_decode_step={"seconds": 1.0, "count": 3},
                    selective_scan_chunk={"seconds": 1.0, "count": 3})
        assert reader.read(_ctx(bench, both, config="nemotron3-nano-L9-ep2",
                                programs=busy)) is None


@pytest.mark.parametrize("metric", METRICS)
def test_the_manifest_lists_the_cell_for_each_new_metric(metric):
    from benchmarks.lib.manifest import Bench

    entry = [m for m in Bench().manifest["per_layer"]
             if m["name"] == metric]
    assert len(entry) == 1 and REAL in entry[0]["workloads"]
    assert entry[0]["unit"] == "%"
